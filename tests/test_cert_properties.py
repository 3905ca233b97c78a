"""Property tests for the static FLOP/byte counter.

Two invariants the certificate's determinism rests on:

* **jit-of-jit nesting invariance** — wrapping a program in any depth of
  ``jax.jit`` must not change its counted cost (the walker recurses
  through ``jit`` transparently), so refactoring jit boundaries never
  shows up as count drift;
* **enumeration-order invariance** — merging per-program counts in any
  order yields the same totals, so the certificate does not depend on
  the envelope's iteration order.

The container has no ``hypothesis``, so the always-on tests drive a
seeded random program generator; equivalent hypothesis variants run
wherever the package exists (gated, never required)."""
import random

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.cert import Counts, count_jaxpr

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _random_program(rng: random.Random):
    """A small random straight-line program over two matrices."""
    m = rng.choice([2, 3, 5, 8])
    k = rng.choice([2, 4, 7])
    n = rng.choice([1, 3, 6])
    n_elt = rng.randrange(0, 3)
    use_exp = rng.random() < 0.5
    use_reduce = rng.random() < 0.5
    scan_len = rng.choice([0, 3, 5])

    def f(a, b):
        x = a @ b
        for _ in range(n_elt):
            x = x * 2.0 + 1.0
        if use_exp:
            x = jnp.exp(x)
        if scan_len:
            def body(c, _):
                return c + x, None
            x, _ = jax.lax.scan(body, x, None, length=scan_len)
        if use_reduce:
            return jnp.sum(x)
        return x

    specs = (jax.ShapeDtypeStruct((m, k), jnp.float32),
             jax.ShapeDtypeStruct((k, n), jnp.float32))
    return f, specs


def _nest(f, depth: int):
    for _ in range(depth):
        f = jax.jit(f)
    return f


def _comparable(c: Counts) -> dict:
    d = c.to_dict()
    d.pop("host_prims")       # paths embed eqn indices, which nesting shifts
    return d


@pytest.mark.parametrize("seed", range(12))
def test_counts_invariant_to_jit_nesting(seed):
    rng = random.Random(1000 + seed)
    f, specs = _random_program(rng)
    base = _comparable(count_jaxpr(jax.make_jaxpr(f)(*specs)))
    for depth in (1, 2, 3):
        nested = _comparable(
            count_jaxpr(jax.make_jaxpr(_nest(f, depth))(*specs)))
        assert nested == base, f"depth {depth} changed the counts"


@pytest.mark.parametrize("seed", range(8))
def test_counts_invariant_to_enumeration_order(seed):
    rng = random.Random(2000 + seed)
    programs = [_random_program(rng) for _ in range(5)]
    counted = [count_jaxpr(jax.make_jaxpr(f)(*specs))
               for f, specs in programs]

    def total(order):
        acc = Counts()
        for i in order:
            acc.merge(counted[i])
        return _comparable(acc)

    forward = total(range(len(counted)))
    shuffled = list(range(len(counted)))
    rng.shuffle(shuffled)
    assert total(shuffled) == forward
    assert total(reversed(range(len(counted)))) == forward


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(m=st.integers(1, 8), k=st.integers(1, 8), n=st.integers(1, 8),
           depth=st.integers(1, 3))
    def test_hypothesis_nesting_invariance(m, k, n, depth):
        f = lambda a, b: jnp.sum(jnp.exp(a @ b))
        specs = (jax.ShapeDtypeStruct((m, k), jnp.float32),
                 jax.ShapeDtypeStruct((k, n), jnp.float32))
        base = _comparable(count_jaxpr(jax.make_jaxpr(f)(*specs)))
        nested = _comparable(
            count_jaxpr(jax.make_jaxpr(_nest(f, depth))(*specs)))
        assert nested == base

    @settings(max_examples=25, deadline=None)
    @given(perm=st.permutations(list(range(4))))
    def test_hypothesis_merge_order_invariance(perm):
        rng = random.Random(7)
        counted = [count_jaxpr(jax.make_jaxpr(f)(*specs))
                   for f, specs in [_random_program(rng) for _ in range(4)]]
        acc_fwd, acc_perm = Counts(), Counts()
        for i in range(4):
            acc_fwd.merge(counted[i])
        for i in perm:
            acc_perm.merge(counted[i])
        assert _comparable(acc_perm) == _comparable(acc_fwd)
