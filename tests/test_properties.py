"""Hypothesis property tests on system invariants."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.deadline import MeanDeadline, PercentileDeadline, WorstObserved, evaluate
from repro.core.stats import Welford, coefficient_of_variation, latency_range, summarize
from repro.perception.detector import batched_nms, dynamic_nms, static_nms
from repro.models.attention import chunked_attention, dense_attention

finite_latencies = st.lists(
    st.floats(min_value=1e-6, max_value=10.0, allow_nan=False), min_size=2, max_size=200
)


@given(finite_latencies)
@settings(max_examples=50, deadline=None)
def test_summary_invariants(xs):
    s = summarize(xs)
    assert s.min <= s.p50 <= s.p99 <= s.max + 1e-12
    assert s.range == max(xs) - min(xs)
    assert s.cv >= 0
    assert s.range_over_mean_pct >= 0


@given(finite_latencies)
@settings(max_examples=50, deadline=None)
def test_welford_matches_batch(xs):
    w = Welford()
    w.update_many(xs)
    assert math.isclose(w.mean, float(np.mean(xs)), rel_tol=1e-9, abs_tol=1e-12)
    assert math.isclose(w.variance, float(np.var(xs)), rel_tol=1e-6, abs_tol=1e-12)


@given(finite_latencies)
@settings(max_examples=30, deadline=None)
def test_worst_observed_never_misses_after_seeing_worst(xs):
    """Once the worst value has been observed, no later job can miss."""
    worst_idx = int(np.argmax(xs))
    trace = xs[: worst_idx + 1] + xs  # worst seen in prefix, then full replay
    rep = evaluate(WorstObserved(), trace, warmup=worst_idx + 1)
    assert rep.miss_rate == 0.0


@given(finite_latencies)
@settings(max_examples=30, deadline=None)
def test_deadline_waste_miss_tradeoff_is_monotone(xs):
    """A larger percentile target can only raise waste and lower misses."""
    lo = evaluate(PercentileDeadline(q=50.0, window=512), xs, warmup=1)
    hi = evaluate(PercentileDeadline(q=100.0, window=512), xs, warmup=1)
    assert hi.miss_rate <= lo.miss_rate + 1e-12


@given(
    st.integers(min_value=1, max_value=3),    # batch
    st.integers(min_value=1, max_value=4),    # kv heads
    st.integers(min_value=1, max_value=3),    # group
    st.sampled_from([32, 64]),                # seq
    st.booleans(),                            # causal
)
@settings(max_examples=20, deadline=None)
def test_chunked_attention_matches_dense(b, k, g, s, causal):
    h = k * g
    d = 8
    key = jax.random.PRNGKey(b * 1000 + k * 100 + g * 10 + s)
    q = jax.random.normal(key, (b, s, h, d))
    kk = jax.random.normal(jax.random.fold_in(key, 1), (b, s, k, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, k, d))
    pos = jnp.arange(s)
    ref = dense_attention(q, kk, v, pos, pos, causal, None)
    for tri in (True, False):
        out = chunked_attention(q, kk, v, 0, causal, None, 16, 16, triangular=tri)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@given(st.integers(min_value=1, max_value=40), st.integers(min_value=0, max_value=10**6))
@settings(max_examples=25, deadline=None)
def test_static_nms_agrees_with_dynamic_on_topk(n, seed):
    """On the same candidate set, the static fixed-shape NMS keeps exactly
    the boxes the dynamic host NMS keeps."""
    rng = np.random.default_rng(seed)
    y0 = rng.uniform(0, 80, n)
    x0 = rng.uniform(0, 300, n)
    boxes = np.stack([y0, x0, y0 + rng.uniform(4, 20, n), x0 + rng.uniform(4, 20, n)], -1)
    scores = rng.uniform(0.1, 1.0, n)
    # dynamic on full set
    keep_dyn = set(map(int, dynamic_nms(boxes.astype(np.float32), scores.astype(np.float32))))
    tb, ts, keep_mask, idx = static_nms(
        jnp.asarray(boxes, jnp.float32), jnp.asarray(scores, jnp.float32), k=n
    )
    keep_static = set(int(i) for i, m in zip(np.asarray(idx), np.asarray(keep_mask)) if m)
    assert keep_static == keep_dyn


FRAME_KINDS = ("inactive", "single", "spread", "cluster", "staircase")


def _frame_candidates(kind: str, rng) -> tuple[np.ndarray, np.ndarray]:
    """One frame's (boxes, scores) of the given kind; ``inactive`` has
    none, as a slot outside the tick's active mask."""
    if kind == "inactive":
        return np.zeros((0, 4), np.float32), np.zeros(0, np.float32)
    if kind == "staircase":
        # each box overlaps the next at IoU 2/3 and the one after at 3/7:
        # one suppression chain through the whole frame
        n = int(rng.integers(2, 40))
        x = np.arange(n) * 2.0
        boxes = np.stack([np.zeros(n), x, np.full(n, 10.0), x + 10], -1)
        return boxes.astype(np.float32), np.linspace(1, 0.1, n).astype(np.float32)
    n = 1 if kind == "single" else int(rng.integers(2, 40))
    if kind == "cluster":                   # a few tight, near-equal clusters
        centres = rng.uniform(0, 60, (int(rng.integers(1, 4)), 2))
        c = centres[rng.integers(0, len(centres), n)] + rng.normal(0, 1.5, (n, 2))
        hw = rng.uniform(8, 12, (n, 2))
        boxes = np.concatenate([c - hw / 2, c + hw / 2], -1)
    else:
        y0, x0 = rng.uniform(0, 80, n), rng.uniform(0, 300, n)
        boxes = np.stack([y0, x0, y0 + rng.uniform(4, 40, n),
                          x0 + rng.uniform(4, 50, n)], -1)
    scores = (rng.choice([0.6, 0.8, 1.0], n) if rng.random() < 0.5
              else rng.uniform(0.1, 1.0, n))    # half the frames tie often
    return boxes.astype(np.float32), scores.astype(np.float32)


@given(st.lists(st.sampled_from(FRAME_KINDS), min_size=1, max_size=8),
       st.sampled_from([0.0, 0.3, 0.5, 0.7]),
       st.integers(min_value=0, max_value=2**32 - 1))
@settings(max_examples=60, deadline=None)
def test_batched_nms_is_dynamic_nms_per_frame(kinds, iou_thr, seed):
    """One batched NMS over a tick keeps, in every frame, exactly the
    indices ``dynamic_nms`` keeps on that frame alone, in the same order:
    tied scores, empty and one-candidate frames, tight clusters and a
    suppression chain as long as the frame."""
    rng = np.random.default_rng(seed)
    frames = [_frame_candidates(k, rng) for k in kinds]
    boxes = np.concatenate([b for b, _ in frames])
    scores = np.concatenate([s for _, s in frames])
    counts = [len(s) for _, s in frames]
    keep, kept, sweeps = batched_nms(boxes, scores, counts, iou_thr)
    assert len(kept) == len(frames) and len(keep) == sum(kept)
    runs = np.split(keep, np.cumsum(kept)[:-1])
    for (b, s), run, start in zip(frames, runs, np.cumsum(counts) - counts):
        want = dynamic_nms(b, s, iou_thr) if len(s) else np.zeros(0, np.int64)
        assert run.dtype == want.dtype
        np.testing.assert_array_equal(run - start, want)
    assert sweeps == 0 if not len(scores) else sweeps >= 1
    if iou_thr == 0.5:
        # a chain of n boxes settles one link a pass: n passes in all
        chains = [len(s) for k, (_, s) in zip(kinds, frames) if k == "staircase"]
        assert sweeps >= max(chains, default=0)


def test_batched_nms_refuses_a_negative_threshold():
    with pytest.raises(ValueError, match="iou_thr"):
        batched_nms(np.zeros((1, 4), np.float32), np.ones(1, np.float32),
                    [1], -0.1)


@given(st.integers(min_value=2, max_value=64), st.integers(min_value=0, max_value=10**6))
@settings(max_examples=20, deadline=None)
def test_moe_dispatch_conservation(tokens, seed):
    """With generous capacity, every (token, choice) is dispatched exactly
    once and combine weights sum to 1 per token."""
    from repro.configs.base import ModelConfig
    from repro.models.moe import moe_block
    from repro.models.params import init_params
    from repro.models.moe import moe_specs

    cfg = ModelConfig(
        name="t", family="moe", num_layers=1, d_model=16, num_heads=2,
        num_kv_heads=2, head_dim=8, d_ff=32, vocab_size=64, num_experts=4,
        num_experts_per_tok=2, capacity_factor=8.0, moe_group_size=16,
        dtype="float32", param_dtype="float32",
    )
    key = jax.random.PRNGKey(seed % (2**31))
    params = init_params(moe_specs(cfg), key, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, tokens, 16))
    out, aux = moe_block(params, x, cfg)
    assert out.shape == x.shape
    assert float(aux["drop_fraction"]) < 1e-6
    assert bool(jnp.isfinite(out).all())
