"""Primitive-level FLOP/byte counting over closed jaxprs.

The certifier's cost model is *static*: it walks a jaxpr (the compiled
program's IR, obtained via ``jax.make_jaxpr`` — no execution, no XLA
compile) and accumulates per-primitive floating-point work and data
movement from the equation avals alone.  Everything here is exact
arithmetic over static shapes, so two walks of the same program agree
bit-for-bit — the property the committed certificate's ``--check``
depends on.

Counting conventions (all choices keep the resulting roofline latency a
true *floor*, i.e. lower bounds):

* ``dot_general`` — ``2 · prod(out_shape) · prod(contracting_dims)``
  (one multiply + one add per MAC).
* ``conv_general_dilated`` — ``2 · prod(out_shape) · C_in/groups ·
  prod(kernel_spatial)``; the kernel's in-channel dim is read off the
  rhs aval, which is already per-group.
* elementwise / transcendental — one flop per output element
  (transcendentals are also tallied separately).
* reductions / cumulative ops — one flop per *input* element.
* pure data movement (reshape/transpose/slice/gather/...) — zero flops,
  input+output bytes into ``mem_bytes``.
* ``scan`` — body × ``length`` (static lengths only; ``fori_loop`` with
  static bounds lowers to scan, which is how the detectors' NMS loop is
  counted).
* ``while`` — body × 1 and ``while_loops`` incremented: an unbounded
  loop runs *at least* once, so counting one trip keeps the floor sound
  while the counter makes data-dependent iteration visible.
* ``cond`` — the *cheapest* branch (the program may take it).
* ``pallas_call`` — the kernel's declared ``cost_estimate`` when the
  author provided one, else the inner kernel jaxpr × ``prod(grid)``.
* host-interaction primitives (``device_put``, ``*callback*``,
  ``infeed``/``outfeed``) contribute nothing to cost but are recorded in
  ``host_prims`` with their nesting path — the certifier's check (3).

Unknown primitives count zero flops and are listed in ``unknown`` so a
new jax version widening the primitive set degrades visibly, never
silently.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import numpy as np

__all__ = [
    "Counts",
    "count_jaxpr",
    "program_io_bytes",
    "outer_donated_invars",
]


def _prod(xs) -> float:
    out = 1.0
    for x in xs:
        out *= float(x)
    return out


def _aval_bytes(aval) -> float:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:        # abstract token etc.
        return 0.0
    return _prod(shape) * float(np.dtype(dtype).itemsize)


def _inner_jaxpr(j):
    """Unwrap a ClosedJaxpr to its raw Jaxpr (raw jaxprs pass through)."""
    return j.jaxpr if hasattr(j, "consts") else j


# one flop per output element
_ELEMENTWISE = {
    "add", "add_any", "sub", "mul", "div", "rem", "max", "min", "neg",
    "abs", "sign", "floor", "ceil", "round", "nextafter", "is_finite",
    "and", "or", "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "lt", "le", "gt", "ge", "eq", "ne",
    "select_n", "clamp", "integer_pow", "square", "population_count",
    "clz", "real", "imag", "conj", "complex",
}

# one flop per output element, tallied as transcendental too
_TRANSCENDENTAL = {
    "exp", "exp2", "expm1", "log", "log1p", "log2", "sqrt", "rsqrt",
    "cbrt", "pow", "sin", "cos", "tan", "asin", "acos", "atan", "atan2",
    "sinh", "cosh", "tanh", "asinh", "acosh", "atanh", "erf", "erfc",
    "erf_inv", "logistic", "digamma", "lgamma",
}

# one flop per input element
_REDUCE = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "cumsum", "cumprod",
    "cummax", "cummin", "cumlogsumexp", "reduce_window_sum",
    "reduce_window_max", "reduce_window_min", "sort", "top_k",
}

# zero flops; input+output bytes into mem_bytes
_MOVEMENT = {
    "broadcast_in_dim", "reshape", "transpose", "slice", "dynamic_slice",
    "dynamic_update_slice", "concatenate", "pad", "rev", "squeeze",
    "expand_dims", "convert_element_type", "bitcast_convert_type", "iota",
    "gather", "copy", "copy_p", "stop_gradient", "split",
    # Pallas Ref ops (kernel-internal loads/stores in the inner jaxpr)
    "get", "swap", "masked_load", "masked_swap",
}

# scatter moves update bytes and (for the arithmetic variants) adds one
# flop per update element
_SCATTER = {"scatter", "scatter-add", "scatter_add", "scatter-mul",
            "scatter_mul", "scatter-max", "scatter-min", "scatter_max",
            "scatter_min"}

_HOST = {"device_put", "infeed", "outfeed", "copy_to_host_async"}

# primitives that are pure bookkeeping at trace level
_FREE = {"jit", "custom_jvp_call", "custom_vjp_call", "closed_call",
         "core_call", "named_call", "remat", "checkpoint", "custom_vmap_call",
         "program_id", "num_programs"}


@dataclasses.dataclass
class Counts:
    """Accumulated static cost of one program."""

    flops: float = 0.0
    mem_bytes: float = 0.0            # movement-primitive traffic
    transcendentals: float = 0.0
    by_prim: dict = dataclasses.field(default_factory=dict)
    host_prims: list = dataclasses.field(default_factory=list)
    while_loops: int = 0
    unknown: list = dataclasses.field(default_factory=list)

    def _bump(self, prim: str, flops: float) -> None:
        self.flops += flops
        self.by_prim[prim] = self.by_prim.get(prim, 0.0) + flops

    def scaled(self, times: float) -> "Counts":
        return Counts(
            flops=self.flops * times,
            mem_bytes=self.mem_bytes * times,
            transcendentals=self.transcendentals * times,
            by_prim={k: v * times for k, v in self.by_prim.items()},
            host_prims=list(self.host_prims),
            while_loops=self.while_loops,
            unknown=list(self.unknown),
        )

    def merge(self, other: "Counts") -> None:
        self.flops += other.flops
        self.mem_bytes += other.mem_bytes
        self.transcendentals += other.transcendentals
        for k, v in other.by_prim.items():
            self.by_prim[k] = self.by_prim.get(k, 0.0) + v
        self.host_prims.extend(other.host_prims)
        self.while_loops += other.while_loops
        for u in other.unknown:
            if u not in self.unknown:
                self.unknown.append(u)

    def to_dict(self) -> dict:
        return {
            "flops": self.flops,
            "mem_bytes": self.mem_bytes,
            "transcendentals": self.transcendentals,
            "by_prim": dict(sorted(self.by_prim.items())),
            "host_prims": list(self.host_prims),
            "while_loops": self.while_loops,
            "unknown": sorted(self.unknown),
        }


def _dot_general_flops(eqn) -> float:
    (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    contract = _prod(lhs.shape[i] for i in lhs_c)
    return 2.0 * _prod(eqn.outvars[0].aval.shape) * contract


def _conv_flops(eqn) -> float:
    rhs = eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    rhs_spec = dn.rhs_spec                  # (out_c, in_c, *spatial)
    in_c = rhs.shape[rhs_spec[1]]           # already per feature group
    k_spatial = _prod(rhs.shape[d] for d in rhs_spec[2:])
    return 2.0 * _prod(eqn.outvars[0].aval.shape) * in_c * k_spatial


def _eqn_io_bytes(eqn) -> float:
    return (sum(_aval_bytes(v.aval) for v in eqn.invars
                if hasattr(v, "aval"))
            + sum(_aval_bytes(v.aval) for v in eqn.outvars))


def _pallas_counts(eqn, path: str) -> Counts:
    est = eqn.params.get("cost_estimate")
    if est is not None:
        c = Counts(flops=float(getattr(est, "flops", 0) or 0),
                   mem_bytes=float(getattr(est, "bytes_accessed", 0) or 0),
                   transcendentals=float(
                       getattr(est, "transcendentals", 0) or 0))
        c.by_prim["pallas_call"] = c.flops
        return c
    grid = ()
    gm = eqn.params.get("grid_mapping")
    if gm is not None:
        grid = tuple(getattr(gm, "grid", ()) or ())
    inner = count_jaxpr(eqn.params["jaxpr"], _path=f"{path}/pallas_call")
    scaled = inner.scaled(_prod(grid) if grid else 1.0)
    # the kernel's true traffic is at least the call's operand/result
    # bytes, whatever the per-block get/swap pattern inside
    scaled.mem_bytes = max(scaled.mem_bytes, _eqn_io_bytes(eqn))
    return scaled


def count_jaxpr(jaxpr, _path: str = "") -> Counts:
    """Walk one (closed or raw) jaxpr and accumulate static costs.

    Deterministic: equations are visited in program order and every
    contribution is exact arithmetic over static avals.  Nested program
    structure (``jit`` of ``jit``, scans, conds, Pallas kernels) is
    recursed into, so counts are invariant to jit-of-jit nesting — the
    property pinned by ``tests/test_cert_properties.py``.
    """
    counts = Counts()
    inner = _inner_jaxpr(jaxpr)
    for i, eqn in enumerate(inner.eqns):
        name = eqn.primitive.name
        here = f"{_path}/eqn{i}:{name}" if _path else f"eqn{i}:{name}"

        if name == "dot_general":
            counts._bump(name, _dot_general_flops(eqn))
        elif name == "conv_general_dilated":
            counts._bump(name, _conv_flops(eqn))
        elif name == "scan":
            body = count_jaxpr(eqn.params["jaxpr"], _path=here)
            counts.merge(body.scaled(float(eqn.params.get("length", 1))))
        elif name == "while":
            counts.while_loops += 1
            counts.merge(count_jaxpr(eqn.params["body_jaxpr"], _path=here))
        elif name == "cond":
            branches = [count_jaxpr(b, _path=f"{here}/branch{k}")
                        for k, b in enumerate(eqn.params["branches"])]
            if branches:
                counts.merge(min(branches, key=lambda c: c.flops))
        elif name == "pallas_call":
            counts.merge(_pallas_counts(eqn, here))
        elif name in _HOST or "callback" in name:
            counts.host_prims.append(here)
        elif name in _SCATTER:
            counts.mem_bytes += _eqn_io_bytes(eqn)
            if name != "scatter":             # arithmetic combiner
                counts._bump(name, _prod(eqn.invars[-1].aval.shape))
        elif name in _MOVEMENT:
            counts.mem_bytes += _eqn_io_bytes(eqn)
        elif name in _TRANSCENDENTAL:
            n = _prod(eqn.outvars[0].aval.shape)
            counts._bump(name, n)
            counts.transcendentals += n
        elif name in _ELEMENTWISE:
            counts._bump(name, _prod(eqn.outvars[0].aval.shape))
        elif name in _REDUCE:
            counts._bump(name, _prod(eqn.invars[0].aval.shape))
        else:
            recursed = False
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                sub = eqn.params.get(key) if eqn.params else None
                if sub is not None:
                    counts.merge(count_jaxpr(sub, _path=here))
                    recursed = True
                    break
            if not recursed and name not in _FREE:
                if name not in counts.unknown:
                    counts.unknown.append(name)
    return counts


def program_io_bytes(jaxpr) -> tuple[float, float]:
    """(input_bytes, output_bytes) of the whole program — the memory a
    perfectly-fused executable must still touch, and therefore the
    bytes term that keeps the roofline a floor."""
    inner = _inner_jaxpr(jaxpr)
    in_b = sum(_aval_bytes(v.aval) for v in inner.invars)
    out_b = sum(_aval_bytes(v.aval) for v in inner.outvars
                if hasattr(v, "aval"))
    return float(in_b), float(out_b)


def outer_donated_invars(jaxpr) -> Optional[tuple[bool, ...]]:
    """Donation mask of a traced jitted call: ``make_jaxpr`` of a jitted
    function yields one outer ``jit`` equation whose ``donated_invars``
    records which (flattened) inputs the compiled program may alias.
    ``None`` when the program is not a single jitted call."""
    inner = _inner_jaxpr(jaxpr)
    if len(inner.eqns) == 1 and inner.eqns[0].primitive.name == "jit":
        mask = inner.eqns[0].params.get("donated_invars")
        return tuple(bool(b) for b in mask) if mask is not None else None
    return None
