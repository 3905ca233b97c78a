"""Aval-recording tracer: drive the *real* engine code over the envelope
while replacing its jitted programs with recorders.

The certification problem is two-sided.  ``jax.make_jaxpr`` alone can
certify a *program* (trace once, walk the IR), but retraces are caused
by the *host logic around* the programs — a shape-dependent branch in
``engine.tick``, a carve-out that builds a differently-shaped batch.  So
instead of tracing programs in isolation, the harness instruments the
executor (``PipelinedExecutor.instrument``) with :class:`ProgramRecorder`
wrappers and then runs the genuine engine host path — ``compile``,
``join``/``leave``, ``tick``, ``probe`` — over every envelope point:

* each recorder captures the **aval signature** of every call and runs
  ``jax.make_jaxpr`` once per new signature (pure tracing — no XLA
  compile, no detector FLOP executes);
* it returns a zeros tree shaped by ``jax.eval_shape``, so downstream
  host logic (drain, vectorized post) runs for real on correctly-shaped
  data;
* after warmup the recorders are **frozen**: any envelope point that
  presents a signature not already seen is a retrace violation, recorded
  with the (rung, occupancy, event) context that produced it.

Because the real ``tick`` path executes, a shape-dependent branch
injected into a copy of ``batched/engine.py`` is caught here — the
acceptance test for the whole subsystem — where a program-only tracer
would certify the unmodified programs and miss it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .costs import Counts, count_jaxpr, outer_donated_invars, program_io_bytes
from .envelope import DTYPES, InputEnvelope, KernelPoint, RungPoint

__all__ = [
    "aval_signature",
    "ProgramRecorder",
    "ProgramSummary",
    "RungTrace",
    "certify_rung",
    "trace_ladder_rung",
    "trace_kernel",
]

_SHORT = {"float32": "f32", "float64": "f64", "float16": "f16",
          "bfloat16": "bf16", "int64": "i64", "int32": "i32",
          "int16": "i16", "int8": "i8", "uint8": "u8", "bool": "pred"}


def _aval_str(x) -> str:
    name = np.dtype(x.dtype).name if not hasattr(x.dtype, "name") \
        else x.dtype.name
    dims = ",".join(str(d) for d in getattr(x, "shape", ()))
    return f"{_SHORT.get(name, name)}[{dims}]"


def aval_signature(args) -> str:
    """Canonical signature of a pytree of arrays: dtype+shape per leaf,
    in flatten order — exactly what jit keys its executable cache on
    (weak types and shardings aside, which this repo holds constant)."""
    leaves = jax.tree.leaves(args)
    return "(" + ", ".join(_aval_str(x) for x in leaves) + ")"


class ProgramRecorder:
    """Stand-in for one jitted program: records signatures, traces each
    new one to a closed jaxpr, executes nothing."""

    def __init__(self, name: str, fn: Callable) -> None:
        self.name = name
        self._fn = fn
        self.signatures: list[str] = []
        self.jaxprs: dict[str, Any] = {}
        self._out_shapes: dict[str, Any] = {}
        self.calls = 0
        self.frozen = False
        self.context = "warmup"
        self.violations: list[tuple[str, str]] = []   # (signature, context)

    def freeze(self) -> None:
        """End of warmup: every signature from here on must already be
        known, or it is a retrace the engine would pay at runtime."""
        self.frozen = True

    def __call__(self, *args):
        self.calls += 1
        sig = aval_signature(args)
        if sig not in self.jaxprs:
            if self.frozen:
                self.violations.append((sig, self.context))
            self.jaxprs[sig] = jax.make_jaxpr(self._fn)(*args)
            self._out_shapes[sig] = jax.eval_shape(self._fn, *args)
            self.signatures.append(sig)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self._out_shapes[sig])


@dataclasses.dataclass
class ProgramSummary:
    """One traced program's static certificate entry."""

    name: str
    signatures: list
    counts: Counts
    in_bytes: float
    out_bytes: float
    donated_invars: Optional[tuple]
    declared_donation: tuple
    calls: int
    violations: list

    def to_dict(self) -> dict:
        return {
            "signatures": list(self.signatures),
            "in_bytes": self.in_bytes,
            "out_bytes": self.out_bytes,
            "donated_invars": (list(self.donated_invars)
                               if self.donated_invars is not None else None),
            "declared_donation": list(self.declared_donation),
            "calls": self.calls,
            "violations": [list(v) for v in self.violations],
            **self.counts.to_dict(),
        }


@dataclasses.dataclass
class RungTrace:
    """All programs of one batched rung after the envelope sweep."""

    rung: str
    programs: dict                      # name -> ProgramSummary
    violations: list                    # flattened (program, sig, context)


def _summarize(name: str, rec: ProgramRecorder,
               declared_donation: tuple) -> ProgramSummary:
    counts = Counts()
    in_b = out_b = 0.0
    donated = None
    for sig in rec.signatures:
        closed = rec.jaxprs[sig]
        counts.merge(count_jaxpr(closed))
        i, o = program_io_bytes(closed)
        in_b, out_b = max(in_b, i), max(out_b, o)
        if donated is None:
            donated = outer_donated_invars(closed)
    return ProgramSummary(
        name=name, signatures=list(rec.signatures), counts=counts,
        in_bytes=in_b, out_bytes=out_b, donated_invars=donated,
        declared_donation=tuple(declared_donation), calls=rec.calls,
        violations=list(rec.violations))


def certify_rung(point: RungPoint, env: InputEnvelope,
                 engine_cls=None) -> RungTrace:
    """Sweep one rung's engine across the occupancy × churn envelope.

    ``engine_cls`` defaults to the shipped ``BatchedPerceptionEngine``;
    the injection acceptance test passes a mutated copy instead.
    """
    if engine_cls is None:
        from repro.batched.engine import BatchedPerceptionEngine
        engine_cls = BatchedPerceptionEngine

    kw = {}
    if point.scale != 1.0:
        kw["scale"] = point.scale
    if not point.pad:
        kw["pad"] = point.pad
    eng = engine_cls(point.pipeline, capacity=env.capacity,
                     image_shape=tuple(env.image_shape), **kw)
    recorders = eng.executor.instrument(
        lambda name, fn: ProgramRecorder(f"{point.name}/{name}", fn))

    def ctx(c: str) -> None:
        for r in recorders.values():
            r.context = c

    eng.compile()                       # warmup traces every program
    for r in recorders.values():
        r.freeze()

    frame = np.zeros(tuple(env.image_shape), np.float32)
    seated: list[str] = []
    for occ in env.occupancies:
        while len(seated) < occ:
            sid = f"cam{len(seated)}"
            ctx(f"occ{occ}/join:{sid}")
            eng.join(sid)
            seated.append(sid)
        while len(seated) > occ:
            sid = seated.pop()
            ctx(f"occ{occ}/leave:{sid}")
            eng.leave(sid)
        ctx(f"occ{occ}/tick")
        eng.tick({sid: frame for sid in seated})
        if occ >= 2:
            # a camera that skipped this tick must not change any aval
            ctx(f"occ{occ}/tick_partial")
            eng.tick({seated[0]: frame})
        if env.churn and occ >= 2:
            sid = seated.pop(0)
            ctx(f"occ{occ}/churn_leave:{sid}")
            eng.leave(sid)                        # carve-out (slot_update)
            ctx(f"occ{occ}/tick_after_leave")
            eng.tick({s: frame for s in seated})
            ctx(f"occ{occ}/churn_rejoin:{sid}")
            eng.join(sid)
            seated.append(sid)
            ctx(f"occ{occ}/tick_after_rejoin")
            eng.tick({s: frame for s in seated})
    # the scheduler's calibration probe (all-dirty upload + step avals)
    ctx("probe")
    eng.probe([frame])

    declared = getattr(eng.executor, "DONATED_ARGNUMS", {})
    programs = {
        rec.name: _summarize(rec.name, rec, declared.get(short, ()))
        for short, rec in recorders.items()
    }
    violations = [(rec.name, sig, where)
                  for rec in recorders.values()
                  for sig, where in rec.violations]
    return RungTrace(rung=point.name, programs=programs,
                     violations=violations)


def trace_ladder_rung(point: RungPoint, env: InputEnvelope) -> ProgramSummary:
    """Trace one anytime-ladder single-frame pipeline at its effective
    (λ-scaled, 8-px-snapped) input shape."""
    from repro.perception.pipelines import build_pipeline, preprocess

    built = build_pipeline(point.pipeline, scale=point.scale, pad=point.pad)
    shape = preprocess(np.zeros(tuple(env.image_shape), np.float32),
                       point.scale, point.pad).shape
    spec = jax.ShapeDtypeStruct(shape, jnp.float32)
    closed = jax.make_jaxpr(built.infer)(spec)
    counts = count_jaxpr(closed)
    i, o = program_io_bytes(closed)
    return ProgramSummary(
        name=f"ladder/{point.name}/infer",
        signatures=[aval_signature((spec,))], counts=counts,
        in_bytes=i, out_bytes=o,
        donated_invars=outer_donated_invars(closed),
        declared_donation=(), calls=1, violations=[])


def trace_kernel(point: KernelPoint) -> ProgramSummary:
    """Trace one Pallas kernel wrapper at its canonical avals."""
    from repro import kernels

    fn = getattr(kernels, point.name)
    specs = tuple(jax.ShapeDtypeStruct(tuple(shape), DTYPES[dt])
                  for dt, shape in point.args)
    closed = jax.make_jaxpr(fn)(*specs)
    counts = count_jaxpr(closed)
    i, o = program_io_bytes(closed)
    return ProgramSummary(
        name=f"kernels/{point.name}", signatures=[aval_signature(specs)],
        counts=counts, in_bytes=i, out_bytes=o,
        donated_invars=outer_donated_invars(closed),
        declared_donation=(), calls=1, violations=[])
