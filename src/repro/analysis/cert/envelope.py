"""The declared input envelope: every (rung × batch-size × occupancy)
point the serving schedulers can produce, plus the single-frame ladder
rungs and the Pallas kernels at their canonical certification shapes.

The envelope is the certifier's universe of discourse.  Retrace-freedom
is only meaningful *relative to a set of inputs*: the claim the
certificate commits is "after warmup, no envelope point presents a new
aval signature to any jitted hot-path program".  Everything in the
envelope is static data — shapes, dtypes, occupancy grids — so its hash
pins the claim: a code change that widens the reachable input set
(a new rung, a new batch size, a capacity change) changes the hash and
forces an explicit ``--regen``.

Occupancies (1..capacity) drive the *certification* sweep: one engine
per rung at fixed ``capacity``, join/leave/carve-out churn between
ticks.  ``batch_sizes`` drive the *cost table*: one row per engine at
capacity *b* with all slots dirty, each checked against the cost
model's prior for that (rung, batch-size).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json

import jax.numpy as jnp

from repro.perception.data import H, W

__all__ = [
    "RungPoint",
    "KernelPoint",
    "InputEnvelope",
    "default_envelope",
    "envelope_hash",
    "DTYPES",
]

# dtype shorthand used in envelope specs and aval signatures
DTYPES = {
    "f32": jnp.float32,
    "f16": jnp.float16,
    "bf16": jnp.bfloat16,
    "i32": jnp.int32,
    "i64": jnp.int64,
    "pred": jnp.bool_,
}


@dataclasses.dataclass(frozen=True)
class RungPoint:
    """One pipeline variant in the envelope."""

    name: str
    pipeline: str
    scale: float = 1.0
    pad: bool = True

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class KernelPoint:
    """One Pallas kernel wrapper at its canonical certification avals.

    ``args`` is a tuple of ``(dtype_short, shape)`` pairs, one per
    positional argument of the ``repro.kernels`` wrapper.
    """

    name: str
    args: tuple

    def to_dict(self) -> dict:
        return {"name": self.name,
                "args": [[d, list(s)] for d, s in self.args]}


@dataclasses.dataclass(frozen=True)
class InputEnvelope:
    """The full declared input set the certifier sweeps."""

    capacity: int
    occupancies: tuple          # engine certification sweep (1..capacity)
    batch_sizes: tuple          # cost-table batch sizes (stream counts)
    image_shape: tuple
    rungs: tuple                # RungPoint — batched engine rungs
    ladder_rungs: tuple         # RungPoint — anytime single-frame rungs
    kernels: tuple              # KernelPoint
    churn: bool = True          # exercise join/leave/carve-out between ticks
    # fleet sharding: declared data-axis shard counts the serving meshes
    # may take.  jit signatures key on *global* avals, so the committed
    # per-program signatures hold at every declared K; what each K adds
    # is a slot-block partition (capacity/K slots per device), certified
    # by the divisibility check in the certificate's ``fleet`` section.
    fleet_shards: tuple = (1, 2)

    def describe(self) -> dict:
        """Canonical JSON-serializable description (hash input)."""
        return {
            "capacity": self.capacity,
            "occupancies": list(self.occupancies),
            "batch_sizes": list(self.batch_sizes),
            "image_shape": list(self.image_shape),
            "rungs": [r.to_dict() for r in self.rungs],
            "ladder_rungs": [r.to_dict() for r in self.ladder_rungs],
            "kernels": [k.to_dict() for k in self.kernels],
            "churn": self.churn,
            "fleet_shards": list(self.fleet_shards),
        }


def envelope_hash(env: InputEnvelope) -> str:
    blob = json.dumps(env.describe(), sort_keys=True,
                      separators=(",", ":"))
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def default_envelope() -> InputEnvelope:
    """The shipped system's envelope.

    * Batched rungs: the three serving rungs (the ladder's top plus the
      cheap bounds), scale 1.0, padded input.
    * Ladder rungs: ``anytime.default_rungs`` — the λ-scaled pad=False
      single-frame pipelines the contract controller can select.
    * Kernels: canonical shapes from ``repro.kernels.CERT_SHAPES``.
    """
    from repro.anytime.ladder import default_rungs
    from repro.kernels import CERT_SHAPES

    capacity = 8
    return InputEnvelope(
        capacity=capacity,
        occupancies=tuple(range(1, capacity + 1)),
        batch_sizes=(1, 2, 4, 8),
        image_shape=(H, W, 3),
        rungs=(
            RungPoint("two_stage", "two_stage"),
            RungPoint("one_stage", "one_stage"),
            RungPoint("early_exit", "early_exit"),
        ),
        ladder_rungs=tuple(
            RungPoint(r.name, r.pipeline, scale=r.scale, pad=False)
            for r in default_rungs()
        ),
        kernels=tuple(
            KernelPoint(name, tuple((d, tuple(s)) for d, s in args))
            for name, args in sorted(CERT_SHAPES.items())
        ),
    )
