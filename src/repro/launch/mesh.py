"""Production meshes.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "parse_mesh_spec",
           "PROD_SHAPE", "MULTIPOD_SHAPE"]

PROD_SHAPE = (16, 16)            # 256 chips, one v5e pod
MULTIPOD_SHAPE = (2, 16, 16)     # 2 pods × 256 chips


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh: (data=16, model=16) single pod, or
    (pod=2, data=16, model=16) across two pods.  The ``pod`` axis composes
    with ``data`` for batch sharding; see DESIGN.md §5."""
    shape = MULTIPOD_SHAPE if multi_pod else PROD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1, devices=None):
    """A mesh over whatever devices exist locally (tests / examples), or
    over the first ``data * model`` of ``devices`` when given.

    An oversubscribed request is factored down to the largest feasible
    shape that preserves *both* axes: ``model`` is the rigid axis (it
    encodes how the program itself is partitioned, so silently shrinking
    it would change every sharded layout), while ``data`` is elastic and
    shrinks to ``n // model``.  ``data=4, model=4`` on 8 devices yields
    ``(2, 4)`` — never ``(8, 1)``.  When ``model`` alone exceeds the
    device count it cannot be honored at any data width; that is an
    error, not a silent collapse.

    Every axis is ``Auto``: JAX now defaults to ``Explicit`` axes, under
    which a reshape that splits a sharded dim (the detector's pooling)
    raises, while the executor's ``out_shardings`` design leaves
    partitioning to GSPMD.
    """
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got data={data} model={model}")
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    if model > n:
        raise ValueError(
            f"mesh model={model} cannot be honored: only {n} device(s) "
            f"available (need at least `model` devices; set "
            f"--xla_force_host_platform_device_count for CPU experiments)")
    if data * model > n:
        data = max(1, n // model)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=devices[:data * model])


def parse_mesh_spec(spec: str) -> dict[str, int]:
    """Parse a CLI mesh spec like ``data=4`` or ``data=4,model=2`` into
    keyword arguments for :func:`make_local_mesh`."""
    out: dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, val = part.partition("=")
        name = name.strip()
        if name not in ("data", "model"):
            raise ValueError(f"unknown mesh axis {name!r} in {spec!r} "
                             f"(expected data=K[,model=M])")
        try:
            out[name] = int(val)
        except ValueError:
            raise ValueError(f"bad mesh axis size {val!r} in {spec!r}") from None
    if not out:
        raise ValueError(f"empty mesh spec {spec!r}")
    return out
