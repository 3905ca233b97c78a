"""Roofline latency floors and the cost-model drift gate.

A program with ``F`` flops, ``B`` unavoidable bytes (its input+output —
what even a perfectly-fused executable must touch) and ``H`` bytes of
host→device upload can finish no sooner than::

    floor = max(F / peak_flops,  B / mem_bw,  H / h2d_bw)

on hardware with those ceilings.  The certifier computes this floor per
(rung, batch-size) from the static counts (``costs.py``) and uses it two
ways:

* **sanity** — the floor must sit at or below the cost-model prior
  committed for the same (rung, batch-size) (a floor above it means the
  counts or the hardware model are wrong);
* **drift gate** — the ratio ``prior / floor`` between the learned
  ``anytime/cost.py`` cold-start prior and the static floor is committed
  in the certificate.  ``--check`` recomputes the floor statically: if
  model code changed the FLOP count without anyone recalibrating the
  cost model, the ratio moves and the gate fails at ±25%.  The same
  comparison, fed a *live* cost model's priors (``drift_findings``),
  catches miscalibration at runtime — the 2×-perturbation acceptance
  test.

Hardware numbers are deliberately on the *optimistic* side for the 2-core
CI container (floors must be floors); they are committed inside the
certificate so a hardware-model change is itself a visible diff.
"""
from __future__ import annotations

import dataclasses

__all__ = ["Hardware", "CPU_2CORE", "roofline_floor", "drift_findings"]


@dataclasses.dataclass(frozen=True)
class Hardware:
    """Peak ceilings for the roofline floor (all per second)."""

    name: str
    peak_flops: float            # FLOP/s
    mem_bw: float                # bytes/s, main memory
    h2d_bw: float                # bytes/s, host→device (loopback on CPU)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# 2 cores × ~3 GHz × 8 f32 lanes (AVX2 FMA counts 2): generous, so the
# floor stays a floor even on a faster runner
CPU_2CORE = Hardware("cpu-2core-avx2", peak_flops=9.6e10,
                     mem_bw=3.0e10, h2d_bw=3.0e10)


def roofline_floor(flops: float, bytes_min: float, h2d_bytes: float,
                   hw: Hardware) -> float:
    """Static latency lower bound in seconds."""
    return max(flops / hw.peak_flops,
               bytes_min / hw.mem_bw,
               h2d_bytes / hw.h2d_bw)


def drift_findings(cost_table: list[dict], priors: dict, tol: float = 0.25
                   ) -> list[str]:
    """Cross-check live per-(rung, batch-size) cost-model priors against
    the certificate's committed ``prior/floor`` ratios.

    ``priors`` maps ``(rung_name, batch_size)`` → predicted latency
    seconds (e.g. from ``anytime.cost.cold_start_prior_table``).  A row
    whose live ratio deviates from the committed ratio by more than
    ``tol`` (relative) is reported — the static program and the learned
    cost model no longer describe the same computation.
    """
    findings = []
    for row in cost_table:
        key = (row["rung"], int(row["batch_size"]))
        if key not in priors or row.get("ratio") is None:
            continue
        floor = row["floor_s"]
        if floor <= 0.0:
            findings.append(f"{key}: non-positive static floor {floor}")
            continue
        live = priors[key] / floor
        committed = row["ratio"]
        drift = abs(live - committed) / committed
        if drift > tol:
            findings.append(
                f"{row['rung']}/batch{int(row['batch_size'])}: "
                f"prior/floor ratio drifted {drift:.0%} "
                f"(committed {committed:.1f}, live {live:.1f}, "
                f"tol {tol:.0%}) — recalibrate the cost model or "
                "regenerate the certificate")
    return findings
