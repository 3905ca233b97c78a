"""Fleet-sharding scaling benchmark: frames/s vs device count.

Each measurement point runs ``launch/serve.py --fleet`` in a child
process with ``--xla_force_host_platform_device_count=K`` forced into
XLA_FLAGS, so every point executes *real* sharded XLA programs over a
K-device ``Mesh`` — even on a 1-accelerator CI host — and the parent
reads the child's ``--json-out`` report.

The sweep is weak scaling at the ISSUE's operating point (8 streams per
shard): K devices serve 8·K cameras, every rung engine's padded slot
batch carrying a ``NamedSharding`` over the mesh's ``data`` axis.  Tick
cost under the seeded virtual-time model is the max over shards (each
device steps its slice in parallel), so frames/s should grow close to
linearly with K — the affine batch-cost law
(``ModeledStageCost.batch_base + batch_slope·n``) caps the strong-
scaling gain at (0.6 + 0.4·2n)/(0.6 + 0.4·n) < 2, which is why CI
asserts the conservative 1.6× floor at data=2 rather than 2×.

A generous budget (``--slo-ms 200``) pins every stream to the top rung
in all configurations; without it, the 1-device run's contract
controllers degrade rungs under batching pressure and the comparison
stops being apples-to-apples.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from .common import csv_line, table

STREAMS_PER_SHARD = 8
TICKS = 30
DEVICE_COUNTS = (1, 2)
MIN_SCALING_X2 = 1.6


def _run_point(k: int) -> dict:
    """One measurement: 8·K streams on a data=K mesh in a child process
    with K forced host devices."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if not f.startswith("--xla_force_host_platform_device_count"))
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={k}".strip())
    # a CPU rehearsal by design: the child reads modeled virtual time on
    # forced host devices, and must never reach for a chip this process
    # (or a parent benchmark) may already hold
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as fh:
        out_path = fh.name
    try:
        cmd = [sys.executable, "-m", "repro.launch.serve", "--fleet",
               "--streams", str(STREAMS_PER_SHARD * k),
               "--mesh", f"data={k}",
               "--ticks", str(TICKS),
               "--slo-ms", "200",
               "--json-out", out_path]
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"fleet child (data={k}) failed:\n{proc.stdout}\n{proc.stderr}")
        with open(out_path) as f:
            return json.load(f)
    finally:
        os.unlink(out_path)


def run() -> None:
    rows = []
    reports = {}
    for k in DEVICE_COUNTS:
        doc = _run_point(k)
        reports[k] = doc
        traces = doc["trace_counts"]
        rows.append({
            "devices": k,
            "streams": doc["streams"],
            "frames": doc["frames"],
            "virtual_ms": doc["virtual_s"] * 1e3,
            "frames_per_s": doc["frames_per_vs"],
            "max_traces": max(traces.values()),
            "wall_s": doc["wall_s"],
        })
    table(rows, "fleet scaling (virtual-time frames/s, weak scaling "
               f"at {STREAMS_PER_SHARD} streams/shard)")
    print("CPU rehearsal: forced host devices and modeled virtual time; "
          "these numbers are not chip measurements")

    base = reports[1]["frames_per_vs"]
    for k in DEVICE_COUNTS:
        doc = reports[k]
        scaling = doc["frames_per_vs"] / base
        tick_us = doc["virtual_s"] / doc["ticks"] * 1e6
        csv_line(f"fleet_data{k}", tick_us,
                 f"frames_per_s={doc['frames_per_vs']:.1f} "
                 f"scaling_x={scaling:.3f} streams={doc['streams']}")
        if max(doc["trace_counts"].values()) != 1:
            raise AssertionError(
                f"data={k}: a rung engine retraced under fleet serving "
                f"(trace_counts={doc['trace_counts']})")
    scaling2 = reports[2]["frames_per_vs"] / base
    print(f"\nscaling at data=2: {scaling2:.3f}x "
          f"(floor {MIN_SCALING_X2:.1f}x)")
    if scaling2 < MIN_SCALING_X2:
        raise AssertionError(
            f"fleet scaling regression: data=2 delivers {scaling2:.3f}x "
            f"frames/s over data=1, below the {MIN_SCALING_X2:.1f}x floor")


if __name__ == "__main__":
    run()
