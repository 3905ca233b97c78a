"""Chaos benchmark: fault-recovery gates for the sharded fleet.

Two measurements, mirroring the chaos catalog:

* ``shard_loss_rush_hour`` runs in a child process with two forced host
  devices (the ``benchmarks/fleet.py`` pattern), so the shard death
  evacuates streams between *real* mesh shards.  The child is
  ``python -m repro.chaos --check``: every evacuated stream must be
  re-seated within ``RESEAT_BOUND`` ticks of the kill, with zero
  backend compiles (failover is slot churn under a
  ``TraceSentinel(compile_budget=0)``).

* ``sensor_stall_storm`` replays in-process: stalls, corrupt frames, a
  latency spike and transient step faults must produce watchdog fires,
  bounded retries and hysteretic recoveries — with every
  degraded-to-healthy recovery inside ``RECOVERY_BOUND`` ticks.

Both episode reports (ledger, recovery times, trace counts) are dropped
as JSON artifacts in ``chaos_reports/`` for CI upload.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from .common import csv_line, table

RESEAT_BOUND = 3
RECOVERY_BOUND = 20
REPORT_DIR = "chaos_reports"


def _save_report(name: str, doc: dict) -> str:
    os.makedirs(REPORT_DIR, exist_ok=True)
    path = os.path.join(REPORT_DIR, f"{name}.report.json")
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True, allow_nan=False)
    return path


def _run_shard_loss() -> dict:
    """Kill-a-shard episode on a forced 2-device host, gated by the
    ``repro.chaos --check`` acceptance criteria in the child itself."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if not f.startswith("--xla_force_host_platform_device_count"))
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count=2".strip())
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
        cmd = [sys.executable, "-m", "repro.chaos",
               "--episode", "shard_loss_rush_hour",
               "--mesh", "data=2",
               "--check",
               "--reseat-bound", str(RESEAT_BOUND),
               "--json-out", out_path]
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"chaos child (shard_loss_rush_hour) failed:\n"
                f"{proc.stdout}\n{proc.stderr}")
        with open(out_path) as f:
            return json.load(f)
    finally:
        os.unlink(out_path)


def _run_storm() -> dict:
    """Sensor-fault storm in-process, under a zero-compile sentinel."""
    from repro.analysis.sentinel import TraceSentinel
    from repro.chaos import run_chaos_episode

    sentinel = TraceSentinel(compile_budget=0)
    report, replayer, plan = run_chaos_episode("sensor_stall_storm",
                                               sentinel=sentinel)
    sched = replayer.scheduler
    ledger = report.chaos or {}
    counts = ledger.get("counts", {})
    recovery = ledger.get("recovery_ticks", [])
    problems = []
    if not counts.get("watchdog"):
        problems.append("latency spike never tripped the watchdog")
    if not counts.get("retry"):
        problems.append("armed step faults produced no retry events")
    if not recovery:
        problems.append("no degraded stream ever recovered to healthy")
    elif max(recovery) > RECOVERY_BOUND:
        problems.append(f"slowest recovery took {max(recovery)} ticks "
                        f"(bound {RECOVERY_BOUND})")
    traces = {name: eng.trace_count for name, eng in sched.engines.items()}
    if any(n > 1 for n in traces.values()):
        problems.append(f"a rung engine retraced under chaos ({traces})")
    if problems:
        raise AssertionError("sensor_stall_storm gates failed: "
                             + "; ".join(problems))
    return {
        "episode": "sensor_stall_storm",
        "n_ticks": report.n_ticks,
        "virtual_s": report.clock_s,
        "n_faults": len(plan.events),
        "trace_counts": traces,
        "ledger_counts": counts,
        "recovery_ticks": recovery,
        "report": report.to_dict(),
    }


def run() -> None:
    shard = _run_shard_loss()
    storm = _run_storm()
    rows = [
        {
            "episode": shard["episode"],
            "faults": shard["n_faults"],
            "failovers": shard["ledger_counts"].get("failover", 0),
            "reseat_ticks": shard["reseat_ticks"],
            "recoveries": len(shard["recovery_ticks"]),
            "max_traces": max(shard["trace_counts"].values()),
        },
        {
            "episode": storm["episode"],
            "faults": storm["n_faults"],
            "failovers": storm["ledger_counts"].get("failover", 0),
            "reseat_ticks": None,
            "recoveries": len(storm["recovery_ticks"]),
            "max_traces": max(storm["trace_counts"].values()),
        },
    ]
    table(rows, f"chaos recovery gates (reseat <= {RESEAT_BOUND} ticks, "
                f"recovery <= {RECOVERY_BOUND} ticks, zero retraces)")
    print("CPU rehearsal: the shard-loss child runs on forced host devices "
          "and every latency is modeled virtual time; these numbers are "
          "not chip measurements")

    tick_us = shard["report"]["clock_s"] / shard["report"]["n_ticks"] * 1e6
    csv_line("chaos_shard_loss", tick_us,
             f"failovers={shard['ledger_counts'].get('failover', 0)} "
             f"reseat_ticks={shard['reseat_ticks']} "
             f"max_traces={max(shard['trace_counts'].values())}")
    tick_us = storm["virtual_s"] / storm["n_ticks"] * 1e6
    worst = max(storm["recovery_ticks"])
    csv_line("chaos_storm", tick_us,
             f"watchdog={storm['ledger_counts'].get('watchdog', 0)} "
             f"retries={storm['ledger_counts'].get('retry', 0)} "
             f"worst_recovery_ticks={worst}")

    for name, doc in (("shard_loss_rush_hour", shard),
                      ("sensor_stall_storm", storm)):
        path = _save_report(name, doc)
        print(f"wrote {path}")


if __name__ == "__main__":
    run()
