"""Benchmark driver: one module per paper table/figure (DESIGN.md §7).

Prints each benchmark's table plus ``CSV,name,us_per_call,derived`` lines,
and mirrors every CSV record into a machine-readable ``BENCH_results.json``
(per-benchmark ``us_per_call`` + derived metrics, wall time, status) so
the perf trajectory is trackable across commits.  Override the output
path with ``BENCH_RESULTS_PATH``.  ``--trace-out DIR`` makes the
tracing-aware benchmarks (scenarios, pipelined, obs_overhead) also drop
Chrome trace_event JSON artifacts in ``DIR``.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

MODULES = [
    "table1_zoo",
    "fig4_scenarios",
    "fig5_object_correlation",
    "fig6_pixels",
    "table4_rain",
    "fig9_bus",
    "table6_breakdown",
    "table8_sched",
    "fig13_hardware",
    "fig16_system",
    "multi_tenant",
    "static_fix",
    "anytime",
    "batched",
    "pipelined",
    "scenarios",
    "obs_overhead",
    "roofline",
    "cert_overhead",
    "fleet",
    "chaos",
]


def _parse_argv(argv: list[str]) -> list[str]:
    """Split flags from module names; exports --trace-out as
    ``BENCH_TRACE_OUT`` for tracing-aware benchmarks (common.trace_out_path)."""
    if "--trace-out" in argv:
        i = argv.index("--trace-out")
        if i + 1 >= len(argv):
            raise SystemExit(
                "usage: python -m benchmarks.run [--trace-out DIR] [module ...]")
        os.environ["BENCH_TRACE_OUT"] = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    return argv


def main() -> int:
    import importlib

    from repro.launch.compile_cache import configure_compile_cache

    from .common import drain_results

    only = _parse_argv(sys.argv[1:]) or MODULES
    configure_compile_cache()
    failures = []
    report: dict[str, dict] = {}
    for name in only:
        t0 = time.time()
        print(f"\n######## {name} ########", flush=True)
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            drain_results()                 # import-time noise, if any
            t0 = time.time()
            mod.run()
            status = "ok"
            print(f"[{name}] done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failures.append(name)
            status = "failed"
            traceback.print_exc()
        report[name] = {
            "status": status,
            "wall_s": round(time.time() - t0, 3),
            "results": drain_results(),
        }

    path = os.environ.get("BENCH_RESULTS_PATH", "BENCH_results.json")
    # merge-update: BENCH_results.json is tracked as the perf trajectory,
    # so a partial run (e.g. `benchmarks.run multi_tenant`) must refresh
    # only the modules it ran instead of clobbering the rest of the file
    merged: dict[str, dict] = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                merged = json.load(f).get("benchmarks", {})
        except (json.JSONDecodeError, OSError):
            merged = {}
    merged.update(report)
    # the file's failure list must describe the file (merged modules, some
    # possibly from earlier runs), not just this invocation
    file_failures = sorted(k for k, v in merged.items()
                           if v.get("status") == "failed")
    with open(path, "w") as f:
        json.dump({"benchmarks": merged, "failures": file_failures}, f, indent=2)
    print(f"\nwrote {path} ({sum(len(v['results']) for v in report.values())} "
          f"records from this run, {len(merged)} modules total)")

    if failures:
        print(f"\nFAILED benchmarks: {failures}")
        return 1
    print("\nAll benchmarks completed.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
