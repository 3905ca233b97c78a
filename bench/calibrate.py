#!/usr/bin/env python3
"""Readings that set a cell's limits (``bench/limits/<cell>.json``).

    python bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] --control-seeds <k>

In one process, for each seed: build the cell, serve ``--seconds`` of its
traffic exactly as ``bench/run.py`` does, and compare the run's seeded
sample of served frames with the configuration's reference in float32
(the lower readings).  For the first ``--control-seeds`` seeds, the
control as well: the same reference computed wholly in bfloat16
(``Reference(config, key, jnp.bfloat16)``), put in the program's place
on the same frames, compared with the float32 reference (the upper
readings).
One JSON line per seed and side.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run as bench  # noqa: E402


def control_numbers(run, frames, lo, hi) -> dict:
    """``compare`` of the reference ``lo`` (bfloat16), put in the
    program's place, against ``hi`` (float32) on the same frames."""
    from bench.compare import compare

    rows = []
    for rung in sorted({f.rung for f in frames}):
        fs = [f for f in frames if f.rung == rung]
        images = bench.np.stack([run.pools[f.stream][f.scene].image
                                 for f in fs])
        kind = run.config["rungs"][rung]["kind"]
        for a, b in zip(lo.detect(rung, images), hi.detect(rung, images)):
            rows.append((kind, a.boxes, a.n_proposals, b))
    return compare(rows, float(run.config["frame"][1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    from repro.analysis.sentinel import TraceSentinel
    from repro.launch.compile_cache import configure_compile_cache

    import jax.numpy as jnp

    from bench.detection import weights_key

    configure_compile_cache()
    cell = bench.load_cell(args.workload)
    Reference = cell["reference"].Reference
    try:
        devs = bench.devices_for(cell["chips"])
    except bench.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    peak = bench.model_flops.peaks(devs[0].device_kind)
    hi = Reference(cell["config"], weights_key())
    lo = Reference(cell["config"], weights_key(), jnp.bfloat16)
    for i, seed in enumerate(args.seeds):
        sched = bench.build(cell, devs)
        pools, program_pools = bench.scene_pools(cell, seed)
        served = bench.serve(sched, cell, program_pools, args.seconds,
                             lambda name: contextlib.nullcontext(),
                             TraceSentinel)
        ladder = list(sched.engines)
        del sched, program_pools
        gc.unfreeze()
        gc.collect()
        run = bench.Run(cell=cell, seconds=args.seconds,
                        t_open=served.t_open, t_close=served.t_close,
                        ticks=served.ticks, frames=served.frames,
                        ladder=ladder, chips=cell["chips"], peak=peak,
                        pools=pools)
        picked = bench.sample(run, seed, cell["traffic"]["sample"])
        rungs = sorted({f.rung for f in picked})
        print(json.dumps({"seed": seed, "side": "program", "rungs": rungs,
                          "frames": len(picked),
                          **bench.check(run, picked, hi)}), flush=True)
        if i < args.control_seeds:
            print(json.dumps({"seed": seed, "side": "control",
                              "rungs": rungs, "frames": len(picked),
                              **control_numbers(run, picked, lo, hi)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
