#!/usr/bin/env python3
"""Bring-up check: serve the camera-fleet perception path on a TPU.

    python chip_smoke.py              # one chip: every rung, depth 1 and 2
    python chip_smoke.py --chips 4    # data=4 fleet vs the same scenes on 1 chip

One chip: 8 camera streams are seated in a ``RungBucketScheduler`` over
the replay ladder in measured wall-clock mode (no clock, no stage-cost
model).  Every rung is warmed, then 40 ticks are served at pipeline
depth 1 and 40 at depth 2 under ``TraceSentinel(compile_budget=0)``.  A
sample of each rung's depth-1 frames is re-run through the unbatched
pipeline (``run_frame``) on the host CPU device and must agree with it.

Four chips (``--chips 4``): 32 streams at capacity 32 on a ``data=4``
mesh, and the same scenes on a ``data=1`` mesh over the first chip in the
same process.  Every shard must seat streams and per-stream outputs must
agree with the one-shard run.  No other phase runs.

Agreement (``_compare``): equal proposal counts, and boxes paired within
``BOX_TOL_PX`` except where greedy NMS could have decided either way.

Any failed check raises.  Only when every check passed is the last line
of standard output one JSON object naming the device.  There is no CPU
fallback: on any other platform the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Box agreement between two runs of a frame, in pixels of the 96x320
# frame.  Box coordinates come from conv-feature refinements; TPU f32
# convolutions at default precision run a bf16 pass (8-bit significand),
# so they drift from the CPU's f32.  The largest drift measured on a v5e
# chip was 0.395 px over 1860 paired boxes; 1 px is 2.5x that and an
# eighth of the 8-px proposal-cell pitch, so a box from a neighbouring
# cell, another stream or other weights cannot pass.
BOX_TOL_PX = 1.0
# Both detectors keep boxes by greedy NMS at this IoU (``static_nms`` on
# the device, ``dynamic_nms`` on the host): a step function of the boxes.
NMS_IOU = 0.5
# An NMS flip is a boundary event (1.1% of the boxes on v5e); a systematic
# error would leave most boxes unpaired, so at least this share must pair.
MIN_PAIRED = 0.9

ONE_CHIP_STREAMS = 8       # capacity 8: one padded slot per stream
FLEET_STREAMS = 32         # capacity 32: 8 slots per shard at data=4
REF_PER_RUNG = 16          # depth-1 frames per rung re-run on the CPU

# (scenario, rain mm/h) per camera, cycled: the paper's city /
# residential / road split, with and without rain
STREAM_MIX = (("city", 0.0), ("residential", 25.0), ("road", 0.0),
              ("city", 60.0), ("residential", 0.0), ("road", 120.0),
              ("city", 10.0), ("road", 40.0))


def _scenes(n_streams: int, ticks: int) -> list[dict]:
    """Seeded scenes per tick: ``[{stream_id: Scene}]``, made up front so
    rendering never sits in a timed tick."""
    from repro.perception.data import SceneConfig, generate_scene

    cfgs = {f"cam{i:02d}": SceneConfig(*STREAM_MIX[i % len(STREAM_MIX)],
                                       seed=i)
            for i in range(n_streams)}
    return [{sid: generate_scene(cfg, t) for sid, cfg in cfgs.items()}
            for t in range(ticks)]


def _warm_budgets(sched, max_warms: int = 8) -> list[float]:
    """Warm ``sched`` and return one residual budget per ladder rung.

    The contract controller takes the best rung whose predicted tail fits,
    so it can only ever pick a rung predicted faster than every better
    rung.  Each ``warm()`` adds one measured full-capacity probe per rung
    to the cost model; warm until the model orders the ladder that way
    (one noisy probe can invert two rungs).  Rung k's budget then lies
    between its predicted tail and the fastest better rung's, so a stream
    given it settles on rung k."""
    from repro.anytime.cost import SceneFeatures

    feats = SceneFeatures(batch_size=float(sched.capacity), batched=True,
                          pipeline_depth=float(sched.depth))
    q = sched.ctl_cfg.quantile
    for _ in range(max_warms):
        sched.warm()
        tails = [sched.cost.quantile(r.name, feats, q) for r in sched.ladder]
        above = [min(tails[:k]) for k in range(1, len(tails) - 1)]
        if all(t < a for t, a in zip(tails[1:-1], above)):
            break
    else:
        raise AssertionError(
            f"after {max_warms} warm probes the predicted tails "
            f"{dict(zip((r.name for r in sched.ladder), tails))} still rank "
            f"a rung slower than a better one: no budget can select it")
    return ([100.0 * tails[0]]
            + [float(np.sqrt(t * a)) for t, a in zip(tails[1:-1], above)]
            + [tails[-1] / 100.0])


def _check_finite(out, what: str) -> None:
    if not (np.all(np.isfinite(out.boxes)) and np.isfinite(out.num_objects)
            and np.isfinite(out.num_proposals)):
        raise AssertionError(f"{what}: non-finite output")


def _nms_could_drop(box, kept) -> bool:
    """Whether greedy NMS could have suppressed ``box``, moved by up to
    ``BOX_TOL_PX``, in favour of one of ``kept``: an upper bound of their
    IoU over every such position exceeds ``NMS_IOU``."""
    if not len(kept):
        return False
    grow = BOX_TOL_PX * np.array([-1.0, -1.0, 1.0, 1.0])
    lo, hi = (box + grow)[:2], (box + grow)[2:]
    inter = np.prod(np.clip(np.minimum(hi, kept[:, 2:])
                            - np.maximum(lo, kept[:, :2]), 0.0, None), axis=1)
    area = np.prod(np.clip((box - grow)[2:] - (box - grow)[:2], 0.0, None))
    union = area + np.prod(kept[:, 2:] - kept[:, :2], axis=1) - inter
    return bool(np.any(inter / np.maximum(union, 1e-9) > NMS_IOU))


def _compare(got, want, what: str) -> tuple[float, int, int]:
    """One frame against another run of it: (largest paired box
    deviation, paired boxes, boxes), counting both runs' boxes.

    Proposal counts come from thresholded pooled luma and must match
    exactly.  Boxes pair one-to-one within ``BOX_TOL_PX``.  Which boxes
    are kept is greedy NMS over the boxes, so the runs may keep different
    boxes where an IoU sits at the threshold: a box only one run kept
    passes only where a box the other run kept could have suppressed it.
    """
    if got.num_proposals != want.num_proposals:
        raise AssertionError(f"{what}: {got.num_proposals} proposals != "
                             f"{want.num_proposals}")
    a, b = got.boxes.reshape(-1, 4), want.boxes.reshape(-1, 4)
    dist = np.max(np.abs(a[:, None] - b[None]), axis=-1)
    close = dist <= BOX_TOL_PX
    if max(close.sum(0).max(initial=0), close.sum(1).max(initial=0)) > 1:
        raise AssertionError(f"{what}: a box pairs with several boxes")
    for side, boxes, other, alone in (("served", a, b, ~close.any(1)),
                                      ("reference", b, a, ~close.any(0))):
        for box in boxes[alone]:
            if not _nms_could_drop(box, other):
                raise AssertionError(
                    f"{what}: box {box} kept only by the {side} run, and no "
                    f"box the other run kept could have suppressed it")
    return (float(dist[close].max(initial=0.0)), 2 * int(close.sum()),
            len(a) + len(b))


def _agreement(frames, label: str, log) -> float:
    """``_compare`` over ``(got, want, what)`` frames; raises unless at
    least ``MIN_PAIRED`` of all boxes pair.  Returns the largest paired
    box deviation."""
    worst, paired, boxes, flipped = 0.0, 0, 0, 0
    for got, want, what in frames:
        dev, p, n = _compare(got, want, what)
        worst, paired, boxes = max(worst, dev), paired + p, boxes + n
        flipped += p < n
    if paired < MIN_PAIRED * boxes:
        raise AssertionError(f"{label}: only {paired}/{boxes} boxes pair "
                             f"within {BOX_TOL_PX} px")
    log(f"{label}: {len(frames)} frames, proposal counts equal, "
        f"{paired}/{boxes} boxes paired within {BOX_TOL_PX} px (max "
        f"deviation {worst:.6g} px), {flipped} frames with an NMS flip")
    return worst


def _check_traces(sched, what: str) -> None:
    for name, eng in sched.engines.items():
        counts = (eng.trace_count, eng.assemble_trace_count,
                  eng.pack_trace_count, eng.update_trace_count)
        if counts != (1, 1, 1, 1):
            raise AssertionError(
                f"{what}: rung {name} traced step/assemble/pack/update "
                f"{counts} times, expected once each")


def _pct_ms(xs) -> tuple[float, float]:
    return (float(np.percentile(xs, 50)) * 1e3,
            float(np.percentile(xs, 99)) * 1e3)


def serve_one_chip(device, ticks: int = 40, log=print) -> dict:
    """The one-chip phase on ``device``; raises on any failed check."""
    import jax

    from repro.analysis.sentinel import TraceSentinel
    from repro.batched.scheduler import RungBucketScheduler
    from repro.perception.pipelines import run_frame
    from repro.scenarios.replay import replay_ladder

    ladder = replay_ladder()
    scenes = _scenes(ONE_CHIP_STREAMS, ticks)
    scheds = {}
    with jax.default_device(device):
        for depth in (1, 2):
            t0 = time.perf_counter()
            sched = RungBucketScheduler(ladder, capacity=ONE_CHIP_STREAMS,
                                        depth=depth)
            budgets = _warm_budgets(sched)
            log(f"depth {depth}: build + compile + warm "
                f"{time.perf_counter() - t0:.3f} s; budgets (ms) "
                f"{[round(b * 1e3, 4) for b in budgets]}")
            for i, sid in enumerate(scenes[0]):
                sched.add_stream(sid, budgets[i % len(budgets)])
            scheds[depth] = sched

        served = {}
        for depth, sched in scheds.items():
            frames = {r.name: 0 for r in ladder}
            tick_s, results = [], []
            with TraceSentinel(compile_budget=0) as sent:
                for t in range(ticks):
                    t0 = time.perf_counter()
                    res = sched.tick(scenes[t])
                    tick_s.append(time.perf_counter() - t0)
                    results.append((t, res))
                results.append((None, sched.flush()))
            for t, res in results:
                for row in res.rows:
                    frames[row["rung"]] += 1
                    _check_finite(res.outputs[row["stream"]],
                                  f"depth {depth} {row['rung']} "
                                  f"{row['stream']}")
            _check_traces(sched, f"depth {depth}")
            idle = [name for name, n in frames.items() if n == 0]
            if idle:
                raise AssertionError(
                    f"depth {depth}: rungs {idle} served no frame "
                    f"(frames per rung {frames})")
            p50, p99 = _pct_ms(tick_s)
            log(f"depth {depth}: {sent.report().render()}")
            log(f"depth {depth}: frames per rung {frames}")
            log(f"depth {depth}: tick wall p50 {p50:.3f} ms, "
                f"p99 {p99:.3f} ms over {ticks} ticks")
            served[depth] = results

    # the plain reference: each sampled depth-1 frame again, unbatched,
    # through the same rung's pipeline on the host CPU device
    cpu = jax.devices("cpu")[0]
    samples: dict[str, list] = {r.name: [] for r in ladder}
    for t, res in served[1][:-1]:            # the last entry is the flush
        for row in res.rows:
            samples[row["rung"]].append(
                (t, row["stream"], res.outputs[row["stream"]]))
    frames = []
    with jax.default_device(cpu):
        for rung in ladder:
            ref = rung.build()
            got = samples[rung.name]
            pick = np.linspace(0, len(got) - 1,
                               min(REF_PER_RUNG, len(got))).astype(int)
            for t, sid, out in (got[i] for i in sorted(set(pick))):
                frames.append((out, run_frame(ref, scenes[t][sid])[1],
                               f"{rung.name} tick {t} {sid} vs CPU"))
    return {"max_box_dev_px": _agreement(frames, "reference", log)}


def serve_fleet(devices, ticks: int = 16, log=print) -> dict:
    """The four-chip phase: a ``data=4`` fleet against the same scenes on
    one chip, in one process; raises on any failed check."""
    from repro.analysis.sentinel import TraceSentinel
    from repro.batched.scheduler import RungBucketScheduler
    from repro.distributed.sharding import data_shards
    from repro.launch.mesh import make_local_mesh
    from repro.scenarios.replay import replay_ladder

    if len(devices) < 4:
        raise AssertionError(f"the fleet phase needs 4 devices, "
                             f"found {len(devices)}")
    meshes = {"data=4": make_local_mesh(data=4, devices=devices[:4]),
              "data=1": make_local_mesh(data=1, devices=devices[:1])}
    if data_shards(meshes["data=4"]) != 4:
        raise AssertionError("the data=4 mesh shrank to "
                             f"{data_shards(meshes['data=4'])} shards")
    ladder = replay_ladder()
    top, floor = ladder.top.name, ladder.floor.name
    scenes = _scenes(FLEET_STREAMS, ticks)
    scheds = {}
    for label, mesh in meshes.items():
        t0 = time.perf_counter()
        sched = RungBucketScheduler(ladder, capacity=FLEET_STREAMS,
                                    mesh=mesh)
        sched.warm()
        log(f"{label}: build + compile + warm "
            f"{time.perf_counter() - t0:.3f} s")
        # budgets that pin a rung whatever the timing: an unbounded one
        # holds the top rung, a zero one finds no fit and takes the floor,
        # so both meshes serve every stream on the same rung every tick
        for i, sid in enumerate(scenes[0]):
            sched.add_stream(sid, 60.0 if i % 2 == 0 else 0.0)
        scheds[label] = sched

    outs = {label: [] for label in scheds}
    tick_s = {label: [] for label in scheds}
    with TraceSentinel(compile_budget=0) as sent:
        for t in range(ticks):
            for label, sched in scheds.items():
                t0 = time.perf_counter()
                res = sched.tick(scenes[t])
                tick_s[label].append(time.perf_counter() - t0)
                outs[label].append(res.outputs)
    log(f"fleet: {sent.report().render()}")
    for label, sched in scheds.items():
        _check_traces(sched, label)
        p50, p99 = _pct_ms(tick_s[label])
        log(f"{label}: tick wall p50 {p50:.3f} ms, p99 {p99:.3f} ms "
            f"over {ticks} ticks")
    for rung in (top, floor):
        occ = scheds["data=4"].engines[rung].shard_occupancy()
        log(f"data=4: {rung} shard occupancy {occ}")
        if len(occ) != 4 or min(occ) == 0:
            raise AssertionError(f"data=4 {rung}: shard occupancy {occ}")

    frames = []
    for t in range(ticks):
        four, one = outs["data=4"][t], outs["data=1"][t]
        if set(four) != set(one) or len(four) != FLEET_STREAMS:
            raise AssertionError(f"tick {t}: served streams differ")
        for sid in sorted(four):
            _check_finite(four[sid], f"data=4 tick {t} {sid}")
            frames.append((four[sid], one[sid],
                           f"tick {t} {sid} data=4 vs data=1"))
    return {"max_box_dev_px": _agreement(
        frames, f"fleet: {FLEET_STREAMS} streams x {ticks} ticks, data=4 vs "
                f"data=1", log)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data=4 fleet phase and its "
                         "1-chip comparison")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import configure_compile_cache

    cache = configure_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{devices[0].platform!r}); this check runs only on a TPU",
              file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    print(f"device_kind {kind!r}, {len(devices)} device(s); compile cache "
          f"{cache}", flush=True)
    if args.chips == 4:
        serve_fleet(devices)
    else:
        serve_one_chip(devices[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
