#!/usr/bin/env python3
"""Benchmark of the camera-fleet serving path, ``RungBucketScheduler.tick``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a TPU host.  The cell names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``) through ``BENCHMARK.json``; per-layer
metrics are read by ``bench/metrics/<metric>.py`` and the limits that
decide ``correct`` are in ``bench/limits/<cell>.json``.  The
configuration names its model module (``"model"``: ``build_scheduler``,
``flops_per_frame``, ``bytes_per_step``) and its plain reference
(``"reference"``: ``Reference``) by path.  Nothing here names a cell, a
configuration, a model, a mix or a metric.

A run: places the compile cache as the program's entry points do, refuses
any platform but a TPU, builds the scheduler through the model module
(weights from one fixed key, ``bench/detection.py``), compiles the rung
engines the traffic uses, renders a scene pool per stream from
``--seed`` at the configuration's frame, serves warm-up ticks, then
serves ``--seconds`` of traffic under ``TraceSentinel(compile_budget=0)``.
Once the window has closed it reads the device memory peak, frees the
scheduler, re-runs a seeded sample of the served frames through the
configuration's reference and compares (``bench/compare.py``).  The last
line of standard output is one JSON object; the numbers compared, each
with its limit, are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import model_flops  # noqa: E402
from bench.compare import compare  # noqa: E402
from bench.quality import frame_quality  # noqa: E402
from bench.scenes import fleet_pools  # noqa: E402
from bench.trace_reduce import short_op  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
UNBOUNDED_S = 3600.0          # a budget every rung fits: pins the top rung
TRACE_S = 2.0                 # traced slice after the window, --trace 1
MIN_PER_RUNG = 16             # sampled frames per served rung, at least


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------- spec
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_file(path: Path, mod_name: str):
    """The Python file at ``path``, executed as a module of its own."""
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# what a configuration's ``model`` and ``reference`` modules must define
PARTS = {"model": ("build_scheduler", "flops_per_frame", "bytes_per_step"),
         "reference": ("Reference",)}


def load_part(conf: str, cfg: dict, part: str, root: Path):
    """The ``part`` module (``model`` or ``reference``) that configuration
    ``conf`` names by its path under ``bench/``; anything missing is an
    error that names the configuration."""
    if part not in cfg:
        raise ValueError(f"configuration {conf!r} names no {part!r} module")
    path = (root / cfg[part]).resolve()
    if (root / "bench").resolve() not in path.parents or not path.is_file():
        raise ValueError(f"configuration {conf!r}: {part} module "
                         f"{cfg[part]!r} is no file under bench/")
    mod = load_file(path, f"bench_{part}_" + "".join(
        c if c.isalnum() else "_" for c in conf))
    missing = [n for n in PARTS[part] if not callable(getattr(mod, n, None))]
    if missing:
        raise ValueError(f"configuration {conf!r}: {part} module "
                         f"{cfg[part]!r} defines no {', '.join(missing)}")
    return mod


def load_cell(name: str, root: Path | None = None) -> dict:
    """The cell's entry in BENCHMARK.json with its configuration, model and
    reference modules, traffic mix, limits and metric entries resolved by
    name."""
    root = ROOT if root is None else root
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def reports(metric: dict, e2e: set) -> bool:
        if "workloads" in metric:
            return name in metric["workloads"]
        return metric.get("moves", metric["name"]) in e2e

    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    cfg = load_json(root / conf["file"])
    return {
        "name": name,
        "chips": cell["chips"],
        "config": cfg,
        "model": load_part(conf["name"], cfg, "model", root),
        "reference": load_part(conf["name"], cfg, "reference", root),
        "traffic": load_json(root / "bench" / "traffic"
                             / f"{cell['traffic']}.json"),
        "limits": load_json(root / "bench" / "limits" / f"{name}.json"),
        "end_to_end": e2e,
        "per_layer": [m for m in spec["per_layer"] if reports(m, names)],
    }


def load_reader(metric: str, root: Path | None = None):
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    root = ROOT if root is None else root
    path = root / "bench" / "metrics" / f"{metric}.py"
    return load_file(path, "bench_metric_" + "".join(
        c if c.isalnum() else "_" for c in metric)).read


# ---------------------------------------------------------------- records
@dataclasses.dataclass
class Step:
    """One call into a rung engine."""

    rung: str
    n_frames: int                 # frames submitted (0 for a flush)
    stages: dict                  # StageRecord stages of what it drained


@dataclasses.dataclass
class Frame:
    stream: int
    scene: int                    # index into the stream's pool
    due: float
    rung: str = ""
    done: float = math.inf        # end of the engine call that drained it
    out: object = None            # the program's FrameOutput


@dataclasses.dataclass
class Tick:
    due: float
    t0: float
    t1: float
    steps: list


@dataclasses.dataclass
class Run:
    """What a run recorded; metric readers take this."""

    cell: dict
    seconds: float
    t_open: float
    t_close: float
    ticks: list
    frames: list
    ladder: list                  # rung names, best first
    chips: int
    peak: dict
    pools: list = None            # per stream: the benchmark's scenes
    traced_ticks: list = None     # ticks of the traced slice, --trace 1
    trace: object = None          # trace_reduce.TraceSummary, traced runs

    @property
    def config(self) -> dict:
        return self.cell["config"]

    @property
    def model(self):
        """The configuration's model module."""
        return self.cell["model"]

    def served(self) -> list:
        """Frames whose host post finished inside the window."""
        return [f for f in self.frames if f.done <= self.t_close]


# ---------------------------------------------------------------- device
def devices_for(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a "
                     f"TPU; this benchmark runs only on a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


# ---------------------------------------------------------------- build
def rungs_used(traffic: dict, ladder: list) -> list:
    policy = traffic["budget"]
    return {"top": ladder[:1], "floor": ladder[-1:],
            "split": [ladder[0], ladder[-1]]}[policy]


def stream_budget(traffic: dict, i: int) -> float:
    policy = traffic["budget"]
    if policy == "top":
        return UNBOUNDED_S
    if policy == "floor":
        return 0.0
    return UNBOUNDED_S if i % 2 == 0 else 0.0


def build(cell: dict, devs):
    """The scheduler the configuration's model module builds, its engines
    compiled for the rungs the traffic uses."""
    from bench.detection import weights_key
    from repro.launch.mesh import make_local_mesh

    cfg, traffic = cell["config"], cell["traffic"]
    mesh = (make_local_mesh(data=cfg["mesh_data"], devices=devs)
            if cfg["mesh_data"] > 1 else None)
    sched = cell["model"].build_scheduler(cfg, weights_key(), mesh)
    for name in rungs_used(traffic, cfg["ladder"]):
        sched.engines[name].compile()
    for i in range(cfg["streams"]):
        sched.add_stream(f"cam{i:02d}", stream_budget(traffic, i))
    return sched


def scene_pools(cell: dict, seed: int) -> tuple[list, list]:
    """Per stream: the benchmark's scenes, and the program's ``Scene``
    objects built from them (image, boxes and labels only)."""
    from repro.perception.data import Scene

    cfg, traffic = cell["config"], cell["traffic"]
    pools = fleet_pools(seed, cfg["streams"], traffic["mix"], traffic["pool"],
                        cfg["frame"])
    lanes = np.zeros((0, 2), np.float32)
    program = [[Scene(image=s.image, boxes=s.boxes, lane_pixels=lanes,
                      scenario=s.scenario, rain=s.rain) for s in pool]
               for pool in pools]
    return pools, program


class Spy:
    """Host spans around each call into a rung engine, from outside."""

    def __init__(self, sched, annotate) -> None:
        self.steps: list = []
        self.drained: list = []       # (rung, t1, outputs, payload)
        for rung, eng in sched.engines.items():
            self._wrap(eng, rung, annotate)

    def _wrap(self, eng, rung: str, annotate) -> None:
        tick, flush = eng.tick, eng.flush
        label = f"bench.step.{rung}"

        def spied_tick(frames, payload):
            with annotate(label):
                rec, outs, echoed = tick(frames, payload=payload)
            t1 = time.perf_counter()
            self.steps.append(Step(rung, len(frames),
                                   dict(rec.stages) if rec else {}))
            if echoed:
                self.drained.append((rung, t1, outs, echoed))
            return rec, outs, echoed

        def spied_flush():
            with annotate(label):
                out = flush()
            t1 = time.perf_counter()
            for rec, outs, echoed in out:
                self.steps.append(Step(rung, 0, dict(rec.stages)))
                self.drained.append((rung, t1, outs, echoed))
            return out

        eng.tick = spied_tick
        eng.flush = spied_flush


# ---------------------------------------------------------------- serve
@dataclasses.dataclass
class Served:
    """What ``serve`` returns."""

    setup_s: float
    t_open: float
    t_close: float
    ticks: list
    frames: list
    compiles: int
    traced_ticks: list            # ticks of the traced slice, if any


def serve(sched, cell: dict, program_pools: list, seconds: float,
          annotate, sentinel_cls, trace=None) -> Served:
    """Warm-up ticks, the measured window, and with ``trace`` (a pair of
    start and stop callables) a traced slice of ``TRACE_S`` seconds of the
    same traffic after the window has closed."""
    traffic = cell["traffic"]
    n = len(program_pools)
    pool = len(program_pools[0])
    sids = [f"cam{i:02d}" for i in range(n)]
    spy = Spy(sched, annotate)
    key_of: dict = {}             # id(program scene) -> Frame in flight
    k = 0                         # ticks served so far, warm-up included

    def one_tick(due: float, ticks: list, frames: list):
        nonlocal k
        with annotate("bench.handoff"):
            scenes = {sids[i]: program_pools[i][k % pool] for i in range(n)}
        t0 = time.perf_counter()
        for i in range(n):
            f = Frame(i, k % pool, due)
            frames.append(f)
            key_of[id(scenes[sids[i]])] = f
        first = len(spy.steps)
        with annotate("bench.tick"):
            sched.tick(scenes)
        ticks.append(Tick(due, t0, time.perf_counter(), spy.steps[first:]))
        k += 1
        settle()

    def settle():
        """Attach drained outputs to their frames."""
        for rung, t1, outs, echoed in spy.drained:
            for sid, (scene, _budget) in echoed.items():
                f = key_of.pop(id(scene), None)
                if f is not None:
                    f.rung, f.done, f.out = rung, t1, outs[sid]
        spy.drained.clear()

    def window(length: float, ticks: list, frames: list):
        """Serve ticks back to back for ``length`` seconds, each due when
        it starts; then drain."""
        t_open = time.perf_counter()
        t_close = t_open + length
        while (now := time.perf_counter()) < t_close:
            one_tick(now, ticks, frames)
        sched.flush()
        settle()
        return t_open, t_close

    for _ in range(traffic["warm_ticks"]):
        one_tick(time.perf_counter(), [], [])
    sched.flush()
    settle()
    spy.steps.clear()
    key_of.clear()
    gc.collect()
    gc.freeze()

    setup_s = time.perf_counter() - T_START
    ticks, frames, traced = [], [], []
    with sentinel_cls(compile_budget=0) as sent:
        t_open, t_close = window(seconds, ticks, frames)
        if trace is not None:
            start, stop = trace
            start()
            with annotate("bench.traced"):
                window(TRACE_S, traced, [])
            stop()
    return Served(setup_s, t_open, t_close, ticks, frames,
                  sent.report().compiles, traced)


# ---------------------------------------------------------------- metrics
def pct(values, q: float) -> float:
    """The q-th percentile, where an infinite value (a frame that never
    completed) lies beyond every finite one."""
    xs = np.sort(np.asarray(values, np.float64))
    if not len(xs):
        return math.nan
    return float(np.percentile(xs, q, method="higher"))


def end_to_end(run: Run, setup_s: float) -> dict:
    lat = [(f.done - f.due) * 1e3 for f in run.frames]
    return {
        "frames_per_s": (len(run.served()) / run.seconds, "frames/s"),
        "frame_p99_ms": (pct(lat, 99), "ms"),
        "setup_s": (setup_s, "s"),
    }


def run_gt(run: Run, f: Frame):
    return run.pools[f.stream][f.scene].boxes


def describe(run: Run) -> str:
    """Frames served and mean quality per rung, for the log."""
    parts = []
    for rung in run.ladder:
        fs = [f for f in run.frames if f.rung == rung]
        qs = [q for f in fs if (q := frame_quality(run_gt(run, f),
                                                   f.out.boxes)) is not None]
        if fs:
            parts.append(f"{rung}: {len(fs)} frames, quality "
                         f"{100 * np.mean(qs) if qs else math.nan:.2f}%")
    return "; ".join(parts)


# ---------------------------------------------------------------- check
def sample(run: Run, seed: int, n: int) -> list:
    """A seeded sample of the frames served in the window, every rung
    served represented."""
    rng = np.random.default_rng([seed, 1])
    by_rung: dict = {}
    for f in run.frames:
        if f.out is not None:
            by_rung.setdefault(f.rung, []).append(f)
    total = sum(len(v) for v in by_rung.values())
    picked = []
    for rung in sorted(by_rung):
        fs = by_rung[rung]
        k = min(len(fs), max(MIN_PER_RUNG, round(n * len(fs) / total)))
        picked += [fs[i] for i in sorted(rng.choice(len(fs), k,
                                                    replace=False))]
    return picked


def check(run: Run, frames: list, ref) -> dict:
    """The numbers of ``compare`` for ``frames`` against ``ref``, an
    instance of the configuration's ``Reference``."""
    rows = []
    for rung in sorted({f.rung for f in frames}):
        fs = [f for f in frames if f.rung == rung]
        images = np.stack([run.pools[f.stream][f.scene].image for f in fs])
        kind = run.config["rungs"][rung]["kind"]
        for f, det in zip(fs, ref.detect(rung, images)):
            rows.append((kind, f.out.boxes, f.out.num_proposals, det))
    return compare(rows, float(run.config["frame"][1]))


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    checks = {}
    ok = True
    for name, limit in limits["limits"].items():
        v = numbers.get(name)
        if v is None:
            continue
        checks[name] = {"value": v, "limit": limit}
        ok &= v <= limit
    return ok, checks


# ---------------------------------------------------------------- trace
def layer_metrics(run: Run) -> dict:
    out = {}
    for m in run.cell["per_layer"]:
        v = load_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------- main
def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = load_cell(args.workload)

    import contextlib

    import jax

    from repro.analysis.sentinel import TraceSentinel
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    try:
        devs = devices_for(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    peak = model_flops.peaks(devs[0].device_kind)
    t_init = time.perf_counter() - T_START

    from bench.detection import weights_key

    if args.trace:
        def annotate(name):
            return jax.profiler.TraceAnnotation(name)
    else:
        def annotate(name):
            return contextlib.nullcontext()

    t = time.perf_counter()
    sched = build(cell, devs)
    t_build = time.perf_counter() - t
    t = time.perf_counter()
    pools, program_pools = scene_pools(cell, args.seed)
    t_pools = time.perf_counter() - t

    def start_trace():
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    served = serve(sched, cell, program_pools, args.seconds, annotate,
                   TraceSentinel,
                   trace=(start_trace, jax.profiler.stop_trace)
                   if args.trace else None)
    mem = memory_peak(devs)
    ladder = list(sched.engines)
    del sched, program_pools
    gc.unfreeze()
    gc.collect()

    run = Run(cell=cell, seconds=args.seconds, t_open=served.t_open,
              t_close=served.t_close, ticks=served.ticks,
              frames=served.frames, ladder=ladder, chips=cell["chips"],
              peak=peak, pools=pools, traced_ticks=served.traced_ticks)
    if args.trace:
        from bench.trace_reduce import find_xplane, reduce_file

        run.trace = reduce_file(find_xplane(str(TRACE_DIR)))

    setup_s, frames = served.setup_s, served.frames
    print(f"bench: set-up {setup_s:.3f} s (imports and TPU runtime "
          f"{t_init:.3f} s, build {t_build:.3f} s, scenes {t_pools:.3f} s); "
          f"{len(run.ticks)} ticks; {describe(run)}",
          file=sys.stderr)
    picked = sample(run, args.seed, cell["traffic"]["sample"])
    numbers = check(run, picked,
                    cell["reference"].Reference(run.config, weights_key()))
    ok, checks = verdict(numbers, cell["limits"])
    attempted = len(frames)
    failed = sum(f.out is None for f in frames)
    ok &= served.compiles == 0 and failed == 0
    checks["window_compiles"] = {"value": served.compiles, "limit": 0}
    checks["frames_lost"] = {"value": failed, "limit": 0}

    if args.trace:
        metrics = layer_metrics(run)
    else:
        e2e = end_to_end(run, setup_s)
        # a tail that reaches a frame never served is beyond every limit;
        # JSON has no infinity, so it reads as 1e12
        metrics = {m["name"]: {"value": min(e2e[m["name"]][0], 1e12),
                               "unit": e2e[m["name"]][1]}
                   for m in cell["end_to_end"]}
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": [[short_op(k), v] for k, v in run.trace.top_ops()],
            "idle_gaps": [list(kv) for kv in run.trace.top_gaps()]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
