"""Pipelined device-resident executor: correctness of the depth-k
pipeline (bitwise identity with the synchronous path, staleness
semantics, slot churn mid-pipeline, single-trace invariants, dirty-slot
H2D accounting, the one-transfer all-dirty upload), the
pipelined-latency cost-model mode, scheduler depth wiring, and the
golden byte-identity regression for the refactored sync engine.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import TraceSentinel
from repro.anytime import Rung, calibrate
from repro.anytime.controller import ContractController, ControllerConfig
from repro.anytime.cost import RungCostModel, SceneFeatures
from repro.batched import BatchedPerceptionEngine, PipelinedExecutor, RungBucketScheduler
from repro.batched.engine import fused_batch_program
from repro.core.timing import StageRecord
from repro.obs import Observatory
from repro.perception import SceneConfig, build_pipeline, generate_scene

CITY = SceneConfig("city", seed=33)
GOLDEN_DIR = Path(__file__).parent / "golden"


def _scenes(n_ticks, n_streams, seed0=200):
    return [
        [generate_scene(SceneConfig("city", seed=seed0 + s), t + 1)
         for s in range(n_streams)]
        for t in range(n_ticks)
    ]


def _outputs_equal(a, b):
    assert a.num_objects == b.num_objects
    assert a.num_proposals == b.num_proposals
    assert a.boxes.shape == b.boxes.shape
    assert np.array_equal(a.boxes, b.boxes), "boxes differ bitwise"


# ------------------------------------------ bitwise depth-k == depth-1 ----
@pytest.mark.parametrize("depth", [2, 3])
def test_depth_k_outputs_bitwise_identical_to_depth_1(depth):
    """The pipeline reorders *when* work happens, never *what* is
    computed: the assemble pass is exact element selection and the fused
    step is the identical XLA program, so every frame's outputs must be
    bitwise identical to the synchronous engine's, in submission order."""
    n_streams, n_ticks = 3, 5
    scenes = _scenes(n_ticks, n_streams)
    results = {}
    for d in (1, depth):
        built = build_pipeline("two_stage")
        eng = BatchedPerceptionEngine(built, capacity=n_streams, depth=d)
        for s in range(n_streams):
            eng.join(f"cam{s}")
        seq = {f"cam{s}": [] for s in range(n_streams)}
        for t in range(n_ticks):
            _, outs = eng.tick({f"cam{s}": scenes[t][s].image
                                for s in range(n_streams)})
            for sid, out in outs.items():
                seq[sid].append(out)
        for _, outs, _ in eng.flush():
            for sid, out in outs.items():
                seq[sid].append(out)
        results[d] = seq
    for sid in results[1]:
        assert len(results[depth][sid]) == n_ticks
        for a, b in zip(results[1][sid], results[depth][sid]):
            _outputs_equal(a, b)


def test_pipeline_fills_then_returns_stale_results():
    built = build_pipeline("early_exit")
    eng = BatchedPerceptionEngine(built, capacity=2, depth=2)
    eng.join("a")
    img0 = generate_scene(CITY, 1).image
    img1 = generate_scene(CITY, 2).image
    rec, outs = eng.tick({"a": img0})
    assert rec is None and outs == {}          # filling: nothing to drain
    assert eng.in_flight == 1
    rec, outs = eng.tick({"a": img1})
    assert rec is not None and set(outs) == {"a"}
    assert rec.meta["staleness_ticks"] == 1.0  # these are tick-0 results
    assert rec.meta["frame_latency_s"] > 0.0
    # drain the tail: exactly one frame still in the pipe
    tail = eng.flush()
    assert len(tail) == 1 and set(tail[0][1]) == {"a"}
    assert eng.in_flight == 0
    # engine accounting counts completed frames only
    assert eng.ticks == 2 and len(eng.tick_log) == 2


def test_payload_echo_pairs_results_with_their_tick():
    built = build_pipeline("early_exit")
    eng = BatchedPerceptionEngine(built, capacity=1, depth=2)
    eng.join("a")
    img = generate_scene(CITY, 1).image
    rec, outs, echoed = eng.tick({"a": img}, payload="tick0")
    assert rec is None and echoed is None
    rec, outs, echoed = eng.tick({"a": img}, payload="tick1")
    assert echoed == "tick0"                   # results are one tick stale
    (_, _, echoed2), = eng.flush()
    assert echoed2 == "tick1"


def test_join_leave_mid_pipeline_drains_cleanly():
    """Slot churn while frames are in flight: results stay attributed to
    the submission-time streams, later occupants of a slot never inherit
    them, and nothing retraces."""
    built = build_pipeline("early_exit")
    eng = BatchedPerceptionEngine(built, capacity=3, depth=2)
    img = generate_scene(CITY, 1).image
    eng.compile()                              # warmup outside the sentinel
    with TraceSentinel(compile_budget=0, transfer_guard="disallow"):
        eng.join("a")
        eng.join("b")
        eng.tick({"a": img, "b": img})         # in flight: {a, b}
        eng.join("c")                          # join mid-pipeline
        rec, outs = eng.tick({"a": img, "b": img, "c": img})
        assert set(outs) == {"a", "b"}         # drained tick predates c
        eng.leave("b")                         # leave with frame in flight
        tail = eng.flush()
    assert len(tail) == 1
    assert set(tail[0][1]) == {"a", "b", "c"}  # b's in-flight result drains
    # b left: its output is returned to the caller but no longer
    # attributed to a seated stream
    assert "b" not in eng.active
    assert eng.trace_count == 1
    assert eng.assemble_trace_count == 1
    assert eng.update_trace_count == 1
    # a rejoin after full churn still works without compile or retrace
    with TraceSentinel(compile_budget=0, transfer_guard="disallow"):
        eng.join("d")
        rec, outs = eng.tick({"a": img, "d": img})
        eng.flush()


def test_h2d_bytes_are_dirty_slots_only():
    built = build_pipeline("early_exit")
    eng = BatchedPerceptionEngine(built, capacity=4, depth=1)
    frame_bytes = int(np.prod(eng.image_shape)) * 4
    for sid in ("a", "b", "c"):
        eng.join(sid)
    img = generate_scene(CITY, 1).image
    rec, _ = eng.tick({"a": img, "b": img})    # only 2 of 4 slots dirty
    assert rec.meta["h2d_bytes"] == 2 * frame_bytes
    rec, _ = eng.tick({"c": img})
    assert rec.meta["h2d_bytes"] == 1 * frame_bytes


def test_pipelined_reports_use_completion_latency_and_serving_span():
    """aggregate_report/per_stream_report must not sell the host residual
    as throughput or latency on a pipelined engine: frames/s comes from
    the observed serving span, percentiles from submit→drain latency."""
    built = build_pipeline("early_exit")
    eng = BatchedPerceptionEngine(built, capacity=2, depth=2)
    eng.join("a")
    img = generate_scene(CITY, 1).image
    for t in range(4):
        eng.tick({"a": img})
    eng.flush()
    agg = eng.aggregate_report()
    host_residual_fps = agg["frames"] / sum(l for _, l in eng.tick_log)
    assert agg["frames"] == 4
    # span-based throughput is necessarily <= the residual-sum fiction
    assert agg["frames_per_s"] <= host_residual_fps
    assert np.isfinite(agg["frames_per_s"]) and agg["frames_per_s"] > 0
    # per-frame latency covers the whole residence in the pipe
    frame_lats = eng.recorder.meta_series("frame_latency_s")
    assert (frame_lats >= eng.recorder.end_to_end_series() - 1e-9).all()
    rows = eng.per_stream_report()
    assert rows[0]["p99_s"] == pytest.approx(
        float(np.percentile(frame_lats, 99)))


def test_stage_cost_requires_sync_depth():
    with pytest.raises(ValueError, match="depth-1"):
        BatchedPerceptionEngine(build_pipeline("early_exit"), capacity=2,
                                depth=2, stage_cost=lambda s, b, w: 0.0)


def test_flush_is_empty_on_sync_engine():
    eng = BatchedPerceptionEngine(build_pipeline("early_exit"), capacity=1)
    eng.join("a")
    eng.tick({"a": generate_scene(CITY, 1).image})
    assert eng.flush() == [] and eng.in_flight == 0


# ------------------------------------------------ executor unit level -----
def test_executor_validates_and_guards():
    step = lambda raw: raw.sum(axis=(1, 2, 3))
    with pytest.raises(ValueError, match="depth"):
        PipelinedExecutor(step, 2, (8, 8, 3), depth=0)
    ex = PipelinedExecutor(step, 2, (8, 8, 3), depth=2)
    with pytest.raises(RuntimeError, match="empty pipeline"):
        ex.drain()
    with pytest.raises(IndexError):
        ex.set_slot(5, None)
    with pytest.raises(IndexError):
        ex.submit({7: np.zeros((8, 8, 3), np.float32)})
    # wrong-shaped frames must raise, not silently retrace the step at
    # the wrong resolution (a full-occupancy submit never touches the
    # resident raw, so nothing else would catch it)
    with pytest.raises(ValueError, match="shape"):
        ex.submit({0: np.zeros((16, 16, 3), np.float32),
                   1: np.zeros((16, 16, 3), np.float32)})
    assert ex.pending == 0 and ex.step_traces == 0


def test_executor_pipeline_order_and_staleness():
    step = lambda raw: raw.sum(axis=(1, 2, 3))
    ex = PipelinedExecutor(step, 1, (4, 4, 3), depth=3)
    for i in range(3):
        ex.submit({0: np.full((4, 4, 3), float(i), np.float32)},
                  payload=i)
    assert ex.ready() and ex.pending == 3
    drains = ex.flush()
    assert [d.payload for d in drains] == [0, 1, 2]   # oldest first
    assert [d.seq for d in drains] == [0, 1, 2]
    assert drains[0].staleness == 2                    # waited out 2 submits
    # the step saw each tick's slot content
    assert [float(d.host[0]) for d in drains] == [0.0, 48.0, 96.0]


# ------------------------------------- all-dirty upload: one transfer -----
def _count_device_puts(monkeypatch) -> list:
    """Record every ``jax.device_put`` the executor issues from here on."""
    calls = []
    real = jax.device_put

    def counting(x, *args, **kwargs):
        calls.append(x)
        return real(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", counting)
    return calls


def _trees_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _rung_executor(capacity, image_shape, depth=1, name="early_exit"):
    built = build_pipeline(name)
    return PipelinedExecutor(fused_batch_program(built), capacity,
                             image_shape, depth=depth)


def test_all_dirty_submit_is_one_transfer(monkeypatch):
    """Every slot dirty: the frames cross in ONE device_put of the
    stacked batch; a partial tick still puts each dirty frame and the
    mask.  Bytes are the dirty frames' either way."""
    ex = PipelinedExecutor(lambda raw: raw.sum(axis=(1, 2, 3)), 4,
                           (8, 8, 3))
    ex.obs = Observatory()
    ex.warmup()
    calls = _count_device_puts(monkeypatch)
    ex.submit({s: np.full((8, 8, 3), s, np.float32) for s in range(4)})
    assert len(calls) == 1
    assert calls[0].shape == (4, 8, 8, 3)
    counters = ex.obs.tracer.counters()
    assert counters["h2d_transfers"] == 1
    assert counters["h2d_bytes"] == 4 * ex.frame_bytes
    drained = ex.drain()
    assert drained.h2d_bytes == 4 * ex.frame_bytes
    assert [float(v) for v in drained.host] == [0.0, 192.0, 384.0, 576.0]

    calls.clear()
    ex.submit({1: np.full((8, 8, 3), 5, np.float32),
               3: np.full((8, 8, 3), 7, np.float32)})
    assert len(calls) == 3                       # two frames + the mask
    after = ex.obs.tracer.counters()
    assert after["h2d_transfers"] - counters["h2d_transfers"] == 3
    assert after["h2d_bytes"] - counters["h2d_bytes"] == 2 * ex.frame_bytes
    drained = ex.drain()
    assert drained.h2d_bytes == 2 * ex.frame_bytes
    # clean slots keep the all-dirty tick's resident frames
    assert [float(v) for v in drained.host] == [0.0, 960.0, 384.0, 1344.0]


def test_all_dirty_and_partial_paths_give_bitwise_equal_outputs():
    """The same frames through one all-dirty submit and through two
    partial submits leave the same resident batch, so the rung step's
    whole output tree is bitwise equal."""
    frames = [s.image for s in _scenes(1, 4)[0]]
    ex = _rung_executor(4, frames[0].shape, name="two_stage")
    ex.submit(dict(enumerate(frames)))
    whole = ex.drain().host
    ex.reset()
    ex.submit({0: frames[0], 2: frames[2]})
    ex.drain()
    ex.submit({1: frames[1], 3: frames[3]})
    assert _trees_equal(ex.drain().host, whole)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_all_dirty_tick_owns_its_bytes_at_depth(depth):
    """Distinct frames each tick, from host buffers the caller rewrites
    right after every submit: the outputs at any depth equal a depth-1
    run on fresh arrays, so no in-flight or aliased upload can see a
    later tick's (or a poisoned) frame."""
    n_ticks, cap = 4, 2
    scenes = _scenes(n_ticks, cap, seed0=310)
    shape = scenes[0][0].image.shape
    ref = _rung_executor(cap, shape)
    want = []
    for t in range(n_ticks):
        ref.submit({s: scenes[t][s].image.copy() for s in range(cap)})
        want.append(ref.drain().host)
    ex = _rung_executor(cap, shape, depth=depth)
    bufs = [np.empty(shape, np.float32) for _ in range(cap)]
    got = []
    for t in range(n_ticks):
        for s in range(cap):
            np.copyto(bufs[s], scenes[t][s].image)
        ex.submit(dict(enumerate(bufs)))
        for buf in bufs:
            buf.fill(np.nan)
        if ex.ready():
            got.append(ex.drain().host)
    got += [d.host for d in ex.flush()]
    assert len(got) == n_ticks
    for a, b in zip(got, want):
        assert _trees_equal(a, b)


def test_staging_batch_waits_for_the_step_still_reading_it():
    """A staging batch comes round again while the step over it has not
    yet read it (the step reads its batch only after a slow loop): the
    submit that rewrites it waits for that step, so each drained tick
    sums its own frames.  On the CPU the put may alias the staging
    batch, so rewriting it early would hand the running step a later
    tick's frames."""
    def step(raw):
        m = jnp.full((256, 256), 1e-3, jnp.float32)
        slow = jax.lax.fori_loop(0, 400, lambda i, a: jnp.tanh(a @ m), m)
        return (raw + slow[0, 0] * 0.0).sum(axis=(1, 2, 3))

    ex = PipelinedExecutor(step, 2, (4, 4, 3))
    ex.warmup()
    ticks = [{s: np.full((4, 4, 3), 10 * t + s, np.float32)
              for s in range(2)} for t in range(ex.depth + 2)]
    for frames in ticks:              # the last submit reuses the first batch
        ex.submit(frames)
    got = [[float(v) for v in d.host] for d in ex.flush()]
    assert got == [[48.0 * (10 * t + s) for s in range(2)]
                   for t in range(len(ticks))]


_MESH_CHILD = r"""
import json
import numpy as np
from repro.analysis import TraceSentinel
from repro.batched import BatchedPerceptionEngine
from repro.launch.mesh import make_local_mesh
from repro.perception import SceneConfig, build_pipeline, generate_scene

mesh = make_local_mesh(data=2)
eng = BatchedPerceptionEngine(
    build_pipeline("early_exit", scale=0.5, pad=False), capacity=4,
    mesh=mesh)
ex = eng.executor
sids = [f"cam{i}" for i in range(4)]
for sid in sids:
    eng.join(sid)
eng.compile()
img = lambda t, i: generate_scene(SceneConfig("road", seed=i), t).image
out = {}
with TraceSentinel(compile_budget=0, transfer_guard="disallow") as sent:
    _, outs = eng.tick({sid: img(0, i) for i, sid in enumerate(sids)})
    raw = ex._raw
    slot_of = {sid: eng.active[sid].slot for sid in sids}
    out["all_dirty_served"] = sorted(outs)
    out["sharding_equal"] = raw.sharding == ex._batch_sharding
    out["shards"] = sorted(
        [s.device.id, s.index[0].start, s.index[0].stop]
        for s in raw.addressable_shards)
    _, outs = eng.tick({"cam1": img(1, 1)})                # partial
    out["partial_served"] = sorted(outs)
    eng.leave("cam3")                                       # churn
    _, outs = eng.tick({sid: img(2, i) for i, sid in enumerate(sids[:3])})
    out["churn_served"] = sorted(outs)
    eng.join("cam3")
    _, outs = eng.tick({sid: img(3, i) for i, sid in enumerate(sids)})
    out["rejoin_served"] = sorted(outs)
out["compiles"] = sent.report().compiles
host = np.asarray(raw)
out["slot_values_ok"] = all(
    np.array_equal(host[slot_of[sid]], img(0, i))
    for i, sid in enumerate(sids))
out["traces"] = [eng.trace_count, eng.assemble_trace_count,
                 eng.update_trace_count]
print(json.dumps(out))
"""


def test_all_dirty_upload_lands_sharded_on_a_two_device_mesh(tmp_path):
    """On a data=2 mesh of forced host devices (a child process), the
    all-dirty upload IS the resident batch with the slot-block sharding
    (each device holds its own two slots), and a warmed engine serves
    all-dirty, partial and churn ticks with no compile and no implicit
    transfer."""
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=2"])
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(Path(__file__).resolve().parents[1] / "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", _MESH_CHILD], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sharding_equal"] is True
    assert out["shards"] == [[0, 0, 2], [1, 2, 4]]
    assert out["slot_values_ok"] is True
    assert out["all_dirty_served"] == ["cam0", "cam1", "cam2", "cam3"]
    assert out["partial_served"] == ["cam1"]
    assert out["churn_served"] == ["cam0", "cam1", "cam2"]
    assert out["rejoin_served"] == ["cam0", "cam1", "cam2", "cam3"]
    assert out["compiles"] == 0
    assert out["traces"] == [1, 1, 1]


# --------------------------------- cost model: pipelined-latency mode -----
def _rung_with_means():
    return Rung("r", "one_stage", 1.0, quality=0.5, stage_means={
        "read": 1e-4, "pre_processing": 1e-3,
        "inference": 5e-3, "post_processing": 1e-3,
    })


def _record(e2e, batch, frame_latency=None):
    meta = {"batch_size": batch}
    if frame_latency is not None:
        meta["frame_latency_s"] = frame_latency
    return StageRecord(stages={"inference": e2e}, meta=meta)


def test_cost_model_pipelined_latency_mode():
    m = RungCostModel(_rung_with_means())
    # cold start: serial pessimistic prior x batch x depth — an untrained
    # controller must never under-estimate pipe residence
    cold1 = m.predict(SceneFeatures(batch_size=4.0, batched=True))
    cold2 = m.predict(SceneFeatures(batch_size=4.0, batched=True,
                                    pipeline_depth=2.0))
    assert cold2.mean == pytest.approx(2.0 * cold1.mean)
    # trained: pipelined records carry frame_latency_s (submit -> drain
    # completion); the regression learns THAT, not the overlapped host
    # residual — a residual-trained model would bless rungs whose
    # completion latency busts the budget exactly when overlap works
    for b in (2.0, 4.0, 8.0):
        for _ in range(4):
            residual = 1e-3                        # overlap hid the step
            completion = 2.0 * (4e-3 + 1e-3 * b)   # what a frame waited
            m.observe(_record(residual, b, frame_latency=completion),
                      SceneFeatures(batch_size=b, batched=True,
                                    pipeline_depth=2.0))
    p = m.predict(SceneFeatures(batch_size=4.0, batched=True,
                                pipeline_depth=2.0))
    assert p.mean == pytest.approx(16e-3, rel=0.15)   # completion, not 1ms
    # trained predictions are completion latencies already: querying at a
    # different depth feature must not rescale observed reality
    p3 = m.predict(SceneFeatures(batch_size=4.0, batched=True,
                                 pipeline_depth=3.0))
    assert p3.mean == pytest.approx(p.mean)
    # sync records (no frame_latency_s) still train on tick e2e
    m2 = RungCostModel(_rung_with_means())
    for _ in range(4):
        m2.observe(_record(6e-3, 4.0), SceneFeatures(batch_size=4.0,
                                                     batched=True))
    assert m2.predict(SceneFeatures(batch_size=4.0, batched=True)).mean \
        == pytest.approx(6e-3, rel=0.15)
    # depth never touches the serial single-frame route
    assert m.predict(SceneFeatures(pipeline_depth=3.0)).mean == \
        m.predict(SceneFeatures()).mean


def test_controller_config_stamps_pipeline_depth():
    ladder = calibrate([Rung("one_stage@0.5", "one_stage", 0.5)], CITY, n=2)
    deep = ContractController(
        ladder, cfg=ControllerConfig(pipeline_depth=3.0))
    flat = ContractController(ladder, cfg=ControllerConfig())
    budget = 1.0
    sel_deep = deep.select(budget, SceneFeatures(batch_size=4.0, batched=True))
    sel_flat = flat.select(budget, SceneFeatures(batch_size=4.0, batched=True))
    assert sel_deep.predicted.mean == pytest.approx(
        3.0 * sel_flat.predicted.mean)
    # an explicit caller-set depth wins over the config stamp
    sel_explicit = deep.select(budget, SceneFeatures(
        batch_size=4.0, batched=True, pipeline_depth=2.0))
    assert sel_explicit.predicted.mean == pytest.approx(
        2.0 * sel_flat.predicted.mean)
    with pytest.raises(ValueError, match="pipeline_depth"):
        ControllerConfig(pipeline_depth=0.5)


# --------------------------------------------- scheduler depth wiring -----
def _tiny_ladder():
    rungs = [
        Rung("one_stage@0.5", "one_stage", 0.5),
        Rung("early_exit@0.5", "early_exit", 0.5),
    ]
    return calibrate(rungs, CITY, n=3)


def test_scheduler_depth2_pairs_stale_results_with_their_scenes():
    ladder = _tiny_ladder()
    sched = RungBucketScheduler(ladder, capacity=2, depth=2)
    sched.warm()
    top = ladder.top
    sched.add_stream("a", 50.0 * top.e2e_mean)
    sched.add_stream("b", 50.0 * top.e2e_mean)
    n_ticks = 4
    rows, tail_rows = [], []
    # the warm depth-2 steady state must neither compile nor transfer
    # implicitly, flush included
    with TraceSentinel(compile_budget=0, transfer_guard="disallow"):
        for t in range(n_ticks):
            scenes = {sid: generate_scene(CITY, 10 + t)
                      for sid in sched.streams}
            res = sched.tick(scenes)
            rows.extend(res.rows)
        tail = sched.flush()
    tail_rows = tail.rows
    # flushed detections are recoverable, as during a regular tick
    assert set(tail.outputs) == {"a", "b"}
    # every submitted frame eventually completed: steady-state drains are
    # one tick stale; the flushed tail completes with no newer submission
    # ahead of it (staleness 0)
    assert len(rows) + len(tail_rows) == n_ticks * 2
    assert all(r["staleness"] == 1 for r in rows)
    assert all(r["staleness"] == 0 for r in tail_rows)
    rows.extend(tail_rows)
    # quality was scored against the echoed (submission-time) scene
    assert all(r["quality"] is not None for r in rows)
    # deadline accounting judged completion latency, which exists
    assert all(r["latency_s"] > 0 for r in rows)
    for st in sched.streams.values():
        assert st.frames == n_ticks
    assert all(e.trace_count == 1 for e in sched.engines.values())


def test_scheduler_flushes_engine_whose_bucket_emptied():
    """A stream migrating rungs must not strand its in-flight frame in
    the old rung's pipeline: the scheduler retires idle engines' work."""
    ladder = _tiny_ladder()
    sched = RungBucketScheduler(ladder, capacity=1, depth=2)
    sched.warm()
    sched.add_stream("a", 50.0 * ladder.top.e2e_mean)
    sched.tick({"a": generate_scene(CITY, 1)})       # in flight in top rung
    st = sched.streams["a"]
    # an impossible budget degrades the stream to the floor rung, so the
    # top rung's bucket is empty this tick
    res = sched.tick({"a": generate_scene(CITY, 2)}, budgets={"a": 1e-9})
    # the old engine's in-flight frame was flushed and accounted
    flushed = [r for r in res.rows if r["rung"] == ladder.top.name]
    assert len(flushed) == 1
    assert sched.engines[ladder.top.name].in_flight == 0
    sched.flush()
    assert st.frames == 2


def test_warm_seeds_completion_latency_at_depth():
    """warm()'s probe is a blocking sync step: at depth d it must seed
    the completion-latency regression at step x residence, not flip the
    model off the depth-aware prior with a raw sync observation."""
    ladder = _tiny_ladder()
    s1 = RungBucketScheduler(ladder, capacity=2, depth=1)
    s2 = RungBucketScheduler(ladder, capacity=2, depth=2)
    fixed = StageRecord(stages={"inference": 5e-3, "post_processing": 1e-3},
                        meta={"batch_size": 2.0})
    for sched in (s1, s2):
        for eng in sched.engines.values():
            eng.probe = lambda frames=None: StageRecord(
                stages=dict(fixed.stages), meta=dict(fixed.meta))
        sched.warm()
    top = ladder.top.name
    f = SceneFeatures(batch_size=2.0, batched=True)
    p1 = s1.cost.predict(top, f)
    p2 = s2.cost.predict(
        top, SceneFeatures(batch_size=2.0, batched=True, pipeline_depth=2.0))
    assert s2.cost.model(top).batched_observations == 1
    assert p2.mean == pytest.approx(2.0 * p1.mean)


def test_scheduler_rejects_stage_cost_with_depth():
    ladder = _tiny_ladder()
    with pytest.raises(ValueError, match="depth"):
        RungBucketScheduler(ladder, capacity=2, depth=2,
                            stage_cost=lambda r, s, b, w: 0.0)
    sched = RungBucketScheduler(ladder, capacity=2, depth=2)
    from repro.bus.clock import SimClock
    with pytest.raises(ValueError, match="depth"):
        sched.set_virtual(SimClock(), lambda r, s, b, w: 0.0)


# ------------------------------------------- replay: sync fallback --------
def test_replayer_depth_falls_back_to_sync():
    from repro.scenarios import ScenarioReplayer, compile_trace, get_episode
    trace = compile_trace(get_episode("highway_cruise"), seed=5,
                          tick_scale=0.25)
    rep = ScenarioReplayer(trace, depth=3)
    assert rep.requested_depth == 3
    assert rep.depth == 1
    assert rep.scheduler.depth == 1
    with pytest.raises(ValueError, match="depth"):
        ScenarioReplayer(trace, depth=0)


# ------------------------------------- golden byte-identity (sync) --------
@pytest.mark.skipif(bool(os.environ.get("CI")),
                    reason="fixtures are host-generated; CI hosts drift "
                           "within tolerance bands (checked by the golden "
                           "CLI step), byte identity is a same-host claim")
def test_golden_fixtures_byte_identical_under_refactored_engine():
    """The executor refactor must not perturb the synchronous path at
    all: replaying a golden episode on the fixtures' host reproduces the
    checked-in JSON byte for byte — no --regen-golden needed."""
    from repro.scenarios.golden import GOLDEN_EPISODES, golden_path, golden_replay
    scheduler = None
    for name in GOLDEN_EPISODES:
        report, scheduler = golden_replay(name, scheduler=scheduler)
        fixture = golden_path(GOLDEN_DIR, name)
        assert fixture.exists(), f"golden fixture {fixture} missing"
        assert report.to_json(indent=2) + "\n" == fixture.read_text(), (
            f"{name}: refactored sync engine no longer reproduces the "
            "golden fixture byte-for-byte")
        # and the parsed structure is a strict dict match, not just bytes
        assert report.to_dict() == json.loads(fixture.read_text())
