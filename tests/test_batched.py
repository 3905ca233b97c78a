"""Batched multi-camera engine suite plus regressions for the frame-loss
and fusion-accounting bugfixes that ride along with it.
"""
import numpy as np
import pytest

from repro.analysis import TraceSentinel
from repro.anytime import Rung, calibrate
from repro.anytime.cost import RungCostModel, SceneFeatures
from repro.batched import BatchedPerceptionEngine, RungBucketScheduler
from repro.core.timing import StageRecord
from repro.perception import (
    ApproxTimeSynchronizer,
    SceneConfig,
    build_pipeline,
    generate_scene,
    run_frame,
    run_pipeline,
)


# ------------------------------------------ bugfix: warmup frame loss -----
def test_run_pipeline_records_every_supplied_image():
    """Regression: the first caller-supplied image used to be consumed as
    the unrecorded warmup frame — n images in, n−1 records out, frame 0
    silently lost.  Warmup must be synthetic and the recorded count must
    equal the supplied count."""
    cfg = SceneConfig("city", seed=7)
    # image 0 carries objects; the rest are blank — if frame 0 were still
    # eaten by warmup, the first *recorded* frame would show zero objects
    images = [generate_scene(cfg, 1).image] + [np.zeros((96, 320, 3), np.float32)] * 3
    rec, outputs = run_pipeline("one_stage", cfg, images=images, collect=True)
    assert len(rec.records) == len(images)
    assert len(outputs) == len(images)
    objs = rec.meta_series("num_objects")
    assert objs[0] > 0, "frame 0 (the only scene with objects) was not recorded"
    assert (objs[1:] == 0).all()


def test_run_pipeline_synthetic_contract_unchanged():
    """Without user images the legacy contract holds: n frames recorded."""
    rec = run_pipeline("one_stage", SceneConfig("city", seed=7), n=3)
    assert len(rec.records) == 3


def test_run_pipeline_warms_on_the_supplied_image_shape():
    """The synthetic warmup frame must take the caller images' shape —
    jit traces per shape, so a canonical-shape warmup would leave
    oddly-sized user images to compile inside the recorded loop."""
    images = [np.random.default_rng(0).random((64, 128, 3)).astype(np.float32)
              for _ in range(2)]
    rec = run_pipeline("one_stage", SceneConfig("city", seed=7), images=images)
    assert len(rec.records) == 2


def test_run_pipeline_empty_images_is_empty_run():
    rec, outputs = run_pipeline("one_stage", SceneConfig("city", seed=7),
                                images=[], collect=True)
    assert rec.records == [] and outputs == []


# ------------------------------------ bugfix: fusion drop accounting ------
def test_fusion_sweep_drops_are_accounted():
    """Regression: messages discarded unmatched by the post-emit sweep
    (stamp ≤ matched) were lost without accounting; only queue-overflow
    evictions counted, under-reporting fusion drop rates."""
    sync = ApproxTimeSynchronizer(["a", "b"], queue_size=10, slop=0.005)
    sync.add("a", 0.0, None, now=0.0)      # will never match topic b
    sync.add("a", 0.02, None, now=0.02)
    ev = sync.add("b", 0.021, None, now=0.021)
    assert ev is not None and ev.stamps == {"a": 0.02, "b": 0.021}
    assert sync.dropped_overflow == 0
    assert sync.dropped_sweep == 1          # a@0.0 swept unmatched
    assert sync.dropped == 1


def test_fusion_matched_traffic_drops_nothing():
    sync = ApproxTimeSynchronizer(["a", "b"], queue_size=100, slop=0.01)
    for i in range(10):
        sync.add("a", float(i), None, now=float(i))
        sync.add("b", float(i), None, now=float(i) + 0.001)
    assert sync.dropped == 0
    assert len(sync.events) == 10


def test_fusion_unknown_topic_raises_clear_error():
    sync = ApproxTimeSynchronizer(["a", "b"], queue_size=4, slop=0.01)
    with pytest.raises(KeyError, match="unknown topic 'camera'"):
        sync.add("camera", 0.0, None, now=0.0)


# ------------------------------------------------ batched engine ----------
CITY = SceneConfig("city", seed=21)


@pytest.mark.parametrize("name,scale,pad", [
    ("one_stage", 1.0, True),
    ("one_stage", 0.5, False),
    ("early_exit", 0.5, False),
    ("two_stage", 1.0, True),
])
def test_batched_matches_serial_per_rung(name, scale, pad):
    """The batched device path (fused device preprocess + vmapped infer +
    vectorized post) must reproduce the serial pipeline's outputs: same
    keep counts, same boxes."""
    built = build_pipeline(name, scale=scale, pad=pad)
    eng = BatchedPerceptionEngine(built, capacity=3)
    scenes = [generate_scene(CITY, i + 1) for i in range(3)]
    for s in range(3):
        eng.join(f"cam{s}")
    _, outs = eng.tick({f"cam{s}": scenes[s].image for s in range(3)})
    for s, scene in enumerate(scenes):
        _, ref = run_frame(built, scene)
        out = outs[f"cam{s}"]
        assert out.num_objects == ref.num_objects
        assert out.num_proposals == ref.num_proposals
        assert out.boxes.shape == ref.boxes.shape
        assert np.allclose(out.boxes, ref.boxes, atol=1e-3)


BENCH_MIXES = {
    "city": [["city", 0.0]],
    "rain": [["city", 60.0], ["residential", 25.0]],
    "road": [["road", 0.0]],
}


@pytest.fixture(scope="module")
def two_stage_ticks():
    """Per mix, two ticks of 8 frames rendered by the benchmark's scene
    generator, through the two-stage detector's device stage: (detector,
    params, [(feat, obj)] per tick)."""
    import jax

    from bench.detection import weights_key
    from bench.scenes import fleet_pools
    from repro.perception import TwoStageDetector, preprocess_device

    det = TwoStageDetector()
    params = det.init(weights_key())
    step = jax.jit(jax.vmap(
        lambda raw: det.infer_device(params, preprocess_device(raw, 1.0, True))))
    ticks = {}
    for mix, streams in BENCH_MIXES.items():
        pools = fleet_pools(4400000001, 8, streams, 2, (96, 320, 3))
        ticks[mix] = [
            jax.device_get(step(np.stack([pool[t].image for pool in pools])))
            for t in range(2)]
    return det, params, ticks


def _per_frame_nms(boxes, scores, counts, iou_thr=0.5):
    """The batched NMS's oracle: ``dynamic_nms`` on each frame alone."""
    from repro.perception import dynamic_nms

    starts = np.cumsum(counts) - counts
    keeps = [dynamic_nms(boxes[s:s + n], scores[s:s + n], iou_thr) + s
             for s, n in zip(starts, counts)]
    return np.concatenate(keeps), np.array([len(k) for k in keeps]), 1


@pytest.mark.parametrize("mix", sorted(BENCH_MIXES))
@pytest.mark.parametrize("inactive", [(), (0, 3, 7)])
def test_post_host_batch_is_the_per_frame_loop_on_bench_scenes(
        two_stage_ticks, monkeypatch, mix, inactive):
    """On the benchmark's scenes, the batched host post returns per slot
    the very boxes (bit for bit) and proposal counts of the same pass
    with ``dynamic_nms`` run frame by frame, and inactive slots stay
    ``None``.  Against the serial ``post_host`` the proposal counts and
    kept counts are equal and the boxes agree to float32 rounding of the
    refinement's matrix products."""
    from repro.perception import detector

    det, params, ticks = two_stage_ticks
    active = np.ones(8, bool)
    active[list(inactive)] = False
    for feat, obj in ticks[mix]:
        got, sweeps = det.post_host_batch(params, feat, obj, active=active)
        with monkeypatch.context() as m:
            m.setattr(detector, "batched_nms", _per_frame_nms)
            want, _ = det.post_host_batch(params, feat, obj, active=active)
        assert sweeps >= 1
        for b, (g, w) in enumerate(zip(got, want)):
            if not active[b]:
                assert g is None and w is None
                continue
            assert g[1] == w[1]
            assert g[0].dtype == w[0].dtype and g[0].shape == w[0].shape
            np.testing.assert_array_equal(g[0], w[0])
            boxes, n = det.post_host(params, feat[b], obj[b])
            assert g[1] == n
            assert g[0].shape == boxes.shape
            np.testing.assert_allclose(g[0], boxes, rtol=0, atol=1e-3)


def test_no_retrace_on_join_and_leave():
    """Slot carve-out from the fixed-capacity padded batch: stream churn
    must never retrace the jitted batched step.  The sentinel counts
    *actual* backend compiles (budget 0 after explicit warmup) and
    disallows implicit host↔device transfers for the whole churn
    sequence — strictly stronger than the old ``trace_count == 1``."""
    eng = BatchedPerceptionEngine(build_pipeline("early_exit"), capacity=4)
    img = generate_scene(CITY, 1).image
    eng.compile()                              # warmup outside the sentinel
    with TraceSentinel(compile_budget=0, transfer_guard="disallow"):
        eng.join("a")
        eng.join("b")
        eng.tick({"a": img, "b": img})
        eng.join("c")                          # join mid-flight
        eng.tick({"a": img, "b": img, "c": img})
        eng.leave("b")
        eng.tick({"a": img, "c": img})
        eng.leave("a")
        eng.leave("c")
        eng.join("d")                          # rejoin after full drain
        eng.tick({"d": img})
    assert eng.trace_count == 1
    assert eng.ticks == 4


def test_engine_slot_exhaustion_and_double_join():
    eng = BatchedPerceptionEngine(build_pipeline("early_exit"), capacity=1)
    eng.join("a")
    with pytest.raises(ValueError, match="already seated"):
        eng.join("a")
    with pytest.raises(RuntimeError, match="no free slot"):
        eng.join("b")
    with pytest.raises(KeyError, match="unseated"):
        eng.tick({"ghost": generate_scene(CITY, 1).image})
    # a frameless tick serves nothing: no device step, no logged tick
    rec, outs = eng.tick({})
    assert rec is None and outs == {}
    assert eng.ticks == 0 and eng.tick_log == []
    # build arguments alongside an already-built pipeline are contradictory
    with pytest.raises(ValueError, match="already built"):
        BatchedPerceptionEngine(build_pipeline("early_exit"), capacity=1,
                                scale=0.5)


def test_throughput_monotonic_with_batch_size():
    """A fixed-capacity padded tick costs roughly the same whether 1 or 8
    slots are live, so served frames/s must grow with the active batch."""
    eng = BatchedPerceptionEngine(build_pipeline("one_stage"), capacity=8)
    img = generate_scene(CITY, 1).image
    for s in range(8):
        eng.join(f"cam{s}")
    eng.compile()

    def fps(n_active, reps=6):
        lats = []
        for _ in range(reps):
            rec, _ = eng.tick({f"cam{s}": img for s in range(n_active)})
            lats.append(rec.end_to_end)
        # min-of-reps: hypervisor steal on shared runners only ever
        # inflates a tick, so the minimum is the robust per-tick cost
        return n_active / float(min(lats))

    fps1, fps8 = fps(1), fps(8)
    assert fps8 > 2.0 * fps1, f"fps did not scale with batch: {fps1} -> {fps8}"


# ------------------------------------------- rung-bucketed scheduling -----
def _tiny_ladder():
    rungs = [
        Rung("one_stage@0.5", "one_stage", 0.5),
        Rung("early_exit@0.5", "early_exit", 0.5),
    ]
    return calibrate(rungs, CITY, n=3)


def test_rung_bucket_scheduling_splits_by_budget():
    ladder = _tiny_ladder()
    sched = RungBucketScheduler(ladder, capacity=3)
    sched.warm()
    top = ladder.top
    sched.add_stream("loose0", 50.0 * top.e2e_mean)
    sched.add_stream("loose1", 50.0 * top.e2e_mean)
    sched.add_stream("tight", 1e-9)            # nothing can fit: floor rung
    last = None
    # bucket churn across rungs must neither compile nor transfer
    # implicitly once the scheduler is warm
    with TraceSentinel(compile_budget=0, transfer_guard="disallow"):
        for t in range(4):
            scenes = {sid: generate_scene(CITY, 10 + t)
                      for sid in sched.streams}
            last = sched.tick(scenes)
    assert set(last.buckets) == {ladder.top.name, ladder.floor.name}
    assert sorted(last.buckets[ladder.top.name]) == ["loose0", "loose1"]
    assert last.buckets[ladder.floor.name] == ["tight"]
    # bucket co-residents share one batched step latency
    rows = {r["stream"]: r for r in last.rows}
    assert rows["loose0"]["latency_s"] == rows["loose1"]["latency_s"]
    assert rows["loose0"]["batch_size"] == 2
    # membership churn across buckets never retraced any engine
    assert all(e.trace_count == 1 for e in sched.engines.values())
    # the cost model saw real (rung, batch-size) observations
    assert sched.cost.model(ladder.floor.name).batched_observations > 0


def test_scheduler_stream_lifecycle():
    ladder = _tiny_ladder()
    sched = RungBucketScheduler(ladder, capacity=2)
    sched.add_stream("a", 1.0)
    with pytest.raises(ValueError, match="already exists"):
        sched.add_stream("a", 1.0)
    sched.add_stream("b", 1.0)
    with pytest.raises(RuntimeError, match="at capacity"):
        sched.add_stream("c", 1.0)
    sched.tick({"a": generate_scene(CITY, 1), "b": generate_scene(CITY, 2)})
    sched.remove_stream("a")
    res = sched.tick({"b": generate_scene(CITY, 3)})
    assert set(res.outputs) == {"b"}
    with pytest.raises(KeyError, match="unknown streams"):
        sched.tick({"a": generate_scene(CITY, 4)})


# --------------------------------- cost model: (rung, batch-size) ---------
def _rung_with_means():
    return Rung("r", "one_stage", 1.0, quality=0.5, stage_means={
        "read": 1e-4, "pre_processing": 1e-3,
        "inference": 5e-3, "post_processing": 1e-3,
    })


def _record(e2e, batch):
    return StageRecord(stages={"inference": e2e}, meta={"batch_size": batch})


def test_cost_model_batch_size_feature():
    m = RungCostModel(_rung_with_means())
    single_mean = m.predict(SceneFeatures()).mean
    # cold start: the batched prior is the pessimistic serial bound
    cold = m.predict(SceneFeatures(batch_size=4.0))
    assert cold.mean == pytest.approx(4.0 * single_mean)
    # batched-step observations: latency = 4ms + 1ms per active slot
    for b in (2.0, 4.0, 8.0):
        for _ in range(4):
            m.observe(_record(4e-3 + 1e-3 * b, b), SceneFeatures(batch_size=b))
    p2 = m.predict(SceneFeatures(batch_size=2.0))
    p8 = m.predict(SceneFeatures(batch_size=8.0))
    assert p2.mean == pytest.approx(6e-3, rel=0.15)
    assert p8.mean == pytest.approx(12e-3, rel=0.15)
    assert p8.mean > p2.mean
    # single-frame predictions are untouched by batched observations
    assert m.predict(SceneFeatures()).mean == pytest.approx(single_mean)
    assert m.observations == 0 and m.batched_observations == 12


def test_cost_model_singleton_bucket_stays_on_batched_route():
    """A bucket of one still pays a full capacity-wide padded step:
    batched=True must route size-1 observations and predictions through
    the batch regression, never the serial per-stage model."""
    m = RungCostModel(_rung_with_means())
    for b in (1.0, 4.0):
        for _ in range(4):
            m.observe(_record(4e-3 + 1e-3 * b, b),
                      SceneFeatures(batch_size=b, batched=True))
    assert m.observations == 0 and m.batched_observations == 8
    p1 = m.predict(SceneFeatures(batch_size=1.0, batched=True))
    assert p1.mean == pytest.approx(5e-3, rel=0.15)
    # without the flag, size 1 stays the serial single-frame prediction
    assert m.predict(SceneFeatures(batch_size=1.0)).mean == pytest.approx(
        7.1e-3, rel=0.01)
