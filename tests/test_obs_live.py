"""Live spans and counters inside the serving path: the tracer's
profiler annotations, tag inheritance and counters, the span tree of a
wall-clock scheduler tick, the executor's byte counters, stage records
that tracing leaves alone, and virtual-time replays that record no live
span."""
import glob
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.batched import BatchedPerceptionEngine
from repro.batched.engine import fused_batch_program
from repro.batched.scheduler import RungBucketScheduler
from repro.bus import SimClock
from repro.core.timing import StageTimer
from repro.obs import NO_SPAN, PROFILER_PREFIX, Observatory, SpanTracer
from repro.perception import SceneConfig, build_pipeline, generate_scene
from repro.perception.data import H, W
from repro.scenarios.golden import (GOLDEN_EPISODES, compare_reports,
                                    golden_path, golden_replay)
from repro.scenarios.replay import replay_ladder

GOLDEN_DIR = Path(__file__).parent / "golden"
FRAME_BYTES = 96 * 320 * 3 * 4            # one float32 camera frame
LIVE = {"sched_tick", "select", "seat", "read", "inference", "upload",
        "dispatch", "readback", "post_processing", "account"}
STREAMS = 4


# ------------------------------------------------------------- tracer --

def test_span_tags_inherit_from_the_enclosing_span():
    tr = SpanTracer(capacity=16)
    with tr.span("sched_tick", tick=7, batch_size=8):
        with tr.span("inference", rung="two_stage", stream="cam"):
            with tr.span("readback", batch_size=3):
                pass
    with tr.span("alone"):
        pass
    by = {s.name: s for s in tr.spans()}
    assert by["readback"].tick == 7 and by["readback"].rung == "two_stage"
    assert by["readback"].stream == "cam" and by["readback"].batch_size == 3
    assert by["inference"].batch_size == 8
    assert by["readback"].parent == by["inference"].seq
    assert by["inference"].parent == by["sched_tick"].seq
    alone = by["alone"]
    assert (alone.tick, alone.rung, alone.batch_size, alone.shard,
            alone.parent) == (0, "", 0, -1, -1)


def test_live_span_is_recorded_when_its_body_raises():
    tr = SpanTracer(capacity=4)
    with pytest.raises(KeyError):
        with tr.span("seat") as live:
            raise KeyError("boom")
    assert live.span is not None and tr.spans() == [live.span]
    with tr.span("after"):
        pass
    assert tr.spans()[-1].parent == -1      # the stack was unwound


def test_counters_accumulate_and_clear():
    tr = SpanTracer(capacity=4)
    tr.add("h2d_bytes", FRAME_BYTES)
    tr.add("h2d_bytes", FRAME_BYTES)
    tr.add("frames_submitted", 2)
    got = tr.counters()
    assert got == {"h2d_bytes": 2 * FRAME_BYTES, "frames_submitted": 2}
    got["h2d_bytes"] = 0                    # a copy, not the live table
    assert tr.counters()["h2d_bytes"] == 2 * FRAME_BYTES
    tr.clear()
    assert tr.counters() == {}


def test_observatory_span_feeds_the_hub_under_the_span_tags():
    clock = SimClock()
    obs = Observatory(clock=clock.time)
    with obs.span("upload", rung="two_stage", batch_size=8,
                  axis="io") as live:
        clock.advance(0.002)
    assert obs.tracer.spans() == [live.span]
    assert live.span.duration == pytest.approx(0.002)
    (key,) = obs.metrics.keys()
    assert (key.stage, key.rung, key.batch_size) == ("upload", "two_stage", 8)
    assert obs.metrics.get(key).mean == pytest.approx(0.002)


def test_stage_timer_spans_are_parents_of_what_the_stage_opens():
    clock = SimClock()
    tr = SpanTracer(capacity=8, clock=clock.time)
    timer = StageTimer(clock=clock.time, tracer=tr, tags={"rung": "r"})
    with timer.stage("inference"):
        with tr.span("readback"):
            clock.advance(0.003)
    rec = timer.finish()
    assert rec.stages == {"inference": pytest.approx(0.003)}
    by = {s.name: s for s in tr.spans()}
    assert by["readback"].parent == by["inference"].seq
    assert by["readback"].rung == "r"


def test_no_span_is_a_reusable_null_context():
    for _ in range(2):
        with NO_SPAN as got:
            assert got is None


# ----------------------------------------------------- serving path ----

def _scenes(tick: int) -> dict:
    return {f"cam{i}": generate_scene(SceneConfig("city", seed=i), tick)
            for i in range(STREAMS)}


@pytest.fixture(scope="module")
def split_sched():
    """A wall-clock scheduler with even streams pinned to the top rung and
    odd ones to the floor: two buckets a tick."""
    sched = RungBucketScheduler(replay_ladder(), capacity=STREAMS,
                                key=jax.random.PRNGKey(7))
    for i in range(STREAMS):
        sched.add_stream(f"cam{i}", 3600.0 if i % 2 == 0 else 0.0)
    for t in range(3):                      # compile and settle the rungs
        sched.tick(_scenes(t))
    return sched


def _live_spans(obs) -> list:
    """The ring's live spans, without the scheduler's instants."""
    spans = obs.tracer.spans()
    assert {s.name for s in spans} - LIVE <= {"rung_switch"}
    return [s for s in spans if s.name in LIVE]


def _traced_ticks(sched, n: int):
    obs = Observatory()
    sched.set_obs(obs)
    try:
        first = sched.ticks
        for t in range(n):
            sched.tick(_scenes(10 + t))
    finally:
        sched.set_obs(None)
    return obs, first


def test_a_wall_clock_tick_records_one_span_tree_per_tick(split_sched):
    obs, first = _traced_ticks(split_sched, 2)
    spans = _live_spans(obs)
    assert obs.tracer.dropped == 0
    assert {s.name for s in spans} == LIVE
    by_seq = {s.seq: s for s in spans}
    for tick in (first, first + 1):
        mine = [s for s in spans if s.tick == tick]
        (root,) = [s for s in mine if s.name == "sched_tick"]
        assert root.parent == -1 and root.batch_size == STREAMS
        for s in mine:
            if s is root:
                continue
            parent = by_seq[s.parent]
            want = ("inference" if s.name in ("upload", "dispatch",
                                              "readback")
                    else "sched_tick")
            assert parent.name == want, s
            assert root.t0 <= s.t0 <= s.t1 <= root.t1
        # one bucket per rung the streams chose: seat, read, inference,
        # post and account once each, all tagged with the bucket's rung
        rungs = sorted({s.rung for s in mine if s.name == "seat"})
        assert len(rungs) == 2
        for name in LIVE - {"sched_tick", "select"}:
            got = sorted(s.rung for s in mine if s.name == name)
            assert got == rungs, name
        assert all(s.batch_size == STREAMS // 2 for s in mine
                   if s.name in LIVE - {"sched_tick", "select"})
    # the old after-the-fact tick reconstruction is gone on wall clock
    assert not [s for s in spans if s.name == "tick"]


def test_pipelined_engine_spans_nest_the_readback_under_inference():
    built = build_pipeline("early_exit", scale=0.5,
                           key=jax.random.PRNGKey(7), pad=False)
    obs = Observatory()
    eng = BatchedPerceptionEngine(built, capacity=2, depth=2, obs=obs)
    for i in range(2):
        eng.join(f"cam{i}")
    for t in range(3):
        eng.tick({f"cam{i}": generate_scene(SceneConfig("road", seed=i),
                                             t).image for i in range(2)})
    eng.flush()
    spans = obs.tracer.spans()
    by_seq = {s.seq: s for s in spans}
    names = [s.name for s in spans]
    for name in ("read", "upload", "dispatch"):
        assert names.count(name) == 3
    for name in ("inference", "readback", "post_processing"):
        assert names.count(name) == 3
    for s in spans:
        if s.name == "readback":
            assert by_seq[s.parent].name == "inference"
            assert s.batch_size == 2
        else:
            assert s.parent == -1, s
    assert obs.tracer.counters()["frames_submitted"] == 6


def test_counters_count_every_byte_across_a_window(split_sched):
    obs = Observatory()
    split_sched.set_obs(obs)
    try:
        split_sched.tick(_scenes(20))
        before = obs.tracer.counters()
        n = 3
        for t in range(n):
            split_sched.tick(_scenes(21 + t))
        after = obs.tracer.counters()
    finally:
        split_sched.set_obs(None)
    delta = {k: after[k] - before[k] for k in after}
    assert delta["frames_submitted"] == n * STREAMS
    assert delta["h2d_bytes"] == FRAME_BYTES * delta["frames_submitted"]
    # each drained tick reads back its engine's whole capacity-wide tree
    raw = jax.ShapeDtypeStruct((STREAMS, H, W, 3), np.float32)
    tree = sum(
        int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        for eng in split_sched.engines.values() if eng.active
        for leaf in jax.tree.leaves(
            jax.eval_shape(fused_batch_program(eng.built), raw)))
    assert delta["d2h_bytes"] == n * tree


@pytest.mark.parametrize("depth", [1, 2])
def test_tracing_leaves_stage_records_alone(depth):
    built = build_pipeline("two_stage", key=jax.random.PRNGKey(7),
                           pad=False)
    eng = BatchedPerceptionEngine(built, capacity=2, depth=depth)
    for i in range(2):
        eng.join(f"cam{i}")
    bystander = Observatory()               # never attached

    def keys(ticks):
        out = []
        for t in ticks:
            rec, _ = eng.tick({f"cam{i}": generate_scene(
                SceneConfig("city", seed=i), t).image for i in range(2)})
            if rec is not None:
                out.append(list(rec.stages))
        return out

    bare = keys(range(3))
    assert eng.executor.obs is None
    eng.obs = Observatory()
    traced = keys(range(3, 6))
    assert eng.executor.obs is eng.obs
    assert eng.obs.tracer.n_recorded > 0
    eng.obs = None
    detached = keys(range(6, 9))
    assert eng.executor.obs is None
    want = (["read", "inference", "post_processing"] if depth == 1 else
            ["read", "upload", "inference", "post_processing"])
    for phase in (bare, traced, detached):
        assert phase and all(k == want for k in phase)
    assert bystander.tracer.n_recorded == 0
    assert bystander.tracer.counters() == {}


@pytest.mark.parametrize("depth", [1, 2])
def test_a_two_stage_tick_counts_its_nms_work(depth):
    built = build_pipeline("two_stage", key=jax.random.PRNGKey(7),
                           pad=False)
    obs = Observatory()
    eng = BatchedPerceptionEngine(built, capacity=3, depth=depth, obs=obs)
    for i in range(2):                      # slot 2 stays inactive
        eng.join(f"cam{i}")
    proposals, ticks = 0, 0
    for t in range(4):
        before = obs.tracer.counters()
        _, outs = eng.tick({f"cam{i}": generate_scene(
            SceneConfig("city", seed=i), t).image for i in range(2)})
        after = obs.tracer.counters()
        if not outs:                        # the pipeline is filling
            continue
        ticks += 1
        got = sum(o.num_proposals for o in outs.values())
        assert got > 0
        assert (after["nms_candidates"] - before.get("nms_candidates", 0)
                == got)
        assert after["nms_sweeps"] - before.get("nms_sweeps", 0) >= 1
        proposals += got
    assert ticks == 5 - depth
    assert obs.tracer.counters()["nms_candidates"] == proposals


@pytest.mark.parametrize("rung", ["one_stage", "early_exit"])
def test_a_one_stage_tick_counts_no_nms_work(rung):
    built = build_pipeline(rung, key=jax.random.PRNGKey(7))
    obs = Observatory()
    eng = BatchedPerceptionEngine(built, capacity=2, obs=obs)
    eng.join("cam0")
    _, outs = eng.tick({"cam0": generate_scene(SceneConfig("city", seed=0),
                                                1).image})
    assert outs["cam0"].num_objects > 0
    counters = obs.tracer.counters()
    assert counters["frames_submitted"] == 1
    assert not {"nms_candidates", "nms_sweeps"} & set(counters)


@pytest.mark.parametrize("name", sorted(GOLDEN_EPISODES))
def test_golden_replays_stay_byte_identical_with_an_observatory(name):
    obs = Observatory()
    on, sched = golden_replay(name, obs=obs)
    off, _ = golden_replay(name, scheduler=sched)
    assert on.to_json() == off.to_json()
    want = json.loads(golden_path(GOLDEN_DIR, name).read_text())
    assert compare_reports(on.to_dict(), want) == []
    # nothing ran for real under virtual time: only the modeled stage
    # timeline, never a live span, and no executor counter
    names = {s.name for s in obs.tracer.spans()}
    assert "tick" in names
    assert not names & {"sched_tick", "select", "seat", "account",
                        "upload", "dispatch", "readback"}
    assert obs.tracer.counters() == {}


# -------------------------------------------------- profiler clock ----

def _host_events(log_dir) -> list:
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events += [(ev.start_ns, -ev.duration_ns, ev.name)
                       for ev in line.events
                       if ev.name.startswith(PROFILER_PREFIX)]
    return [(name, -neg_ns * 1e-9) for _, neg_ns, name in sorted(events)]


def test_live_spans_share_the_profiler_clock(split_sched, tmp_path):
    obs = Observatory()
    split_sched.set_obs(obs)
    try:
        split_sched.tick(_scenes(30))       # warm the traced path
        obs.tracer.clear()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("warm"):
                pass
            for t in range(3):
                split_sched.tick(_scenes(31 + t))
        finally:
            jax.profiler.stop_trace()
    finally:
        split_sched.set_obs(None)
    ring = sorted(_live_spans(obs), key=lambda s: (s.t0, s.seq))
    events = _host_events(tmp_path)
    assert [name for name, _ in events] == [PROFILER_PREFIX + s.name
                                            for s in ring]
    assert len(ring) == 3 * (2 + 8 * 2)     # sched_tick, select; 8 a bucket
    for (name, dur), s in zip(events, ring):
        assert abs(dur - s.duration) < 50e-6, (name, dur, s.duration)
