"""Observability layer: span tracer ring semantics, quantile sketches,
metrics rollups, Chrome-trace export + validation, per-axis variance
attribution, and the end-to-end wiring through the batched scheduler,
scenario replayer, sentinel, and multi-tenant engine — including the
golden-checked claim that attaching an observatory never changes what it
observes.
"""
import io
import json
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import TraceSentinel, lint_source
from repro.analysis.findings import AXES
from repro.bus import SimClock
from repro.core.timing import STAGE_AXES, StageTimer, TimelineRecorder
from repro.obs import (
    LatencySketch,
    MetricKey,
    MetricsHub,
    Observatory,
    P2Quantile,
    SpanTracer,
    attribute,
    render_table,
    to_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.obs.attribution import ATTRIBUTION_ORDER, FrameSample
from repro.obs.__main__ import MEDIATED_ORDER, contention_attribution
from repro.obs.__main__ import main as obs_main
from repro.scenarios.golden import golden_replay

GOLDEN_DIR = Path(__file__).parent / "golden"


# ------------------------------------------------------------- tracer --

def test_span_record_tags_and_duration():
    clock = SimClock()
    tr = SpanTracer(capacity=16, clock=clock.time)
    s = tr.record("inference", 1.0, 1.5, stream="cam0", tick=3,
                  rung="two_stage", batch_size=4, axis="model", track=1)
    assert s.duration == pytest.approx(0.5)
    assert s.stream == "cam0" and s.axis == "model" and s.parent == -1
    assert tr.spans() == [s]
    d = s.to_dict()
    assert d["tick"] == 3 and d["track"] == 1 and d["seq"] == 0


def test_span_rejects_unknown_axis():
    tr = SpanTracer(capacity=4)
    with pytest.raises(ValueError, match="unknown axis"):
        tr.record("x", 0.0, 1.0, axis="gpu")
    with pytest.raises(ValueError, match="unknown axis"):
        with tr.span("x", axis="nope"):
            pass
    assert set(STAGE_AXES.values()) <= set(AXES)


def test_span_nesting_assigns_parents():
    clock = SimClock()
    tr = SpanTracer(capacity=16, clock=clock.time)
    with tr.span("tick", axis="end_to_end"):
        clock.advance(0.1)
        with tr.span("inference", axis="model"):
            clock.advance(0.2)
        tr.instant("rung_switch", axis="model")
        clock.advance(0.05)
    spans = {s.name: s for s in tr.spans()}
    assert spans["tick"].parent == -1
    # seq is assigned at open, so the outer span (opened first) is the
    # inner spans' parent even though it closes last
    assert spans["inference"].parent == spans["tick"].seq
    assert spans["rung_switch"].parent == spans["tick"].seq
    assert spans["rung_switch"].duration == 0.0
    assert spans["inference"].duration == pytest.approx(0.2)
    # ring holds close order: children land before their parent
    names = [s.name for s in tr.spans()]
    assert names.index("inference") < names.index("tick")


def test_ring_overwrites_oldest_and_counts_drops():
    tr = SpanTracer(capacity=4)
    for i in range(7):
        tr.record(f"s{i}", float(i), float(i) + 0.5)
    assert tr.n_recorded == 7
    assert tr.dropped == 3
    assert [s.name for s in tr.spans()] == ["s3", "s4", "s5", "s6"]
    tr.clear()
    assert tr.n_recorded == 0 and tr.dropped == 0 and tr.spans() == []


def test_span_fence_accepts_callable_evaluated_at_exit():
    tr = SpanTracer(capacity=4)
    with tr.span("step", axis="model", fence=lambda: out):
        out = jnp.ones(8) * 2
    (s,) = tr.spans()
    assert s.name == "step" and s.t1 >= s.t0


def test_tracer_is_deterministic_under_simclock():
    def run():
        clock = SimClock()
        tr = SpanTracer(capacity=32, clock=clock.time)
        for i in range(5):
            with tr.span("tick", tick=i, axis="end_to_end"):
                clock.advance(0.01 * (i + 1))
        return [s.to_dict() for s in tr.spans()]

    assert run() == run()


def test_capacity_validation():
    with pytest.raises(ValueError, match="capacity"):
        SpanTracer(capacity=0)


# ------------------------------------------------------------- export --

def _make_spans():
    clock = SimClock()
    tr = SpanTracer(capacity=32, clock=clock.time)
    for tick, stream in enumerate(["cam0", "cam1"]):
        with tr.span("tick", stream=stream, tick=tick, axis="end_to_end",
                     track=tick % 2):
            clock.advance(0.004)
            with tr.span("inference", stream=stream, tick=tick,
                         rung="two_stage", batch_size=2, axis="model"):
                clock.advance(0.002)
        tr.instant("rung_switch", stream=stream, axis="model")
    return tr.spans()


def test_chrome_trace_structure():
    spans = _make_spans()
    doc = to_chrome_trace(spans, process_label="test")
    assert validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    # one process per stream, named for Perfetto's row groups
    assert {e["args"]["name"] for e in meta} == {"test/cam0", "test/cam1"}
    complete = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert len(complete) == 4 and len(instants) == 2
    assert all(e["s"] == "t" for e in instants)
    # timestamps are microseconds on the span clock
    tick0 = next(e for e in complete
                 if e["name"] == "tick" and e["args"]["tick"] == 0)
    assert tick0["dur"] == pytest.approx(6000.0)
    assert tick0["args"]["axis"] == "end_to_end"
    # distinct streams get distinct pids; track becomes tid
    pids = {e["pid"] for e in complete}
    assert len(pids) == 2
    assert {e["tid"] for e in complete if e["name"] == "tick"} == {0, 1}


def test_chrome_trace_round_trips_through_disk(tmp_path):
    path = tmp_path / "trace.json"
    doc = write_chrome_trace(_make_spans(), str(path))
    back = json.loads(path.read_text())
    assert back == json.loads(json.dumps(doc))
    assert validate_chrome_trace(back) == []


def test_validate_chrome_trace_catches_violations():
    assert validate_chrome_trace([]) == ["document is not a JSON object"]
    assert validate_chrome_trace({}) == ["missing traceEvents array"]
    bad = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 0, "ts": -1.0, "dur": 2.0},
        {"ph": "??", "name": "b", "pid": 1, "tid": 0, "ts": 0.0},
        {"ph": "i", "name": "c", "pid": "one", "tid": 0, "ts": 0.0, "s": "x"},
        {"ph": "X", "name": "d"},
        "not-an-object",
    ]}
    errors = validate_chrome_trace(bad)
    assert any("ts must be" in e for e in errors)
    assert any("unknown phase" in e for e in errors)
    assert any("pid must be an int" in e for e in errors)
    assert any("instant scope" in e for e in errors)
    assert any("missing keys" in e for e in errors)
    assert any("not an object" in e for e in errors)


# ------------------------------------------------------------ sketches --

def test_p2_exact_below_five_samples():
    p = P2Quantile(0.5)
    assert np.isnan(p.value())
    for x in (5.0, 1.0, 3.0):
        p.update(x)
    assert p.value() == 3.0


def test_p2_converges_on_uniform():
    rng = np.random.default_rng(0)
    xs = rng.uniform(0.0, 1.0, 4000)
    p = P2Quantile(0.9)
    for x in xs:
        p.update(x)
    assert p.value() == pytest.approx(np.percentile(xs, 90), abs=0.05)


def test_p2_rejects_degenerate_quantile():
    for q in (0.0, 1.0, -0.1, 1.5):
        with pytest.raises(ValueError):
            P2Quantile(q)


def test_latency_sketch_quantiles_and_extremes():
    rng = np.random.default_rng(1)
    xs = rng.lognormal(-6.0, 0.8, 5000)
    sk = LatencySketch()
    sk.extend(xs)
    assert sk.count == len(xs)
    assert sk.quantile(0.0) == xs.min()
    assert sk.quantile(1.0) == xs.max()
    for q in (0.5, 0.95, 0.99):
        want = np.percentile(xs, q * 100)
        assert sk.quantile(q) == pytest.approx(want, rel=0.03)


def test_latency_sketch_merge_is_exact():
    rng = np.random.default_rng(2)
    a_xs, b_xs = rng.exponential(0.01, 800), rng.exponential(0.05, 1200)
    whole = LatencySketch()
    whole.extend(np.concatenate([a_xs, b_xs]))
    a, b = LatencySketch(), LatencySketch()
    a.extend(a_xs)
    b.extend(b_xs)
    merged = a.copy().merge(b)
    # merging is exact bin-count addition: bit-identical to one sketch
    # that saw every observation
    assert merged.to_dict() == whole.to_dict()
    assert merged.quantile(0.99) == whole.quantile(0.99)


def test_latency_sketch_rejects_mismatched_edges():
    with pytest.raises(ValueError, match="different edges"):
        LatencySketch(gamma=1.02).merge(LatencySketch(gamma=1.05))
    with pytest.raises(ValueError):
        LatencySketch(lo=-1.0)


def test_latency_sketch_underflow_bin():
    sk = LatencySketch(lo=1e-6)
    sk.update(0.0)
    sk.update(-3.0)          # cannot happen for latencies; must not crash
    assert sk.count == 2
    assert sk.quantile(0.5) <= 0.0


# ------------------------------------------------------------- metrics --

def test_metrics_hub_keys_and_summaries():
    hub = MetricsHub()
    for i in range(10):
        hub.observe("cam0", "inference", 0.010 + 0.001 * i,
                    rung="two_stage", batch_size=4)
    hub.observe("cam1", "inference", 0.020, rung="one_stage", batch_size=2)
    assert len(hub) == 2
    key = MetricKey("cam0", "inference", "two_stage", 4)
    m = hub.get(key)
    assert m.count == 10
    assert m.mean == pytest.approx(0.0145)
    assert m.cv > 0
    rows = hub.table()
    assert [r["stream"] for r in rows] == ["cam0", "cam1"]
    assert set(rows[0]) >= {"count", "mean", "cv", "p50", "p95", "p99"}


def test_metrics_rollup_is_exact_merge():
    hub = MetricsHub()
    rng = np.random.default_rng(3)
    lats = {("cam0", "two_stage"): rng.exponential(0.01, 400),
            ("cam0", "one_stage"): rng.exponential(0.002, 300),
            ("cam1", "two_stage"): rng.exponential(0.02, 500)}
    for (stream, rung), xs in lats.items():
        for x in xs:
            hub.observe(stream, "inference", x, rung=rung)
    per_stream = hub.rollup(lambda k: k.stream)
    cam0 = np.concatenate([lats[("cam0", "two_stage")],
                           lats[("cam0", "one_stage")]])
    want = LatencySketch()
    want.extend(cam0)
    # rolled-up sketch == one sketch fed every cam0 observation
    assert per_stream["cam0"].sketch.to_dict() == want.to_dict()
    assert per_stream["cam0"].count == cam0.size
    assert per_stream["cam0"].mean == pytest.approx(cam0.mean())
    assert per_stream["cam0"].welford.std == pytest.approx(cam0.std(),
                                                           rel=1e-9)
    # rollup must not mutate the source buckets
    assert hub.get(MetricKey("cam0", "inference", "two_stage", 0)).count == 400


def test_observe_span_keys_on_span_tags():
    hub = MetricsHub()
    tr = SpanTracer(capacity=8)
    s = tr.record("step", 0.0, 0.25, stream="tenant3", rung="r",
                  batch_size=2, axis="model")
    hub.observe_span(s)
    m = hub.get(MetricKey("tenant3", "step", "r", 2))
    assert m.count == 1 and m.mean == pytest.approx(0.25)


# ------------------------------------------------------------ adapters --

def test_timeline_recorder_forwards_to_hub():
    from repro.core.timing import StageRecord

    hub = MetricsHub()
    rec = TimelineRecorder(metrics=hub, stream="cam0", rung="two_stage")
    r = StageRecord(stages={"read": 0.001, "inference": 0.004},
                    meta={"batch_size": 4.0})
    rec.add(r)
    assert hub.get(MetricKey("cam0", "read", "two_stage", 4)).count == 1
    inf = hub.get(MetricKey("cam0", "inference", "two_stage", 4))
    assert inf.mean == pytest.approx(0.004)
    e2e = hub.get(MetricKey("cam0", "end_to_end", "two_stage", 4))
    assert e2e.mean == pytest.approx(0.005)
    # the legacy recorder still works standalone
    assert rec.summary("read").mean == pytest.approx(0.001)


def test_stage_timer_forwards_spans_with_axis_tags():
    clock = SimClock()
    tr = SpanTracer(capacity=16, clock=clock.time)
    timer = StageTimer(clock=clock.time, tracer=tr,
                       tags={"stream": "decode", "tick": 7, "batch_size": 3})
    with timer.stage("read"):
        clock.advance(0.001)
    with timer.stage("inference"):
        clock.advance(0.004)
    with timer.stage("custom_stage"):
        clock.advance(0.002)
    rec = timer.finish()
    assert rec.end_to_end == pytest.approx(0.007)
    spans = {s.name: s for s in tr.spans()}
    assert spans["read"].axis == "io"
    assert spans["inference"].axis == "model"
    assert spans["custom_stage"].axis == "end_to_end"   # fallback
    assert all(s.stream == "decode" and s.tick == 7 and s.batch_size == 3
               for s in spans.values())
    assert spans["inference"].duration == pytest.approx(0.004)


# --------------------------------------------------------- attribution --

def _frames(rng, n, *, rung="r", contention=1.0, work=0, batch=4,
            segment="seg", base=0.010, noise=0.0):
    out = []
    for i in range(n):
        lat = base * contention + (noise * rng.standard_normal() if noise
                                   else 0.0)
        out.append(FrameSample(latency_s=float(lat), stream="cam0", tick=i,
                               segment=segment, scenario="city", rung=rung,
                               batch_size=batch, work=work,
                               contention=contention))
    return out


def test_attribution_shares_telescope_to_one():
    rng = np.random.default_rng(4)
    frames = (_frames(rng, 50, rung="a", noise=1e-3)
              + _frames(rng, 50, rung="b", base=0.02, noise=1e-3))
    att = attribute(frames)
    shares = sum(e["share"] for e in att.explained.values())
    assert shares == pytest.approx(1.0, abs=1e-9)
    assert att.n == 100 and att.order == ATTRIBUTION_ORDER
    # unexplained noise lands on the residual axis
    assert att.explained["end_to_end"]["variance"] > 0


def test_attribution_assigns_rung_variance_to_model():
    rng = np.random.default_rng(5)
    frames = (_frames(rng, 60, rung="two_stage", base=0.013)
              + _frames(rng, 60, rung="one_stage", base=0.007))
    att = attribute(frames)
    assert att.share("model") > 0.99
    # constant contention: hardware explains nothing (float epsilon only)
    assert att.share("hardware") == pytest.approx(0.0, abs=1e-12)
    assert att.table().startswith("variance attribution over 120 frames")


def test_attribution_assigns_contention_variance_to_hardware():
    rng = np.random.default_rng(6)
    frames = []
    for level in (1.0, 1.1, 1.2, 1.3):
        frames += _frames(rng, 40, contention=level)
    att = attribute(frames)
    assert att.share("hardware") > 0.95


def test_attribution_order_mediates_correlated_axes():
    """When the controller downgrades the rung *because* of contention,
    hardware-first attribution charges the shared variance to hardware;
    model-first (the mediated order) conditions the adaptation out
    first.  Both decompositions telescope to 1."""
    rng = np.random.default_rng(7)
    frames = (_frames(rng, 80, rung="two_stage", contention=1.0)
              + _frames(rng, 80, rung="one_stage", contention=1.3,
                        base=0.006))
    hw_first = attribute(frames)
    model_first = attribute(frames, order=MEDIATED_ORDER)
    assert hw_first.share("hardware") > 0.99
    assert model_first.share("model") > 0.99
    for att in (hw_first, model_first):
        total = sum(e["share"] for e in att.explained.values())
        assert total == pytest.approx(1.0, abs=1e-9)


def test_attribution_empty_and_errors():
    att = attribute([])
    assert att.n == 0 and att.total_variance == 0.0 and att.explained == {}
    with pytest.raises(ValueError, match="no grouping feature"):
        attribute([FrameSample(latency_s=0.01)], order=("end_to_end",))
    with pytest.raises(ValueError, match="unknown axis"):
        att.share("gpu")


def test_attribution_json_round_trip():
    rng = np.random.default_rng(8)
    att = attribute(_frames(rng, 30, noise=1e-4))
    d = json.loads(att.to_json())
    assert d["n"] == 30
    assert set(d["explained"]) == set(ATTRIBUTION_ORDER) | {"end_to_end"}


# ------------------------------------------------------------ dashboard --

def test_dashboard_renders_on_period():
    obs = Observatory(clock=SimClock().time)
    for i in range(12):
        obs.record("step", 0.0, 0.001 * (i + 1), stream="t0", axis="model")
    sink = io.StringIO()
    dash = obs.dashboard(period=5, sink=sink)
    rendered = [dash.step() for _ in range(12)]
    assert rendered.count(True) == 2            # steps 5 and 10
    assert dash.renders == 2
    out = sink.getvalue()
    assert "obs dashboard" in out and "t0" in out and "spans:" in out
    with pytest.raises(ValueError, match="period"):
        obs.dashboard(period=0)


def test_render_table_truncates_to_hottest_keys():
    hub = MetricsHub()
    for i in range(20):
        hub.observe(f"s{i:02d}", "step", 0.001, batch_size=i)
    text = render_table(hub, top=4)
    assert "... 16 more keys" in text


# ------------------------------------------- golden replay wiring ------

@pytest.fixture(scope="module")
def traced_golden():
    """One traced golden replay + one untraced replay on the same
    compiled scheduler (XLA compile paid once for the module)."""
    obs = Observatory()
    report_on, scheduler = golden_replay("urban_rush_hour", obs=obs)
    report_off, _ = golden_replay("urban_rush_hour", scheduler=scheduler)
    return {"obs": obs, "on": report_on, "off": report_off}


def test_tracing_never_perturbs_the_replay(traced_golden):
    """The observatory is pure observation: the traced report is byte-
    identical to the untraced one."""
    assert traced_golden["on"].to_json() == traced_golden["off"].to_json()


def test_golden_replay_emits_spans_on_virtual_time(traced_golden):
    obs = traced_golden["obs"]
    spans = obs.tracer.spans()
    assert spans and obs.tracer.dropped == 0
    ticks = [s for s in spans if s.name == "tick"]
    assert ticks
    # engine streams are tagged episode/rung
    assert all(s.stream.startswith("urban_rush_hour/") for s in ticks)
    # stage children tile their parent tick exactly
    for parent in ticks[:10]:
        kids = sorted((s for s in spans if s.parent == parent.seq),
                      key=lambda s: s.t0)
        assert kids, "tick span has no stage children"
        assert kids[0].t0 == pytest.approx(parent.t0)
        assert kids[-1].t1 == pytest.approx(parent.t1)
        for a, b in zip(kids, kids[1:]):
            assert a.t1 == pytest.approx(b.t0)
        assert {k.axis for k in kids} <= set(AXES)
    # virtual timeline: spans are on the SimClock, not wall time
    assert max(s.t1 for s in spans) < 1e4


def test_golden_replay_records_rung_switches(traced_golden):
    spans = traced_golden["obs"].tracer.spans()
    switches = [s for s in spans if s.name == "rung_switch"]
    # urban_rush_hour's density ramp forces fidelity changes
    assert switches
    assert all(s.axis == "model" and s.duration == 0.0 for s in switches)
    assert all(s.rung for s in switches)


def test_golden_replay_collects_frame_samples(traced_golden):
    obs, report = traced_golden["obs"], traced_golden["on"]
    assert len(obs.frames) == report.totals()["frames"]
    segs = {s.label for s in report.segments}
    assert {f.segment for f in obs.frames} <= segs
    assert all(f.latency_s > 0 for f in obs.frames)
    assert any(f.contention > 1.0 for f in obs.frames)


def test_golden_replay_trace_exports_clean(traced_golden):
    doc = traced_golden["obs"].chrome_trace(process_label="urban_rush_hour")
    assert validate_chrome_trace(doc) == []
    assert len(doc["traceEvents"]) > 0


def test_golden_replay_metrics_feed(traced_golden):
    hub = traced_golden["obs"].metrics
    assert len(hub) > 0
    per_stage = hub.rollup(lambda k: k.stage)
    assert "tick" in per_stage
    assert per_stage["tick"].count > 0


def test_contention_attribution_meets_hardware_floor(traced_golden):
    """Acceptance: >= 80% of the injected contention-segment variance is
    assigned to the hardware axis (after conditioning out the
    controller's rung adaptation, which contention itself triggers)."""
    att = contention_attribution(traced_golden["obs"])
    assert att.n > 0 and att.order == MEDIATED_ORDER
    injected = att.total_variance - att.explained["model"]["variance"]
    assert injected > 0
    assert att.explained["hardware"]["variance"] / injected >= 0.80


def test_golden_attribution_fixture(traced_golden, regen_golden):
    """The mediated contention attribution is a golden fixture: axis
    shares must stay within an absolute band of the checked-in values
    (regenerate intentionally with --regen-golden)."""
    att = contention_attribution(traced_golden["obs"])
    got = {"order": list(att.order), "n": att.n,
           "shares": {axis: round(e["share"], 6)
                      for axis, e in sorted(att.explained.items())}}
    path = GOLDEN_DIR / "urban_rush_hour.attribution.json"
    if regen_golden or not path.exists():
        if not regen_golden:
            pytest.fail(f"golden fixture {path} is missing — run "
                        f"`pytest --regen-golden` and commit the result")
        path.write_text(json.dumps(got, indent=2, sort_keys=True) + "\n")
        return
    want = json.loads(path.read_text())
    assert got["order"] == want["order"]
    assert set(got["shares"]) == set(want["shares"])
    assert got["n"] == pytest.approx(want["n"], rel=0.25)
    for axis, share in want["shares"].items():
        assert got["shares"][axis] == pytest.approx(share, abs=0.10), axis


def test_obs_smoke_cli_passes(tmp_path):
    """The CI obs-smoke step end-to-end: schema, drops, byte-identity,
    attribution floor, artifact."""
    out = tmp_path / "obs_trace.json"
    assert obs_main(["--episode", "urban_rush_hour",
                     "--out", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert validate_chrome_trace(doc) == []


# --------------------------------------------------------- sentinel ----

def test_sentinel_records_compiles_as_runtime_spans():
    clock = SimClock()
    tr = SpanTracer(capacity=16, clock=clock.time)

    @jax.jit
    def fresh(x):
        return x * 5 + 2

    x = jnp.ones(3)          # built outside the budgeted region
    jax.block_until_ready(x)
    with TraceSentinel(compile_budget=1, transfer_guard="allow",
                       tracer=tr) as sent:
        fresh(x)
    assert sent.report().compiles == 1
    compiles = [s for s in tr.spans() if s.name == "backend_compile"]
    assert len(compiles) == 1
    assert compiles[0].axis == "runtime"
    assert compiles[0].duration >= 0.0


def test_sentinel_without_tracer_stays_silent():
    @jax.jit
    def fresh(x):
        return x * 7 + 2

    x = jnp.ones(3)
    jax.block_until_ready(x)
    with TraceSentinel(compile_budget=1, transfer_guard="allow") as sent:
        fresh(x)
    assert sent.tracer is None
    assert sent.report().compiles == 1


# ------------------------------------------------------ multi-tenant ---

def test_multi_tenant_engine_emits_obs_events():
    from repro.configs import get_config
    from repro.models import Model
    from repro.runtime import (MultiTenantConfig, MultiTenantEngine,
                               RequestQueue, StreamRequest)

    cfg = get_config("rwkv6-3b", smoke=True).replace(num_layers=2,
                                                     vocab_size=64)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    obs = Observatory()
    eng = MultiTenantEngine(
        model, params,
        MultiTenantConfig(capacity=2, context=64, warmup_steps=0),
        obs=obs)
    eng.compile()
    q = RequestQueue()
    for t in range(2):
        q.push(StreamRequest(tenant=f"t{t}",
                             prompt=np.asarray([1, 2], np.int32),
                             max_new_tokens=3))
    eng.admit_from(q)
    while eng.active:
        eng.step()
    spans = obs.tracer.spans()
    admits = [s for s in spans if s.name == "admit"]
    assert len(admits) == 2
    assert {s.stream for s in admits} == {"t0", "t1"}
    assert all(s.axis == "runtime" for s in admits)
    # the shared decode step emits its stage timeline under obs_tag
    decode = [s for s in spans if s.name == "inference"]
    assert decode and all(s.stream == "decode" for s in decode)
    assert all(s.axis == "model" for s in decode)
    # per-tenant step metrics landed in the hub
    tenants = {k.stream for k in obs.metrics.keys() if k.stage == "step"}
    assert {"t0", "t1"} <= tenants


# ------------------------------------------------------- tvlint TV006 --

def _tv006(src: str):
    return [f.rule for f in lint_source(textwrap.dedent(src), "pkg/mod.py")
            if f.rule == "TV006" and not f.suppressed]


def test_tv006_still_flags_unfenced_interval():
    src = """
        import time
        import jax

        step = jax.jit(lambda x: x)

        def run_tick(x):
            t0 = time.perf_counter()
            out = step(x)
            return time.perf_counter() - t0
    """
    assert _tv006(src) == ["TV006"]


def test_tv006_fenced_span_cm_is_a_fence():
    src = """
        import time
        import jax

        step = jax.jit(lambda x: x)

        def run_tick(tracer, x):
            t0 = time.perf_counter()
            with tracer.span("step", axis="model", fence=lambda: out):
                out = step(x)
            return time.perf_counter() - t0
    """
    assert _tv006(src) == []


def test_tv006_unfenced_span_cm_is_not_a_fence():
    src = """
        import time
        import jax

        step = jax.jit(lambda x: x)

        def run_tick(tracer, x):
            t0 = time.perf_counter()
            with tracer.span("step", axis="model"):
                out = step(x)
            return time.perf_counter() - t0
    """
    assert _tv006(src) == ["TV006"]


def test_tv006_explicit_fence_false_is_not_a_fence():
    src = """
        import time
        import jax

        step = jax.jit(lambda x: x)

        def run_tick(tracer, x):
            t0 = time.perf_counter()
            with tracer.span("step", axis="model", fence=False):
                out = step(x)
            return time.perf_counter() - t0
    """
    assert _tv006(src) == ["TV006"]
