"""Fleet sharding: mesh helpers, slot-block specs, the cost-driven
placer, engine shard bookkeeping, and the bugfix regressions that rode
along with the mesh work (oversubscription factoring, non-finite sketch
samples, the ``warm()`` mutable default, strict-JSON reports).

Real multi-axis meshes cannot be built on the 1-device CI host, so the
pure spec-mapping tests drive ``distributed.sharding`` with a stub mesh
exposing only what those functions read (``.shape`` and
``.axis_names``); the one test that needs *actual* multi-device
execution forces ``--xla_force_host_platform_device_count=2`` into a
child process, exactly like ``benchmarks/fleet.py``.
"""
from __future__ import annotations

import inspect
import json
import math
import os
import subprocess
import sys
import types

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.anytime.cost import LadderCostModel
from repro.batched.fleet import FleetPlacer
from repro.batched.scheduler import RungBucketScheduler
from repro.core.stats import json_num
from repro.distributed.sharding import (
    Ruleset,
    _data_or_replicated,
    axis_size,
    data_shards,
    decode_state_spec,
    slot_batch_spec,
)
from repro.launch.mesh import make_local_mesh, parse_mesh_spec
from repro.obs.dashboard import render_table
from repro.obs.export import to_chrome_trace
from repro.obs.metrics import MetricsHub, StageMetrics
from repro.obs.sketch import LatencySketch
from repro.obs.span import SpanTracer
from repro.scenarios.replay import ScenarioReplayer, replay_ladder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    """Just enough mesh for the pure spec-mapping helpers: they read
    only ``.shape`` (axis name -> size) and ``.axis_names``."""

    def __init__(self, shape: dict):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


# ------------------------------------------------------------- mesh CLI --
def test_parse_mesh_spec():
    assert parse_mesh_spec("data=4") == {"data": 4}
    assert parse_mesh_spec("data=4,model=2") == {"data": 4, "model": 2}
    assert parse_mesh_spec(" data = 8 ") == {"data": 8}


@pytest.mark.parametrize("bad", ["pod=2", "data=x", "", "data"])
def test_parse_mesh_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_mesh_spec(bad)


def test_make_local_mesh_factors_down_preserving_model():
    # regression: oversubscribed data must shrink to n // model, never
    # silently collapse the model axis
    mesh = make_local_mesh(data=4, model=1)
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert mesh.axis_names == ("data", "model")


def test_make_local_mesh_model_overflow_is_an_error():
    # model encodes the program partition; it cannot be quietly shrunk
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_local_mesh(data=1, model=2)


def test_make_local_mesh_rejects_nonpositive_axes():
    with pytest.raises(ValueError):
        make_local_mesh(data=0)


# ----------------------------------------------------- sharding helpers --
def test_data_shards():
    assert data_shards(None) == 1
    assert data_shards(make_local_mesh(data=1)) == 1
    assert data_shards(FakeMesh({"data": 4, "model": 2})) == 4
    assert data_shards(FakeMesh({"model": 2})) == 1  # no data axis


def test_slot_batch_spec():
    assert slot_batch_spec(None, 8) == P()
    mesh = FakeMesh({"data": 2})
    assert slot_batch_spec(mesh, 8) == P("data")
    with pytest.raises(ValueError, match="divisible"):
        slot_batch_spec(mesh, 7)


def test_data_or_replicated_tuple_prefix_fallback():
    mesh = FakeMesh({"pod": 2, "data": 4})
    rules = Ruleset((("batch", ("pod", "data")),))
    assert axis_size(mesh, ("pod", "data")) == 8
    # divides the full product -> both axes
    assert _data_or_replicated(mesh, rules, 8) == ("pod", "data")
    # divides only the ("pod",) prefix -> single-axis fallback
    assert _data_or_replicated(mesh, rules, 2) == "pod"
    # divides nothing -> replicated
    assert _data_or_replicated(mesh, rules, 3) is None


def test_decode_state_spec_gqa_deficit_shards_slots():
    # MQA: 1 kv head cannot shard over model=2, so the KV cache's slots
    # dim takes the model axis instead (flash-decode partitioning)
    mesh = FakeMesh({"data": 2, "model": 2})
    cfg = types.SimpleNamespace(num_kv_heads=1, head_dim=4, d_inner=16)
    rules = Ruleset((("batch", "data"), ("kv_heads", None), ("mlp", "model")))
    kv_cache = np.zeros((2, 2, 8, 1, 4), np.float32)   # (L, B, slots, K, D)
    spec = decode_state_spec(cfg, mesh, rules, kv_cache)
    assert spec == P(None, "data", "model", None, None)
    # ragged slots (not divisible by model) stay replicated
    ragged = np.zeros((2, 2, 7, 1, 4), np.float32)
    assert decode_state_spec(cfg, mesh, rules, ragged) == P(
        None, "data", None, None, None)


def test_ruleset_with_overrides():
    base = Ruleset((("batch", "data"), ("mlp", "model")))
    out = base.with_overrides(mlp=None, vocab="model")
    assert out.lookup("mlp") is None
    assert out.lookup("vocab") == "model"
    assert out.lookup("batch") == "data"
    assert base.lookup("mlp") == "model"   # frozen original untouched


# ------------------------------------------------- sketch dropped bin --
def test_sketch_counts_nonfinite_as_dropped():
    sk = LatencySketch()
    for x in (float("nan"), float("inf"), float("-inf")):
        sk.update(x)
    assert sk.count == 0 and sk.dropped == 3
    sk.update(1e-3)
    assert sk.count == 1
    assert math.isfinite(sk.quantile(0.5))
    assert sk.to_dict()["dropped"] == 3


def test_sketch_dropped_survives_merge_and_copy():
    a, b = LatencySketch(), LatencySketch()
    a.update(float("nan"))
    b.update(float("nan"))
    b.update(2e-3)
    a.merge(b)
    assert a.dropped == 2 and a.count == 1
    assert a.copy().dropped == 2


def test_stage_metrics_keeps_welford_finite():
    sm = StageMetrics()
    sm.update(1e-3)
    sm.update(float("nan"))
    assert sm.count == 1 and sm.dropped == 1
    assert sm.mean == pytest.approx(1e-3)


def test_dashboard_surfaces_dropped_samples():
    hub = MetricsHub()
    hub.observe("cam0", "inference", float("nan"))
    hub.observe("cam0", "inference", 1e-3)
    text = render_table(hub)
    assert "non-finite samples dropped: 1" in text


# ------------------------------------------- warm() default + reports --
def test_warm_default_is_none_sentinel():
    # regression: a SceneConfig() default instance would be shared (and
    # mutable) across every scheduler; tvlint TV007 now flags the pattern
    assert (inspect.signature(RungBucketScheduler.warm)
            .parameters["probe_cfg"].default is None)


def test_json_num_sanitizes_report_floats():
    assert json_num(float("nan")) is None
    assert json_num(float("inf")) is None
    assert json_num(None) is None
    assert json_num(0.12345678949) == 0.123456789
    json.dumps({"x": json_num(float("nan"))}, allow_nan=False)


# ------------------------------------------------------- fleet placer --
@pytest.fixture(scope="module")
def placer2():
    return FleetPlacer(LadderCostModel(replay_ladder()), 2)


def test_placer_seats_on_cheapest_shard(placer2):
    # prior-mode cost is monotone in batch size -> emptier shard wins
    assert placer2.place("two_stage", [2, 0], 4) == 1
    assert placer2.place("two_stage", [0, 0], 4) == 0   # tie -> lower index
    assert placer2.place("two_stage", [1, 4], 4) == 0   # full shard excluded


def test_placer_raises_when_fleet_full(placer2):
    with pytest.raises(RuntimeError, match="full"):
        placer2.place("two_stage", [4, 4], 4)


def test_placer_validates_occupancy_arity(placer2):
    with pytest.raises(ValueError):
        placer2.place("two_stage", [1], 4)


def test_placer_rebalance_threshold(placer2):
    assert placer2.rebalance("two_stage", [3, 1]) == (0, 1)
    assert placer2.rebalance("two_stage", [1, 3]) == (1, 0)
    assert placer2.rebalance("two_stage", [2, 1]) is None   # skew of 1 is fine
    assert placer2.rebalance("two_stage", [2, 2]) is None
    one = FleetPlacer(placer2.cost, 1)
    assert one.rebalance("two_stage", [4]) is None


# --------------------------------------------- engine shard accounting --
@pytest.fixture(scope="module")
def engine1():
    from repro.batched.engine import BatchedPerceptionEngine
    from repro.perception.pipelines import build_pipeline
    return BatchedPerceptionEngine(
        build_pipeline("early_exit", pad=False), capacity=4)


def test_engine_single_shard_bookkeeping(engine1):
    eng = engine1
    eng.reset()
    assert eng.n_shards == 1 and eng.slots_per_shard == eng.capacity
    eng.join("cam0")
    eng.join("cam1", shard=0)           # explicit seat on the only shard
    assert eng.shard_of("cam0") == 0
    assert eng.shard_occupancy() == [2]
    assert eng.n_free == 2
    with pytest.raises(ValueError, match="out of range"):
        eng.join("cam2", shard=1)
    st = eng.migrate("cam0", 0)          # same-shard migrate is a no-op
    assert st.slot == eng.active["cam0"].slot
    with pytest.raises(ValueError, match="out of range"):
        eng.migrate("cam0", 1)
    eng.leave("cam0")
    eng.leave("cam1")
    assert eng.n_free == eng.capacity and eng.shard_occupancy() == [0]


def test_engine_join_drains_free_slots(engine1):
    eng = engine1
    eng.reset()
    for i in range(eng.capacity):
        eng.join(f"cam{i}")
    with pytest.raises(RuntimeError, match="no free slot"):
        eng.join("overflow")
    eng.reset()


# ------------------------------------------------------- span shard tag --
def test_span_shard_tag_reaches_chrome_trace():
    tr = SpanTracer()
    tagged = tr.record("shard_serve", 0.0, 1e-3, shard=2)
    plain = tr.record("serve", 0.0, 1e-3)
    assert tagged.shard == 2 and plain.shard == -1
    doc = to_chrome_trace(tr.spans())
    by_name = {e["name"]: e for e in doc["traceEvents"]
               if e.get("ph") == "X"}
    assert by_name["shard_serve"]["args"]["shard"] == 2
    assert "shard" not in by_name["serve"]["args"]


# --------------------------------------- 1-shard mesh == meshless golden --
def _reject_constant(name):
    raise ValueError(f"non-strict JSON constant {name!r} in report")


def test_one_shard_mesh_replay_byte_identical():
    """A data=1 mesh must leave replay reports byte-identical to the
    meshless goldens: every sharded behaviour (placer seating, modeled
    max-over-shards cost, shard spans) is gated on n_shards > 1."""
    from repro.scenarios.catalog import get_episode
    from repro.scenarios.trace import compile_trace

    ladder = replay_ladder(["two_stage", "early_exit@0.5"])
    trace = compile_trace(get_episode("rain_onset_clear"), seed=11,
                          tick_scale=0.25)
    plain = ScenarioReplayer(trace, ladder=replay_ladder(
        ["two_stage", "early_exit@0.5"]), capacity=4).run()
    sharded = ScenarioReplayer(trace, ladder=ladder, capacity=4,
                               mesh=make_local_mesh(data=1)).run()
    assert sharded.to_json(indent=2) == plain.to_json(indent=2)
    # reports must stay strict JSON (no NaN/Infinity literals)
    json.loads(plain.to_json(), parse_constant=_reject_constant)


# --------------------------------------------- real 2-device fleet run --
def test_fleet_serve_on_two_forced_devices(tmp_path):
    """End to end in a child process with 2 forced host devices: the
    serve --fleet path builds a data=2 mesh, seats streams across both
    shards, and every rung engine stays retrace-free."""
    out = tmp_path / "fleet.json"
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=2"])
    env["JAX_PLATFORMS"] = "cpu"      # a forced-host-device rehearsal
    env["PYTHONPATH"] = (os.path.join(REPO, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--fleet",
         "--streams", "4", "--mesh", "data=2", "--ticks", "3",
         "--slo-ms", "200", "--json-out", str(out)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(out.read_text())
    assert doc["devices"] == 2 and doc["n_shards"] == 2
    assert doc["frames"] == 4 * 3
    # no rung engine retraced under sharded churn
    assert max(doc["trace_counts"].values()) == 1
    # the placer spread the 4 streams across both shard slot blocks
    for occ in doc["shard_occupancy"].values():
        assert len(occ) == 2 and occ[0] == occ[1]
