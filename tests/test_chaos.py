"""tvchaos: deterministic fault injection + graceful degradation.

Covers the compile-time plan contract (all randomness at compile, byte-
stable serialization), the recovery primitives (health machines, bounded
retry, force-degrade, dead-shard placement), and the episode-level
acceptance gates: fault-free chaos attach is byte-identical to the
committed goldens, a killed shard's streams fail over retrace-free
within the reseat bound, and the sensor storm degrades and *recovers*.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.anytime.cost import LadderCostModel
from repro.batched.fleet import FleetPlacer
from repro.chaos import (
    ChaosSpec,
    FaultClause,
    FaultInjector,
    FaultPlan,
    FleetResilience,
    ResilienceConfig,
    compile_plan,
    corrupt_frame,
    run_chaos_episode,
)
from repro.chaos.catalog import CHAOS_CATALOG, get_chaos_episode
from repro.scenarios import ScenarioReplayer, compile_trace, get_episode, replay_ladder
from repro.scenarios.golden import GOLDEN_CAPACITY, GOLDEN_EPISODES, GOLDEN_TICK_SCALE

REPO = Path(__file__).parent.parent
GOLDEN_DIR = Path(__file__).parent / "golden"

_STREAMS = ("cam_front", "cam_left", "cam_right")

_FLAKY_SPEC = ChaosSpec(
    name="flaky", description="probabilistic mix",
    clauses=(
        FaultClause(kind="sensor_stall", at=2, duration=6, probability=0.5),
        FaultClause(kind="nan_frame", at=1, duration=8,
                    streams=("cam_front",), probability=0.4),
        FaultClause(kind="step_fault", at=4, duration=3, count=2,
                    probability=0.6),
        FaultClause(kind="latency_spike", at=3, duration=4, scale=2.5),
        FaultClause(kind="shard_loss", at=5, duration=4, shard=1),
    ))


# ------------------------------------------------------------- plan ----

def test_clause_validation_rejects_bad_fields():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultClause(kind="gremlins", at=0)
    with pytest.raises(ValueError, match="at must be >= 0"):
        FaultClause(kind="sensor_stall", at=-1)
    with pytest.raises(ValueError, match="permanent"):
        FaultClause(kind="sensor_stall", at=0, duration=0)
    with pytest.raises(ValueError, match="probability"):
        FaultClause(kind="nan_frame", at=0, probability=0.0)
    with pytest.raises(ValueError, match="scale"):
        FaultClause(kind="latency_spike", at=0, scale=0.0)
    with pytest.raises(ValueError, match="count"):
        FaultClause(kind="step_fault", at=0, count=0)
    # permanent shard loss is legal: kill with no revive
    plan = compile_plan(
        ChaosSpec("perm", "", (FaultClause(kind="shard_loss", at=1,
                                           duration=0, shard=0),)),
        _STREAMS, 10, seed=0)
    assert plan.kills == {1: [0]} and plan.revives == {}


def test_compile_same_seed_byte_identical_different_seed_differs():
    a = compile_plan(_FLAKY_SPEC, _STREAMS, 12, seed=5)
    b = compile_plan(_FLAKY_SPEC, _STREAMS, 12, seed=5)
    c = compile_plan(_FLAKY_SPEC, _STREAMS, 12, seed=6)
    assert a.to_json() == b.to_json()
    assert a.to_json(indent=2) == b.to_json(indent=2)
    assert a.to_json() != c.to_json()


def test_compile_all_certain_spec_is_seed_independent():
    spec = ChaosSpec(
        name="certain", description="",
        clauses=(FaultClause(kind="sensor_stall", at=1, duration=2),
                 FaultClause(kind="latency_spike", at=0, duration=3,
                             scale=2.0)))
    a = compile_plan(spec, _STREAMS, 8, seed=1)
    b = compile_plan(spec, _STREAMS, 8, seed=99)
    # the seed is recorded in the plan metadata, but with no
    # probabilistic clause it never influences the compiled events
    assert a.to_dict()["events"] == b.to_dict()["events"]


def test_plan_round_trips_json_and_file(tmp_path):
    plan = compile_plan(_FLAKY_SPEC, _STREAMS, 12, seed=3)
    back = FaultPlan.from_json(plan.to_json())
    assert back.to_json() == plan.to_json()
    path = tmp_path / "plan.json"
    plan.save(path)
    assert FaultPlan.load(path).to_json() == plan.to_json()
    # spec round trip too
    assert ChaosSpec.from_dict(_FLAKY_SPEC.to_dict()) == _FLAKY_SPEC


def test_plan_lookup_tables_and_clipping():
    spec = ChaosSpec(
        name="tables", description="",
        clauses=(
            FaultClause(kind="shard_loss", at=2, duration=3, shard=1),
            FaultClause(kind="sensor_stall", at=0, duration=2,
                        streams=("cam_left",)),
            FaultClause(kind="step_fault", at=1, duration=1, count=3),
            # overlapping spikes compound multiplicatively
            FaultClause(kind="latency_spike", at=4, duration=2, scale=2.0),
            FaultClause(kind="latency_spike", at=5, duration=1, scale=3.0),
            # window extends past the horizon: clipped, not an error
            FaultClause(kind="nan_frame", at=5, duration=99,
                        streams=("cam_front",)),
        ))
    plan = compile_plan(spec, _STREAMS, 6, seed=0)
    assert plan.kills == {2: [1]}
    assert plan.revives == {5: [1]}
    assert plan.stalls == {0: {"cam_left"}, 1: {"cam_left"}}
    assert plan.step_faults == {1: 3}
    assert plan.latency == {4: 2.0, 5: 6.0}
    assert plan.nans == {5: {"cam_front"}}
    assert all(e.tick < 6 for e in plan.events)
    # a revive past the horizon never happens
    short = compile_plan(
        ChaosSpec("s", "", (FaultClause(kind="shard_loss", at=2,
                                        duration=10, shard=0),)),
        _STREAMS, 6, seed=0)
    assert short.kills == {2: [0]} and short.revives == {}


def test_empty_plan_is_inert():
    plan = FaultPlan.empty()
    assert plan.is_empty
    assert not plan.kills and not plan.stalls and not plan.latency
    assert FaultPlan.from_json(plan.to_json()).to_json() == plan.to_json()


# --------------------------------------------------------- recovery ----

def test_health_machine_full_lifecycle():
    res = FleetResilience(ResilienceConfig(quarantine_faults=3,
                                           probation_ticks=2,
                                           recover_ticks=3))
    sid = "cam_front"
    assert res.state(sid) == "healthy"
    assert res.note_fault(sid, tick=10) == "degrade"
    assert res.state(sid) == "degraded"
    # clean ticks below recover_ticks don't recover
    assert res.note_clean(sid, 11) is None
    assert res.note_clean(sid, 12) is None
    # a fault resets the clean streak
    assert res.note_fault(sid, 13) == "degrade"
    assert res.note_fault(sid, 14) == "quarantine"
    assert res.is_quarantined(sid)
    # quarantine dwells probation_ticks, then probation (degraded)
    assert res.age_quarantine(15) == []
    assert res.age_quarantine(16) == [sid]
    assert res.state(sid) == "degraded"
    # one more strike re-quarantines immediately (faults were kept)
    assert res.note_fault(sid, 17) == "quarantine"
    res.age_quarantine(18)
    res.age_quarantine(19)
    # full recovery: recover_ticks consecutive clean ticks
    assert res.note_clean(sid, 20) is None
    assert res.note_clean(sid, 21) is None
    healthy_after = res.note_clean(sid, 22)
    assert healthy_after is not None and healthy_after >= 0
    assert res.state(sid) == "healthy"
    # fault count reset: next fault degrades, not quarantines
    assert res.note_fault(sid, 23) == "degrade"


def test_step_fault_arming_is_consumed_per_attempt():
    res = FleetResilience()
    res.arm_step_faults(2)
    assert res.armed == 2
    assert res.take_step_fault() and res.take_step_fault()
    assert not res.take_step_fault()
    assert res.armed == 0


def test_resilience_config_validation():
    with pytest.raises(ValueError, match="watchdog_scale"):
        ResilienceConfig(watchdog_scale=1.0)
    with pytest.raises(ValueError, match="recover_ticks"):
        ResilienceConfig(recover_ticks=0)


def test_force_degrade_clamps_at_ladder_floor():
    from repro.anytime.controller import ContractController
    ladder = replay_ladder()
    ctl = ContractController(ladder)
    assert ctl._idx == 0
    assert ctl.force_degrade()
    assert ctl._idx == 1 and ctl.switches == 1
    assert ctl.force_degrade(steps=5)          # clamped to the floor
    assert ctl._idx == len(ladder) - 1
    assert not ctl.force_degrade()             # already at the floor
    with pytest.raises(ValueError):
        ctl.force_degrade(steps=0)


def test_placer_avoids_dead_shards():
    ladder = replay_ladder()
    placer = FleetPlacer(LadderCostModel(ladder), n_shards=2)
    placer.mark_dead(1)
    # only shard 0 is a candidate even when emptier slots sit on shard 1
    assert placer.place("two_stage", [2, 0], slots_per_shard=4) == 0
    with pytest.raises(RuntimeError, match="dead"):
        placer.place("two_stage", [4, 0], slots_per_shard=4)
    # rebalance never proposes moves onto (or off) a dead shard
    assert placer.rebalance("two_stage", [4, 0]) is None
    placer.mark_alive(1)
    assert placer.rebalance("two_stage", [4, 0]) == (0, 1)


# --------------------------------------------------------- injector ----

def test_corrupt_frame_is_nonfinite_and_pure():
    from repro.perception.data import SceneConfig, generate_scene
    scene = generate_scene(SceneConfig(scenario="city", seed=3), 0)
    bad = corrupt_frame(scene)
    assert not np.all(np.isfinite(bad.image))
    assert np.all(np.isfinite(scene.image))    # original untouched


def test_filter_scenes_stalls_and_corrupts_preserving_order():
    from repro.perception.data import SceneConfig, generate_scene
    plan = compile_plan(
        ChaosSpec("f", "", (
            FaultClause(kind="sensor_stall", at=0, duration=1,
                        streams=("cam_left",)),
            FaultClause(kind="nan_frame", at=0, duration=1,
                        streams=("cam_front",)))),
        _STREAMS, 4, seed=0)
    inj = FaultInjector(plan)
    scenes = {sid: generate_scene(SceneConfig(seed=i), 0)
              for i, sid in enumerate(_STREAMS)}
    out = inj.filter_scenes(0, scenes)
    assert list(out) == ["cam_front", "cam_right"]   # caller order kept
    assert not np.all(np.isfinite(out["cam_front"].image))
    quiet = inj.filter_scenes(1, scenes)       # no faults at tick 1
    assert list(quiet) == list(scenes)
    assert all(quiet[sid] is scenes[sid] for sid in scenes)
    assert len(inj.ledger) == 2


# ----------------------------------------------------- episode level ---

@pytest.fixture(scope="module")
def sched_pool():
    """One compiled scheduler shared by every replay in this module."""
    return {"sched": None}


def test_chaos_catalog_names_and_bases():
    assert set(CHAOS_CATALOG) == {"shard_loss_rush_hour",
                                  "sensor_stall_storm"}
    for ep in CHAOS_CATALOG.values():
        assert ep.base in ("urban_rush_hour", "rain_onset_clear")
    with pytest.raises(KeyError, match="unknown chaos episode"):
        get_chaos_episode("nope")


@pytest.mark.parametrize("name", sorted(GOLDEN_EPISODES))
def test_fault_free_chaos_attach_matches_golden_bytes(sched_pool, name):
    """Chaos machinery attached with an empty plan is pure observation:
    the report is byte-identical to the committed golden fixture."""
    trace = compile_trace(get_episode(name), seed=GOLDEN_EPISODES[name],
                          tick_scale=GOLDEN_TICK_SCALE)
    rep = ScenarioReplayer(trace, scheduler=sched_pool["sched"],
                           capacity=(GOLDEN_CAPACITY
                                     if sched_pool["sched"] is None else None),
                           chaos=FaultPlan.empty())
    sched_pool["sched"] = rep.scheduler
    got = rep.run()
    assert got.chaos is None and "chaos" not in got.to_dict()
    want = (GOLDEN_DIR / f"{name}.json").read_text()
    assert got.to_json(indent=2) + "\n" == want


@pytest.fixture(scope="module")
def storm_runs(sched_pool):
    if sched_pool["sched"] is None:
        # ensure the shared scheduler exists at the canonical capacity
        trace = compile_trace(get_episode("urban_rush_hour"), seed=7,
                              tick_scale=GOLDEN_TICK_SCALE)
        rep = ScenarioReplayer(trace, capacity=GOLDEN_CAPACITY)
        rep.run()
        sched_pool["sched"] = rep.scheduler
    runs = []
    for _ in range(2):
        report, replayer, plan = run_chaos_episode(
            "sensor_stall_storm", scheduler=sched_pool["sched"])
        sched_pool["sched"] = replayer.scheduler
        runs.append((report, replayer, plan))
    return runs


def test_chaos_replay_same_seed_is_byte_identical(storm_runs):
    (a, _, plan_a), (b, _, plan_b) = storm_runs
    assert plan_a.to_json() == plan_b.to_json()
    assert a.to_json() == b.to_json()
    assert a.chaos is not None                 # faults actually fired


def test_sensor_stall_storm_degrades_and_recovers(storm_runs):
    report, replayer, plan = storm_runs[0]
    counts = report.chaos["counts"]
    # every fault family fired: stalls/NaNs (injected), watchdog trips on
    # the latency spike, transient step faults were retried through
    assert counts["fault_inject"] >= 10
    assert counts.get("nan_drop", 0) >= 1
    assert counts.get("watchdog", 0) >= 1
    assert counts.get("retry", 0) >= 1
    # and the fleet *recovered*: degraded streams returned to healthy
    # within a bounded number of ticks
    recovery = report.chaos["recovery_ticks"]
    assert recovery and max(recovery) <= 20
    # chaos never retraced an engine
    for eng in replayer.scheduler.engines.values():
        assert eng.trace_count <= 1
    # the report (with its chaos block) stays strict JSON
    json.loads(report.to_json(),
               parse_constant=lambda s: pytest.fail(f"bare {s}"))


def test_shard_loss_rush_hour_two_device_failover(tmp_path):
    """The acceptance gate, end to end in a child with 2 forced host
    devices: kill a shard mid-episode, every seated stream fails over
    within 3 ticks, zero backend compiles during the whole replay
    (TraceSentinel compile_budget=0), populated failover ledger."""
    out = tmp_path / "chaos.json"
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=2"])
    env["JAX_PLATFORMS"] = "cpu"      # a forced-host-device rehearsal
    env["PYTHONPATH"] = (os.path.join(REPO, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.chaos",
         "--episode", "shard_loss_rush_hour", "--mesh", "data=2",
         "--check", "--reseat-bound", "3", "--json-out", str(out)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(out.read_text())
    assert doc["gates"]["checked"] and doc["gates"]["problems"] == []
    assert doc["n_shards"] == 2
    assert doc["ledger_counts"]["failover"] >= 1
    assert doc["reseat_ticks"] is not None and doc["reseat_ticks"] <= 3
    assert max(doc["trace_counts"].values()) == 1
    assert doc["report"]["chaos"]["counts"]["failover"] >= 1
