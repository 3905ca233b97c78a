"""``correct`` comes out false when the timed path is broken underneath the
harness, and the control (the reference in bfloat16, in the program's
place) fails the limits the cells hold the program to."""
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as bench
from bench.calibrate import control_numbers

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def _shift_answers(monkeypatch):
    """Every served frame's boxes moved by 3 px where the host post
    produces them."""
    import dataclasses

    from repro.batched.engine import BatchedPerceptionEngine

    post = BatchedPerceptionEngine._post

    def shifted(self, host, active_mask):
        return [o if o is None else dataclasses.replace(o, boxes=o.boxes + 3)
                for o in post(self, host, active_mask)]

    monkeypatch.setattr(BatchedPerceptionEngine, "_post", shifted)


def _drop_half_the_batch(monkeypatch):
    """The fused step blanks the upper half of its slots."""
    import repro.batched.engine as engine

    program = engine.fused_batch_program

    def half(built):
        step = program(built)

        def run(raw):
            keep = (jnp.arange(raw.shape[0]) < raw.shape[0] // 2)
            return step(raw * keep[:, None, None, None])
        return run

    monkeypatch.setattr(engine, "fused_batch_program", half)


def _stale_state(monkeypatch):
    """The resident batch keeps its first frames: later ticks upload
    nothing and the step serves the old state again."""
    from repro.batched.executor import PipelinedExecutor

    submit = PipelinedExecutor.submit

    def stale(self, slot_frames, payload=None):
        fresh = getattr(self, "_stale_seen", False)
        self._stale_seen = True
        return submit(self, {} if fresh else slot_frames, payload)

    monkeypatch.setattr(PipelinedExecutor, "submit", stale)


@pytest.mark.parametrize("fault", [_shift_answers, _drop_half_the_batch,
                                   _stale_state])
@pytest.mark.parametrize("cell", ["cam8-urban-top", "cam8-road-floor"])
def test_broken_timed_path_is_not_correct(on_cpu, run_cell, monkeypatch,
                                          fault, cell):
    fault(monkeypatch)
    res = run_cell(cell, seconds=0.3)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits(on_cpu, cell):
    """The reference in bfloat16 against the float32 reference, on the
    rungs the cell serves, breaks at least one of its limits; the float32
    reference against itself breaks none."""
    from bench.reference import Reference, weights_key

    limits = json.loads((ROOT / f"bench/limits/{cell}.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/det3x16-96x320-cam8.json").read_text())
    traffic = json.loads((ROOT / "bench/traffic/rush-split.json")
                         .read_text())
    seed = 2 ** 31 + 99
    pools = bench.fleet_pools(seed, 8, traffic["mix"], 8)
    cell_entry = {w["name"]: w for w in SPEC["workloads"]}[cell]
    used = bench.rungs_used(
        json.loads((ROOT / f"bench/traffic/{cell_entry['traffic']}.json")
                   .read_text()), cfg["ladder"])
    frames = [bench.Frame(i, j, 0.0, rung=r)
              for r in used for i in range(8) for j in range(8)]
    run = bench.Run(cell={"config": cfg}, seconds=1.0, t_open=0.0,
                    t_close=1.0, ticks=[], frames=frames, ladder=used,
                    chips=1, peak={}, pools=pools)
    hi = Reference(cfg, weights_key())
    lo = Reference(cfg, weights_key(), jnp.bfloat16)
    ok, checks = bench.verdict(control_numbers(run, frames, lo, hi), limits)
    assert not ok, checks
    _fill_outputs(run, hi)
    ok, checks = bench.verdict(bench.check(run, frames, hi), limits)
    assert ok, checks


def _fill_outputs(run, ref):
    """Give every frame the float32 reference's own answer."""
    from repro.perception.pipelines import FrameOutput

    for rung in sorted({f.rung for f in run.frames}):
        fs = [f for f in run.frames if f.rung == rung]
        images = np.stack([run.pools[f.stream][f.scene].image for f in fs])
        for f, det in zip(fs, ref.detect(rung, images)):
            f.out = FrameOutput(boxes=np.asarray(det.boxes), num_objects=0.0,
                                num_proposals=det.n_proposals)
