"""``correct`` comes out false when the timed path is broken underneath the
harness, and the control (the reference in bfloat16, in the program's
place) fails the limits the cells hold the program to."""
import json
from pathlib import Path

import pytest

import faults
from bench import run as bench

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("fault", [faults.shift_answers, faults.drop_half,
                                   faults.stale_state],
                         ids=["_shift_answers", "_drop_half_the_batch",
                              "_stale_state"])
@pytest.mark.parametrize("cell", ["cam8-urban-top", "cam8-road-floor"])
def test_broken_timed_path_is_not_correct(on_cpu, run_cell, monkeypatch,
                                          fault, cell):
    monkeypatch.setattr(*fault())
    res = run_cell(cell, seconds=0.3)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits(on_cpu, cell):
    """The cell's reference in bfloat16 against the same reference in
    float32, on the rungs and scenes the cell serves, breaks at least one
    of its limits; the float32 reference against itself breaks none."""
    (ok, checks), (sound, sound_checks) = faults.control_verdicts(
        bench, bench.load_cell(cell))
    assert not ok, checks
    assert sound, sound_checks
