"""Rehearse the harness on the CPU: each one-chip cell's loop for about a
second, the shape of its last line, the refusal of a machine without a
TPU, and a cell, traffic mix and metric added as files alone."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _e2e_names(cell: str) -> set:
    return {m["name"] for m in SPEC["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_cell_loop_on_cpu(on_cpu, run_cell, cell):
    res = run_cell(cell, seconds=1.0)
    assert KEYS <= set(res)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == _e2e_names(cell)
    for name, m in res["metrics"].items():
        assert m["value"] > 0, name
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == 1
    assert res["checks"]["window_compiles"]["value"] == 0


def test_traced_run_reports_per_layer_metrics(on_cpu, fake_trace, run_cell):
    cell = "cam8-urban-top"
    res = run_cell(cell, seconds=0.5, trace=1)
    want = {m["name"] for m in SPEC["per_layer"]
            if cell in m.get("workloads", [])}
    assert set(res["metrics"]) == want
    assert res["device"]["busy_s"] == fake_trace.busy_s
    assert res["device"]["window_s"] == fake_trace.window_s
    assert res["breakdown"]["device_ops"] == [["fusion.1", 0.2]]
    assert 0 < res["metrics"]["step_roofline"]["value"] < 100
    assert 0 < res["metrics"]["step_mfu_pct"]["value"] < 100


def test_harness_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cam8-road-floor",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "not a TPU" in p.stderr


def test_addition_needs_no_edit(on_cpu, fake_trace, run_cell, tmp_path,
                                monkeypatch):
    """A new configuration, traffic mix, metric and cell, added as files
    and entries in a copy, run without an edit to any existing file."""
    from bench import run as bench

    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((ROOT / "bench/configs/det3x16-96x320-cam8.json").read_text())
    conf.update(name="det3x16-96x320-cam4", streams=4, capacity=4)
    (tmp_path / "bench/configs/det3x16-96x320-cam4.json").write_text(json.dumps(conf))
    # two rung buckets a tick, even streams on the top rung and odd ones
    # on the floor: a split no committed cell serves
    (tmp_path / "bench/traffic/suburb.json").write_text(json.dumps({
        "loop": "closed", "budget": "split",
        "mix": [["residential", 25.0], ["road", 0.0]],
        "pool": 8, "warm_ticks": 2, "sample": 32}))
    (tmp_path / "bench/limits/cam4-suburb.json").write_text(
        (ROOT / "bench/limits/cam8-urban-top.json").read_text())
    (tmp_path / "bench/metrics/ticks_served.py").write_text(
        "def read(run):\n    return float(len(run.ticks))\n")
    spec["configs"].append({"name": "det3x16-96x320-cam4", "source": "x",
                            "file": "bench/configs/det3x16-96x320-cam4.json",
                            "reduced": ["streams"], "why": "x"})
    spec["workloads"].append({"name": "cam4-suburb", "config": "det3x16-96x320-cam4",
                              "traffic": "suburb", "chips": 1, "why": "x"})
    spec["per_layer"].append({
        "name": "ticks_served", "unit": "ticks", "better": "higher",
        "source": "host_clock", "layer": "bucketing",
        "moves": "frames_per_s", "workloads": ["cam4-suburb"]})
    spec["end_to_end"][0]["workloads"].append("cam4-suburb")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(bench, "ROOT", tmp_path)

    res = run_cell("cam4-suburb", seconds=0.41, trace=0)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"frames_per_s", "frame_p99_ms", "setup_s"}
    assert res["attempted"] > 0 and res["attempted"] % 4 == 0
    assert res["failed"] == 0
    res = run_cell("cam4-suburb", seconds=0.3, trace=1)
    assert res["metrics"]["ticks_served"]["value"] > 0
