"""Fixtures that run the benchmark harness on the CPU.

The harness refuses any platform but a TPU.  These tests steer that check
themselves: they replace ``devices_for`` and the peaks lookup on the
imported module, and leave the harness's own code path untouched.
"""
import json

import pytest

from bench import model_flops
from bench import run as bench
from bench.trace_reduce import TraceSummary

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2 ** 32 + 17


@pytest.fixture
def on_cpu(monkeypatch):
    """The harness on the CPU.  The persistent compile cache stays as the
    test session set it, so other tests in this process are unaffected."""
    import jax

    import repro.launch.compile_cache as compile_cache

    monkeypatch.setattr(bench, "devices_for",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(model_flops, "peaks", lambda kind: V5E)
    monkeypatch.setattr(compile_cache, "configure_compile_cache", lambda: "")
    return bench


@pytest.fixture
def fake_trace(monkeypatch):
    """A traced run on the CPU has no device plane: stand in a summary."""
    import bench.trace_reduce as tr

    summary = TraceSummary(devices=1, window_s=1.0, busy_s=0.25,
                           idle_pct=75.0, op_s={"fusion.1": 0.2},
                           module_s={"jit_counted_step": 0.2},
                           idle_gaps={"bench.tick": 0.5})
    monkeypatch.setattr(tr, "reduce_file", lambda path: summary)
    monkeypatch.setattr(tr, "find_xplane", lambda d: "")
    return summary


@pytest.fixture
def run_cell(capsys):
    """``run_cell(workload, seconds, trace, seed)``: run the harness's
    ``main`` and return its last stdout line."""
    def go(workload: str, seconds: float = 0.5, trace: int = 0,
           seed: int = SEED) -> dict:
        rc = bench.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)])
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0
        return json.loads(out[-1])
    return go
