"""CPU rehearsal of ``chip_smoke.py`` and of the compile cache it places.

The script itself refuses every platform but a TPU; these tests call its
phase functions directly on CPU devices with a few ticks, so each change
to the serving path is rehearsed against the script's checks (every rung
serves, no compile in the timed window, one trace per rung, finite
outputs, agreement with the reference).
"""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.perception.pipelines import FrameOutput

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phase_rehearsal_on_cpu():
    smoke, lines = _chip_smoke(), []
    out = smoke.serve_one_chip(jax.devices("cpu")[0], ticks=4,
                               log=lines.append)
    assert out["max_box_dev_px"] <= smoke.BOX_TOL_PX
    text = "\n".join(lines)
    assert "depth 1: frames per rung" in text
    assert "depth 2: frames per rung" in text
    assert text.count("TraceSentinel[ok] compiles=0/0") == 2


_A = [0.0, 0.0, 16.0, 20.0]
_B = [0.0, 6.8, 16.0, 26.8]          # IoU with _A just under 0.5
_C = [50.0, 100.0, 66.0, 120.0]      # overlaps neither


@pytest.mark.parametrize("served, ref, n_prop, passes", [
    ([_A, _B, _C], [_A, _B, _C], 3, True),
    ([[a + 0.5 for a in _A], _B], [_A, _B], 3, True),    # drift in tolerance
    ([_A, _C], [_A, _B, _C], 3, True),     # _B at the NMS boundary
    ([_A, _B], [_A, _B, _C], 3, False),    # _C lost: NMS cannot explain it
    ([_A, _B, _C], [_A, _B, _C], 4, False),  # proposal counts differ
], ids=["same", "drift", "nms_flip", "lost_box", "proposals"])
def test_compare_accepts_only_drift_and_nms_boundary_flips(
        served, ref, n_prop, passes):
    smoke = _chip_smoke()

    def out(boxes, n):
        return FrameOutput(np.asarray(boxes, np.float32), float(len(boxes)),
                           float(n))

    # flips are rare among the frames of a run: one clean frame of ten
    # boxes keeps the paired share above MIN_PAIRED
    clean = out([[0.0, 30.0 * i, 16.0, 30.0 * i + 20.0] for i in range(10)], 10)
    frames = [(out(served, n_prop), out(ref, 3), "frame"),
              (clean, clean, "clean")]
    if passes:
        assert smoke._agreement(frames, "t", log=lambda _: None) <= 0.5
    else:
        with pytest.raises(AssertionError):
            smoke._agreement(frames, "t", log=lambda _: None)


def test_agreement_refuses_a_systematic_shift():
    """Every box 3 px off overlaps its twin enough to pass as an NMS flip
    frame by frame; the paired share still catches it."""
    smoke = _chip_smoke()
    boxes = np.asarray([_A, _C], np.float32)
    frames = [(FrameOutput(boxes + 3.0, 2.0, 2.0), FrameOutput(boxes, 2.0, 2.0),
               "frame")]
    with pytest.raises(AssertionError, match="boxes pair"):
        smoke._agreement(frames, "t", log=lambda _: None)


def test_fleet_phase_rehearsal_on_four_forced_host_devices():
    """The ``--chips 4`` phase on four forced CPU devices, in a child (the
    device count is fixed when JAX starts): the data=4 mesh is real,
    every shard seats streams, outputs agree with the one-shard run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import jax, chip_smoke; "
            "chip_smoke.serve_fleet(jax.devices(), ticks=2)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "data=4 vs data=1: 64 frames, proposal counts equal" in proc.stdout


def test_script_refuses_a_platform_without_tpu(tmp_path):
    """No CPU fallback: off the TPU the script exits non-zero and prints
    no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU found" in proc.stderr


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import configure_compile_cache
where = configure_compile_cache()
if {compile}:
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(where, jax.config.jax_compilation_cache_dir)
"""


def _cache_probe(compile_: bool, cache_dir=None) -> list[str]:
    """Configure the cache in a fresh CPU process (it changes process-wide
    JAX config) and return (helper's answer, JAX's configured dir)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=compile_)],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()


def test_compile_cache_lands_where_the_environment_says(tmp_path):
    where, configured = _cache_probe(True, cache_dir=tmp_path)
    assert where == configured == str(tmp_path)
    assert any(p.name.startswith("jit__lambda") for p in tmp_path.iterdir())


def test_compile_cache_defaults_to_a_fixed_checkout_path():
    from repro.launch.compile_cache import DEFAULT_CACHE_DIR

    where, configured = _cache_probe(False)
    assert where == configured == str(DEFAULT_CACHE_DIR)
    assert DEFAULT_CACHE_DIR.parent == ROOT
