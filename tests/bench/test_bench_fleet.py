"""The four-chip fleet cell ``fleet32-rush-split`` on four virtual CPU
devices: sound, and with the timed path broken underneath in each way the
cell can be (the exchange between chips left out, answers altered where
they are made, half of the batch left out, state left unchanged), and its
control.  The cell is not in ``BENCHMARK.json``: its configuration runs
the repo's own small detector, so it waits for a published one.  The
tests add it, with its committed configuration, traffic and limits (from
four-chip readings), to a copy of the benchmark as entries alone."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import faults

ROOT = Path(__file__).resolve().parents[2]
NAME = "fleet32-rush-split"
CELL = {"name": NAME, "config": "det3x16-96x320-fleet32-data4",
        "traffic": "rush-split", "chips": 4,
        "why": "32 streams on data=4, even on two_stage, odd on early_exit"}
CONFIG = {"name": "det3x16-96x320-fleet32-data4", "source": "x",
          "file": "bench/configs/det3x16-96x320-fleet32-data4.json",
          "reduced": ["frame"], "why": "x"}

DRIVER = r'''
import sys
from pathlib import Path
import jax
sys.path[:0] = [".", "tests/bench"]
import faults
from bench import model_flops, run as bench

bench.ROOT = Path(sys.argv[1])
bench.devices_for = lambda chips: jax.devices()[:chips]
model_flops.peaks = lambda kind: {"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9}
argv = ["--workload", "fleet32-rush-split", "--seed", str(2 ** 31 + 5),
        "--seconds", "0.5", "--trace", "0"]
assert bench.main(argv) == 0

for fault in (faults.no_exchange, faults.shift_answers, faults.drop_half,
              faults.stale_state):
    owner, name, broken = fault()
    sound = getattr(owner, name)
    setattr(owner, name, broken)
    print(fault.__name__, file=sys.stderr)
    assert bench.main(argv) == 0
    setattr(owner, name, sound)
'''


def _with_fleet_cell(tmp_path: Path) -> Path:
    """A copy of the benchmark with the fleet cell's entries added."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append(CONFIG)
    spec["workloads"].append(CELL)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(NAME)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def test_fleet_cell_on_four_virtual_devices(tmp_path):
    root = _with_fleet_cell(tmp_path)
    assert (ROOT / f"bench/limits/{NAME}.json").is_file()

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", DRIVER, str(root)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    sound, *broken = [json.loads(line) for line in p.stdout.splitlines()
                      if line.startswith("{")]
    assert sound["correct"] is True, sound["checks"]
    assert sound["device"]["count"] == 4
    assert sound["attempted"] % 32 == 0 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"frames_per_s", "frame_p99_ms",
                                     "setup_s"}
    assert len(broken) == 4
    for res in broken:
        assert res["correct"] is False, res["checks"]


def test_fleet_control_fails_the_limits(tmp_path):
    """The fleet cell's committed limits hold its configuration's
    reference in float32 and fail it in bfloat16."""
    from bench import run as bench

    cell = bench.load_cell(NAME, root=_with_fleet_cell(tmp_path))
    (ok, checks), (sound, sound_checks) = faults.control_verdicts(bench, cell)
    assert not ok, checks
    assert sound, sound_checks
