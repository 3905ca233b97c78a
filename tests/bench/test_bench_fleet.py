"""The four-chip fleet cell's loop on four virtual CPU devices, sound and
with the exchange between chips left out.  The cell is not in
``BENCHMARK.json`` yet (its limits need readings on four chips), so the
test adds it, as files and entries, to a copy of the benchmark."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CELL = {"name": "fleet32-rush-split", "config": "det3x16-96x320-fleet32-data4",
        "traffic": "rush-split", "chips": 4,
        "why": "32 streams on data=4, even on two_stage, odd on early_exit"}
CONFIG = {"name": "det3x16-96x320-fleet32-data4", "source": "x",
          "file": "bench/configs/det3x16-96x320-fleet32-data4.json", "reduced": ["frame"],
          "why": "x"}

DRIVER = r'''
import sys
from pathlib import Path
import jax
import numpy as np
sys.path.insert(0, ".")
from bench import model_flops, run as bench
from repro.batched.executor import PipelinedExecutor

bench.ROOT = Path(sys.argv[1])
bench.devices_for = lambda chips: jax.devices()[:chips]
model_flops.peaks = lambda kind: {"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9}
argv = ["--workload", "fleet32-rush-split", "--seed", str(2 ** 31 + 5),
        "--seconds", "0.5", "--trace", "0"]
assert bench.main(argv) == 0

drain = PipelinedExecutor.drain


def no_exchange(self):
    """Every shard's results replaced by the first shard's: what a
    readback that never gathered the other chips would hand the host."""
    d = drain(self)
    block = self.capacity // self.n_shards
    d.host = jax.tree.map(
        lambda x: np.concatenate([x[:block]] * self.n_shards), d.host)
    return d


PipelinedExecutor.drain = no_exchange
assert bench.main(argv) == 0
'''


def test_fleet_cell_on_four_virtual_devices(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append(CONFIG)
    spec["workloads"].append(CELL)
    spec["end_to_end"][0]["workloads"].append(CELL["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench/limits/fleet32-rush-split.json").write_text(
        (ROOT / "bench/limits/cam8-urban-top.json").read_text())

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", DRIVER, str(tmp_path)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    sound, broken = [json.loads(line) for line in p.stdout.splitlines()
                     if line.startswith("{")]
    assert sound["correct"] is True, sound["checks"]
    assert sound["device"]["count"] == 4
    assert sound["attempted"] % 32 == 0 and sound["failed"] == 0
    assert broken["correct"] is False, broken["checks"]
