"""A configuration brings its own model and reference, by files it names:
a second model added to a copy of the benchmark as new files and entries
alone runs correct, the reference it names decides ``correct`` and is
the one the control check reads, and a configuration that names no
usable module is refused with its name."""
import json
import shutil
from pathlib import Path

import pytest

import faults

ROOT = Path(__file__).resolve().parents[2]
CAM8 = "bench/configs/det3x16-96x320-cam8.json"
TWIN_MODEL = "bench/models/det3x16_twin.py"
TWIN_REF = "bench/references/det3x16_twin.py"
SHIFTED_REF = "bench/references/det3x16_shifted.py"

# appended to a copy of the det3x16 reference: every box it returns moves
# by 2 px
SHIFT = '''

import dataclasses as _dc

_detect = Reference.detect


def _shifted(self, rung_name, images):
    return [_dc.replace(d, boxes=d.boxes + 2, candidates=d.candidates + 2)
            for d in _detect(self, rung_name, images)]


Reference.detect = _shifted
'''


def _copy(tmp_path: Path) -> dict:
    """The benchmark's files in ``tmp_path``; returns its spec."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _add(tmp_path: Path, spec: dict, conf: dict, cell: str,
         traffic: str) -> None:
    """A configuration and a cell on ``traffic``, as new files and entries."""
    name = conf["name"]
    (tmp_path / f"bench/configs/{name}.json").write_text(json.dumps(conf))
    (tmp_path / f"bench/limits/{cell}.json").write_text(
        (ROOT / "bench/limits/cam8-urban-top.json").read_text())
    spec["configs"].append({"name": name, "source": "x",
                            "file": f"bench/configs/{name}.json",
                            "reduced": ["frame", "streams"], "why": "x"})
    spec["workloads"].append({"name": cell, "config": name,
                              "traffic": traffic, "chips": 1, "why": "x"})
    spec["end_to_end"][0]["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))


def _twin(tmp_path: Path) -> dict:
    """A copy of the benchmark with a twin of det3x16 under new names, a
    4-stream configuration and the cell ``cam4-twin-top``; returns the
    twin's configuration."""
    spec = _copy(tmp_path)
    src = (ROOT / "bench/references/det3x16.py").read_text()
    shutil.copy(ROOT / "bench/models/det3x16.py", tmp_path / TWIN_MODEL)
    (tmp_path / TWIN_REF).write_text(src)
    (tmp_path / SHIFTED_REF).write_text(src + SHIFT)
    conf = json.loads((ROOT / CAM8).read_text())
    conf.update(name="twin-96x320-cam4", streams=4, capacity=4,
                model=TWIN_MODEL, reference=TWIN_REF)
    _add(tmp_path, spec, conf, "cam4-twin-top", "urban-top")
    return conf


def test_second_model_by_files_alone(on_cpu, run_cell, tmp_path,
                                     monkeypatch):
    """A twin of det3x16 under new names, with a 4-stream configuration,
    runs correct; naming a reference that shifts every box by 2 px turns
    ``correct`` false."""
    bench = on_cpu
    conf = _twin(tmp_path)
    monkeypatch.setattr(bench, "ROOT", tmp_path)

    cell = bench.load_cell("cam4-twin-top")
    assert Path(cell["model"].__file__) == tmp_path / TWIN_MODEL
    assert Path(cell["reference"].__file__) == tmp_path / TWIN_REF
    res = run_cell("cam4-twin-top", seconds=0.4)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["attempted"] % 4 == 0
    assert res["checks"]["box_dev_mean_px"]["value"] < 0.06

    conf["reference"] = SHIFTED_REF
    (tmp_path / "bench/configs/twin-96x320-cam4.json").write_text(
        json.dumps(conf))
    res = run_cell("cam4-twin-top", seconds=0.4)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["box_dev_mean_px"]["value"] >= 2.0


def test_second_model_control_fails_the_limits(tmp_path):
    """The control check takes the twin cell's own reference, streams and
    frame: its bfloat16 control fails the cell's limits, and its float32
    reference holds them."""
    from bench import run as bench

    _twin(tmp_path)
    cell = bench.load_cell("cam4-twin-top", root=tmp_path)
    twin = cell["reference"]
    assert Path(twin.__file__) == tmp_path / TWIN_REF
    built = []

    class Counted(twin.Reference):
        def __init__(self, *args):
            built.append(args[2:])
            super().__init__(*args)

    twin.Reference = Counted
    (ok, checks), (sound, sound_checks) = faults.control_verdicts(bench, cell)
    assert not ok, checks
    assert sound, sound_checks
    assert len(built) == 2          # the float32 reference and its control


def _drop(key):
    def edit(conf, tmp_path):
        del conf[key]
    return edit


def _set(key, value):
    def edit(conf, tmp_path):
        conf[key] = value
    return edit


def _lacking(name):
    """A model module without ``name``."""
    def edit(conf, tmp_path):
        src = (ROOT / "bench/models/det3x16.py").read_text()
        src = src.replace(f"def {name}(", f"def _{name}(")
        (tmp_path / "bench/models/partial.py").write_text(src)
        conf["model"] = "bench/models/partial.py"
    return edit


@pytest.mark.parametrize("edit,says", [
    (_drop("model"), "names no 'model' module"),
    (_drop("reference"), "names no 'reference' module"),
    (_set("model", "bench/models/nowhere.py"), "is no file under bench/"),
    (_set("reference", "BENCHMARK.json"), "is no file under bench/"),
    (_set("model", "bench/../conftest.py"), "is no file under bench/"),
    (_lacking("bytes_per_step"), "defines no bytes_per_step"),
    (_lacking("build_scheduler"), "defines no build_scheduler"),
], ids=["no-model", "no-reference", "unknown-path", "outside-bench",
        "leaves-bench", "no-bytes", "no-build"])
def test_configuration_without_its_modules_is_refused(tmp_path, edit, says):
    from bench import run as bench

    spec = _copy(tmp_path)
    conf = json.loads((ROOT / CAM8).read_text())
    conf["name"] = "broken-96x320-cam8"
    edit(conf, tmp_path)
    _add(tmp_path, spec, conf, "cam8-broken", "urban-top")
    with pytest.raises(ValueError, match="broken-96x320-cam8") as err:
        bench.load_cell("cam8-broken", root=tmp_path)
    assert says in str(err.value)


def test_committed_configurations_name_their_modules():
    from bench import run as bench

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = bench.load_cell(w["name"])
        assert cell["config"]["model"] == "bench/models/det3x16.py"
        assert cell["config"]["reference"] == "bench/references/det3x16.py"
