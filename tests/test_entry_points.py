"""Every command the CI workflow runs or the README tells a user to run
names something that exists: a ``python -m <module>`` resolves to a
runnable module (the repo's own under ``src/``), and a script or test
path is a file in the tree."""
import importlib.util
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCS = (".github/workflows/ci.yml", "README.md")

_MODULE = re.compile(r"\bpython3? -m ([A-Za-z_][\w.]*)")
_SCRIPT = re.compile(r"\b(?:python3?|pytest) ([\w./-]+\.py)\b")


def _commands():
    cases = {}
    for doc in DOCS:
        text = (REPO / doc).read_text()
        for m in _MODULE.finditer(text):
            cases[(doc, "module", m.group(1))] = None
        for m in _SCRIPT.finditer(text):
            cases[(doc, "path", m.group(1))] = None
    return sorted(cases)


def _find(name):
    try:
        return importlib.util.find_spec(name)
    except ModuleNotFoundError:          # a missing parent package
        return None


@pytest.mark.parametrize("doc,kind,target", _commands(),
                         ids=lambda v: str(v))
def test_documented_command_resolves(doc, kind, target):
    if kind == "path":
        assert (REPO / target).is_file(), f"{doc}: no file {target}"
        return
    spec = _find(target)
    assert spec is not None, f"{doc}: no module {target}"
    if spec.submodule_search_locations is not None:
        # ``python -m <package>`` runs the package's ``__main__``
        spec = _find(f"{target}.__main__")
        assert spec is not None, f"{doc}: package {target} has no __main__"
    if target.split(".")[0] == "repro":
        assert (REPO / "src").resolve() in Path(spec.origin).resolve().parents, \
            f"{doc}: {target} resolves outside src/ ({spec.origin})"
