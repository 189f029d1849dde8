"""The documents at the root describe the tree that is there, and tier-1
has one instrument.

Two guards.  A file that `README.md` or `PERF.md` names in backticks, or
a script either tells the reader to run, exists: a deleted instrument may
not live on as instructions.  And no test outside `tests/perfbench/`
imports or starts a root-level script other than the program's own entry
points: speeds are the ledger's (`BENCHMARK.json`, `perfbench/`), and a
second instrument does not grow back inside tier-1.
"""

import ast
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

# `dir/file.ext`, `dir/file.ext:12`, `dir/file.ext:12-40`
_NAMED = re.compile(
    r"`((?:[\w.-]+/)+[\w.-]+\.(?:py|json|md|cpp))(?::\d+(?:[-–]\d+)?)?`")
# python [-flags] script.py, in prose or in a code block
_RUN = re.compile(r"\bpython3?\s+(?:-\w+\s+)*((?:[\w.-]+/)*[\w.-]+\.py)\b")


@pytest.mark.parametrize("doc", ["README.md", "PERF.md"])
def test_paths_a_root_document_names_exist(doc):
    text = (ROOT / doc).read_text()
    named = set(_NAMED.findall(text))
    run = set(_RUN.findall(text))
    assert named and run, f"{doc}: the scan found nothing to check"
    missing = sorted(
        p for p in named
        if not (ROOT / p).exists() and not (ROOT / "seaweedfs_tpu" / p).exists())
    missing += sorted(f"python {p}" for p in run if not (ROOT / p).exists())
    assert not missing, f"{doc} names files that are not there: {missing}"


_ENTRY_POINTS = {"weed", "chip_smoke", "__graft_entry__"}


def _root_scripts_used(path: pathlib.Path) -> set[str]:
    """Root-level scripts a test file imports, or names as a `*.py`
    string (the argv of a subprocess), other than the entry points."""
    scripts = {p.stem for p in ROOT.glob("*.py")}
    used = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            used |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            used.add((node.module or "").split(".")[0])
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            m = re.fullmatch(r"(?:\./)?(\w+)\.py", node.value)
            if m:
                used.add(m.group(1))
    return (used & scripts) - _ENTRY_POINTS


def test_no_test_runs_a_root_level_instrument():
    files = [p for p in (ROOT / "tests").rglob("*.py")
             if "perfbench" not in p.relative_to(ROOT).parts]
    assert len(files) > 50, "the scan lost the tests"
    offenders = {str(p.relative_to(ROOT)): sorted(used)
                 for p in files if (used := _root_scripts_used(p))}
    assert not offenders, (
        f"tests that import or start a root-level script: {offenders}")
