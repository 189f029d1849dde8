"""Every WEED_* environment knob the code reads must be documented in
README.md — an undocumented knob is a support ticket waiting to happen —
and every knob the README's tables document must be one the code reads:
a row for a knob nothing reads is a setting that does nothing.

The scan extracts `WEED_[A-Z0-9_]*` string literals from the source
tree (literal reads like os.environ.get("WEED_X") and f-string
prefixes like f"WEED_EC_CODE_{slug}").  A name ending in "_" is a
dynamic prefix: the README must document it with a placeholder row
(e.g. `WEED_EC_CODE_<COLLECTION>`) or an expansion in the same
family.  Prose mentions of the naming *scheme* (unquoted, e.g.
util/config.py's WEED_SECTION_KEY docstring) are deliberately not
matched — only knobs the code actually reads are enforced.
"""

import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

# string literals opening with WEED_...; the leading quote keeps
# docstring/comment prose (unquoted names) out of the knob set
_LITERAL = re.compile(r'["\'](WEED_[A-Z0-9_]*)')


# a knob-table row: the first cell holds the name(s) in backticks
_ROW = re.compile(r"^\|([^|]*`WEED_[^|]*)\|", re.M)
_ROW_NAME = re.compile(r"`(WEED_[A-Z0-9_]+)(<?)")


def _knobs_in_source() -> set[str]:
    names: set[str] = set()
    files = list((ROOT / "seaweedfs_tpu").rglob("*.py"))
    files += [ROOT / "weed.py", *(ROOT / "native").glob("*.cpp")]
    for f in files:
        try:
            text = f.read_text()
        except OSError:
            continue
        names.update(_LITERAL.findall(text))
    return {n for n in names if len(n) > len("WEED_")}


def test_all_weed_knobs_documented_in_readme():
    readme = (ROOT / "README.md").read_text()
    knobs = _knobs_in_source()
    assert knobs, "knob scan found nothing — the extraction regex broke"
    missing = []
    for name in sorted(knobs):
        if name.endswith("_"):
            # dynamic prefix: accept a placeholder (`WEED_X_<...>`) or
            # any documented expansion of the prefix
            ok = re.search(re.escape(name) + r"[<A-Z]", readme)
        else:
            ok = name in readme
        if not ok:
            missing.append(name)
    assert not missing, (
        f"undocumented WEED_* knobs (add rows to the README knob "
        f"tables): {missing}")


def test_every_knob_table_row_names_a_knob_the_code_reads():
    """The other direction.  A placeholder row (`WEED_X_<...>`) needs
    its dynamic prefix (`WEED_X_`) among the literals."""
    readme = (ROOT / "README.md").read_text()
    knobs = _knobs_in_source()
    rows = [m for cell in _ROW.findall(readme)
            for m in _ROW_NAME.findall(cell)]
    assert len(rows) > 100, "row scan found too little — the regex broke"
    unread = sorted(name + ("<...>" if dynamic else "")
                    for name, dynamic in rows if name not in knobs)
    assert not unread, (
        f"README knob-table rows for WEED_* knobs that no file of "
        f"seaweedfs_tpu/, weed.py or native/ reads: {unread}")


def test_coding_tier_knobs_present():
    """The coding-tier policy knobs specifically (regression anchor for
    the family-selection docs)."""
    readme = (ROOT / "README.md").read_text()
    assert "WEED_EC_CODE" in readme
    assert re.search(r"WEED_EC_CODE_<", readme)


if __name__ == "__main__":
    pytest.main([__file__, "-v"])
