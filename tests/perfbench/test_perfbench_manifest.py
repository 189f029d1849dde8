"""BENCHMARK.json against its contract, and every name in it against the
files the harness finds by that name.  The checks themselves live in
`bench_checks.py`, where they take a tree root: here they run on the
repo's own tree, one case a name, and `test_perfbench_extend.py` runs the
same on a copy grown by data."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_checks as checks  # noqa: E402

TREE = checks.Tree(checks.ROOT)


def test_top_level_keys_and_sizes():
    checks.check_top_level(TREE)


def test_no_duplicate_names():
    checks.check_no_duplicate_names(TREE)


@pytest.mark.parametrize("name", sorted(TREE.end) + sorted(TREE.layer))
def test_metric_entry(name):
    checks.check_metric_entry(TREE, name)


@pytest.mark.parametrize("name", sorted(TREE.layer))
def test_layer_metric_file_resolves_and_agrees(name):
    checks.check_layer_metric_file(TREE, name)


@pytest.mark.parametrize("name", sorted(TREE.configs))
def test_config_resolves(name):
    checks.check_config(TREE, name)


@pytest.mark.parametrize("name", sorted(TREE.cells))
def test_cell_resolves(name):
    checks.check_cell(TREE, name)


def test_at_most_half_the_cells_ask_for_four_chips():
    checks.check_four_chip_share(TREE)


def test_paths_hold_only_allowed_file_names():
    checks.check_file_names(TREE)


def test_accepted_per_layer_names_stay_first_and_in_order():
    """The prefix guard: what follows the accepted names is free, so a PR
    that appends an entry edits no test."""
    checks.check_accepted_prefix(TREE)


LAST = len(checks.ACCEPTED_PER_LAYER) - 1


@pytest.mark.parametrize("drop,swap", [
    (0, None), (LAST, None), (None, (3, 4)), (None, (LAST - 1, LAST))],
    ids=["first-dropped", "last-dropped", "two-swapped", "last-two-swapped"])
def test_prefix_guard_refuses_a_dropped_or_reordered_accepted_name(
        drop, swap, tmp_path):
    """The guard has to fail on what it is there to catch, and pass with
    any tail appended."""
    import copy
    import json

    manifest = copy.deepcopy(TREE.manifest)
    manifest["per_layer"].append(dict(manifest["per_layer"][0],
                                      name="appended_by_a_later_pr"))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    checks.check_accepted_prefix(checks.Tree(str(tmp_path)))
    layer = manifest["per_layer"]
    if drop is not None:
        del layer[drop]
    else:
        a, b = swap
        layer[a], layer[b] = layer[b], layer[a]
    path.write_text(json.dumps(manifest))
    with pytest.raises(AssertionError):
        checks.check_accepted_prefix(checks.Tree(str(tmp_path)))
