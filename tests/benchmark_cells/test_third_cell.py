"""The proof that a cell arrives as files: a third deployment inside the
tests.

A temporary tree is assembled from the repo's manifest and ``benchmark/``
files plus what ``tests/benchmark_cells/third_cell/`` holds, laid out as
a PR that is not a ``benchmark`` PR would bring it: a configuration that
keeps constraint planes and names a reference, a pods file of two sizes,
a workload file, two metric files on readers that are there (one a
roofline over a constraint plane), a control, the cell's file beside the
tests, and ``manifest_additions.json``: the entries appended to the
manifest and the lists the cell's name is appended to.  No file that is
there is edited.  Every rule the repo's own manifest is held to
(``RULES``) and both rehearsals then hold for that tree.

Should a later edit of the harness or of a rule shut one of the doors
again, it fails here, not in the PR that needed the door.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import faults, readers, roofline, run
import test_span_metrics
from test_benchmark_cells import (
    FIT, HERE, KWOK, REPO, RULES, TWELVE, Tree, _rehearse, _shapes, _tiny,
    rehearsal_prints_a_well_formed_line,
)

THIRD = os.path.join(HERE, "third_cell")
CELL = "third-1m.fill"

# the same files added by hand to a copy of the repo: that copy's own tree
# is the second tree then, and the tests of the repo's own hold it
pytestmark = pytest.mark.skipif(
    CELL in REPO.cells, reason="the repo's own manifest holds the third cell")


def assemble(src: str, dest: str, third: str = THIRD) -> None:
    """``dest`` becomes the tree at ``src`` (its manifest, ``benchmark/``
    and the cells' files beside the tests) with the deployment under
    ``third`` added: its files copied beside those that are there, none
    over one, and its manifest entries appended."""
    shutil.copytree(os.path.join(src, "benchmark"), os.path.join(dest, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(src, "tests", "benchmark_cells", "cells"),
                    os.path.join(dest, "tests", "benchmark_cells", "cells"))
    for sub in ("benchmark", "tests"):
        for d, _dirs, files in os.walk(os.path.join(third, sub)):
            to = os.path.join(dest, os.path.relpath(d, third))
            os.makedirs(to, exist_ok=True)
            for f in files:
                assert not os.path.exists(os.path.join(to, f)), f"{f} is there"
                shutil.copy(os.path.join(d, f), to)
    with open(os.path.join(src, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(third, "manifest_additions.json")) as f:
        add = json.load(f)
    for kind in ("configs", "workloads", "per_layer"):
        manifest[kind] += add[kind]
    metrics = {m["name"]: m for m in manifest["end_to_end"] + manifest["per_layer"]}
    for cell, names in add["append_cell_to"].items():
        for name in names:
            metrics[name]["workloads"].append(cell)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)


@pytest.fixture(scope="module")
def third(tmp_path_factory):
    dest = str(tmp_path_factory.mktemp("third_tree"))
    assemble(ROOT, dest)
    tree = Tree(dest)
    with tree.pointed():
        yield tree


def test_the_tree_is_the_repos_plus_files_and_appended_names(third):
    assert third.cells == [KWOK, FIT, CELL]
    assert len(third.per_layer) == len(REPO.per_layer) + 2
    assert run.ROOT == third.root != ROOT
    was = {m["name"]: m for m in REPO.manifest["end_to_end"] + REPO.manifest["per_layer"]}
    appended = 0
    for m in third.manifest["end_to_end"] + third.manifest["per_layer"]:
        old = was.get(m["name"])
        if old is None:
            assert m["workloads"] == [CELL]
            continue
        # no key of an entry that is there changes; a list only grows at its end
        assert {k: v for k, v in m.items() if k != "workloads"} == \
            {k: v for k, v in old.items() if k != "workloads"}
        if "workloads" in old:
            assert m["workloads"][:len(old["workloads"])] == old["workloads"]
            assert m["workloads"][len(old["workloads"]):] in ([], [CELL])
            appended += m["workloads"][-1] == CELL
    assert appended == 5        # binds_per_s and four of the eighteen
    # the files that were there are byte for byte what they were
    for d, _dirs, files in os.walk(os.path.join(ROOT, "benchmark")):
        for f in files:
            if "__pycache__" in d:
                continue
            rel = os.path.relpath(os.path.join(d, f), ROOT)
            with open(os.path.join(ROOT, rel), "rb") as a, \
                    open(os.path.join(third.root, rel), "rb") as b:
                assert a.read() == b.read(), rel


@pytest.mark.parametrize("held", RULES, ids=lambda r: r[0].__name__)
def test_the_third_cells_tree_holds(held, third):
    """Every rule of the manifest and its files, on the second tree: over
    all of its cells and all of its metrics, today's with them."""
    assert test_span_metrics.EIGHTEEN       # its rules are registered by its import
    check, over = held
    if over is None:
        check(third)
    else:
        for name in getattr(third, over):
            check(third, name)


def test_the_third_cell_rehearses_with_its_references_numbers(
        third, capsys, monkeypatch):
    """Fourteen numbers compared: the twelve and the two its reference
    returned, each 0 with the limit 0."""
    rehearsal_prints_a_well_formed_line(third, CELL, capsys, monkeypatch)
    line = _rehearse(CELL, tree=third)
    assert set(line["compared"]) == TWELVE | {
        "bound_past_the_last_node", "bound_to_a_full_node"}


def test_the_third_cells_tiny_run_reads_its_counters(third):
    test_span_metrics.the_harness_reads_the_counters_of_a_tiny_run(third, CELL)
    assert third.cell_data(CELL)["untraced_metrics"] == 4
    assert not REPO.cell_data(CELL)         # the repo keeps no file for it


def test_the_third_cells_control_is_a_file_and_fails_its_numbers(third):
    """``binds_rotated`` leaves every count right, so a mix of one size
    cannot see it; the third cell's two sizes do, in the requested cpu of
    the mirror and of the device's table."""
    from k8s1m_tpu.store.native import MemStore

    real = MemStore.bind_batch
    assert "binds_rotated" in faults.names() and "binds_rotated" not in faults.FAULTS
    line = _rehearse(CELL, fault="binds_rotated", tree=third)
    assert line["correct"] is False and line["failed"] == 0
    for caught_by in ("mirror_rows_wrong", "device_rows_wrong"):
        assert line["compared"][caught_by]["value"] > 0
    assert MemStore.bind_batch is real
    blind = _rehearse(KWOK, fault="binds_rotated", tree=third)
    assert blind["correct"] is True


def test_the_third_cells_roofline_counts_its_plane(third):
    """The metric file names ``spread_node`` among its columns; the run's
    ``shapes()`` holds it, so the bytes are the table's 42 a row and the
    plane's 4 x spread_slots."""
    spec = run.read_json("benchmark", "metrics", "third_topk_roofline.fill.json")
    shapes = _shapes(CELL, third)
    slots = _tiny(CELL, third)[1]["table_spec"]["spread_slots"]
    assert shapes["columns"]["spread_node"] == (4, slots)
    assert roofline.row_bytes(shapes["columns"], spec["args"]["columns"]) \
        == 42 + 4 * slots
    # on the fixture of test_span_metrics a second of the kernel a wave reads
    ctx = {**test_span_metrics._full_ctx(), "shapes": shapes}
    moved = shapes["scan_rows"] * (42 + 4 * slots) + 128 * 16 + 128 * 4 * 8
    assert readers.resolve(spec["reader"])(spec["args"], ctx) == pytest.approx(
        100 * moved / 819e9 / 0.1)
    # nothing to read (no trace): nothing, and never 0
    assert readers.resolve(spec["reader"])(spec["args"], {**ctx, "trace": None}) is None
