"""The proof that a cell arrives as files: a third deployment inside the
tests.

A temporary tree is assembled from a tree's manifest and ``benchmark/``
files plus what ``tests/benchmark_cells/third_cell/`` holds, laid out as
a PR that is not a ``benchmark`` PR would bring it: a configuration that
keeps constraint planes and names a reference, a pods file of two sizes,
a workload file, two metric files on readers that are there (one a
roofline over a constraint plane), a control, the cell's file beside the
tests, and ``manifest_additions.json``: the entries appended to the
manifest and the lists the cell's name is appended to.  No file that is
there is edited.  Every rule the repo's own manifest is held to
(``RULES``) and both rehearsals then hold for that tree.

The tree the deployment is added to is the repo's own, whatever cells it
holds (``one_more``), and the repo's with the same deployment already in
it under other names (``two_more``: the assembly run twice over), as the
repo's own manifest will be once a later PR has brought its cell.  Every
expectation is stated against the tree that was there, none against a
list of today's cells.

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
    HERE, REPO, RULES, TWELVE, Tree, _rehearse, _shapes, _tiny,
    rehearsal_prints_a_well_formed_line,
)

THIRD = os.path.join(HERE, "third_cell")
CELL = "third-1m.fill"
CONTROL = "binds_rotated"
# the same deployment under names of its own, every file's with them: two
# files of one name collide, a pods file or a control as much as a cell's
OTHER_NAMES = (("third", "other"), ("two-sizes", "other-sizes"),
               (CONTROL, "binds_turned"))

# the same files added by hand to a copy of the repo: that copy's own tree
# is the second tree then, and the tests of the repo's own hold it
pytestmark = pytest.mark.skipif(
    CELL in REPO.cells, reason="the repo's own manifest holds the third cell")


def _renamed(text: str, names) -> str:
    for old, new in names:
        text = text.replace(old, new)
    return text


def renamed_copy(third: str, dest: str, names=OTHER_NAMES) -> str:
    """The deployment under ``third`` written to ``dest`` with every one
    of ``names`` replaced, in its files' paths and in what they hold."""
    for d, _dirs, files in os.walk(third):
        to = os.path.join(dest, _renamed(os.path.relpath(d, third), names))
        os.makedirs(to, exist_ok=True)
        for f in files:
            with open(os.path.join(d, f)) as src, \
                    open(os.path.join(to, _renamed(f, names)), "w") as out:
                out.write(_renamed(src.read(), names))
    return dest


def additions(third: str = THIRD) -> dict:
    with open(os.path.join(third, "manifest_additions.json")) as f:
        return json.load(f)


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
    add = additions(third)
    for kind in ("configs", "workloads", "per_layer"):
        manifest[kind] += add[kind]
    metrics = {m["name"]: m for m in manifest["end_to_end"] + manifest["per_layer"]}
    for cell, names in add["append_cell_to"].items():
        for name in names:
            metrics[name]["workloads"].append(cell)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)


@pytest.fixture(scope="module", params=["one_more", "two_more"])
def grown(request, tmp_path_factory):
    """``(tree, base)``: the tree with the third deployment, pointed at,
    and the tree it was added to."""
    base = REPO
    if request.param == "two_more":
        other = renamed_copy(THIRD, str(tmp_path_factory.mktemp("other_cell")))
        first = str(tmp_path_factory.mktemp("other_tree"))
        assemble(ROOT, first, other)
        base = Tree(first)
    dest = str(tmp_path_factory.mktemp("third_tree"))
    assemble(base.root, dest)
    tree = Tree(dest)
    with tree.pointed():
        yield tree, base


@pytest.fixture
def third(grown):
    return grown[0]


def test_the_deployment_under_other_names_shares_no_name_with_it(tmp_path):
    """What the second tree starts from: every file and every manifest
    name of the renamed deployment is its own."""
    other = renamed_copy(THIRD, str(tmp_path))
    files = lambda root: {os.path.relpath(os.path.join(d, f), root)
                          for d, _dirs, fs in os.walk(root) for f in fs}
    assert files(other) & files(THIRD) == {"manifest_additions.json"}
    assert len(files(other)) == len(files(THIRD))
    names = lambda add: {e["name"] for kind in ("configs", "workloads", "per_layer")
                         for e in add[kind]} | set(add["append_cell_to"])
    assert not names(additions(other)) & names(additions())
    assert additions(other)["append_cell_to"]["other-1m.fill"] \
        == additions()["append_cell_to"][CELL]


def test_the_tree_is_the_one_that_was_there_plus_files_and_appended_names(grown):
    third, base = grown
    add = additions()
    assert third.cells == base.cells + [CELL]
    assert len(third.cells) == len(REPO.cells) + 1 + (base is not REPO)
    assert third.per_layer == base.per_layer + [m["name"] for m in add["per_layer"]]
    assert run.ROOT == third.root != base.root
    was = {m["name"]: m for m in base.manifest["end_to_end"] + base.manifest["per_layer"]}
    appended = []
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
            if m["workloads"][-1] == CELL:
                appended.append(m["name"])
    assert appended == add["append_cell_to"][CELL]      # in the manifest's order
    # the files that were there are byte for byte what they were
    for d, _dirs, files in os.walk(os.path.join(base.root, "benchmark")):
        for f in files:
            if "__pycache__" in d:
                continue
            rel = os.path.relpath(os.path.join(d, f), base.root)
            with open(os.path.join(base.root, rel), "rb") as a, \
                    open(os.path.join(third.root, rel), "rb") as b:
                assert a.read() == b.read(), rel


@pytest.mark.parametrize("held", RULES, ids=lambda r: r[0].__name__)
def test_the_third_cells_tree_holds(held, third):
    """Every rule of the manifest and its files, on the grown tree: over
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
    """The twelve and what its reference returned, each 0 with the limit 0."""
    rehearsal_prints_a_well_formed_line(third, CELL, capsys, monkeypatch)
    line = _rehearse(CELL, tree=third)
    assert set(line["compared"]) == TWELVE | {
        "bound_past_the_last_node", "bound_to_a_full_node"}


def test_the_third_cells_tiny_run_reads_its_counters(grown):
    third, base = grown
    test_span_metrics.the_harness_reads_the_counters_of_a_tiny_run(third, CELL)
    with open(os.path.join(THIRD, "tests", "benchmark_cells", "cells",
                           f"{CELL}.json")) as f:
        assert third.cell_data(CELL) == json.load(f)
    assert "untraced_metrics" in third.cell_data(CELL)
    assert not base.cell_data(CELL)         # the tree that was there keeps no file for it


def test_every_cell_that_was_there_rehearses_as_it_did(grown, capsys, monkeypatch):
    """The cells the deployment was added beside are untouched by it: the
    newest of them rehearses in the grown tree, its own reference's numbers
    with it where it names one."""
    third, base = grown
    rehearsal_prints_a_well_formed_line(third, base.cells[-1], capsys, monkeypatch)


def test_the_third_cells_control_is_a_file_and_fails_its_numbers(grown):
    """``binds_rotated`` leaves every count right, so a mix of one size
    cannot see it; the third cell's two sizes do, in the requested cpu of
    the mirror and of the device's table."""
    from k8s1m_tpu.store.native import MemStore

    third, base = grown
    real = MemStore.bind_batch
    assert CONTROL in faults.names() and CONTROL not in faults.FAULTS
    line = _rehearse(CELL, fault=CONTROL, tree=third)
    assert line["correct"] is False and line["failed"] == 0
    for caught_by in ("mirror_rows_wrong", "device_rows_wrong"):
        assert line["compared"][caught_by]["value"] > 0
    assert MemStore.bind_batch is real
    one_size = next(c for c in base.cells if len(
        run.load_cell(third.manifest, c)[2]["shapes"]) == 1)
    blind = _rehearse(one_size, fault=CONTROL, tree=third)
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
