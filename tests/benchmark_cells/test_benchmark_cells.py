"""The benchmark's own tests (CPU, no chip, no TPU library).

The manifest and every data file cross-reference; the window arithmetic,
the plain reference, the trace reduction, the roofline bytes and the
templates do what PERF.md says; the harness runs end to end at a tiny
size, and with the timed path broken underneath ``correct`` comes out
false — once for each control and once for each fault a cell can have.

The tests of the manifest and its files hold rules, not a list of today's
cells: each is a function of a ``Tree`` (a root with a manifest and the
benchmark's files under it), registered with ``@rule`` and run here on
the repo's own tree.  test_third_cell.py runs every one of them again on
a second tree, the repo's files plus a third deployment brought as new
files alone.
"""

from __future__ import annotations

import contextlib
import copy
import json
import os
import random
import re
import shutil
import subprocess
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import benchmark
from benchmark import (
    faults, generate, readers, reference, roofline, run, span_readers,
    trace_reduce,
)
from benchmark.faults import FAULTS

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class Tree:
    """A checkout as the harness reads it: ``BENCHMARK.json`` at its root
    and the benchmark's data files under ``benchmark/``; beside the
    tests, ``cells/<cell>.json`` with what is particular to a cell."""

    def __init__(self, root: str) -> None:
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.manifest = json.load(f)
        self.cells = [w["name"] for w in self.manifest["workloads"]]
        self.per_layer = [m["name"] for m in self.manifest["per_layer"]]
        self.e2e = {m["name"]: m for m in self.manifest["end_to_end"]}

    @contextlib.contextmanager
    def pointed(self):
        """The harness reads this tree: every path it resolves, it
        resolves from ``run.ROOT`` or from a directory named here."""
        under = os.path.join(self.root, "benchmark")
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(run, "ROOT", self.root)
            patch.setattr(run, "REFERENCES_DIR", os.path.join(under, "references"))
            patch.setattr(faults, "CONTROLS_DIR", os.path.join(under, "controls"))
            yield self

    def cell_data(self, cell: str) -> dict:
        """``tests/benchmark_cells/cells/<cell>.json``: what a tiny run of
        this cell reads that no rule gives.  A cell without the file is
        held to the rules alone."""
        path = os.path.join(self.root, "tests", "benchmark_cells", "cells",
                            f"{cell}.json")
        if not os.path.exists(path):
            return {}
        with open(path) as f:
            return json.load(f)


REPO = Tree(ROOT)
MANIFEST, CELLS, PER_LAYER, E2E = REPO.manifest, REPO.cells, REPO.per_layer, REPO.e2e
# the cells the benchmark had when its tests were restated as rules (PR 28):
# what is pinned, is pinned for these by name
KWOK, FIT = "kwok-1m-pct5.fill", "fit-10k.fill"

RULES = []      # (rule, what of the tree it is run over: None, "cells", "per_layer")


def rule(over=None):
    """Registers a rule of the manifest and its files: ``f(tree)``, or
    ``f(tree, name)`` for every cell or every per-layer metric."""
    def register(f):
        RULES.append((f, over))
        return f
    return register


# ---- the manifest and its files ---------------------------------------------


@rule()
def manifest_shape(tree):
    manifest = tree.manifest
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert "setup_s" in tree.e2e and tree.e2e["setup_s"]["bound"] <= 0.25
    assert 1 <= manifest["run_seconds"] <= 51
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    assert len(tree.cells) == len(set(tree.cells))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        # a list of cells names cells of this manifest, each once
        listed = m.get("workloads", [])
        assert set(listed) <= set(tree.cells) and len(listed) == len(set(listed))
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    chips4 = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert chips4 <= max(1, len(tree.cells) // 2)
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}


def test_manifest_shape():
    manifest_shape(REPO)


@rule()
def every_file_under_paths_is_named_plainly(tree):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in tree.manifest["paths"]:
        for d, dirs, files in os.walk(os.path.join(tree.root, path)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(d, f), tree.root))


def test_every_file_under_paths_is_named_plainly():
    every_file_under_paths_is_named_plainly(REPO)


@rule("cells")
def cell_files_cross_reference(tree, cell):
    MANIFEST = tree.manifest
    workload, config, pods = run.load_cell(MANIFEST, cell)
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == entry["config"])
    assert NAME.match(cell) and NAME.match(entry["traffic"])
    assert cell == f"{entry['config']}.{entry['traffic']}"
    assert workload["config"] == entry["config"] == config["name"]
    assert cfg["file"].startswith("benchmark/configs/")
    assert config["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    assert config["guarantees"] and "coordinator" in config
    assert workload["arrival"] == "backlog"
    assert workload["wave"] == config["pod_spec"]["batch"]
    assert config["nodes"]["cordon_every"] > 1 and config["assumed"]
    assert pods["shapes"] and pods["source"] and len(entry["why"]) <= 200
    # every cell reports set-up, another end-to-end metric, a per-layer one
    e2e = [m["name"] for m in run.metrics_of(MANIFEST, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert run.metrics_of(MANIFEST, "per_layer", cell)
    # what the tests keep of a cell beside them, where they keep anything
    assert set(tree.cell_data(cell)) <= {
        "untraced_metrics", "window_lanes", "shapes_interned", "values"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_cross_reference(cell):
    cell_files_cross_reference(REPO, cell)


def _hold_metric_file(m, spec, tree=REPO):
    """What a ``per_layer`` entry and its metric file have to satisfy; the
    reader is resolved as the harness resolves it."""
    MANIFEST, CELLS, E2E = tree.manifest, tree.cells, tree.e2e
    assert set(spec) <= {"reader", "args", "what"} and spec["what"]
    assert callable(readers.resolve(spec["reader"]))
    assert m["moves"] in E2E and m["layer"]
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    # every entry says which cells report it: a cell a later PR adds opts
    # into a metric by name
    cells = [c for c in CELLS
             if m in run.metrics_of(MANIFEST, "per_layer", c)]
    assert cells and set(m["workloads"]) == set(cells)
    for cell in cells:
        moved = [x["name"] for x in run.metrics_of(MANIFEST, "end_to_end", cell)]
        assert m["moves"] in moved
    if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
        assert m["unit"] == "%"


@rule("per_layer")
def per_layer_metric_files(tree, metric):
    m = next(x for x in tree.manifest["per_layer"] if x["name"] == metric)
    _hold_metric_file(
        m, run.read_json("benchmark", "metrics", f"{metric}.json"), tree)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_metric_files(metric):
    per_layer_metric_files(REPO, metric)


@rule()
def every_metric_file_is_in_the_manifest(tree):
    files = {f[:-len(".json")]
             for f in os.listdir(os.path.join(tree.root, "benchmark", "metrics"))}
    assert files == set(tree.per_layer)
    assert "assign_loop_ms.fill" not in files       # retired for assign_ms.fill
    # and every cell's file beside the tests is a cell's
    cells_dir = os.path.join(tree.root, "tests", "benchmark_cells", "cells")
    assert {f[:-len(".json")] for f in os.listdir(cells_dir)} <= set(tree.cells)


def test_every_metric_file_is_in_the_manifest():
    every_metric_file_is_in_the_manifest(REPO)


@pytest.mark.parametrize("reader", [
    "no_such_reader", "span_readers.no_such_function", "no_such_module.reader",
    "span_readers.SCOPES", "benchmark.span_readers.setup_stage_s", "os.getcwd",
    "../tools/x.f", ""])
def test_a_metric_file_with_an_unknown_reader_fails(reader):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == PER_LAYER[0])
    spec = {"reader": reader, "args": {}, "what": "nothing"}
    with pytest.raises(LookupError, match="reader"):
        _hold_metric_file(m, spec)


def test_a_reader_is_a_bare_name_or_a_function_of_a_module_of_its_own(
        tmp_path, monkeypatch):
    """The door for readers: a module beside the harness's, brought as a
    new file, is named ``<module>.<function>`` and needs no registration.
    (The file is written to a temporary directory that the ``benchmark``
    package is told to look in, so the test adds nothing under it.)"""
    assert readers.resolve("registry_stage_per_bind") is readers.registry_stage_per_bind
    assert readers.resolve("span_readers.counter_share_pct") \
        is span_readers.counter_share_pct
    (tmp_path / "toy_readers.py").write_text(
        "def binds_twice(args, ctx):\n"
        "    return args['times'] * ctx['binds'] or None\n")
    monkeypatch.setattr(benchmark, "__path__", [*benchmark.__path__, str(tmp_path)])
    monkeypatch.delitem(sys.modules, "benchmark.toy_readers", raising=False)
    reader = readers.resolve("toy_readers.binds_twice")
    assert reader({"times": 2}, {"binds": 21}) == 42
    assert reader({"times": 2}, {"binds": 0}) is None
    sys.modules.pop("benchmark.toy_readers", None)


def test_peaks_table_names_its_source():
    peaks = run.read_json("benchmark", "peaks.json")
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    assert "cpu" not in peaks


# ---- window arithmetic and the plain reference ----------------------------


KEY = b"/registry/pods/b1/bench-pod-"


def _put(i, node=None, parsed=True):
    """One put event: (type, key, flags, aux).  A value the store parsed
    natively comes with its node name alone; any other comes whole."""
    name = None if node is None else b"n-%d" % node
    if parsed:
        return (0, KEY + b"%d" % i, 1 | (2 if name else 0), name or b"")
    val = b'{"spec":{"schedulerName":"x"' + (
        b',"nodeName":"%s"' % name if name else b"") + b"}}"
    return (0, KEY + b"%d" % i, 0, val)


def _delete(i):
    return (1, KEY + b"%d" % i, 0, b"")


def _batch(events):
    """The store's columnar pod events, as ``Watcher.poll_pods`` gives them."""
    off = lambda parts: np.cumsum([0] + [len(x) for x in parts]).astype(np.uint32)
    keys, aux = [e[1] for e in events], [e[3] for e in events]
    return types.SimpleNamespace(
        n=len(events), etype=np.array([e[0] for e in events], np.uint8),
        flags=np.array([e[2] for e in events], np.uint8),
        koff=off(keys), aoff=off(aux),
        key_blob=b"".join(keys), aux_blob=b"".join(aux),
    )


def _ledger(batches):
    led = reference.Ledger(len(KEY))
    for t, events in batches:
        led.add(t, _batch(events))
    return led.arrays({b"n-%d" % i: i for i in range(4)})


def _replay(seen, offered, pods_per_node=2, cordoned=()):
    n = 4
    shut = np.zeros(n, bool)
    shut[list(cordoned)] = True
    return reference.replay(
        seen, offered=offered, pod_cpu=np.full(offered, 10),
        pod_mem=np.full(offered, 1024), alloc_cpu=np.full(n, 1000),
        alloc_mem=np.full(n, 1 << 20), alloc_pods=np.full(n, pods_per_node),
        cordoned=shut,
    )


def test_binds_are_counted_by_the_time_the_client_saw_them():
    seen = _ledger([
        (0.5, [_put(0), _put(1), _put(0, node=0)]),     # before the window
        (1.5, [_put(1, node=1), _put(2), _put(3)]),
        (2.5, [_put(2, node=2)]),
        (3.5, [_put(3, node=3)]),                       # after it closed
    ])
    n, rate = reference.window_rate(seen["bind_t"], 1.0, 3.0)
    assert (n, rate) == (2, 1.0)
    assert seen["bind_pod"].tolist() == [0, 1, 2, 3]


def test_a_stall_lowers_the_rate():
    steady = np.arange(0.0, 10.0, 0.1)
    stalled = np.r_[steady[:50], steady[50:] + 5.05]
    _n, r0 = reference.window_rate(steady, 0.0, 10.0)
    n1, r1 = reference.window_rate(stalled, 0.0, 10.0)
    assert n1 == 50 and r1 == pytest.approx(r0 / 2)


@pytest.mark.parametrize("parsed", [True, False])
def test_replay_a_sound_history_reads_nought(parsed):
    seen = _ledger([(1.0, [_put(0, parsed=parsed), _put(1, parsed=parsed),
                           _put(0, 0, parsed), _put(1, 0, parsed)]),
                    (2.0, [_put(2, parsed=parsed), _put(2, 1, parsed)])])
    out = _replay(seen, 3)
    assert set(out["numbers"].values()) == {0} and set(out["numbers"]) == {
        "never_bound", "bound_twice", "unknown_node", "bound_to_cordoned",
        "overcommitted_nodes", "deleted"}
    assert out["final_pods"].tolist() == [2, 1, 0, 0]
    assert out["final_cpu"].tolist() == [20, 10, 0, 0]
    assert out["node_of_pod"].tolist() == [0, 0, 1]


def test_replay_an_unbound_pod_is_a_failure():
    seen = _ledger([(1.0, [_put(0), _put(1), _put(0, node=0)])])
    assert _replay(seen, 2)["numbers"]["never_bound"] == 1


def test_replay_sees_a_second_bind_and_an_unknown_node():
    seen = _ledger([(1.0, [_put(0, node=0), _put(0, node=1), _put(1, node=9)])])
    numbers = _replay(seen, 2)["numbers"]
    assert numbers["bound_twice"] == 1 and numbers["unknown_node"] == 1
    assert numbers["never_bound"] == 0


@pytest.mark.parametrize("resource,pods_per_node,cpu,over", [
    ("pods", 2, 10, 1), ("pods", 3, 10, 0), ("cpu", 3, 400, 1)])
def test_replay_a_node_over_its_allocatable_is_counted(resource, pods_per_node,
                                                       cpu, over):
    seen = _ledger([(1.0, [_put(i, node=0) for i in range(3)] + [_put(3, node=1)])])
    out = reference.replay(
        seen, offered=4, pod_cpu=np.full(4, cpu), pod_mem=np.full(4, 1024),
        alloc_cpu=np.full(4, 1000), alloc_mem=np.full(4, 1 << 20),
        alloc_pods=np.full(4, pods_per_node), cordoned=np.zeros(4, bool))
    assert out["numbers"]["overcommitted_nodes"] == over


def test_replay_a_bind_to_a_cordoned_node_is_counted():
    seen = _ledger([(1.0, [_put(0, node=0), _put(1, node=3), _put(2, node=3)])])
    assert _replay(seen, 3)["numbers"]["bound_to_cordoned"] == 0
    assert _replay(seen, 3, cordoned=[3])["numbers"]["bound_to_cordoned"] == 2


def test_replay_counts_a_delete_nobody_asked_for():
    seen = _ledger([(1.0, [_put(0, node=0), _delete(0)])])
    assert _replay(seen, 1)["numbers"]["deleted"] == 1


def test_rows_wrong_compares_every_row():
    final = {"final_cpu": np.array([20, 0, 10]), "final_mem": np.array([2, 0, 1]),
             "final_pods": np.array([2, 0, 1])}
    row_of = np.array([3, 0, 1])
    cpu, mem, pods = np.zeros(4), np.zeros(4), np.zeros(4)
    cpu[[3, 1]], mem[[3, 1]], pods[[3, 1]] = [20, 10], [2, 1], [2, 1]
    assert reference.rows_wrong(final, row_of, cpu, mem, pods) == 0
    pods[2] = 1     # a row that holds no node has to read nought
    cpu[3] = 10
    assert reference.rows_wrong(final, row_of, cpu, mem, pods) == 2


# ---- traffic generation --------------------------------------------------------


def test_shape_pattern_same_set_another_order():
    params = {"shapes": [{"weight": 3, "cpu_milli": 10, "mem_kib": 1},
                         {"weight": 1, "cpu_milli": 500, "mem_kib": 9}]}
    a, b = generate.shape_pattern(params, 1), generate.shape_pattern(params, 2)
    key = lambda s: (s["cpu_milli"], s["mem_kib"])
    assert sorted(a, key=key) == sorted(b, key=key) and len(a) == 4
    assert generate.shape_pattern(params, 1) == a


@rule()
def templates_equal_the_programs_encoders(tree):
    rng = random.Random(0)
    for cell in tree.cells:
        _w, config, pods = run.load_cell(tree.manifest, cell)
        generate.Nodes({**config["nodes"], "count": 4096}).verify(rng)
        generate.Pods(pods, seed=(1 << 31) + 7).verify(rng)
    # the pod make_pods makes: pinned for the reference's own cell by name,
    # whatever pods a later cell sends
    p = generate.Pods(run.load_cell(tree.manifest, KWOK)[2], seed=5)
    key, val = p.wave(41, 1)[0]
    assert key == p.key(41) == b"/registry/pods/b5/bench-pod-41"
    assert json.loads(val)["metadata"] == {
        "name": "bench-pod-41", "namespace": "b5", "labels": {"app": "bench-pod"}}


def test_templates_equal_the_programs_encoders():
    templates_equal_the_programs_encoders(REPO)


def test_the_pod_is_the_one_make_pods_makes():
    from k8s1m_tpu.control.objects import decode_pod
    from k8s1m_tpu.tools.make_pods import build_pod

    p = generate.Pods(run.read_json("benchmark", "pods", "uniform.json"), seed=5)
    got = decode_pod(p.wave(7, 1)[0][1])
    want = build_pod(7, namespace="b5")
    assert (got.name, got.cpu_milli, got.mem_kib, got.labels) == (
        want.name, want.cpu_milli, want.mem_kib, want.labels)
    assert [t.key for t in got.tolerations] == ["kwok.x-k8s.io/node"]


def test_the_frame_is_what_the_store_packs():
    from k8s1m_tpu.store.native import pack_put_frame

    p = generate.Pods(run.read_json("benchmark", "pods", "uniform.json"), seed=9)
    assert p.frame(95, 12) == pack_put_frame(p.wave(95, 12))


def test_one_node_in_every_n_is_cordoned():
    from k8s1m_tpu.control.objects import decode_node

    _w, config, _p = run.load_cell(MANIFEST, CELLS[0])
    every = config["nodes"]["cordon_every"]
    n = generate.Nodes({**config["nodes"], "count": 4 * every})
    shut = [i for i in range(n.count) if n.cordoned(i)]
    assert shut == [every - 1, 2 * every - 1, 3 * every - 1, 4 * every - 1]
    for i, (_key, val) in enumerate(n.items(0, n.count)):
        assert decode_node(val).unschedulable == (i in shut)
    assert not any(generate.Nodes(
        {**config["nodes"], "count": 64, "cordon_every": 0}).cordoned(i)
        for i in range(64))


def test_a_cell_that_fills_to_the_brim_offers_whole_waves_that_fit():
    workload, config, pods = _tiny("fit-10k.fill")
    from k8s1m_tpu.store.native import MemStore

    with MemStore() as store:
        cell = run.Cell(store, config, workload, pods, seed=1)
        n = cell.nodes
        slots = sum(not n.cordoned(i) for i in range(n.count)) * n.pods
        assert cell.most % cell.wave == 0
        assert 0 <= slots - workload["brim_slack_pods"] - cell.most < cell.wave
        workload.pop("brim_slack_pods")
        free = run.Cell(store, config, workload, pods, seed=1)
        assert free.most > 1 << 40


# ---- the door for traffic: a shape's keys are the builder's keywords ------------


def test_a_pods_file_key_reaches_the_pod():
    """``tolerate_kwok`` is a keyword ``build_pod`` has and no pods file
    used: a new pods file is all it takes, and a shape that leaves out
    its requests gets ``build_pod``'s."""
    from k8s1m_tpu.control.objects import decode_pod

    params = {"shapes": [{"weight": 2, "tolerate_kwok": False},
                         {"weight": 1, "cpu_milli": 250, "mem_kib": 1024}],
              "source": "a test"}
    p = generate.Pods(params, seed=(1 << 31) + 3)
    p.verify(random.Random(0))
    assert sorted(map(sorted, p.pattern)) == [
        ["cpu_milli", "mem_kib"], ["tolerate_kwok"], ["tolerate_kwok"]]
    assert sorted(p.requests("cpu_milli")) == [100, 100, 250]
    assert sorted(p.requests("mem_kib")) == [1024, 200 << 10, 200 << 10]
    for i, shape in enumerate(p.pattern):
        pod = decode_pod(p.wave(i, 1)[0][1])
        assert bool(pod.tolerations) == shape.get("tolerate_kwok", True)
        assert pod.cpu_milli == shape.get("cpu_milli", 100)


@pytest.mark.parametrize("shape,named", [
    ({"cpu_milli": 100, "gpus": 1}, "'gpus'"),
    ({"topology_spread": [{"max_skew": 1}]}, "'topology_spread'"),
    ({"namespace": "mine"}, "'namespace'"), ({"prefix": "p"}, "'prefix'")])
def test_a_pods_file_key_the_builder_does_not_take_fails_by_name(shape, named):
    with pytest.raises(ValueError, match=f"pods shape: key {named}"):
        generate.Pods({"shapes": [{"cpu_milli": 1, "mem_kib": 1}, shape]}, seed=1)


def test_a_nodes_key_reaches_the_node_or_fails_by_name():
    from k8s1m_tpu.control.objects import decode_node

    _w, config, _p = run.load_cell(MANIFEST, CELLS[0])
    small = {"count": 64, "cordon_every": 4, "zones": 3, "prefix": "edge"}
    n = generate.Nodes(small)
    n.verify(random.Random(0))
    node = decode_node(n.items(5, 6)[0][1])
    assert node.name == "edge-5" and n.name(5) == b"edge-5"
    assert node.labels["topology.kubernetes.io/zone"] == "zone-2"
    # what the comparison reads is build_node's default where the object has none
    assert (n.cpu_milli, n.mem_kib, n.pods) == (32000, 64 << 20, 110)
    assert (node.cpu_milli, node.pods) == (32000, 110)
    with pytest.raises(ValueError, match="nodes: key 'taints'"):
        generate.Nodes({**config["nodes"], "count": 64, "taints": ["x"]})


# ---- trace reduction ------------------------------------------------------------

DEV = "/device:TPU:0"
EVENTS = [
    (DEV, "XLA Modules", "jit__lambda(1)", 0.0, 1.0),
    (DEV, "XLA Modules", "jit__lambda(1)", 2.0, 1.0),
    (DEV, "XLA Modules", "jit_scatter_rows(2)", 3.0, 0.1),
    (DEV, "XLA Ops", "%while.7 = (s32[]) while(...)", 0.1, 0.8),
    (DEV, "XLA Ops", "%fusion.1 = fusion(...)", 0.2, 0.1),       # inside the while
    (DEV, "XLA Ops", '%fused_topk.1 = custom-call(...), custom_call_target="tpu_custom_call"', 0.0, 0.1),
    (DEV, "XLA Ops", "%while.7 = (s32[]) while(...)", 2.1, 0.8),
    (DEV, "XLA Ops", '%fused_topk.1 = custom-call(...), custom_call_target="tpu_custom_call"', 2.0, 0.1),
    ("/host:CPU", "python3", "bench.put", 0.0, 0.9),
    ("/host:CPU", "python3", "bench.step", 0.9, 1.2),
    ("/host:CPU", "python3", "bench.watch", 2.1, 0.9),
]


def test_union_counts_overlaps_once():
    assert trace_reduce.union_seconds([(0, 1), (0.5, 1), (3, 1), (3.2, 0.1)]) \
        == pytest.approx(2.5)
    assert trace_reduce.union_seconds([]) == 0


def test_busy_window_and_idle_share():
    d = trace_reduce.busy_window(EVENTS, 0.0, 3.0)
    assert d["busy_s"] == pytest.approx(1.8) and d["window_s"] == 3.0
    clipped = trace_reduce.busy_window(EVENTS, 0.5, 2.5)
    assert clipped["busy_s"] == pytest.approx(0.4 + 0.5)
    with pytest.raises(RuntimeError):
        trace_reduce.busy_window([e for e in EVENTS if e[0] != DEV], 0, 1)


def test_per_name_sums_and_patterns():
    total, count = trace_reduce.per_event(EVENTS, DEV, "XLA Ops", "tpu_custom_call")
    assert (total, count) == (pytest.approx(0.2), 2)
    assert trace_reduce.per_event(EVENTS, DEV, "XLA Modules", r"^jit__lambda\(") \
        == (2.0, 2)
    top = trace_reduce.sums_by_name(EVENTS, DEV, "XLA Ops", top=2)
    assert trace_reduce.short_name(top[0][0]) == "%while.7"
    assert trace_reduce.short_name(EVENTS[5][2]) == "%fused_topk.1 tpu_custom_call"


def test_idle_gaps_go_to_the_span_that_covers_them():
    spans = [(n, s, d) for p, _l, n, s, d in EVENTS if p == "/host:CPU"]
    gaps = dict(trace_reduce.idle_gaps(EVENTS, DEV, spans, 0.0, 3.0))
    assert gaps["bench.step"] == pytest.approx(1.1)       # 0.9 .. 2.0
    assert gaps["bench.watch"] == pytest.approx(0.1)      # 2.9 .. 3.0
    # a gap goes whole to what covers its middle; past the last span, to nobody
    late = dict(trace_reduce.idle_gaps(EVENTS, DEV, spans, 0.0, 3.2))
    assert late["unattributed"] == pytest.approx(0.3) and "bench.watch" not in late


COLUMNS = {"cpu_alloc": (4, 1), "mem_alloc": (4, 1), "cpu_req": (4, 1),
           "mem_req": (4, 1), "pods_req": (4, 1), "pods_alloc": (2, 1),
           "meta": (4, 1), "taint_id": (2, 8), "label_key": (4, 16)}


def _synthetic_ctx(**more):
    return {
        "stage_s": {"drain": 1.0, "bind": 3.0, "sync_out": 5.0}, "binds": 1000,
        "trace": {"events": EVENTS, "plane": DEV},
        "shapes": {"scan_rows": 53248, "columns": COLUMNS, "batch": 4096,
                   "k": 4, "pod_bytes": 16},
        "peaks": {"hbm_bytes_per_s": 819e9}, **more,
    }


def _value(metric, ctx):
    spec = run.read_json("benchmark", "metrics", f"{metric}.json")
    return readers.resolve(spec["reader"])(spec["args"], ctx)


def test_readers_on_a_synthetic_window():
    ctx = _synthetic_ctx()
    assert _value("host_us_per_bind.fill", ctx) == pytest.approx(4000.0)
    assert _value("store_bind_us_per_bind.fill", ctx) == pytest.approx(3000.0)
    assert _value("engine_step_ms.fill", ctx) == pytest.approx(1000.0)
    assert _value("fused_topk_ms.fill", ctx) == pytest.approx(100.0)
    moved = 53248 * 42 + 4096 * 16 + 4096 * 4 * 8
    assert _value("fused_topk_roofline.fill", ctx) == pytest.approx(
        100 * moved / 819e9 / 0.1)
    # nothing to read: no value, never a nought
    roof = run.read_json("benchmark", "metrics", "fused_topk_roofline.fill.json")
    assert readers.trace_roofline_pct(roof["args"], {**ctx, "trace": None}) is None
    assert readers.registry_stage_per_bind(
        {"stages": ["bind"]}, {**ctx, "binds": 0}) is None


def test_a_stage_the_program_lacks_gives_no_value():
    ctx = _synthetic_ctx()
    assert readers.registry_stage_per_bind({"stages": ["encode"]}, ctx) is None
    assert readers.registry_stage_per_bind(
        {"stages": ["resync", "fallback"]}, ctx) is None
    # some of them observed: those are summed
    assert readers.registry_stage_per_bind(
        {"stages": ["encode", "drain"]}, ctx) == pytest.approx(1000.0)
    assert _value("encode_us_per_bind.fill", ctx) is None


def test_the_kernel_is_matched_by_its_name_not_as_any_custom_call():
    """A cell that runs a second Mosaic kernel does not sum it in."""
    call = 'custom-call(...), custom_call_target="tpu_custom_call"'
    more = EVENTS + [
        (DEV, "XLA Ops", f"%delta_plane_topk.2 = {call}", 0.3, 0.4),
        (DEV, "XLA Ops", f"%fused_topk_affinity.3 = {call}", 0.4, 0.4),
    ]
    ctx = _synthetic_ctx(trace={"events": more, "plane": DEV})
    assert _value("fused_topk_ms.fill", ctx) == pytest.approx(100.0)
    assert _value("fused_topk_roofline.fill", ctx) == \
        _value("fused_topk_roofline.fill", _synthetic_ctx())
    # without the kernel in the trace: nothing, not 0
    bare = [e for e in EVENTS if "fused_topk" not in e[2]]
    ctx = _synthetic_ctx(trace={"events": bare, "plane": DEV})
    assert _value("fused_topk_ms.fill", ctx) is None
    assert _value("fused_topk_roofline.fill", ctx) is None


def test_the_roofline_counts_the_columns_its_metric_file_names():
    roof = run.read_json("benchmark", "metrics", "fused_topk_roofline.fill.json")
    ctx = _synthetic_ctx()
    base = readers.trace_roofline_pct(roof["args"], ctx)
    wider = readers.trace_roofline_pct(
        {**roof["args"], "columns": [*roofline.BASE_COLUMNS, "label_key"]}, ctx)
    rows, rest = 53248, 4096 * 16 + 4096 * 4 * 8
    assert wider / base == pytest.approx((rows * (42 + 64) + rest) / (rows * 42 + rest))
    with pytest.raises(KeyError, match="zone"):
        readers.trace_roofline_pct({**roof["args"], "columns": ["zone"]}, ctx)


def test_xplane_loader_reads_what_jax_reads(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.put"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    got = trace_reduce.load(str(tmp_path))
    want = [
        (p.name, l.name, e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
        for p in ProfileData.from_file(trace_reduce.trace_file(str(tmp_path))).planes
        for l in p.lines for e in l.events
    ]
    assert len(got) == len(want) and any(e[2] == "bench.put" for e in got)
    for a, b in zip(got, want):
        assert a[:3] == b[:3] and a[3] == pytest.approx(b[3], abs=1e-6)
        assert a[4] == pytest.approx(b[4], abs=1e-9)
    assert "bench.put" in trace_reduce.overview(got)


# ---- roofline ------------------------------------------------------------------


def test_roofline_bytes_for_both_deployments():
    assert roofline.window_rows(1 << 20, 5, 4096) == 53248
    assert roofline.window_rows(16384, 100, 4096) == 16384
    per_row = roofline.row_bytes(COLUMNS)
    assert per_row == 42
    kwok = roofline.wave_bytes(scan_rows=53248, bytes_per_row=per_row,
                               batch=4096, k=4, pod_bytes=16)
    fit = roofline.wave_bytes(scan_rows=16384, bytes_per_row=per_row,
                              batch=4096, k=4, pod_bytes=16)
    assert kwok == 53248 * 42 + 4096 * 16 + 4096 * 32 == 2433024
    assert fit == 16384 * 42 + 4096 * 48 == 884736
    assert roofline.hbm_share_pct(819e9 // 100, 0.01, 819e9) == pytest.approx(100.0)


# ---- the harness, end to end at a tiny size ---------------------------------

CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _tiny(cell, tree=REPO):
    """The cell at 1,000 nodes and waves of 128, XLA scan; where it fills
    to the brim, nodes of 8 pod slots, so that half a second gets there."""
    workload, config, pods = run.load_cell(tree.manifest, cell)
    config = copy.deepcopy(config)
    config["nodes"].update(count=1000, cordon_every=16)
    config["table_spec"]["max_nodes"] = 1024
    config["pod_spec"]["batch"] = 128
    config["coordinator"].update(chunk=128, backend="xla")
    workload = dict(workload, wave=128)
    if "brim_slack_pods" in workload:
        config["nodes"]["pods"] = 8
        workload["brim_slack_pods"] = 64
    return workload, config, pods


def _rehearse(cell, fault=None, seconds=0.5, tree=REPO):
    return run.run_cell(
        tree.manifest, cell, _tiny(cell, tree), seed=(1 << 31) + 11,
        seconds=seconds, trace=False, device=dict(CPU_DEVICE), peaks={},
        fault=fault,
    )


def own_numbers(monkeypatch) -> dict:
    """Filled, in the runs that follow, with what a configuration's own
    reference returns (nothing where it names none)."""
    returned = {}
    real = run.load_reference

    def load(name):
        numbers = real(name)

        def recording(*args, **kw):
            out = numbers(*args, **kw)
            returned.update(out)
            return out

        return recording

    monkeypatch.setattr(run, "load_reference", load)
    return returned


def rehearsal_prints_a_well_formed_line(tree, cell, capsys, monkeypatch):
    own = own_numbers(monkeypatch)
    result = _rehearse(cell, tree=tree)
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert set(line["metrics"]) == {
        m["name"] for m in run.metrics_of(tree.manifest, "end_to_end", cell)}
    for m in line["metrics"].values():
        assert m["value"] > 0 and UNIT.match(m["unit"])
    assert all(c["value"] == c["limit"] == 0 for c in line["compared"].values())
    # the twelve and, where the configuration names a reference, exactly
    # what that reference returned; without the key, exactly the twelve
    config = run.load_cell(tree.manifest, cell)[1]
    assert bool(own) == ("reference" in config) and not set(own) & TWELVE
    assert set(line["compared"]) == TWELVE | set(own)
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1] == "correct=True failed_pods=0"
    assert err[-2].startswith("compared ") and " limit=0" in err[-2]
    compared = [l.split()[1].split("=")[0] for l in err if l.startswith("compared ")]
    assert compared == list(line["compared"])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_a_well_formed_line(cell, capsys, monkeypatch):
    rehearsal_prints_a_well_formed_line(REPO, cell, capsys, monkeypatch)


TWELVE = {"never_bound", "bound_twice", "unknown_node", "bound_to_cordoned",
          "overcommitted_nodes", "deleted", "store_disagrees",
          "mirror_rows_wrong", "device_rows_wrong", "compiled_in_window",
          "fell_back", "watch_dropped"}


def test_a_pods_file_with_a_new_key_runs_a_cell_to_correct():
    workload, config, _pods = _tiny(CELLS[0])
    pods = {"shapes": [{"weight": 3, "tolerate_kwok": False},
                       {"weight": 1, "cpu_milli": 250, "mem_kib": 4096}],
            "source": "a test"}
    line = run.run_cell(
        MANIFEST, CELLS[0], (workload, config, pods), seed=(1 << 31) + 12,
        seconds=0.3, trace=False, device=dict(CPU_DEVICE), peaks={},
    )
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["compared"]) == TWELVE


# ---- the door for guarantees: a configuration's own reference -----------------

TOY_REFERENCES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "references")


def _rehearse_with_reference(monkeypatch, name, cell="fit-10k.fill"):
    monkeypatch.setattr(run, "REFERENCES_DIR", TOY_REFERENCES)
    workload, config, pods = _tiny(cell)
    config["reference"] = name
    return run.run_cell(
        MANIFEST, cell, (workload, config, pods), seed=(1 << 31) + 13,
        seconds=0.3, trace=False, device=dict(CPU_DEVICE), peaks={},
    )


def test_a_configurations_own_reference_is_merged_with_limit_nought(monkeypatch):
    """A guarantee no sound run keeps (no pod on an even-numbered node)
    turns a sound tiny run not correct; the twelve are compared all the same."""
    line = _rehearse_with_reference(monkeypatch, "even_nodes")
    assert line["correct"] is False and line["failed"] == 0
    assert set(line["compared"]) == TWELVE | {"bound_to_even_node"}
    assert line["compared"]["bound_to_even_node"]["limit"] == 0
    assert line["compared"]["bound_to_even_node"]["value"] > 100
    assert all(line["compared"][k]["value"] == 0 for k in TWELVE)
    assert list(line)[-1] == "compared"


def test_a_reference_whose_guarantee_holds_leaves_the_run_correct(monkeypatch):
    line = _rehearse_with_reference(monkeypatch, "quiet", cell=CELLS[0])
    assert line["correct"] is True
    assert line["compared"]["bound_past_the_last_node"] == {"value": 0, "limit": 0}


def test_a_reference_may_not_take_a_base_numbers_name(monkeypatch):
    with pytest.raises(RuntimeError, match="device_rows_wrong"):
        _rehearse_with_reference(monkeypatch, "collides")


def test_a_reference_that_is_not_there_is_an_error(monkeypatch):
    with pytest.raises(SystemExit, match="no_such_reference"):
        _rehearse_with_reference(monkeypatch, "no_such_reference")


@rule()
def a_reference_imports_nothing(tree):
    """Neither the program nor the harness: what it is given is all it
    has.  A reference a configuration names is a file under
    ``benchmark/references/``, and every file there is some
    configuration's."""
    dirs = [TOY_REFERENCES, run.REFERENCES_DIR]
    files = [os.path.join(d, f) for d in dirs if os.path.isdir(d)
             for f in os.listdir(d) if f.endswith(".py")]
    assert len(files) >= 3
    for path in files:
        with open(path) as f:
            src = f.read()
        assert not re.search(r"^\s*(from|import)\s+(k8s1m_tpu|benchmark)\b", src, re.M)
        assert "def numbers(seen, replayed, *, nodes, pattern, offered)" in src
    assert run.REFERENCES_DIR == os.path.join(tree.root, "benchmark", "references")
    named = {run.read_json(c["file"]).get("reference")
             for c in tree.manifest["configs"]} - {None}
    there = {os.path.basename(f)[:-len(".py")] for f in files
             if os.path.dirname(f) == run.REFERENCES_DIR}
    assert named == there
    for name in named:
        assert NAME.match(name) and callable(run.load_reference(name))


def test_a_reference_imports_nothing():
    a_reference_imports_nothing(REPO)


# ---- the traced part of a window that closes at the brim ----------------------


@pytest.mark.parametrize("case,want", [
    # no trace asked for
    (dict(now=19.5, trace_seconds=0.0), False),
    # the clock: one second before the deadline, brim or none
    (dict(now=18.9), False), (dict(now=19.0), True),
    (dict(now=19.0, most=1 << 62), True), (dict(now=18.9, most=1 << 62), False),
    # the pods: 100 waves in 10 s is 0.1 s a wave; 10 waves left are 1.0 s
    (dict(now=10.0, offered=103 * 128, most=114 * 128), False),
    (dict(now=10.0, offered=103 * 128, most=113 * 128), True),
    (dict(now=10.0, offered=103 * 128, most=113 * 128 + 127), True),
    # at twice the pace the same ten waves are half a second: five more waves to go
    (dict(now=5.0, offered=103 * 128, most=113 * 128, trace_seconds=0.5), True),
    (dict(now=5.0, offered=103 * 128, most=124 * 128), False),
    (dict(now=5.0, offered=103 * 128, most=123 * 128), True),
    # a cell with no brim never starts by its pods; nothing offered yet, no pace
    (dict(now=10.0, offered=103 * 128, most=1 << 62), False),
    (dict(now=0.0, offered=3 * 128, most=4 * 128), False),
])
def test_the_trace_starts_by_the_clock_or_by_the_pods_left(case, want):
    base = dict(t0=0.0, deadline=20.0, trace_seconds=1.0, first=3 * 128,
                offered=103 * 128, most=1000 * 128, wave=128)
    assert run.trace_due(**{**base, **case}) is want


def test_a_cell_that_reaches_its_brim_early_asks_for_its_trace_before_it(
        monkeypatch):
    """A tiny cell that fills to the brim, with a deadline far beyond it:
    the window closes at the brim, and the trace is asked for
    ``trace_seconds`` of waves before that (no profiler on the CPU: the
    start and the stop are counted, not made)."""
    import jax
    from k8s1m_tpu.store.native import MemStore

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **kw: calls.append(("start", time.perf_counter())))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", time.perf_counter())))
    workload, config, pods = _tiny("fit-10k.fill")
    trace_seconds = 0.04        # some fifteen of its fifty-five waves here
    with MemStore() as store:
        cell = run.Cell(store, config, workload, pods, seed=(1 << 31) + 14)
        try:
            cell.setup()
            win = cell.window(600.0, trace_seconds)
            assert cell.brim() == 0             # the window got there itself
            cell.idle()
            out = cell.check(win)
        finally:
            cell.close()
    assert [c[0] for c in calls] == ["start", "stop"]
    assert win["last"] == cell.most and win["t1"] - win["t0"] < 300
    assert win["traced"] and set(out["numbers"].values()) == {0}
    waves = (win["last"] - win["first"]) // cell.wave
    pace = (win["t1"] - win["t0"]) / waves
    # trace_seconds, give or take a wave and what this host's pace wanders
    assert 0 < win["traced_s"] < trace_seconds + 3 * pace + 0.5
    assert win["t0"] < calls[0][1] < win["t1"] < calls[1][1]


def test_a_cell_without_a_brim_starts_its_trace_by_the_clock_alone(monkeypatch):
    import jax
    from k8s1m_tpu.store.native import MemStore

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **kw: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append("stop"))
    workload, config, pods = _tiny(CELLS[0])
    with MemStore() as store:
        cell = run.Cell(store, config, workload, pods, seed=(1 << 31) + 15)
        try:
            cell.setup()
            win = cell.window(0.6, 0.3)
            cell.idle()
        finally:
            cell.close()
    assert calls == ["start", "stop"] and cell.most > 1 << 40
    assert 0.6 <= win["t1"] - win["t0"] and 0 < win["traced_s"] < 0.3 + 0.5


# the control first, then each fault a one-chip cell can have
@pytest.mark.parametrize("fault,cell,caught_by", [
    ("lazy_bind", "kwok-1m-pct5.fill", "never_bound"),
    ("lazy_bind", "fit-10k.fill", "never_bound"),
    ("filter_off", "kwok-1m-pct5.fill", "bound_to_cordoned"),
    ("filter_off", "fit-10k.fill", "bound_to_cordoned"),
    ("capacity_off", "fit-10k.fill", "overcommitted_nodes"),
    ("half_batch", "kwok-1m-pct5.fill", "never_bound"),
    ("answer_altered", "fit-10k.fill", "device_rows_wrong"),
    ("state_unchanged", "kwok-1m-pct5.fill", "device_rows_wrong"),
])
def test_a_broken_timed_path_is_not_correct(fault, cell, caught_by):
    assert fault in FAULTS
    line = _rehearse(cell, fault=fault)
    assert line["correct"] is False
    assert line["compared"][caught_by]["value"] > line["compared"][caught_by]["limit"]


def test_state_unchanged_is_undone():
    import k8s1m_tpu.control.coordinator as mod
    from k8s1m_tpu.engine.cycle import schedule_batch_packed

    assert mod.schedule_batch_packed is schedule_batch_packed


# ---- the door for controls: a fault that arrives as a file ---------------------

TOY_CONTROLS = os.path.join(HERE, "controls")


def test_a_control_that_is_not_there_is_an_error_by_name(monkeypatch):
    monkeypatch.setattr(faults, "CONTROLS_DIR", TOY_CONTROLS)
    assert faults.names() == sorted([*FAULTS, "wave_on_one_node"])
    assert faults.load("lazy_bind") is FAULTS["lazy_bind"]
    with pytest.raises(SystemExit, match="no_such_control"):
        _rehearse(CELLS[0], fault="no_such_control")
    # where the directory itself is not there (the repo today): the six
    monkeypatch.setattr(faults, "CONTROLS_DIR", os.path.join(TOY_CONTROLS, "none"))
    assert faults.names() == sorted(FAULTS)


def test_a_control_file_breaks_a_sound_run_and_is_undone(monkeypatch):
    """``--fault <name>`` with no built-in of that name is
    ``controls/<name>.py``'s ``plant(store, coord)``, planted where the
    built-in ones are; what it returns undoes it when the cell closes."""
    from k8s1m_tpu.store.native import MemStore

    real = MemStore.bind_batch
    monkeypatch.setattr(faults, "CONTROLS_DIR", TOY_CONTROLS)
    line = _rehearse(KWOK, fault="wave_on_one_node")
    assert line["correct"] is False and line["failed"] == 0
    assert line["compared"]["overcommitted_nodes"]["value"] > 0
    assert MemStore.bind_batch is real
    assert _rehearse(KWOK)["correct"] is True


def test_a_built_in_fault_keeps_its_name_from_a_control_file(tmp_path, monkeypatch):
    (tmp_path / "lazy_bind.py").write_text("def plant(store, coord):\n    1 / 0\n")
    monkeypatch.setattr(faults, "CONTROLS_DIR", str(tmp_path))
    assert faults.load("lazy_bind") is FAULTS["lazy_bind"]
    assert faults.names() == sorted(FAULTS)


# ---- the constraint planes among the roofline's columns ------------------------

TABLE_COLUMNS = {
    "cpu_alloc": (4, 1), "mem_alloc": (4, 1), "cpu_req": (4, 1), "mem_req": (4, 1),
    "pods_req": (4, 1), "name_id": (4, 1), "label_num": (4, 16), "meta": (4, 1),
    "label_key": (4, 16), "label_val": (4, 0), "taint_id": (2, 8), "zone": (2, 1),
    "region": (1, 1), "pods_alloc": (2, 1)}


def _shapes(cell, tree=REPO, **changes):
    """``Cell.shapes()`` of the tiny cell once it is set up."""
    from k8s1m_tpu.store.native import MemStore

    workload, config, pods = _tiny(cell, tree)
    config["coordinator"].update(changes.pop("coordinator", {}))
    config["table_spec"].update(changes.pop("table_spec", {}))
    with MemStore() as store:
        made = run.Cell(store, config, workload, pods, seed=(1 << 31) + 16)
        try:
            made.setup()
            return made.shapes()
        finally:
            made.close()


@pytest.mark.parametrize("cell", [KWOK, FIT])
def test_the_columns_of_both_cells_are_the_node_tables_alone(cell):
    """No constraint planes in either deployment: ``columns`` holds what
    it held, and the roofline's 42 bytes a row with it."""
    shapes = _shapes(cell)
    assert shapes["columns"] == TABLE_COLUMNS
    assert roofline.row_bytes(shapes["columns"]) == 42
    assert set(shapes) == {"scan_rows", "columns", "batch", "k", "pod_bytes"}


def test_the_constraint_planes_are_columns_of_a_deployment_that_keeps_them():
    """``[slots, N]`` planes as bytes per node row, like every other
    column; the planes over zones and regions have no node axis."""
    shapes = _shapes(KWOK, coordinator={"with_constraints": True},
                     table_spec={"spread_slots": 4, "affinity_slots": 2})
    planes = {"spread_node": (4, 4), "tgt_node": (4, 2), "own_node": (4, 2)}
    assert shapes["columns"] == {**TABLE_COLUMNS, **planes}
    assert roofline.row_bytes(shapes["columns"]) == 42
    assert roofline.row_bytes(
        shapes["columns"], [*roofline.BASE_COLUMNS, "spread_node"]) == 42 + 16


def _cli(cwd):
    env = {k: v for k, v in os.environ.items() if not k.startswith("BENCH_")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_the_command_refuses_a_machine_without_a_chip():
    out = _cli(ROOT)
    assert out.returncode == 2
    assert "refusing to run" in out.stderr
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]


def test_the_command_fails_where_the_program_is_missing(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in MANIFEST["paths"]:
        shutil.copytree(
            os.path.join(ROOT, path), tmp_path / path,
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    out = _cli(tmp_path)
    assert out.returncode != 0
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]
