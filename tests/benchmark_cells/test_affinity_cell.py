"""affinity-100k.fill's own tests: its control kept at 1,000 nodes (as the
built-in ones are), its reference on hand-made histories, and its three
metrics on the synthetic trace and counters the accepted readers are
tested on.
"""

from __future__ import annotations

import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import readers, roofline, run
from test_benchmark_cells import DEV, EVENTS, MANIFEST, REPO, _rehearse, _tiny
from test_span_metrics import OPS

CELL = "affinity-100k.fill"
OWN = {"selector_mismatch", "taint_untolerated"}

pytestmark = pytest.mark.skipif(
    CELL not in REPO.cells, reason="a tree without affinity-100k.fill")


def test_the_sound_cell_keeps_both_filters_and_the_control_breaks_them_alone():
    """Half a second of waves of 128 on 1,000 nodes, 100 of them the
    tainted pool: sound, the fourteen numbers read 0 and no pod is parked;
    with the selectors and the tolerations taken from what the device
    sees, both of the configuration's numbers are far above their limit
    and no other number moves."""
    import k8s1m_tpu.control.coordinator  # noqa: F401  (registers the histogram)
    from k8s1m_tpu.obs.metrics import REGISTRY

    # as a process of its own starts: the harness reads the process's
    # ``fallback`` stage seconds at set-up and again after its window has
    # reset the histogram, so seconds an earlier test of this worker left
    # there would read as a wave that fell back (PERF.md 7 (q))
    REGISTRY.get("coordinator_cycle_seconds").reset()
    sound = _rehearse(CELL)
    assert sound["correct"] is True and sound["failed"] == 0
    assert OWN <= set(sound["compared"])
    assert all(c["value"] == 0 for c in sound["compared"].values())
    line = _rehearse(CELL, fault="node_filters_off")
    assert line["correct"] is False and line["failed"] == 0
    over = {k for k, c in line["compared"].items() if c["value"] > c["limit"]}
    assert over == OWN
    # a selector is right by chance one time in ten or four: over a third
    # of all binds miss; a tenth of nine sixteenths land on the pool
    assert line["compared"]["selector_mismatch"]["value"] > line["attempted"] // 3
    assert line["compared"]["taint_untolerated"]["value"] > line["attempted"] // 40
    # undone at close: the next sound run is sound
    again = _rehearse(CELL, seconds=0.2)
    assert again["correct"] is True


def test_the_tiny_cell_is_the_deployment_in_small():
    """What the tiny run changes is size alone: the taints, the pool, the
    widths and the mix are the configuration's."""
    workload, config, pods = _tiny(CELL)
    assert config["nodes"]["node_taints"] and config["nodes"]["group_taints"]
    assert config["table_spec"]["taint_slots"] == 3
    assert config["pod_spec"]["query_keys"] == 4
    assert sum(s["weight"] for s in pods["shapes"]) == 16
    assert sum(s["weight"] for s in pods["shapes"]
               if "node_selector" in s or "node_affinity" in s) == 10
    assert config["reference"] == "affinity" and workload["pods"] == "affinity"


# ---- the reference, on hand-made histories -------------------------------------

NODES = {"prefix": "n", "zones": 4, "regions": 2,
         "node_taints": [{"key": "kwok.x-k8s.io/node", "value": "fake",
                          "effect": "NoSchedule"}],
         "group_taints": {"9": [{"key": "dedicated", "value": "batch",
                                 "effect": "NoSchedule"}]},
         "group_labels": {"9": {"dedicated": "batch"}}}
ZONE = "topology.kubernetes.io/zone"


def _numbers(bind_node, pattern, nodes=NODES):
    seen = {"bind_pod": np.arange(len(bind_node)), "bind_node": np.asarray(bind_node)}
    return run.load_reference("affinity")(
        seen, None, nodes=nodes, pattern=pattern, offered=len(bind_node))


def _required(*exprs):
    return {"requiredDuringSchedulingIgnoredDuringExecution": {
        "nodeSelectorTerms": [{"matchExpressions": list(e)} for e in exprs]}}


def _expr(key, op, *values):
    return {"key": key, "operator": op, **({"values": list(values)} if values else {})}


DEDICATED = {"node_selector": {"dedicated": "batch"}, "tolerations": [
    {"key": "dedicated", "operator": "Equal", "value": "batch",
     "effect": "NoSchedule"}]}


@pytest.mark.parametrize("shape, node, want", [
    # node i: kwok-group i mod 10, zone-(i mod 4), region-(i mod 2)
    ({}, 3, (0, 0)),                                   # plain on a plain node
    ({}, 9, (0, 1)),                                   # plain on the pool
    ({"tolerate_kwok": False}, 3, (0, 1)),             # the kwok taint itself
    ({"node_selector": {"kwok-group": "3"}}, 13, (0, 0)),
    ({"node_selector": {"kwok-group": "3"}}, 14, (1, 0)),
    ({"node_selector": {"kwok-group": "3", ZONE: "zone-2"}}, 13, (1, 0)),
    (DEDICATED, 19, (0, 0)),
    (DEDICATED, 18, (1, 0)),
    ({**DEDICATED, "tolerations": [{"key": "dedicated", "operator": "Equal",
                                    "value": "web"}]}, 19, (0, 1)),
    ({**DEDICATED, "tolerations": [{"operator": "Exists"}]}, 19, (0, 0)),
    ({**DEDICATED, "tolerations": [{"key": "dedicated", "operator": "Exists",
                                    "effect": "NoExecute"}]}, 19, (0, 1)),
    ({"node_affinity": _required([_expr(ZONE, "In", "zone-0", "zone-1")])}, 5, (0, 0)),
    ({"node_affinity": _required([_expr(ZONE, "In", "zone-0", "zone-1")])}, 6, (1, 0)),
    # terms are ORed, the expressions of one ANDed
    ({"node_affinity": _required([_expr(ZONE, "In", "zone-0")],
                                 [_expr("kwok-group", "In", "6")])}, 6, (0, 0)),
    ({"node_affinity": _required([_expr(ZONE, "In", "zone-2"),
                                  _expr("kwok-group", "In", "7")])}, 6, (1, 0)),
    ({"node_affinity": _required([_expr(ZONE, "NotIn", "zone-2")])}, 6, (1, 0)),
    ({"node_affinity": _required([_expr("dedicated", "DoesNotExist")])}, 6, (0, 0)),
    ({"node_affinity": _required([_expr("dedicated", "Exists")])}, 6, (1, 0)),
    ({"node_affinity": _required([_expr("kwok-group", "Gt", "5")])}, 6, (0, 0)),
    ({"node_affinity": _required([_expr("kwok-group", "Lt", "5")])}, 6, (1, 0)),
    ({"node_affinity": _required([_expr(ZONE, "Gt", "1")])}, 6, (1, 0)),
    ({"node_affinity": _required([_expr("kubernetes.io/hostname", "In", "n-6")])},
     6, (0, 0)),
    ({"node_affinity": _required([_expr("kubernetes.io/hostname", "In", "n-6")])},
     46, (1, 0)),
    # a preferred term forbids nothing
    ({"node_affinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": 1, "preference": {"matchExpressions": [
            _expr("kwok-group", "In", "1")]}}]}}, 6, (0, 0)),
])
def test_the_reference_judges_one_bind(shape, node, want):
    got = _numbers([node], [shape])
    assert (got["selector_mismatch"], got["taint_untolerated"]) == want


def test_the_reference_counts_every_bind_by_its_pods_shape():
    pattern = [{}, {"node_selector": {"kwok-group": "3"}}, DEDICATED]
    # pods 0, 3 plain; 1, 4 group-3; 2, 5 dedicated
    sound = [0, 3, 9, 1, 13, 19]
    assert _numbers(sound, pattern) == {"selector_mismatch": 0, "taint_untolerated": 0}
    assert _numbers([9, 4, 8, 19, 13, 19], pattern) == {
        "selector_mismatch": 2, "taint_untolerated": 2}
    # an unknown node is the base replay's to count
    assert _numbers([-1, 3, 9], pattern) == {
        "selector_mismatch": 0, "taint_untolerated": 0}
    # no taints in the deployment: nothing to tolerate
    bare = {"prefix": "n", "zones": 4, "regions": 2}
    assert _numbers([9, 3, 9], pattern, nodes=bare) == {
        "selector_mismatch": 1, "taint_untolerated": 0}


def test_the_reference_and_the_control_import_as_the_readme_says():
    ref = os.path.join(ROOT, "benchmark", "references", "affinity.py")
    with open(ref) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+\w", src, re.M)     # nothing at all
    assert "def numbers(seen, replayed, *, nodes, pattern, offered)" in src
    control = os.path.join(ROOT, "benchmark", "controls", "node_filters_off.py")
    with open(control) as f:
        src = f.read()
    assert "def plant(store, coord)" in src
    # the program is imported inside plant alone, the harness never
    assert not re.search(r"^(from|import)\s", src, re.M)
    assert not re.search(r"import\s+benchmark|from\s+benchmark", src)


# ---- the cell's three metrics ------------------------------------------------------


def _ctx(events, counters=None):
    columns = {c: (4, 1) for c in (
        "cpu_alloc", "mem_alloc", "cpu_req", "mem_req", "pods_req", "meta")}
    columns.update(pods_alloc=(2, 1), taint_id=(2, 3), label_key=(4, 6),
                   label_val=(4, 0), label_num=(4, 6), zone=(2, 1))
    return {"trace": {"events": events, "plane": DEV, "op_names": {}},
            "shapes": {"scan_rows": 8192, "batch": 4096, "k": 4, "pod_bytes": 16,
                       "columns": columns},
            "peaks": {"hbm_bytes_per_s": 819e9}, "stage_s": {},
            "setup_stage_s": {}, "counters": counters, "binds": 0}


def test_the_kernels_two_metrics_read_the_affinity_kernel_by_its_name():
    """``%fused_topk_affinity.<n>`` is no ``%fused_topk.<n>``: each metric
    reads its own kernel, and the roofline counts the label planes as the
    layout holds them (``label_val`` is empty where it is fused away)."""
    kernel = "%fused_topk_affinity.1 = custom-call(...)"
    events = [(p, l, kernel if n == EVENTS[5][2] else n, s, d)
              for p, l, n, s, d in OPS]
    got = run.per_layer_values(MANIFEST, CELL, _ctx(events))
    assert got["affinity_topk_ms.fill"] == pytest.approx(100.0)
    row = 4 * 6 + 2 + 2 * 3 + 4 * 6 + 0 + 4 * 6          # 80 bytes
    moved = 8192 * row + 4096 * 16 + 4096 * 4 * 8
    assert got["affinity_topk_roofline.fill"] == pytest.approx(
        100 * moved / 819e9 / 0.1)
    spec = run.read_json("benchmark", "metrics", "affinity_topk_roofline.fill.json")
    assert spec["args"]["columns"][:len(roofline.BASE_COLUMNS)] == list(
        roofline.BASE_COLUMNS)
    assert spec["args"]["columns"][len(roofline.BASE_COLUMNS):] == [
        "label_key", "label_val", "label_num"]
    # the split layout's label_val is counted when it is there
    split = _ctx(events)
    split["shapes"]["columns"]["label_val"] = (4, 6)
    assert run.per_layer_values(MANIFEST, CELL, split)[
        "affinity_topk_roofline.fill"] == pytest.approx(
            100 * (moved + 8192 * 24) / 819e9 / 0.1)
    # the base kernel's metrics find nothing in this trace, and these two
    # nothing in the base kernel's
    old = run.read_json("benchmark", "metrics", "fused_topk_ms.fill.json")
    assert readers.resolve(old["reader"])(old["args"], _ctx(events)) is None
    bare = run.per_layer_values(MANIFEST, CELL, _ctx(OPS))
    assert bare["affinity_topk_ms.fill"] is None
    assert bare["affinity_topk_roofline.fill"] is None


def _waves(at_open, at_close):
    key = lambda kernel: (("kernel", kernel),)
    return {"open": {"coordinator_waves_total": {key(k): v for k, v in at_open.items()}},
            "close": {"coordinator_waves_total": {key(k): v for k, v in at_close.items()}}}


@pytest.mark.parametrize("at_open, at_close, want", [
    ({"fused_topk_affinity": 3.0}, {"fused_topk_affinity": 103.0}, 100.0),
    ({}, {"filter_score_topk_affinity": 40.0}, 100.0),
    ({"fused_topk": 3.0, "fused_topk_affinity": 1.0},
     {"fused_topk": 33.0, "fused_topk_affinity": 11.0}, 25.0),
    ({"fused_topk": 3.0}, {"fused_topk": 33.0}, 0.0),
    ({"fused_topk_affinity": 3.0}, {"fused_topk_affinity": 3.0}, None),
])
def test_the_share_of_waves_built_with_the_affinity_stage(at_open, at_close, want):
    spec = run.read_json("benchmark", "metrics", "affinity_wave_pct.fill.json")
    reader = readers.resolve(spec["reader"])
    got = reader(spec["args"], {"counters": _waves(at_open, at_close)})
    assert got == (pytest.approx(want) if want is not None else None)


def test_a_program_without_the_counter_gives_no_share():
    """The parent commit: no ``coordinator_waves_total``: nothing, no error."""
    spec = run.read_json("benchmark", "metrics", "affinity_wave_pct.fill.json")
    reader = readers.resolve(spec["reader"])
    other = {"open": {"coordinator_pod_intake_total": {}}, "close": {}}
    assert reader(spec["args"], {"counters": other}) is None
    assert reader(spec["args"], {"counters": None}) is None
