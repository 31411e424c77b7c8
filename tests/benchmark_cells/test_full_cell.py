"""full-1m-pct5.fill's own tests: the tiny served run and the three controls
(as the built-in ones are kept), its reference as the union of the three
it is made of, and its four metrics on the synthetic trace and counters the
accepted readers are tested on.
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
from test_benchmark_cells import (
    DEV, EVENTS, MANIFEST, REPO, _rehearse, _shapes, _synthetic_ctx, _tiny,
)

CELL = "full-1m-pct5.fill"
PARTS = {"affinity": {"selector_mismatch", "taint_untolerated"},
         "spread": {"zone_skew_exceeded"},
         "interpod": {"anti_affinity_violated", "affinity_unmet"}}
OWN = set().union(*PARTS.values())

pytestmark = pytest.mark.skipif(
    CELL not in REPO.cells, reason="a tree without full-1m-pct5.fill")


# ---- the tiny served run, sound and with each control ---------------------------


@pytest.mark.parametrize("fault, breaks", [
    (None, set()),
    ("node_filters_off", PARTS["affinity"]),
    ("skew_in_wave_off", PARTS["spread"]),
    ("interpod_in_wave_off", {"anti_affinity_violated"}),
], ids=["sound", "node_filters_off", "skew_in_wave_off", "interpod_in_wave_off"])
def test_the_sound_cell_keeps_every_rule_and_each_control_breaks_its_own(
        fault, breaks):
    """A third of a second of waves of 128 on 1,000 nodes: sound, the
    seventeen numbers read 0; each configuration's control breaks the
    numbers of its own family and nothing else (required affinity is safe
    on the wave-start counts, so the gate's control breaks anti-affinity
    alone), and is undone at close."""
    import k8s1m_tpu.control.coordinator  # noqa: F401  (registers the histogram)
    from k8s1m_tpu.obs.metrics import REGISTRY

    # as a process of its own starts (PERF.md 7 (q)): seconds an earlier
    # test of this worker left under ``fallback`` read as a wave that fell back
    REGISTRY.get("coordinator_cycle_seconds").reset()
    line = _rehearse(CELL, fault=fault, seconds=0.3)
    assert OWN <= set(line["compared"]) and line["failed"] == 0
    over = {k for k, c in line["compared"].items() if c["value"] > c["limit"]}
    assert over == breaks and line["correct"] is (not breaks)
    if fault:
        assert _rehearse(CELL, seconds=0.2)["correct"] is True


def test_the_tiny_cell_is_the_deployment_in_small():
    """What the tiny run changes is size alone: the slots, the widths, the
    profile, the skew count and the mix are the configuration's."""
    workload, config, pods = _tiny(CELL)
    assert config["table_spec"]["spread_slots"] == 16
    assert config["table_spec"]["affinity_slots"] == 16
    assert config["table_spec"]["label_slots"] == 6
    assert config["pod_spec"]["affinity_refs"] == 2
    assert config["pod_spec"]["spread_refs"] == 2
    assert config["coordinator"]["in_wave_skew"] is True
    assert all(w > 0 for w in config["profile"].values())
    assert sum(s["weight"] for s in pods["shapes"]) == 64
    assert len(pods["shapes"]) == 26
    both = [s for s in pods["shapes"] if "spread_constraints" in s
            and "pod_anti_affinity" in s and "node_affinity" in s]
    assert [s["app"] for s in both] == [f"ha-{d}" for d in range(4)]
    assert config["reference"] == "full" and workload["pods"] == "full"


# ---- the reference: the union of the three ---------------------------------------

ZONE, HOST = "topology.kubernetes.io/zone", "kubernetes.io/hostname"
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
NODES = {"zones": 4, "regions": 2, "node_taints": [
    {"key": "kwok.x-k8s.io/node", "value": "fake", "effect": "NoSchedule"}],
    "group_taints": {"9": [{"key": "dedicated", "value": "batch",
                            "effect": "NoSchedule"}]},
    "group_labels": {"9": {"dedicated": "batch"}}}
HA = {"app": "ha", "spread_constraints": [
    {"maxSkew": 1, "topologyKey": ZONE, "whenUnsatisfiable": "DoNotSchedule",
     "labelSelector": {"matchLabels": {"app": "ha"}}}],
    "pod_anti_affinity": {REQUIRED: [{"labelSelector": {"matchExpressions": [
        {"key": "app", "operator": "In", "values": ["ha"]}]}, "topologyKey": HOST}]},
    "node_affinity": {REQUIRED: {"nodeSelectorTerms": [{"matchExpressions": [
        {"key": "kwok-group", "operator": "NotIn", "values": ["9"]}]}]}}}


def _numbers(name, binds, pattern):
    seen = {"bind_pod": np.arange(len(binds)), "bind_node": np.asarray(binds)}
    return run.load_reference(name)(
        seen, None, nodes=NODES, pattern=pattern, offered=len(binds))


@pytest.mark.parametrize("binds, want", [
    # node i: zone i mod 4, kwok-group i mod 10, its own hostname
    ([0, 1, 2, 3], {}),
    ([0, 1, 2, 7], {}),                                  # zone 3 twice is one over
    ([0, 0], {"anti_affinity_violated": 1, "zone_skew_exceeded": 1}),
    ([0, 4], {"zone_skew_exceeded": 1}),                 # zone 0 twice, 1 over 0
    ([9], {"selector_mismatch": 1, "taint_untolerated": 1}),   # the pool
    ([0, 4, 4], {"zone_skew_exceeded": 2, "anti_affinity_violated": 1}),
])
def test_the_reference_is_the_union_of_the_three(binds, want):
    """One pod of a shape that carries all three families: each family's
    number is its own reference's, and nothing is counted twice."""
    got = _numbers("full", binds, [HA])
    assert got == {**dict.fromkeys(OWN, 0), **want}
    parts = {}
    for name in PARTS:
        parts.update(_numbers(name, binds, [HA]))
    assert got == parts


def test_the_reference_reads_the_mix_of_the_cell():
    pods = run.read_json("benchmark", "pods", "full.json")
    pattern = [{k: v for k, v in s.items() if k != "weight"}
               for s in pods["shapes"] for _ in range(s["weight"])]
    ha = next(i for i, s in enumerate(pattern) if s.get("app") == "ha-0")
    period = len(pattern)
    # two replicas of ha-0 on node 8 (zone 0, group 8), then one on node 19
    # (group 9: the pool, tainted, and NotIn [9])
    seen = {"bind_pod": np.asarray([ha, ha + period, ha + 2 * period]),
            "bind_node": np.asarray([8, 8, 19])}
    got = run.load_reference("full")(
        seen, None, nodes={**NODES, "zones": 8, "regions": 4}, pattern=pattern,
        offered=ha + 2 * period + 1)
    assert got == {"selector_mismatch": 1, "taint_untolerated": 1,
                   "zone_skew_exceeded": 1, "anti_affinity_violated": 1,
                   "affinity_unmet": 0}


def test_the_reference_imports_nothing_of_the_program_or_the_harness():
    ref = os.path.join(ROOT, "benchmark", "references", "full.py")
    with open(ref) as f:
        src = f.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", src, re.M)
    assert imports == ["importlib.util", "os"]
    assert "def numbers(seen, replayed, *, nodes, pattern, offered)" in src
    for name in PARTS:
        assert f'"{name}"' in src


# ---- the cell's four metrics ------------------------------------------------------

CALL = 'custom-call(...), custom_call_target="tpu_custom_call"'
KERNELS = EVENTS + [
    (DEV, "XLA Ops", f"%fused_topk_affinity_constraints.2 = {CALL}", 2.3, 0.05),
    (DEV, "XLA Ops", f"%fused_topk_affinity.3 = {CALL}", 2.4, 0.04),
    (DEV, "XLA Ops", f"%fused_topk_constraints.4 = {CALL}", 2.5, 0.03),
]


def _value(metric, ctx):
    spec = run.read_json("benchmark", "metrics", f"{metric}.json")
    return readers.resolve(spec["reader"])(spec["args"], ctx)


def test_each_kernel_is_read_by_its_own_name():
    """The combined kernel, the affinity one and the constraint one in one
    wave: each metric reads its own kernel alone."""
    ctx = _synthetic_ctx(trace={"events": KERNELS, "plane": DEV})
    assert _value("affinity_constraints_topk_ms.fill", ctx) == pytest.approx(50.0)
    assert _value("affinity_topk_ms.fill", ctx) == pytest.approx(40.0)
    assert _value("constraints_topk_ms.fill", ctx) == pytest.approx(30.0)
    assert _value("fused_topk_ms.fill", ctx) == pytest.approx(100.0)
    # a trace without the kernel (the parent, a wave of other shapes): nothing
    bare = _synthetic_ctx(trace={"events": EVENTS, "plane": DEV})
    assert _value("affinity_constraints_topk_ms.fill", bare) is None
    assert _value("affinity_constraints_topk_roofline.fill", bare) is None


def test_the_roofline_counts_both_stages_planes():
    """Split label words (1M hostnames) make ``label_val`` a plane of its
    own; the constraint planes are ``(4, slots)`` a node row."""
    spec = run.read_json("benchmark", "metrics",
                         "affinity_constraints_topk_roofline.fill.json")
    columns = spec["args"]["columns"]
    assert columns[:len(roofline.BASE_COLUMNS)] == list(roofline.BASE_COLUMNS)
    assert {"label_key", "label_val", "label_num", "zone", "region",
            "spread_node", "tgt_node", "own_node"} == set(columns) - set(
                roofline.BASE_COLUMNS)
    split = {"cpu_alloc": (4, 1), "mem_alloc": (4, 1), "cpu_req": (4, 1),
             "mem_req": (4, 1), "pods_req": (4, 1), "pods_alloc": (2, 1),
             "meta": (4, 1), "taint_id": (2, 3), "label_key": (4, 6),
             "label_val": (4, 6), "label_num": (4, 6), "zone": (2, 1),
             "region": (1, 1), "spread_node": (4, 16), "tgt_node": (4, 16),
             "own_node": (4, 16)}
    assert roofline.row_bytes(split, columns) == 32 + 72 + 3 + 192
    ctx = _synthetic_ctx(trace={"events": KERNELS, "plane": DEV}, shapes={
        "scan_rows": 53248, "columns": split, "batch": 4096, "k": 9,
        "pod_bytes": 16})
    moved = 53248 * 299 + 4096 * 16 + 4096 * 9 * 8
    assert _value("affinity_constraints_topk_roofline.fill", ctx) == \
        pytest.approx(100 * moved / 819e9 / 0.05)
    # the tiny cell's own columns: every one the metric names is there
    shapes = _shapes(CELL)
    assert set(columns) <= set(shapes["columns"])
    assert shapes["columns"]["spread_node"] == (4, 16)
    assert shapes["columns"]["tgt_node"] == shapes["columns"]["own_node"] == (4, 16)
    assert shapes["k"] == 9


def _counted(waves, unbound, valid):
    """Snapshots of the counters the two shares read, at open and close."""
    kernel = lambda d: {(("kernel", k),): v for k, v in d.items()}
    reason = lambda d: {(("reason", k),): v for k, v in d.items()}
    return {
        "open": {"coordinator_waves_total": kernel(waves[0]),
                 "coordinator_wave_unbound_total": reason(unbound[0]),
                 "coordinator_assign_pods_total": {(("path", "scan"),): valid[0]}},
        "close": {"coordinator_waves_total": kernel(waves[1]),
                  "coordinator_wave_unbound_total": reason(unbound[1]),
                  "coordinator_assign_pods_total": {(("path", "scan"),): valid[1]}},
    }


@pytest.mark.parametrize("waves, want", [
    (({"fused_topk_constraints": 2.0},
      {"fused_topk_constraints": 2.0, "fused_topk_affinity_constraints": 40.0}),
     100.0),
    (({}, {"filter_score_topk_affinity_constraints": 3.0,
           "filter_score_topk_constraints": 1.0}), 75.0),
    (({}, {"fused_topk_affinity": 5.0, "fused_topk_constraints": 5.0}), 0.0),
])
def test_the_wave_share_counts_the_combined_kernel_alone(waves, want):
    counters = _counted(waves, ({}, {}), (0.0, 1.0))
    assert _value("affinity_constraints_wave_pct.fill",
                  {"counters": counters}) == pytest.approx(want)


def test_the_share_the_skew_sent_back(monkeypatch):
    """``skew`` over the valid pods the window dispatched; the other reasons
    do not count; a program that names no such reason reads nothing."""
    from k8s1m_tpu.engine import assign

    counters = _counted(({}, {}), ({"skew": 4.0, "interpod": 9.0},
                                   {"skew": 45.0, "interpod": 20.0}),
                        (100.0, 8292.0))
    assert _value("skew_blocked_pct.fill", {"counters": counters}) == \
        pytest.approx(100 * 41 / 8192)
    monkeypatch.setattr(assign, "UNBOUND_REASONS", ("capacity",))
    assert _value("skew_blocked_pct.fill", {"counters": counters}) is None
    assert {m["name"] for m in run.metrics_of(MANIFEST, "per_layer", CELL)} >= {
        "affinity_constraints_topk_ms.fill",
        "affinity_constraints_topk_roofline.fill",
        "affinity_constraints_wave_pct.fill", "skew_blocked_pct.fill",
        "interpod_gate_ms.fill", "interpod_blocked_pct.fill",
        "cons_prologue_ms.fill", "engine_step_ms.fill"}
