"""spread-1m-pct5.fill's own tests: its control kept at 1,000 nodes (as the
three built-in ones are), its reference on hand-made histories, and the
reader its prologue metric brought (benchmark/scope_readers.py) on the
synthetic trace the accepted readers are tested on.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import readers, run, scope_readers, span_readers
from test_benchmark_cells import DEV, EVENTS, MANIFEST, REPO, _rehearse
from test_span_metrics import OP_NAMES, OPS, WAVES

CELL = "spread-1m-pct5.fill"
NUMBER = "zone_skew_exceeded"

pytestmark = pytest.mark.skipif(
    CELL not in REPO.cells, reason="a tree without spread-1m-pct5.fill")


def test_the_sound_cell_keeps_the_skew_and_the_control_breaks_it_alone():
    """Half a second of waves of 128 on 1,000 nodes (63 open nodes in zone
    7): sound, the thirteen numbers read 0 and no pod comes back; with the
    in-wave count off, ``zone_skew_exceeded`` alone is above its limit."""
    import k8s1m_tpu.control.coordinator  # noqa: F401  (registers the counter)
    from k8s1m_tpu.obs.metrics import REGISTRY

    sched = REGISTRY.get("coordinator_pods_scheduled_total")
    retried = sched.value(outcome="retry")
    sound = _rehearse(CELL)
    assert sound["correct"] is True and sound["compared"][NUMBER]["value"] == 0
    assert sched.value(outcome="retry") == retried      # every pod at once
    line = _rehearse(CELL, fault="skew_in_wave_off")
    assert line["correct"] is False and line["failed"] == 0
    over = {k for k, c in line["compared"].items() if c["value"] > c["limit"]}
    assert over == {NUMBER}
    assert line["compared"][NUMBER]["value"] > line["attempted"] // 20


def _numbers(bind_node, pattern, zones=4):
    seen = {"bind_pod": np.arange(len(bind_node)), "bind_node": np.asarray(bind_node)}
    return run.load_reference("spread")(
        seen, None, nodes={"zones": zones}, pattern=pattern, offered=len(bind_node)
    )[NUMBER]


def _shape(app, skew=1, mode="DoNotSchedule", key="topology.kubernetes.io/zone"):
    return {"app": app, "spread_constraints": [{
        "maxSkew": skew, "topologyKey": key, "whenUnsatisfiable": mode,
        "labelSelector": {"matchLabels": {"app": app}}}]}


def test_the_reference_counts_each_deployment_in_watch_order():
    two = [_shape("a"), _shape("b")]
    # pods 0, 2, 4, 6 are a's, nodes 0..3 lie in zones 0..3
    assert _numbers([0, 0, 1, 1, 2, 2, 3, 3], two) == 0
    assert _numbers([0, 0, 4, 1, 2, 2, 3, 3], two) == 1     # a: 2 in zone 0
    # the order of the watch decides: the same binds, a's second first
    assert _numbers([0, 0, 1, 1, 2, 2, 3, 3, 0, 0], two) == 0
    assert _numbers([0, 0, 4, 1, 1, 2, 2, 3, 3, 0], two) == 1
    # maxSkew is the shape's own; ScheduleAnyway and hostname forbid nothing
    assert _numbers([0, 0, 4, 1, 2, 2, 3, 3], [_shape("a", 2), _shape("b")]) == 0
    assert _numbers([0, 4, 8, 12], [_shape("a", mode="ScheduleAnyway")]) == 0
    assert _numbers([0, 4, 8, 12], [_shape("a", key="kubernetes.io/hostname")]) == 0
    assert _numbers([0, 4, 8, 12], [_shape("a")]) == 3
    # a pod without the key is counted for nobody and held to nothing
    assert _numbers([0, 4, 8, 12], [{"cpu_milli": 100}]) == 0


def test_a_scope_inside_a_phase_is_read_alone_and_the_phase_still_holds_it():
    """The kernel's 0.1 s a wave lies under candidates; a prologue op of
    0.05 s under candidates/cons_prologue beside it reads 50 ms under its
    own name and brings candidates to 150."""
    prologue = "%fusion.77 = fusion(...)"
    events = OPS + [(DEV, "XLA Ops", prologue, 0.9, 0.05),
                    (DEV, "XLA Ops", prologue, 2.9, 0.05)]
    names = {**OP_NAMES,
             prologue: "jit(<lambda>)/candidates/cons_prologue/reduce_min:"}
    ctx = {"trace": {"events": events, "plane": DEV, "op_names": names}}
    spec = run.read_json("benchmark", "metrics", "cons_prologue_ms.fill.json")
    reader = readers.resolve(spec["reader"])
    assert reader is scope_readers.named_scope_ms_per_wave
    assert reader(spec["args"], ctx) == pytest.approx(50.0)
    assert span_readers.trace_scope_ms_per_wave(
        {"scope": "candidates", **WAVES}, ctx) == pytest.approx(150.0)
    # a program that never opens the scope (the parent): nothing, no error
    bare = {"trace": {"events": OPS, "plane": DEV, "op_names": OP_NAMES}}
    assert reader(spec["args"], bare) is None
    assert reader(spec["args"], {"trace": None}) is None
    assert reader(spec["args"], {"trace": {"events": OPS, "plane": DEV}}) is None


def test_the_kernels_two_metrics_read_the_constraint_kernel_by_its_name():
    """``%fused_topk_constraints.<n>`` is no ``%fused_topk.<n>``: each
    metric reads its own kernel, and the roofline counts the planes."""
    kernel = "%fused_topk_constraints.3 = custom-call(...)"
    events = [(p, l, kernel if n == EVENTS[5][2] else n, s, d)
              for p, l, n, s, d in OPS]
    ctx = {"trace": {"events": events, "plane": DEV, "op_names": {}},
           "shapes": {"scan_rows": 1000, "batch": 10, "k": 9, "pod_bytes": 16,
                      "columns": {c: (4, 1) for c in (
                          "cpu_alloc", "mem_alloc", "cpu_req", "mem_req",
                          "pods_req", "pods_alloc", "meta", "taint_id", "zone",
                          "region")} | {"spread_node": (4, 32), "tgt_node": (4, 1),
                                        "own_node": (4, 1)}},
           "peaks": {"hbm_bytes_per_s": 1e9}}
    got = run.per_layer_values(MANIFEST, CELL, {
        **ctx, "stage_s": {}, "setup_stage_s": {}, "counters": None, "binds": 0})
    assert got["constraints_topk_ms.fill"] == pytest.approx(100.0)
    moved = 1000 * 4 * (10 + 34) + 10 * 16 + 10 * 9 * 8
    assert got["constraints_topk_roofline.fill"] == pytest.approx(
        100 * moved / 1e9 / 0.1)
    old = run.read_json("benchmark", "metrics", "fused_topk_ms.fill.json")
    assert readers.resolve(old["reader"])(old["args"], ctx) is None
