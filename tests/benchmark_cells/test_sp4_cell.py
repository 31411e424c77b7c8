"""full-1m-pct5-sp4.fill's own tests: the configuration is full-1m-pct5's
on a 1x4 mesh and nothing else; the tiny served run on four of the test
session's eight host devices, sound and with each of the three controls
full-1m-pct5.fill has; no executable built after warm-up in either full
cell (a leftover wave runs the window's step); and the seven metrics of the
mesh on the synthetic trace the accepted readers are tested on.
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generate, readers, roofline, run
from test_benchmark_cells import (
    CPU_DEVICE, DEV, EVENTS, MANIFEST, REPO, _rehearse, _synthetic_ctx, _tiny,
)
from test_full_cell import OWN, PARTS

CELL = "full-1m-pct5-sp4.fill"
ONE_CHIP = "full-1m-pct5.fill"
MESH_METRICS = [
    "mesh_step_ms.fill", "mesh_topk_ms.fill", "mesh_topk_roofline.fill",
    "sp_merge_ms.fill", "mesh_collective_ms.fill", "mesh_assign_ms.fill",
    "mesh_zone_merge_wave_pct.fill",
]

pytestmark = pytest.mark.skipif(
    CELL not in REPO.cells, reason="a tree without full-1m-pct5-sp4.fill")


def test_the_configuration_is_full_1m_pct5s_on_a_1x4_mesh():
    """Nodes, widths, profile, reference and every knob of the coordinator
    are the one-chip cell's; the mesh, the name, the source and two more
    assumptions (the replicas, the shard-local window) are its own."""
    _w1, one, pods1 = run.load_cell(MANIFEST, ONE_CHIP)
    workload, sp4, pods = run.load_cell(MANIFEST, CELL)
    assert sp4["coordinator"] == {**one["coordinator"], "mesh": "1x4"}
    same = set(one) - {"name", "source", "coordinator", "assumed"}
    assert same == set(sp4) - {"name", "source", "coordinator", "assumed"}
    for key in same:
        assert sp4[key] == one[key], key
    assert sp4["assumed"][:len(one["assumed"])] == one["assumed"]
    assert "16,384 rows a chip" in sp4["assumed"][-1]
    assert pods == pods1 and workload["wave"] == 4096
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 4
    assert sp4["table_spec"]["max_nodes"] // 4 == 262144
    assert roofline.window_rows(262144, sp4["coordinator"]["score_pct"],
                                sp4["coordinator"]["chunk"]) == 16384


# ---- the tiny served run on a 1x4 mesh, sound and with each control ----------------


def _rehearse_pods(cell, pods: int, fault=None):
    """The tiny run, its window ended by a number of pods and not by the
    clock (a brim that many pods above nothing), so that what it offers
    does not hang on how fast the host runs."""
    workload, config, mix = _tiny(cell)
    nodes = generate.Nodes(config["nodes"])
    slots = sum(not nodes.cordoned(i) for i in range(nodes.count)) * nodes.pods
    workload = dict(workload, brim_slack_pods=slots - pods)
    return run.run_cell(
        MANIFEST, cell, (workload, config, mix), seed=(1 << 31) + 11,
        seconds=600.0, trace=False, device=dict(CPU_DEVICE), peaks={},
        fault=fault)


@pytest.mark.parametrize("fault, breaks", [
    (None, set()),
    ("node_filters_off", PARTS["affinity"]),
    ("skew_in_wave_off", PARTS["spread"]),
    ("interpod_in_wave_off", {"anti_affinity_violated"}),
], ids=["sound", "node_filters_off", "skew_in_wave_off", "interpod_in_wave_off"])
def test_the_sound_mesh_cell_keeps_every_rule_and_each_control_breaks_its_own(
        fault, breaks):
    """As full-1m-pct5.fill's: sound, the seventeen numbers read 0 on the
    mesh; each control breaks the numbers of its own family alone (the
    gate's control reaches the mesh's step too), and is undone at close.
    Sixty-four waves of 128 each time: four shards scan four one-device
    windows a wave, so two replicas of a wave meet on a node less often
    than on one device, and a window by the clock could see none."""
    import k8s1m_tpu.control.coordinator  # noqa: F401  (registers the histogram)
    from k8s1m_tpu.obs.metrics import REGISTRY

    REGISTRY.get("coordinator_cycle_seconds").reset()
    line = _rehearse_pods(CELL, 64 * 128, fault=fault)
    assert line["attempted"] == (64 - run.WARM_UP_WAVES) * 128
    assert OWN <= set(line["compared"]) and line["failed"] == 0
    over = {k for k, c in line["compared"].items() if c["value"] > c["limit"]}
    assert over == breaks and line["correct"] is (not breaks)
    if fault:
        assert _rehearse(CELL, seconds=0.2)["correct"] is True


# ---- after the window: no executable built -----------------------------------------


@pytest.mark.parametrize("cell", [ONE_CHIP, CELL])
def test_a_tiny_full_run_builds_no_executable_after_warm_up(cell, monkeypatch):
    """From the end of warm-up to the check (the window, the drain of what
    the gate and the skew sent back), ``run.py``'s counter of executables
    built does not move: a leftover wave of fewer field groups runs the
    step the window ran."""
    marks = {}
    warm_up, check = run.Cell.warm_up, run.Cell.check

    def marked_warm_up(self):
        warm_up(self)
        marks["warm"] = self.compiles.built

    def marked_check(self, win):
        marks["check"] = self.compiles.built
        return check(self, win)

    monkeypatch.setattr(run.Cell, "warm_up", marked_warm_up)
    monkeypatch.setattr(run.Cell, "check", marked_check)
    line = _rehearse(cell, seconds=0.5)
    assert line["correct"] is True
    assert marks["check"] == marks["warm"]


# ---- the mesh's seven metrics ------------------------------------------------------

CALL = 'custom-call(...), custom_call_target="tpu_custom_call"'
STEP = lambda name: name.replace("jit__lambda(", "jit__step_cons(")
MESH_EVENTS = [(p, line, STEP(name), s, d) for p, line, name, s, d in EVENTS] + [
    (DEV, "XLA Ops", EVENTS[4][2], 2.2, 0.1),       # the second while's body
    (DEV, "XLA Ops", f"%fused_topk_affinity_constraints.2 = {CALL}", 2.91, 0.05),
    (DEV, "XLA Ops", "%all-gather-start.3 = (s32[4,4096,9]) all-gather-start(...)",
     2.96, 0.002),
    (DEV, "XLA Ops", "%all-gather-done.3 = s32[4,4096,9] all-gather-done(...)",
     2.97, 0.003),
    (DEV, "XLA Ops", "%all-reduce.5 = s32[9] all-reduce(...)", 2.98, 0.001),
    # a collective outside any whole step: not counted
    (DEV, "XLA Ops", "%all-reduce.6 = s32[9] all-reduce(...)", 1.5, 0.1),
]
OP_NAMES = {
    "%all-gather-start.3 = (s32[4,4096,9]) all-gather-start(...)":
        "jit(_step_cons)/shard_map/candidates/sp_merge/all_gather:",
    "%all-gather-done.3 = s32[4,4096,9] all-gather-done(...)":
        "jit(_step_cons)/shard_map/candidates/sp_merge/all_gather:",
    EVENTS[4][2]: "jit(_step_cons)/shard_map/assign/while/body/reduce_or:",
}


def _value(metric, ctx):
    spec = run.read_json("benchmark", "metrics", f"{metric}.json")
    return readers.resolve(spec["reader"])(spec["args"], ctx)


def _mesh_ctx(**more):
    return _synthetic_ctx(trace={"events": MESH_EVENTS, "plane": DEV,
                                 "op_names": OP_NAMES}, **more)


def test_the_mesh_step_and_its_kernel_are_read_on_the_mesh_module():
    ctx = _mesh_ctx()
    assert _value("mesh_step_ms.fill", ctx) == pytest.approx(1000.0)
    assert _value("mesh_topk_ms.fill", ctx) == pytest.approx(50.0)
    assert _value("mesh_assign_ms.fill", ctx) == pytest.approx(800.0)
    assert _value("sp_merge_ms.fill", ctx) == pytest.approx(5.0)
    assert _value("mesh_collective_ms.fill", ctx) == pytest.approx(6.0)
    # the one-chip step is another module: the one-chip metrics read
    # nothing of a mesh's trace, and the mesh's nothing of one chip's
    assert _value("engine_step_ms.fill", ctx) is None
    one_chip = _synthetic_ctx()
    for metric in MESH_METRICS[:-1]:
        assert _value(metric, one_chip) is None, metric
        assert _value(metric, {**ctx, "trace": None}) is None, metric


def test_the_mesh_roofline_counts_one_shards_window():
    """16,384 rows of the columns the one-chip roofline counts, the wave's
    pods and one shard's candidates, over the kernel's seconds on that
    chip; the whole table's window would read 53,248 / 16,384 times
    that."""
    spec = run.read_json("benchmark", "metrics", "mesh_topk_roofline.fill.json")
    one = run.read_json("benchmark", "metrics",
                        "affinity_constraints_topk_roofline.fill.json")
    assert spec["args"]["columns"] == one["args"]["columns"]
    columns = {c: (4, 1) for c in spec["args"]["columns"]}
    shapes = {"scan_rows": 53248, "columns": columns, "batch": 4096, "k": 9,
              "pod_bytes": 16}
    got = _value("mesh_topk_roofline.fill", _mesh_ctx(shapes=shapes))
    row = 4 * len(columns)
    moved = 16384 * row + 4096 * 16 + 4096 * 9 * 8
    assert got == pytest.approx(100 * moved / 819e9 / 0.05)
    whole = 53248 * row + 4096 * 16 + 4096 * 9 * 8
    assert got < 100 * whole / 819e9 / 0.05


@pytest.mark.parametrize("merges, want", [
    (({"zone": 2.0}, {"zone": 40.0}), 100.0),
    (({}, {"zone": 3.0, "topk": 1.0}), 75.0),
    (({}, {"topk": 5.0}), 0.0),
])
def test_the_zone_merge_share_counts_mesh_waves(merges, want):
    merge = lambda d: {(("merge", k),): v for k, v in d.items()}
    counters = {"open": {"mesh_waves_total": merge(merges[0])},
                "close": {"mesh_waves_total": merge(merges[1])}}
    assert _value("mesh_zone_merge_wave_pct.fill",
                  {"counters": counters}) == pytest.approx(want)
    # a program without the counter (the parent): nothing
    assert _value("mesh_zone_merge_wave_pct.fill",
                  {"counters": {"open": {}, "close": {}}}) is None


def test_the_mesh_metrics_list_the_mesh_cell_alone():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    for metric in MESH_METRICS:
        assert entries[metric]["workloads"] == [CELL], metric
    # the one-chip kernel's roofline counts the whole window: not this cell's
    assert CELL not in entries["affinity_constraints_topk_roofline.fill"]["workloads"]
