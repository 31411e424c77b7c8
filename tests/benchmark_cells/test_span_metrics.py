"""The readers of what the program names (benchmark/span_readers.py), on
synthetic events; the eleven metrics that read them, in the manifest since
PR 27; the kept metrics unmoved; and the harness itself at a tiny size, with
the context it hands the readers kept.
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import readers, run, span_readers, trace_reduce
from test_benchmark_cells import (
    CELLS, COLUMNS, CPU_DEVICE, DEV, EVENTS, FIT, KWOK, MANIFEST, NAME, REPO, UNIT,
    _tiny, rule,
)

# the eleven that read spans, scopes and counters (PR 25), as PR 27 entered them
SPAN_METRICS = [
    "candidates_ms.fill", "assign_ms.fill", "commit_ms.fill",
    "drain_poll_us_per_bind.fill", "drain_apply_us_per_bind.fill",
    "bind_cas_us_per_bind.fill", "intake_fast_lane_pct.fill",
    "requeued_pct.fill", "bootstrap_s", "bootstrap_ingest_s",
    "bulkload_per_node_pct",
]
KEPT_METRICS = [
    "engine_step_ms.fill", "fused_topk_ms.fill", "fused_topk_roofline.fill",
    "host_us_per_bind.fill", "store_bind_us_per_bind.fill",
    "encode_us_per_bind.fill", "drain_us_per_bind.fill",
]
WAVES = {"wave_line": "XLA Modules", "wave_pattern": r"^jit__lambda\("}
# the accepted fixture, with the second while's body in it too; and its
# device ops by the scope XLA's tf_op stat would give them: the while has
# none (as on the chip), its body has
OPS = EVENTS + [(DEV, "XLA Ops", EVENTS[4][2], 2.2, 0.1)]
OP_NAMES = {
    EVENTS[4][2]: "jit(<lambda>)/assign/while/body/closed_call/reduce_or:",
    EVENTS[5][2]: "jit(<lambda>)/candidates/jit(_call)/fused_topk/pallas_call:",
}
HOST = [  # (line id, name, start, dur): the loop's thread is line 1
    (1, "bench.put", 0.0, 0.9), (1, "bench.step", 0.9, 1.2),
    (1, "coord.step", 0.95, 1.1), (1, "coord.drain", 1.0, 0.6),
    (1, "coord.drain.apply", 1.1, 0.4), (1, "coord.bind", 1.7, 0.2),
    (2, "feed.encode", 1.0, 0.9), (1, "bench.watch", 2.1, 0.9),
]


def _ctx(**more):
    return {"trace": {"events": OPS, "plane": DEV, "op_names": OP_NAMES},
            **more}


# ---- device scopes --------------------------------------------------------


def test_scope_time_is_a_union_not_a_sum():
    """The while (0.8 s, twice) contains its body's fusion (0.1 s): the
    assign scope covers 1.6 s over two waves, not 1.7."""
    ms = span_readers.trace_scope_ms_per_wave({"scope": "assign", **WAVES}, _ctx())
    assert ms == pytest.approx(800.0)
    assert span_readers.trace_scope_ms_per_wave(
        {"scope": "candidates", **WAVES}, _ctx()) == pytest.approx(100.0)


def test_a_loop_without_an_op_name_takes_its_bodys_scope():
    whiles = lambda ev: [sc for s, d, sc in
                         span_readers.scoped_ops(ev, DEV, OP_NAMES) if d == 0.8]
    assert whiles(OPS) == ["assign", "assign"]
    # the accepted fixture's second while holds nothing named
    assert whiles(EVENTS) == ["assign", None]
    # a container over two scopes takes neither; over one, that one
    both = [(DEV, "XLA Ops", "%call.9", 0.0, 1.0), *OPS]
    assert span_readers.scoped_ops(both, DEV, OP_NAMES)[0] == (0.0, 1.0, None)
    one = {**OP_NAMES, EVENTS[5][2]: "jit(f)/assign/x:"}
    assert span_readers.scoped_ops(both, DEV, one)[0] == (0.0, 1.0, "assign")


def test_scope_is_a_component_of_the_path_not_a_substring():
    assert span_readers.scope_of("jit(f)/assign/while/body/add:") == "assign"
    assert span_readers.scope_of("jit(f)/commit:") == "commit"
    assert span_readers.scope_of("jit(f)/reassign/add:") is None
    assert span_readers.scope_of("jit(f)/jit(commit_binds)/add:") is None


def test_device_scopes_add_up_to_busy_with_the_rest_unscoped():
    ev = OPS + [(DEV, "XLA Ops", "%copy.3 = copy(...)", 1.0, 0.05)]
    rows = span_readers.device_scopes(ev, DEV, OP_NAMES, 0.0, 3.0)
    assert [r[0] for r in rows] == ["assign", "candidates", "commit", "unscoped"]
    busy = trace_reduce.busy_window(ev, 0.0, 3.0)["busy_s"]
    assert sum(r[1] for r in rows) == pytest.approx(busy) == pytest.approx(1.85)
    assert dict(rows) == pytest.approx(
        {"assign": 1.6, "candidates": 0.2, "commit": 0.0, "unscoped": 0.05})
    # clipped to the window like busy_s
    half = dict(span_readers.device_scopes(ev, DEV, OP_NAMES, 0.5, 2.5))
    assert half["assign"] == pytest.approx(0.4 + 0.4)


def test_no_op_names_no_value():
    bare = {"trace": {"events": OPS, "plane": DEV}}
    args = {"scope": "assign", **WAVES}
    assert span_readers.trace_scope_ms_per_wave(args, bare) is None
    assert span_readers.trace_scope_ms_per_wave(args, {"trace": None}) is None
    assert span_readers.trace_scope_ms_per_wave(
        {"scope": "commit", **WAVES}, _ctx()) is None


# ---- a wave counted is a whole wave -----------------------------------------

KERNEL, LOOP, BODY = EVENTS[5][2], EVENTS[3][2], EVENTS[4][2]
COMMIT = "%scatter.9 = scatter(...)"
STEP_NAMES = {**OP_NAMES, COMMIT: "jit(<lambda>)/commit/scatter:"}
DEVICE_METRICS = ["engine_step_ms.fill", "fused_topk_ms.fill",
                  "fused_topk_roofline.fill", "candidates_ms.fill",
                  "assign_ms.fill", "commit_ms.fill"]


def _step(t, begins=None, ends=None):
    """The device's events of one 0.9 s step that starts at ``t``: the
    kernel (0.1 s, candidates), the loop (0.7 s, no op_name of its own)
    with two ops of its body (assign), the commit (0.05 s).  Where the
    trace ``begins`` or ``ends`` inside the step, what the profiler keeps
    of it: the module's event from there or up to there, and the ops that
    start and end on the trace's side; an op cut with it is gone."""
    lo = t if begins is None else begins
    hi = t + 0.9 if ends is None else ends
    ops = [(KERNEL, t, 0.1), (LOOP, t + 0.1, 0.7), (BODY, t + 0.2, 0.1),
           (BODY, t + 0.6, 0.1), (COMMIT, t + 0.85, 0.05)]
    return [(DEV, "XLA Modules", "jit__lambda(1)", lo, hi - lo)] + [
        (DEV, "XLA Ops", n, s, d) for n, s, d in ops if s >= lo and s + d <= hi + 1e-9]


def OTHER_MODULE(t):
    """Another module's event on the line, with its one op."""
    return [(DEV, "XLA Modules", "jit_scatter_rows(2)", t, 0.05),
            (DEV, "XLA Ops", "%copy.3 = copy(...)", t, 0.05)]


TRACES = {
    # the device was idle between two other modules: every step is whole
    "uncut": OTHER_MODULE(-0.2) + _step(0.0) + _step(1.0) + _step(2.0)
             + OTHER_MODULE(3.0),
    # the trace begins inside the first step: its kernel and the loop's own
    # event lie before the trace, half of the body and the commit inside
    "cut_at_its_start": _step(0.0, begins=0.5) + _step(1.0) + _step(2.0)
                        + OTHER_MODULE(3.0),
    "cut_at_its_end": OTHER_MODULE(-0.2) + _step(0.0) + _step(1.0)
                      + _step(2.0, ends=2.45),
    "cut_at_both_ends": _step(0.0, begins=0.5) + _step(1.0) + _step(2.0)
                        + _step(3.0, ends=3.45),
    # nothing before the first step and nothing after the last: both are
    # whole, and neither can be told from a cut one
    "nothing_around": _step(0.0) + _step(1.0) + _step(2.0) + _step(3.0),
}
CUT_ALONE = _step(0.0, begins=0.5)


def _device_values(events):
    ctx = {**_full_ctx(), "trace": {"events": events, "plane": DEV,
                                    "op_names": STEP_NAMES, "host_spans": []}}
    got = run.per_layer_values(MANIFEST, KWOK, ctx)
    return {m: got[m] for m in DEVICE_METRICS}


@pytest.mark.parametrize("trace", TRACES)
def test_a_trace_reads_its_whole_waves_whatever_its_ends_cut(trace):
    """A module, a kernel, a scope and the roofline: the per-wave times of
    the steps the trace holds whole, the same in every trace."""
    moved = 53248 * 42 + 4096 * 16 + 4096 * 4 * 8
    assert _device_values(TRACES[trace]) == pytest.approx({
        "engine_step_ms.fill": 900.0, "fused_topk_ms.fill": 100.0,
        "fused_topk_roofline.fill": 100 * moved / 819e9 / 0.1,
        "candidates_ms.fill": 100.0, "assign_ms.fill": 700.0,
        "commit_ms.fill": 50.0,
    })


def test_a_cut_wave_counted_as_one_read_every_time_low():
    """What the readers did before (ISSUE 32 (j)): the cut step's event
    counted as a wave, so three events for two kernels, and 0.4 s of a
    step for a third one."""
    cut = TRACES["cut_at_its_start"]
    kernel = trace_reduce.per_event(cut, DEV, "XLA Ops", r"^%fused_topk[.\d]* = ")
    steps = trace_reduce.per_event(cut, DEV, "XLA Modules", r"^jit__lambda\(")
    assert (kernel[1], steps[1]) == (2, 3)
    assert 1e3 * kernel[0] / steps[1] == pytest.approx(100.0 * 2 / 3)
    assert 1e3 * steps[0] / steps[1] == pytest.approx((400.0 + 2 * 900.0) / 3)
    waves = trace_reduce.whole_waves(cut, DEV, "XLA Modules", r"^jit__lambda\(")
    assert waves == [(1.0, pytest.approx(1.9)), (2.0, pytest.approx(2.9))]


def test_a_trace_with_no_cut_event_reads_what_it_read_before():
    """Sum over count, as ``per_event`` gives them, on the uncut trace and
    on the accepted fixture."""
    for events, waves in ((TRACES["uncut"], 3), (OPS, 2)):
        for metric in ("engine_step_ms.fill", "fused_topk_ms.fill"):
            args = run.read_json("benchmark", "metrics", f"{metric}.json")["args"]
            total, count = trace_reduce.per_event(
                events, DEV, args["line"], args["pattern"])
            assert count == waves
            ctx = {"trace": {"events": events, "plane": DEV}}
            assert readers.trace_ms_per_wave(args, ctx) == pytest.approx(
                1e3 * total / waves)


@pytest.mark.parametrize("metric", DEVICE_METRICS)
def test_a_trace_with_nothing_but_a_cut_event_reads_nothing(metric):
    """Never 0, and never the cut step's part as if it were a step."""
    assert _device_values(CUT_ALONE)[metric] is None
    # nor does a whole step alone, which no reader can tell from a cut one
    assert _device_values(_step(0.0))[metric] is None


def test_whole_waves_are_those_with_something_before_and_after_on_their_line():
    step = r"^jit__lambda\("
    waves = lambda ev: trace_reduce.whole_waves(ev, DEV, "XLA Modules", step)
    assert waves(TRACES["uncut"]) == [
        (0.0, pytest.approx(0.9)), (1.0, pytest.approx(1.9)), (2.0, pytest.approx(2.9))]
    assert [w[0] for w in waves(TRACES["nothing_around"])] == [1.0, 2.0]
    assert waves(CUT_ALONE) == [] and waves([]) == []
    # another module's event on the line is something before, an op is not
    only_ops_before = [(DEV, "XLA Ops", "%copy.3 = copy(...)", -0.2, 0.05),
                       *_step(0.0), *_step(1.0), *OTHER_MODULE(2.0)]
    assert [w[0] for w in waves(only_ops_before)] == [1.0]
    # two timestamps a rounding apart are one
    near = [*OTHER_MODULE(-0.05 + 4e-7), *_step(0.0), *OTHER_MODULE(1.0)]
    assert [w[0] for w in waves(near)] == [0.0]


def test_inside_keeps_what_lies_in_a_whole_wave():
    waves = [(1.0, 1.9), (2.0, 2.9)]
    ops = [(0.95, 0.1, "a"), (1.0, 0.9, "b"), (1.5, 0.1, "c"), (1.85, 0.1, "d"),
           (1.95, 0.02, "e"), (2.0 - 4e-7, 0.5, "f"), (2.5, 0.4 + 4e-7, "g"),
           (3.0, 0.1, "h")]
    assert [o[2] for o in trace_reduce.inside(waves, ops)] == ["b", "c", "f", "g"]
    assert trace_reduce.inside([], ops) == [] and trace_reduce.inside(waves, []) == []


# ---- idle time by host span -----------------------------------------------


def test_the_innermost_span_wins():
    segs = span_readers.innermost_segments(
        [(0, 10, "a"), (1, 3, "b"), (2, 2.5, "c"), (5, 6, "d"), (12, 13, "e")])
    assert segs == [(0, 1, "a"), (1, 2, "b"), (2, 2.5, "c"), (2.5, 3, "b"),
                    (3, 5, "a"), (5, 6, "d"), (6, 10, "a"), (12, 13, "e")]


def test_idle_time_is_split_over_the_spans_second_for_second():
    """The device idles 0.9 .. 2.0 and 2.9 .. 3.0; the first gap is split
    over what the loop's thread was in, innermost first."""
    idle = dict(span_readers.idle_by_span(EVENTS, DEV, HOST, 0.0, 3.0))
    assert idle == pytest.approx({
        "bench.step": 0.05, "coord.step": 0.05 + 0.1 + 0.1, "coord.drain": 0.1 + 0.1,
        "coord.drain.apply": 0.4, "coord.bind": 0.2, "bench.watch": 0.1,
    })
    assert sum(idle.values()) == pytest.approx(
        3.0 - trace_reduce.busy_window(EVENTS, 0.0, 3.0)["busy_s"])
    late = dict(span_readers.idle_by_span(EVENTS, DEV, HOST, 0.0, 3.2))
    assert late["unattributed"] == pytest.approx(0.2)


def test_a_span_on_another_threads_line_never_attributes():
    idle = dict(span_readers.idle_by_span(EVENTS, DEV, HOST, 0.0, 3.0))
    assert "feed.encode" not in idle
    # even where nothing on the loop's own line covers the gap
    alone = [sp for sp in HOST if sp[1] in ("bench.step", "feed.encode")]
    idle = dict(span_readers.idle_by_span(EVENTS, DEV, alone, 0.0, 3.0))
    assert set(idle) == {"bench.step", "unattributed"}


# ---- counters and set-up stages -------------------------------------------


def _snap(**lanes):
    return {"c_total": {(("lane", k),): float(v) for k, v in lanes.items()}}


def test_counter_share_over_the_window_and_over_setup():
    ctx = {"counters": {"open": _snap(json=10, batch_fast=30),
                        "close": _snap(json=110, batch_fast=30, delete=5)}}
    args = {"counter": "c_total", "labels": [{"lane": "json"}],
            "of": [{"lane": "json"}, {"lane": "batch_fast"}], "over": "window"}
    assert span_readers.counter_share_pct(args, ctx) == pytest.approx(100.0)
    assert span_readers.counter_share_pct({**args, "over": "setup"}, ctx) \
        == pytest.approx(25.0)
    every = {k: v for k, v in args.items() if k != "of"}      # all label sets
    assert span_readers.counter_share_pct(every, ctx) == pytest.approx(100 * 100 / 105)


def test_counter_share_of_a_zero_denominator_is_nothing():
    ctx = {"counters": {"open": _snap(json=10), "close": _snap(json=10)}}
    args = {"counter": "c_total", "labels": [{"lane": "json"}], "over": "window"}
    assert span_readers.counter_share_pct(args, ctx) is None
    assert span_readers.counter_share_pct({**args, "counter": "absent"}, ctx) is None
    assert span_readers.counter_share_pct(args, {}) is None


def test_setup_stages_are_read_from_before_the_window():
    ctx = {"setup_stage_s": {"bootstrap": 26.0, "bootstrap_ingest": 17.0},
           "stage_s": {"bootstrap": 0.0}}
    assert span_readers.setup_stage_s({"stages": ["bootstrap"]}, ctx) == 26.0
    assert span_readers.setup_stage_s({"stages": ["resync"]}, ctx) is None
    assert span_readers.setup_stage_s({"stages": ["bootstrap"]}, {}) is None


def test_snapshots_read_the_programs_registry():
    import k8s1m_tpu.control.coordinator  # noqa: F401  (registers the counters)

    from k8s1m_tpu.obs.metrics import REGISTRY, Counter

    snap = span_readers.snapshot_counters(
        ["coordinator_pod_intake_total", "no_such_counter_total",
         "coordinator_cycle_seconds"])          # a histogram is no counter
    assert set(snap) == {"coordinator_pod_intake_total"}
    assert all(k[0][0] == "lane" for k in snap["coordinator_pod_intake_total"])
    assert isinstance(span_readers.stage_sums(), dict)
    # with no names: every counter the registry holds, one a later PR adds too
    every = span_readers.snapshot_counters()
    assert set(every) == {m.name for m in REGISTRY.metrics()
                          if isinstance(m, Counter)}
    assert {"coordinator_pod_intake_total", "coordinator_pod_shapes_total",
            "coordinator_pods_scheduled_total", "bulkload_values_total"} <= set(every)
    assert every["coordinator_pod_intake_total"] == snap["coordinator_pod_intake_total"]


# ---- the one loader ---------------------------------------------------------


def test_loader_reads_op_names_and_line_ids(tmp_path):
    space = trace_reduce._xspace_class()()
    dev = space.planes.add(name=DEV)
    dev.stat_metadata.add(key=7).value.name = "tf_op"
    dev.stat_metadata.add(key=8).value.name = "hlo_category"
    named = dev.event_metadata.add(key=1).value
    named.name = "%fusion.1 = fusion(...)"
    named.stats.add(metadata_id=8, str_value="fusion")
    named.stats.add(metadata_id=7, str_value="jit(f)/assign/while/body/add:")
    dev.event_metadata.add(key=2).value.name = "%while.7 = while(...)"
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "coord.drain"
    host.event_metadata.add(key=2).value.name = "PjRtExecute"
    for line_id in (11, 12):                # two threads, as two lines do
        line = host.lines.add(id=line_id, name="python3",
                              timestamp_ns=2_000_000_000)
        line.events.add(metadata_id=1, offset_ps=500_000_000_000,
                        duration_ps=250_000_000_000)
        line.events.add(metadata_id=2, offset_ps=0, duration_ps=1)
    out = tmp_path / "plugins" / "profile" / "run"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(space.SerializeToString())
    got = trace_reduce.load_trace(str(tmp_path))
    assert got["op_names"] == {DEV: {
        "%fusion.1 = fusion(...)": "jit(f)/assign/while/body/add:"}}
    assert got["host_spans"] == [
        (11, "coord.drain", pytest.approx(2.5), pytest.approx(0.25)),
        (12, "coord.drain", pytest.approx(2.5), pytest.approx(0.25)),
    ]
    # one parse gives the events too, every one of them; the two older
    # entry points are views of it
    assert sorted(e[:3] for e in got["events"]) == sorted(
        [("/host:CPU", "python3", "coord.drain"),
         ("/host:CPU", "python3", "PjRtExecute")] * 2)
    assert trace_reduce.load(str(tmp_path)) == got["events"]
    assert span_readers.load_names(str(tmp_path)) == {
        "op_names": got["op_names"], "host_spans": got["host_spans"]}
    assert not hasattr(span_readers, "_xspace_class")


# ---- the eleven, and the seven kept metrics ----------------------------------


EIGHTEEN = KEPT_METRICS + SPAN_METRICS


@rule()
def the_manifest_holds_the_eleven_and_the_seven(tree):
    """The eighteen first and in their order, each with the cells it had
    (PR 27); what a later PR appends, a metric or a cell's name in a
    list, comes after them."""
    per_layer = tree.manifest["per_layer"]
    assert [m["name"] for m in per_layer][:len(EIGHTEEN)] == EIGHTEEN
    by_name = {m["name"]: m for m in per_layer}
    # the two restated: a sound reading of exactly 0 has no ratio to keep
    assert "intake_slow_lane_pct.fill" not in by_name
    assert by_name["intake_fast_lane_pct.fill"]["better"] == "higher"
    assert by_name["requeued_pct.fill"]["workloads"][0] == FIT
    assert KWOK not in by_name["requeued_pct.fill"]["workloads"]
    assert all(by_name[m]["workloads"][:2] == [KWOK, FIT]
               for m in EIGHTEEN if m != "requeued_pct.fill")
    assert tree.cells[:2] == [KWOK, FIT]
    for m in tree.manifest["end_to_end"] + per_layer:
        assert set(m.get("workloads", [])) <= set(tree.cells)
    assert {by_name[m]["moves"] for m in SPAN_METRICS[-3:]} == {"setup_s"}
    assert {by_name[m]["layer"] for m in SPAN_METRICS[-3:]} == {"snapshot"}


def test_the_manifest_holds_the_eleven_and_the_seven():
    the_manifest_holds_the_eleven_and_the_seven(REPO)


@rule("per_layer")
def span_metric_spec(tree, metric):
    """An entry and its file, whatever reader the file names; for the
    eleven, the reader it named when it entered the manifest."""
    entry = next(m for m in tree.manifest["per_layer"] if m["name"] == metric)
    spec = run.read_json("benchmark", "metrics", f"{metric}.json")
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert set(spec) == {"reader", "args", "what"} and spec["what"]
    assert NAME.match(metric) and UNIT.match(entry["unit"])
    assert entry["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
    assert entry["moves"] in tree.e2e
    reader = readers.resolve(spec["reader"])
    if metric not in SPAN_METRICS:
        return
    assert entry["source"] in ("device_trace", "program_counter")
    if "." in spec["reader"]:
        assert reader is getattr(span_readers, spec["reader"].split(".")[1])
    else:
        assert reader is readers.registry_stage_per_bind


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_span_metric_spec(metric):
    span_metric_spec(REPO, metric)


@rule()
def span_readers_take_no_accepted_readers_name(tree):
    """A reader of ``span_readers`` is named ``span_readers.<function>`` by
    the metric files that read it, and none of them has the name of one of
    ``readers.READERS``, which a metric file gives bare."""
    dotted = {run.read_json("benchmark", "metrics", f"{m}.json")["reader"]
              for m in SPAN_METRICS} - set(readers.READERS)
    assert len(dotted) == 3
    for name in dotted:
        module, function = name.split(".")
        assert module == "span_readers" and function not in readers.READERS
        assert readers.resolve(name) is getattr(span_readers, function)


def test_span_readers_take_no_accepted_readers_name():
    span_readers_take_no_accepted_readers_name(REPO)


def _full_ctx():
    events = OPS + [
        ("/host:CPU", "python3", n, s, d) for _l, n, s, d in HOST
        if n.startswith(("coord.", "feed."))
    ]
    return {
        "stage_s": {"drain": 1.0, "drain_poll": 0.1, "drain_apply": 0.9,
                    "bind": 3.0, "bind_cas": 1.0, "sync_out": 5.0},
        "setup_stage_s": {"bootstrap": 26.0, "bootstrap_ingest": 24.0},
        "counters": {
            "open": {"coordinator_pod_intake_total": {
                         (("lane", "batch_fast"),): 384.0, (("lane", "json"),): 7.0},
                     "coordinator_pods_scheduled_total": {
                         (("outcome", "bound"),): 384.0},
                     "coordinator_bind_retire_total": {
                         (("lane", "columnar"),): 384.0},
                     "bulkload_values_total": {
                         (("path", "per_node"),): 750.0, (("path", "template"),): 250.0}},
            "close": {"coordinator_pod_intake_total": {
                          (("lane", "batch_fast"),): 1284.0, (("lane", "json"),): 107.0,
                          (("lane", "delete"),): 5.0},
                      "coordinator_pods_scheduled_total": {
                          (("outcome", "bound"),): 1334.0, (("outcome", "retry"),): 50.0},
                      "coordinator_bind_retire_total": {
                          (("lane", "columnar"),): 1234.0, (("lane", "per_pod"),): 150.0},
                      "bulkload_values_total": {
                          (("path", "per_node"),): 750.0, (("path", "template"),): 250.0}},
        },
        "binds": 1000,
        "trace": {"events": events, "plane": DEV, "op_names": OP_NAMES,
                  "host_spans": HOST},
        "shapes": {"scan_rows": 53248, "columns": COLUMNS, "batch": 4096,
                   "k": 4, "pod_bytes": 16},
        "peaks": {"hbm_bytes_per_s": 819e9},
    }


@rule()
def every_manifest_metric_reads_a_full_context(tree):
    """With the program's spans in the trace, op_names beside it, every
    stage label and both counter snapshots, ``run.per_layer_values`` reads
    all eighteen: the seven kept ones what test_readers_on_a_synthetic_window
    pins (``encode`` is no stage of this window: no value, not 0).  Held
    for the two cells of PR 27 by name; any other cell reads, of the
    eighteen it opts into, the same values.  A metric this fixture knows
    nothing of has a file whose reader resolves; the fixture's shapes are
    not its deployment's, so it is not read here."""
    moved = 53248 * 42 + 4096 * 16 + 4096 * 4 * 8
    pinned = {
        "encode_us_per_bind.fill": None,
        "commit_ms.fill": None,                     # nothing under commit here
        "requeued_pct.fill": 5.0,
        "engine_step_ms.fill": 1000.0, "fused_topk_ms.fill": 100.0,
        "fused_topk_roofline.fill": 100 * moved / 819e9 / 0.1,
        "host_us_per_bind.fill": 4000.0, "store_bind_us_per_bind.fill": 3000.0,
        "drain_us_per_bind.fill": 1000.0,
        "candidates_ms.fill": 100.0, "assign_ms.fill": 800.0,
        "drain_poll_us_per_bind.fill": 100.0,
        "drain_apply_us_per_bind.fill": 900.0,
        "bind_cas_us_per_bind.fill": 1000.0,
        "intake_fast_lane_pct.fill": 90.0,      # 900 of 1000; deletes apart
        "bootstrap_s": 26.0, "bootstrap_ingest_s": 24.0,
        "bulkload_per_node_pct": 75.0,
    }
    assert sorted(pinned) == sorted(EIGHTEEN)
    known = {**tree.manifest, "per_layer": [
        m for m in tree.manifest["per_layer"] if m["name"] in pinned]}
    for cell in tree.cells:
        got = run.per_layer_values(known, cell, _full_ctx())
        opted = {m["name"] for m in run.metrics_of(known, "per_layer", cell)}
        if cell in (KWOK, FIT):
            assert opted == set(pinned) - ({"requeued_pct.fill"} if cell == KWOK else set())
        assert set(got) == opted
        for name in opted:
            assert got[name] == (pinned[name] if pinned[name] is None
                                 else pytest.approx(pinned[name])), name
    for m in tree.manifest["per_layer"]:
        if m["name"] not in pinned:
            spec = run.read_json("benchmark", "metrics", f"{m['name']}.json")
            assert callable(readers.resolve(spec["reader"]))


def test_every_manifest_metric_reads_a_full_context():
    every_manifest_metric_reads_a_full_context(REPO)


NINETEENTH = "bind_columnar_pct.fill"


@rule()
def the_nineteenth_follows_the_eighteen(tree):
    """PR 32's metric of the retire lanes: right after the eighteen, with
    both cells of PR 27 first in its list; a cell whose pods retire one by
    one appends its name and reads its share here."""
    entry = tree.manifest["per_layer"][len(EIGHTEEN)]
    assert entry == {
        "name": NINETEENTH, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "store", "moves": "binds_per_s",
        "workloads": entry["workloads"]}
    assert entry["workloads"][:2] == [KWOK, FIT]
    spec = run.read_json("benchmark", "metrics", f"{NINETEENTH}.json")
    assert spec["reader"] == "span_readers.counter_share_pct"
    assert spec["args"] == {
        "counter": "coordinator_bind_retire_total", "over": "window",
        "labels": [{"lane": "columnar"}],
        "of": [{"lane": "columnar"}, {"lane": "per_pod"}]}


def test_the_nineteenth_follows_the_eighteen():
    the_nineteenth_follows_the_eighteen(REPO)


def test_the_nineteenth_reads_the_share_of_the_columnar_lane():
    """850 of the window's 1,000 retired pods in columns; the counter is
    registered by the program, so a window in which no pod reached the
    bind stage reads nothing."""
    import k8s1m_tpu.control.coordinator  # noqa: F401  (registers the counter)

    ctx = _full_ctx()
    for cell in (KWOK, FIT):
        assert run.per_layer_values(MANIFEST, cell, ctx)[NINETEENTH] \
            == pytest.approx(85.0)
    assert "coordinator_bind_retire_total" in span_readers.snapshot_counters()
    still = {**ctx, "counters": {"open": ctx["counters"]["close"],
                                 "close": ctx["counters"]["close"]}}
    assert run.per_layer_values(MANIFEST, KWOK, still)[NINETEENTH] is None


def test_assign_ms_reads_what_the_retired_loop_metric_read():
    """``assign_loop_ms.fill`` matched ``^%while``; ``assign_ms.fill`` reads
    the assign scope, whatever is under it: the same 800 ms on the fixture,
    and still 800 when the loop is rebuilt without a ``while``."""
    ctx = _full_ctx()
    assert run.per_layer_values(MANIFEST, CELLS[0], ctx)["assign_ms.fill"] \
        == pytest.approx(800.0)
    loop = trace_reduce.per_event(OPS, DEV, "XLA Ops", r"^%while[.\d]* = ")
    assert 1e3 * loop[0] / 2 == pytest.approx(800.0)
    renamed = [(p, l, n.replace("%while.7", "%scan_fusion.7"), s, d)
               for p, l, n, s, d in ctx["trace"]["events"]]
    ctx["trace"] = {**ctx["trace"], "events": renamed}
    assert trace_reduce.per_event(renamed, DEV, "XLA Ops", r"^%while")[1] == 0
    assert run.per_layer_values(MANIFEST, CELLS[0], ctx)["assign_ms.fill"] \
        == pytest.approx(800.0)


# ---- the harness itself at a tiny size, its readers' context kept ------------


def the_harness_reads_the_counters_of_a_tiny_run(tree, cell):
    """The rules every cell is held to; what is particular to one (the
    lanes its window rides, the shapes it interns, how many of its metrics
    need no trace, values a tiny run pins) is in
    ``tests/benchmark_cells/cells/<cell>.json`` (``Tree.cell_data``)."""
    from k8s1m_tpu.obs.metrics import REGISTRY

    manifest, data = tree.manifest, tree.cell_data(cell)
    shapes = REGISTRY.get("coordinator_pod_shapes_total")
    interned = shapes.value(event="interned")
    before = span_readers.snapshot_counters(["bulkload_values_total"])
    ctx = {}
    result = run.run_cell(
        manifest, cell, _tiny(cell, tree), seed=(1 << 31) + 25, seconds=0.5,
        trace=False, device=dict(CPU_DEVICE), peaks={}, keep=ctx,
    )
    assert result["correct"] is True
    assert set(result["metrics"]) == {
        m["name"] for m in run.metrics_of(manifest, "end_to_end", cell)}
    assert set(ctx) == {"stage_s", "setup_stage_s", "counters", "binds", "trace",
                        "shapes", "peaks"}
    # a cordon in its one chunk: set-up ingested every node value one by one
    # (the reader counts from the process's start, which is this run's on
    # the chip and the test session's here: take this run's part)
    ctx["counters"]["open"]["bulkload_values_total"] = {
        key: n - before.get("bulkload_values_total", {}).get(key, 0.0)
        for key, n in ctx["counters"]["open"]["bulkload_values_total"].items()}
    got = {k: v for k, v in run.per_layer_values(manifest, cell, ctx).items()
           if v is not None}
    reports = {m["name"]: m for m in run.metrics_of(manifest, "per_layer", cell)}
    untraced = {m for m in reports if reports[m]["source"] != "device_trace"}
    assert set(got) == untraced         # each has a value; no trace, no other
    for a, b in (("bootstrap_ingest_s", "bootstrap_s"),
                 ("bind_cas_us_per_bind.fill", "store_bind_us_per_bind.fill"),
                 ("drain_poll_us_per_bind.fill", "drain_us_per_bind.fill"),
                 ("drain_apply_us_per_bind.fill", "drain_us_per_bind.fill")):
        if a in got and b in got:
            assert 0 < got[a] < got[b]
    assert set(ctx["stage_s"]) >= {"drain", "drain_poll", "drain_apply",
                                   "bind", "bind_cas", "encode", "device"}
    assert set(ctx["setup_stage_s"]) >= {"bootstrap", "bootstrap_ingest"}
    # every counter of the registry, not a list of names
    assert "coordinator_pod_shapes_total" in ctx["counters"]["close"]
    assert len(ctx["counters"]["close"]) > 10
    # the cell's own: how many metrics read without a trace, what they pin
    if "untraced_metrics" in data:
        assert len(untraced) == data["untraced_metrics"]
    for name, value in data.get("values", {}).items():
        assert got[name] == value, name
    # the lanes its window rides, and no other; their counts are the pods
    # it offered, give or take the wave in flight at each edge
    lanes = lambda at: ctx["counters"][at]["coordinator_pod_intake_total"]
    grown = {key[0][1]: n - lanes("open").get(key, 0)
             for key, n in lanes("close").items()}
    if "window_lanes" in data:
        assert {lane for lane, n in grown.items() if n} == set(data["window_lanes"])
        wave = _tiny(cell, tree)[0]["wave"]
        rode = sum(grown[lane] for lane in data["window_lanes"])
        assert abs(rode - result["attempted"]) <= 2 * wave
    if "shapes_interned" in data:
        assert shapes.value(event="interned") - interned == data["shapes_interned"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_harness_reads_the_counters_of_a_tiny_run(cell):
    the_harness_reads_the_counters_of_a_tiny_run(REPO, cell)
    # both cells of PR 27 bring their file, with that PR's values
    if cell in (KWOK, FIT):
        assert REPO.cell_data(cell) == {
            "untraced_metrics": 13 if cell == FIT else 12,
            "window_lanes": ["batch_fast"], "shapes_interned": 1,
            "values": {"intake_fast_lane_pct.fill": 100.0,
                       "bulkload_per_node_pct": 100.0},
        }
