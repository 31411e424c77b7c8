"""The coordinator's stages as spans on the profiler's clock, the lane
counters where intake forks, and the names on the device step (ISSUE 25);
the bind stage's inside, the step's own stages and the collector (ISSUE 37).

One 256-node CPU coordinator runs a bootstrap and four waves under a
short ``jax.profiler`` session; the tests read the trace it wrote, the
``coordinator_cycle_seconds`` histogram and the counters.
"""

from __future__ import annotations

import gc
import os
import re
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import span_readers
from k8s1m_tpu.config import PodSpec, TableSpec
from k8s1m_tpu.control.coordinator import _OVERLAP_STAGES, Coordinator
from k8s1m_tpu.control.objects import encode_node, encode_pod, node_key, pod_key
from k8s1m_tpu.engine import cycle
from k8s1m_tpu.obs import gcspan
from k8s1m_tpu.obs.metrics import REGISTRY
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot.bulkload import BulkNodeLoader
from k8s1m_tpu.snapshot.hotfeed import HotPodBatchHost
from k8s1m_tpu.snapshot.node_table import NodeTableHost
from k8s1m_tpu.snapshot.pod_encoding import PodInfo
from k8s1m_tpu.store.native import MemStore
from k8s1m_tpu.tools.make_nodes import build_node
from k8s1m_tpu.tools.make_pods import build_pod

NODES, WAVE = 256, 64
PROFILE = Profile(node_affinity=0, topology_spread=0, interpod_affinity=0)
# ISSUE 37: the step's own stages, the bind stage's children, the collector
STEP_STAGES = ("take", "prep", "settle")
BIND_CHILDREN = ("gather", "cas", "account", "per_pod", "nofit")
SPANS = {
    "coord.step", "coord.drain", "coord.drain.poll", "coord.drain.apply",
    "coord.sync", "coord.encode", "coord.device", "coord.sync_out",
    "coord.bind", "coord.bootstrap",
    "coord.bootstrap.relist", "coord.bootstrap.ingest",
    "coord.bootstrap.to_device", "feed.encode", gcspan.SPAN,
    *("coord." + s for s in STEP_STAGES),
    *("coord.bind." + c for c in BIND_CHILDREN),
}
CHILDREN = {
    "drain_poll": "drain", "drain_apply": "drain",
    "bootstrap_relist": "bootstrap", "bootstrap_ingest": "bootstrap",
    "bootstrap_to_device": "bootstrap",
    **{"bind_" + c: "bind" for c in BIND_CHILDREN},
}
NO_STAGE = {"coord.step", "feed.encode", gcspan.SPAN}
FORCED_COLLECTIONS = 2


def _lanes() -> dict:
    c = REGISTRY.get("coordinator_pod_intake_total")
    return {k[0]: c.value(lane=k[0]) for k in c.label_keys()}


def _grown(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _put(store, pods) -> None:
    for p in pods:
        store.put(pod_key(p.namespace, p.name), encode_pod(p))


def _collections() -> dict:
    runs = REGISTRY.get("process_gc_collections_total")
    secs = REGISTRY.get("process_gc_seconds_total")
    return {"runs": runs.value(generation="2"),
            "seconds": secs.value(generation="2")}


def _coordinator(store, **kw) -> Coordinator:
    return Coordinator(
        store, TableSpec(max_nodes=NODES), PodSpec(batch=WAVE), PROFILE,
        chunk=128, with_constraints=False, backend="pallas", pipeline=True,
        depth=2, packing="packed", score_pct=50, **kw,
    )


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Bootstrap, a canonical wave, two ``make_pods`` waves offered at once
    (so the hotfeed worker encodes the second behind the first) and a last
    one, all inside one profiler session.  The last wave holds a pod with
    a ``priority`` (lane ``json``: its record holds a PodInfo, so the
    retire enters it one by one) and a pod no node has room for (the
    device gives it no row; with one attempt allowed it is parked at
    once), and the collector is forced twice between steps."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    cyc = REGISTRY.get("coordinator_cycle_seconds")
    overlap = REGISTRY.get("pipeline_stage_overlap_seconds_total")
    store = MemStore()
    for i in range(NODES):
        store.put(node_key(f"kwok-node-{i}"), encode_node(build_node(i)))
    coord = _coordinator(store, max_attempts=1)
    cyc.reset()
    lanes = {"start": _lanes()}
    collections = {"start": _collections()}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        coord.bootstrap()
        _put(store, [PodInfo(f"plain-{i}", cpu_milli=10, mem_kib=1024)
                     for i in range(WAVE)])
        coord.step()
        lanes["canonical_wave"] = _lanes()
        _put(store, [build_pod(i) for i in range(2 * WAVE)])
        coord.step()
        lanes["make_pods_waves"] = _lanes()
        # a node update between waves dirties a row: the sync stage runs
        store.put(node_key("kwok-node-0"),
                  encode_node(build_node(0, cpu_milli=31000)))
        _put(store, [build_pod(i) for i in range(2 * WAVE, 3 * WAVE - 2)])
        _put(store, [
            PodInfo("selective", cpu_milli=10, mem_kib=1024,
                    node_selector={"kwok-group": "3"}, priority=3),
            PodInfo("too-big", cpu_milli=10 ** 7, mem_kib=1024),
        ])
        for _ in range(FORCED_COLLECTIONS):
            gc.collect()
        bound = coord.run_until_idle()
        collections["end"] = _collections()
    finally:
        jax.profiler.stop_trace()
        coord.close()
        store.close()
    return {
        "spans": [
            (line, n, s, s + d) for line, n, s, d in
            span_readers.load_names(trace_dir)["host_spans"]
        ],
        "stage_s": {k[0]: cyc.sum(stage=k[0]) for k in cyc.label_keys()},
        "overlap": {k[0] for k in overlap.label_keys()},
        "lanes": lanes, "bound": bound, "collections": collections,
        "parked": sorted(coord.unschedulable),
    }


def _spans(traced, name=None):
    """``(line id, name, start, end)`` of the trace's host spans."""
    return [sp for sp in traced["spans"] if name in (None, sp[1])]


def test_every_stage_is_a_span(traced):
    assert {n for _l, n, _s, _e in _spans(traced)} == SPANS
    assert traced["bound"] > 0


def test_every_span_is_a_stage_of_the_histogram(traced):
    """A stage cannot be timed without being a span, nor the other way:
    coord.<a>.<b> is the label <a>_<b>, and coord.step, feed.encode and
    the collector's coord.gc alone are no stage."""
    labels = {
        n[len("coord."):].replace(".", "_") for n in SPANS - NO_STAGE
    }
    assert set(traced["stage_s"]) == labels
    # the histogram and the trace count the same occurrences
    cyc = REGISTRY.get("coordinator_cycle_seconds")
    assert cyc.quantile(0.5, stage="drain_apply") > 0


@pytest.mark.parametrize("child,parent", sorted(CHILDREN.items()))
def test_children_lie_inside_parents(traced, child, parent):
    kids = _spans(traced, "coord." + child.replace("_", ".", 1))
    folks = _spans(traced, "coord." + parent)
    assert kids
    for line, _n, s, e in kids:
        assert any(l == line and ps <= s and e <= pe for l, _pn, ps, pe in folks)
    assert traced["stage_s"][child] <= traced["stage_s"][parent]


def test_poll_and_apply_make_up_the_drain(traced):
    st = traced["stage_s"]
    assert 0 < st["drain_poll"] + st["drain_apply"] <= st["drain"]
    assert 0 < st["bind_cas"] < st["bind"]
    parts = ("bootstrap_relist", "bootstrap_ingest", "bootstrap_to_device")
    assert sum(st[p] for p in parts) <= st["bootstrap"]


def test_step_is_the_root_and_the_feed_has_a_line_of_its_own(traced):
    steps = _spans(traced, "coord.step")
    main = {l for l, *_ in steps}
    assert len(main) == 1
    for line, name, s, e in _spans(traced):
        if name == "feed.encode":
            assert line not in main
            continue
        if name == gcspan.SPAN:         # on whatever thread it interrupted
            continue
        assert line in main
        # run_until_idle also drains and retires waves outside any step
        if name in ("coord.encode", "coord.device"):
            assert any(rs <= s and e <= re_ for _l, _n, rs, re_ in steps)


def test_the_bind_stage_accounts_for_itself(traced):
    """The five children are the bind stage but for its own few lines:
    their labels sum to no more than ``bind``, and the exception pods of
    the last wave opened the two that a plain wave never opens."""
    st = traced["stage_s"]
    parts = [st["bind_" + c] for c in BIND_CHILDREN]
    assert all(p > 0 for p in parts)
    assert sum(parts) <= st["bind"]
    assert traced["parked"] == ["default/too-big"]


@pytest.mark.parametrize("stage", STEP_STAGES)
def test_the_steps_own_stages_lie_inside_it(traced, stage):
    """``take``, ``prep`` and ``settle`` are top-level stages: inside a
    ``coord.step`` (or, for ``settle``, a retire of run_until_idle's
    flush) and inside no other stage."""
    steps = _spans(traced, "coord.step")
    others = [sp for sp in _spans(traced)
              if sp[1] not in NO_STAGE | {"coord." + stage}]
    found = _spans(traced, "coord." + stage)
    assert found
    for line, _n, s, e in found:
        assert not any(l == line and ps <= s and e <= pe
                       for l, _pn, ps, pe in others)
        if stage != "settle":
            assert any(l == line and ps <= s and e <= pe
                       for l, _pn, ps, pe in steps)


@pytest.mark.parametrize("name", [
    *("coord." + s for s in STEP_STAGES),
    *("coord.bind." + c for c in BIND_CHILDREN if c != "cas"),
])
def test_nothing_new_is_timed_per_pod(traced, name):
    """Every span of ISSUE 37 opens at most twice a wave, however many
    pods the wave holds."""
    waves = max(len(_spans(traced, "coord.bind")),
                len(_spans(traced, "coord.device")))
    assert 0 < len(_spans(traced, name)) <= 2 * waves


def test_the_collector_is_a_span_and_two_counters(traced):
    """Every collection is a ``coord.gc`` span and an addition to both
    counters, the forced ones among them; it is no stage of a wave."""
    before, after = (traced["collections"][k] for k in ("start", "end"))
    assert after["runs"] - before["runs"] >= FORCED_COLLECTIONS
    assert after["seconds"] > before["seconds"]
    assert len(_spans(traced, gcspan.SPAN)) >= FORCED_COLLECTIONS
    assert "gc" not in traced["stage_s"]
    # a span of the collector nests: it never straddles a stage's edge
    # on the line it interrupted
    for line, _n, s, e in _spans(traced, gcspan.SPAN):
        for l, name, ps, pe in _spans(traced):
            if l == line and name != gcspan.SPAN:
                assert e <= ps or pe <= s or (ps <= s and e <= pe), name


def test_installing_the_hook_twice_registers_one_callback():
    gcspan.install()
    gcspan.install()
    assert sum(cb is gcspan._HOOK for cb in gc.callbacks) == 1


def test_the_overlap_counter_keeps_its_labels(traced):
    assert traced["overlap"] <= set(_OVERLAP_STAGES)
    assert "drain_apply" not in traced["overlap"]


def test_a_canonical_wave_takes_the_batch_fast_lane(traced):
    lanes = traced["lanes"]
    assert _grown(lanes["start"], lanes["canonical_wave"]) == \
        {"batch_fast": WAVE}


def test_a_make_pods_wave_takes_the_batch_fast_lane(traced):
    """``make_pods``' pod (a label, a toleration) is parsed natively: no
    pod of it reaches json.loads."""
    lanes = traced["lanes"]
    assert _grown(lanes["canonical_wave"], lanes["make_pods_waves"]) == \
        {"batch_fast": 2 * WAVE}


def test_mixed_polls_count_every_event_once():
    """A poll that holds a delete is applied event by event: the canonical
    puts (one with a label), the delete and the put with a priority each
    count in their lane; a pod listed at bootstrap counts as the watch
    would have."""
    with MemStore() as store:
        for i in range(8):
            store.put(node_key(f"kwok-node-{i}"), encode_node(build_node(i)))
        _put(store, [PodInfo("listed", cpu_milli=10, mem_kib=1024)])
        coord = _coordinator(store)
        try:
            before = _lanes()
            coord.bootstrap()
            assert _grown(before, _lanes()) == {"decode_fast": 1}
            before = _lanes()
            _put(store, [PodInfo("a", cpu_milli=10, mem_kib=1024)])
            store.delete(pod_key("default", "a"))
            _put(store, [PodInfo("b", cpu_milli=10, mem_kib=1024),
                         PodInfo("c", cpu_milli=10, mem_kib=1024,
                                 labels={"app": "x"}),
                         PodInfo("d", cpu_milli=10, mem_kib=1024,
                                 priority=5)])
            coord.drain_watches()
            assert _grown(before, _lanes()) == \
                {"canonical": 3, "delete": 1, "json": 1}
        finally:
            coord.close()


def test_bulkload_counts_values_by_path():
    """Two chunks of 128, one cordoned node in the second: that chunk's
    128 values go per node, the other's take the template lane."""
    c = REGISTRY.get("bulkload_values_total")
    before = {p: c.value(path=p) for p in ("template", "per_node")}
    values = []
    for i in range(256):
        node = build_node(i)
        node.unschedulable = i == 200
        values.append(encode_node(node))
    rows = BulkNodeLoader(
        NodeTableHost(TableSpec(max_nodes=256)), chunk=128
    ).ingest(values)
    assert len(rows) == 256
    assert c.value(path="template") - before["template"] == 128
    assert c.value(path="per_node") - before["per_node"] == 128


@pytest.mark.parametrize("sample_rows", [None, 128])
def test_the_step_names_its_phases(sample_rows):
    """Both branches of the packed step (whole table, scan window) carry
    the three scopes and the kernel's name in every op_name under them."""
    ts, ps = TableSpec(max_nodes=NODES), PodSpec(batch=WAVE)
    host = NodeTableHost(ts)
    for i in range(NODES):
        host.upsert(build_node(i))
    packed = HotPodBatchHost(ps, ts, host.vocab).encode_packed_plain(
        np.full(WAVE, 100, np.int32), np.full(WAVE, 1024, np.int32)
    )
    step = cycle._jitted_schedule_packed(
        PROFILE, 128, 4, False, "pallas", packed.spec, packed.table_spec,
        packed.groups, sample_rows, False, False, 0,
    )
    hlo = step.lower(
        host.to_device(), packed.ints, packed.bools, jax.random.key(0),
        np.int32(0),
    ).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("candidates", "assign", "commit"):
        assert any(f"/{scope}/" in n for n in op_names), scope
    assert any("/candidates/" in n and "/fused_topk/" in n for n in op_names)
    assert any("/assign/while/" in n for n in op_names)
