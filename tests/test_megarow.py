"""megarow (ISSUE 14): the million-row shape's host-path rewrites,
each gated by a byte-identity differential against the code it
replaced, plus the 131k tier-1 smoke of the end-to-end drill.

1. ``NodeTableHost.bulk_upsert`` == a loop of ``upsert`` — columns,
   dtypes, row mapping, vocab contents AND intern order, epoch, row
   journal — including re-add-same-name and quarantined-row reuse.
2. ``snapshot/bulkload.BulkNodeLoader`` (the template cold-relist
   lane) == ``upsert(decode_node(v))`` over mixed canonical /
   non-canonical value streams, across chunk boundaries.
3. ``list_prefix_values`` / ``list_prefix_sharded`` == ``list_prefix``.
4. ``RowVersions`` journal boundary: exactly-full vs one-past-full
   fail closed the same way before and after the scale-aware cap —
   and the derived cap IS the old fixed cap at the old 131k size.
5. The incremental preemption-victims index materializes to exactly
   the old full ``_bound.items()`` scan, through binds, deletes,
   evictions and a resync.
6. Host-mirror narrow dtypes: spec-bounded columns shrink, the device
   table stays int32, out-of-range effects fail closed.
7. ``megarow_drill --smoke``: 131,072 rows end to end in tier-1, with
   the >= 3x cold-build proxy and the peak-RSS budget gated inside
   the drill (the full 1M run is ``-m slow``).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from k8s1m_tpu.config import PodSpec, TableSpec  # noqa: E402
from k8s1m_tpu.control.coordinator import Coordinator  # noqa: E402
from k8s1m_tpu.control.objects import (  # noqa: E402
    decode_node,
    encode_node,
    encode_pod,
    node_key,
    pod_key,
)
from k8s1m_tpu.engine.deltacache import DeltaPlaneCache  # noqa: E402
from k8s1m_tpu.plugins.registry import Profile  # noqa: E402
from k8s1m_tpu.snapshot.bulkload import BulkNodeLoader  # noqa: E402
from k8s1m_tpu.snapshot.node_table import (  # noqa: E402
    NodeInfo,
    NodeTableHost,
    RowVersions,
    Taint,
    mirror_dtype,
)
from k8s1m_tpu.snapshot.pod_encoding import PodInfo  # noqa: E402
from k8s1m_tpu.store.native import (  # noqa: E402
    MemStore,
    list_prefix,
    list_prefix_sharded,
    list_prefix_values,
)
from k8s1m_tpu.tenancy import TenancyController, TenancyPolicy  # noqa: E402
from k8s1m_tpu.tools.make_nodes import build_node  # noqa: E402

COLUMNS = (
    "valid", "cpu_alloc", "mem_alloc", "pods_alloc",
    "cpu_req", "mem_req", "pods_req",
    "label_key", "label_val", "label_num",
    "taint_id", "taint_effect", "zone", "region", "name_id",
)


def _spec(n=256):
    return TableSpec(max_nodes=n, max_zones=16, max_regions=8)


def _vocab_state(v):
    return {
        k: list(getattr(v, k)._to_val)
        for k in ("label_keys", "label_values", "taints",
                  "node_names", "zones", "regions")
    }


def _assert_identical(a: NodeTableHost, b: NodeTableHost):
    for col in COLUMNS:
        ca, cb = getattr(a, col), getattr(b, col)
        assert ca.dtype == cb.dtype, col
        assert np.array_equal(ca, cb), col
    assert a._row_of == b._row_of
    assert a.epoch == b.epoch
    assert a._row_journal == b._row_journal
    assert _vocab_state(a.vocab) == _vocab_state(b.vocab)


def _mixed_nodes(n=400):
    nodes = []
    for i in range(n):
        nd = build_node(i)
        if i % 7 == 0:
            nd.taints = [Taint("gpu", "true", 1), Taint("spot", "", 3)]
        if i % 11 == 0:
            nd.unschedulable = True
        if i % 13 == 0:
            nd.labels["kubernetes.io/hostname"] = f"alias-{i}"
        if i % 17 == 0:
            nd.labels["intl"] = "зона"          # non-ASCII: json escapes
        if i % 5 == 0:
            nd.labels["rank"] = str(i * 3)      # numeric label value
        nodes.append(nd)
    return nodes


# ---- 1. bulk_upsert == loop of upserts -------------------------------


def test_bulk_upsert_identical_to_sequential_loop():
    nodes = _mixed_nodes()
    a, b = NodeTableHost(_spec(512)), NodeTableHost(_spec(512))
    a.enable_row_journal()
    b.enable_row_journal()
    rows = a.bulk_upsert(nodes)
    ref = [b.upsert(nd) for nd in nodes]
    assert rows.tolist() == ref
    _assert_identical(a, b)


def test_bulk_upsert_null_label_value_matches_upsert():
    """A JSON-null label value (decode_node passes None through) must
    intern to NONE_ID in the bulk lane exactly like Interner.intern's
    None mapping in upsert — not as a fresh vocab id."""
    nd = build_node(0)
    nd.labels["nulled"] = None
    a, b = NodeTableHost(_spec(16)), NodeTableHost(_spec(16))
    a.bulk_upsert([nd, build_node(1)])
    b.upsert(nd)
    b.upsert(build_node(1))
    _assert_identical(a, b)
    assert None not in a.vocab.label_values._to_val[1:]


def test_bulk_upsert_readd_same_name_and_update():
    """Re-adding a present name updates its row in place (last write
    wins inside one batch too), exactly like repeated upserts."""
    base = _mixed_nodes(60)
    changed = [build_node(i) for i in range(30, 90)]
    for nd in changed:
        nd.cpu_milli = 999
    a, b = NodeTableHost(_spec(512)), NodeTableHost(_spec(512))
    a.bulk_upsert(base)
    a.bulk_upsert(changed)
    # duplicate names within ONE batch: later entry wins
    dup = build_node(5)
    dup.mem_kib = 123456
    a.bulk_upsert([build_node(5), dup])
    for nd in base:
        b.upsert(nd)
    for nd in changed:
        b.upsert(nd)
    b.upsert(build_node(5))
    b.upsert(dup)
    _assert_identical(a, b)
    assert int(a.mem_alloc[a.row_of("kwok-node-5")]) == 123456


def test_bulk_upsert_quarantined_row_interaction():
    """A remove under a live wave epoch parks the row; bulk re-add must
    allocate fresh rows (never the quarantined ids), like upsert."""
    a, b = NodeTableHost(_spec(512)), NodeTableHost(_spec(512))
    first = [build_node(i) for i in range(50)]
    for h in (a, b):
        h.bulk_upsert(first) if h is a else [h.upsert(n) for n in first]
        h.begin_wave()
        h.remove("kwok-node-3")
        h.remove("kwok-node-7")
    readd = [build_node(i) for i in range(60)]
    a.bulk_upsert(readd)
    for nd in readd:
        b.upsert(nd)
    _assert_identical(a, b)
    assert a.quarantined == b.quarantined == 2
    qrows = {row for _e, row in a._quarantine}
    assert qrows.isdisjoint(a._row_of.values())
    # quarantined rows release after the wave retires, then get reused
    a.release_rows(None)
    b.release_rows(None)
    extra = [build_node(100), build_node(101)]
    ra = a.bulk_upsert(extra)
    rb = [b.upsert(nd) for nd in extra]
    assert ra.tolist() == rb and set(rb) == qrows
    _assert_identical(a, b)


def test_bulk_upsert_validates_before_mutating():
    host = NodeTableHost(_spec(64))
    bad = build_node(1)
    bad.labels = {f"k{i}": "v" for i in range(40)}   # > label_slots
    with pytest.raises(ValueError):
        host.bulk_upsert([build_node(0), bad])
    # nothing landed: no rows, no journal, untouched columns
    assert host.num_nodes == 0 and not host.valid.any()
    with pytest.raises(ValueError):
        host.bulk_upsert([NodeInfo("t", taints=[Taint("k", "v", 9)])])
    with pytest.raises(ValueError):
        host.upsert(NodeInfo("t", taints=[Taint("k", "v", 9)]))


def test_bulk_alloc_capacity_checked_before_any_allocation():
    """A batch larger than the allocatable rows raises RowsExhausted
    BEFORE any name is mapped — a mid-batch raise would leave names
    resolving to rows whose columns were never written."""
    from k8s1m_tpu.snapshot.node_table import RowsExhausted

    host = NodeTableHost(_spec(16))
    host.bulk_upsert([build_node(i) for i in range(10)])
    host.begin_wave()
    host.remove("kwok-node-0")       # quarantined: not allocatable
    before = dict(host._row_of)
    with pytest.raises(RowsExhausted) as ei:
        host.bulk_upsert([build_node(i) for i in range(100, 108)])
    assert ei.value.quarantined == 1
    assert host._row_of == before    # nothing mapped
    # duplicates within the batch count once: 6 distinct fresh names
    # fit exactly (16 max - 10 ever-allocated; the quarantined row is
    # NOT reusable), even though the batch has 7 entries
    dup = [build_node(i) for i in (200, 200, 201, 202, 203, 204, 205)]
    rows = host.bulk_upsert(dup)
    assert rows[0] == rows[1]


# ---- 2. the bulkload template lane -----------------------------------


def test_bulkload_ingest_identical_mixed_stream():
    values = [encode_node(nd) for nd in _mixed_nodes(300)]
    a, b = NodeTableHost(_spec(512)), NodeTableHost(_spec(512))
    a.enable_row_journal()
    b.enable_row_journal()
    rows = BulkNodeLoader(a, chunk=64).ingest(values)
    ref = [b.upsert(decode_node(v)) for v in values]
    assert rows.tolist() == ref
    _assert_identical(a, b)


def test_bulkload_template_reupsert_clears_taints():
    """A canonical (taintless) re-upsert of a previously tainted node
    must zero the taint columns through the template fast path."""
    tainted = build_node(0)
    tainted.taints = [Taint("gpu", "x", 1)]
    plain = build_node(0)
    a, b = NodeTableHost(_spec(64)), NodeTableHost(_spec(64))
    loader = BulkNodeLoader(a)
    loader.ingest([encode_node(tainted)])
    loader.ingest([encode_node(plain)] * 2)   # template path, re-upsert
    b.upsert(tainted)
    b.upsert(plain)
    b.upsert(plain)
    _assert_identical(a, b)
    assert not a.taint_id[a.row_of("kwok-node-0")].any()


# ---- 3. relist variants == list_prefix -------------------------------


def test_list_prefix_values_and_sharded_match():
    store = MemStore()
    prefix = b"/registry/minions/"
    items = [
        (node_key(f"kwok-node-{i}"), encode_node(build_node(i)))
        for i in range(731)
    ]
    for off in range(0, len(items), 100):
        store.put_batch(items[off:off + 100])
    try:
        kvs, rev = list_prefix(store, prefix, page=97)
        vals, vrev = list_prefix_values(store, prefix, page=97)
        skvs, srev = list_prefix_sharded(store, prefix, shards=5, page=97)
        assert vrev == rev and srev == rev
        assert vals == [kv.value for kv in kvs]
        assert [(kv.key, kv.value, kv.mod_revision) for kv in skvs] == \
               [(kv.key, kv.value, kv.mod_revision) for kv in kvs]
        # shards=1 degrades to the serial path
        s1, r1 = list_prefix_sharded(store, prefix, shards=1, page=97)
        assert [kv.key for kv in s1] == [kv.key for kv in kvs] and r1 == rev
    finally:
        store.close()


# ---- 4. RowVersions: boundary + the scale-aware cap ------------------


def _drive(rv: RowVersions, batches):
    stamps = []
    for rows in batches:
        stamps.append(rv.note(rows))
    return stamps


def test_rowversions_boundary_full_vs_one_past_full():
    """Journal exactly full: every consumer delta stays enumerable.
    One entry past full: compaction raises the floor and consumers
    stamped below it fail CLOSED (None = recompute), never a partial
    delta.  Identical behavior at the old fixed cap and at the
    scale-aware cap evaluated at the old size."""
    for rv in (RowVersions(cap=64),
               DeltaPlaneCache(128, journal_cap=64).versions):
        v0 = rv.ver
        _drive(rv, ([i] for i in range(64)))      # exactly full
        assert len(rv) == 64 and rv.floor == 0
        assert rv.rows_since(v0) == set(range(64))
        rv.note([64])                              # one past full
        assert rv.floor > 0
        assert len(rv) == 32                       # compacted to cap//2
        assert rv.rows_since(v0) is None           # fail closed
        assert rv.rows_since(rv.floor - 1) is None
        live = rv.rows_since(rv.floor)
        assert live is not None and 64 in live


def test_scale_aware_journal_cap_derivation():
    # old size -> exactly the old fixed cap (the differential anchor)
    assert DeltaPlaneCache(131072).versions.cap == 1 << 16
    # below: floored at the old cap; above: half the table
    assert DeltaPlaneCache(2048).versions.cap == 1 << 16
    assert DeltaPlaneCache(1 << 20).versions.cap == 1 << 19
    # explicit override still wins
    assert DeltaPlaneCache(1 << 20, journal_cap=123).versions.cap == 123


def test_scale_aware_cap_trajectory_matches_fixed_cap_at_old_size():
    """Same note/compact/release trajectory, entry for entry."""
    a = RowVersions(cap=1 << 16)
    b = DeltaPlaneCache(131072).versions
    rng = np.random.default_rng(0)
    for _ in range(40):
        rows = rng.integers(0, 131072, size=int(rng.integers(1, 4096)))
        a.note(rows)
        b.note(rows)
    assert (a.ver, a.floor, len(a)) == (b.ver, b.floor, len(b))
    assert list(a._journal) == list(b._journal)
    a.release(a.ver - 5)
    b.release(b.ver - 5)
    assert list(a._journal) == list(b._journal) and a.floor == b.floor


# ---- 5. incremental victims index == full scan -----------------------


def test_victims_index_incremental_matches_full_scan():
    store = MemStore()
    for i in range(8):
        store.put(node_key(f"n{i:03d}"), encode_node(NodeInfo(
            name=f"n{i:03d}", cpu_milli=8000, mem_kib=1 << 20, pods=16,
        )))
    tn = TenancyController(TenancyPolicy(log_preemptions=True))
    coord = Coordinator(
        store, TableSpec(max_nodes=16, max_zones=4, max_regions=2),
        PodSpec(batch=16), Profile(topology_spread=0, interpod_affinity=0),
        chunk=16, k=4, with_constraints=False, seed=3, tenancy=tn,
    )
    try:
        coord.bootstrap()
        assert coord._track_victims
        for i in range(48):
            pod = PodInfo(f"f-{i:03d}", namespace=f"t{i % 3}",
                          cpu_milli=1000, mem_kib=1 << 10)
            store.put(pod_key(pod.namespace, pod.name), encode_pod(pod))
        assert coord.run_until_idle() == 48
        assert coord._victims_index() == coord._victims_index_full()
        # deletions drop entries
        store.delete(pod_key("t0", "f-000"))
        store.delete(pod_key("t1", "f-001"))
        coord.drain_watches()
        assert coord._victims_index() == coord._victims_index_full()
        # a preemption (evict + host-side rebind) keeps them in lockstep
        pre = PodInfo("pre", namespace="t9", cpu_milli=8000,
                      mem_kib=1 << 10, priority=5)
        store.put(pod_key("t9", pre.name), encode_pod(pre))
        coord.run_until_idle()
        assert coord.preempt_log
        assert coord._victims_index() == coord._victims_index_full()
        # node removal hides its victims; re-add (new row) restores them
        store.delete(node_key("n003"))
        coord.drain_watches()
        assert coord._victims_index() == coord._victims_index_full()
        # full relist reconciliation stays in lockstep too
        coord.resync()
        assert coord._victims_index() == coord._victims_index_full()
    finally:
        coord.close()
        store.close()


# ---- 6. host-mirror narrow dtypes ------------------------------------


def test_mirror_dtypes_follow_table_spec_bounds():
    assert mirror_dtype(100) == np.int8
    assert mirror_dtype(1 << 7) == np.int8
    assert mirror_dtype((1 << 7) + 1) == np.int16
    assert mirror_dtype(1 << 15) == np.int16
    assert mirror_dtype(1 << 20) == np.int32
    host = NodeTableHost(TableSpec(
        max_nodes=32, max_zones=512, max_regions=64, max_taint_ids=128,
    ))
    assert host.zone.dtype == np.int16       # 512 > int8
    assert host.region.dtype == np.int8
    assert host.taint_id.dtype == np.int8
    assert host.taint_effect.dtype == np.int8
    assert host.label_key.dtype == np.int32  # unbounded namespaces
    host.upsert(build_node(0))
    table = host.to_device()
    for col in ("zone", "region", "taint_id", "taint_effect", "name_id"):
        assert getattr(table, col).dtype == np.int32, col
    assert host.mirror_nbytes() > 0


# ---- 7. make_nodes --bulk over the wire ------------------------------


def test_make_nodes_bulk_batched_puts():
    """--bulk N registers nodes through BatchKV put-frames (connection
    reuse via the shared client pool); the store ends up with exactly
    the same objects the per-node lane writes."""
    import asyncio

    from k8s1m_tpu.store.native import WireFront
    from k8s1m_tpu.tools import make_nodes

    store = MemStore()
    wf = WireFront(store)
    try:
        args = make_nodes.parse_args([
            "--target", f"127.0.0.1:{wf.port}", "--count", "500",
            "--bulk", "128", "--concurrency", "4", "--clients", "1",
            "--quiet",
        ])
        summary = asyncio.run(make_nodes.amain(args))
        assert summary["count"] == 500 and summary["errors"] == 0
        kvs, _ = list_prefix(store, b"/registry/minions/")
        assert len(kvs) == 500
        by_key = {kv.key: kv.value for kv in kvs}
        for i in (0, 123, 499):
            assert by_key[node_key(f"kwok-node-{i}")] == \
                encode_node(build_node(i))
    finally:
        wf.close()
        store.close()


# ---- 8. the drill smoke (tier-1) and full shape (slow) ---------------


def _run_drill(extra, timeout):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "k8s1m_tpu.tools.megarow_drill", *extra],
        cwd=REPO, env=env, timeout=timeout,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_megarow_drill_smoke_131k():
    """The tier-1 megarow gate: 131,072 rows end to end — bulk
    registration, the cold build byte-identical to the per-node loop,
    the composed churn+tenant+overload window, and the peak-RSS
    budget (the drill itself fails past --rss-budget-mib).  How long
    the cold build takes is the benchmark's ``bootstrap_ingest_s``, read
    on the chip's host; no stopwatch is held here."""
    out = _run_drill(["--smoke"], timeout=600)
    assert out["metric"] == "pod_binds_per_sec_131072_nodes"
    assert out["passed"], out["evidence"]
    ev = out["evidence"]
    assert ev["lost"] == 0
    assert ev["pipeline_quiesce"] == {"structural": 0, "resync": 0}
    assert ev["cold_build_compare"]["byte_identical"]
    assert ev["rss_budget_mib"] and ev["peak_rss_mib"] <= ev["rss_budget_mib"]
    assert ev["binds_per_sec"] > 0


@pytest.mark.slow
def test_megarow_drill_full_million():
    """The committed-artifact shape: 1,048,576 rows (several minutes)."""
    out = _run_drill([], timeout=3000)
    assert out["metric"] == "pod_binds_per_sec_1048576_nodes"
    assert out["passed"], out["evidence"]
