"""Native memstore tests — ports the semantics of the reference's Rust
corpus (reference mem_etcd/tests/store_test.rs, watch_test.rs), which
encodes the etcd-subset contract Kubernetes depends on.
"""

from __future__ import annotations

import os

import pytest

from k8s1m_tpu.store import (
    CompactedError,
    FutureRevError,
    MemStore,
    prefix_end,
)

K = b"/registry/pods/default/a"
K2 = b"/registry/pods/default/b"
NODE_PREFIX = b"/registry/minions/"


@pytest.fixture()
def store():
    s = MemStore()
    yield s
    s.close()


# ---- MVCC / revisions (store_test.rs:1-120) ------------------------------


def test_revisions_start_at_one(store):
    # The dummy key makes the first real write revision 2, like etcd after
    # its bootstrap write (reference main.rs:103-104).
    assert store.current_revision == 1
    rev = store.put(K, b"v1")
    assert rev == 2


def test_put_get_roundtrip(store):
    rev = store.put(K, b"v1")
    kv = store.get(K)
    assert kv.value == b"v1"
    assert kv.mod_revision == rev
    assert kv.create_revision == rev
    assert kv.version == 1


def test_version_increments_and_create_rev_stable(store):
    r1 = store.put(K, b"v1")
    r2 = store.put(K, b"v2")
    kv = store.get(K)
    assert kv.version == 2
    assert kv.create_revision == r1
    assert kv.mod_revision == r2


def test_range_at_historical_revision(store):
    r1 = store.put(K, b"v1")
    store.put(K, b"v2")
    old = store.get(K, revision=r1)
    assert old.value == b"v1"
    assert old.version == 1
    new = store.get(K)
    assert new.value == b"v2"


def test_range_before_key_existed(store):
    rev0 = store.current_revision
    store.put(K, b"v1")
    assert store.get(K, revision=rev0) is None


def test_delete_and_recreate_resets_create_revision(store):
    # store_test.rs:212-218: re-create after delete resets create_rev and
    # version.
    r1 = store.put(K, b"v1")
    store.delete(K)
    r3 = store.put(K, b"v2")
    kv = store.get(K)
    assert kv.create_revision == r3 != r1
    assert kv.version == 1


def test_delete_missing_is_noop(store):
    rev_before = store.current_revision
    rev, deleted = store.delete(K)
    assert not deleted
    assert store.current_revision == rev_before


def test_historical_read_sees_deleted_key(store):
    r1 = store.put(K, b"v1")
    store.delete(K)
    assert store.get(K) is None
    assert store.get(K, revision=r1).value == b"v1"


def test_future_revision_errors(store):
    store.put(K, b"v1")
    with pytest.raises(FutureRevError):
        store.range(K, revision=store.current_revision + 1)


# ---- CAS (store_test.rs Txn semantics) -----------------------------------


def test_cas_by_mod_revision(store):
    rev = store.put(K, b"v1")
    ok, new_rev, _ = store.cas(K, b"v2", required_mod=rev)
    assert ok and new_rev > rev
    # Stale revision fails and returns the current KV.
    ok, latest, cur = store.cas(K, b"v3", required_mod=rev)
    assert not ok
    assert latest == store.current_revision
    assert cur.value == b"v2"


def test_cas_create_only(store):
    # mod_revision 0 compare == "key must not exist" (the k8s Create Txn).
    ok, _, _ = store.cas(K, b"v1", required_mod=0)
    assert ok
    ok, _, cur = store.cas(K, b"v1b", required_mod=0)
    assert not ok
    assert cur.value == b"v1"


def test_cas_by_version(store):
    store.put(K, b"v1")
    ok, _, _ = store.cas(K, b"v2", required_version=1)
    assert ok
    ok, _, _ = store.cas(K, b"v3", required_version=1)
    assert not ok


def test_cas_delete(store):
    rev = store.put(K, b"v1")
    ok, _, _ = store.cas(K, None, required_mod=rev)
    assert ok
    assert store.get(K) is None


def test_cas_on_deleted_key_compares_zero(store):
    store.put(K, b"v1")
    store.delete(K)
    ok, _, _ = store.cas(K, b"v2", required_mod=0)
    assert ok


# ---- ranges (store_test.rs + kv_service_test.rs) --------------------------


def _fill_nodes(store, n=10):
    revs = []
    for i in range(n):
        revs.append(store.put(NODE_PREFIX + f"node-{i:03d}".encode(), b"x" * 8))
    return revs


def test_prefix_range_sorted(store):
    _fill_nodes(store, 10)
    store.put(b"/registry/pods/default/p", b"y")  # different prefix
    res = store.range(NODE_PREFIX, prefix_end(NODE_PREFIX))
    assert len(res.kvs) == 10
    keys = [kv.key for kv in res.kvs]
    assert keys == sorted(keys)
    assert res.count == 10
    assert not res.more


def test_range_limit_and_count(store):
    _fill_nodes(store, 10)
    res = store.range(NODE_PREFIX, prefix_end(NODE_PREFIX), limit=3)
    assert len(res.kvs) == 3
    # Count beyond the limit is approximate (reference README.adoc:326-328):
    # the scan stops one element past the limit so a paginated list costs
    # O(limit), not O(keys).  Exact counts come from count_only/no-limit.
    assert res.count == 4
    assert res.more


def test_range_count_only(store):
    _fill_nodes(store, 10)
    res = store.range(NODE_PREFIX, prefix_end(NODE_PREFIX), count_only=True)
    assert res.count == 10
    assert res.kvs == []


def test_range_keys_only(store):
    _fill_nodes(store, 3)
    res = store.range(NODE_PREFIX, prefix_end(NODE_PREFIX), keys_only=True)
    assert all(kv.value == b"" for kv in res.kvs)
    assert len(res.kvs) == 3


def test_bounded_range_exclusive_end(store):
    _fill_nodes(store, 5)
    res = store.range(NODE_PREFIX + b"node-001", NODE_PREFIX + b"node-003")
    assert [kv.key for kv in res.kvs] == [
        NODE_PREFIX + b"node-001",
        NODE_PREFIX + b"node-002",
    ]


def test_cross_prefix_range(store):
    # A deliberate capability beyond the reference (its per-Kind trees
    # reject cross-Kind ranges, reference store.rs:590-675).
    _fill_nodes(store, 2)
    store.put(b"/registry/pods/default/p", b"y")
    res = store.range(b"/registry/", prefix_end(b"/registry/"))
    assert len(res.kvs) == 3


def test_historical_range_includes_later_deleted_keys(store):
    _fill_nodes(store, 3)
    rev = store.current_revision
    store.delete(NODE_PREFIX + b"node-001")
    now = store.range(NODE_PREFIX, prefix_end(NODE_PREFIX))
    assert len(now.kvs) == 2
    old = store.range(NODE_PREFIX, prefix_end(NODE_PREFIX), revision=rev)
    assert len(old.kvs) == 3


# ---- compaction -----------------------------------------------------------


def test_compact_basic(store):
    r1 = store.put(K, b"v1")
    store.put(K, b"v2")
    r3 = store.put(K, b"v3")
    store.compact(r3)
    with pytest.raises(CompactedError):
        store.range(K, revision=r1)
    assert store.get(K).value == b"v3"


def test_compact_preserves_values_live_at_compact_rev(store):
    # Key written before the compact revision, unmodified since: reads at
    # rev >= compact_rev must still see it (etcd keeps non-superseded
    # versions; the reference can lose these, see memstore.cc header).
    store.put(K, b"stable")
    r_marker = store.put(K2, b"x1")
    store.put(K2, b"x2")
    store.compact(store.current_revision)
    res = store.get(K, revision=store.current_revision)
    assert res.value == b"stable"
    del r_marker


def test_compact_value_superseded_then_modified_later(store):
    r1 = store.put(K, b"v1")
    r2 = store.put(K, b"v2")
    store.put(K2, b"pad")
    store.compact(store.current_revision)
    r4 = store.put(K, b"v3")
    # v2 was live at compact time and must survive for reads in [C, r4).
    assert store.get(K, revision=r4 - 1).value == b"v2"
    assert store.get(K).value == b"v3"
    del r1, r2


def test_compact_errors(store):
    store.put(K, b"v1")
    store.compact(store.current_revision)
    with pytest.raises(CompactedError):
        store.compact(1)
    with pytest.raises(FutureRevError):
        store.compact(store.current_revision + 10)


def test_tombstone_gc_at_compaction(store):
    store.put(K, b"v1")
    store.delete(K)
    keys_before = store.num_keys
    store.compact(store.current_revision)
    # Key count metric unchanged (already decremented at delete), but the
    # tombstone row is gone: a re-create behaves like a fresh key.
    rev = store.put(K, b"v2")
    kv = store.get(K)
    assert kv.create_revision == rev and kv.version == 1
    assert store.num_keys == keys_before + 1


# ---- watches (watch_test.rs) ---------------------------------------------


def test_watch_live_events(store):
    w = store.watch(NODE_PREFIX, prefix_end(NODE_PREFIX))
    assert w.poll() == []
    store.put(NODE_PREFIX + b"n1", b"v1")
    store.delete(NODE_PREFIX + b"n1")
    evs = w.poll()
    assert [e.type for e in evs] == ["PUT", "DELETE"]
    assert evs[0].kv.value == b"v1"
    assert evs[1].kv.key == NODE_PREFIX + b"n1"
    assert evs[1].kv.value == b""
    # Revision-ordered.
    assert evs[0].kv.mod_revision < evs[1].kv.mod_revision


def test_watch_past_replay_from_revision(store):
    r1 = store.put(K, b"v1")
    store.put(K, b"v2")
    w = store.watch(K, start_revision=r1)
    evs = w.poll()
    assert [e.kv.value for e in evs] == [b"v1", b"v2"]
    assert [e.kv.mod_revision for e in evs] == [r1, r1 + 1]


def test_watch_single_key_ignores_others(store):
    w = store.watch(K)
    store.put(K2, b"other")
    store.put(K, b"mine")
    evs = w.poll()
    assert len(evs) == 1
    assert evs[0].kv.key == K


def test_watch_future_revision_suppresses_earlier_events(store):
    # Watch starting at a future revision only sees events >= it
    # (watch_test.rs future-revision watches).
    target = store.current_revision + 2
    w = store.watch(K, start_revision=target)
    store.put(K, b"early")      # rev = target - 1
    store.put(K, b"on-time")    # rev = target
    evs = w.poll()
    assert [e.kv.value for e in evs] == [b"on-time"]


def test_watch_at_compacted_revision_errors(store):
    store.put(K, b"v1")
    store.put(K, b"v2")
    store.compact(store.current_revision)
    with pytest.raises(CompactedError) as ei:
        store.watch(K, start_revision=1)
    assert ei.value.compact_revision == store.compact_revision


def test_watch_prev_kv(store):
    store.put(K, b"v1")
    w = store.watch(K, prev_kv=True)
    store.put(K, b"v2")
    store.delete(K)
    evs = w.poll()
    assert evs[0].prev_kv.value == b"v1"
    assert evs[1].type == "DELETE"
    assert evs[1].prev_kv.value == b"v2"


def test_watch_prev_kv_across_start_revision(store):
    # watch_service_test.rs:372-425: the replayed event's prev_kv comes
    # from *before* the start revision.
    store.put(K, b"v1")
    r2 = store.put(K, b"v2")
    w = store.watch(K, start_revision=r2, prev_kv=True)
    evs = w.poll()
    assert evs[0].kv.value == b"v2"
    assert evs[0].prev_kv.value == b"v1"


def test_watch_cancel(store):
    w = store.watch(K)
    w.cancel()
    store.put(K, b"v1")
    assert w.poll() == []
    assert w.canceled


def test_watch_batching(store):
    w = store.watch(NODE_PREFIX, prefix_end(NODE_PREFIX))
    for i in range(25):
        store.put(NODE_PREFIX + f"n{i:02d}".encode(), b"v")
    first = w.poll(max_events=10)
    assert len(first) == 10
    rest = w.poll(max_events=1000)
    assert len(rest) == 15


# ---- WAL checkpoint/resume (RUNNING.adoc:68-111) --------------------------


def test_wal_persist_and_replay(tmp_path):
    wal = str(tmp_path / "wal")
    with MemStore(wal_dir=wal, wal_mode="buffered") as s:
        s.put(K, b"v1")
        s.put(K2, b"other")
        s.put(K, b"v2")
        s.delete(K2)
        s.wal_sync()
    with MemStore(wal_dir=wal, wal_mode="buffered") as s:
        assert s.get(K).value == b"v2"
        assert s.get(K2) is None
        kv = s.get(K)
        assert kv.version == 2


def test_wal_fsync_mode(tmp_path):
    wal = str(tmp_path / "wal")
    with MemStore(wal_dir=wal, wal_mode="fsync") as s:
        for i in range(50):
            s.put(K, b"v%d" % i)
    with MemStore(wal_dir=wal, wal_mode="fsync") as s:
        assert s.get(K).value == b"v49"


def test_wal_no_write_prefix(tmp_path):
    wal = str(tmp_path / "wal")
    with MemStore(
        wal_dir=wal, wal_mode="buffered",
        no_write_prefixes=("/registry/leases/",),
    ) as s:
        s.put(b"/registry/leases/kube-node-lease/n1", b"lease")
        s.put(K, b"durable")
        s.wal_sync()
    with MemStore(wal_dir=wal, wal_mode="buffered") as s:
        assert s.get(K).value == b"durable"
        assert s.get(b"/registry/leases/kube-node-lease/n1") is None


def test_wal_per_prefix_files(tmp_path):
    wal = str(tmp_path / "wal")
    with MemStore(wal_dir=wal, wal_mode="buffered") as s:
        s.put(b"/registry/pods/default/a", b"1")
        s.put(b"/registry/minions/n1", b"2")
        s.wal_sync()
    files = [f for f in os.listdir(wal) if f.endswith(".wal")]
    assert len(files) == 2  # one per /registry/<kind>/ prefix


# ---- stats ---------------------------------------------------------------


def test_stats(store):
    _fill_nodes(store, 4)
    store.put(b"/registry/pods/default/p", b"yy")
    st = store.stats()
    assert st["keys"] == store.num_keys == 6  # 4 nodes + 1 pod + dummy "~"
    assert st["prefixes"]["/registry/minions/"]["keys"] == 4
    assert st["revision"] == store.current_revision
    assert store.db_size > 0


def test_lock_contention_stats(tmp_path):
    """The store exports (method, structure, rw) lock cells and watcher
    pressure counters (reference mem_etcd_lock_seconds/count,
    metrics.rs:78-94; watcher blocking metrics, store.rs:478-495 — our
    drop-at-cap design reports drops instead of blocking time)."""
    s = MemStore(wal_dir=str(tmp_path))
    try:
        s.put(b"/registry/pods/ns/a", b"v")
        s.put_batch([(b"/registry/pods/ns/b%d" % i, b"v") for i in range(10)])
        s.range(b"/registry/pods/", prefix_end(b"/registry/pods/"))
        w = s.watch(b"/registry/pods/", prefix_end(b"/registry/pods/"),
                    queue_cap=5)
        s.put_batch([(b"/registry/pods/ns/c%d" % i, b"v") for i in range(8)])
        st = s.stats()
        cells = {
            (c["method"], c["structure"], c["rw"]): c for c in st["locks"]
        }
        assert cells[("set", "store_mu", "write")]["count"] >= 1
        assert cells[("put_batch", "store_mu", "write")]["count"] == 2
        assert cells[("range", "store_mu", "read")]["count"] >= 1
        assert cells[("watch", "store_mu", "write")]["count"] >= 1
        assert cells[("wal_append", "wal_queue", "write")]["count"] >= 11
        for c in st["locks"]:
            assert c["wait_ns"] >= 0
        wp = st["watch_pressure"]
        assert wp["enqueued"] == 5          # cap 5: first 5 enqueue
        assert wp["dropped"] == 3           # remaining 3 drop
        assert wp["queue_hwm"] == 5
        assert w.dropped == 3
    finally:
        s.close()


def test_lock_metrics_rendered(tmp_path):
    """Serving a store exposes the contention cells on /metrics."""
    import asyncio

    from k8s1m_tpu.obs.metrics import REGISTRY
    from k8s1m_tpu.store.etcd_server import serve

    s = MemStore()
    loop = asyncio.new_event_loop()
    try:
        server, port = loop.run_until_complete(
            serve(s, port=0, metrics_port=0)
        )
        # metrics_port=0 skips the HTTP server but serve() must still
        # register the store for aggregation when metrics are enabled;
        # register manually like serve(metrics_port=N) does.
        from k8s1m_tpu.store import etcd_server

        etcd_server._SERVED_STORES.add(s)
        s.put(b"/registry/pods/ns/a", b"v")
        s.range(b"/registry/pods/ns/a")
        rendered = REGISTRY.render()
        assert 'memstore_lock_count_total{method="set"' in rendered
        assert "memstore_lock_wait_seconds_total" in rendered
        assert "memstore_watch_dropped_total" in rendered
        loop.run_until_complete(server.stop(None))
    finally:
        loop.close()
        s.close()


# ---- native pod intake (ms_watch_poll_pods) + echo suppression -----------
# The C fast parser and Python's decode_pod_fast accept the same canonical
# shape; these tests pin the frame layout, the parity, and the
# exclude_watcher contract (memstore.h).


def _pods_watch(store, **kw):
    p = b"/registry/pods/"
    return store.watch(p, prefix_end(p), **kw)


def test_poll_pods_columnar_frame(store):
    from k8s1m_tpu.control.objects import encode_pod, pod_key
    from k8s1m_tpu.snapshot.pod_encoding import PodInfo
    from k8s1m_tpu.store.native import (
        POD_CANONICAL,
        POD_HAS_NODE,
        POD_SCHED_MATCH,
    )

    w = _pods_watch(store)
    v1 = encode_pod(PodInfo("p1", cpu_milli=250, mem_kib=2048))
    store.put(pod_key("default", "p1"), v1)
    v2 = encode_pod(PodInfo("p2", priority=7))               # non-canonical
    store.put(pod_key("default", "p2"), v2)
    v3 = encode_pod(PodInfo("p3", scheduler_name="default-scheduler"))
    store.put(pod_key("default", "p3"), v3)
    store.delete(pod_key("default", "p3"))

    evb = w.poll_pods(100, b"dist-scheduler")
    assert evb.n == 4
    assert evb.etype.tolist() == [0, 0, 0, 1]
    assert evb.flags.tolist() == [
        POD_CANONICAL | POD_SCHED_MATCH, 0, POD_CANONICAL, 0,
    ]
    assert evb.cpu.tolist()[0] == 250 and evb.mem.tolist()[0] == 2048
    keys = [
        evb.key_blob[evb.koff[i]: evb.koff[i + 1]] for i in range(evb.n)
    ]
    assert keys == [
        pod_key("default", "p1"), pod_key("default", "p2"),
        pod_key("default", "p3"), pod_key("default", "p3"),
    ]
    # Non-canonical PUT carries the whole value in aux; others nothing.
    aux = [evb.aux_blob[evb.aoff[i]: evb.aoff[i + 1]] for i in range(evb.n)]
    assert aux == [b"", v2, b"", b""]
    assert evb.mrev.tolist() == [2, 3, 4, 5]
    # Neither labels nor tolerations anywhere: no shape table.
    assert evb.shape.tolist() == [0, 0, 0, 0] and evb.shapes == ()
    assert w.poll_pods(100, b"dist-scheduler").n == 0


def test_poll_pods_shape_table(store):
    """Labels and tolerations come back as byte spans, each distinct
    pair once a frame, and every event names its pair by index."""
    from k8s1m_tpu.control.objects import encode_pod, pod_key
    from k8s1m_tpu.snapshot.pod_encoding import PodInfo, Toleration
    from k8s1m_tpu.store.native import POD_CANONICAL, POD_SCHED_MATCH
    from k8s1m_tpu.tools.make_pods import build_pod

    w = _pods_watch(store)
    pods = [
        build_pod(0), PodInfo("bare"), build_pod(1),
        PodInfo("l", labels={"a": "b", "c": "d"}),
        PodInfo("t", tolerations=[Toleration(key="k")]),
        build_pod(2), PodInfo("l2", labels={"a": "b", "c": "d"}),
    ]
    for p in pods:
        store.put(pod_key("default", p.name), encode_pod(p))
    evb = w.poll_pods(100, b"dist-scheduler")
    assert evb.flags.tolist() == [POD_CANONICAL | POD_SCHED_MATCH] * 7
    assert evb.shape.tolist() == [1, 0, 1, 2, 3, 1, 2]
    assert evb.shapes == (
        (b'"app":"bench-pod"',
         b'{"key":"kwok.x-k8s.io/node","operator":"Exists"}'),
        (b'"a":"b","c":"d"', b""),
        (b"", b'{"key":"k","operator":"Exists"}'),
    )
    assert evb.aoff.tolist() == [0] * 8


def _pod_grammar_corpus():
    """(id, value, accepted) for the canonical pod grammar: what both
    parsers must take and the near-misses both must leave to JSON."""
    from k8s1m_tpu.config import (
        EFFECT_NO_EXECUTE,
        EFFECT_NO_SCHEDULE,
        EFFECT_PREFER_NO_SCHEDULE,
        SEL_OP_IN,
        TOL_OP_EQUAL,
    )
    from k8s1m_tpu.control.coordinator import splice_node_name
    from k8s1m_tpu.control.objects import encode_pod
    from k8s1m_tpu.snapshot.pod_encoding import (
        NodeSelectorTerm,
        PodInfo,
        SelectorRequirement,
        Toleration,
    )
    from k8s1m_tpu.tools.make_pods import build_pod

    T = Toleration
    kwok = T(key="kwok.x-k8s.io/node")
    equal = T("k", TOL_OP_EQUAL, "v")
    full = T("k", TOL_OP_EQUAL, "v", EFFECT_NO_SCHEDULE)
    three = {"app": "web", "tier": "front", "k8s1m.io/tenant": "t-1"}
    accepted = {
        "bare": encode_pod(PodInfo("a", cpu_milli=1, mem_kib=1)),
        "bare-wide": encode_pod(PodInfo(
            "b", namespace="kube-system", cpu_milli=999999,
            mem_kib=123456789)),
        "label-1": encode_pod(PodInfo("c", labels={"x": "y"})),
        "label-3": encode_pod(PodInfo("d", labels=three)),
        "label-empty-value": encode_pod(PodInfo("d", labels={"x": ""})),
        "make-pods": encode_pod(build_pod(7)),
        "tol-exists-key": encode_pod(PodInfo("e", tolerations=[kwok])),
        "tol-equal-value": encode_pod(PodInfo("f", tolerations=[equal])),
        "tol-effect": encode_pod(PodInfo("g", tolerations=[full])),
        "tol-exists-effect": encode_pod(PodInfo(
            "g", tolerations=[T("k", effect=EFFECT_PREFER_NO_SCHEDULE)])),
        "tol-keyless-exists": encode_pod(PodInfo("h", tolerations=[T()])),
        "tol-two": encode_pod(PodInfo(
            "i", tolerations=[kwok, T("z", effect=EFFECT_NO_EXECUTE)])),
        "tol-three-labels": encode_pod(PodInfo(
            "j", labels=three, tolerations=[T(), full, equal])),
        "node-appended": encode_pod(PodInfo("k", node_name="n-1")),
        "node-spliced": splice_node_name(encode_pod(PodInfo("l")), "n-2"),
        "node-appended-labels": encode_pod(PodInfo(
            "m", node_name="n-1", labels=three)),
        "node-appended-tols": encode_pod(PodInfo(
            "n", node_name="n-1", tolerations=[kwok, full])),
        "node-appended-both": encode_pod(PodInfo(
            "o", node_name="n-1", labels={"x": "y"}, tolerations=[kwok])),
        "node-spliced-labels": splice_node_name(
            encode_pod(PodInfo("p", labels=three)), "n-2"),
        "node-spliced-tols": splice_node_name(
            encode_pod(PodInfo("q", tolerations=[equal])), "n-2"),
        "node-spliced-both": splice_node_name(encode_pod(build_pod(9)), "n-2"),
        "other-scheduler": encode_pod(PodInfo(
            "r", scheduler_name="default-scheduler", labels={"x": "y"})),
    }
    mp = accepted["make-pods"]
    tol = b'"tolerations":[{"key":"kwok.x-k8s.io/node","operator":"Exists"}]'
    assert tol in mp
    rejected = {
        "backslash-name": encode_pod(PodInfo('esc"aped', cpu_milli=5)),
        "backslash-label": encode_pod(PodInfo("a", labels={"x": 'q"r'})),
        "backslash-toleration": encode_pod(PodInfo(
            "a", tolerations=[T(key="a\\b")])),
        "priority": encode_pod(PodInfo("a", priority=3)),
        "priority-shaped": encode_pod(PodInfo(
            "a", priority=3, labels={"x": "y"}, tolerations=[kwok])),
        "node-selector": encode_pod(PodInfo("a", node_selector={"k": "v"})),
        "node-selector-tols": encode_pod(PodInfo(
            "a", node_selector={"k": "v"}, tolerations=[kwok])),
        "affinity": encode_pod(PodInfo(
            "a", required_terms=[NodeSelectorTerm([
                SelectorRequirement("k", SEL_OP_IN, ["v"])])])),
        "spread": encode_pod(PodInfo("a", labels={"x": "y"}), raw_spread=[{
            "topologyKey": "topology.kubernetes.io/zone", "maxSkew": 1,
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {}}}]),
        "tol-unknown-key": mp.replace(
            b'"operator":"Exists"}',
            b'"operator":"Exists","tolerationSeconds":5}'),
        "tol-unknown-operator": mp.replace(b'"Exists"', b'"Exist"'),
        "tol-unknown-effect": mp.replace(
            b'"operator":"Exists"}', b'"operator":"Exists","effect":"No"}'),
        "tol-reordered": mp.replace(
            b'{"key":"kwok.x-k8s.io/node","operator":"Exists"}',
            b'{"operator":"Exists","key":"kwok.x-k8s.io/node"}'),
        "tol-effect-before-value": accepted["tol-effect"].replace(
            b'"value":"v","effect":"NoSchedule"',
            b'"effect":"NoSchedule","value":"v"'),
        "tol-empty-list": mp.replace(tol, b'"tolerations":[]'),
        "tol-empty-object": mp.replace(tol, b'"tolerations":[{}]'),
        "tol-trailing-comma": mp.replace(b'"Exists"}]', b'"Exists"},]'),
        "tol-before-containers": mp.replace(b"," + tol, b"").replace(
            b'"containers":', tol + b',"containers":'),
        "tols-before-appended-node": accepted["node-appended-tols"].replace(
            b',"nodeName":"n-1"', b"").replace(
            b'},"status"', b',"nodeName":"n-1"},"status"'),
        "node-twice": splice_node_name(
            accepted["bare"], "n-1").replace(
            b'}}}]}', b'}}}],"nodeName":"n-2"}'),
        "label-number": mp.replace(b'"app":"bench-pod"', b'"app":1'),
        "label-nested": mp.replace(b'"app":"bench-pod"', b'"app":{"a":"b"}'),
        "label-trailing-comma": mp.replace(
            b'"app":"bench-pod"', b'"app":"bench-pod",'),
        "no-labels-key": mp.replace(b',"labels":{"app":"bench-pod"}', b""),
        "reordered-metadata": mp.replace(
            b'"name":"bench-pod-7","namespace":"default"',
            b'"namespace":"default","name":"bench-pod-7"'),
        "annotations": mp.replace(
            b'},"spec":', b',"annotations":{"a":"b"}},"spec":'),
        "status-running": mp.replace(b'"Pending"', b'"Running"'),
        "trailing-byte": mp + b" ",
        "cpu-cores": mp.replace(b'"cpu":"100m"', b'"cpu":"1"'),
    }
    cases = [(k, v, True) for k, v in accepted.items()]
    cases += [(k, v, False) for k, v in rejected.items()]
    # A value cut at (and just inside) every landmark of the grammar.
    marks = (
        b'"metadata"', b'"namespace"', b'"labels"', b'"app"', b'bench-pod"}',
        b'"spec"', b'"schedulerName"', b'"containers"', b'"cpu"', b'"memory"',
        b'"tolerations"', b'"key"', b'kwok', b'"operator"', b'Exists',
        b'}]}', b'"status"', b'"phase"', b'Pending',
    )
    for m in marks:
        at = mp.index(m)
        for cut in (at, at + 1, at + len(m)):
            cases.append((f"cut-{m.decode()}-{cut - at}", mp[:cut], False))
    cases.append(("cut-last-byte", mp[:-1], False))
    return cases


_POD_GRAMMAR = _pod_grammar_corpus()


@pytest.mark.parametrize(
    "value,accepted", [c[1:] for c in _POD_GRAMMAR],
    ids=[c[0] for c in _POD_GRAMMAR],
)
def test_poll_pods_parses_exactly_what_decode_pod_fast_does(
    store, value, accepted
):
    """Parser parity: the C parser flags a value CANONICAL exactly when
    decode_pod_fast takes it, and then the frame's scalars, node name
    and shape spans say what json.loads + decode_pod_obj say of the same
    value; what they reject comes back whole for the Python fallback."""
    import json

    from k8s1m_tpu.control.objects import (
        decode_pod_fast,
        decode_pod_obj,
        decode_pod_shape,
        pod_key,
    )
    from k8s1m_tpu.store.native import (
        POD_CANONICAL,
        POD_HAS_NODE,
        POD_SCHED_MATCH,
        parse_pod_events,
    )

    w = _pods_watch(store)
    store.put(pod_key("t", "case"), value)
    evb = w.poll_pods(100, b"dist-scheduler")
    assert evb.n == 1
    py = decode_pod_fast(value, None)
    flags = int(evb.flags[0])
    assert bool(flags & POD_CANONICAL) == (py is not None) == accepted
    aux = evb.aux_blob[evb.aoff[0]: evb.aoff[1]]
    wire = parse_pod_events([(0, b"k", value, 1)], b"dist-scheduler")
    assert wire.flags.tolist() == [flags] and wire.shapes == evb.shapes
    if not accepted:
        assert aux == value and evb.shape.tolist() == [0]
        return
    ref = decode_pod_obj(json.loads(value))
    assert py == ref
    assert (evb.cpu[0], evb.mem[0]) == (ref.cpu_milli, ref.mem_kib)
    assert bool(flags & POD_SCHED_MATCH) == (
        ref.scheduler_name == "dist-scheduler"
    )
    assert bool(flags & POD_HAS_NODE) == (ref.node_name is not None)
    assert aux.decode() == (ref.node_name or "")
    if ref.labels or ref.tolerations:
        assert evb.shape.tolist() == [1] and len(evb.shapes) == 1
        assert decode_pod_shape(*evb.shapes[0]) == (
            ref.labels, ref.tolerations
        )
    else:
        assert evb.shape.tolist() == [0] and evb.shapes == ()


def test_bind_batch_echo_suppression(store):
    from k8s1m_tpu.control.objects import encode_pod, pod_key
    from k8s1m_tpu.snapshot.pod_encoding import PodInfo

    mine = _pods_watch(store)
    other = _pods_watch(store)
    k = pod_key("default", "p")
    rev = store.put(k, encode_pod(PodInfo("p")))
    assert store.bind_batch([(k, rev, b"n-1")], exclude_watcher=mine.id) == [
        rev + 1
    ]
    # The issuing watcher sees only the original create, not the bind.
    assert [e[0] for e in mine.poll_light()] == [0]
    assert mine.poll_light() == []
    # Everyone else sees both events.
    evs = other.poll_light()
    assert len(evs) == 2
    assert b'"nodeName":"n-1"' in evs[1][2]
    # Default (-1) suppresses nobody.
    k2 = pod_key("default", "q")
    rev2 = store.put(k2, encode_pod(PodInfo("q")))
    store.bind_batch([(k2, rev2, b"n-2")])
    assert len(mine.poll_light()) == 2


def test_parse_pod_events_matches_poll_pods(store):
    """The store-independent parser (wire-side fast lane) emits the same
    columnar frame as the store-side drain for the same events."""
    from k8s1m_tpu.control.objects import encode_pod, pod_key
    from k8s1m_tpu.snapshot.pod_encoding import PodInfo
    from k8s1m_tpu.store.native import parse_pod_events

    w1 = _pods_watch(store)
    w2 = _pods_watch(store)
    store.put(pod_key("a", "p1"), encode_pod(PodInfo("p1", cpu_milli=7)))
    store.put(pod_key("a", "p2"), encode_pod(PodInfo("p2", labels={"x": "y"})))
    store.put(pod_key("a", "p3"),
              encode_pod(PodInfo("p3", scheduler_name="other")))
    store.delete(pod_key("a", "p3"))

    native = w1.poll_pods(100, b"dist-scheduler")
    wire = parse_pod_events(
        ((0 if e.type == "PUT" else 1, e.kv.key, e.kv.value,
          e.kv.mod_revision) for e in w2.poll(100)),
        b"dist-scheduler",
    )
    assert wire.n == native.n == 4
    for f in ("etype", "flags", "mrev", "cpu", "mem", "koff", "aoff"):
        import numpy as np

        np.testing.assert_array_equal(
            getattr(wire, f), getattr(native, f), f
        )
    assert wire.key_blob == native.key_blob
    assert wire.aux_blob == native.aux_blob
