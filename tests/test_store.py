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


def test_compact_past_a_tombstone_of_a_key_that_comes_back(store):
    # The tombstone is dropped with the history below the compact
    # revision and the item lives on: reads from the compact revision up
    # to the re-create see the key absent, not a compacted error.
    store.put(K, b"v1")
    store.put(K2, b"keep")
    r_del, _ = store.delete(K)
    store.put(K2, b"keep2")
    r_back = store.put(K, b"v2")
    store.compact(r_del + 1)
    for rev in range(r_del + 1, r_back):
        assert store.get(K, revision=rev) is None
        assert [kv.key for kv in store.range(K, K2 + b"\0", revision=rev).kvs] == [K2]
    assert store.get(K, revision=r_back).value == b"v2"
    with pytest.raises(CompactedError):
        store.get(K, revision=r_del)


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
        w = s.watch(b"/registry/pods/", prefix_end(b"/registry/pods/"))
        s.put_batch([(b"/registry/pods/ns/b%d" % i, b"v") for i in range(4)])
        rendered = REGISTRY.render()
        assert 'memstore_lock_count_total{method="set"' in rendered
        assert "memstore_lock_wait_seconds_total" in rendered
        assert "memstore_watch_dropped_total" in rendered
        # one frame of four events: four enqueued, one queue acquisition
        enqueued = rendered.split("\nmemstore_watch_enqueued_total ")[1]
        batches = rendered.split("\nmemstore_watch_enqueue_batches_total ")[1]
        assert float(enqueued.split()[0]) >= 4.0 and w.pending == 4
        assert 1.0 <= float(batches.split()[0]) <= float(enqueued.split()[0]) - 3
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
    """Labels and tolerations come back as byte spans (of the five a
    shape has, in encode_pod's order), each distinct shape once a frame,
    and every event names its shape by index."""
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
        (b'"app":"bench-pod"', b"",
         b'{"key":"kwok.x-k8s.io/node","operator":"Exists"}', b"", b""),
        (b'"a":"b","c":"d"', b"", b"", b"", b""),
        (b"", b"", b'{"key":"k","operator":"Exists"}', b"", b""),
    )
    assert evb.aoff.tolist() == [0] * 8


def test_poll_pods_shape_table_holds_the_spread_span(store):
    """The spread span is part of the shape: two Deployments that differ
    in their spread constraints alone are two shapes, a pod with
    constraints and nothing else is one too, and equal shapes share an
    entry."""
    from k8s1m_tpu.control.objects import encode_pod, pod_key
    from k8s1m_tpu.snapshot.pod_encoding import PodInfo
    from k8s1m_tpu.tools.make_pods import build_pod

    def zone(app, skew=1):
        return {"maxSkew": skew, "topologyKey": "topology.kubernetes.io/zone",
                "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": {"matchLabels": {"app": app}}}

    w = _pods_watch(store)
    pods = [
        build_pod(0, app="web", spread_constraints=[zone("web")]),
        build_pod(1, app="web", spread_constraints=[zone("web", 2)]),
        build_pod(2, app="web"),
        PodInfo("only", topology_spread=[zone("db")]),
        build_pod(3, app="web", spread_constraints=[zone("web")]),
    ]
    for p in pods:
        store.put(pod_key("default", p.name), encode_pod(p))
    evb = w.poll_pods(100, b"dist-scheduler")
    assert evb.shape.tolist() == [1, 2, 3, 4, 1]
    tol = b'{"key":"kwok.x-k8s.io/node","operator":"Exists"}'
    span = (b'{"maxSkew":%d,"topologyKey":"topology.kubernetes.io/zone",'
            b'"whenUnsatisfiable":"DoNotSchedule",'
            b'"labelSelector":{"matchLabels":{"app":"%s"}}}')
    assert evb.shapes == (
        (b'"app":"web"', b"", tol, b"", span % (1, b"web")),
        (b'"app":"web"', b"", tol, b"", span % (2, b"web")),
        (b'"app":"web"', b"", tol, b"", b""),
        (b"", b"", b"", b"", span % (1, b"db")),
    )


def _pod_grammar_corpus():
    """(id, value, accepted) for the canonical pod grammar: what both
    parsers must take and the near-misses both must leave to JSON."""
    import dataclasses

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
        PreferredSchedulingTerm,
        SelectorRequirement,
        Toleration,
    )
    from k8s1m_tpu.tools.make_pods import build_pod

    T = Toleration
    kwok = T(key="kwok.x-k8s.io/node")
    equal = T("k", TOL_OP_EQUAL, "v")
    full = T("k", TOL_OP_EQUAL, "v", EFFECT_NO_SCHEDULE)
    three = {"app": "web", "tier": "front", "k8s1m.io/tenant": "t-1"}

    def zone(match, key="topology.kubernetes.io/zone"):
        return {"topologyKey": key, "maxSkew": 1,
                "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": {"matchLabels": match}}

    two = [zone({"app": "web-0"}),
           dict(zone({"app": "web-0"}, "kubernetes.io/hostname"),
                whenUnsatisfiable="ScheduleAnyway")]
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
        "spread": encode_pod(PodInfo("a", labels={"x": "y"}), raw_spread=[
            zone({})]),
        "spread-alone": encode_pod(PodInfo("a", topology_spread=[zone({})])),
        "spread-cell": encode_pod(build_pod(
            3, app="web-0", spread_constraints=two)),
        "spread-other-scheduler": encode_pod(build_pod(
            3, app="web-0", spread_constraints=two),
            scheduler_name="default-scheduler"),
        "spread-node-appended": encode_pod(PodInfo(
            "s", node_name="n-1", labels=three, tolerations=[kwok, full],
            topology_spread=two)),
        "spread-node-spliced": splice_node_name(encode_pod(build_pod(
            4, app="web-0", spread_constraints=two)), "n-2"),
        # Brackets, braces and commas inside strings are not structure.
        "spread-brackets-in-selector": encode_pod(PodInfo(
            "t", labels={"x": "]}"}, topology_spread=[
                zone({"x": "]}", "[{": "}],"})])),
        "label-brackets": encode_pod(PodInfo("u", labels={"a]": "}],{["})),
        "spread-unknown-keys": encode_pod(PodInfo("v", topology_spread=[
            dict(zone({"a": "b"}), minDomains=3, matchLabelKeys=["k"],
                 labelSelector={"matchExpressions": [
                     {"key": "k", "operator": "In", "values": ["v"]}]})])),
        "spread-unsupported-key": encode_pod(PodInfo("w", topology_spread=[
            dict(zone({}), topologyKey="example.com/rack")])),
    }
    # Selectors and affinity: benchmark/pods/affinity.json's four kinds
    # (nodeSelector alone, required nodeAffinity, required + preferred,
    # toleration + nodeSelector), each also with nodeName in both forms.
    required = [NodeSelectorTerm([SelectorRequirement(
        "topology.kubernetes.io/zone", SEL_OP_IN, ["zone-0", "zone-1"])])]
    preferred = [PreferredSchedulingTerm(1, NodeSelectorTerm([
        SelectorRequirement("kwok-group", SEL_OP_IN, ["0"])]))]
    batch = T("dedicated", TOL_OP_EQUAL, "batch", EFFECT_NO_SCHEDULE)
    kinds = {
        "selector": dict(node_selector={"kwok-group": "3"}),
        "required": dict(required_terms=required),
        "required-preferred": dict(
            required_terms=required, preferred_terms=preferred),
        "dedicated": dict(
            node_selector={"dedicated": "batch"}, tolerations=[kwok, batch]),
    }
    for kind, fields in kinds.items():
        fields.setdefault("tolerations", [kwok])
        pod = PodInfo("a", labels={"app": kind}, **fields)
        accepted[f"kind-{kind}"] = encode_pod(pod)
        accepted[f"kind-{kind}-node-appended"] = encode_pod(
            dataclasses.replace(pod, node_name="n-1"))
        accepted[f"kind-{kind}-node-spliced"] = splice_node_name(
            encode_pod(pod), "n-2")
    anti = {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [{
            "topologyKey": "kubernetes.io/hostname",
            "labelSelector": {"matchLabels": {"a": "b"}}}]}}
    ipa = {"podAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [{
            "topologyKey": "kubernetes.io/hostname",
            "labelSelector": {"matchLabels": {"a": "b"}}}]}}
    every = PodInfo(
        "a", labels=three, node_selector={"k": "v", "k2": "v2"},
        tolerations=[kwok, full], required_terms=required,
        preferred_terms=preferred, topology_spread=two)
    accepted.update({
        "node-selector": encode_pod(PodInfo("a", node_selector={"k": "v"})),
        "node-selector-tols": encode_pod(PodInfo(
            "a", node_selector={"k": "v"}, tolerations=[kwok])),
        "node-selector-brackets": encode_pod(PodInfo(
            "a", node_selector={"a}": "]{,", "": ""})),
        "affinity": encode_pod(PodInfo(
            "a", required_terms=[NodeSelectorTerm([
                SelectorRequirement("k", SEL_OP_IN, ["v"])])])),
        "affinity-preferred-alone": encode_pod(PodInfo(
            "a", preferred_terms=preferred)),
        "affinity-anti": encode_pod(PodInfo("a"), raw_affinity=anti),
        "affinity-anti-and-node": encode_pod(
            PodInfo("a", labels={"a": "b"}, required_terms=required),
            raw_affinity=anti),
        # Braces, brackets and commas inside strings are not structure.
        "affinity-braces-in-values": encode_pod(PodInfo(
            "a", required_terms=[NodeSelectorTerm([SelectorRequirement(
                "}{", SEL_OP_IN, ["}", "]}", "{[,"])])])),
        "spread-node-selector": encode_pod(PodInfo(
            "a", node_selector={"k": "v"}, topology_spread=two)),
        "spread-affinity-before": encode_pod(
            PodInfo("a", topology_spread=two), raw_affinity=ipa),
        "every-member": encode_pod(every, raw_affinity={**ipa, **anti}),
        "every-member-node-appended": encode_pod(
            dataclasses.replace(every, node_name="n-1"), raw_affinity=anti),
        "every-member-node-spliced": splice_node_name(
            encode_pod(every, raw_affinity=anti), "n-2"),
        "every-member-other-scheduler": encode_pod(
            every, scheduler_name="default-scheduler"),
    })
    mp = accepted["make-pods"]
    cell = accepted["spread-cell"]
    whole = accepted["every-member"]
    sel_key, aff_key = b',"nodeSelector":{', b',"affinity":{'
    sel = sel_key + b'"k":"v","k2":"v2"}'
    assert sel in whole and aff_key in whole
    # Objects the encoder never writes and JSON allows: nothing selected.
    accepted["node-selector-empty"] = mp.replace(
        b'}}}],', b'}}}]' + sel_key + b"},")
    accepted["affinity-empty"] = mp.replace(
        b'},"status"', aff_key + b'}},"status"')
    accepted["affinity-whitespace"] = whole.replace(
        aff_key, aff_key + b" ").replace(b'"weight":1', b'"weight": 1')
    spread_key = b',"topologySpreadConstraints":['
    assert spread_key in cell
    # An array the encoder never writes and JSON allows: no constraints.
    accepted["spread-empty-list"] = mp.replace(
        b'},"status"', spread_key + b']},"status"')
    accepted["spread-whitespace"] = cell.replace(
        spread_key + b"{", spread_key + b' {').replace(
        b'}]},"status"', b'} ]},"status"')
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
        "spread-priority-after": encode_pod(PodInfo(
            "a", priority=3, labels={"x": "y"}, topology_spread=two)),
        "spread-backslash": encode_pod(PodInfo("a", topology_spread=[
            zone({"a": 'q"r'})])),
        "spread-before-tolerations": cell.replace(b"," + tol, b"").replace(
            b'},"status"', b"," + tol + b'},"status"'),
        "spread-twice": cell.replace(
            b'},"status"', spread_key + b']},"status"'),
        "spread-unclosed": cell.replace(b'}]},"status"', b'}},"status"'),
        "spread-closed-early": cell.replace(
            spread_key + b"{", spread_key + b"]{"),
        "spread-closed-by-brace": mp.replace(
            b'},"status"', spread_key + b'}},"status"'),
        "spread-open-string": cell.replace(
            b'"maxSkew":1', b'"maxSkew:1', 1),
        "spread-object": cell.replace(spread_key, spread_key[:-1] + b"{", 1),
        "spread-trailing-key": cell.replace(
            b'}]},"status"', b'}],"x":1},"status"'),
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
    aff_at = whole.index(aff_key)
    aff_end = whole.index(b',"topologySpreadConstraints"')
    aff = whole[aff_at:aff_end]
    tols_at = whole.index(b',"tolerations":[')
    rejected.update({
        "priority-node-selector": encode_pod(PodInfo(
            "a", priority=3, node_selector={"k": "v"})),
        "priority-affinity": encode_pod(PodInfo(
            "a", priority=3, required_terms=required)),
        "priority-every-member": encode_pod(
            dataclasses.replace(every, priority=1)),
        "backslash-node-selector": encode_pod(PodInfo(
            "a", node_selector={"k": 'q"r'})),
        "backslash-affinity": encode_pod(PodInfo(
            "a", required_terms=[NodeSelectorTerm([SelectorRequirement(
                "k", SEL_OP_IN, ["a\\b"])])])),
        # Members out of encode_pod's order.
        "node-selector-after-tolerations": whole.replace(sel, b"").replace(
            aff_key, sel + aff_key),
        "node-selector-after-affinity": whole.replace(sel, b"").replace(
            b',"topologySpreadConstraints"',
            sel + b',"topologySpreadConstraints"'),
        "node-selector-last": whole.replace(sel, b"").replace(
            b'},"status"', sel + b'},"status"'),
        "node-selector-before-appended-node": accepted[
            "every-member-node-appended"].replace(
            b',"nodeName":"n-1"', b"").replace(
            sel, sel + b',"nodeName":"n-1"'),
        "node-selector-before-containers": whole.replace(sel, b"").replace(
            b'"containers":', sel[1:] + b',"containers":'),
        "affinity-before-tolerations": (
            whole[:tols_at] + aff + whole[tols_at:aff_at] + whole[aff_end:]),
        "affinity-after-spread": whole.replace(aff, b"").replace(
            b'},"status"', aff + b'},"status"'),
        "node-selector-twice": whole.replace(sel, sel + sel_key + b"}"),
        "affinity-twice": whole.replace(
            b',"topologySpreadConstraints"',
            aff_key + b'},"topologySpreadConstraints"'),
        # A nodeSelector must be a flat map of strings.
        "node-selector-number": whole.replace(b'"k":"v"', b'"k":1'),
        "node-selector-null": whole.replace(b'"k":"v"', b'"k":null'),
        "node-selector-nested": whole.replace(b'"k":"v"', b'"k":{"a":"b"}'),
        "node-selector-array": whole.replace(sel, sel_key[:-1] + b'["k"]'),
        "node-selector-trailing-comma": whole.replace(
            b'"k2":"v2"}', b'"k2":"v2",}'),
        "node-selector-unclosed": whole.replace(b'"k2":"v2"}', b'"k2":"v2"'),
        # An affinity must be a balanced object.
        "affinity-array": whole.replace(aff, b',"affinity":[]'),
        "affinity-array-of-object": whole.replace(
            aff, b',"affinity":[' + aff[len(aff_key) - 1:] + b"]"),
        "affinity-string": whole.replace(aff, b',"affinity":"x"'),
        "affinity-unclosed": whole.replace(aff, aff[:-1]),
        "affinity-closed-twice": whole.replace(aff, aff + b"}"),
        "affinity-closed-by-bracket": whole.replace(aff, aff[:-1] + b"]"),
        "affinity-closed-early": whole.replace(aff_key, aff_key + b"}"),
        "affinity-open-string": whole.replace(
            b'"nodeAffinity"', b'"nodeAffinity', 1),
        "affinity-trailing-key": whole.replace(
            b'},"status"', b',"x":1},"status"'),
    })
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
    # ... and the same inside the spread span of the cell's pod.
    at = cell.index(spread_key)
    for m in (spread_key, b'"maxSkew"', b'"labelSelector"', b'web-0"}}}',
              b'"kubernetes.io/hostname"', b'}]},"status"'):
        lo = cell.index(m, at)
        for cut in (lo, lo + 1, lo + len(m)):
            cases.append((f"cut-spread-{m.decode()}-{cut - lo}", cell[:cut],
                          False))
    # ... and inside the selector and the affinity of the pod with every
    # member.
    for m in (sel_key, b'"k2"', b'"v2"}', aff_key, b'"nodeAffinity"',
              b'"nodeSelectorTerms"', b'"zone-1"]}]}]}',
              b'"podAntiAffinity"', b'{"a":"b"}}}]}'):
        lo = whole.index(m)
        for cut in (lo, lo + 1, lo + len(m)):
            cases.append((f"cut-whole-{m.decode()}-{cut - lo}", whole[:cut],
                          False))
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
    spec = json.loads(value)["spec"]
    raw_affinity = spec.get("affinity", {})
    if (ref.labels or ref.node_selector or ref.tolerations or raw_affinity
            or ref.topology_spread):
        assert evb.shape.tolist() == [1] and len(evb.shapes) == 1
        assert decode_pod_shape(*evb.shapes[0]) == dict(
            labels=ref.labels, node_selector=ref.node_selector,
            tolerations=ref.tolerations, required_terms=ref.required_terms,
            preferred_terms=ref.preferred_terms, affinity=raw_affinity,
            topology_spread=ref.topology_spread,
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


# ---- frames: fan-out once a frame, records shared by reference ------------
# What a batch lane does per frame it must do exactly as the one-record
# lane does per record: the same revisions, stored bytes, history, stats
# and watch streams, whatever a frame holds and wherever a queue's cap
# falls inside it.

_ERR_CAS, _ERR_INVALID = -1, -5


def _pod(name, **kw):
    from k8s1m_tpu.control.objects import encode_pod, pod_key
    from k8s1m_tpu.snapshot.pod_encoding import PodInfo

    return pod_key("default", name), encode_pod(PodInfo(name, **kw))


def _raw_frame(w, max_events, pods):
    """ms_watch_poll's frame or ms_watch_poll_pods', unparsed."""
    import ctypes

    from k8s1m_tpu.store import native

    lib = native._lib()
    out, out_len = ctypes.POINTER(ctypes.c_uint8)(), ctypes.c_size_t()
    outs = ctypes.byref(out), ctypes.byref(out_len)
    if pods:
        sched = b"dist-scheduler"
        rc = lib.ms_watch_poll_pods(w._store._h, w.id, max_events, sched,
                                    len(sched), *outs)
    else:
        rc = lib.ms_watch_poll(w._store._h, w.id, max_events, 0, *outs)
    assert rc >= 0
    return native._take_buf(lib, out, out_len)


def _raw_poll(w, max_events=1 << 20):
    return _raw_frame(w, max_events, pods=False)


def _raw_poll_pods(w, max_events=1 << 20):
    return _raw_frame(w, max_events, pods=True)


def _readable_history(store):
    """``range`` over everything at every revision still readable."""
    lo = max(store.compact_revision, 1)
    return {
        rev: store.range(b"\0", b"\0", revision=rev).kvs
        for rev in range(lo, store.current_revision + 1)
    }


def _bind_one_by_one(store, binds):
    """ms_bind_batch's contract, record by record through ``cas``."""
    from k8s1m_tpu.control.coordinator import splice_node_name

    results = []
    for key, required_mod, name in binds:
        kv = store.get(key)
        if kv is None or kv.mod_revision != required_mod:
            results.append(_ERR_CAS)
            continue
        plain = all(c >= 0x20 and c not in b'"\\' for c in name)
        new = splice_node_name(kv.value, name.decode()) if plain else None
        if new is None:
            results.append(_ERR_INVALID)
            continue
        ok, rev, _ = store.cas(key, new, required_mod=required_mod,
                               lease=kv.lease)
        assert ok
        results.append(rev)
    return results


@pytest.mark.parametrize("lane", ["put", "bind"])
def test_frame_fanout_cap_falls_inside_the_frame(store, lane):
    """A queue smaller than a frame takes the frame's first events, in
    order, and counts exactly the rest; a queue with room takes all."""
    pods = [_pod(f"p{i}") for i in range(8)]
    if lane == "bind":
        rev = store.put_batch(pods)
        first = rev - len(pods) + 1
    small = _pods_watch(store, queue_cap=5)
    roomy = _pods_watch(store)
    before = store.stats()["watch_pressure"]
    if lane == "put":
        last = store.put_batch(pods)
    else:
        res = store.bind_batch(
            [(k, first + i, b"n-%d" % i) for i, (k, _) in enumerate(pods)]
        )
        last = int(res[-1])
        assert (res > 0).all()
    revs = list(range(last - 7, last + 1))
    assert small.dropped == 3 and roomy.dropped == 0
    wp = store.stats()["watch_pressure"]
    assert wp["enqueued"] - before["enqueued"] == 5 + 8
    assert wp["dropped"] - before["dropped"] == 3
    assert wp["queue_hwm"] >= 8
    # a full queue takes nothing of the next frame, and counts all of it
    store.put_batch([_pod("q0"), _pod("q1")])
    assert small.dropped == 5 and small.pending == 5
    got_small, got_roomy = small.poll(), roomy.poll()
    assert [e.kv.mod_revision for e in got_small] == revs[:5]
    assert [e.kv.key for e in got_small] == [k for k, _ in pods[:5]]
    assert [e.kv.mod_revision for e in got_roomy] == revs + [last + 1, last + 2]
    # drained, it takes events again, from the next write on
    store.put(*_pod("q2"))
    assert [e.kv.key for e in small.poll()] == [_pod("q2")[0]]


def test_events_outlive_their_key(store):
    """A queued event keeps its key and value after the item is gone:
    delete, compact past the tombstone (tombstone GC frees the item),
    churn the allocator, then poll."""
    plain, pods = _pods_watch(store), _pods_watch(store)
    k, v = _pod("gone", cpu_milli=123)
    r1 = store.put(k, v)
    r2, _ = store.delete(k)
    store.compact(store.current_revision)
    assert store.get(k) is None
    store.put_batch([_pod(f"churn{i}") for i in range(64)])
    evs = plain.poll(2)
    assert [(e.type, e.kv.key, e.kv.value, e.kv.mod_revision) for e in evs] == [
        ("PUT", k, v, r1), ("DELETE", k, b"", r2),
    ]
    evb = pods.poll_pods(2)
    assert evb.key_blob == k + k and evb.mrev.tolist() == [r1, r2]
    assert evb.cpu.tolist() == [123, 0]
    # the key can come back: a fresh item under the same bytes
    r3 = store.put(k, v)
    kv = store.get(k)
    assert (kv.create_revision, kv.version) == (r3, 1)


def test_put_frame_equals_one_by_one():
    """Keys out of order, a key twice in one frame, a delete marker for a
    key the frame wrote, one for a key it did not, one for a key that is
    absent: the frame leaves what the same operations leave one by one."""
    a, b = MemStore(), MemStore()
    try:
        for s in (a, b):
            s.put(NODE_PREFIX + b"n0", b"old")
            s.put(b"/registry/pods/default/z", b"zz")
        items = [
            (b"/registry/pods/default/m", b"m1"),
            (b"/registry/pods/default/c", b"c1"),
            (NODE_PREFIX + b"n0", b"new"),
            (b"/registry/pods/default/m", b"m2"),       # twice in the frame
            (b"/registry/pods/default/c", None),        # written above
            (b"/registry/pods/default/z", None),        # written before
            (b"/registry/pods/default/nope", None),     # absent: no revision
            (b"/registry/apps.k8s.io/deployments/d", b"d1"),
            (b"/registry/pods/default/c", b"c2"),       # resurrected
            (b"/registry/pods/default/a", b"a1"),
        ]
        watches = {}
        for name, s in (("a", a), ("b", b)):
            watches[name] = (
                s.watch(b"\0", b"\0"), s.watch(b"\0", b"\0", prev_kv=True),
            )
        last = a.put_batch(items)
        for key, value in items:
            if value is None:
                b.delete(key)
            else:
                b.put(key, value)
        assert last == a.current_revision == b.current_revision
        assert _readable_history(a) == _readable_history(b)
        assert a.get(b"/registry/pods/default/m").version == 2
        assert a.get(b"/registry/pods/default/c").version == 1
        sa, sb = a.stats(), b.stats()
        for f in ("keys", "db_bytes", "prefixes", "revision"):
            assert sa[f] == sb[f], f
        for wa, wb in zip(watches["a"], watches["b"]):
            ra = _raw_poll(wa)
            assert ra == _raw_poll(wb) and len(ra) > 5
        # the ordered index took every insert, whatever the hint was worth
        keys = [kv.key for kv in a.range(b"/registry/", b"/registry0").kvs]
        assert keys == sorted(keys) and len(keys) == 5
    finally:
        a.close()
        b.close()


def test_bind_frame_equals_cas_by_cas():
    """A mixed bind frame: per-record results, stored bytes, history and
    events equal the same binds sent ``cas`` by ``cas``."""
    from k8s1m_tpu.control.coordinator import splice_node_name

    a, b = MemStore(), MemStore()
    try:
        seed = [
            _pod("good0"), _pod("stale"), _pod("good1", labels={"x": "y"}),
            _pod("appended", node_name="n-old"),      # nodeName after containers
            _pod("quote"), _pod("good2"),
            (b"/registry/pods/default/raw", b"not a pod at all"),
        ]
        spliced_k, spliced_v = _pod("spliced")
        spliced_v = splice_node_name(spliced_v, "n-old")  # nodeName in spec's head
        seed.append((spliced_k, spliced_v))
        revs = {}
        for s in (a, b):
            last = s.put_batch(seed)
            for i, (k, _) in enumerate(seed):
                revs[k] = last - len(seed) + 1 + i
            s.put(*_pod("stale", cpu_milli=9))    # moves its mod_revision on
        binds = [
            (_pod("good0")[0], revs[_pod("good0")[0]], b"n-0"),
            (_pod("stale")[0], revs[_pod("stale")[0]], b"n-1"),
            (b"/registry/pods/default/missing", 7, b"n-2"),
            (_pod("good1")[0], revs[_pod("good1")[0]], b"n-3"),
            (_pod("appended")[0], revs[_pod("appended")[0]], b"n-4"),
            (spliced_k, revs[spliced_k], b"n-5"),
            (_pod("quote")[0], revs[_pod("quote")[0]], b'n-"6'),
            (b"/registry/pods/default/raw",
             revs[b"/registry/pods/default/raw"], b"n-7"),
            (_pod("good2")[0], revs[_pod("good2")[0]], b"n-8"),
            (_pod("good0")[0], revs[_pod("good0")[0]], b"n-9"),  # bound above
        ]
        wa = (_pods_watch(a), _pods_watch(a, prev_kv=True))
        wb = (_pods_watch(b), _pods_watch(b, prev_kv=True))
        got = a.bind_batch(binds).tolist()
        want = _bind_one_by_one(b, binds)
        r = a.current_revision
        assert got == want == [
            r - 2, _ERR_CAS, _ERR_CAS, r - 1, _ERR_INVALID, _ERR_INVALID,
            _ERR_INVALID, _ERR_INVALID, r, _ERR_CAS,
        ]
        assert _readable_history(a) == _readable_history(b)
        kv = a.get(_pod("good1")[0])
        assert kv.value == splice_node_name(_pod("good1", labels={"x": "y"})[1], "n-3")
        assert kv.version == 2 and kv.create_revision == revs[kv.key]
        assert a.stats()["prefixes"] == b.stats()["prefixes"]
        assert _raw_poll_pods(wa[0]) == _raw_poll_pods(wb[0])
        ra = _raw_poll(wa[1])
        assert ra == _raw_poll(wb[1]) and ra[:4] == (3).to_bytes(4, "little")
    finally:
        a.close()
        b.close()


@pytest.mark.parametrize("lane", ["put", "put_batch", "bind_batch"])
def test_prev_kv_only_where_asked(store, lane):
    """One frame, two watchers on the same keys: prev_kv rides only to
    the watcher that asked for it."""
    pods = [_pod(f"p{i}") for i in range(3)]
    first = store.put_batch(pods) - 2
    asked, plain = _pods_watch(store, prev_kv=True), _pods_watch(store)
    if lane == "put":
        for k, _ in pods:
            store.put(k, b"v2")
    elif lane == "put_batch":
        store.put_batch([(k, b"v2") for k, _ in pods] + [_pod("fresh")])
    else:
        store.bind_batch([(k, first + i, b"n") for i, (k, _) in enumerate(pods)])
    got, bare = asked.poll(), plain.poll()
    assert [e.prev_kv.value for e in got[:3]] == [v for _, v in pods]
    assert [e.prev_kv.mod_revision for e in got[:3]] == [first, first + 1, first + 2]
    assert all(e.prev_kv is None for e in bare)
    assert [e.kv for e in got] == [e.kv for e in bare]
    if lane == "put_batch":
        assert got[3].prev_kv is None       # a create has nothing before it
    # the last asker gone, the store carries prev_kv to nobody
    asked.cancel()
    store.put(pods[0][0], b"v3")
    assert plain.poll()[0].prev_kv is None


def test_exclude_watcher_through_the_frame(store):
    """exclude_watcher through the batched fan-out: the excluded watcher
    gets none of the frame's events and loses none of its room; every
    other watcher gets each of them once."""
    pods = [_pod(f"p{i}") for i in range(6)]
    first = store.put_batch(pods) - 5
    mine = _pods_watch(store, queue_cap=4)
    others = [_pods_watch(store), _pods_watch(store, prev_kv=True)]
    unrelated = store.watch(NODE_PREFIX, prefix_end(NODE_PREFIX))
    res = store.bind_batch(
        [(k, first + i, b"n-%d" % i) for i, (k, _) in enumerate(pods)],
        exclude_watcher=mine.id,
    )
    assert res.tolist() == list(range(first + 6, first + 12))
    assert mine.poll() == [] and mine.dropped == 0
    for w in others:
        assert [e.kv.mod_revision for e in w.poll()] == res.tolist()
        assert w.poll() == []
    assert unrelated.poll() == []
    # its own puts still reach it, through the same fan-out
    store.put_batch([_pod("later")])
    assert len(mine.poll()) == 1


def test_enqueue_batches_counts_queue_acquisitions(store):
    """``watch_pressure.enqueue_batches``: +1 a matching watcher a frame,
    whatever the frame's length, and +1 a matching watcher a put."""
    w1, w2 = _pods_watch(store), _pods_watch(store)
    store.watch(NODE_PREFIX, prefix_end(NODE_PREFIX))     # matches nothing

    def pressure():
        wp = store.stats()["watch_pressure"]
        return wp["enqueued"], wp["enqueue_batches"]

    e0, b0 = pressure()
    pods = [_pod(f"p{i}") for i in range(50)]
    first = store.put_batch(pods) - 49
    assert pressure() == (e0 + 100, b0 + 2)
    store.bind_batch(
        [(k, first + i, b"n") for i, (k, _) in enumerate(pods)],
        exclude_watcher=w1.id,
    )
    assert pressure() == (e0 + 150, b0 + 3)
    store.put(*_pod("one"))
    store.delete(_pod("one")[0])
    assert pressure() == (e0 + 154, b0 + 7)
    store.put_batch([])                                 # an empty frame
    store.delete(b"/registry/pods/default/absent")      # no revision
    assert pressure() == (e0 + 154, b0 + 7)
    assert len(w1.poll()) == 52 and len(w2.poll()) == 102


def test_batch_lanes_equal_the_one_record_lane_randomised():
    """A few hundred seeded operations — puts, deletes, CAS, put frames
    and bind frames of mixed length, polls that take part of a queue, a
    compaction in the middle — once through the batch lanes and once
    record by record: the same ``range`` at every revision still
    readable, the same stats, byte-identical ``poll`` / ``poll_pods``
    frames."""
    import random

    rng = random.Random(31)
    a, b = MemStore(), MemStore()
    try:
        names = [f"p{i}" for i in range(40)]
        node_keys = [NODE_PREFIX + b"n%d" % i for i in range(12)]
        wa = (_pods_watch(a), a.watch(b"\0", b"\0", prev_kv=True))
        wb = (_pods_watch(b), b.watch(b"\0", b"\0", prev_kv=True))
        frames_a, frames_b = [], []

        def poll_some():
            n = rng.choice([1, 3, 1 << 20])
            frames_a.append((_raw_poll_pods(wa[0], n), _raw_poll(wa[1], n)))
            frames_b.append((_raw_poll_pods(wb[0], n), _raw_poll(wb[1], n)))

        def a_put_record():
            if rng.random() < 0.25:
                return rng.choice(node_keys), b"node-%d" % rng.randrange(99)
            name = rng.choice(names)
            if rng.random() < 0.2:
                return _pod(name, priority=rng.randrange(1, 5))  # lane json
            return _pod(name, cpu_milli=rng.randrange(1, 999))

        def a_key():
            if rng.random() < 0.25:
                return rng.choice(node_keys)
            return _pod(rng.choice(names))[0]

        for step in range(320):
            op = rng.random()
            if step == 160:
                poll_some()
                at = a.current_revision - rng.randrange(0, 20)
                a.compact(at)
                b.compact(at)
            elif op < 0.25:
                k, v = a_put_record()
                assert a.put(k, v) == b.put(k, v)
            elif op < 0.35:
                k = a_key()
                assert a.delete(k) == b.delete(k)
            elif op < 0.45:
                k, v = a_put_record()
                cur = a.get(k)
                req = cur.mod_revision if cur and rng.random() < 0.7 else 3
                ra = a.cas(k, v, required_mod=req)
                assert ra == b.cas(k, v, required_mod=req)
            elif op < 0.75:
                items = []
                for _ in range(rng.choice([0, 1, 2, 7, 30])):
                    if rng.random() < 0.2:
                        items.append((a_key(), None))
                    else:
                        items.append(a_put_record())
                if rng.random() < 0.5:
                    items.sort(key=lambda kv: kv[0])    # a client in order
                a.put_batch(items)
                for k, v in items:
                    b.delete(k) if v is None else b.put(k, v)
            elif op < 0.95:
                binds = []
                for _ in range(rng.choice([1, 2, 9, 25])):
                    k = _pod(rng.choice(names))[0]
                    cur = a.get(k)
                    mod = cur.mod_revision if cur and rng.random() < 0.8 else 5
                    name = rng.choice([b"n-1", b"kwok-node-77", b'bad"name'])
                    binds.append((k, mod, name))
                exclude = rng.choice([-1, 0])
                got = a.bind_batch(
                    binds, exclude_watcher=wa[0].id if exclude == 0 else -1
                ).tolist()
                assert got == _bind_one_by_one(b, binds)
                if exclude == 0:
                    # b's plain watcher saw the echoes; drop them there
                    n = sum(1 for r in got if r > 0)
                    assert b"nodeName" in _raw_poll(wb[0]) or n == 0
                    _raw_poll(wa[0])
            else:
                poll_some()
            assert a.current_revision == b.current_revision
        poll_some()
        assert frames_a == frames_b
        assert sum(len(f[1]) for f in frames_a) > 10000
        assert _readable_history(a) == _readable_history(b)
        sa, sb = a.stats(), b.stats()
        for f in ("revision", "compact_revision", "keys", "db_bytes",
                  "prefixes"):
            assert sa[f] == sb[f], f
        assert sa["watch_pressure"]["enqueued"] < sb["watch_pressure"]["enqueued"]
        assert (sa["watch_pressure"]["enqueue_batches"]
                < sb["watch_pressure"]["enqueue_batches"])
    finally:
        a.close()
        b.close()
