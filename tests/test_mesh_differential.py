"""meshscale: the dp x sp sharded cycle as the production execution path.

The differential gate (ROADMAP item 1): a coordinator driving the
8-device CPU mesh must be BYTE-IDENTICAL to the single-device pipeline —
binds (stored pod bytes, spliced nodeName included), host mirror, and
device request totals — at 4096+ nodes, including capacity churn and
structural adds landing while waves are in flight, and through the
quarantine-exhaustion quiesce.  The contract that makes this possible:
every device hashes tie-break jitter over GLOBAL (pod row, node row)
coordinates with the SAME per-wave seed (parallel/sharded_cycle
mesh_offsets), so the sharded step is bit-equal to the single-device
step, not merely statistically equivalent.

Also here: the per-dp-shard host feed (snapshot/hotfeed.ShardedHostFeed)
— merge byte-identity against the inline full-batch encode, and the
mesh-selection funnel (parse_mesh/auto_mesh_shape/resolve_mesh).
"""

import json

import jax
import numpy as np
import pytest

from k8s1m_tpu.cluster import populate_kwok_nodes, uniform_pods
from k8s1m_tpu.config import PodSpec, TableSpec
from k8s1m_tpu.control.coordinator import Coordinator
from k8s1m_tpu.control.objects import encode_node, encode_pod, node_key, pod_key
from k8s1m_tpu.engine.cycle import schedule_batch_packed
from k8s1m_tpu.obs.metrics import REGISTRY
from k8s1m_tpu.parallel import (
    auto_mesh_shape,
    make_mesh,
    parse_mesh,
    resolve_mesh,
)
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot import NodeInfo, NodeTableHost, PodBatchHost, PodInfo
from k8s1m_tpu.snapshot.hotfeed import HotPodBatchHost, ShardedHostFeed, merge_packed
from k8s1m_tpu.store.native import MemStore, prefix_end

PROFILE = Profile(topology_spread=0, interpod_affinity=0)
SPEC4K = TableSpec(max_nodes=4096, max_zones=16, max_regions=8)
PODS = PodSpec(batch=64)
CHUNK = 512


def mesh_2x4():
    return make_mesh(dp=2, sp=4)


# ---- 1. the sharded step is bit-equal to the single-device step -------


def test_sharded_step_byte_identical_at_4096_nodes():
    """4096 KWOK nodes (maximum tie pressure: capacities repeat across
    groups), 64 pods: the mesh step's bind rows, scores, and the FULL
    per-row request columns must equal the single-device step's exactly
    — not within a tolerance."""
    host = NodeTableHost(SPEC4K)
    populate_kwok_nodes(host, 4096, zones=8, regions=4)
    enc = PodBatchHost(PODS, SPEC4K, host.vocab)
    packed = enc.encode_packed(uniform_pods(64))
    key = jax.random.key(3)

    t1, _, a1, rows1 = schedule_batch_packed(
        host.to_device(), packed, key,
        profile=PROFILE, chunk=CHUNK, k=4,
    )
    mesh = mesh_2x4()
    from jax.sharding import NamedSharding, PartitionSpec as P

    t2, _, a2, rows2 = schedule_batch_packed(
        host.to_device(NamedSharding(mesh, P("sp"))), packed, key,
        profile=PROFILE, chunk=CHUNK, k=4, mesh=mesh,
    )
    np.testing.assert_array_equal(np.asarray(rows1), np.asarray(rows2))
    np.testing.assert_array_equal(np.asarray(a1.score), np.asarray(a2.score))
    np.testing.assert_array_equal(np.asarray(a1.bound), np.asarray(a2.bound))
    for col in ("cpu_req", "mem_req", "pods_req"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t1, col)), np.asarray(getattr(t2, col))
        )


# ---- 2. coordinator differential: mesh == single-device under churn ---


def put_node(store, name, zone="z0", cpu=4000, mem=8 << 20, pods=64, **kw):
    labels = {"topology.kubernetes.io/zone": zone, **kw.pop("labels", {})}
    store.put(
        node_key(name),
        encode_node(NodeInfo(name=name, cpu_milli=cpu, mem_kib=mem,
                             pods=pods, labels=labels, **kw)),
    )


def put_pod(store, name, ns="default", cpu=20, mem=200 << 10, **kw):
    store.put(
        pod_key(ns, name),
        encode_pod(PodInfo(name=name, namespace=ns, cpu_milli=cpu,
                           mem_kib=mem, **kw)),
    )


def node_of(store, ns, name):
    kv = store.get(pod_key(ns, name))
    return json.loads(kv.value)["spec"].get("nodeName")


def _snapshot(c, store):
    res = store.range(b"/registry/pods/", prefix_end(b"/registry/pods/"))
    pods = {bytes(kv.key): bytes(kv.value) for kv in res.kvs}
    host = {
        "row_of": dict(c.host._row_of),
        "valid": c.host.valid.copy(),
        "cpu_alloc": c.host.cpu_alloc.copy(),
        "cpu_req": c.host.cpu_req.copy(),
        "mem_req": c.host.mem_req.copy(),
        "pods_req": c.host.pods_req.copy(),
    }
    table_req = np.asarray(c.table.pods_req).copy()
    return pods, host, table_req


def _drive_churned_4k(mesh):
    """One deterministic schedule at 4096 nodes: pod waves + capacity
    churn on held rows + structural fresh-row adds, all applied while
    waves are in flight; same seed both modes.  mesh=None IS the
    single-device pipeline."""
    with MemStore() as store:
        # 4090 of 4096 rows filled: headroom for the structural adds.
        for i in range(4090):
            put_node(store, f"n{i}", zone=f"z{i % 4}")
        c = Coordinator(
            store, SPEC4K, PODS, PROFILE, chunk=CHUNK, k=4,
            with_constraints=False, pipeline=True, depth=3, seed=7,
            max_attempts=8, mesh=mesh,
        )
        c.bootstrap()
        max_depth = 0
        for wave in range(5):
            for i in range(48):
                put_pod(store, f"w{wave}-{i}")
            # Capacity-only churn against rows the table holds, landing
            # mid-flight through the (sharded) CAP-columns scatter.
            for j in range(4):
                put_node(store, f"n{(17 * wave + j) % 4090}",
                         zone=f"z{(17 * wave + j) % 4}",
                         cpu=4000 + 100 * wave)
            if wave == 2:
                put_node(store, "fresh-a")   # structural fresh rows
                put_node(store, "fresh-b")
            c.step()
            max_depth = max(max_depth, len(c._inflights))
        c.run_until_idle()
        snap = _snapshot(c, store)
        c.close()
        return (*snap, max_depth)


def structural_quiesces() -> float:
    return REGISTRY.get("pipeline_quiesce_total").value(reason="structural")


def test_mesh_coordinator_byte_identical_under_churn_4096():
    base = structural_quiesces()
    pods_m, host_m, treq_m, depth_m = _drive_churned_4k(mesh_2x4())
    assert structural_quiesces() == base     # churn never quiesced the mesh
    assert depth_m >= 2                      # ...and the pipeline stayed deep
    pods_s, host_s, treq_s, _ = _drive_churned_4k(None)
    # Byte-identical binds: every stored pod object, spliced nodeName
    # included, matches the single-device pipeline exactly.
    assert pods_m == pods_s
    assert host_m["row_of"] == host_s["row_of"]
    for col in ("valid", "cpu_alloc", "cpu_req", "mem_req", "pods_req"):
        np.testing.assert_array_equal(host_m[col], host_s[col])
    np.testing.assert_array_equal(treq_m, treq_s)
    assert host_m["pods_req"].sum() == 5 * 48


# ---- 3. removes + quarantine exhaustion on the mesh -------------------

SMALL = TableSpec(max_nodes=128, max_zones=16, max_regions=8)
SMALL_PODS = PodSpec(batch=32)


def test_mesh_remove_readd_no_row_aliasing():
    """Remove + immediate re-add of a node name while a mesh wave is in
    flight: fresh row, tombstone scattered through the SHARDED scatter,
    in-flight bind retries onto the new row — same invariants as the
    single-device quarantine suite."""
    with MemStore() as store:
        put_node(store, "a", labels={"disk": "ssd"})
        c = Coordinator(
            store, SMALL, SMALL_PODS, PROFILE, chunk=16, k=4,
            with_constraints=False, pipeline=True, depth=2,
            max_attempts=8, mesh=mesh_2x4(),
        )
        c.bootstrap()
        put_pod(store, "p0", node_selector={"disk": "ssd"})
        c.step()
        assert len(c._inflights) == 1
        old_row = c.host.row_of("a")
        store.delete(node_key("a"))
        put_node(store, "a", labels={"disk": "ssd"})
        assert c._drain_node_events() == 2
        new_row = c.host.row_of("a")
        assert new_row != old_row
        assert c.host.quarantined == 1
        assert not c.host.valid[old_row]
        total = c.run_until_idle()
        assert total == 1
        assert node_of(store, "default", "p0") == "a"
        assert c.host.pods_req[new_row] == 1
        assert c.host.pods_req[old_row] == 0
        assert c.host.quarantined == 0
        c.close()


def _drive_exhaustion(mesh):
    """Quarantine exhaustion on a full table while a wave is in flight:
    the one remaining structural quiesce, driven identically through
    both execution paths and compared byte-for-byte."""
    tiny = TableSpec(max_nodes=8, max_zones=16, max_regions=8)
    with MemStore() as store:
        for i in range(8):
            put_node(store, f"n{i}")
        c = Coordinator(
            store, tiny, PodSpec(batch=8), PROFILE, chunk=2, k=2,
            with_constraints=False, pipeline=True, depth=2, seed=3,
            max_attempts=8, mesh=mesh,
        )
        c.bootstrap()
        put_pod(store, "p0")
        c.step()
        assert len(c._inflights) == 1
        store.delete(node_key("n0"))
        put_node(store, "m0")    # table full; only the quarantined row fits
        base = structural_quiesces()
        c._drain_node_events()
        assert structural_quiesces() == base + 1
        assert not c._inflights              # pipeline was retired
        c.run_until_idle()
        snap = _snapshot(c, store)
        c.close()
        return snap


def test_mesh_quarantine_exhaustion_differential():
    pods_m, host_m, treq_m = _drive_exhaustion(mesh_2x4())
    pods_s, host_s, treq_s = _drive_exhaustion(None)
    assert pods_m == pods_s
    assert host_m["row_of"] == host_s["row_of"]
    for col in ("valid", "cpu_req", "pods_req"):
        np.testing.assert_array_equal(host_m[col], host_s[col])
    np.testing.assert_array_equal(treq_m, treq_s)
    assert host_m["pods_req"].sum() == 1


# ---- 4. the per-dp-shard host feed ------------------------------------


def _shaped_pods(vocab, n):
    """Pods with structural features spanning both dp slices, sharing
    selector keys across the slice boundary (the qkey-merge case)."""
    host = NodeTableHost(SMALL, vocab)
    host.upsert(NodeInfo(
        "seed-node", labels={"disk": "ssd", "tier": "gold", "rack": "r1"},
    ))
    pods = []
    for i in range(n):
        sel = (
            {"disk": "ssd"} if i % 3 == 0
            else {"tier": "gold", "rack": "r1"} if i % 3 == 1
            else {}
        )
        pods.append(PodInfo(
            name=f"sp{i}", cpu_milli=100 + i, mem_kib=(1 << 14) + i,
            node_selector=sel or None,
        ))
    return pods


def test_merge_packed_byte_identical_to_inline_encode():
    from k8s1m_tpu.snapshot.interning import Vocab

    vocab = Vocab()
    pods = _shaped_pods(vocab, 32)
    full_enc = HotPodBatchHost(SMALL_PODS, SMALL, vocab)
    inline = full_enc.encode_packed(pods)

    half_spec = PodSpec(batch=16)
    subs = [
        HotPodBatchHost(half_spec, SMALL, vocab).encode_packed(pods[:16]),
        HotPodBatchHost(half_spec, SMALL, vocab).encode_packed(pods[16:]),
    ]
    merged = merge_packed(subs)
    assert merged is not None
    assert merged.groups == inline.groups
    assert merged.vocab_gen == inline.vocab_gen
    np.testing.assert_array_equal(merged.ints, inline.ints)
    np.testing.assert_array_equal(merged.bools, inline.bools)
    for name, arr in inline.fields.items():
        np.testing.assert_array_equal(merged.fields[name], arr)


def test_merge_packed_plain_lane():
    from k8s1m_tpu.snapshot.interning import Vocab

    vocab = Vocab()
    full_enc = HotPodBatchHost(SMALL_PODS, SMALL, vocab)
    cpu = list(range(100, 132))
    mem = list(range(1000, 1032))
    inline = full_enc.encode_packed_plain(cpu, mem)
    half = PodSpec(batch=16)
    subs = [
        HotPodBatchHost(half, SMALL, vocab).encode_packed_plain(
            cpu[:16], mem[:16]
        ),
        HotPodBatchHost(half, SMALL, vocab).encode_packed_plain(
            cpu[16:], mem[16:]
        ),
    ]
    merged = merge_packed(subs)
    assert merged.vocab_gen is None and merged.groups == frozenset()
    np.testing.assert_array_equal(merged.ints, inline.ints)
    np.testing.assert_array_equal(merged.bools, inline.bools)


def test_merge_packed_qkey_overflow_returns_none():
    """Sub-batches each within query_keys but overflowing merged must
    fail closed (claim falls back to the inline encode, which raises the
    real batch-level overflow on the cycle thread)."""
    from k8s1m_tpu.snapshot.interning import Vocab

    vocab = Vocab()
    half = PodSpec(batch=16, query_keys=4)      # 3 usable slots per batch
    host = NodeTableHost(SMALL, vocab)
    labels = {f"k{j}": "v" for j in range(6)}
    host.upsert(NodeInfo("seed", labels=labels))

    def sub(base):
        enc = HotPodBatchHost(half, SMALL, vocab)
        pods = [
            PodInfo(
                name=f"q{base}-{i}",
                node_selector={f"k{base + i % 3}": "v"},
            )
            for i in range(16)
        ]
        return enc.encode_packed(pods)

    # Disjoint key sets: 3 + 3 distinct keys > 3 usable merged slots.
    merged = merge_packed([sub(0), sub(3)])
    assert merged is None


def test_sharded_feed_stages_and_coordinator_stays_identical():
    """Mesh coordinator with the per-dp-shard feed: staged batches are
    actually used AND the run remains byte-identical to the
    single-device pipeline (claims are byte-identical by contract)."""
    used = REGISTRY.get("hotfeed_staged_used_total")

    def drive(mesh):
        with MemStore() as store:
            for i in range(64):
                put_node(store, f"n{i}")
            c = Coordinator(
                store, SMALL, SMALL_PODS, PROFILE, chunk=16, k=4,
                with_constraints=False, pipeline=True, depth=2, seed=11,
                mesh=mesh, hotfeed=True,
            )
            if mesh is not None:
                assert isinstance(c._feed, ShardedHostFeed)
                assert len(c._feed.feeds) == 2          # one per dp shard
            c.bootstrap()
            for i in range(192):
                put_pod(store, f"p{i}")
            total = c.run_until_idle()
            snap = _snapshot(c, store)
            c.close()
            return total, snap

    before = used.value()
    total_m, snap_m = drive(mesh_2x4())
    assert total_m == 192
    assert used.value() > before       # the sharded feed staged real waves
    total_s, snap_s = drive(None)
    assert total_s == 192
    assert snap_m[0] == snap_s[0]
    np.testing.assert_array_equal(snap_m[1]["pods_req"], snap_s[1]["pods_req"])
    np.testing.assert_array_equal(snap_m[2], snap_s[2])


# ---- 5. mesh selection (the production funnel) ------------------------


def test_parse_mesh_forms():
    assert parse_mesh(None) is None
    assert parse_mesh("none") is None
    assert parse_mesh("") is None
    assert parse_mesh("auto") == "auto"
    assert parse_mesh("2x4") == (2, 4)
    assert parse_mesh("2,4") == (2, 4)
    assert parse_mesh("1X8") == (1, 8)
    with pytest.raises(ValueError):
        parse_mesh("8")
    with pytest.raises(ValueError):
        parse_mesh("0x4")


def test_auto_mesh_shape_respects_divisibility():
    # 8 devices, everything divides: use them all, sp-major.
    assert auto_mesh_shape(8, batch=64, max_nodes=4096, chunk=512) == (1, 8)
    # rows-per-shard must stay chunk-aligned: sp=8 gives 512%512=0, but
    # chunk 1024 forces sp<=4.
    assert auto_mesh_shape(8, batch=64, max_nodes=4096, chunk=1024) == (2, 4)
    # batch indivisible by any dp>1 pushes dp to 1.
    assert auto_mesh_shape(8, batch=63, max_nodes=4096, chunk=512) == (1, 8)
    # nothing fits -> single-device fallback.
    assert auto_mesh_shape(8, batch=63, max_nodes=4095, chunk=512) is None
    assert auto_mesh_shape(1, batch=64, max_nodes=4096, chunk=512) is None


def test_coordinator_mesh_from_string():
    with MemStore() as store:
        c = Coordinator(
            store, SMALL, SMALL_PODS, PROFILE, chunk=16, k=4,
            with_constraints=False, mesh="2x4",
        )
        assert c.mesh is not None
        assert (c.mesh.shape["dp"], c.mesh.shape["sp"]) == (2, 4)
        c.close()
    for single in ("none", None):
        with MemStore() as store:
            c = Coordinator(
                store, SMALL, SMALL_PODS, PROFILE, chunk=16, k=4,
                with_constraints=False, mesh=single,
            )
            assert c.mesh is None
            c.close()


def test_coordinator_mesh_auto_string():
    with MemStore() as store:
        c = Coordinator(
            store, SMALL, SMALL_PODS, PROFILE, chunk=16, k=4,
            with_constraints=False, mesh="auto",
        )
        assert c.mesh is not None          # 8 virtual devices fit 128 rows
        assert c.mesh.shape["dp"] * c.mesh.shape["sp"] == 8
        c.close()


def test_resolve_mesh_auto_falls_back_single_device():
    # A workload no split fits: prime node count.
    assert resolve_mesh(
        "auto", batch=64, max_nodes=4095, chunk=512
    ) is None


def test_mesh_metrics_registered_and_live():
    """mesh_* metrics exist (graftlint's registry pass covers the
    declarations; this pins the runtime wiring) and report the live
    coordinator's axes."""
    with MemStore() as store:
        c = Coordinator(
            store, SMALL, SMALL_PODS, PROFILE, chunk=16, k=4,
            with_constraints=False, mesh=mesh_2x4(),
        )
        g = REGISTRY.get("mesh_devices")
        assert g.value(axis="dp") >= 2
        assert g.value(axis="sp") >= 4
        c.bootstrap()
        put_node(store, "n0")
        c.step()                                   # node add -> full scatter
        sc = REGISTRY.get("mesh_sharded_scatter_total")
        assert sc.value(cols="full") >= 1
        assert REGISTRY.get("mesh_feed_staged_depth").value() >= 0
        c.close()
