"""deltasched: incremental filter+score via shape-keyed plane reuse.

The cache is an invisible replay, never a semantic (engine/deltacache.py)
— so the gates here are differential: a delta-cached coordinator must be
BYTE-IDENTICAL to the full-recompute coordinator (stored pod bytes incl.
the spliced nodeName, host mirror, device request totals) under every
condition that can move a cached plane out from under a wave.

Layers:

1. RowVersions — the monotone per-row mutation journal: enumeration,
   the fail-closed compaction floor, targeted release.
2. DeltaPlaneCache.plan — promotion on second sighting, hits, LRU slot
   eviction (counted), oversized-dirty slot refresh, the epoch-checked
   plane accessor.
3. shape_key — what is cacheable (structural fingerprint + request
   scalars) and what is not (constraint-coupled pods, spec.nodeName).
4. Epoch invalidation edges (the ISSUE 12 checklist): remove →
   re-add-same-name, a mid-flight structural add landing between a
   shape's cache fill and its next hit, a packing-overflow rebuild
   dropping the cache, and a mesh rebuild retiring the donated planes.
5. The composed tier-1 gate at 4096 nodes: delta-cached packed ×
   sharded × donated pipeline at depth 3 under capacity churn +
   structural adds + priority preemption + gang scheduling ==
   full-recompute plain single-device, byte for byte.

Also here: the bounded Coordinator._shape_bindings (ISSUE 12
satellite — it grew per (registration-count, namespace) key forever).
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

import jax.numpy as jnp

from k8s1m_tpu.config import PodSpec, TableSpec
from k8s1m_tpu.control.coordinator import Coordinator
from k8s1m_tpu.control.objects import encode_node, encode_pod, node_key, pod_key
from k8s1m_tpu.engine.deltacache import (
    INDEX_FLOOR_UNBUILT,
    DeltaPlaneCache,
    dedup_rows,
    index_topk,
    index_usable,
    plane_topk,
    rebuild_index,
    resolve_deltasched,
    update_index,
)
from k8s1m_tpu.ops.priority import (
    JITTER_BITS,
    class_key,
    hash_jitter,
    pack_hashed,
    stratum_hash,
)
from k8s1m_tpu.obs.metrics import REGISTRY
from k8s1m_tpu.parallel import make_mesh
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot import NodeInfo, PodInfo
from k8s1m_tpu.snapshot.hotfeed import shape_key
from k8s1m_tpu.snapshot.node_table import RowVersions
from k8s1m_tpu.snapshot.packing import build_packing_spec, is_packed, pack_table_host
from k8s1m_tpu.store.native import MemStore, prefix_end
from k8s1m_tpu.tenancy import TenancyController
from k8s1m_tpu.tenancy.policy import TenancyPolicy

PROFILE = Profile(topology_spread=0, interpod_affinity=0)


# ---- 1. RowVersions: the dirty-row journal ----------------------------


def test_row_versions_enumerates_rows_since():
    rv = RowVersions(cap=64)
    v1 = rv.note([3, 5])
    v2 = rv.note([5, 9])
    assert rv.rows_since(0) == {3, 5, 9}
    assert rv.rows_since(v1) == {5, 9}
    assert rv.rows_since(v2) == set()


def test_row_versions_compaction_floor_fails_closed():
    rv = RowVersions(cap=8)
    for i in range(12):
        rv.note([i])
    # The journal compacted: a consumer stamped before the floor cannot
    # enumerate its delta and must treat its state as wholly stale.
    assert rv.floor > 0
    assert rv.rows_since(0) is None
    # At or past the floor the delta is still exact.
    assert rv.rows_since(rv.ver) == set()
    assert len(rv) <= 8


def test_row_versions_release_keeps_live_consumers():
    rv = RowVersions(cap=64)
    rv.note([1])
    v2 = rv.note([2])
    rv.release(v2)
    # Consumers stamped >= v2 still enumerate exactly.
    assert rv.rows_since(v2) == set()
    assert rv.note([7]) == v2 + 1
    assert rv.rows_since(v2) == {7}
    # A consumer at v2-1 only needs entries >= v2 — still exact.
    assert rv.rows_since(v2 - 1) == {2, 7}
    # Consumers needing the dropped entries went stale.
    assert rv.rows_since(0) is None


# ---- 2. DeltaPlaneCache.plan: promotion, hits, eviction, refresh ------


def _plan_keys(cache, keys, b=8):
    return cache.plan(keys, b)


def test_plan_promotes_on_second_sighting_then_hits():
    cache = DeltaPlaneCache(64, slots=4)
    k = ("shape-a", 20, 1024)
    # First sighting: seen-noted, full pass, NO fill (one-shot shapes
    # never pay a plane fill).
    p1 = _plan_keys(cache, [k])
    assert p1.slot_ids is None and p1.fill_idx == []
    # Second sighting: promoted — fill dispatched, wave goes delta;
    # duplicate pods of the shape share the one representative fill.
    p2 = _plan_keys(cache, [k, k])
    assert p2.slot_ids is not None
    assert len(p2.fill_idx) == 1          # one representative per shape
    assert p2.slot_ids[0] == p2.slot_ids[1]
    cache.note_fill(p2)
    assert cache.resident == 1
    # Third sighting: a pure hit; the journaled rows since the fill are
    # the wave's dirty slice.
    cache.note_rows([17, 3])
    p3 = _plan_keys(cache, [k, k])
    assert p3.slot_ids is not None and p3.fill_idx == []
    dirty = set(int(r) for r in p3.dirty if r < cache.num_rows)
    assert dirty == {3, 17}
    assert p3.stamp_ver == cache.versions.ver


def test_plan_uncacheable_shape_poisons_wave():
    cache = DeltaPlaneCache(64, slots=4)
    k = ("shape-a", 20, 1024)
    _plan_keys(cache, [k])
    p = _plan_keys(cache, [k, None])
    assert p.slot_ids is None and p.fill_idx == []


def test_plan_lru_eviction_counted():
    ev = REGISTRY.get("deltasched_evictions_total")
    base = ev.value()
    cache = DeltaPlaneCache(64, slots=2)
    keys = [(f"s{i}", 1, 1) for i in range(3)]
    for k in keys:
        _plan_keys(cache, [k])            # seen once each
    for k in keys:                        # promote all three into 2 slots
        p = _plan_keys(cache, [k])
        cache.note_fill(p)
    assert cache.resident == 2
    assert ev.value() == base + 1


def test_plan_oversized_dirty_refreshes_slots_not_full_pass():
    cache = DeltaPlaneCache(64, slots=4, dirty_cap=4)
    k = ("shape-a", 20, 1024)
    _plan_keys(cache, [k])
    p = _plan_keys(cache, [k])
    cache.note_fill(p)
    cache.note_rows(range(10))            # past dirty_cap
    p2 = _plan_keys(cache, [k])
    # The slot refreshes wholesale (one fill) and the wave still runs
    # delta — over an empty journaled dirty set.
    assert p2.slot_ids is not None
    assert len(p2.fill_idx) == 1
    assert set(int(r) for r in p2.dirty if r < cache.num_rows) == set()


def test_plan_never_evicts_a_slot_assigned_to_this_wave():
    """A promotion must not LRU-evict a slot an earlier pod of the SAME
    wave already resolved to — the refill would hand that pod another
    shape's plane and binds would silently diverge.  With every
    resident slot busy the wave takes the full pass instead."""
    cache = DeltaPlaneCache(64, slots=2)
    a, b, c = (("a", 1, 1), ("b", 1, 1), ("c", 1, 1))
    for k in (a, b, c):
        _plan_keys(cache, [k])            # all seen once
    for k in (a, b):                      # a and b resident
        cache.note_fill(_plan_keys(cache, [k]))
    assert cache.resident == 2
    ev = REGISTRY.get("deltasched_evictions_total").value()
    p = _plan_keys(cache, [a, b, c])
    assert p.slot_ids is None             # full pass, not a wrong-plane bind
    assert p.fill_idx == []               # and no partial promotion either
    assert REGISTRY.get("deltasched_evictions_total").value() == ev
    assert cache.resident == 2            # a and b untouched


def test_plan_evicts_only_untouched_slots():
    """Eviction still works when a resident slot is NOT used by the
    current wave: the untouched LRU shape goes, the wave stays delta."""
    cache = DeltaPlaneCache(64, slots=2)
    a, b, c = (("a", 1, 1), ("b", 1, 1), ("c", 1, 1))
    for k in (a, b, c):
        _plan_keys(cache, [k])
    for k in (a, b):
        cache.note_fill(_plan_keys(cache, [k]))
    p = _plan_keys(cache, [b, c])         # a is untouched -> the victim
    assert p.slot_ids is not None and len(p.fill_idx) == 1
    cache.note_fill(p)
    assert cache.resident == 2
    # a was evicted: its next sighting is a MISS that re-promotes via a
    # fresh fill (a stayed in the seen set), never a silent stale hit.
    p2 = _plan_keys(cache, [a])
    assert len(p2.fill_idx) == 1


def test_planes_accessor_is_epoch_checked():
    cache = DeltaPlaneCache(16, slots=2)
    cache.check_generation(7)
    mask, score = cache.planes(7)
    assert mask.shape == (2, 16) and score.shape == (2, 16)
    with pytest.raises(RuntimeError, match="generation"):
        cache.planes(8)


def test_resolve_deltasched_forms():
    assert resolve_deltasched(True) == "on"
    assert resolve_deltasched(False) == "off"
    assert resolve_deltasched("on") == "on"
    assert resolve_deltasched("off") == "off"
    assert resolve_deltasched(None) == "off"
    with pytest.raises(ValueError):
        resolve_deltasched("yes")


# ---- 3. shape_key: what is cacheable ----------------------------------


def test_shape_key_extends_fingerprint_with_request_scalars():
    a = PodInfo("a", cpu_milli=20, mem_kib=1024,
                node_selector={"disk": "ssd"})
    b = PodInfo("b", cpu_milli=20, mem_kib=1024,
                node_selector={"disk": "ssd"})
    c = PodInfo("c", cpu_milli=30, mem_kib=1024,
                node_selector={"disk": "ssd"})
    assert shape_key(a) == shape_key(b)
    assert shape_key(a) != shape_key(c)   # Fit reads the scalars


def test_shape_key_constraint_coupled_and_nodename_not_cacheable():
    assert shape_key(PodInfo("p", cpu_milli=1, mem_kib=1,
                             node_name="n0")) is None
    spread = PodInfo("q", cpu_milli=1, mem_kib=1)
    spread.spread_refs = ((0, 1),)
    assert shape_key(spread) is None
    aff = PodInfo("r", cpu_milli=1, mem_kib=1)
    aff.affinity_refs = ((0, 1),)
    assert shape_key(aff) is None


# ---- 4. + 5. coordinator differentials --------------------------------

SPEC = TableSpec(max_nodes=256, max_zones=16, max_regions=8)
PODS = PodSpec(batch=32)


def put_node(store, name, zone="z0", cpu=4000, pods=110, **kw):
    labels = {"topology.kubernetes.io/zone": zone, **kw.pop("labels", {})}
    store.put(node_key(name), encode_node(NodeInfo(
        name=name, cpu_milli=cpu, mem_kib=1 << 25, pods=pods,
        labels=labels, **kw,
    )))


def put_pod(store, name, ns="default", cpu=20, **kw):
    store.put(pod_key(ns, name), encode_pod(PodInfo(
        name=name, namespace=ns, cpu_milli=cpu, mem_kib=200 << 10, **kw,
    )))


def _snapshot(c, store):
    res = store.range(b"/registry/pods/", prefix_end(b"/registry/pods/"))
    pods = {bytes(kv.key): bytes(kv.value) for kv in res.kvs}
    host = {
        "row_of": dict(c.host._row_of),
        "valid": c.host.valid.copy(),
        "cpu_req": c.host.cpu_req.copy(),
        "mem_req": c.host.mem_req.copy(),
        "pods_req": c.host.pods_req.copy(),
    }
    table_req = np.asarray(c.table.pods_req).copy()
    return pods, host, table_req


def _assert_identical(a, b):
    pods_a, host_a, treq_a = a
    pods_b, host_b, treq_b = b
    assert pods_a == pods_b
    assert host_a["row_of"] == host_b["row_of"]
    for col in ("valid", "cpu_req", "mem_req", "pods_req"):
        np.testing.assert_array_equal(host_a[col], host_b[col])
    np.testing.assert_array_equal(treq_a, treq_b)


def _delta_waves():
    return REGISTRY.get("deltasched_waves_total").value(path="delta")


def _coord(store, *, delta, mesh=None, packing=None, tenancy=None,
           spec=SPEC, pods=PODS, chunk=64, depth=3, seed=7,
           backend="xla", index_k=0, stratum=0, index_dirty_cap=None):
    c = Coordinator(
        store, spec, pods, PROFILE, chunk=chunk, k=4,
        with_constraints=False, pipeline=True, depth=depth, seed=seed,
        max_attempts=8, mesh=mesh, packing=packing, tenancy=tenancy,
        deltacache=delta, backend=backend,
        delta_index_k=index_k, stratum_bits=stratum,
        delta_index_dirty_cap=index_dirty_cap,
    )
    c.bootstrap()
    return c


def _drive_steady(delta, *, backend="xla", index_k=0, stratum=0,
                  index_dirty_cap=None):
    """Template waves at low churn: the cache's home regime."""
    with MemStore() as store:
        for i in range(250):
            put_node(store, f"n{i}", zone=f"z{i % 4}")
        c = _coord(store, delta=delta, backend=backend, index_k=index_k,
                   stratum=stratum, index_dirty_cap=index_dirty_cap)
        for wave in range(6):
            for i in range(24):
                put_pod(store, f"w{wave}-{i}")
            for j in range(2):      # trickle of capacity churn
                put_node(store, f"n{(13 * wave + j) % 250}",
                         zone=f"z{(13 * wave + j) % 4}",
                         cpu=4000 + 100 * wave)
            c.step()
        c.run_until_idle()
        snap = _snapshot(c, store)
        c.close()
        return snap


def test_delta_coordinator_byte_identical_steady_state():
    base = _delta_waves()
    snap_d = _drive_steady(True)
    assert _delta_waves() > base          # the cache actually engaged
    snap_f = _drive_steady(False)
    assert _delta_waves() == _delta_waves()  # full run never goes delta
    _assert_identical(snap_d, snap_f)


def _drive_remove_readd(delta, *, index_k=0, stratum=0):
    """Epoch edge 1: remove + re-add the SAME node name while the shape
    is plane-cached — the tombstoned row and the fresh row both ride
    the journaled dirty slice; a delta wave must neither bind the dead
    row nor miss the new one."""
    with MemStore() as store:
        for i in range(64):
            put_node(store, f"n{i}")
        put_node(store, "target", labels={"disk": "ssd"})
        c = _coord(store, delta=delta, index_k=index_k, stratum=stratum)
        for wave in range(2):             # promote + fill the shape
            for i in range(4):
                put_pod(store, f"sel{wave}-{i}",
                        node_selector={"disk": "ssd"})
            c.step()
        c.run_until_idle()
        store.delete(node_key("target"))
        put_node(store, "target", labels={"disk": "ssd"})
        c._drain_node_events()
        for i in range(4):                # cached-shape wave, post-churn
            put_pod(store, f"post-{i}", node_selector={"disk": "ssd"})
        c.step()
        c.run_until_idle()
        names = {
            json.loads(v)["spec"].get("nodeName")
            for k, v in _snapshot(c, store)[0].items()
            if k.decode().rsplit("/", 1)[-1].startswith("post-")
        }
        assert names == {"target"}        # bound onto the re-added row
        snap = _snapshot(c, store)
        c.close()
        return snap


def test_epoch_remove_readd_same_name_differential():
    _assert_identical(_drive_remove_readd(True), _drive_remove_readd(False))


def _drive_midflight_add(delta):
    """Epoch edge 2: a structural add lands between a shape's cache
    fill and its next hit, while a wave is still in flight — the fresh
    row is journaled at its scatter dispatch, so the delta wave
    recomputes it and can bind onto the brand-new node."""
    with MemStore() as store:
        for i in range(8):
            put_node(store, f"n{i}", cpu=4000)
        c = _coord(store, delta=delta)
        # Each 3000m pod fills a node: after two 4-pod waves of the one
        # template shape (promote at wave 0, plane-fill at wave 1) every
        # existing node is exhausted for that shape.
        for wave in range(2):
            for i in range(4):
                put_pod(store, f"w{wave}-{i}", cpu=3000)
            c.step()                      # waves stay in flight (depth 3)
        # The add lands while those waves are unretired, before the
        # shape's next hit — the ONLY row the post wave can bind is the
        # one the cached plane has never seen.
        put_node(store, "fresh", cpu=1 << 20)
        for i in range(4):
            put_pod(store, f"post-{i}", cpu=3000)
        c.step()
        c.run_until_idle()
        pods = _snapshot(c, store)[0]
        fresh_binds = sum(
            1 for k, v in pods.items()
            if k.decode().rsplit("/", 1)[-1].startswith("post-")
            and json.loads(v)["spec"].get("nodeName") == "fresh"
        )
        snap = _snapshot(c, store)
        c.close()
        return snap, fresh_binds


def test_epoch_midflight_structural_add_differential():
    snap_d, fresh_d = _drive_midflight_add(True)
    snap_f, fresh_f = _drive_midflight_add(False)
    _assert_identical(snap_d, snap_f)
    # The fresh row was recomputed into the cached planes: all four
    # post pods bound, and only the new node could hold them.
    assert fresh_d == fresh_f == 4


SPEC_SM = TableSpec(max_nodes=128, max_zones=16, max_regions=8)


def _drive_overflow(delta, mesh=None):
    """Epoch edges 3+4: a mid-run PackingOverflow rebuild (and, on the
    mesh, the donated sharded planes it retires) must drop the cache
    wholesale — the re-upload resets device request columns to host
    truth, a state no journaled row set describes."""
    with MemStore() as store:
        for i in range(8):
            put_node(store, f"n{i}")
        c = _coord(store, delta=delta, mesh=mesh, packing="packed",
                   spec=SPEC_SM, chunk=32, depth=2, seed=1)
        assert is_packed(c.table)
        tight = dataclasses.replace(
            build_packing_spec(SPEC_SM, c.host.vocab),
            val_bits=max(len(c.host.vocab.label_values).bit_length(), 2),
        )
        c._packing_spec = tight
        c.table = pack_table_host(c.host, tight, c._table_sharding)
        while len(c.host.vocab.label_values) < (1 << tight.val_bits):
            c.host.vocab.label_values.intern(
                f"pad-{len(c.host.vocab.label_values)}"
            )
        for wave in range(2):             # promote + fill the pod shape
            put_pod(store, f"warm-{wave}")
            c.step()
        c.run_until_idle()
        if delta:
            assert c._delta.resident > 0
        # One more interned label value overflows the tightened layout
        # mid-flight; the rebuild must drop every cached plane.
        put_pod(store, "inflight")
        c.step()
        put_node(store, "n0", labels={"drift": "novel-value"})
        put_pod(store, "p0")
        c.run_until_idle()
        if delta:
            assert c._delta.resident == 0  # dropped, not patched
        assert is_packed(c.table) and not c.table.spec.fuse_labels
        # The cache re-engages against the rebuilt table, still exact.
        for wave in range(3):
            put_pod(store, f"tail-{wave}")
            c.step()
        c.run_until_idle()
        snap = _snapshot(c, store)
        c.close()
        return snap


def test_epoch_packing_overflow_rebuild_drops_cache_differential():
    _assert_identical(_drive_overflow(True), _drive_overflow(False))


def test_epoch_mesh_rebuild_retires_donated_planes_differential():
    snap_m = _drive_overflow(True, mesh=make_mesh(dp=2, sp=4))
    snap_s = _drive_overflow(False)
    _assert_identical(snap_m, snap_s)


def test_vocab_generation_movement_drops_cache():
    """A novel label VALUE interning moves Vocab.generation — cached
    planes bake interned selector ids, so the whole cache drops."""
    with MemStore() as store:
        for i in range(64):
            put_node(store, f"n{i}")
        c = _coord(store, delta=True)
        for wave in range(2):
            for i in range(4):
                put_pod(store, f"w{wave}-{i}")
            c.step()
        c.run_until_idle()
        assert c._delta.resident > 0
        put_node(store, "n1", labels={"brand": "new-value"})  # interns
        c._drain_node_events()
        for i in range(4):
            put_pod(store, f"post-{i}")
        c.step()
        c.run_until_idle()
        # check_generation dropped the old planes before planning.
        assert c._delta._gen == c.host.vocab.generation()
        assert all(
            json.loads(v)["spec"].get("nodeName")
            for v in _snapshot(c, store)[0].values()
        )
        c.close()


# ---- 5. the composed tier-1 gate at 4096 nodes ------------------------

SPEC_4K = TableSpec(max_nodes=4096, max_zones=16, max_regions=8)
PODS_4K = PodSpec(batch=64)
CHUNK_4K = 512


def _drive_composed_4k(delta, mesh, packing):
    """The ISSUE 12 acceptance drill: capacity churn + structural adds
    at pipeline depth 3, priority preemption, all-or-none gangs —
    on the packed × sharded × donated path for the delta run, against
    the plain single-device full-recompute run.  Same seed everywhere.
    """
    with MemStore() as store:
        for i in range(4090):
            put_node(store, f"n{i}", zone=f"z{i % 4}")
        # A 2-node selector-fenced pool with tiny pod capacity: the
        # preemption arena (high-priority pods can only go here).
        put_node(store, "hot-a", labels={"pool": "hot"}, pods=2)
        put_node(store, "hot-b", labels={"pool": "hot"}, pods=2)
        tn = TenancyController(TenancyPolicy(log_preemptions=True))
        c = _coord(store, delta=delta, mesh=mesh, packing=packing,
                   tenancy=tn, spec=SPEC_4K, pods=PODS_4K,
                   chunk=CHUNK_4K, depth=3, seed=7)
        # Saturate the hot pool with low-priority selector pods.
        for i in range(4):
            put_pod(store, f"low-{i}", ns="ten-b",
                    node_selector={"pool": "hot"})
        c.run_until_idle()
        for wave in range(5):
            for i in range(48):           # the hot template shape
                put_pod(store, f"w{wave}-{i}")
            for j in range(4):            # capacity churn on held rows
                put_node(store, f"n{(17 * wave + j) % 4090}",
                         zone=f"z{(17 * wave + j) % 4}",
                         cpu=4000 + 100 * wave)
            if wave == 1:                 # an all-or-none gang
                for j in range(3):
                    put_pod(store, f"g-{j}", ns="ten-a", labels={
                        "k8s1m.io/gang": "g3",
                        "k8s1m.io/gang-size": "3",
                    })
            if wave == 2:                 # structural mid-flight adds
                put_node(store, "fresh-a")
                put_node(store, "fresh-b")
            if wave == 3:                 # preemptors: hot pool is full
                for j in range(2):
                    put_pod(store, f"hi-{j}", ns="ten-a", priority=5,
                            node_selector={"pool": "hot"})
            c.step()
        c.run_until_idle()
        snap = _snapshot(c, store)
        c.close()
        return snap


def test_delta_composed_4096_differential_gate():
    ev = REGISTRY.get("preemption_evictions_total")
    gangs = REGISTRY.get("gang_admit_total")
    waves_base, ev_base = _delta_waves(), ev.value()
    gang_base = gangs.value(outcome="bound")
    snap_d = _drive_composed_4k(True, make_mesh(dp=2, sp=4), "packed")
    # The drill composed everything it claims to compose:
    assert _delta_waves() > waves_base    # delta waves engaged
    assert ev.value() >= ev_base + 2      # preemption evicted in-drill
    assert gangs.value(outcome="bound") == gang_base + 1
    snap_f = _drive_composed_4k(False, None, None)
    _assert_identical(snap_d, snap_f)
    # Every template pod, the gang, and both preemptors landed; the two
    # evicted victims cannot rebind (the hot pool refilled) and park.
    pods, host, _ = snap_d
    assert host["pods_req"].sum() == (4 - 2) + 5 * 48 + 3 + 2


def test_delta_composed_4096_single_device_differential():
    """The same composed drill, delta on WITHOUT the mesh/packing —
    isolates the plane cache itself from the meshpack composition."""
    snap_d = _drive_composed_4k(True, None, None)
    snap_f = _drive_composed_4k(False, None, None)
    _assert_identical(snap_d, snap_f)


# ---- 6. the score-stratified candidate index (ISSUE 18) ----------------
#
# Same differential discipline as the plane cache above: the index is an
# invisible replay of plane_topk, never a semantic — so the gates are
# (a) the class_key algebra the fail-closed floor rests on, (b) unit
# byte-identity of index_topk vs plane_topk at every width edge the
# floor can sit on, (c) coordinator differentials with the index ON, and
# (d) every fail-closed path counted in deltasched_index_*.


def test_stratum_hash_bounds_and_jitter_identity():
    cols = jnp.arange(32, dtype=jnp.int32)
    for bad in (0, JITTER_BITS + 1, -3):
        with pytest.raises(ValueError):
            stratum_hash(cols, bad)
    h = np.asarray(stratum_hash(cols, 12))
    assert ((0 <= h) & (h < (1 << 12))).all()
    # stratum_bits=0 is bit-identical to the historical draw.
    seed = jnp.int32(77)
    rows = jnp.arange(8, dtype=jnp.int32)[:, None]
    base = hash_jitter(seed, rows, cols[None, :])
    np.testing.assert_array_equal(
        np.asarray(base), np.asarray(hash_jitter(seed, rows, cols[None, :], 0))
    )
    # Stratified draw: top bits from the column hash, low bits shared
    # with the base draw.
    hb = 6
    strat = np.asarray(hash_jitter(seed, rows, cols[None, :], hb))
    low = JITTER_BITS - hb
    np.testing.assert_array_equal(
        strat >> low,
        np.broadcast_to(np.asarray(stratum_hash(cols, hb)), strat.shape),
    )
    np.testing.assert_array_equal(
        strat & ((1 << low) - 1), np.asarray(base) & ((1 << low) - 1)
    )


def test_class_key_decomposes_packed_priority():
    """The whole floor invariant: prio == (class << low) | low jitter
    bits, for every (seed, pod row) — so strictly-greater class
    dominates regardless of the wave."""
    rng = np.random.default_rng(3)
    scores = jnp.asarray(rng.integers(0, 2048, (4, 64)), jnp.int32)
    mask = jnp.asarray(rng.random((4, 64)) < 0.8)
    rows = jnp.arange(4, dtype=jnp.int32)[:, None]
    cols = jnp.arange(64, dtype=jnp.int32)[None, :]
    for hb in (0, 1, 8, JITTER_BITS):
        low = JITTER_BITS - hb
        for seed in (0, 9, -123456):
            s = jnp.int32(seed)
            prio = np.asarray(pack_hashed(scores, s, mask, rows, cols, hb))
            cls = np.asarray(class_key(scores, cols, hb))
            j = np.asarray(hash_jitter(s, rows, cols))
            expect = (cls.astype(np.int64) << low) | (j & ((1 << low) - 1))
            np.testing.assert_array_equal(
                prio, np.where(np.asarray(mask), expect, -1)
            )


def _build_index(scores, mask, k_idx, hb, chunk=None):
    """Planes from per-slot score/feasibility rows, index rebuilt from
    the planes for every slot (the plane-tail rebuild path)."""
    pscore = jnp.asarray(scores, jnp.int32)
    pmask = jnp.asarray(mask, jnp.bool_)
    s, n = pscore.shape
    ir, ic, fl = rebuild_index(
        pmask, pscore, jnp.arange(s, dtype=jnp.int32),
        jnp.zeros((s,), jnp.int32),
        jnp.full((s, k_idx), n, jnp.int32),
        jnp.full((s, k_idx), -1, jnp.int32),
        jnp.full((s,), INDEX_FLOOR_UNBUILT, jnp.int32),
        chunk=chunk or n, stratum_bits=hb, batch_b=1,
    )
    return pmask, pscore, ir, ic, fl


def _assert_floor_invariant(pmask, pscore, ir, ic, fl, hb):
    """Every feasible row NOT in a slot's index has class <= floor."""
    pm, ps = np.asarray(pmask), np.asarray(pscore)
    rows, floor = np.asarray(ir), np.asarray(fl)
    s, n = pm.shape
    cls = np.asarray(class_key(
        jnp.asarray(ps), jnp.arange(n, dtype=jnp.int32)[None, :], hb
    ))
    for si in range(s):
        held = {int(r) for r in rows[si] if r < n}
        out = [c for c in range(n) if pm[si, c] and c not in held]
        assert all(cls[si, c] <= floor[si] for c in out), si
        # Storage order is ascending-row (the earlier-row-wins tie rule).
        live = [int(r) for r in rows[si] if r < n]
        assert live == sorted(live), si


def _assert_index_matches_plane(pmask, pscore, ir, ic, slot_ids, hb, k=4):
    """Bit-identity for REAL slots.  Padding pods (slot sentinel) are
    excluded: plane_topk's jnp.take fills out-of-range slots while the
    index clips — both are don't-cares (padding pods are valid-masked
    out of finalize), so bind byte-identity never sees them."""
    n = pmask.shape[1]
    sl = jnp.asarray(slot_ids, jnp.int32)
    assert (np.asarray(sl) < pmask.shape[0]).all()
    for seed in (0, 1, 12345, -7):
        s = jnp.int32(seed)
        cand_i = index_topk(ir, ic, sl, s, k=k, stratum_bits=hb)
        cand_p = plane_topk(pmask, pscore, sl, s, chunk=n, k=k,
                            stratum_bits=hb)
        np.testing.assert_array_equal(
            np.asarray(cand_i.idx), np.asarray(cand_p.idx)
        )
        np.testing.assert_array_equal(
            np.asarray(cand_i.prio), np.asarray(cand_p.prio)
        )


def test_index_equal_scores_straddling_floor_fail_closed():
    """A homogeneous score tier wider than K: unstratified, the floor
    equals the kept entries' class — zero strictly above, unusable
    (this is exactly why a strict score index dies on a uniform
    cluster).  Stratified, the same plane splits into distinct classes
    and the index engages, byte-identical to the full scan."""
    n, k_idx = 64, 8
    scores = np.full((1, n), 7, np.int32)
    mask = np.ones((1, n), bool)
    pm, ps, ir, ic, fl = _build_index(scores, mask, k_idx, 0)
    assert int(np.asarray(fl)[0]) == 7          # floor AT the kept class
    assert not bool(index_usable(ic, fl, jnp.zeros(2, jnp.int32), 4))
    _assert_floor_invariant(pm, ps, ir, ic, fl, 0)
    hb = 12
    pm, ps, ir, ic, fl = _build_index(scores, mask, k_idx, hb)
    assert bool(index_usable(ic, fl, jnp.zeros(2, jnp.int32), 4))
    _assert_floor_invariant(pm, ps, ir, ic, fl, hb)
    _assert_index_matches_plane(pm, ps, ir, ic, [0, 0, 0], hb)


def test_index_k_exactly_full_is_exhaustive():
    """Exactly K feasible rows (and fewer): the spill entry is
    infeasible, the floor stays -1, and the index IS the feasible set —
    usable even with tied scores, padding (-1) included."""
    n, k_idx = 32, 4
    scores = np.zeros((2, n), np.int32)
    mask = np.zeros((2, n), bool)
    mask[0, [3, 9, 17, 30]] = True              # K exactly full, all tied
    mask[1, [5, 6]] = True                      # fewer than k feasible
    scores[0], scores[1] = 7, 9
    pm, ps, ir, ic, fl = _build_index(scores, mask, k_idx, 0)
    np.testing.assert_array_equal(np.asarray(fl), [-1, -1])
    assert bool(index_usable(ic, fl, jnp.asarray([0, 1, 2], jnp.int32), 4))
    _assert_floor_invariant(pm, ps, ir, ic, fl, 0)
    _assert_index_matches_plane(pm, ps, ir, ic, [0, 1, 1, 0], 0)


def test_index_dirty_row_evicts_floor_candidate():
    """A dirty row re-scores above everything: it inserts, the old K-th
    entry evicts, the floor rises to the evicted class — and the index
    stays byte-identical to a plane scan of the merged planes."""
    n, k_idx = 16, 4
    scores = np.arange(n, dtype=np.int32)[None, :].copy()
    mask = np.ones((1, n), bool)
    pm, ps, ir, ic, fl = _build_index(scores, mask, k_idx, 0)
    assert int(np.asarray(fl)[0]) == n - k_idx - 1   # best discarded
    # Row 0 jumps to score 100; rows 2,3 go infeasible (both below the
    # floor — invalidation only, no index change beyond their absence).
    rows = dedup_rows(jnp.asarray([0, 2, 3, n], jnp.int32), n)
    mask_d = jnp.asarray([[True, False, False, False]])
    score_d = jnp.asarray([[100, 0, 0, 0]], jnp.int32)
    ir2, ic2, fl2 = update_index(
        ir, ic, fl, jnp.zeros(1, jnp.int32), rows, mask_d, score_d, n,
        stratum_bits=0,
    )
    held = sorted(int(r) for r in np.asarray(ir2)[0] if r < n)
    assert 0 in held                        # inserted
    assert n - k_idx not in held            # the old floor candidate evicted
    assert int(np.asarray(fl2)[0]) == n - k_idx      # floor rose to it
    assert bool(index_usable(ic2, fl2, jnp.zeros(1, jnp.int32), 4))
    # Merge the same dirty columns into the planes and cross-check.
    ps2 = ps.at[0, jnp.asarray([0, 2, 3])].set(jnp.asarray([100, 0, 0]))
    pm2 = pm.at[0, jnp.asarray([2, 3])].set(False)
    _assert_floor_invariant(pm2, ps2, ir2, ic2, fl2, 0)
    _assert_index_matches_plane(pm2, ps2, ir2, ic2, [0, 0], 0)


def test_index_shrinks_below_k_fails_closed():
    """Dirty rows going infeasible INSIDE the index shrink the
    strictly-above count below k: the wave must fail closed (the floor
    cannot lower without a rebuild)."""
    n, k_idx = 16, 4
    scores = np.arange(n, dtype=np.int32)[None, :].copy()
    mask = np.ones((1, n), bool)
    pm, ps, ir, ic, fl = _build_index(scores, mask, k_idx, 0)
    rows = dedup_rows(jnp.asarray([n - 1, n - 2], jnp.int32), n)
    ir2, ic2, fl2 = update_index(
        ir, ic, fl, jnp.zeros(1, jnp.int32), rows,
        jnp.asarray([[False, False]]), jnp.zeros((1, 2), jnp.int32), n,
        stratum_bits=0,
    )
    assert not bool(index_usable(ic2, fl2, jnp.zeros(1, jnp.int32), 4))
    # The padding slot alone never blocks.
    assert bool(index_usable(ic2, fl2, jnp.full(3, 1, jnp.int32), 4))


def test_index_dedup_rows_first_occurrence():
    rows = jnp.asarray([5, 3, 5, 7, 3, 16], jnp.int32)
    out = np.asarray(dedup_rows(rows, 16))
    np.testing.assert_array_equal(out, [5, 3, 16, 7, 16, 16])


def test_index_update_untouched_slots_stay():
    """Slots without a representative this wave keep rows, classes and
    floor byte-identical — their planes weren't merged either."""
    n, k_idx = 16, 4
    scores = np.stack([np.arange(n), np.arange(n)[::-1]]).astype(np.int32)
    pm, ps, ir, ic, fl = _build_index(scores, np.ones((2, n), bool), k_idx, 0)
    rows = dedup_rows(jnp.asarray([0, n], jnp.int32), n)
    # Batch of one: slot 0's representative is position 0, slot 1 gets
    # the out-of-bounds sentinel (= batch size) — unused this wave.
    rep = jnp.asarray([0, 1], jnp.int32)
    ir2, ic2, fl2 = update_index(
        ir, ic, fl, rep, rows, jnp.asarray([[True, False]]),
        jnp.asarray([[50, 0]], jnp.int32), n, stratum_bits=0,
    )
    np.testing.assert_array_equal(np.asarray(ir2)[1], np.asarray(ir)[1])
    np.testing.assert_array_equal(np.asarray(ic2)[1], np.asarray(ic)[1])
    assert int(np.asarray(fl2)[1]) == int(np.asarray(fl)[1])
    assert 0 in set(int(r) for r in np.asarray(ir2)[0])  # slot 0 updated


def test_index_randomized_update_differential():
    """Property form of the edges above: random planes, random dirty
    batches folded through update_index — whenever the index says
    usable, its candidates are bit-identical to the plane scan; the
    floor invariant holds throughout."""
    rng = np.random.default_rng(18)
    n, k_idx, s = 64, 8, 3
    for hb in (0, 10):
        scores = rng.integers(0, 6, (s, n)).astype(np.int32)
        mask = rng.random((s, n)) < 0.7
        pm, ps, ir, ic, fl = _build_index(scores, mask, k_idx, hb, chunk=16)
        _assert_floor_invariant(pm, ps, ir, ic, fl, hb)
        for step in range(6):
            d = 8
            drows = rng.choice(n, size=d, replace=False).astype(np.int32)
            dm = rng.random((s, d)) < 0.7
            dsc = rng.integers(0, 6, (s, d)).astype(np.int32)
            rows = dedup_rows(jnp.asarray(drows), n)
            ir, ic, fl = update_index(
                ir, ic, fl, jnp.arange(s, dtype=jnp.int32), rows,
                jnp.asarray(dm), jnp.asarray(dsc), n, stratum_bits=hb,
            )
            pm = pm.at[:, drows].set(jnp.asarray(dm))
            ps = ps.at[:, drows].set(jnp.asarray(dsc))
            _assert_floor_invariant(pm, ps, ir, ic, fl, hb)
            slot_ids = rng.integers(0, s, 8).astype(np.int32)
            if bool(index_usable(ic, fl, jnp.asarray(slot_ids), 4)):
                _assert_index_matches_plane(pm, ps, ir, ic, slot_ids, hb)


# -- coordinator differentials with the index on ------------------------


def _index_waves(path):
    return REGISTRY.get("deltasched_index_waves_total").value(path=path)


def _index_drops(reason):
    return REGISTRY.get("deltasched_index_drops_total").value(reason=reason)


def test_index_coordinator_byte_identical_and_engages():
    """The composed gate: index-enabled delta coordinator == full
    recompute at the same stratum_bits, byte for byte, with the index
    path actually taken (not silently failing closed every wave)."""
    base = _index_waves("index")
    snap_i = _drive_steady(True, index_k=32, stratum=12)
    assert _index_waves("index") > base
    snap_f = _drive_steady(False, stratum=12)
    _assert_identical(snap_i, snap_f)


def test_index_remove_readd_same_name_differential():
    _assert_identical(
        _drive_remove_readd(True, index_k=32, stratum=12),
        _drive_remove_readd(False, stratum=12),
    )


def test_index_unstratified_underflow_counted():
    """stratum_bits=0 on a homogeneous cluster: every attempted index
    wave underflows the floor and falls to the plane tail — counted,
    and still byte-identical (the fail-closed differential)."""
    under = _index_drops("underflow")
    waves = _index_waves("index")
    snap_i = _drive_steady(True, index_k=32, stratum=0)
    assert _index_drops("underflow") > under
    assert _index_waves("index") == waves       # never engaged
    _assert_identical(snap_i, _drive_steady(False))


def test_index_oversized_dirty_counted():
    """A dirty cap below the pipeline's in-flight row width: every
    delta wave compiles the plane-only variant — counted as
    oversized-dirty, byte-identity untouched."""
    over = _index_drops("oversized-dirty")
    snap_i = _drive_steady(True, index_k=32, stratum=12, index_dirty_cap=1)
    assert _index_drops("oversized-dirty") > over
    _assert_identical(snap_i, _drive_steady(False, stratum=12))


def test_index_fill_and_drop_reasons_counted():
    """The host-side fail-closed stamps: a fresh fill floors the slot
    unbuilt (reason=fill); vocab-generation movement and wholesale
    drops count under their reason labels."""
    cache = DeltaPlaneCache(64, slots=4, index_k=8)
    k = ("shape-a", 20, 1024)
    cache.plan([k], 8)
    fills = _index_drops("fill")
    p = cache.plan([k], 8)
    cache.note_fill(p)
    assert _index_drops("fill") == fills + 1
    assert int(np.asarray(cache._idx_floor)[p.fill_slots[0]]) \
        == INDEX_FLOOR_UNBUILT
    # plan() of an index cache carries the rep/rebuild plumbing.
    p2 = cache.plan([k], 8)
    assert p2.rep_idx is not None and p2.rebuild_slots is not None
    assert p2.rep_idx[p2.slot_ids[0]] == 0
    gen = _index_drops("generation")
    cache.check_generation(99)
    assert _index_drops("generation") == gen + 1


def test_index_construction_guards():
    with pytest.raises(ValueError, match="mesh"):
        DeltaPlaneCache(64, slots=2, index_k=8, sharding=object())
    with pytest.raises(ValueError, match="index_k"):
        DeltaPlaneCache(64, slots=2, index_k=-1)
    with MemStore() as store:
        put_node(store, "n0")
        with pytest.raises(ValueError, match="deltacache"):
            Coordinator(
                store, SPEC, PODS, PROFILE, chunk=64, k=4,
                with_constraints=False, delta_index_k=8,
            )
        with pytest.raises(ValueError, match="stratum_bits"):
            Coordinator(
                store, SPEC, PODS, PROFILE, chunk=64, k=4,
                with_constraints=False, stratum_bits=21,
            )
        with pytest.raises(ValueError, match="mesh"):
            Coordinator(
                store, SPEC, PODS, PROFILE, chunk=64, k=4,
                with_constraints=False, deltacache=True,
                delta_index_k=8, mesh=make_mesh(dp=2, sp=4),
            )


def test_deltacache_pallas_byte_identical():
    """PR 12's loud failure is gone: deltacache + pallas constructs,
    runs the fused delta tail (delta_plane_topk) on delta waves, and
    stays byte-identical to the XLA full-recompute coordinator."""
    base = _delta_waves()
    snap_p = _drive_steady(True, backend="pallas")
    assert _delta_waves() > base            # delta waves on pallas
    _assert_identical(snap_p, _drive_steady(False))


# ---- satellite: the bounded binding cache (the label-less shape) ------


def test_shape_bindings_bounded():
    with MemStore() as store:
        put_node(store, "n0")
        c = Coordinator(
            store, TableSpec(max_nodes=16, max_zones=4, max_regions=2),
            PodSpec(batch=8), PROFILE, chunk=16, k=2,
        )
        c.bootstrap()
        try:
            for i in range(1100):
                c._bound_shape(None, f"ns-{i}")
            # The cap clears the dict rather than let dead generations
            # pile up across long soaks.
            assert len(c._shape_bindings) <= 1024
            # Still correct after the clear: nothing registered, so a
            # label-less pod carries nothing but its scalars.
            assert c._bound_shape(None, "ns-0") is None
            assert c._bound_shape(None, "ns-0") is None
        finally:
            c.close()
