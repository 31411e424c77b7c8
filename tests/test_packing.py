"""Packed device snapshot + buffer donation (the devicestate PR).

Layers of evidence:

1. **Roundtrip property**: encode→device→decode is the identity for
   every column dtype/width at the bit-budget edges, including the
   label-word fusion and its fail-closed split on vocab overflow.
2. **Engine differential**: schedule_batch_packed over the packed
   layout is byte-identical to the unpacked layout on BOTH backends
   (XLA scan and the fused pallas kernel), across full scans, rotating
   pct windows, row masks, affinity selectors, and constraint state.
3. **Coordinator differential at 4096 nodes under churn** (the tier-1
   acceptance gate, same bar as the PR 6 mesh gate): a packed pipelined
   coordinator run under capacity churn + a structural add produces
   byte-identical stored pod objects, host mirror, and device request
   totals vs the unpacked run.
4. **Fail-closed drift**: a vocab outgrowing the fused-label bit budget
   triggers a counted layout rebuild (split words), never a truncated
   id.  (The packed x mesh composition — once a fallback — is the
   production path since meshpack; its gates live in
   tests/test_meshpack.py.)
5. **Donation**: the donating executable returns identical binds and
   consumes its input buffers (the coordinator's in-place commit path).
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s1m_tpu.cluster import populate_kwok_nodes, uniform_pods
from k8s1m_tpu.config import PodSpec, TableSpec
from k8s1m_tpu.control.coordinator import Coordinator
from k8s1m_tpu.control.objects import encode_node, encode_pod, node_key, pod_key
from k8s1m_tpu.engine.cycle import schedule_batch_packed
from k8s1m_tpu.obs.metrics import REGISTRY
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot import NodeTableHost, PodBatchHost
from k8s1m_tpu.snapshot.node_table import ALL_COLUMNS, NodeInfo, Taint
from k8s1m_tpu.snapshot.packing import (
    COLD_COLUMNS,
    PackingOverflow,
    build_packing_spec,
    bytes_report,
    cold_bytes_per_node,
    is_packed,
    pack_columns_np,
    pack_row_delta,
    pack_table_host,
    resolve_packing,
    unpack_chunk,
    unpacked_cold_bytes,
)
from k8s1m_tpu.snapshot.pod_encoding import PodInfo
from k8s1m_tpu.store.native import MemStore, prefix_end

PROFILE = Profile(node_affinity=0, topology_spread=0, interpod_affinity=0)

TABLE_FIELDS = (
    "valid", "cpu_alloc", "mem_alloc", "pods_alloc",
    "cpu_req", "mem_req", "pods_req",
    "label_key", "label_val", "label_num",
    "taint_id", "taint_effect", "zone", "region", "name_id",
)


def assert_tables_equal(decoded, plain):
    for f in TABLE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(decoded, f)), np.asarray(getattr(plain, f)),
            err_msg=f,
        )


# ---- 1. roundtrip property --------------------------------------------


def _edge_host(spec: TableSpec, pspec, rng) -> NodeTableHost:
    """A host mirror whose columns sit at the packed widths' EDGES."""
    host = NodeTableHost(spec)
    n, l, t = spec.max_nodes, spec.label_slots, spec.taint_slots
    host.valid[:] = rng.integers(0, 2, n).astype(bool)
    host.cpu_alloc[:] = rng.integers(0, 1 << 30, n)
    host.mem_alloc[:] = rng.integers(0, 1 << 30, n)
    host.pods_alloc[:] = rng.integers(0, (1 << 15) - 1, n)   # int16 edge
    host.cpu_req[:] = rng.integers(0, 1 << 20, n)
    host.mem_req[:] = rng.integers(0, 1 << 20, n)
    host.pods_req[:] = rng.integers(0, 1 << 10, n)
    host.label_key[:] = rng.integers(0, 1 << pspec.key_bits, (n, l))
    host.label_val[:] = rng.integers(0, 1 << pspec.val_bits, (n, l))
    # label_num stays full-range i32 (incl. the NO_NUMERIC sentinel).
    host.label_num[:] = rng.integers(-(1 << 31), (1 << 31) - 1, (n, l))
    host.taint_id[:] = rng.integers(0, spec.max_taint_ids, (n, t))
    host.taint_effect[:] = rng.integers(0, 4, (n, t))        # 2-bit edge
    host.zone[:] = rng.integers(0, spec.max_zones, n)
    host.region[:] = rng.integers(0, spec.max_regions, n)
    host.name_id[:] = rng.integers(0, 1 << 20, n)
    return host


def test_roundtrip_every_column_at_width_edges(rng):
    spec = TableSpec(max_nodes=256)
    pspec = build_packing_spec(spec)
    assert pspec.fuse_labels
    host = _edge_host(spec, pspec, rng)
    packed = pack_table_host(host, pspec)
    assert_tables_equal(unpack_chunk(packed), host.to_device())
    # Narrow dtypes actually landed narrow.
    assert packed.zone.dtype == jnp.int16
    assert packed.region.dtype == jnp.int8
    assert packed.pods_alloc.dtype == jnp.int16
    assert packed.taint_id.dtype == jnp.int16
    assert packed.label_val.shape == (256, 0)     # fused: no value plane


@pytest.mark.parametrize("layout", ["plain", "packed"])
def test_the_device_table_does_not_read_the_mirrors_request_columns(layout):
    """On the CPU backend ``jnp.asarray`` takes a 64-byte aligned numpy
    buffer zero-copy; a device table built from the mirror's own request
    columns would then learn of binds through the retire's in-place
    ``np.add.at`` and not through its own commit (seen as the
    benchmark's fault ``state_unchanged`` passing for sound).  Several
    mirrors, so that some are aligned whatever the allocator does."""
    spec = TableSpec(max_nodes=1024)
    for _ in range(8):
        host = NodeTableHost(spec)
        if layout == "packed":
            table = pack_table_host(host, build_packing_spec(spec, host.vocab))
        else:
            table = host.to_device()
        for name in ("cpu_req", "mem_req", "pods_req"):
            np.add.at(getattr(host, name), [3, 3, 7], 5)
            assert int(np.asarray(getattr(table, name)).sum()) == 0, name


def test_roundtrip_split_words_layout(rng):
    """The fail-closed fallback layout (fusion off) is also exact."""
    spec = TableSpec(max_nodes=128)
    pspec = dataclasses.replace(build_packing_spec(spec), fuse_labels=False)
    host = _edge_host(spec, pspec, rng)
    # Split words carry full i32 ids — push past the fused budget.
    host.label_val[:] = np.random.default_rng(1).integers(
        0, 1 << 30, host.label_val.shape
    )
    packed = pack_table_host(host, pspec)
    assert_tables_equal(unpack_chunk(packed), host.to_device())


def test_fusion_fails_closed_on_vocab_width():
    spec = TableSpec(max_nodes=64)

    class FakeVocab:
        label_keys = range(1 << 12)      # len() == 2**12: at the budget
        label_values = range(10)

    assert build_packing_spec(spec, FakeVocab()).fuse_labels is False
    # And taint_slots past the meta word disable packing entirely.
    assert build_packing_spec(TableSpec(max_nodes=64, taint_slots=16)) is None


def test_pack_overflow_raises_never_truncates():
    spec = TableSpec(max_nodes=8)
    pspec = build_packing_spec(spec)
    host = NodeTableHost(spec)
    host.pods_alloc[:] = 1 << 15                 # > int16
    with pytest.raises(PackingOverflow) as ei:
        pack_table_host(host, pspec)
    assert ei.value.field == "pods_alloc"
    host.pods_alloc[:] = 1
    host.label_val[:] = 1 << pspec.val_bits      # vocab drift shape
    with pytest.raises(PackingOverflow) as ei:
        pack_table_host(host, pspec)
    assert ei.value.field == "label_val"
    host.label_val[:] = 0
    host.taint_effect[:, 0] = 4                  # next EFFECT_* constant
    with pytest.raises(PackingOverflow) as ei:
        pack_table_host(host, pspec)
    assert ei.value.field == "taint_effect"


def test_row_delta_matches_full_pack(rng):
    spec = TableSpec(max_nodes=64)
    pspec = build_packing_spec(spec)
    host = _edge_host(spec, pspec, rng)
    rows = np.array([3, 17, 40], np.int32)
    delta = pack_row_delta(host, rows, pspec, ALL_COLUMNS)
    full = pack_columns_np(
        {f: getattr(host, f) for f in TABLE_FIELDS}, pspec
    )
    for name, arr in delta.items():
        np.testing.assert_array_equal(arr, full[name][rows], err_msg=name)


def test_bytes_accounting():
    spec = TableSpec(max_nodes=256)
    host = NodeTableHost(spec)
    populate_kwok_nodes(host, 256)
    plain = host.to_device()
    packed = pack_table_host(host, build_packing_spec(spec, host.vocab))
    assert cold_bytes_per_node(plain) == unpacked_cold_bytes(spec)
    rep = bytes_report(packed, spec)
    # The acceptance bar: >= 2x cold-column reduction under defaults.
    assert rep["cold_bytes_reduction"] >= 2.0
    assert rep["hbm_bytes_per_node"] < bytes_report(plain)["hbm_bytes_per_node"]
    assert set(COLD_COLUMNS) <= set(TABLE_FIELDS)
    assert resolve_packing("packed") == "packed"
    with pytest.raises(ValueError):
        resolve_packing("sideways")


# ---- 2. engine differential -------------------------------------------


def _tables(nodes=512, taints=False):
    spec = TableSpec(max_nodes=nodes)
    host = NodeTableHost(spec)
    populate_kwok_nodes(host, nodes)
    if taints:
        # A few tainted rows so the effect decode is live in the wave.
        for i in range(0, nodes, 7):
            host.upsert(NodeInfo(
                name=f"kwok-node-{i}", cpu_milli=32000, mem_kib=1 << 25,
                pods=110, taints=[Taint("dedicated", "batch", 2)],
            ))
    return spec, host


def _run(table, pb, key, backend, **kw):
    _t, _c, _asg, rows = schedule_batch_packed(
        table, pb, key, profile=kw.pop("profile", PROFILE),
        chunk=kw.pop("chunk", 128), k=4, backend=backend, **kw,
    )
    return np.asarray(rows), np.asarray(_t.pods_req)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_differential_full_window_mask(backend):
    spec, host = _tables(512, taints=True)
    pspec = build_packing_spec(spec, host.vocab)
    enc = PodBatchHost(PodSpec(batch=64), spec, host.vocab)
    pb = enc.encode_packed(uniform_pods(64))
    key = jax.random.key(3)
    plain = host.to_device()
    packed = pack_table_host(host, pspec)
    for kw in (
        {},
        {"sample_rows": 128, "sample_offset": 128},
        {"row_mask": jnp.asarray(np.arange(512) % 3 != 0)},
    ):
        r1, q1 = _run(plain, pb, key, backend, **kw)
        r2, q2 = _run(packed, pb, key, backend, **kw)
        np.testing.assert_array_equal(r1, r2, err_msg=str(kw))
        np.testing.assert_array_equal(q1, q2, err_msg=str(kw))
    assert (r1 >= 0).any()


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_differential_affinity(backend):
    """Selector waves: the fused-label in-kernel decode must reproduce
    the split-plane resolution bit for bit."""
    from k8s1m_tpu.cluster.workload import node_affinity_pods

    spec, host = _tables(512)
    pspec = build_packing_spec(spec, host.vocab)
    assert pspec.fuse_labels
    pod_spec = PodSpec(
        batch=64, aff_terms=1, aff_exprs=2, aff_values=2, pref_terms=1
    )
    enc = PodBatchHost(pod_spec, spec, host.vocab)
    pb = enc.encode_packed(node_affinity_pods(64))
    prof = Profile(topology_spread=0, interpod_affinity=0)
    key = jax.random.key(5)
    r1, q1 = _run(host.to_device(), pb, key, backend, profile=prof)
    r2, q2 = _run(pack_table_host(host, pspec), pb, key, backend, profile=prof)
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(q1, q2)
    assert (r1 >= 0).any()


def test_engine_differential_constraints():
    from k8s1m_tpu.cluster.workload import spread_deployment
    from k8s1m_tpu.snapshot.constraints import (
        ConstraintTracker,
        empty_constraints,
    )

    spec = TableSpec(max_nodes=256, max_zones=128, max_regions=16)
    host = NodeTableHost(spec)
    populate_kwok_nodes(host, 256)
    tracker = ConstraintTracker(spec)
    pods = spread_deployment(tracker, "pk-spread", 64, topo=1)
    pod_spec = PodSpec(batch=64, spread_refs=1, spread_incs=1, ipa_incs=1)
    enc = PodBatchHost(pod_spec, spec, host.vocab)
    pb = enc.encode_packed(pods)
    key = jax.random.key(7)
    prof = Profile()
    c0 = empty_constraints(spec)
    t1, cons1, _a1, r1 = schedule_batch_packed(
        host.to_device(), pb, key, profile=prof, constraints=c0,
        chunk=128, k=4,
    )
    t2, cons2, _a2, r2 = schedule_batch_packed(
        pack_table_host(host, build_packing_spec(spec, host.vocab)),
        pb, key, profile=prof, constraints=empty_constraints(spec),
        chunk=128, k=4,
    )
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(
        np.asarray(cons1.spread_zone), np.asarray(cons2.spread_zone)
    )
    assert (np.asarray(r1) >= 0).any()


# ---- 5. donation -------------------------------------------------------


def test_donating_step_identical_and_consumes_input():
    spec, host = _tables(256)
    pspec = build_packing_spec(spec, host.vocab)
    enc = PodBatchHost(PodSpec(batch=64), spec, host.vocab)
    pb = enc.encode_packed(uniform_pods(64))
    key = jax.random.key(11)
    r_plain, q_plain = _run(pack_table_host(host, pspec), pb, key, "xla")
    donated = pack_table_host(host, pspec)
    t, _c, _a, rows = schedule_batch_packed(
        donated, pb, key, profile=PROFILE, chunk=128, k=4, donate=True
    )
    np.testing.assert_array_equal(np.asarray(rows), r_plain)
    np.testing.assert_array_equal(np.asarray(t.pods_req), q_plain)
    # The donated input is DEAD: jax deletes the buffers.
    assert donated.cpu_req.is_deleted()


# ---- 3. the coordinator gate: 4096 nodes under churn -------------------

SPEC_4K = TableSpec(max_nodes=4096, max_zones=16, max_regions=8)
PODS_4K = PodSpec(batch=256)


def put_node(store, name, zone="z0", cpu=32000, **kw):
    labels = {"topology.kubernetes.io/zone": zone, **kw.pop("labels", {})}
    store.put(node_key(name), encode_node(NodeInfo(
        name=name, cpu_milli=cpu, mem_kib=1 << 25, pods=110,
        labels=labels, **kw,
    )))


def put_pod(store, name, cpu=20, **kw):
    store.put(pod_key("default", name), encode_pod(PodInfo(
        name=name, namespace="default", cpu_milli=cpu, mem_kib=200 << 10,
        **kw,
    )))


def _drive_4k(packing: str):
    """Deterministic pipelined run at 4096 nodes: pod waves arriving
    while capacity-only churn scatters into the live packed table and a
    structural add lands mid-flight.  Returns (stored pod bytes, host
    mirror, device request totals)."""
    with MemStore() as store:
        # One row short of max_nodes so the mid-flight structural add
        # ("fresh") lands on the last free row instead of exhausting.
        for i in range(4095):
            put_node(store, f"n{i}", zone=f"z{i % 4}")
        c = Coordinator(
            store, SPEC_4K, PODS_4K, PROFILE, chunk=1024, k=4,
            with_constraints=False, pipeline=True, depth=3, seed=9,
            max_attempts=8, packing=packing,
        )
        c.bootstrap()
        assert is_packed(c.table) == (packing == "packed")
        for wave in range(4):
            for i in range(192):
                put_pod(store, f"w{wave}-{i}")
            for j in range(16):       # heartbeat-shaped capacity churn
                put_node(store, f"n{(wave * 29 + j) % 4095}",
                         zone=f"z{(wave * 29 + j) % 4}",
                         cpu=32000 + 100 * wave)
            if wave == 2:
                put_node(store, "fresh")      # structural fresh row
            c.step()
        c.run_until_idle()
        res = store.range(b"/registry/pods/", prefix_end(b"/registry/pods/"))
        pods = {bytes(kv.key): bytes(kv.value) for kv in res.kvs}
        host = {
            "row_of": dict(c.host._row_of),
            "cpu_req": c.host.cpu_req.copy(),
            "pods_req": c.host.pods_req.copy(),
        }
        treq = np.asarray(c.table.pods_req).copy()
        bound = sum(c.host.pods_req)
        c.close()
        return pods, host, treq, bound


def test_coordinator_4096_churn_differential():
    """The tier-1 acceptance gate: packed == unpacked bind-for-bind,
    byte-identical stored pods, equal host mirror and device request
    totals, at 4096 nodes under churn with the pipeline held deep."""
    pods_p, host_p, treq_p, bound_p = _drive_4k("packed")
    pods_u, host_u, treq_u, bound_u = _drive_4k("off")
    assert bound_p == bound_u == 4 * 192
    assert pods_p == pods_u                      # byte-identical, nodeName incl.
    assert host_p["row_of"] == host_u["row_of"]
    np.testing.assert_array_equal(host_p["cpu_req"], host_u["cpu_req"])
    np.testing.assert_array_equal(host_p["pods_req"], host_u["pods_req"])
    np.testing.assert_array_equal(treq_p, treq_u)
    # Donation ran in place for the packed coordinator's waves.
    assert REGISTRY.get("commit_donation_total").value(inplace="yes") > 0


# ---- 4. fail-closed drift + composition gates --------------------------


def test_vocab_drift_rebuilds_split_words():
    """A label value interned past the fused bit budget mid-run: the
    dirty-row scatter fails closed, the layout rebuilds with split
    words (counted), and scheduling continues correctly."""
    base = REGISTRY.get("device_packing_fallback_total").value(
        reason="label_val"
    )
    spec = TableSpec(max_nodes=128, max_zones=16, max_regions=8)
    with MemStore() as store:
        for i in range(8):
            put_node(store, f"n{i}")
        c = Coordinator(
            store, spec, PodSpec(batch=32), PROFILE, chunk=64, k=4,
            with_constraints=False, packing="packed", seed=1,
        )
        c.bootstrap()
        # Shrink the live layout's value budget to the already-interned
        # width, then intern ONE more value: the next scatter overflows.
        tight = dataclasses.replace(
            build_packing_spec(spec, c.host.vocab),
            val_bits=max(len(c.host.vocab.label_values).bit_length(), 2),
        )
        c._packing_spec = tight
        c.table = pack_table_host(c.host, tight)
        while len(c.host.vocab.label_values) < (1 << tight.val_bits):
            c.host.vocab.label_values.intern(
                f"pad-{len(c.host.vocab.label_values)}"
            )
        put_node(store, "n0", labels={"drift": "novel-value"})
        put_pod(store, "p0")
        c.run_until_idle()
        assert REGISTRY.get("device_packing_fallback_total").value(
            reason="label_val"
        ) == base + 1
        # Rebuilt packed with split words — and the bind landed.
        assert is_packed(c.table) and not c.table.spec.fuse_labels
        kv = store.get(pod_key("default", "p0"))
        assert json.loads(kv.value)["spec"].get("nodeName")
        assert_tables_equal(unpack_chunk(c.table), c.host.to_device())
        c.close()


def test_double_overflow_retry_falls_back_unpacked():
    """A SECOND PackingOverflow during the post-label-split retry (a
    node past the int16 pods budget in the same rebuild window as label
    vocab drift) must also fail closed — rebuild unpacked, both
    widenings counted — never escape _table_to_device into the cycle
    loop."""
    fb = REGISTRY.get("device_packing_fallback_total")
    base_lv = fb.value(reason="label_val")
    base_pa = fb.value(reason="pods_alloc")
    spec = TableSpec(max_nodes=128, max_zones=16, max_regions=8)
    with MemStore() as store:
        for i in range(8):
            put_node(store, f"n{i}")
        c = Coordinator(
            store, spec, PodSpec(batch=32), PROFILE, chunk=64, k=4,
            with_constraints=False, packing="packed", seed=1,
        )
        c.bootstrap()
        assert is_packed(c.table) and c.table.spec.fuse_labels
        # Drift both budgets at once on the host mirror: a label value
        # id past the fused val budget AND a pods_alloc past int16.
        c.host.label_val[0, 0] = 1 << c._packing_spec.val_bits
        c.host.pods_alloc[0] = 1 << 15
        c.table = c._table_to_device()
        assert not is_packed(c.table)
        assert c._packing_mode == "off"
        assert fb.value(reason="label_val") == base_lv + 1
        assert fb.value(reason="pods_alloc") == base_pa + 1
        c.close()


# ---- bench-surface smokes ---------------------------------------------


def test_sched_bench_backend_auto_packed_smoke(tmp_path):
    """Satellites as one run: --backend auto resolves to xla on this CPU
    env (no silently-interpreted pallas numbers), --packing packed lands
    the device_state evidence (layout, >=2x cold reduction, donation
    in-place), and --kernel-profile emits the per-stage DCE breakdown."""
    from k8s1m_tpu.tools.sched_bench import main

    out = tmp_path / "bench.json"
    report = main([
        "--nodes", "256", "--pods", "512", "--batch", "128",
        "--depth", "2", "--packing", "packed", "--kernel-profile",
        "--out", str(out),
    ])
    d = report["detail"]
    assert d["backend"] == "xla"              # auto off-TPU
    ds = d["device_state"]
    assert ds["layout"] == "packed"
    assert ds["cold_bytes_reduction"] >= 2.0
    assert ds["donation_inplace"] is True
    kp = d["kernel_profile"]
    assert kp["ms_per_batch"]["full"] > 0
    assert kp["stages"]["filter_topk_floor"] > 0
    assert json.loads(out.read_text())["detail"]["device_state"]["layout"] == "packed"
