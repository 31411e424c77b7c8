"""Fused Pallas kernel vs the XLA path and a pure-numpy oracle.

Runs the kernel in interpreter mode on the CPU mesh (the wrapper
auto-selects).  The interpreter is exact where Mosaic need not be (an
f32 dot contracts in bf16 on the chip unless asked otherwise), so these
assertions do NOT carry over to hardware by themselves: phase B of
chip_smoke.py repeats the pallas-vs-XLA comparison compiled, at 1M
rows, on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from k8s1m_tpu.config import (
    EFFECT_NO_SCHEDULE,
    EFFECT_PREFER_NO_SCHEDULE,
    PodSpec,
    TableSpec,
)
from k8s1m_tpu.engine.cycle import filter_score_topk, schedule_batch
from k8s1m_tpu.ops.pallas_topk import (
    fused_topk,
    np_reference_topk,
    pallas_candidates,
    supports,
)
from k8s1m_tpu.ops.priority import unpack_score
from k8s1m_tpu.plugins.registry import Profile, score_and_filter
from k8s1m_tpu.snapshot.node_table import NodeInfo, NodeTableHost, Taint
from k8s1m_tpu.snapshot.pod_encoding import (
    NodeSelectorTerm,
    PodBatchHost,
    PodInfo,
    PreferredSchedulingTerm,
    SelectorRequirement,
    Toleration,
)

BASE = Profile(node_affinity=0, topology_spread=0, interpod_affinity=0)
N = 256
CHUNK = 128


def build(rng, num_nodes=N, with_taints=True):
    spec = TableSpec(max_nodes=num_nodes, max_taint_ids=16)
    host = NodeTableHost(spec)
    for i in range(num_nodes - 8):  # leave invalid tail rows
        taints = []
        if with_taints and i % 5 == 0:
            taints.append(Taint("dedicated", "infra", EFFECT_NO_SCHEDULE))
        if with_taints and i % 7 == 0:
            taints.append(
                Taint("flaky", "", EFFECT_PREFER_NO_SCHEDULE)
            )
        host.upsert(
            NodeInfo(
                f"node-{i}",
                cpu_milli=int(rng.integers(500, 8000)),
                mem_kib=int(rng.integers(1 << 20, 16 << 20)),
                pods=int(rng.integers(1, 16)),
                taints=taints,
            )
        )
    for i in range(0, num_nodes - 8, 3):
        host.add_pod(
            f"node-{i}", int(rng.integers(0, 2000)), int(rng.integers(0, 1 << 20))
        )
    return spec, host


def pods(host, spec, batch=16, tolerate=False):
    enc = PodBatchHost(PodSpec(batch=batch), spec, host.vocab)
    infos = []
    for i in range(batch - 2):  # leave padding slots
        tol = (
            [Toleration(key="dedicated"), Toleration(key="flaky")]
            if tolerate and i % 2
            else []
        )
        infos.append(
            PodInfo(
                f"pod-{i}",
                cpu_milli=100 + 50 * (i % 7),
                mem_kib=(100 + 30 * (i % 5)) << 10,
                tolerations=tol,
            )
        )
    return enc.encode(infos)


def test_matches_numpy_oracle(rng):
    spec, host = build(rng)
    batch = pods(host, spec, tolerate=True)
    table = host.to_device()
    idx, prio = fused_topk(table, batch, jnp.int32(1234), BASE, chunk=CHUNK, k=4)
    ref_i, ref_p = np_reference_topk(table, batch, 1234, BASE, k=4)
    np.testing.assert_array_equal(np.asarray(prio), ref_p)
    np.testing.assert_array_equal(np.asarray(idx), ref_i)


def test_matches_xla_feasibility_and_scores(rng):
    """Same feasible set and same integer scores as the XLA plugin path."""
    spec, host = build(rng)
    batch = pods(host, spec, tolerate=True)
    table = host.to_device()

    idx, prio = fused_topk(table, batch, jnp.int32(7), BASE, chunk=CHUNK, k=4)
    mask, score = score_and_filter(table, batch, BASE)
    mask = np.asarray(mask & batch.valid[:, None] & table.valid[None, :])
    score = np.asarray(jnp.where(mask, score, -1))

    idx, prio = np.asarray(idx), np.asarray(prio)
    for b in range(batch.batch):
        feasible = mask[b].sum()
        expect_k = min(4, int(feasible))
        got = (prio[b] >= 0).sum()
        assert got == expect_k
        # Each candidate's unpacked score equals the XLA score at that row,
        # and the candidate list is exactly the k best scores.
        order = np.sort(score[b][mask[b]])[::-1]
        for j in range(expect_k):
            assert score[b, idx[b, j]] == (prio[b, j] >> 20)
        np.testing.assert_array_equal(
            np.sort(prio[b, :expect_k] >> 20)[::-1], order[:expect_k]
        )


def test_candidates_drop_in(rng):
    """pallas_candidates carries the same payload the XLA path gathers."""
    spec, host = build(rng)
    batch = pods(host, spec)
    table = host.to_device()
    cand = pallas_candidates(
        table, batch, jax.random.key(0), BASE, chunk=CHUNK, k=4, row_offset=1000
    )
    free_cpu = np.asarray(table.cpu_alloc - table.cpu_req)
    idx = np.asarray(cand.idx)
    for b in range(batch.batch):
        for j in range(4):
            if idx[b, j] >= 0:
                row = idx[b, j] - 1000
                assert np.asarray(cand.cpu)[b, j] == free_cpu[row]
                assert np.asarray(cand.zone)[b, j] == np.asarray(table.zone)[row]


def test_schedule_batch_backend_parity(rng):
    """End-to-end schedule_batch is BIT-IDENTICAL across backends: both
    derive tie-break jitter from the same separable hash over
    (seed_of(key), pod row, node column) — ops/priority.hash_jitter —
    so ties resolve to the same node, not just the same score."""
    spec, host = build(rng)
    batch = pods(host, spec, tolerate=True)
    t1 = host.to_device()
    t2 = host.to_device()
    key = jax.random.key(3)
    _, _, asg_x = schedule_batch(
        t1, batch, key, profile=BASE, chunk=CHUNK, k=4, backend="xla"
    )
    _, _, asg_p = schedule_batch(
        t2, batch, key, profile=BASE, chunk=CHUNK, k=4, backend="pallas"
    )
    np.testing.assert_array_equal(np.asarray(asg_x.bound), np.asarray(asg_p.bound))
    np.testing.assert_array_equal(
        np.asarray(asg_x.score), np.asarray(asg_p.score)
    )
    # The strong form: identical placements, tie-breaks included.
    np.testing.assert_array_equal(
        np.asarray(asg_x.node_row), np.asarray(asg_p.node_row)
    )


def test_backend_guard():
    with pytest.raises(ValueError):
        schedule_batch(
            None, None, None, profile=Profile(), backend="pallas"
        )
    assert not supports(Profile())
    assert supports(BASE)


def test_node_name_filter(rng):
    spec, host = build(rng, with_taints=False)
    enc = PodBatchHost(PodSpec(batch=4), spec, host.vocab)
    batch = enc.encode(
        [
            PodInfo("pinned", node_name="node-17", cpu_milli=1, mem_kib=1),
            PodInfo("free", cpu_milli=1, mem_kib=1),
        ]
    )
    table = host.to_device()
    idx, prio = fused_topk(table, batch, jnp.int32(0), BASE, chunk=CHUNK, k=4)
    idx = np.asarray(idx)
    assert idx[0, 0] == host.row_of("node-17")
    assert (idx[0, 1:] == -1).all()
    assert (np.asarray(prio)[1] >= 0).all()


# ---- NodeAffinity on the fused kernel ---------------------------------

AFF = Profile(topology_spread=0, interpod_affinity=0)   # default minus constraints


def build_labeled(rng, num_nodes=N):
    """Nodes with tiered labels + numeric labels for Gt/Lt (values beyond
    f32's 2^24 integer range to pin the exact-compare path)."""
    spec = TableSpec(max_nodes=num_nodes, max_taint_ids=16)
    host = NodeTableHost(spec)
    for i in range(num_nodes - 8):
        labels = {
            "tier": ("web", "db", "cache")[i % 3],
            "disk": ("ssd", "hdd")[i % 2],
            "gen": str(100_000_000 + i * 7_919),   # > 2^24: f32 would round
        }
        if i % 4 == 0:
            labels["gpu"] = "true"
        host.upsert(
            NodeInfo(
                f"node-{i}",
                cpu_milli=int(rng.integers(500, 8000)),
                mem_kib=int(rng.integers(1 << 20, 16 << 20)),
                pods=8,
                labels=labels,
            )
        )
    return spec, host


def affinity_pods(host, spec, batch=16):
    from k8s1m_tpu.config import (
        SEL_OP_DOES_NOT_EXIST,
        SEL_OP_EXISTS,
        SEL_OP_GT,
        SEL_OP_IN,
        SEL_OP_LT,
        SEL_OP_NOT_IN,
    )

    enc = PodBatchHost(PodSpec(batch=batch), spec, host.vocab)
    infos = [
        # nodeSelector exact match
        PodInfo("sel", node_selector={"tier": "db"}),
        # required: In
        PodInfo("req-in", required_terms=[NodeSelectorTerm([
            SelectorRequirement("tier", SEL_OP_IN, ["web", "cache"])])]),
        # required: NotIn + Exists ANDed
        PodInfo("req-and", required_terms=[NodeSelectorTerm([
            SelectorRequirement("disk", SEL_OP_NOT_IN, ["hdd"]),
            SelectorRequirement("gpu", SEL_OP_EXISTS)])]),
        # required: OR of two terms
        PodInfo("req-or", required_terms=[
            NodeSelectorTerm([SelectorRequirement("tier", SEL_OP_IN, ["db"])]),
            NodeSelectorTerm([SelectorRequirement("gpu", SEL_OP_EXISTS)])]),
        # required: Gt/Lt on a >2^24 numeric label
        PodInfo("req-gt", required_terms=[NodeSelectorTerm([
            SelectorRequirement("gen", SEL_OP_GT, ["100500000"]),
            SelectorRequirement("gen", SEL_OP_LT, ["101000000"])])]),
        # required: DoesNotExist
        PodInfo("req-dne", required_terms=[NodeSelectorTerm([
            SelectorRequirement("gpu", SEL_OP_DOES_NOT_EXIST)])]),
        # unsatisfiable: selector value never interned
        PodInfo("req-none", node_selector={"tier": "never-seen"}),
        # preferred only: scoring, no filtering
        PodInfo("pref", preferred_terms=[
            PreferredSchedulingTerm(3, NodeSelectorTerm([
                SelectorRequirement("tier", SEL_OP_IN, ["db"])])),
            PreferredSchedulingTerm(1, NodeSelectorTerm([
                SelectorRequirement("disk", SEL_OP_IN, ["ssd"])]))]),
        # plain pod: affinity stage must be a no-op for it
        PodInfo("plain"),
    ]
    return enc.encode(infos)


def test_affinity_matches_numpy_oracle(rng):
    spec, host = build_labeled(rng)
    batch = affinity_pods(host, spec)
    table = host.to_device()
    idx, prio = fused_topk(table, batch, jnp.int32(99), AFF, chunk=CHUNK, k=4)
    ref_i, ref_p = np_reference_topk(table, batch, 99, AFF, k=4)
    np.testing.assert_array_equal(np.asarray(prio), ref_p)
    np.testing.assert_array_equal(np.asarray(idx), ref_i)


def test_affinity_matches_xla_path(rng):
    """Same feasible sets and integer scores as the XLA plugin path for
    every selector shape (all six ops, OR terms, preferred weights)."""
    spec, host = build_labeled(rng)
    batch = affinity_pods(host, spec)
    table = host.to_device()

    idx, prio = fused_topk(table, batch, jnp.int32(5), AFF, chunk=CHUNK, k=4)
    mask, score = score_and_filter(table, batch, AFF)
    mask = np.asarray(mask & batch.valid[:, None] & table.valid[None, :])
    score = np.asarray(jnp.where(mask, score, -1))
    idx, prio = np.asarray(idx), np.asarray(prio)
    for b in range(batch.batch):
        expect_k = min(4, int(mask[b].sum()))
        assert (prio[b] >= 0).sum() == expect_k, b
        order = np.sort(score[b][mask[b]])[::-1]
        for j in range(expect_k):
            assert score[b, idx[b, j]] == (prio[b, j] >> 20), (b, j)
        np.testing.assert_array_equal(
            np.sort(prio[b, :expect_k] >> 20)[::-1], order[:expect_k]
        )


def test_affinity_semantics_spot_checks(rng):
    """Direct semantic pins, independent of the XLA path."""
    spec, host = build_labeled(rng)
    batch = affinity_pods(host, spec)
    table = host.to_device()
    idx, prio = fused_topk(table, batch, jnp.int32(1), AFF, chunk=CHUNK, k=4)
    idx, prio = np.asarray(idx), np.asarray(prio)
    tiers = {i: ("web", "db", "cache")[i % 3] for i in range(N - 8)}

    # sel: every candidate is a db node.
    assert (prio[0] >= 0).all()
    assert all(tiers[int(r)] == "db" for r in idx[0])
    # req-in: web or cache only.
    assert all(tiers[int(r)] in ("web", "cache") for r in idx[1] if r >= 0)
    # req-and: ssd AND gpu -> i % 2 == 0 and i % 4 == 0.
    for r in idx[2]:
        if r >= 0:
            assert int(r) % 4 == 0
    # req-gt: 100.5M < 100M + 7919*i < 101M.
    for r in idx[4]:
        if r >= 0:
            g = 100_000_000 + int(r) * 7_919
            assert 100_500_000 < g < 101_000_000
    # req-dne: no gpu label -> i % 4 != 0.
    for r in idx[5]:
        if r >= 0:
            assert int(r) % 4 != 0
    # unsatisfiable selector: no candidates.
    assert (idx[6] == -1).all()
    # plain pod unaffected by the affinity stage.
    assert (prio[8] >= 0).all()


def test_affinity_backend_parity_end_to_end(rng):
    spec, host = build_labeled(rng)
    batch = affinity_pods(host, spec)
    key = jax.random.key(11)
    _, _, asg_x = schedule_batch(
        host.to_device(), batch, key, profile=AFF, chunk=CHUNK, k=4,
        backend="xla",
    )
    _, _, asg_p = schedule_batch(
        host.to_device(), batch, key, profile=AFF, chunk=CHUNK, k=4,
        backend="pallas",
    )
    np.testing.assert_array_equal(np.asarray(asg_x.bound), np.asarray(asg_p.bound))
    np.testing.assert_array_equal(np.asarray(asg_x.score), np.asarray(asg_p.score))


# ---- fused constraint stage (PodTopologySpread + InterPodAffinity) -------


def build_cons(rng, num_nodes=N):
    """Nodes over zones/regions with adversarial missing-label rows, a
    populated ConstraintState (spread + affinity + anti owners), and a
    mixed constrained pod batch."""
    from k8s1m_tpu.cluster.workload import (
        affinity_deployment,
        spread_deployment,
    )
    from k8s1m_tpu.config import TOPO_REGION, TOPO_ZONE
    from k8s1m_tpu.snapshot.constraints import (
        ConstraintTracker,
        empty_constraints,
    )
    from k8s1m_tpu.snapshot.node_table import REGION_LABEL, ZONE_LABEL

    spec = TableSpec(
        max_nodes=num_nodes, max_zones=8, max_regions=4,
        spread_slots=8, affinity_slots=8,
    )
    host = NodeTableHost(spec)
    for i in range(num_nodes):
        labels = {}
        if i % 11 != 7:
            labels[ZONE_LABEL] = f"z{i % 5}"
        if i % 13 != 5:
            labels[REGION_LABEL] = f"r{i % 3}"
        host.upsert(NodeInfo(
            name=f"n{i}", cpu_milli=64_000, mem_kib=1 << 26, pods=64,
            labels=labels,
        ))
    tracker = ConstraintTracker(spec)
    pods = (
        spread_deployment(tracker, "sp-z", 6, topo=TOPO_ZONE)
        + spread_deployment(tracker, "sp-r", 4, topo=TOPO_REGION, max_skew=2)
        + affinity_deployment(tracker, "aff", 4, anti=False, required=True)
        + affinity_deployment(tracker, "anti", 6, anti=True, required=True)
        + affinity_deployment(tracker, "pref", 4, required=False)
    )
    rng.shuffle(pods)
    pspec = PodSpec(batch=32)
    enc = PodBatchHost(pspec, spec, host.vocab)
    cons = empty_constraints(spec)
    return spec, host, enc, pods, cons


def _populate_counts(host, enc, pods, cons):
    """Schedule a first constrained wave on the XLA path so the count
    tables are non-trivial for the comparison batch."""
    table = host.to_device()
    batch = enc.encode(pods[:12])
    table, cons, _ = schedule_batch(
        table, batch, jax.random.key(11), profile=Profile(),
        constraints=cons, chunk=CHUNK, k=4, backend="xla",
    )
    return table, cons


def test_constraints_match_xla_feasibility_and_scores(rng):
    """The fused constraint stage computes the same feasible set and the
    same integer scores as plugins/topology.py on populated count
    tables (the configs 3-4 exactness check)."""
    from k8s1m_tpu.plugins import topology

    spec, host, enc, pods, cons = build_cons(rng)
    table, cons = _populate_counts(host, enc, pods, cons)
    batch = enc.encode(pods[12:])
    prof = Profile()
    stats = topology.prologue(table, cons)

    idx, prio = fused_topk(
        table, batch, jnp.int32(77), prof, chunk=CHUNK, k=4,
        constraints=cons, stats=stats,
    )
    mask, score = score_and_filter(table, batch, prof, cons, stats)
    mask = np.asarray(mask & batch.valid[:, None] & table.valid[None, :])
    score = np.asarray(jnp.where(mask, score, -1))

    idx, prio = np.asarray(idx), np.asarray(prio)
    for b in range(batch.batch):
        feasible = mask[b].sum()
        expect_k = min(4, int(feasible))
        assert (prio[b] >= 0).sum() == expect_k, b
        order = np.sort(score[b][mask[b]])[::-1]
        for j in range(expect_k):
            assert mask[b, idx[b, j]], (b, j)
            assert score[b, idx[b, j]] == (prio[b, j] >> 20), (b, j)
        np.testing.assert_array_equal(
            np.sort(prio[b, :expect_k] >> 20)[::-1], order[:expect_k]
        )


def test_constrained_schedule_batch_parity(rng):
    """End-to-end constrained cycle agrees across backends on bound set
    and scores (jitter differs, so tie choices may differ)."""
    spec, host, enc, pods, cons = build_cons(rng)
    table, cons = _populate_counts(host, enc, pods, cons)
    batch = enc.encode(pods[12:])
    key = jax.random.key(5)
    _, _, asg_x = schedule_batch(
        table, batch, key, profile=Profile(), constraints=cons,
        chunk=CHUNK, k=4, backend="xla",
    )
    _, _, asg_p = schedule_batch(
        table, batch, key, profile=Profile(), constraints=cons,
        chunk=CHUNK, k=4, backend="pallas",
    )
    np.testing.assert_array_equal(
        np.asarray(asg_x.bound), np.asarray(asg_p.bound)
    )
    np.testing.assert_array_equal(
        np.asarray(asg_x.score), np.asarray(asg_p.score)
    )


def test_scaled_oracle_chunk_and_tile_boundaries(rng):
    """Bit-exact oracle parity at a scale that crosses both grid axes:
    4096 nodes / chunk 512 (8 node chunks) and a 512-pod batch (2 pod
    tiles of 256) — the boundary classes a 256-node test cannot reach
    (running top-k carry across chunks, per-tile row offsets in the
    jitter hash, padding rows in the last chunk)."""
    spec, host = build(rng, num_nodes=4096)
    batch = pods(host, spec, batch=512, tolerate=True)
    table = host.to_device()
    idx, prio = fused_topk(
        table, batch, jnp.int32(99991), BASE, chunk=512, k=4
    )
    ref_i, ref_p = np_reference_topk(table, batch, 99991, BASE, k=4)
    np.testing.assert_array_equal(np.asarray(prio), ref_p)
    np.testing.assert_array_equal(np.asarray(idx), ref_i)


def test_scaled_affinity_oracle_boundaries(rng):
    """Affinity-kernel oracle parity across chunk and pod-tile
    boundaries: 1024 labeled nodes / 4 chunks / 512-pod batch of every
    selector shape, on a workload-fitted PodSpec (the production sizing
    rule) — pins the per-tile row offsets and cross-chunk top-k carry
    for the with_aff kernel the way the base-profile scaled test does."""
    from k8s1m_tpu.config import (
        SEL_OP_EXISTS,
        SEL_OP_GT,
        SEL_OP_IN,
        SEL_OP_LT,
        SEL_OP_NOT_IN,
    )

    spec, host = build_labeled(rng, num_nodes=1024)
    pspec = PodSpec(
        batch=512, aff_terms=2, aff_exprs=2, aff_values=2, pref_terms=2,
    )
    enc = PodBatchHost(pspec, spec, host.vocab)
    shapes = [
        lambda i: PodInfo(f"sel-{i}", node_selector={"tier": "db"}),
        lambda i: PodInfo(f"in-{i}", required_terms=[NodeSelectorTerm([
            SelectorRequirement("tier", SEL_OP_IN, ["web", "cache"])])]),
        lambda i: PodInfo(f"and-{i}", required_terms=[NodeSelectorTerm([
            SelectorRequirement("disk", SEL_OP_NOT_IN, ["hdd"]),
            SelectorRequirement("gpu", SEL_OP_EXISTS)])]),
        lambda i: PodInfo(f"or-{i}", required_terms=[
            NodeSelectorTerm([SelectorRequirement("tier", SEL_OP_IN, ["db"])]),
            NodeSelectorTerm([SelectorRequirement("gpu", SEL_OP_EXISTS)])]),
        lambda i: PodInfo(f"gt-{i}", required_terms=[NodeSelectorTerm([
            SelectorRequirement("gen", SEL_OP_GT, [str(100_000_000 + i * 7919)]),
            SelectorRequirement("gen", SEL_OP_LT, [str(103_000_000 + i)])])]),
        lambda i: PodInfo(f"pref-{i}", preferred_terms=[
            PreferredSchedulingTerm(3, NodeSelectorTerm([
                SelectorRequirement("tier", SEL_OP_IN, ["db"])])),
            PreferredSchedulingTerm(1, NodeSelectorTerm([
                SelectorRequirement("disk", SEL_OP_IN, ["ssd"])]))]),
        lambda i: PodInfo(f"plain-{i}"),
    ]
    infos = [shapes[i % len(shapes)](i) for i in range(500)]
    batch = enc.encode(infos)
    table = host.to_device()
    prof = Profile(topology_spread=0, interpod_affinity=0)
    idx, prio = fused_topk(
        table, batch, jnp.int32(4242), prof, chunk=256, k=4,
    )
    ref_i, ref_p = np_reference_topk(table, batch, 4242, prof, k=4)
    np.testing.assert_array_equal(np.asarray(prio), ref_p)
    np.testing.assert_array_equal(np.asarray(idx), ref_i)


# ---- exactness of the one-pass bf16 selection dots at wide values -------
#
# The kernel's selection dots carry wide values as bf16 bytes (module doc
# of ops/pallas_topk.py).  Each case below puts one regime of wide values
# through them: constraint counts at and across the byte boundaries, up to
# 2^24 - 1, in the hostname, zone and region topologies of spread, target
# and own refs; label value ids past 2^24 and near 2^31; numerics past
# 2^24, negative, and just above the NO_NUMERIC sentinel.  The candidates
# must equal the XLA scan's, and, where the numpy oracle covers the
# plugins, the oracle's, bit for bit.

WIDE_COUNTS = (255, 256, 65_535, 65_536, (1 << 24) - 1)
NARROW = PodSpec(
    batch=64, query_keys=4, aff_terms=1, aff_exprs=1, aff_values=1,
    pref_terms=1, spread_refs=2, affinity_refs=2, spread_incs=2, ipa_incs=2,
)


def _wide_count_inputs(rng, level):
    """build_cons' cluster after a first wave, with every count table —
    node, zone and region planes of the spread, target and own families —
    redrawn from {0, level - 2, ..., level + 1} (capped at 2^24 - 1), and
    a batch whose refs name all three topologies in all three families."""
    from k8s1m_tpu.cluster.workload import (
        affinity_deployment,
        spread_deployment,
    )
    from k8s1m_tpu.config import TOPO_HOSTNAME, TOPO_REGION, TOPO_ZONE

    from k8s1m_tpu.snapshot.constraints import ConstraintTracker

    spec, host, enc, pods, cons = build_cons(rng)
    table, cons = _populate_counts(host, enc, pods, cons)
    tracker = ConstraintTracker(spec)
    wave = []
    for t in (TOPO_HOSTNAME, TOPO_ZONE, TOPO_REGION):
        target = {"app": f"w-sp{t}"}
        wave += spread_deployment(tracker, f"w-sp{t}", 4, topo=t)
        wave += affinity_deployment(
            tracker, f"w-aff{t}", 3, target=target, topo=t)
        # Self-selecting required anti-affinity: the own (symmetry) refs.
        wave += affinity_deployment(tracker, f"w-anti{t}", 3, anti=True, topo=t)
        wave += affinity_deployment(
            tracker, f"w-pref{t}", 3, required=False, anti=bool(t % 2),
            weight=7 + t, target=target, topo=t,
        )
    rng.shuffle(wave)
    batch = PodBatchHost(NARROW, spec, host.vocab).encode(wave)
    vals = np.minimum(
        np.array([0, level - 2, level - 1, level, level + 1]), (1 << 24) - 1
    )

    def redraw(t):
        return jnp.asarray(vals[rng.integers(0, len(vals), t.shape)], jnp.int32)

    cons = cons.replace(**{
        name: redraw(getattr(cons, name)) for name in (
            "spread_node", "spread_zone", "spread_region", "tgt_node",
            "tgt_zone", "tgt_region", "own_node", "own_zone", "own_region")
    })
    return table, batch, cons, Profile()


def _wide_label_inputs(rng, regime):
    """affinity_pods on build_labeled's nodes (nodeSelector, required and
    preferred slots, all six ops, a never-interned value as NONE_ID, the
    tier labels' NO_NUMERIC) with the value ids or the numerics moved into
    one wide regime by an order-keeping map that leaves both sentinels."""
    from k8s1m_tpu.config import NO_NUMERIC, NONE_ID

    spec, host = build_labeled(rng)
    batch = affinity_pods(host, spec)
    table = host.to_device()
    top = int(np.asarray(table.label_val).max())
    shift = {
        "ids-past-2^24": ("ids", (1 << 24) + 1),
        "ids-near-2^31": ("ids", (1 << 31) - 1 - top),
        # gen runs from 1e8 (past 2^24): mirrored below zero, then moved
        # to just above the sentinel.
        "numerics-negative": ("num", None),
        "numerics-above-NO_NUMERIC": ("num", NO_NUMERIC + 1),
    }
    kind, by = shift[regime]
    if kind == "ids":
        move = lambda x: jnp.where(x != NONE_ID, x + by, x)
        return (
            table.replace(label_val=move(table.label_val)),
            batch.replace(
                sel_val=move(batch.sel_val), req_vals=move(batch.req_vals),
                pref_vals=move(batch.pref_vals),
            ),
        )
    if by is None:
        move = lambda x: jnp.where(x != NO_NUMERIC, -x, x)
    else:
        move = lambda x: jnp.where(x != NO_NUMERIC, x - 100_000_000 + by, x)
    return (
        table.replace(label_num=move(table.label_num)),
        batch.replace(req_num=move(batch.req_num), pref_num=move(batch.pref_num)),
    )


@pytest.mark.parametrize(
    "regime",
    [f"counts-{c}" for c in WIDE_COUNTS]
    + ["ids-past-2^24", "ids-near-2^31", "numerics-negative",
       "numerics-above-NO_NUMERIC"],
)
def test_selection_dots_exact_at_wide_values(rng, regime):
    from k8s1m_tpu.engine.cycle import candidates

    if regime.startswith("counts-"):
        table, batch, cons, prof = _wide_count_inputs(
            rng, int(regime.split("-")[1]))
    else:
        (table, batch), cons, prof = _wide_label_inputs(rng, regime), None, AFF
        idx, prio = fused_topk(table, batch, jnp.int32(31), prof, chunk=CHUNK, k=4)
        ref_i, ref_p = np_reference_topk(table, batch, 31, prof, k=4)
        np.testing.assert_array_equal(np.asarray(prio), ref_p)
        np.testing.assert_array_equal(np.asarray(idx), ref_i)
    key = jax.random.key(17)
    got = {
        backend: candidates(
            table, batch, key, cons, prof, chunk=CHUNK, k=4, backend=backend,
        )
        for backend in ("pallas", "xla")
    }
    np.testing.assert_array_equal(
        np.asarray(got["pallas"].prio), np.asarray(got["xla"].prio))
    np.testing.assert_array_equal(
        np.asarray(got["pallas"].idx), np.asarray(got["xla"].idx))
    prio = np.asarray(got["pallas"].prio)
    # The regime decides something: some pods keep candidates, and some
    # pods or slots lose them.
    assert (prio >= 0).any() and (prio < 0).any()


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters."""
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_kernel_dots_are_one_default_pass(rng):
    """No dot of the kernel with both stages asks for more than the
    default precision, and every one-hot selection runs on bf16 operands;
    only the two taint dots (0/1 and small counts) keep f32 ones."""
    from k8s1m_tpu.plugins import topology

    spec, host, enc, pods, cons = build_cons(rng)
    table = host.to_device()
    batch = PodBatchHost(NARROW, spec, host.vocab).encode(pods[:12])
    stats = topology.prologue(table, cons)
    closed = jax.make_jaxpr(lambda: fused_topk(
        table, batch, jnp.int32(0), Profile(), chunk=CHUNK, k=4,
        constraints=cons, stats=stats,
    ))()
    (call,) = [e for e in _eqns(closed.jaxpr) if e.primitive.name == "pallas_call"]
    dots = [e for e in _eqns(call.params["jaxpr"])
            if e.primitive.name == "dot_general"]
    assert all(d.params["precision"] in (None, (lax.Precision.DEFAULT,) * 2)
               for d in dots)
    wide = [d for d in dots
            if any(v.aval.dtype != jnp.bfloat16 for v in d.invars)]
    assert len(wide) == 2 and len(dots) > 2
