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

from k8s1m_tpu.config import (
    EFFECT_NO_SCHEDULE,
    EFFECT_PREFER_NO_SCHEDULE,
    PodSpec,
    TableSpec,
)
from k8s1m_tpu.engine.cycle import filter_score_topk, schedule_batch
from k8s1m_tpu.ops.pallas_topk import (
    delta_plane_topk,
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


# ---- the fused delta tail (deltasched plane top-k) ------------------------


def _delta_parity(rng, n, s, b, chunk, hb=0, seeds=(0, 4242)):
    """delta_plane_topk (fused dirty-gather → merge → top-k) vs
    plane_topk (the XLA delta tail) over the same cached planes: idx
    AND prio bit-identical for real pods.  Padding pods (slot sentinel)
    are don't-cares — plane_topk's jnp.take fills out-of-range slots
    while the kernel clips, and finalize valid-masks padding out before
    anything binds."""
    from k8s1m_tpu.engine.deltacache import plane_topk

    pmask = jnp.asarray(rng.random((s, n)) < 0.6)
    pscore = jnp.asarray(rng.integers(0, 2048, (s, n)), jnp.int32)
    slot_ids = jnp.asarray(
        np.concatenate([rng.integers(0, s, b - 2), [s, s]]), jnp.int32
    )
    real = np.asarray(slot_ids) < s
    for seed in seeds:
        sd = jnp.int32(seed)
        cand_p = delta_plane_topk(
            pmask, pscore, slot_ids, sd, chunk=chunk, k=4, stratum_bits=hb
        )
        cand_x = plane_topk(
            pmask, pscore, slot_ids, sd, chunk=chunk, k=4, stratum_bits=hb
        )
        np.testing.assert_array_equal(
            np.asarray(cand_p.idx)[real], np.asarray(cand_x.idx)[real]
        )
        np.testing.assert_array_equal(
            np.asarray(cand_p.prio)[real], np.asarray(cand_x.prio)[real]
        )


def test_delta_tail_matches_xla_plane_topk(rng):
    """Chunk-carry and slot-gather parity at small scale, with and
    without stratification."""
    _delta_parity(rng, n=512, s=8, b=16, chunk=128)
    _delta_parity(rng, n=512, s=8, b=16, chunk=128, hb=12)


def test_delta_tail_bit_identical_at_131072_rows(rng):
    """The ISSUE 18 acceptance gate: the pallas delta step's top-k tail
    is bit-identical to the XLA delta step at 131,072 plane rows
    (interpreter mode here; the identical kernel compiles on TPU)."""
    _delta_parity(rng, n=131072, s=4, b=8, chunk=16384, hb=12, seeds=(7,))


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
