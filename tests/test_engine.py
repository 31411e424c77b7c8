"""End-to-end single-device scheduling-cycle tests."""

import jax
import numpy as np
import pytest

from k8s1m_tpu.config import PodSpec, TableSpec
from k8s1m_tpu.engine import schedule_batch
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot import NodeInfo, NodeTableHost, PodBatchHost, PodInfo

SPEC = TableSpec(max_nodes=64, max_zones=8, max_regions=4)
PROFILE = Profile(topology_spread=0, interpod_affinity=0)


def setup(nodes, pods, batch=16):
    host = NodeTableHost(SPEC)
    for n in nodes:
        host.upsert(n)
    enc = PodBatchHost(PodSpec(batch=batch), SPEC, host.vocab)
    return host, host.to_device(), enc.encode(pods)


def test_binds_best_node_and_feedback():
    # One clearly-best (empty) node; second pod must see the first pod's
    # commit and still choose sensibly.
    host, table, batch = setup(
        [NodeInfo(name="big", cpu_milli=10_000, mem_kib=1 << 24),
         NodeInfo(name="small", cpu_milli=1000, mem_kib=1 << 20)],
        [PodInfo(name=f"p{i}", cpu_milli=100, mem_kib=1 << 15) for i in range(10)],
    )
    t2, _, asg = schedule_batch(table, batch, jax.random.key(0), profile=PROFILE,chunk=64)
    bound = np.asarray(asg.bound)
    assert bound[:10].all() and not bound[10:].any()
    # Table feedback: total requested equals sum of bound pods.
    assert int(t2.cpu_req.sum()) == 1000
    assert int(t2.pods_req.sum()) == 10


def test_conflict_resolution_spills_to_second_node():
    # Each node fits exactly one pod; two pods in one batch must split.
    host, table, batch = setup(
        [NodeInfo(name="a", cpu_milli=1000, mem_kib=1 << 20, pods=1),
         NodeInfo(name="b", cpu_milli=1000, mem_kib=1 << 20, pods=1)],
        [PodInfo(name="p0", cpu_milli=800, mem_kib=1 << 18),
         PodInfo(name="p1", cpu_milli=800, mem_kib=1 << 18)],
    )
    _, _, asg = schedule_batch(table, batch, jax.random.key(1), profile=PROFILE,chunk=64)
    rows = np.asarray(asg.node_row)[:2]
    assert np.asarray(asg.bound)[:2].all()
    assert rows[0] != rows[1]


def test_unschedulable_pod_left_unbound():
    host, table, batch = setup(
        [NodeInfo(name="a", cpu_milli=100, mem_kib=1 << 20)],
        [PodInfo(name="p0", cpu_milli=500)],
    )
    _, _, asg = schedule_batch(table, batch, jax.random.key(2), profile=PROFILE,chunk=64)
    assert not np.asarray(asg.bound)[0]
    assert int(asg.node_row[0]) == -1


def test_batch_overflow_spills_and_rest_unbound():
    # 3 pod slots total; 5 pods -> exactly 3 bind.
    host, table, batch = setup(
        [NodeInfo(name="a", cpu_milli=10_000, mem_kib=1 << 24, pods=2),
         NodeInfo(name="b", cpu_milli=10_000, mem_kib=1 << 24, pods=1)],
        [PodInfo(name=f"p{i}", cpu_milli=10, mem_kib=1 << 10) for i in range(5)],
    )
    _, _, asg = schedule_batch(table, batch, jax.random.key(3), profile=PROFILE,chunk=64)
    assert int(np.asarray(asg.bound).sum()) == 3


def test_tiebreak_is_random_but_deterministic_per_key():
    # 32 identical nodes; one pod.  Different keys should not always pick
    # the same node; the same key must.
    host, table, batch = setup(
        [NodeInfo(name=f"n{i}", cpu_milli=1000, mem_kib=1 << 20) for i in range(32)],
        [PodInfo(name="p", cpu_milli=10, mem_kib=1 << 10)],
        batch=4,
    )
    picks = set()
    for seed in range(12):
        _, _, asg = schedule_batch(table, batch, jax.random.key(seed), profile=PROFILE,chunk=64)
        picks.add(int(asg.node_row[0]))
    assert len(picks) > 3  # uniform over 32 — 12 draws landing on <4 nodes is ~impossible
    _, _, a1 = schedule_batch(table, batch, jax.random.key(7), profile=PROFILE,chunk=64)
    _, _, a2 = schedule_batch(table, batch, jax.random.key(7), profile=PROFILE,chunk=64)
    assert int(a1.node_row[0]) == int(a2.node_row[0])


def test_chunking_invariant_scores():
    # Same cluster scheduled with different chunk sizes must produce the
    # same *scores* (tie-break jitter may differ, but score part may not).
    host, table, batch = setup(
        [NodeInfo(name=f"n{i}", cpu_milli=1000 + 13 * i, mem_kib=(1 << 20) + (i << 10))
         for i in range(16)],
        [PodInfo(name=f"p{i}", cpu_milli=50 + i, mem_kib=1 << 12) for i in range(8)],
    )
    _, _, a1 = schedule_batch(table, batch, jax.random.key(0), profile=PROFILE,chunk=64)
    _, _, a2 = schedule_batch(table, batch, jax.random.key(0), profile=PROFILE,chunk=16)
    np.testing.assert_array_equal(np.asarray(a1.score), np.asarray(a2.score))
    np.testing.assert_array_equal(np.asarray(a1.bound), np.asarray(a2.bound))


def test_sampled_window_with_constraints_matches_full():
    """percentageOfNodesToScore + constraint plugins: a window covering
    every valid row must reproduce the full-scan result bit-for-bit
    (domain statistics are global prologue reductions either way)."""
    from k8s1m_tpu.cluster.workload import spread_deployment
    from k8s1m_tpu.engine.cycle import schedule_batch_packed
    from k8s1m_tpu.snapshot.constraints import (
        ConstraintTracker,
        empty_constraints,
    )

    spec = TableSpec(max_nodes=128, max_zones=8, max_regions=4)
    host = NodeTableHost(spec)
    for i in range(64):                      # rows 64..127 stay invalid
        host.upsert(NodeInfo(
            name=f"n{i}", cpu_milli=4000, mem_kib=1 << 20, pods=16,
            labels={"topology.kubernetes.io/zone": f"z{i % 4}"},
        ))
    tracker = ConstraintTracker(spec)
    pods = spread_deployment(tracker, "d", 24, topo=1)
    enc = PodBatchHost(PodSpec(batch=32), spec, host.vocab)
    packed = enc.encode_packed(pods)
    key = jax.random.key(3)
    profile = Profile()

    outs = []
    for sample_rows in (None, 64):
        table = host.to_device()
        cons = empty_constraints(spec)
        t, c, asg, rows = schedule_batch_packed(
            table, packed, key, profile=profile, constraints=cons,
            chunk=32, k=4, sample_rows=sample_rows, sample_offset=0,
        )
        outs.append((np.asarray(rows), np.asarray(c.spread_zone)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    assert (outs[0][0] >= 0).sum() == 24


@pytest.mark.parametrize("offset", [32, 64])
@pytest.mark.parametrize("with_constraints", [False, True])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_window_is_the_slice_plus_offset(backend, with_constraints, offset):
    """What a scan window MEANS on one device: the step over rows
    [O, O+R) binds exactly what the unsampled step binds on that slice of
    the table (window-local hash columns), plus O — with constraint
    plugins too, whose per-node counts follow the window while the
    domain statistics stay global."""
    from k8s1m_tpu.cluster.workload import spread_deployment
    from k8s1m_tpu.engine.cycle import schedule_batch_packed
    from k8s1m_tpu.snapshot.constraints import (
        ConstraintTracker,
        empty_constraints,
        slice_constraints,
    )

    rows = 64
    spec = TableSpec(max_nodes=128, max_zones=8, max_regions=4)
    host = NodeTableHost(spec)
    for i in range(128):
        host.upsert(NodeInfo(
            name=f"n{i}", cpu_milli=4000, mem_kib=1 << 20, pods=4,
            labels={"topology.kubernetes.io/zone": f"z{i % 4}"},
        ))
    enc = PodBatchHost(PodSpec(batch=32), spec, host.vocab)
    table, cons = host.to_device(), None
    kw = dict(chunk=32, k=4, backend=backend)
    if with_constraints:
        # Live counts, not zeros: a first unsampled wave of the same
        # deployment commits into the state both sides then read.
        profile = Profile()
        tracker = ConstraintTracker(spec)
        first = spread_deployment(tracker, "d", 24, topo=1, max_skew=2)
        pods = spread_deployment(tracker, "d", 24, topo=1, max_skew=2, start=24)
        table, cons, _, bound = schedule_batch_packed(
            table, enc.encode_packed(first), jax.random.key(8),
            profile=profile, constraints=empty_constraints(spec), **kw,
        )
        assert (np.asarray(bound) >= 0).sum() == 24
    else:
        profile = PROFILE
        pods = [PodInfo(name=f"p{i}", cpu_milli=100 + i, mem_kib=1 << 15)
                for i in range(24)]
    key = jax.random.key(9)

    new_table, _, _, got = schedule_batch_packed(
        table, enc.encode_packed(pods), key, profile=profile,
        constraints=cons, sample_rows=rows, sample_offset=offset, **kw,
    )
    sliced = jax.tree.map(lambda a: a[offset:offset + rows], table)
    ref_table, _, asg = schedule_batch(
        sliced, enc.encode(pods), key, profile=profile,
        constraints=None if cons is None
        else slice_constraints(cons, offset, rows), **kw,
    )
    want = np.where(np.asarray(asg.bound), np.asarray(asg.node_row) + offset, -1)
    got = np.asarray(got)
    np.testing.assert_array_equal(got, want)
    assert (got >= 0).sum() == 24 and got[24:].max() == -1
    # The commit lands in the full table, on the window's rows only.
    want_req = np.asarray(table.pods_req).copy()
    want_req[offset:offset + rows] = np.asarray(ref_table.pods_req)
    np.testing.assert_array_equal(np.asarray(new_table.pods_req), want_req)


def test_topk_by_argmax_matches_lax_top_k():
    """chunk_topk's two forms must stay interchangeable.

    chunk_topk dispatches per backend (knock-out argmax on CPU,
    lax.top_k on TPU), so the CPU suite would otherwise never assert the
    equivalence the dispatch relies on.  lax.top_k runs on CPU too:
    compare the forms directly on duplicate-heavy int32 inputs,
    including all-equal rows and the -1 INFEASIBLE sentinel.

    Tie semantics caveat: the earlier-index-wins tie-break this test
    asserts is only verified on CPU (both forms here run on the CPU
    backend); on silicon the same equivalence — including index order
    under ties — is covered by phase B of chip_smoke.py (XLA scan vs
    the fused kernel, bit for bit, on the chip).
    """
    import jax.numpy as jnp
    from jax import lax

    from k8s1m_tpu.engine.cycle import topk_by_argmax

    # Domain note: pack_hashed emits {-1 (INFEASIBLE)} ∪ [0, int32max] —
    # int32 min never occurs, which matters: the knock-out's sentinel IS
    # int32 min, so rows containing it would diverge in index order
    # (values still agree).  Test over the real domain, duplicates and
    # all-infeasible rows included.
    rng = np.random.default_rng(7)
    cases = [
        rng.integers(-1, 7, size=(16, 97)).astype(np.int32),    # dup-heavy
        np.zeros((4, 33), np.int32),                            # all-equal
        np.full((3, 17), -1, np.int32),                         # all-infeasible
        rng.integers(-1, np.iinfo(np.int32).max,
                     size=(8, 64)).astype(np.int32),            # full range
    ]
    for prio in cases:
        for k in (1, 4, 8):
            a_v, a_i = topk_by_argmax(jnp.asarray(prio), k)
            t_v, t_i = lax.top_k(jnp.asarray(prio), k)
            np.testing.assert_array_equal(np.asarray(a_v), np.asarray(t_v))
            np.testing.assert_array_equal(np.asarray(a_i), np.asarray(t_i))
