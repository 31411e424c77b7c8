"""full-1m-pct5's waves on a 1x4 mesh: the in-wave skew count and the
inter-pod gate on the sharded step (full-1m-pct5-sp4).

The byte-identity contract of parallel/sharded_cycle.py, held with every
plug-in on: at pct 100 a wave of the mix binds on four shards exactly what
it binds on one device — rows, the reasons pods stayed unbound, what
settled them, every count table and request column — on both backends.
Under it: the shards' candidates merge zone by zone as a plain NumPy
per-zone argmax does (the lowest row wins a tie), and the prologue the
count and the gate start from is the whole table's, not a shard's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import test_full_waves as F
from k8s1m_tpu.control.objects import decode_pod, encode_pod
from k8s1m_tpu.engine.cycle import (
    Candidates,
    merge_shards,
    prologue_stats,
    schedule_batch_packed,
)
from k8s1m_tpu.parallel import make_mesh
from k8s1m_tpu.parallel.mesh import constraint_specs, table_specs
from k8s1m_tpu.snapshot import NodeInfo, NodeTableHost, PodBatchHost
from k8s1m_tpu.snapshot.constraints import ConstraintTracker, empty_constraints
from k8s1m_tpu.tools.make_pods import build_pod

SP = 4
CHUNK = 64          # two chunks a shard of 128 rows


def _mesh():
    return make_mesh(1, SP)


def _on_mesh(mesh, table, cons):
    put = lambda tree, specs: jax.device_put(
        tree, jax.tree.map(lambda s: NamedSharding(mesh, s), specs))
    return put(table, table_specs(table)), put(cons, constraint_specs(cons))


# ---- a wave of the mix binds on four shards what it binds on one device ------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_a_1x4_mesh_binds_what_one_device_binds_with_the_skew_and_the_gate(
        backend):
    host = NodeTableHost(F.SPEC)
    host.bulk_upsert(F.NODE)
    tracker = ConstraintTracker(F.SPEC)
    enc = PodBatchHost(F.POD_SPEC, F.SPEC, host.vocab)
    mesh = _mesh()
    cons = empty_constraints(F.SPEC)
    one = (host.to_device(), cons)
    four = _on_mesh(mesh, host.to_device(), cons)
    pattern = F.pattern_of(46)
    unbound = np.zeros(4, int)
    for w in range(3):
        shapes = [pattern[i % len(pattern)]
                  for i in range(w * F.WAVE, (w + 1) * F.WAVE)]
        packed = enc.encode_packed([
            decode_pod(encode_pod(build_pod(w * F.WAVE + j, **s)), tracker)
            for j, s in enumerate(shapes)])
        key = jax.random.key(46_000 + w)
        got = []
        for (table, c), m in ((one, None), (four, mesh)):
            table, c, asg, rows = schedule_batch_packed(
                table, packed, key, profile=F.PROFILE, constraints=c,
                chunk=CHUNK, k=F.K, backend=backend, in_wave_skew=True, mesh=m)
            got.append(((table, c), jax.tree.map(np.asarray, (asg, rows, table, c))))
        (one, (a1, rows1, t1, c1)), (four, (a2, rows2, t2, c2)) = got
        np.testing.assert_array_equal(rows1, rows2)
        for name in ("node_row", "bound", "score", "zone", "region", "unbound",
                     "settled"):
            np.testing.assert_array_equal(getattr(a1, name), getattr(a2, name), name)
        for name in vars(c1):
            np.testing.assert_array_equal(getattr(c1, name), getattr(c2, name), name)
        for name in ("cpu_req", "mem_req", "pods_req"):
            np.testing.assert_array_equal(getattr(t1, name), getattr(t2, name), name)
        assert (rows1 >= 0).sum() > F.WAVE // 2
        unbound += a1.unbound
    # the waves reached the count's and the gate's own cases: some pods
    # had no feasible row at their wave's start
    assert unbound.sum() > 0


# ---- the merge over sp is a per-zone argmax -----------------------------------


def _shard_candidates(seed: int, b: int, k: int):
    """Per-shard candidate lists [SP, B, K], rows ascending with the shard,
    priorities from a narrow range so that shards tie, -1 for a slot with
    no feasible row."""
    rng = np.random.default_rng(seed)
    prio = rng.integers(-1, 4, (SP, b, k)).astype(np.int32)
    rows = (np.arange(SP)[:, None, None] * 1000
            + rng.integers(0, 1000, (SP, b, k))).astype(np.int32)
    idx = np.where(prio >= 0, rows, -1).astype(np.int32)
    payload = lambda: rng.integers(0, 1 << 20, (SP, b, k)).astype(np.int32)
    return Candidates(idx=idx, prio=prio, cpu=payload(), mem=payload(),
                      pods=payload(), zone=payload(), region=payload())


def _merged(cand, k: int, per_zone: bool):
    mesh = _mesh()
    local = lambda c: jax.tree.map(
        lambda x: x[None], merge_shards(jax.tree.map(lambda x: x[0], c), "sp",
                                        k, per_zone))
    fn = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=P("sp"), out_specs=P("sp"), check_vma=False))
    out = jax.tree.map(np.asarray, fn(jax.tree.map(jnp.asarray, cand)))
    for leaf in jax.tree.leaves(out):                  # whole on every shard
        assert (leaf == leaf[:1]).all()
    return jax.tree.map(lambda x: x[0], out)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_per_zone_merge_is_a_numpy_per_zone_argmax(seed):
    b, k = 16, 9
    cand = _shard_candidates(seed, b, k)
    got = _merged(cand, k, per_zone=True)
    for i in range(b):
        for z in range(k):
            prios = [int(cand.prio[s, i, z]) for s in range(SP)]
            best = max(prios)
            shard = prios.index(best)          # the first, so the lowest rows
            ties = [int(cand.idx[s, i, z]) for s in range(SP) if prios[s] == best]
            assert best < 0 or int(got.idx[i, z]) == min(ties)
            for name in vars(got):
                assert int(getattr(got, name)[i, z]) == int(
                    getattr(cand, name)[shard, i, z]), (name, i, z)


def test_the_topk_merge_keeps_the_k_best_earlier_shard_first():
    b, k = 16, 4
    cand = _shard_candidates(3, b, k)
    got = _merged(cand, k, per_zone=False)
    for i in range(b):
        flat = [(int(cand.prio[s, i, j]), s * k + j) for s in range(SP)
                for j in range(k)]
        want = sorted(flat, key=lambda t: (-t[0], t[1]))[:k]
        assert [int(p) for p in got.prio[i]] == [p for p, _ in want]
        assert [int(x) for x in got.idx[i]] == [
            int(cand.idx[at // k, i, at % k]) for _, at in want]


# ---- the prologue the count and the gate start from is global -----------------


def test_the_prologue_on_the_mesh_is_the_whole_tables():
    """Each shard of 128 rows holds two zones of its own and one region:
    a shard alone sees two of eight zones present; the mesh's prologue
    sees all eight, and each constraint's least and most populated domain
    and each term's total over the whole table, as one device does."""
    spec = F.SPEC
    zone = lambda i: 1 + 2 * (i // 128) + i % 2
    nodes = [NodeInfo(name=f"n{i}", labels={
        "topology.kubernetes.io/zone": f"z{zone(i)}",
        "topology.kubernetes.io/region": f"r{i // 128}"}) for i in range(496)]
    host = NodeTableHost(spec)
    host.bulk_upsert(nodes)
    rng = np.random.default_rng(9)
    cons = jax.tree.map(
        lambda x: jnp.asarray(rng.integers(0, 50, x.shape).astype(np.int32)),
        empty_constraints(spec))
    want = jax.tree.map(np.asarray, jax.jit(prologue_stats)(host.to_device(), cons))
    mesh = _mesh()
    table, mcons = _on_mesh(mesh, host.to_device(), cons)

    def stats(axis_name):
        fn = jax.shard_map(
            lambda t, c: jax.tree.map(
                lambda x: x[None], prologue_stats(t, c, axis_name)),
            mesh=mesh, in_specs=(table_specs(table), constraint_specs(mcons)),
            out_specs=P("sp"), check_vma=False)
        return jax.tree.map(np.asarray, jax.jit(fn)(table, mcons))

    got, alone = stats("sp"), stats(None)
    assert want.zone_present.sum() == 8 and want.region_present.sum() == 4
    for name in vars(want):
        for s in range(SP):
            np.testing.assert_array_equal(getattr(got, name)[s],
                                          getattr(want, name), name)
    # a shard's own view would not do: it sees its own zones alone
    assert all(alone.zone_present[s].sum() == 2 for s in range(SP))
    assert not (alone.spread_min == want.spread_min[None]).all()
