"""Required inter-pod anti-affinity exact inside a wave: ``greedy_assign``
with ``interpod`` against a plain Python sequential scan, bind for bind.

The scan is the rule as upstream applies it at every bind, over the
candidates the wave brought: pod i takes the first of its candidates
that still has room after pods j < i and that no earlier pod of the wave
has closed to it.  An earlier bound pod j closes the candidates in its
domain of a topology where the two conflict: j matches a required
anti-affinity term of i, or i one of j's (the symmetry rule).  A required
affinity term that selects its own pod and that no pod matched at wave
start bootstraps: the first such pod goes anywhere, and once an earlier
bound pod of the wave matches the term, a candidate has to share a
domain with one of them.  Domain 0 of a zone or region is no domain.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s1m_tpu.config import (
    PodSpec,
    TOPO_HOSTNAME,
    TOPO_REGION,
    TOPO_ZONE,
    TableSpec,
)
from k8s1m_tpu.engine import cycle
from k8s1m_tpu.engine.assign import (
    UNBOUND_REASONS,
    WaveInterpod,
    WaveSkew,
    greedy_assign,
    unbound_by_reason,
)
from k8s1m_tpu.ops.priority import unpack_score

TOPOS = (TOPO_HOSTNAME, TOPO_ZONE, TOPO_REGION)


def plain_scan(raw, terms):
    """The rule, one pod at a time, in Python: (node_row, bound, score,
    chosen_k, blocked)."""
    (idx, prio, cpu, mem, pods, pcpu, pmem, valid, zone, region) = raw
    b, k = idx.shape
    node_of = np.full(b, -1)
    chosen = np.zeros(b, int)
    blocked = np.zeros(b, bool)
    where = {}                       # j -> (row, zone, region) of its choice
    matches = lambda j, tid: any(
        terms["inc_valid"][j, a] and terms["inc_tid"][j, a] == tid
        for a in range(terms["inc_tid"].shape[1]))
    refs = lambda i: [
        (terms["ipa_tid"][i, r], terms["ipa_topo"][i, r],
         terms["ipa_required"][i, r], terms["ipa_anti"][i, r],
         terms["ipa_self"][i, r])
        for r in range(terms["ipa_tid"].shape[1]) if terms["ipa_valid"][i, r]]

    def same(j, c, topo):
        d = where[j][topo]
        return d == c[topo] and (topo == TOPO_HOSTNAME or d != 0)

    for i in range(b):
        room, opened = [], []
        for kk in range(k):
            row = int(idx[i, kk])
            if prio[i, kk] < 0 or row < 0:
                room.append(False)
                opened.append(False)
                continue
            took = [j for j in where if where[j][0] == row]
            ok = (pcpu[i] <= cpu[i, kk] - sum(pcpu[j] for j in took)
                  and pmem[i] <= mem[i, kk] - sum(pmem[j] for j in took)
                  and pods[i, kk] - len(took) >= 1)
            c = (row, int(zone[i, kk]), int(region[i, kk]))
            closed = False
            for j in where:
                for tid, topo, req, anti, _self in refs(i):
                    if req and anti and matches(j, tid) and same(j, c, topo):
                        closed = True
                for tid, topo, req, anti, _self in refs(j):
                    if req and anti and matches(i, tid) and same(j, c, topo):
                        closed = True
            for tid, topo, req, anti, self_ in refs(i):
                if not req or anti or not self_ or terms["tgt_total"][tid]:
                    continue
                selected = [j for j in where if matches(j, tid)]
                if selected and not any(same(j, c, topo) for j in selected):
                    closed = True
            room.append(ok)
            opened.append(ok and not closed)
        if not valid[i]:
            continue
        if any(opened):
            kk = opened.index(True)
            chosen[i] = kk
            node_of[i] = idx[i, kk]
            where[i] = (int(idx[i, kk]), int(zone[i, kk]), int(region[i, kk]))
        else:
            blocked[i] = any(room)
    bound = node_of >= 0
    score = np.where(
        bound, np.asarray(unpack_score(jnp.asarray(
            np.take_along_axis(prio, chosen[:, None], axis=1)[:, 0]))), -1)
    return node_of, bound, score, chosen, blocked


def random_wave(rng, b, k, nodes, *, slots=(1, 4), terms=6, refs=2, incs=2,
                valid=0.95, holes=0.1):
    """``b`` pods, ``k`` distinct candidate rows each among ``nodes`` (node
    n in zone n % 5 and region n % 3, both 0 — no label — for some),
    every pod with a few of ``terms`` term slots of random topology,
    required or not, anti or not, and the terms it matches."""
    idx = np.stack([rng.permutation(nodes)[:k] for _ in range(b)]).astype(np.int32)
    prio = np.sort(rng.integers(0, 1 << 20, (b, k)), axis=1)[:, ::-1].astype(np.int32)
    idx[rng.random((b, k)) < holes] = -1
    prio[rng.random((b, k)) < holes / 2] = -1
    zone_of = np.where(np.arange(nodes) % 7 == 3, 0, np.arange(nodes) % 5 + 1)
    region_of = np.where(np.arange(nodes) % 11 == 5, 0, np.arange(nodes) % 3 + 1)
    room = rng.integers(*slots, nodes)
    full = lambda v: np.full((b, k), v, np.int32)
    raw = (idx, prio, full(32000), full(64 << 20),
           np.where(idx >= 0, room[np.maximum(idx, 0)], 0).astype(np.int32),
           np.full(b, 100, np.int32), np.full(b, 200 << 10, np.int32),
           rng.random(b) < valid,
           np.where(idx >= 0, zone_of[np.maximum(idx, 0)], 0).astype(np.int32),
           np.where(idx >= 0, region_of[np.maximum(idx, 0)], 0).astype(np.int32))
    topo_of = rng.choice(TOPOS, terms)
    tid = rng.integers(0, terms, (b, refs)).astype(np.int32)
    inc_tid = rng.integers(0, terms, (b, incs)).astype(np.int32)
    fields = dict(
        ipa_valid=rng.random((b, refs)) < 0.6,
        ipa_tid=tid,
        ipa_topo=topo_of[tid].astype(np.int32),
        ipa_required=rng.random((b, refs)) < 0.8,
        ipa_anti=rng.random((b, refs)) < 0.6,
        inc_valid=rng.random((b, incs)) < 0.5,
        inc_tid=inc_tid,
        tgt_total=np.where(rng.random(terms) < 0.5, 0,
                           rng.integers(1, 9, terms)).astype(np.int32),
    )
    fields["ipa_self"] = np.array([
        [any(fields["inc_valid"][i, a] and inc_tid[i, a] == tid[i, r]
             for a in range(incs)) for r in range(refs)] for i in range(b)])
    return raw, fields


def interpod_of(fields, slots):
    return WaveInterpod(slots=slots, **{k: jnp.asarray(v) for k, v in fields.items()})


def no_skew(b, k):
    """A ``WaveSkew`` that holds nothing back: the path of a wave that
    counts skew alone (its blocks, the step that finishes them)."""
    z = jnp.zeros((b, 1), jnp.int32)
    return WaveSkew(
        counts=jnp.zeros((1, 10), jnp.int32), present=jnp.ones((10,), bool),
        zones=6, cid=z, topo=z + TOPO_ZONE, max_skew=z + 1, self_inc=z,
        hard=jnp.zeros((b, 1), bool), inc_valid=jnp.zeros((b, 1), bool),
        inc_cid=z, inc_topo=z,
    )


@jax.jit
def _rounds(raw, ip):
    return greedy_assign(*raw[:8], None, raw[8], raw[9], ip)


@jax.jit
def _scan(raw, ip, skew):
    return greedy_assign(*raw[:8], skew, raw[8], raw[9], ip)


def check(raw, fields, terms):
    want = plain_scan(raw, fields)
    b, k = raw[0].shape
    ip = interpod_of(fields, terms)
    args = tuple(map(jnp.asarray, raw))
    for path, out in (("rounds", _rounds(args, ip)),
                      ("scan", _scan(args, ip, no_skew(b, k)))):
        node_row, bound, score, chosen_k, _legal, settled, blocked = out
        for name, got, exp in zip(
                ("node_row", "bound", "score", "blocked"),
                (node_row, bound, score, blocked),
                (want[0], want[1], want[2], want[4])):
            assert (np.asarray(got) == exp).all(), (path, name, np.flatnonzero(
                np.asarray(got) != exp)[:8])
        got_k = np.asarray(chosen_k)
        assert (got_k[want[1]] == want[3][want[1]]).all(), path
        assert int(np.asarray(settled)[:2].sum()) == int(raw[7].sum())
    return want


@pytest.mark.parametrize("seed", range(10))
@pytest.mark.parametrize("b,k,nodes", [(48, 4, 16), (96, 4, 40), (64, 9, 24)])
def test_the_gate_gives_the_plain_scans_binds(seed, b, k, nodes):
    rng = np.random.default_rng([seed, b, k, nodes])
    raw, fields = random_wave(rng, b, k, nodes)
    want = check(raw, fields, 6)
    # the waves hold what the gate is about: binds, pods it sent back
    assert want[1].any()


@pytest.mark.parametrize("topo", TOPOS)
def test_a_deployment_with_required_anti_affinity_takes_a_domain_a_pod(topo):
    """Every pod selects itself and the others: one pod a domain, the
    rest sent back (capacity is ample; ties on every candidate)."""
    rng = np.random.default_rng(topo)
    b, k, nodes = 40, 4, 30
    raw, fields = random_wave(rng, b, k, nodes, slots=(50, 51), holes=0.0, valid=1.0)
    fields.update(
        ipa_valid=np.ones((b, 1), bool), ipa_tid=np.zeros((b, 1), np.int32),
        ipa_topo=np.full((b, 1), topo, np.int32),
        ipa_required=np.ones((b, 1), bool), ipa_anti=np.ones((b, 1), bool),
        ipa_self=np.ones((b, 1), bool), inc_valid=np.ones((b, 1), bool),
        inc_tid=np.zeros((b, 1), np.int32), tgt_total=np.zeros(1, np.int32))
    node_row, bound, _s, _k, blocked = check(raw, fields, 1)
    dom = {TOPO_HOSTNAME: lambda r: r, TOPO_ZONE: lambda r: r % 7 == 3 or r % 5 + 1,
           TOPO_REGION: lambda r: r % 11 == 5 or r % 3 + 1}[topo]
    taken = [dom(int(r)) for r in node_row[bound]]
    labelled = [d for d in taken if d is not True]
    assert len(labelled) == len(set(labelled))
    assert blocked.any() and not (bound & blocked).any()


def test_the_bootstrap_keeps_a_term_to_the_first_pods_domain():
    """Required zone affinity to itself, no pod matching at wave start: the
    first pod goes anywhere, every later one to its zone."""
    rng = np.random.default_rng(7)
    b, k, nodes = 32, 4, 40
    raw, fields = random_wave(rng, b, k, nodes, slots=(50, 51), holes=0.0, valid=1.0)
    fields.update(
        ipa_valid=np.ones((b, 1), bool), ipa_tid=np.zeros((b, 1), np.int32),
        ipa_topo=np.full((b, 1), TOPO_ZONE, np.int32),
        ipa_required=np.ones((b, 1), bool), ipa_anti=np.zeros((b, 1), bool),
        ipa_self=np.ones((b, 1), bool), inc_valid=np.ones((b, 1), bool),
        inc_tid=np.zeros((b, 1), np.int32), tgt_total=np.zeros(1, np.int32))
    node_row, bound, *_ = check(raw, fields, 1)
    zones = {int(raw[8][i, list(raw[0][i]).index(node_row[i])])
             for i in np.flatnonzero(bound)}
    assert len(zones) == 1 and bound.sum() > 1
    # with a matching pod anywhere at wave start the rule is off: zones free
    fields["tgt_total"] = np.ones(1, np.int32)
    node_row, bound, *_ = check(raw, fields, 1)
    assert bound.all()


def test_unbound_pods_are_counted_under_interpod():
    rng = np.random.default_rng(3)
    raw, fields = random_wave(rng, 96, 4, 12)
    args = tuple(map(jnp.asarray, raw))
    out = _rounds(args, interpod_of(fields, 6))
    node_row, bound, _s, _k, legal, _settled, blocked = out
    reasons = dict(zip(UNBOUND_REASONS, np.asarray(unbound_by_reason(
        bound, legal, args[0], args[1], args[7], blocked)).tolist()))
    assert reasons["interpod"] == int((np.asarray(blocked) & raw[7]).sum()) > 0
    assert reasons["skew"] == 0
    assert sum(reasons.values()) == int((raw[7] & ~np.asarray(bound)).sum())


# ---- through the step ------------------------------------------------------------


def test_thirty_two_replicas_of_a_required_anti_affinity_deployment_on_64_nodes():
    """One wave of a Deployment whose pods may not share a node: 32 pods,
    32 nodes (the parent, reading the wave-start counts alone, put two on
    one node three times)."""
    from k8s1m_tpu.cluster.workload import affinity_deployment
    from k8s1m_tpu.engine import schedule_batch
    from k8s1m_tpu.plugins.registry import Profile
    from k8s1m_tpu.snapshot import NodeInfo, NodeTableHost, PodBatchHost
    from k8s1m_tpu.snapshot.constraints import ConstraintTracker, empty_constraints

    spec = TableSpec(max_nodes=64, max_zones=8, max_regions=4,
                     spread_slots=1, affinity_slots=4)
    host = NodeTableHost(spec)
    for i in range(64):
        host.upsert(NodeInfo(f"n{i}", cpu_milli=32000, mem_kib=64 << 20, pods=110,
                             labels={"topology.kubernetes.io/zone": f"z{i % 4}"}))
    tracker = ConstraintTracker(spec)
    pods = affinity_deployment(tracker, "cache", 32, topo=TOPO_HOSTNAME,
                               required=True, anti=True)
    enc = PodBatchHost(PodSpec(batch=32, affinity_refs=1, ipa_incs=1,
                               spread_refs=1, spread_incs=1), spec, host.vocab)
    _t, _c, asg = schedule_batch(
        host.to_device(), enc.encode(pods), jax.random.PRNGKey(0),
        profile=Profile(), constraints=empty_constraints(spec), chunk=64, k=4)
    rows = np.asarray(asg.node_row)
    assert (rows >= 0).all() and len(set(rows.tolist())) == 32


def test_a_wave_without_a_required_inter_pod_term_builds_no_gate():
    """The gate is decided by one rule, ``carries_required_ipa``: a pod of
    the wave carries a required inter-pod term.  Both encoders mark such a
    wave's groups with ``GATE_GROUP`` and the packed step reads the mark;
    ``schedule_batch`` applies the rule to its batch.  A wave of preferred
    terms alone, and the four cells that carry no term, build the step
    they built before."""
    from k8s1m_tpu.cluster.workload import affinity_deployment
    from k8s1m_tpu.snapshot import NodeTableHost
    from k8s1m_tpu.snapshot.constraints import ConstraintTracker
    from k8s1m_tpu.snapshot.hotfeed import HotPodBatchHost
    from k8s1m_tpu.snapshot.pod_encoding import (
        GATE_GROUP,
        PodBatchHost,
        carries_required_ipa,
    )

    assert not cycle.has_interpod_gate(frozenset(), True)
    assert not cycle.has_interpod_gate(frozenset({"spread", "sinc"}), True)
    assert not cycle.has_interpod_gate(frozenset({"ipa", "iinc"}), True)
    assert not cycle.has_interpod_gate(frozenset({"ipa", GATE_GROUP}), False)
    assert cycle.has_interpod_gate(frozenset({"ipa", GATE_GROUP}), True)
    spec = TableSpec(max_nodes=64, max_zones=8, max_regions=4,
                     spread_slots=1, affinity_slots=4)
    pod_spec = PodSpec(batch=8, affinity_refs=1, ipa_incs=1,
                       spread_refs=1, spread_incs=1)
    vocab = NodeTableHost(spec).vocab
    for required, anti in ((False, True), (False, False), (True, True),
                           (True, False)):
        pods = affinity_deployment(ConstraintTracker(spec), "d", 8,
                                   required=required, anti=anti)
        for enc in (PodBatchHost(pod_spec, spec, vocab),
                    HotPodBatchHost(pod_spec, spec, vocab)):
            packed = enc.encode_packed(pods)
            assert "ipa" in packed.groups
            assert (GATE_GROUP in packed.groups) is required
            assert carries_required_ipa(
                packed.fields["ipa_valid"], packed.fields["ipa_required"]
            ) is required
    raw, fields = random_wave(np.random.default_rng(0), 16, 4, 8)
    args = tuple(map(jnp.asarray, raw))
    plain = jax.jit(lambda *a: greedy_assign(*a)).lower(*args[:8]).as_text(debug_info=True)
    gated = _rounds.lower(args, interpod_of(fields, 6)).as_text(debug_info=True)
    assert "interpod_gate" not in plain and "interpod_gate" in gated
