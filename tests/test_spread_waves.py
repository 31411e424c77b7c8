"""PodTopologySpread inside a wave: the batched step and the served path
against a plain sequential scheduler.

The reference here is upstream's order of events written out in Python:
one pod at a time, in wave order, with exact counts — a bind is legal if
the pod's hard zone constraint holds on the counts as the pods before it
left them (plugins/topology.py's documented filter: ``count + self - min
over the zones that hold nodes <= maxSkew``) and the node has room.  The
batched step (``schedule_batch(in_wave_skew=True)``) has to agree with it
bind for bind; the count tables it leaves on the device have to be the
replay's; and through ``Coordinator`` + ``MemStore`` the client's own
watch history has to read 0 on the benchmark's reference for the
deployment (benchmark/references/spread.py) — and above 0 with the
in-wave count switched off.
"""

import importlib.util
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s1m_tpu.config import PodSpec, TOPO_HOSTNAME, TOPO_ZONE, TableSpec
from k8s1m_tpu.control.objects import decode_pod, encode_pod, pod_key
from k8s1m_tpu.engine import assign, schedule_batch
from k8s1m_tpu.engine.assign import (
    UNBOUND_REASONS, greedy_assign, unbound_by_reason,
)
from k8s1m_tpu.engine.cycle import candidates, prologue_stats, wave_skew
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot import NodeInfo, NodeTableHost, PodBatchHost
from k8s1m_tpu.snapshot.constraints import ConstraintTracker, empty_constraints
from k8s1m_tpu.snapshot.node_table import REGION_LABEL, ZONE_LABEL
from k8s1m_tpu.tools.make_pods import build_pod
from chip_smoke import sequential_assign    # conftest.py: the root is on the path

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ZONES, NODES, WAVE, DEPLOYMENTS = 8, 256, 64, 4
SPEC = TableSpec(
    max_nodes=NODES, max_zones=ZONES + 1, max_regions=5, spread_slots=8,
    affinity_slots=1,
)
POD_SPEC = PodSpec(
    batch=WAVE, spread_refs=2, spread_incs=2, affinity_refs=1, ipa_incs=1
)
PROFILE = Profile(node_affinity=0, interpod_affinity=0)
K = SPEC.max_zones
ZONE_KEY = "topology.kubernetes.io/zone"


def spread_of(app: str, hostname: str = "ScheduleAnyway") -> list[dict]:
    """The Kubernetes documentation's two-constraint example."""
    select = {"labelSelector": {"matchLabels": {"app": app}}}
    return [
        {"maxSkew": 1, "topologyKey": ZONE_KEY,
         "whenUnsatisfiable": "DoNotSchedule", **select},
        {"maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
         "whenUnsatisfiable": hostname, **select},
    ]


def build_nodes(host: NodeTableHost, pods_per_node=lambda i: 110) -> None:
    for i in range(NODES):
        host.upsert(NodeInfo(
            f"n{i}", cpu_milli=32000, mem_kib=64 << 20, pods=pods_per_node(i),
            labels={ZONE_LABEL: f"z{i % ZONES}", REGION_LABEL: f"r{i % 4}"},
        ))


def make_wave(tracker, first: int, order: list[int]):
    """Pods ``first ..``, pod j of Deployment ``web-<order[j]>``, as
    intake makes them: ``build_pod``'s keywords, the wire, ``decode_pod``."""
    return [
        decode_pod(
            encode_pod(build_pod(
                first + j, app=f"web-{d}", spread_constraints=spread_of(f"web-{d}")
            )),
            tracker,
        )
        for j, d in enumerate(order)
    ]


class Sequential:
    """The plain reference: exact counts, one pod at a time."""

    def __init__(self, host: NodeTableHost) -> None:
        self.zone = host.zone[:NODES].astype(int)
        self.free_pods = host.pods_alloc[:NODES].astype(int)
        self.free_cpu = host.cpu_alloc[:NODES].astype(int)
        present = sorted(set(self.zone.tolist()) - {0})
        self.present = present
        self.in_zone = {}       # app -> {zone id: pods}
        self.on_node = {}       # app -> {row: pods}

    def counts(self, app):
        return self.in_zone.setdefault(app, dict.fromkeys(self.present, 0))

    def legal(self, pod, row: int) -> bool:
        """The zone constraint at this pod's turn, and room on the node."""
        counts = self.counts(pod.labels["app"])
        z = int(self.zone[row])
        return (
            z != 0 and counts[z] + 1 - min(counts.values()) <= 1
            and self.free_pods[row] >= 1
            and self.free_cpu[row] >= pod.cpu_milli
        )

    def commit(self, pod, row: int) -> None:
        app = pod.labels["app"]
        self.counts(app)[int(self.zone[row])] += 1
        per_node = self.on_node.setdefault(app, {})
        per_node[row] = per_node.get(row, 0) + 1
        self.free_pods[row] -= 1
        self.free_cpu[row] -= pod.cpu_milli


def run_waves(seed: int, backend: str, waves: int, pods_per_node=lambda i: 110):
    """``waves`` waves through the batched step, each replayed: yields
    (pods, candidates, device rows, the replay, the device's state)."""
    rng = random.Random(seed)
    host = NodeTableHost(SPEC)
    build_nodes(host, pods_per_node)
    tracker = ConstraintTracker(SPEC)
    enc = PodBatchHost(POD_SPEC, SPEC, host.vocab)
    table, cons = host.to_device(), empty_constraints(SPEC)
    seq = Sequential(host)
    for w in range(waves):
        order = [d for d in range(DEPLOYMENTS) for _ in range(WAVE // DEPLOYMENTS)]
        rng.shuffle(order)
        pods = make_wave(tracker, w * WAVE, order)
        batch = enc.encode(pods)
        key = jax.random.key(seed * 100 + w)
        cand = candidates(
            table, batch, key, cons, PROFILE, chunk=64, k=K, backend=backend,
            with_affinity=False, in_wave_skew=True,
        )
        table, cons, asg = schedule_batch(
            table, batch, key, profile=PROFILE, constraints=cons, chunk=64,
            k=K, backend=backend, with_affinity=False, in_wave_skew=True,
        )
        yield pods, jax.device_get(cand), jax.device_get(asg), seq, (
            tracker, cons, table)


@pytest.mark.parametrize("backend", ("xla", "pallas"))
@pytest.mark.parametrize("seed", range(3))
def test_a_wave_is_the_sequential_schedule_bind_for_bind(seed, backend):
    """Every bind legal at its turn; the chosen node the first candidate,
    in priority order, that the sequential reference allows; an unbound
    pod had no such candidate; and after every wave the device's count
    tables are the replay's (what the benchmark's door 2 cannot see)."""
    # nodes of zone z3 hold two pods each: late waves find them full
    tight = lambda i: 2 if i % ZONES == 2 else 110
    for pods, cand, asg, seq, (tracker, cons, _t) in run_waves(
            seed, backend, waves=4, pods_per_node=tight):
        assert (np.diff(cand.prio, axis=1) <= 0).all()      # priority order
        for i, pod in enumerate(pods):
            allowed = [
                int(cand.idx[i, j]) for j in range(K)
                if cand.prio[i, j] >= 0 and seq.legal(pod, int(cand.idx[i, j]))
            ]
            row = int(asg.node_row[i])
            if not allowed:
                assert row == -1 and not asg.bound[i]
                continue
            assert row == allowed[0], (i, row, allowed)
            assert seq.legal(pod, row)
            seq.commit(pod, row)
        zone_tab = np.asarray(cons.spread_zone)
        node_tab = np.asarray(cons.spread_node)
        for app, counts in seq.in_zone.items():
            z_slot = tracker.spread_slot("default", {"app": app}, TOPO_ZONE)
            h_slot = tracker.spread_slot("default", {"app": app}, TOPO_HOSTNAME)
            want = np.zeros(SPEC.max_zones, int)
            for z, n in counts.items():
                want[z] = n
            assert (zone_tab[z_slot] == want).all(), app
            per_node = np.zeros(NODES, int)
            for row, n in seq.on_node.get(app, {}).items():
                per_node[row] = n
            assert (node_tab[h_slot] == per_node).all(), app
    # 4 waves of 16 a Deployment: 8 a zone, zone z3 (32 nodes of 2) included
    assert all(set(c.values()) == {8} for c in seq.in_zone.values())


def test_a_full_zone_sends_pods_back_for_skew_and_says_so():
    """One zone with room for 32 pods in all, and six waves that would
    put 48 there: once it is full the other zones may go one above it and
    no further; what is left comes back unbound — under ``capacity`` in
    the wave that fills it (its last slots were candidates, and went to
    earlier pods), under ``skew`` after that (no candidate there at all,
    and no other zone allowed)."""
    tight = lambda i: 1 if i % ZONES == 5 else 110      # 32 slots in z6
    total = np.zeros(len(UNBOUND_REASONS), int)
    bound = 0
    for pods, _cand, asg, seq, _ in run_waves(7, "xla", 6, pods_per_node=tight):
        for i, pod in enumerate(pods):
            if asg.bound[i]:
                assert seq.legal(pod, int(asg.node_row[i]))
                seq.commit(pod, int(asg.node_row[i]))
        total += np.asarray(asg.unbound)
        bound += int(asg.bound.sum())
    reasons = dict(zip(UNBOUND_REASONS, total.tolist()))
    assert reasons["skew"] > 0 < reasons["capacity"]
    assert reasons["no_candidate"] == 0 and bound + total.sum() == 6 * WAVE
    # 32 pods in the tight zone, and every other zone of a Deployment at
    # most one above its count there
    for counts in seq.in_zone.values():
        assert max(counts.values()) - min(counts.values()) <= 1
    assert sum(c[6] for c in seq.in_zone.values()) == 32


@pytest.mark.parametrize("backend", ("xla", "pallas"))
def test_a_wave_of_one_pod_is_what_it_was(backend):
    """One pod a wave: counting inside the wave changes nothing, and the
    best row of each zone holds the row the k best held first."""
    host = NodeTableHost(SPEC)
    build_nodes(host)
    tracker = ConstraintTracker(SPEC)
    enc = PodBatchHost(PodSpec(batch=1, spread_refs=2, spread_incs=2,
                               affinity_refs=1, ipa_incs=1), SPEC, host.vocab)
    state = {False: (host.to_device(), empty_constraints(SPEC)),
             True: (host.to_device(), empty_constraints(SPEC))}
    rng = random.Random(3)
    for i in range(40):
        (pod,) = make_wave(tracker, i, [rng.randrange(DEPLOYMENTS)])
        batch = enc.encode([pod])
        rows = {}
        for in_wave, (table, cons) in state.items():
            table, cons, asg = schedule_batch(
                table, batch, jax.random.key(i), profile=PROFILE,
                constraints=cons, chunk=64, k=K if in_wave else 4,
                backend=backend, with_affinity=False, in_wave_skew=in_wave,
            )
            state[in_wave] = (table, cons)
            rows[in_wave] = (int(asg.node_row[0]), int(asg.score[0]))
        assert rows[True] == rows[False] and rows[True][0] >= 0, i


# ``greedy_assign`` as it stood before it learnt to count skew (and, since
# PR 36, before its rounds): the one sequential scan, kept where the chip
# smoke compares with it too.
parents_greedy_assign = sequential_assign


def contended_candidates(seed: int, b: int = 48, k: int = 4):
    """Candidates that fight: 12 rows with room for two pods each."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(-1, 12, (b, k)).astype(np.int32)
    prio = np.sort(rng.integers(-1, 1 << 20, (b, k)), axis=1)[:, ::-1]
    return (idx, prio.astype(np.int32),
            np.full((b, k), 250, np.int32), np.full((b, k), 1 << 20, np.int32),
            np.full((b, k), 2, np.int32),
            rng.integers(50, 150, b).astype(np.int32),
            np.full(b, 1 << 10, np.int32), rng.random(b) < 0.9)


def loops_of(jaxpr) -> list[str]:
    """The loop primitives of a jaxpr, those inside its sub-jaxprs too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("scan", "while"):
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += loops_of(sub)
    return found


@pytest.mark.parametrize("seed", range(4))
def test_a_wave_without_constraints_is_the_parents_row_for_row(seed):
    """Without ``skew`` the rows are the parent's scan's (by rounds since
    PR 36: tests/test_assign_rounds.py); with count tables in hand but no
    pod carrying a constraint, still its rows, settled in blocks (since
    PR 42: tests/test_assign_blocks.py) — a loop over the blocks, and in it
    a block's rounds and the step that finishes it — and nothing of the
    whole wave's rounds is traced with ``skew``."""
    raw = contended_candidates(seed)
    args = tuple(map(jnp.asarray, raw))
    want = parents_greedy_assign(*args)
    got = greedy_assign(*args)
    assert got[4] is None
    for a, b in zip(got[:4], want):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert (np.asarray(want[0]) == -1).any()        # some did lose
    assert int(got[5][:2].sum()) == int(raw[7].sum())
    # count tables, and pods that carry nothing
    host = NodeTableHost(SPEC)
    build_nodes(host)
    enc = PodBatchHost(
        PodSpec(batch=48, spread_refs=2, spread_incs=2), SPEC, host.vocab)
    batch = enc.encode([build_pod(i) for i in range(48)])
    cons = empty_constraints(SPEC)
    skew = wave_skew(batch, cons, prologue_stats(host.to_device(), cons))
    zone = jnp.ones_like(args[0])
    counted = greedy_assign(*args, skew, zone, zone)
    for a, b in zip(counted[:4], want):
        assert (np.asarray(a) == np.asarray(b)).all()
    left = unbound_by_reason(counted[1], counted[4], args[0], args[1], args[7])
    assert int(left[1]) == 0 and int(left.sum()) == int(
        (raw[7] & ~np.asarray(counted[1])).sum())
    # the blocks' rounds settle most pods (48 pods: three blocks of 16),
    # and the blocks' loops are the only ones traced
    by_rounds, by_scan, evaluations = np.asarray(counted[5]).tolist()
    assert by_rounds > 0 and by_rounds + by_scan == int(raw[7].sum())
    assert evaluations >= 48 // assign._block_pods(48)
    loops = lambda *extra: loops_of(
        jax.make_jaxpr(lambda *a: greedy_assign(*a, *extra))(*args).jaxpr)
    assert loops(skew, zone, zone) == ["while", "while", "while"]  # blocks:
    # their rounds, their step
    assert loops() == ["while", "while"]             # the rounds, the tail


# ---- the served path: store -> Coordinator -> bind_batch -> the watch --------


def load_spread_reference():
    path = os.path.join(ROOT, "benchmark", "references", "spread.py")
    spec = importlib.util.spec_from_file_location("spread_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.numbers


def serve(in_wave_skew: bool, *, pods: int = 4 * WAVE, seed: int = 5):
    """``pods`` pods of four Deployments through the store and a
    pipelined coordinator; returns (binds in the client's watch order as
    (pod, node) index arrays, the pattern, the coordinator's counters'
    growth)."""
    from k8s1m_tpu.control.coordinator import PODS_PREFIX, Coordinator
    from k8s1m_tpu.control.objects import encode_node, node_key
    from k8s1m_tpu.obs.metrics import REGISTRY
    from k8s1m_tpu.store.native import MemStore, prefix_end
    from k8s1m_tpu.tools.make_nodes import build_node

    pattern = [{"app": f"web-{d}", "spread_constraints": spread_of(f"web-{d}")}
               for d in range(DEPLOYMENTS)]
    random.Random(seed).shuffle(pattern)
    sched = REGISTRY.get("coordinator_pods_scheduled_total")
    before = sched.value(outcome="bound")
    with MemStore() as store:
        store.put_batch([
            (node_key(f"kwok-node-{i}"),
             encode_node(build_node(i, zones=ZONES, regions=4)))
            for i in range(NODES)
        ])
        coord = Coordinator(
            store, SPEC, POD_SPEC, PROFILE, chunk=64,
            with_constraints=True, max_attempts=64, backend="xla",
            pipeline=True, depth=2, in_wave_skew=in_wave_skew, seed=seed,
        )
        watch = store.watch(PODS_PREFIX, prefix_end(PODS_PREFIX))
        try:
            coord.bootstrap()
            for i in range(pods):
                pod = build_pod(i, namespace="t", **pattern[i % len(pattern)])
                store.put(pod_key(pod.namespace, pod.name), encode_pod(pod))
            coord.run_until_idle()
            bind_pod, bind_node = [], []
            while True:
                events = watch.poll(4096)
                for ev in events:
                    obj = ev.kv.value
                    at = obj.find(b'"nodeName":"')
                    if at >= 0:
                        node = obj[at + 12:obj.index(b'"', at + 12)]
                        bind_node.append(int(node.rsplit(b"-", 1)[1]))
                        bind_pod.append(int(ev.kv.key.rsplit(b"-", 1)[1]))
                if not events:
                    break
        finally:
            watch.cancel()
            coord.close()
    seen = {"bind_pod": np.asarray(bind_pod), "bind_node": np.asarray(bind_node)}
    return seen, pattern, sched.value(outcome="bound") - before


def test_the_served_path_keeps_the_skew_at_every_bind_the_watch_shows():
    numbers = load_spread_reference()
    seen, pattern, bound = serve(True)
    assert bound == 4 * WAVE and sorted(seen["bind_pod"].tolist()) == list(
        range(4 * WAVE))
    assert numbers(seen, None, nodes={"zones": ZONES}, pattern=pattern,
                   offered=4 * WAVE) == {"zone_skew_exceeded": 0}


def test_the_served_path_with_the_count_off_breaks_it():
    """Wave-start counts alone: the pods of a wave crowd the zones that
    stood at the minimum when it began."""
    numbers = load_spread_reference()
    seen, pattern, bound = serve(False)
    assert bound == 4 * WAVE
    assert numbers(seen, None, nodes={"zones": ZONES}, pattern=pattern,
                   offered=4 * WAVE)["zone_skew_exceeded"] > 10
