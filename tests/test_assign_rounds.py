"""A wave's conflicts resolved in parallel rounds: ``greedy_assign``
against the sequential scan it replaced, bind for bind.

The reference is the scan itself (``test_spread_waves.parents_greedy_assign``:
pod i takes the first of its candidates that still has room after pods
j < i).  Without ``skew`` ``greedy_assign`` reaches the same answer by
evaluating every pod's choice at once until nothing changes and handing
what has not settled to the scan's step, so on every input its four
outputs have to be the scan's, and ``settled`` has to say which of the
two did the work.  Then the same through ``Coordinator.step()``: the
binds of a drive equal a drive with the reference patched in, and the two
counters the mechanism brings add up.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s1m_tpu.engine import assign, cycle
from k8s1m_tpu.engine.assign import SETTLED_BY, greedy_assign
from test_spread_waves import contended_candidates, parents_greedy_assign


@functools.lru_cache(maxsize=None)
def jitted(fn):
    return jax.jit(fn)


def rounds_of(*args):
    out = greedy_assign(*args)
    assert out[4] is None
    return out[:4] + out[5:]


def both(raw):
    """(the rounds' four outputs, the scan's, settled as a list)."""
    args = tuple(map(jnp.asarray, raw))
    *got, settled = jitted(rounds_of)(*args)
    want = jitted(parents_greedy_assign)(*args)
    return ([np.asarray(x) for x in got], [np.asarray(x) for x in want],
            np.asarray(settled).tolist())


def wave(rng, b, k, nodes, *, cpu=32000, mem=64 << 20, slots=110,
         pod_cpu=100, pod_mem=200 << 10, valid=1.0, holes=0.0):
    """``b`` pods with ``k`` distinct candidates each among ``nodes`` nodes
    (fewer than ``k``: the rest of the row is -1), every node with the
    same room; ``pod_cpu`` / ``pod_mem`` an int or a (low, high) range;
    ``holes``: the share of candidate slots emptied (-1) and of priorities
    made infeasible."""
    have = min(k, nodes)
    idx = np.full((b, k), -1, np.int32)
    spread = rng.permuted(np.tile(np.arange(have), (b, 1)), axis=1)
    idx[:, :have] = (
        rng.integers(0, nodes, (b, 1)) + spread * (nodes // have)) % nodes
    prio = np.sort(rng.integers(0, 1 << 20, (b, k)), axis=1)[:, ::-1].copy()
    idx[rng.random((b, k)) < holes] = -1
    prio[rng.random((b, k)) < holes] = -1
    draw = lambda v: (rng.integers(*v, b) if isinstance(v, tuple)
                      else np.full(b, v)).astype(np.int32)
    full = lambda v: np.full((b, k), v, np.int32)
    return (idx, prio.astype(np.int32), full(cpu), full(mem), full(slots),
            draw(pod_cpu), draw(pod_mem), rng.random(b) < valid)


def one_class(rng, b=768, k=4):
    """``affinity-100k.fill``'s case: identical pods chase the same four
    nodes of one score class, 110 slots each."""
    raw = list(wave(rng, b, k, nodes=k))
    raw[0] = np.tile(np.arange(k, dtype=np.int32), (b, 1))
    raw[1] = np.tile(np.arange(k, 0, -1, dtype=np.int32) << 10, (b, 1))
    return tuple(raw)


def chain(rng, b, k):
    """A bump chain as long as the wave: pod i's first candidate is pod
    i-1's second, one slot a node, and pod 0 sits on pod 1's first."""
    raw = list(wave(rng, b, k, nodes=b + 2, slots=1))
    first = np.maximum(np.arange(b, dtype=np.int32), 1)
    raw[0] = np.full((b, k), -1, np.int32)
    raw[0][:, 0], raw[0][:, 1] = first, first + 1
    return tuple(raw)


REGIMES = {
    # name: (builder(rng, b, k), what ``settled`` has to say)
    "uncontended": (lambda r, b, k: wave(r, b, k, nodes=64 * b), "one_round"),
    "contended": (lambda r, b, k: contended_candidates(int(r.integers(1 << 30)), b, k), None),
    "brim_one_slot": (lambda r, b, k: wave(r, b, k, nodes=k - 1, slots=1), None),
    "brim_two_slots": (lambda r, b, k: wave(r, b, k, nodes=k - 1, slots=2), None),
    "chain": (chain, "scan"),
    "holes": (lambda r, b, k: wave(r, b, k, nodes=b // 8, slots=3, valid=0.8, holes=0.2), None),
    "cpu_limited": (lambda r, b, k: wave(r, b, k, nodes=b // 4, cpu=250, pod_cpu=(50, 150)), None),
    "mem_limited": (lambda r, b, k: wave(r, b, k, nodes=b // 4, mem=3 << 10, pod_mem=(1 << 9, 1 << 11)), None),
    "slot_limited": (lambda r, b, k: wave(r, b, k, nodes=b // 4, slots=2), None),
}


def check(raw, expect=None):
    got, want, (by_rounds, by_scan, evaluations) = both(raw)
    for name, a, b in zip(("node_row", "bound", "score", "chosen_k"), got, want):
        assert (a == b).all(), (name, int((a != b).sum()))
    valid = int(raw[7].sum())
    assert by_rounds + by_scan == valid and evaluations >= 0
    if expect == "one_round":
        assert (by_rounds, by_scan) == (valid, 0) and evaluations in (1, 2)
    elif expect == "scan":
        assert by_scan > 0
    return got, (by_rounds, by_scan, evaluations)


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("b,k", [(256, 4), (512, 4), (384, 9)])
@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_the_rounds_give_the_scans_binds(regime, b, k, seed):
    build, expect = REGIMES[regime]
    check(build(np.random.default_rng([seed, b, k]), b, k), expect)


@pytest.mark.parametrize("seed", range(3))
def test_identical_pods_on_one_score_class_settle_in_about_k_rounds(seed):
    raw = one_class(np.random.default_rng(seed))
    got, (by_rounds, by_scan, evaluations) = check(raw)
    assert got[1].sum() == 4 * 110 and by_scan == 0
    assert evaluations <= raw[0].shape[1] + 2


def test_a_chain_as_long_as_the_wave_is_left_to_the_scan_after_the_grace():
    """The stop rule: a pod a round pays for no round, so the rounds stop
    at the grace and the scan's step takes nearly the whole wave."""
    b = 512
    got, (by_rounds, by_scan, evaluations) = check(
        chain(np.random.default_rng(0), b, 4), "scan")
    assert got[1].all() and (got[3][1:] == 1).all()      # every pod bumped once
    # a pod a round: m = evaluations + 1 stops paying at the grace
    steps = assign._round_steps(b, 4)
    assert evaluations <= (assign._GRACE_ROUNDS * steps + 1) // (steps - 1) + 1
    assert by_scan >= b - 2 * evaluations - 2


def test_behind_its_last_pod_with_a_candidate_a_wave_has_nothing_to_scan():
    """A short wave (the brim's retried pods and their padding): the chain
    goes to the scan, the padding behind it to nobody."""
    b, front = 512, 200
    raw = list(chain(np.random.default_rng(1), b, 4))
    raw[1][front:] = -1                     # the candidates stage's padding
    raw[7] = np.arange(b) < front
    got, (by_rounds, by_scan, evaluations) = check(tuple(raw), "scan")
    assert got[1].sum() == front and by_rounds + by_scan == front
    # a wave of candidates nobody can take any more: nothing to settle
    raw[1][:] = -1
    assert check(tuple(raw))[1] == (front, 0, 0)


def test_the_stop_rule_is_a_function_of_the_prefix_the_rounds_and_the_wave():
    steps = assign._round_steps(4096, 4)
    go = lambda m, rounds, last=4096: bool(
        assign._go_on(m, rounds, last, 4096, 4))
    assert go(1, 0) and go(1, assign._GRACE_ROUNDS)
    assert not go(1, assign._GRACE_ROUNDS + 1)            # nothing paid for it
    assert go(steps * 3, assign._GRACE_ROUNDS + 3)        # the prefix did
    assert not go(4096 - steps, 1) and not go(4096, 1)    # the scan is cheaper
    assert not go(300 - steps, 1, last=300)               # a short wave's end
    assert assign._round_steps(256, 4) < steps            # a small wave's round


@pytest.mark.parametrize("regime", ["uncontended", "slot_limited"])
def test_a_wave_of_4096_by_4(regime):
    build, expect = REGIMES[regime]
    check(build(np.random.default_rng(7), 4096, 4), expect)


# ---- through the coordinator -------------------------------------------------


def scan_in_place_of_the_rounds(*args):
    """``greedy_assign``'s signature over the reference scan (every pod
    counted under ``scan``)."""
    assert args[8] is None                       # no ``skew`` in these drives
    valid = args[7].sum().astype(jnp.int32)
    return (*parents_greedy_assign(*args[:8]), None,
            jnp.stack([jnp.int32(0), valid, jnp.int32(0)]))


def clear_step_caches():
    for fn in (cycle._jitted_schedule, cycle._jitted_schedule_packed,
               cycle._jitted_schedule_delta):
        fn.cache_clear()


def counters():
    """(pods by path, evaluations, outcomes of dispatched pods) so far."""
    import k8s1m_tpu.control.coordinator  # noqa: F401  (registers them)
    from k8s1m_tpu.obs.metrics import REGISTRY

    pods = REGISTRY.get("coordinator_assign_pods_total")
    sched = REGISTRY.get("coordinator_pods_scheduled_total")
    return (
        {p: pods.value(path=p) for p in SETTLED_BY},
        REGISTRY.get("coordinator_assign_rounds_total").value(),
        sum(sched.value(outcome=o) for o in ("bound", "retry", "unschedulable")),
    )


def grown(before):
    by_path, evaluations, outcomes = counters()
    return ({p: by_path[p] - before[0][p] for p in SETTLED_BY},
            evaluations - before[1], outcomes - before[2])


def drive(*, waves=4, wave=128, nodes=40, slots=8, seed=3):
    """``waves`` waves of ``wave`` pods onto ``nodes`` nodes that hold
    ``slots`` pods each (so the later waves fight for the last room)
    through the store and a pipelined coordinator.  Returns ({pod: node}
    from the client's watch, how the counters grew)."""
    from k8s1m_tpu.config import PodSpec, TableSpec
    from k8s1m_tpu.control.coordinator import PODS_PREFIX, Coordinator
    from k8s1m_tpu.control.objects import (
        encode_node, encode_pod, node_key, pod_key,
    )
    from k8s1m_tpu.faultline.policy import RetryPolicy
    from k8s1m_tpu.plugins.registry import Profile
    from k8s1m_tpu.store.native import MemStore, prefix_end
    from k8s1m_tpu.tools.make_nodes import build_node
    from k8s1m_tpu.tools.make_pods import build_pod

    before = counters()
    binds = {}
    with MemStore() as store:
        store.put_batch([
            (node_key(f"kwok-node-{i}"),
             encode_node(build_node(i, pods=slots, zones=4, regions=2)))
            for i in range(nodes)
        ])
        coord = Coordinator(
            store, TableSpec(max_nodes=64), PodSpec(batch=wave),
            Profile(node_affinity=0, interpod_affinity=0), chunk=64,
            backend="xla", pipeline=True, depth=2, seed=seed, max_attempts=2,
            retry_policy=RetryPolicy(base_delay_s=0.0),
        )
        watch = store.watch(PODS_PREFIX, prefix_end(PODS_PREFIX))
        try:
            coord.bootstrap()
            for lo in range(0, waves * wave, wave):
                for i in range(lo, lo + wave):
                    pod = build_pod(i, namespace="t")
                    store.put(pod_key(pod.namespace, pod.name), encode_pod(pod))
                coord.step()
            coord.run_until_idle()
            while events := watch.poll(4096):
                for ev in events:
                    obj = ev.kv.value
                    at = obj.find(b'"nodeName":"')
                    if at >= 0:
                        binds[ev.kv.key] = obj[at + 12:obj.index(b'"', at + 12)]
        finally:
            watch.cancel()
            coord.close()
    return binds, grown(before)


def test_a_drive_binds_as_the_scan_and_counts_what_settled_its_pods(monkeypatch):
    binds, (by_path, evaluations, outcomes) = drive()
    # every valid pod a wave dispatched left it bound, sent back or
    # given up: the two paths hold exactly those
    assert by_path["rounds"] + by_path["scan"] == outcomes > len(binds) > 0
    assert by_path["rounds"] > 0 and evaluations >= 4       # one a wave at least
    assert len(binds) == 40 * 8                              # the cluster is full
    clear_step_caches()
    monkeypatch.setattr(cycle, "greedy_assign", scan_in_place_of_the_rounds)
    try:
        want, (ref_path, ref_evaluations, ref_outcomes) = drive()
    finally:
        clear_step_caches()
    assert binds == want
    assert (ref_path["rounds"], ref_evaluations) == (0, 0)
    assert ref_path["scan"] == ref_outcomes == outcomes


def test_a_drive_that_counts_skew_counts_every_pod_under_scan():
    from test_spread_waves import WAVE, serve

    before = counters()
    seen, _pattern, bound = serve(True, pods=2 * WAVE)
    by_path, evaluations, outcomes = grown(before)
    assert bound == 2 * WAVE == len(seen["bind_pod"])
    assert by_path == {"rounds": 0, "scan": outcomes} and evaluations == 0
