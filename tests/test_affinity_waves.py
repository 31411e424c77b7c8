"""affinity-100k's pods and nodes at a small size: the candidates stage
against the plain oracle (feasibility and score of every candidate, and
that the best feasible node of the window is among them) on the XLA scan
and the interpreted fused kernel, on both table layouts; waves of the mix
through the store and a pipelined ``Coordinator`` on both backends, every
bind held to ``oracle_feasible`` and to the cell's own reference; a table
with fewer nodes than rows under ``score_pct`` 5; and the builders'
output without a new keyword, byte for byte what it was.
"""

from __future__ import annotations

import importlib.util
import json
import os
import random

import jax
import numpy as np
import pytest

from k8s1m_tpu.config import PodSpec, TableSpec
from k8s1m_tpu.control.objects import (
    decode_node, decode_pod, encode_node, encode_pod, node_key, pod_key,
)
from k8s1m_tpu.engine.cycle import (
    candidates, candidates_kernel, has_selectors, sample_offset_for,
    sample_rows_for,
)
from k8s1m_tpu.ops.priority import JITTER_BITS
from k8s1m_tpu.oracle import oracle_feasible, oracle_score
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot import NodeTableHost, PodBatchHost
from k8s1m_tpu.snapshot.packing import is_packed, pack_table_auto
from k8s1m_tpu.tools.make_nodes import build_node
from k8s1m_tpu.tools.make_pods import build_pod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*rel):
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


CONFIG = _json("benchmark", "configs", "affinity-100k.json")
NODE_KW = {k: v for k, v in CONFIG["nodes"].items()
           if k not in ("count", "cordon_every")}
SHAPES = [{k: v for k, v in s.items() if k != "weight"}
          for s in _json("benchmark", "pods", "affinity.json")["shapes"]
          for _ in range(s["weight"])]
PROFILE = Profile(**CONFIG["profile"])
WEIGHTS = (PROFILE.least_allocated, PROFILE.balanced_allocation,
           PROFILE.taint_toleration, PROFILE.node_affinity)
NODES, CHUNK, WAVE = 2000, 256, 256
SPEC = TableSpec(**{**CONFIG["table_spec"], "max_nodes": 2048})
POD_SPEC = PodSpec(**{**CONFIG["pod_spec"], "batch": WAVE})


def node(i: int):
    n = build_node(i, **NODE_KW)
    n.unschedulable = i % 16 == 15
    return n


def pods_of(pattern, lo: int, n: int, namespace: str = "t"):
    return [build_pod(i, namespace=namespace, **pattern[i % len(pattern)])
            for i in range(lo, lo + n)]


def pattern_of(seed: int):
    pattern = list(SHAPES)
    random.Random(seed).shuffle(pattern)
    return pattern


def reference_numbers():
    """benchmark/references/affinity.py, loaded by path as the harness
    loads it (it imports nothing)."""
    path = os.path.join(ROOT, "benchmark", "references", "affinity.py")
    spec = importlib.util.spec_from_file_location("affinity_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.numbers


# ---- the mix really is the cell's ------------------------------------------


def test_the_mix_puts_every_selector_group_in_a_wave_and_names_its_kernel():
    host = NodeTableHost(SPEC)
    host.bulk_upsert([node(i) for i in range(NODES)])
    packed = PodBatchHost(POD_SPEC, SPEC, host.vocab).encode_packed(
        pods_of(pattern_of(1), 0, WAVE))
    assert packed.groups == {"tol", "sel", "req", "pref", "qkey"}
    assert has_selectors(packed.groups)
    assert candidates_kernel("pallas", packed.groups, False) == "fused_topk_affinity"
    assert candidates_kernel("xla", packed.groups, False) == "filter_score_topk_affinity"
    plain = frozenset({"tol"})
    assert candidates_kernel("pallas", plain, False) == "fused_topk"
    assert candidates_kernel("pallas", plain, True) == "fused_topk_constraints"
    assert candidates_kernel("pallas", packed.groups, True) == \
        "fused_topk_affinity_constraints"
    assert candidates_kernel("pallas", plain, False, "delta") == "delta_plane_topk"
    assert candidates_kernel("xla", plain, False, "delta") == "plane_topk"
    # at most three taints and six labels a node, three taint triples in all
    assert len(list(host.vocab.taints.items())) == 3
    assert max(len(node(i).taints) + node(i).unschedulable
               for i in range(640)) == SPEC.taint_slots == 3


# ---- candidates against the oracle ------------------------------------------


@pytest.fixture(scope="module")
def seeded():
    """Nodes with some of their room taken, so that scores differ."""
    rng = np.random.default_rng(35)
    host = NodeTableHost(SPEC)
    nodes = [node(i) for i in range(NODES)]
    host.bulk_upsert(nodes)
    used = {}
    for i, n in enumerate(nodes):
        cpu = int(rng.integers(0, 30)) * 1000
        mem = int(rng.integers(0, 30)) << 21
        count = int(rng.integers(0, 110))
        host.cpu_req[host.row_of(n.name)] = cpu
        host.mem_req[host.row_of(n.name)] = mem
        host.pods_req[host.row_of(n.name)] = count
        used[host.row_of(n.name)] = (n, (cpu, mem, count))
    return host, used


@pytest.mark.parametrize("layout", ["packed", "plain"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_candidates_are_feasible_and_scored_as_the_oracle_scores(
        seeded, backend, layout):
    host, used = seeded
    table = pack_table_auto(host, SPEC) if layout == "packed" else host.to_device()
    assert is_packed(table) == (layout == "packed")
    pods = pods_of(pattern_of(7), 0, WAVE)
    batch = PodBatchHost(POD_SPEC, SPEC, host.vocab).encode(pods)
    rows = sample_rows_for(SPEC.max_nodes, 25, CHUNK)
    k = 4
    for window_i in (1, 3):          # a full window, and the one with empty rows
        offset = sample_offset_for(window_i, SPEC.max_nodes, rows)
        cand = jax.jit(lambda t, b, key: candidates(
            t, b, key, None, PROFILE, chunk=CHUNK, k=k, backend=backend,
            with_affinity=True, window=(offset, rows),
        ))(table, batch, jax.random.key(3))
        idx, prio = np.asarray(cand.idx), np.asarray(cand.prio)
        in_window = {r: v for r, v in used.items() if offset <= r < offset + rows}
        for b, pod in enumerate(pods):
            scores = {
                r: oracle_score(n, pod, req, taint_slots=SPEC.taint_slots,
                                weights=WEIGHTS)
                for r, (n, req) in in_window.items()
                if oracle_feasible(n, pod, req)
            }
            got = [(int(r), int(p)) for r, p in zip(idx[b], prio[b]) if p >= 0]
            assert len(got) == min(k, len(scores)), (pod.key, window_i)
            assert len({r for r, _p in got}) == len(got)
            for r, p in got:
                assert r in scores, (pod.key, r)            # feasible
                assert p >> JITTER_BITS == scores[r], (pod.key, r)
            if scores:
                # the best rows the window holds, by score, are the candidates
                best = sorted(scores.values(), reverse=True)[:len(got)]
                assert sorted((p >> JITTER_BITS for _r, p in got),
                              reverse=True) == best, pod.key
        # every shape of the mix found a node, and the mix is not all alike
        assert (prio[:, 0] >= 0).all()
        assert len({int(p) >> JITTER_BITS for p in prio[:, 0]}) > 1


# ---- through the store and the coordinator -----------------------------------


def serve(backend: str, *, nodes: int = NODES, spec: TableSpec = SPEC,
          waves: int = 3, seed: int = 11, score_pct: int = 25,
          max_attempts: int = 64):
    """``waves`` waves of the mix through the store and a pipelined
    coordinator.  Returns the binds in the client's watch order, the
    pattern, and how the coordinator's counters grew."""
    from k8s1m_tpu.control.coordinator import PODS_PREFIX, Coordinator
    from k8s1m_tpu.faultline.policy import RetryPolicy
    from k8s1m_tpu.obs.metrics import REGISTRY
    from k8s1m_tpu.store.native import MemStore, prefix_end

    pattern = pattern_of(seed)
    sched = REGISTRY.get("coordinator_pods_scheduled_total")
    wave_counter = REGISTRY.get("coordinator_waves_total")
    kernel = candidates_kernel(backend, frozenset({"sel"}), False)
    before = {o: sched.value(outcome=o) for o in ("bound", "retry", "unschedulable")}
    waves_before = wave_counter.value(kernel=kernel)
    total = waves * WAVE
    with MemStore() as store:
        store.put_batch([(node_key(node(i).name), encode_node(node(i)))
                         for i in range(nodes)])
        coord = Coordinator(
            store, spec, POD_SPEC, PROFILE, chunk=CHUNK, with_constraints=False,
            backend=backend, pipeline=True, depth=2, packing="packed",
            score_pct=score_pct, max_attempts=max_attempts, seed=seed,
            retry_policy=RetryPolicy(base_delay_s=0.0),
        )
        watch = store.watch(PODS_PREFIX, prefix_end(PODS_PREFIX))
        try:
            coord.bootstrap()
            assert is_packed(coord.table)
            for lo in range(0, total, WAVE):
                for pod in pods_of(pattern, lo, WAVE):
                    store.put(pod_key(pod.namespace, pod.name), encode_pod(pod))
                coord.step()
            coord.run_until_idle()
            bind_pod, bind_node = [], []
            while True:
                events = watch.poll(4096)
                for ev in events:
                    obj = ev.kv.value
                    at = obj.find(b'"nodeName":"')
                    if at >= 0:
                        name = obj[at + 12:obj.index(b'"', at + 12)]
                        bind_node.append(int(name.rsplit(b"-", 1)[1]))
                        bind_pod.append(int(ev.kv.key.rsplit(b"-", 1)[1]))
                if not events:
                    break
        finally:
            watch.cancel()
            coord.close()
    grown = {o: sched.value(outcome=o) - before[o] for o in before}
    grown["waves"] = wave_counter.value(kernel=kernel) - waves_before
    seen = {"bind_pod": np.asarray(bind_pod), "bind_node": np.asarray(bind_node)}
    return seen, pattern, grown


def hold_binds(seen, pattern, total: int, nodes: int = NODES):
    """Every pod bound once, to a node the oracle finds feasible with the
    room the earlier binds left, and the cell's reference reads nought."""
    assert sorted(seen["bind_pod"].tolist()) == list(range(total))
    used = {}
    for p, n in zip(seen["bind_pod"].tolist(), seen["bind_node"].tolist()):
        pod = build_pod(p, namespace="t", **pattern[p % len(pattern)])
        cpu, mem, count = used.get(n, (0, 0, 0))
        assert oracle_feasible(node(n), pod, (cpu, mem, count)), (pod.key, n)
        used[n] = (cpu + pod.cpu_milli, mem + pod.mem_kib, count + 1)
    assert reference_numbers()(
        seen, None, nodes={**CONFIG["nodes"], "count": nodes}, pattern=pattern,
        offered=total) == {"selector_mismatch": 0, "taint_untolerated": 0}
    # the mix lands where it says: the pool takes its three sixteenths
    on_pool = sum(n % 10 == 9 for n in seen["bind_node"].tolist())
    assert on_pool == total * 3 // 16


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_waves_of_the_mix_bind_feasibly_on_both_backends(backend):
    seen, pattern, grown = serve(backend)
    hold_binds(seen, pattern, 3 * WAVE)
    assert grown["bound"] == 3 * WAVE and grown["unschedulable"] == 0
    # every wave's step was built with the affinity stage, and says so
    assert grown["waves"] >= 3


@pytest.mark.parametrize("nodes, rows", [(1300, 2048), (600, 2048)])
def test_a_table_with_fewer_nodes_than_rows_binds_every_pod(nodes, rows):
    """``score_pct`` 5 of 2,048 rows is one 256-row chunk a wave: the
    rotation passes over the rows that hold nodes alone, so no window is
    empty and no pod comes back for want of a node to look at."""
    spec = TableSpec(**{**CONFIG["table_spec"], "max_nodes": rows})
    seen, pattern, grown = serve("xla", nodes=nodes, spec=spec, waves=4,
                                 score_pct=5)
    hold_binds(seen, pattern, 4 * WAVE, nodes=nodes)
    assert grown["bound"] == 4 * WAVE and grown["unschedulable"] == 0
    assert grown["retry"] == 0


def test_the_rotation_covers_the_rows_that_hold_nodes():
    from k8s1m_tpu.control.coordinator import window_rows_of

    # a full table, and a table the nodes fill to a chunk's edge: as before
    assert window_rows_of(1 << 20, 1 << 20, 4096, 53248) == 1 << 20
    assert window_rows_of(100_000, 102_400, 4096, 8192) == 102_400
    # 100K nodes in a 131,072-row table: 25 chunks, not 32
    assert window_rows_of(100_000, 131_072, 4096, 8192) == 102_400
    offsets = {sample_offset_for(i, 102_400, 8192) for i in range(64)}
    assert max(offsets) + 8192 == 102_400 and len(offsets) == 13
    # never narrower than one window, never wider than the table
    assert window_rows_of(10, 16_384, 4096, 8192) == 8192
    assert window_rows_of(0, 16_384, 4096, 8192) == 8192
    assert window_rows_of(20_000, 16_384, 4096, 8192) == 16_384


# ---- the builders, with and without the new keywords --------------------------

OLD_NODE = (
    b'{"apiVersion":"v1","kind":"Node","metadata":{"name":"kwok-node-19",'
    b'"labels":{"type":"kwok","kwok-group":"9","topology.kubernetes.io/zone":'
    b'"zone-3","topology.kubernetes.io/region":"region-3"}},"spec":{},"status":'
    b'{"allocatable":{"cpu":"32000m","memory":"67108864Ki","pods":"110"},'
    b'"conditions":[{"type":"Ready","status":"True"}]}}'
)
OLD_POD = (
    b'{"apiVersion":"v1","kind":"Pod","metadata":{"name":"bench-pod-7",'
    b'"namespace":"default","labels":{"app":"bench-pod"}},"spec":{"schedulerName":'
    b'"dist-scheduler","containers":[{"name":"app","image":"img","resources":'
    b'{"requests":{"cpu":"100m","memory":"204800Ki"}}}],"tolerations":[{"key":'
    b'"kwok.x-k8s.io/node","operator":"Exists"}]},"status":{"phase":"Pending"}}'
)


@pytest.mark.parametrize("built, want", [
    (lambda: encode_node(build_node(19)), OLD_NODE),
    (lambda: encode_node(build_node(19, node_taints=None, group_taints=None,
                                    group_labels=None)), OLD_NODE),
    (lambda: encode_node(build_node(19, group_taints={"3": NODE_KW["node_taints"]},
                                    group_labels={"3": {"a": "b"}})), OLD_NODE),
    (lambda: encode_pod(build_pod(7)), OLD_POD),
    (lambda: encode_pod(build_pod(7, node_selector=None, node_affinity=None,
                                  tolerations=None)), OLD_POD),
], ids=["node", "node_none", "node_other_group", "pod", "pod_none"])
def test_without_a_new_keyword_the_builders_write_what_they_wrote(built, want):
    assert built() == want


def test_the_new_keywords_reach_the_wire_and_come_back():
    n = build_node(19, **NODE_KW)
    assert [(t.key, t.value) for t in n.taints] == [
        ("kwok.x-k8s.io/node", "fake"), ("dedicated", "batch")]
    assert n.labels["dedicated"] == "batch" and "dedicated" not in build_node(
        18, **NODE_KW).labels
    assert decode_node(encode_node(n)) == n
    for shape in SHAPES:
        pod = build_pod(3, **shape)
        back = decode_pod(encode_pod(pod))
        assert (back.node_selector, back.required_terms, back.preferred_terms,
                back.tolerations) == (pod.node_selector, pod.required_terms,
                                      pod.preferred_terms, pod.tolerations)
    dedicated = build_pod(3, **next(s for s in SHAPES if s.get("tolerations")))
    assert [t.key for t in dedicated.tolerations] == [
        "kwok.x-k8s.io/node", "dedicated"]
    assert dedicated.node_selector == {"dedicated": "batch"}
