"""full-1m-pct5's pods and nodes at a small size: every plug-in in one wave.

The reference is upstream's order of events written out in Python, one pod
at a time in wave order on exact counts: the node filters
(``oracle_feasible``: capacity, the cordon, taints, ``nodeSelector``,
required node affinity), the hard zone spread constraints, required
anti-affinity and its symmetry, required affinity with its bootstrap.  The
spread and inter-pod rules are the benchmark's own references
(``benchmark/references/spread.py``, ``interpod.py``), loaded by path as the
harness loads them, so each rule has one implementation.  The batched step
(``schedule_batch(in_wave_skew=True)``: the skew blocks under the
inter-pod gate) has to agree with it bind for bind on both backends; the combined
kernel has to be the plugins' filter and score, on fused and on split label
words; and a Deployment larger than its candidates can hold comes back
under ``skew`` or ``interpod``, one reason a pod.
"""

from __future__ import annotations

import importlib.util
import json
import os
import random

import jax
import numpy as np
import pytest

from k8s1m_tpu.config import TOPO_ZONE, PodSpec, TableSpec
from k8s1m_tpu.control.objects import decode_pod, encode_pod
from k8s1m_tpu.engine import schedule_batch
from k8s1m_tpu.engine.assign import UNBOUND_REASONS
from k8s1m_tpu.engine.cycle import (
    _NO_SKEW_LIMIT,
    _counted_in_wave,
    candidates,
    candidates_kernel,
    prologue_stats,
    schedule_batch_packed,
)
from k8s1m_tpu.ops.priority import JITTER_BITS
from k8s1m_tpu.oracle import oracle_feasible
from k8s1m_tpu.plugins.registry import Profile, score_and_filter
from k8s1m_tpu.snapshot import NodeTableHost, PodBatchHost
from k8s1m_tpu.snapshot.constraints import ConstraintTracker, empty_constraints
from k8s1m_tpu.snapshot.packing import (
    build_packing_spec,
    pack_table_host,
    unpack_chunk,
)
from k8s1m_tpu.snapshot.pod_encoding import GATE_GROUP
from k8s1m_tpu.tools.make_nodes import build_node
from k8s1m_tpu.tools.make_pods import build_pod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*rel):
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def _reference(name):
    """A module of ``benchmark/references/``, loaded by path (it imports
    nothing of the program or the harness)."""
    path = os.path.join(ROOT, "benchmark", "references", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"full_waves_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


SPREAD, IPA = _reference("spread"), _reference("interpod")
CONFIG = _json("benchmark", "configs", "full-1m-pct5.json")
NODE_KW = {k: v for k, v in CONFIG["nodes"].items()
           if k not in ("count", "cordon_every")}
SHAPES = [{k: v for k, v in s.items() if k != "weight"}
          for s in _json("benchmark", "pods", "full.json")["shapes"]
          for _ in range(s["weight"])]
PROFILE = Profile(**CONFIG["profile"])
ZONES, REGIONS = CONFIG["nodes"]["zones"], CONFIG["nodes"]["regions"]
NODES, CHUNK, WAVE = 512, 128, 128
SPEC = TableSpec(**{**CONFIG["table_spec"], "max_nodes": NODES})
POD_SPEC = PodSpec(**{**CONFIG["pod_spec"], "batch": WAVE})
K = SPEC.max_zones
HA = next(s for s in SHAPES if s.get("app") == "ha-0")
# the Deployments a hard zone constraint selects: web-0..3, ha-0..3
SPREAD_APPS = sorted({app for s in SHAPES
                      for app, _skew in SPREAD.hard_zone_constraints(s)})


def node(i: int):
    """The configuration's node; but nodes 0..7, one a zone, are the only
    ones of full size, so every pod's best row in a zone is the same node
    and two replicas of a Deployment in one wave meet at the gate."""
    small = {} if i < ZONES else {"cpu_milli": 4000, "mem_kib": 8 << 20}
    n = build_node(i, **{**NODE_KW, **small})
    n.unschedulable = i % 16 == 15
    return n


NODE = [node(i) for i in range(NODES)]


def pattern_of(seed: int):
    pattern = list(SHAPES)
    random.Random(seed).shuffle(pattern)
    return pattern


class Replay:
    """Upstream's filters, one pod at a time, on exact counts."""

    def __init__(self, host: NodeTableHost) -> None:
        self.index = {host.row_of(n.name): i for i, n in enumerate(NODE)}
        # the table's id of zone ``zone-<z>``, z = node index mod ZONES
        self.zone_id = [int(host.zone[host.row_of(NODE[z].name)])
                        for z in range(ZONES)]
        self.used: dict[int, tuple[int, int, int]] = {}
        self.placed: list[tuple[dict, int]] = []      # (shape, node index)
        self.in_zone: dict[str, list[int]] = {}       # app -> pods a zone

    @staticmethod
    def _domain(i: int, topo: int) -> int:
        return (i, i % ZONES, i % REGIONS)[topo]

    def legal(self, shape: dict, pod, row: int) -> bool:
        i = self.index[row]
        if not oracle_feasible(NODE[i], pod, self.used.get(i, (0, 0, 0))):
            return False
        labels = IPA.labels_of(shape)
        for app, skew in SPREAD.hard_zone_constraints(shape):
            counts = self.in_zone.get(app, [0] * ZONES)
            here = counts[i % ZONES] + (labels["app"] == app)
            if here - min(counts) > skew:
                return False
        near = lambda j, topo: self._domain(j, topo) == self._domain(i, topo)
        for sel, topo in IPA.required_terms(shape, "pod_anti_affinity"):
            if any(near(j, topo) and IPA.selects(sel, IPA.labels_of(s))
                   for s, j in self.placed):
                return False
        for s, j in self.placed:
            for sel, topo in IPA.required_terms(s, "pod_anti_affinity"):
                if near(j, topo) and IPA.selects(sel, labels):
                    return False
        for sel, topo in IPA.required_terms(shape, "pod_affinity"):
            matching = [j for s, j in self.placed
                        if IPA.selects(sel, IPA.labels_of(s))]
            if not any(near(j, topo) for j in matching) and (
                    matching or not IPA.selects(sel, labels)):
                return False                # not met, and no bootstrap
        return True

    def commit(self, shape: dict, pod, row: int) -> None:
        i = self.index[row]
        cpu, mem, count = self.used.get(i, (0, 0, 0))
        self.used[i] = (cpu + pod.cpu_milli, mem + pod.mem_kib, count + 1)
        self.placed.append((shape, i))
        app = IPA.labels_of(shape)["app"]
        self.in_zone.setdefault(app, [0] * ZONES)[i % ZONES] += 1


def run_waves(backend: str, waves: int, shapes_of):
    """``waves`` waves through the batched step; ``shapes_of(w)`` gives
    wave w's shapes.  Yields (shapes, pods, candidates, assignment, the
    replay, the tracker, the device's count tables)."""
    host = NodeTableHost(SPEC)
    host.bulk_upsert(NODE)
    tracker = ConstraintTracker(SPEC)
    enc = PodBatchHost(POD_SPEC, SPEC, host.vocab)
    table, cons = host.to_device(), empty_constraints(SPEC)
    replay = Replay(host)
    for w in range(waves):
        shapes = shapes_of(w)
        pods = [decode_pod(encode_pod(build_pod(w * WAVE + j, **s)), tracker)
                for j, s in enumerate(shapes)]
        batch = enc.encode(pods)
        key = jax.random.key(41_000 + w)
        step = dict(chunk=CHUNK, k=K, backend=backend, with_affinity=True,
                    in_wave_skew=True)
        cand = candidates(table, batch, key, cons, PROFILE, **step)
        table, cons, asg = schedule_batch(table, batch, key, profile=PROFILE,
                                          constraints=cons, **step)
        yield (shapes, pods, jax.device_get(cand), jax.device_get(asg),
               replay, tracker, cons)


# ---- (a) a wave of the mix is the sequential schedule, bind for bind ----------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_a_wave_of_the_mix_is_the_sequential_schedule_bind_for_bind(backend):
    """Every bind legal at its turn under every plug-in; the chosen node
    the first candidate, in priority order, the reference allows; a pod
    left unbound had none; the zone count tables on the device are the
    replay's after every wave."""
    pattern = pattern_of(41)
    shapes_of = lambda w: [pattern[i % len(pattern)]
                           for i in range(w * WAVE, (w + 1) * WAVE)]
    bound = gated = 0
    for shapes, pods, cand, asg, replay, tracker, cons in run_waves(
            backend, 3, shapes_of):
        assert (np.diff(cand.prio, axis=1) <= 0).all()      # priority order
        for i, (shape, pod) in enumerate(zip(shapes, pods)):
            allowed = [int(cand.idx[i, j]) for j in range(K)
                       if cand.prio[i, j] >= 0
                       and replay.legal(shape, pod, int(cand.idx[i, j]))]
            row = int(asg.node_row[i])
            if not allowed:
                assert row == -1 and not asg.bound[i], (i, shape.get("app"))
                continue
            assert row == allowed[0], (i, shape.get("app"), row, allowed)
            # the gate moved it: its best candidate was closed by a replica
            gated += allowed[0] != int(cand.idx[i, 0])
            replay.commit(shape, pod, row)
            bound += 1
        zone_tab = np.asarray(cons.spread_zone)
        for app in SPREAD_APPS:
            want = np.zeros(SPEC.max_zones, int)
            want[replay.zone_id] = replay.in_zone.get(app, [0] * ZONES)
            slot = tracker.spread_slot("default", {"app": app}, TOPO_ZONE)
            assert (zone_tab[slot] == want).all(), app
    # the mix really ran every plug-in: most pods bound, and the gate or
    # the skew moved some of them off their best node
    assert bound > 2 * WAVE and gated > 0


def test_a_wave_without_a_spread_constraint_runs_under_the_skew_scan():
    """Pods the gate or the skew sent back come in a wave of their own,
    and such a wave may carry no spread constraint: its packed spread
    fields are numpy zeros, which the skew path slices by a traced pod
    index.  The wave binds as the reference allows."""
    host = NodeTableHost(SPEC)
    host.bulk_upsert(NODE)
    tracker = ConstraintTracker(SPEC)
    shapes = [s for s in SHAPES
              if "spread_constraints" not in s and "pod_affinity" not in s]
    shapes = [shapes[j % len(shapes)] for j in range(WAVE)]
    pods = [decode_pod(encode_pod(build_pod(j, **s)), tracker)
            for j, s in enumerate(shapes)]
    packed = PodBatchHost(POD_SPEC, SPEC, host.vocab).encode_packed(pods)
    assert "spread" not in packed.groups and GATE_GROUP in packed.groups
    _t, _c, asg, rows = schedule_batch_packed(
        host.to_device(), packed, jax.random.key(5), profile=PROFILE,
        constraints=empty_constraints(SPEC), chunk=CHUNK, k=K, backend="xla",
        in_wave_skew=True)
    replay = Replay(host)
    for shape, pod, row in zip(shapes, pods, np.asarray(rows).tolist()):
        assert row >= 0 and replay.legal(shape, pod, row), shape.get("app")
        replay.commit(shape, pod, row)
    assert np.asarray(asg.unbound).tolist() == [0] * len(UNBOUND_REASONS)


# ---- (b) the combined kernel is the plugins' filter and score -----------------


def _populated(fuse: bool):
    """The mix's count tables after two waves on a packed table whose
    label words are fused or split, and the next wave's batch."""
    host = NodeTableHost(SPEC)
    host.bulk_upsert(NODE)
    pspec = build_packing_spec(SPEC, host.vocab, fuse_labels=fuse)
    table = pack_table_host(host, pspec)
    assert table.spec.fuse_labels is fuse
    assert table.label_val.shape[1] == (0 if fuse else SPEC.label_slots)
    tracker = ConstraintTracker(SPEC)
    enc = PodBatchHost(POD_SPEC, SPEC, host.vocab)
    cons = empty_constraints(SPEC)
    pattern = pattern_of(7)
    batches = []
    for w in range(3):
        pods = [decode_pod(encode_pod(build_pod(
            w * WAVE + j, **pattern[j % len(pattern)])), tracker)
            for j in range(WAVE)]
        batches.append(enc.encode(pods))
        packed = enc.encode_packed(pods)
    for w, batch in enumerate(batches[:2]):
        table, cons, _asg = schedule_batch(
            table, batch, jax.random.key(w), profile=PROFILE, constraints=cons,
            chunk=CHUNK, k=K, backend="xla", in_wave_skew=True)
    assert int(np.asarray(cons.tgt_node).sum()) > 0
    assert int(np.asarray(cons.spread_node).sum()) > 0
    return table, cons, batches[2], packed.groups


@pytest.fixture(scope="module", params=[True, False], ids=["fused", "split"])
def populated(request):
    return _populated(request.param)


@pytest.mark.parametrize("per_zone", [True, False], ids=["per_zone", "top_k"])
def test_the_combined_kernel_is_the_plugins_filter_and_score(populated, per_zone):
    table, cons, batch, groups = populated
    assert candidates_kernel("pallas", groups, True) == \
        "fused_topk_affinity_constraints"
    key = jax.random.key(3)
    got = {
        backend: jax.device_get(candidates(
            table, batch, key, cons, PROFILE, chunk=CHUNK, k=K,
            backend=backend, with_affinity=True, in_wave_skew=per_zone))
        for backend in ("pallas", "xla")
    }
    assert (got["pallas"].idx == got["xla"].idx).all()
    assert (got["pallas"].prio == got["xla"].prio).all()
    # every candidate against plugins/ over the whole table at once
    if per_zone:
        batch = batch.replace(spread_max_skew=np.where(
            np.asarray(_counted_in_wave(batch)), _NO_SKEW_LIMIT,
            np.asarray(batch.spread_max_skew)))
    mask, score = jax.device_get(score_and_filter(
        unpack_chunk(table), batch, PROFILE, cons, prologue_stats(table, cons)))
    zone = np.asarray(unpack_chunk(table).zone)
    idx, prio = got["pallas"].idx, got["pallas"].prio
    valid = np.asarray(batch.valid)
    for b in np.flatnonzero(valid):
        rows = [(int(r), int(p)) for r, p in zip(idx[b], prio[b]) if p >= 0]
        for r, p in rows:
            assert mask[b, r] and p >> JITTER_BITS == score[b, r], (b, r)
        feasible = np.flatnonzero(mask[b])
        if per_zone:
            # the best score of every zone that holds a feasible row
            want = {int(z): int(score[b, feasible][zone[feasible] == z].max())
                    for z in set(zone[feasible].tolist())}
            assert {int(zone[r]): p >> JITTER_BITS for r, p in rows} == want, b
        else:
            best = sorted(score[b, feasible].tolist(), reverse=True)[:K]
            assert sorted((p >> JITTER_BITS for _r, p in rows),
                          reverse=True) == best, b
    assert (prio[valid] >= 0).any(axis=1).mean() > 0.5


# ---- (c) a Deployment larger than its candidates can hold ---------------------


def test_a_deployment_past_its_candidates_comes_back_under_one_reason():
    """Waves of nothing but ``ha-0``: one replica a node, off the pool, one
    candidate a zone, maxSkew 1 over the zones.  Zone 7 (half cordoned)
    runs out of nodes first; the pods that come back say ``skew`` (no
    lawful candidate at their turn) or ``interpod`` (every lawful one
    closed by a replica of the wave), each pod one reason, never capacity
    and never none; every bind is still legal at its turn."""
    waves = 4
    reasons = np.zeros(len(UNBOUND_REASONS), int)
    unbound = 0
    for shapes, pods, cand, asg, replay, _tracker, _cons in run_waves(
            "xla", waves, lambda w: [HA] * WAVE):
        for i, (shape, pod) in enumerate(zip(shapes, pods)):
            if asg.bound[i]:
                assert replay.legal(shape, pod, int(asg.node_row[i])), i
                replay.commit(shape, pod, int(asg.node_row[i]))
        reasons += np.asarray(asg.unbound)
        unbound += int((~np.asarray(asg.bound)).sum())
    got = dict(zip(UNBOUND_REASONS, reasons.tolist()))
    assert unbound > 0 and sum(got.values()) == unbound
    assert got["capacity"] == 0 and got["no_candidate"] == 0
    assert got["skew"] > 0 and got["interpod"] > 0
    counts = replay.in_zone["ha-0"]
    assert max(counts) - min(counts) <= 1
    assert len({i for _s, i in replay.placed}) == len(replay.placed)
