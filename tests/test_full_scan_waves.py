"""Waves that scan every row (``score_pct`` 100) held to the plain oracle.

A coordinator on a ``MemStore`` schedules waves while the cluster moves
under it; every wave it launches is replayed afterwards, pod by pod,
against ``oracle_feasible`` / ``oracle_score`` over the nodes as the
store held them at that launch and the binds of every earlier wave (a
wave is scored against the table its predecessors' commits left,
whether or not they had retired).  A full scan sees every row, so each
pod must sit on a feasible node whose score is the best any feasible
node offered it.  Capacity is ample throughout, so no pod loses its
first candidate to another pod of its wave.

Scenarios: a packed layout that overflows and rebuilds with a wave in
flight, a label value interned between two waves (the encode cache and
the staged batch see a new vocab generation), and the 2 x 4 mesh
(capacity churn and structural adds on sp-sharded packed planes).
"""

from __future__ import annotations

import dataclasses
import json

import pytest

from k8s1m_tpu.config import PodSpec, TableSpec
from k8s1m_tpu.control.coordinator import Coordinator
from k8s1m_tpu.control.objects import encode_node, encode_pod, node_key, pod_key
from k8s1m_tpu.obs.metrics import REGISTRY
from k8s1m_tpu.oracle import oracle_feasible, oracle_score
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot import NodeInfo, PodInfo
from k8s1m_tpu.snapshot.packing import (
    build_packing_spec,
    is_packed,
    pack_table_host,
)
from k8s1m_tpu.store.native import MemStore

PROFILE = Profile(topology_spread=0, interpod_affinity=0)
WEIGHTS = (PROFILE.least_allocated, PROFILE.balanced_allocation,
           PROFILE.taint_toleration, PROFILE.node_affinity)
ZONE = "topology.kubernetes.io/zone"


class World:
    """A store, what was put into it, and every wave the coordinator
    launched with the nodes the store held at that moment."""

    def __init__(self) -> None:
        self.store = MemStore()
        self.nodes: dict[str, NodeInfo] = {}
        self.pods: dict[str, PodInfo] = {}
        self.waves: list[tuple[list[str], dict[str, NodeInfo]]] = []

    def node(self, name, *, zone="z0", cpu=4000, labels=None) -> None:
        node = NodeInfo(name=name, cpu_milli=cpu, mem_kib=1 << 25, pods=110,
                        labels={ZONE: zone, **(labels or {})})
        self.store.put(node_key(name), encode_node(node))
        self.nodes[name] = node

    def pod(self, name, *, cpu=20, **kw) -> None:
        pod = PodInfo(name=name, namespace="default", cpu_milli=cpu,
                      mem_kib=200 << 10, **kw)
        self.store.put(pod_key("default", name), encode_pod(pod))
        self.pods[f"default/{name}"] = pod

    def coordinator(self, spec, **kw) -> Coordinator:
        c = Coordinator(
            self.store, spec, PodSpec(batch=32), PROFILE, k=4,
            with_constraints=False, pipeline=True, score_pct=100, seed=5,
            **kw,
        )
        launch = c._launch

        def spy(batch_pods, batch):
            self.waves.append(
                ([p.key_str for p in batch_pods], dict(self.nodes))
            )
            return launch(batch_pods, batch)

        c._launch = spy
        c.bootstrap()
        return c

    def replay(self, spec) -> int:
        """Hold every launched wave to the oracle; returns the pods
        checked."""
        used: dict[str, list[int]] = {}
        checked = 0
        for keys, nodes in self.waves:
            start = {n: tuple(u) for n, u in used.items()}
            for key in keys:
                pod = self.pods[key]
                kv = self.store.get(pod_key("default", pod.name))
                name = json.loads(kv.value)["spec"].get("nodeName")
                assert name in nodes, (key, name)

                def score(node):
                    req = start.get(node.name, (0, 0, 0))
                    if not oracle_feasible(node, pod, req):
                        return None
                    return oracle_score(node, pod, req, weights=WEIGHTS,
                                        taint_slots=spec.taint_slots)

                best = max(s for s in map(score, nodes.values())
                           if s is not None)
                assert score(nodes[name]) == best, (key, name)
                u = used.setdefault(name, [0, 0, 0])
                u[0] += pod.cpu_milli
                u[1] += pod.mem_kib
                u[2] += 1
                checked += 1
        assert checked == len(self.pods)
        return checked

    def close(self, c) -> None:
        c.close()
        self.store.close()


def test_a_packing_rebuild_with_a_wave_in_flight_keeps_every_bind_best():
    spec = TableSpec(max_nodes=128, max_zones=16, max_regions=8)
    fb = REGISTRY.get("device_packing_fallback_total")
    base = fb.value(reason="label_val")
    w = World()
    for i in range(24):
        w.node(f"n{i}", zone=f"z{i % 4}")
    c = w.coordinator(spec, chunk=32, depth=2, packing="packed")
    try:
        # Shrink the live layout's value budget to what is interned and
        # fill it: the next novel label value overflows the packed words.
        tight = dataclasses.replace(
            build_packing_spec(spec, c.host.vocab),
            val_bits=max(len(c.host.vocab.label_values).bit_length(), 2),
        )
        c._packing_spec = tight
        c.table = pack_table_host(c.host, tight, c._table_sharding)
        while len(c.host.vocab.label_values) < (1 << tight.val_bits):
            c.host.vocab.label_values.intern(
                f"pad-{len(c.host.vocab.label_values)}"
            )
        for i in range(20):
            w.pod(f"warm-{i}")
        c.step()                                # left in flight
        w.node("n3", zone="z3", labels={"drift": "novel-value"})
        for i in range(8):
            w.pod(f"sel-{i}", node_selector={"drift": "novel-value"})
        for i in range(12):
            w.pod(f"plain-{i}", cpu=300)
        c.step()
        for wave in range(3):
            w.node(f"n{5 + wave}", zone="z1", cpu=6000)  # capacity churn
            for i in range(16):
                w.pod(f"tail{wave}-{i}")
            c.step()
        c.run_until_idle()
        assert fb.value(reason="label_val") == base + 1
        assert is_packed(c.table) and not c.table.spec.fuse_labels
        assert len(w.waves) >= 5
        assert w.replay(spec) == 20 + 20 + 48
    finally:
        w.close(c)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_a_label_value_interned_between_waves_reaches_the_next_wave(backend):
    spec = TableSpec(max_nodes=64, max_zones=16, max_regions=8)
    w = World()
    for i in range(48):
        w.node(f"n{i}", zone=f"z{i % 3}")
    c = w.coordinator(spec, chunk=32, depth=3, packing="packed",
                      backend=backend)
    try:
        gen = c.host.vocab.generation()
        for wave in range(2):
            for i in range(24):
                w.pod(f"w{wave}-{i}")
            c.step()
        # A value no node had: pods that select it were unschedulable a
        # wave ago and now have exactly one node.
        w.node("n7", zone="z1", labels={"disk": "nvme"})
        w.node("fresh", zone="z2", labels={"disk": "nvme"}, cpu=8000)
        for i in range(10):
            w.pod(f"nvme-{i}", node_selector={"disk": "nvme"})
        for i in range(14):
            w.pod(f"rest-{i}")
        c.step()
        for i in range(24):
            w.pod(f"tail-{i}", node_selector={ZONE: "z2"})
        c.step()
        c.run_until_idle()
        assert c.host.vocab.generation() != gen
        assert w.replay(spec) == 96
    finally:
        w.close(c)


def test_the_2x4_mesh_full_scan_keeps_every_bind_best():
    spec = TableSpec(max_nodes=256, max_zones=16, max_regions=8)
    w = World()
    for i in range(200):
        w.node(f"n{i}", zone=f"z{i % 4}")
    c = w.coordinator(spec, chunk=32, depth=3, packing="packed", mesh="2x4")
    try:
        assert not c.table.meta.sharding.is_fully_replicated
        for wave in range(5):
            for i in range(32):
                w.pod(f"w{wave}-{i}", cpu=20 + 40 * (i % 3))
            for j in range(3):                 # capacity churn
                n = (29 * wave + 7 * j) % 200
                w.node(f"n{n}", zone=f"z{n % 4}", cpu=4000 + 500 * wave)
            if wave == 2:                      # structural adds mid-flight
                w.node("fresh-a", zone="z1", cpu=16000)
                w.node("fresh-b", zone="z3", cpu=16000)
            c.step()
        c.run_until_idle()
        assert w.replay(spec) == 160
    finally:
        w.close(c)

