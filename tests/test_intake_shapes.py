"""Interned pod shapes: the native intake lane against the JSON lane.

``make_pods``' pod carries a label and a toleration, a Deployment's pod
its spread constraints besides, a pinned pod a ``nodeSelector`` or an
``affinity``.  The native parser (native/memstore parse_pod) proves their
grammar and hands back their bytes once per distinct quintuple; the
coordinator decodes each quintuple once (``PodShape``),
binds it to the tracker once per namespace and registration state
(``Coordinator._bound_shape``) and queues ``PendingPod(None, ...,
shape=...)`` records.  The lane must be invisible: the same pods through a coordinator whose
watcher has no ``poll_pods`` (every event through ``_on_pod_put``, the
lane the shaped one is held to) give the same queue, the same packed
batches byte for byte, the same binds and the same accounting.
"""

import dataclasses
import json
import os
import random

import numpy as np
import pytest

from k8s1m_tpu.config import (
    EFFECT_NO_SCHEDULE,
    TOPO_HOSTNAME,
    TOPO_ZONE,
    PodSpec,
    TableSpec,
)
from k8s1m_tpu.control import coordinator as coordinator_mod
from k8s1m_tpu.control.coordinator import Coordinator
from k8s1m_tpu.control.objects import (
    decode_pod,
    decode_pod_fast,
    decode_pod_obj,
    encode_node,
    encode_pod,
    node_key,
    pod_key,
)
from k8s1m_tpu.obs.metrics import REGISTRY
from k8s1m_tpu.oracle import oracle_feasible
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot.constraints import ConstraintTracker
from k8s1m_tpu.snapshot.hotfeed import fingerprint
from k8s1m_tpu.snapshot.node_table import Taint
from k8s1m_tpu.snapshot.pod_encoding import PodInfo, Toleration
from k8s1m_tpu.store.native import MemStore
from k8s1m_tpu.tools.make_nodes import build_node
from k8s1m_tpu.tools.make_pods import build_pod

NODES = 1000
WAVE = 128
SPEC = TableSpec(max_nodes=1024)
PODS = PodSpec(batch=WAVE)
PLAIN_PROFILE = Profile(
    node_affinity=0, topology_spread=0, interpod_affinity=0
)
KWOK_TAINT = Taint("kwok.x-k8s.io/node", "", EFFECT_NO_SCHEDULE)
# spread-1m-pct5's sizes on 1,000 nodes: 8 zones and 4 regions (and id 0),
# two slots for each of sixteen Deployments; a pod may match four.
CONS_SPEC = TableSpec(max_nodes=1024, max_zones=9, max_regions=5,
                      spread_slots=32, affinity_slots=1)
CONS_PODS = PodSpec(batch=WAVE, spread_refs=2, spread_incs=4,
                    affinity_refs=1, ipa_incs=1)
CONS = dict(
    with_constraints=True, in_wave_skew=True, spec=CONS_SPEC, pods=CONS_PODS,
    profile=Profile(node_affinity=0, interpod_affinity=0),
)


class _EventWatch:
    """The coordinator's own pod watcher with ``poll_pods`` taken away:
    a third-party watcher's shape, so every event goes through
    ``_on_pod_put``."""

    def __init__(self, watcher) -> None:
        self._w = watcher
        self.id = watcher.id

    def poll_light(self, batch):
        return self._w.poll_light(batch)

    @property
    def dropped(self):
        return self._w.dropped

    @property
    def canceled(self):
        return self._w.canceled

    def cancel(self) -> None:
        self._w.cancel()


class _Lane:
    """One store, one coordinator, and a record of what it launched."""

    def __init__(self, native: bool, *, taint=None, nodes=NODES, spec=SPEC,
                 pods=PODS, build=build_node, **kw) -> None:
        self.store = MemStore()
        for i in range(nodes):
            node = build(i)
            if taint is not None:
                node.taints = [taint]
            self.store.put(node_key(node.name), encode_node(node))
        kw.setdefault("with_constraints", False)
        kw.setdefault("profile", PLAIN_PROFILE)
        profile = kw.pop("profile")
        self.coord = Coordinator(
            self.store, spec, pods, profile, chunk=256, pipeline=True,
            depth=2, packing="packed", score_pct=50, seed=11, **kw,
        )
        self.waves: list = []
        launch = self.coord._launch

        def spy(batch_pods, batch):
            self.waves.append((
                [p.key_str for p in batch_pods],
                [Coordinator._delta_key(p) for p in batch_pods],
                batch.ints.copy(), batch.bools.copy(), batch.groups,
            ))
            return launch(batch_pods, batch)

        self.coord._launch = spy
        self.native = native

    def bootstrap(self) -> None:
        self.coord.bootstrap()
        if not self.native:
            self.coord._pods_watch = _EventWatch(self.coord._pods_watch)

    def put(self, pods, raw_affinity: dict | None = None) -> None:
        """``raw_affinity``: pod name -> spec.affinity beside the pod's
        own nodeAffinity (encode_pod's keyword)."""
        raw = raw_affinity or {}
        self.store.put_batch([
            (pod_key(p.namespace, p.name),
             encode_pod(p, raw_affinity=raw.get(p.name)))
            for p in pods
        ])

    def close(self) -> None:
        self.coord.close()
        self.store.close()


@pytest.fixture()
def lanes(request):
    made: list[_Lane] = []

    def make(**kw):
        pair = (_Lane(True, **kw), _Lane(False, **kw))
        made.extend(pair)
        return pair

    yield make
    for lane in made:
        lane.close()


def _waves(seed: int, n_waves: int) -> list[list[PodInfo]]:
    """Seeded waves of ``build_pod`` pods in three request sizes, with a
    bare label-less pod every 16th (the empty shape in a shaped frame)."""
    rng = np.random.default_rng(seed)
    sizes = [(100, 200 << 10), (250, 512 << 10), (50, 64 << 10)]
    out, i = [], 0
    for _ in range(n_waves):
        wave = []
        for _ in range(WAVE):
            cpu, mem = sizes[int(rng.integers(len(sizes)))]
            if i % 16 == 15:
                wave.append(PodInfo(f"bare-{i}", namespace="bench",
                                    cpu_milli=cpu, mem_kib=mem))
            else:
                wave.append(build_pod(i, namespace="bench", cpu_milli=cpu,
                                      mem_kib=mem))
            i += 1
        out.append(wave)
    return out


def _queue_view(coord) -> list:
    return [
        (p.key_str, p.cpu_milli, p.mem_kib, p.mod_revision, p.priority,
         p.gang_id, p.gang_size, dataclasses.asdict(p.peek_pod()))
        for p in coord.queue
    ]


def _assert_waves_equal(a: _Lane, b: _Lane, first: int | None = None) -> None:
    """Every launched wave (or the ``first`` ones: retries leave their
    backoff by the clock, whatever the lane) equal in pods, delta keys,
    groups and packed bytes."""
    assert a.waves and b.waves
    if first is None:
        assert len(a.waves) == len(b.waves)
    pairs = list(zip(a.waves, b.waves))[:first]
    for (ka, da, ia, ba, ga), (kb, db, ib, bb, gb) in pairs:
        assert ka == kb
        assert da == db
        assert ga == gb
        assert ia.tobytes() == ib.tobytes()
        assert ba.tobytes() == bb.tobytes()


def _assert_accounting_equal(a: Coordinator, b: Coordinator) -> None:
    """``_bound`` equal but for ``_bind_seq`` (its field 7), which is held
    up to its order among the pods of one wave: a record that holds a
    PodInfo takes its number after the wave's fast-lane records
    (``Coordinator._complete``), and which records hold one is what
    differs between the two lanes."""
    strip = lambda bound: {k: r[:7] + r[8:] for k, r in bound.items()}
    assert strip(a._bound) == strip(b._bound)
    assert sorted(r[7] for r in a._bound.values()) == \
        sorted(r[7] for r in b._bound.values())
    assert set(a.unschedulable) == set(b.unschedulable)
    np.testing.assert_array_equal(a.host.cpu_req, b.host.cpu_req)
    np.testing.assert_array_equal(a.host.mem_req, b.host.mem_req)
    np.testing.assert_array_equal(a.host.pods_req, b.host.pods_req)


def test_shaped_lane_equals_json_lane(lanes):
    """Same seeded waves, both lanes: equal queue order, equal
    ``peek_pod`` of every record, byte-equal packed batches, equal
    ``_delta_key``, equal binds, equal ``_bound``."""
    shaped, legacy = lanes()
    for lane in (shaped, legacy):
        lane.bootstrap()
    bound = [0, 0]
    for wave in _waves(5, 4):
        for lane in (shaped, legacy):
            lane.put(wave)
            lane.coord.drain_watches()
        assert _queue_view(shaped.coord) == _queue_view(legacy.coord)
        # The shaped lane built no PodInfo; the JSON lane one per pod.
        assert all(p.pod is None for p in shaped.coord.queue)
        assert all(p.pod is not None for p in legacy.coord.queue)
        assert sum(p.shape is not None for p in shaped.coord.queue) == \
            WAVE - WAVE // 16
        for k, lane in enumerate((shaped, legacy)):
            bound[k] += lane.coord.run_until_idle()
    assert bound == [4 * WAVE, 4 * WAVE]
    _assert_waves_equal(shaped, legacy)
    _assert_accounting_equal(shaped.coord, legacy.coord)
    # One template, decoded once, whatever the number of pods.
    assert len(shaped.coord._pod_shapes) == 1
    # The tenant of a bound pod is its namespace on both lanes.
    assert {r[8] for r in shaped.coord._bound.values()} == {"bench"}


def test_tenant_label_reaches_bind_meta_on_both_lanes(lanes):
    shaped, legacy = lanes(nodes=64)
    pods = [
        PodInfo(f"t-{i}", namespace="ns", cpu_milli=10, mem_kib=1024,
                labels={"k8s1m.io/tenant": f"team-{i % 2}", "app": "x"})
        for i in range(8)
    ]
    for lane in (shaped, legacy):
        lane.bootstrap()
        lane.put(pods)
        assert lane.coord.run_until_idle() == 8
    _assert_accounting_equal(shaped.coord, legacy.coord)
    assert {r[8] for r in shaped.coord._bound.values()} == \
        {"team-0", "team-1"}


@pytest.mark.parametrize("tolerate", [True, False])
def test_parsed_toleration_decides_a_bind_on_tainted_nodes(lanes, tolerate):
    """Every node carries kwok.x-k8s.io/node:NoSchedule, so the taint
    filter decides: the shaped pod binds, the same pod without its
    toleration is unschedulable, on both lanes alike."""
    shaped, legacy = lanes(taint=KWOK_TAINT, nodes=64, max_attempts=2)
    pods = [
        build_pod(i, cpu_milli=10, mem_kib=1024, tolerate_kwok=tolerate)
        for i in range(16)
    ]
    for lane in (shaped, legacy):
        lane.bootstrap()
        lane.put(pods)
        lane.coord.drain_watches()
    assert _queue_view(shaped.coord) == _queue_view(legacy.coord)
    for lane in (shaped, legacy):
        assert lane.coord.run_until_idle() == (16 if tolerate else 0)
        assert len(lane.coord.unschedulable) == (0 if tolerate else 16)
        assert ("tol" in lane.waves[0][4]) == tolerate
    _assert_waves_equal(shaped, legacy, first=None if tolerate else 1)
    _assert_accounting_equal(shaped.coord, legacy.coord)


def test_spread_constraint_matches_shaped_labels_on_both_lanes(lanes):
    """With a spread constraint interned whose selector matches
    app=bench-pod, shaped pods refer to their shape as bound to the
    tracker, with the matches decode_pod_fast sets pod by pod."""
    shaped, legacy = lanes(
        with_constraints=True, profile=Profile(interpod_affinity=0),
        nodes=64,
    )
    pods = [build_pod(i, cpu_milli=10, mem_kib=1024) for i in range(12)]
    pods += [PodInfo(f"other-{i}", cpu_milli=10, mem_kib=1024,
                     labels={"app": "other"}) for i in range(4)]
    slots = []
    for lane in (shaped, legacy):
        slots.append(lane.coord.tracker.spread_slot(
            "default", {"app": "bench-pod"}, TOPO_ZONE))
        lane.bootstrap()
        lane.put(pods)
        lane.coord.drain_watches()
    assert slots[0] == slots[1]
    assert _queue_view(shaped.coord) == _queue_view(legacy.coord)
    incs = [p.peek_pod().spread_incs for p in shaped.coord.queue]
    assert incs == [[(slots[0], TOPO_ZONE)]] * 12 + [[]] * 4
    # No record holds a PodInfo; the twelve share one bound shape, whose
    # bind keeps one (what a delete takes the increment back with).
    assert all(p.pod is None for p in shaped.coord.queue)
    assert [p.shape.keeps for p in shaped.coord.queue] == \
        [True] * 12 + [False] * 4
    assert len({id(p.shape) for p in shaped.coord.queue}) == 2
    for lane in (shaped, legacy):
        assert lane.coord.run_until_idle() == 16
    _assert_waves_equal(shaped, legacy)
    _assert_accounting_equal(shaped.coord, legacy.coord)
    assert sum(b[5] is not None for b in shaped.coord._bound.values()) == 12


def test_gang_labels_are_staged_not_queued_on_both_lanes(lanes):
    from k8s1m_tpu.loadshed import LoadshedConfig
    from k8s1m_tpu.tenancy import TenancyController, TenancyPolicy

    def tenancy(name):
        return TenancyController(
            TenancyPolicy(weights={"default": 1}),
            loadshed_config=LoadshedConfig(queue_cap=1 << 16), name=name,
        )

    shaped = _Lane(True, nodes=64, tenancy=tenancy("shapes-gang-native"))
    legacy = _Lane(False, nodes=64, tenancy=tenancy("shapes-gang-events"))
    try:
        gang = [
            PodInfo(f"g-{m}", cpu_milli=10, mem_kib=1024,
                    labels={"k8s1m.io/gang": "g", "k8s1m.io/gang-size": "4"},
                    tolerations=[Toleration(key="kwok.x-k8s.io/node")])
            for m in range(4)
        ]
        loner = build_pod(0, cpu_milli=10, mem_kib=1024)
        for lane in (shaped, legacy):
            lane.bootstrap()
            lane.put(gang[:3] + [loner])
            lane.coord.drain_watches()
            # Three of four members wait in staging; only the loner queues.
            assert [p.key_str for p in lane.coord.queue] == \
                ["default/bench-pod-0"]
            assert set(lane.coord._gang_staging["default/g"][1]) == \
                {f"default/g-{m}" for m in range(3)}
            lane.put(gang[3:])
            lane.coord.drain_watches()
            assert not lane.coord._gang_staging
        assert _queue_view(shaped.coord) == _queue_view(legacy.coord)
        assert [(p.gang_id, p.gang_size) for p in shaped.coord.queue] == \
            [("", 0)] + [("default/g", 4)] * 4
        for lane in (shaped, legacy):
            assert lane.coord.run_until_idle() == 5
        _assert_waves_equal(shaped, legacy)
        _assert_accounting_equal(shaped.coord, legacy.coord)
    finally:
        shaped.close()
        legacy.close()


def test_external_bind_of_a_shaped_pod_is_accounted_with_its_labels(lanes):
    """An external writer's bind (POD_HAS_NODE) of a labelled pod is
    accounted with its shape's labels: tenant and gang as the JSON lane
    reads them."""
    shaped, legacy = lanes(nodes=8)
    pod = PodInfo("ext", cpu_milli=70, mem_kib=512, node_name="kwok-node-3",
                  labels={"k8s1m.io/tenant": "team-x"},
                  tolerations=[Toleration(key="kwok.x-k8s.io/node")])
    for lane in (shaped, legacy):
        lane.bootstrap()
        lane.put([pod])
        lane.coord.drain_watches()
        assert not lane.coord.queue
        assert lane.coord._bound["default/ext"][0] == "kwok-node-3"
        assert lane.coord._bound["default/ext"][8] == "team-x"
    _assert_accounting_equal(shaped.coord, legacy.coord)


# ---- pods that carry spread constraints ------------------------------


def _two(match: dict) -> list:
    """The Kubernetes documentation's two-constraint example, as
    benchmark/pods/spread.json writes it."""
    return [
        {"maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
         "whenUnsatisfiable": "DoNotSchedule",
         "labelSelector": {"matchLabels": match}},
        {"maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
         "whenUnsatisfiable": "ScheduleAnyway",
         "labelSelector": {"matchLabels": match}},
    ]


def _web(i: int, d: int, **kw) -> PodInfo:
    """Pod ``i`` of Deployment ``web-<d>``."""
    app = f"web-{d}"
    kw.setdefault("spread_constraints", _two({"app": app}))
    return build_pod(i, app=app, cpu_milli=10, mem_kib=1024, **kw)


def _tables(coord) -> dict:
    c = coord.constraints
    return {name: np.asarray(getattr(c, name))
            for name in ("spread_zone", "spread_region", "spread_node")}


def _assert_tables_equal(a: Coordinator, b: Coordinator) -> dict:
    ta, tb = _tables(a), _tables(b)
    for name in ta:
        np.testing.assert_array_equal(ta[name], tb[name], err_msg=name)
    return ta


def _both(shaped: _Lane, legacy: _Lane, pods) -> None:
    """One frame into both lanes, drained; the queues are then equal."""
    for lane in (shaped, legacy):
        lane.put(pods)
        lane.coord.drain_watches()
    assert _queue_view(shaped.coord) == _queue_view(legacy.coord)
    assert shaped.coord.tracker._spread == legacy.coord.tracker._spread


def test_sixteen_deployments_in_a_wave_ride_the_shaped_lane(lanes):
    """The cell's traffic: every pod carries the two constraints of one
    of sixteen Deployments, eight pods of each a wave.  Equal queue,
    equal packed batches, equal binds, equal count tables on the device;
    no pod on lane ``json``, a template decoded once and bound once per
    state of the tracker it was seen in."""
    shaped, legacy = lanes(**CONS)
    for lane in (shaped, legacy):
        lane.bootstrap()
    order = np.random.default_rng(7).permutation(16).tolist()
    waves = [
        [_web(w * WAVE + i, order[i % 16]) for i in range(WAVE)]
        for w in range(3)
    ]
    before = _counts()
    shaped.put(waves[0])
    shaped.coord.drain_watches()
    # Sixteen first sights, each registering two constraints; then each
    # template's second pod finds the count moved (but for the last).
    assert _grown(before, _counts()) == \
        {"batch_fast": WAVE, "interned": 16, "bound": 31}
    before = _counts()
    legacy.put(waves[0])
    legacy.coord.drain_watches()
    assert _grown(before, _counts()) == {"decode_fast": WAVE}
    assert _queue_view(shaped.coord) == _queue_view(legacy.coord)
    assert all(p.pod is None for p in shaped.coord.queue)
    # One bound shape a Deployment: one interned fingerprint for encode.
    assert len({id(p.shape) for p in shaped.coord.queue}) == 16
    assert len({id(p.shape.fp) for p in shaped.coord.queue}) == 16
    refs = shaped.coord.queue[0].peek_pod().spread_refs
    assert [(r.topo, r.mode, r.self_match) for r in refs] == \
        [(TOPO_ZONE, 0, True), (TOPO_HOSTNAME, 1, True)]
    bound = [0, 0]
    for k, lane in enumerate((shaped, legacy)):
        bound[k] += lane.coord.run_until_idle()
    before = _counts()
    for wave in waves[1:]:
        _both(shaped, legacy, wave)
        for k, lane in enumerate((shaped, legacy)):
            bound[k] += lane.coord.run_until_idle()
        _assert_tables_equal(shaped.coord, legacy.coord)
    # Steady state: nothing interned, nothing bound anew, nothing on json.
    assert _grown(before, _counts()) == \
        {"batch_fast": 2 * WAVE, "decode_fast": 2 * WAVE}
    assert bound == [3 * WAVE, 3 * WAVE]
    _assert_waves_equal(shaped, legacy)
    _assert_accounting_equal(shaped.coord, legacy.coord)
    # Every bound pod keeps what a delete takes its increments back with.
    assert all(r[5] is not None for r in shaped.coord._bound.values())
    zone = _tables(shaped.coord)["spread_zone"]
    assert zone.sum() == 3 * WAVE and (zone.sum(axis=1) > 0).sum() == 16


def test_two_namespaces_share_a_template_and_not_its_slots(lanes):
    shaped, legacy = lanes(nodes=64, **CONS)
    for lane in (shaped, legacy):
        lane.bootstrap()
    pods = [_web(i, 0, namespace=("blue", "green")[i % 2]) for i in range(8)]
    before = _counts()
    _both(shaped, legacy, pods)
    grown = _grown(before, _counts())
    # One template; bound in blue, in green, and in blue again once
    # green's two constraints had registered (which changed nothing for
    # blue: its pods go on referring to the one shape).
    assert (grown["interned"], grown["bound"]) == (1, 3)
    cids = [[r.cid for r in p.peek_pod().spread_refs]
            for p in shaped.coord.queue]
    assert cids == [[0, 1], [2, 3]] * 4
    assert len({id(p.shape) for p in shaped.coord.queue}) == 2
    for lane in (shaped, legacy):
        assert lane.coord.run_until_idle() == 8
    _assert_waves_equal(shaped, legacy)
    _assert_accounting_equal(shaped.coord, legacy.coord)
    zone = _assert_tables_equal(shaped.coord, legacy.coord)["spread_zone"]
    assert zone.sum(axis=1)[:4].tolist() == [4, 0, 4, 0]


def test_a_constraint_registered_mid_frame_reaches_the_next_pod(lanes):
    """``tier`` registers a constraint that selects ``web-0``'s labels
    after ``web-0``'s first pod: that pod stays without the increment,
    ``web-0``'s next pod and a plain pod of the same labels carry it."""
    shaped, legacy = lanes(nodes=64, **CONS)
    for lane in (shaped, legacy):
        lane.bootstrap()
    pods = [
        _web(0, 0),
        _web(1, 1, spread_constraints=_two({"app": "web-0"})[:1]),
        _web(2, 0),
        build_pod(3, app="web-0", cpu_milli=10, mem_kib=1024),
        build_pod(4, app="other", cpu_milli=10, mem_kib=1024),
    ]
    _both(shaped, legacy, pods)
    incs = [p.peek_pod().spread_incs for p in shaped.coord.queue]
    z, h = TOPO_ZONE, TOPO_HOSTNAME
    assert incs == [
        [(0, z), (1, h)],
        [],                           # selects web-0, is web-1
        [(0, z), (1, h)],             # (its selector is web-0's own: slot 0)
        [(0, z), (1, h)],
        [],
    ]
    assert all(p.pod is None for p in shaped.coord.queue)
    # A selector of its own, then: the frame's later pods see it.
    later = [
        _web(5, 0),
        _web(6, 2, spread_constraints=[dict(
            _two({})[0], labelSelector={"matchLabels": {}})]),
        _web(7, 0),
        PodInfo("bare", cpu_milli=10, mem_kib=1024),
    ]
    _both(shaped, legacy, later)
    incs = [p.peek_pod().spread_incs for p in shaped.coord.queue][5:]
    assert incs == [
        [(0, z), (1, h)],
        [(2, z)],
        [(0, z), (1, h), (2, z)],
        [(2, z)],                     # the empty selector takes a bare pod
    ]
    for lane in (shaped, legacy):
        assert lane.coord.run_until_idle() == 9
    _assert_waves_equal(shaped, legacy)
    _assert_accounting_equal(shaped.coord, legacy.coord)
    _assert_tables_equal(shaped.coord, legacy.coord)
    # The plain pod the constraint matched keeps a PodInfo, the other not.
    kept = {k for k, r in shaped.coord._bound.items() if r[5] is not None}
    assert kept == {f"default/bench-pod-{i}" for i in (0, 2, 3, 5, 6, 7)} \
        | {"default/bare"}


def test_a_delete_takes_a_shaped_pods_increments_back(lanes):
    shaped, legacy = lanes(nodes=64, **CONS)
    for lane in (shaped, legacy):
        lane.bootstrap()
    pods = [_web(i, i % 2) for i in range(16)]
    _both(shaped, legacy, pods)
    for lane in (shaped, legacy):
        assert lane.coord.run_until_idle() == 16
    _assert_accounting_equal(shaped.coord, legacy.coord)
    full = _assert_tables_equal(shaped.coord, legacy.coord)
    assert full["spread_zone"].sum(axis=1)[:4].tolist() == [8, 0, 8, 0]
    assert full["spread_node"].sum(axis=1)[:4].tolist() == [0, 8, 0, 8]
    gone = pods[:6]
    for lane in (shaped, legacy):
        lane.store.put_batch(
            [(pod_key(p.namespace, p.name), None) for p in gone])
        lane.coord.run_until_idle()
        lane.coord.step()
    _assert_accounting_equal(shaped.coord, legacy.coord)
    assert len(shaped.coord._bound) == 10
    left = _assert_tables_equal(shaped.coord, legacy.coord)
    assert left["spread_zone"].sum(axis=1)[:4].tolist() == [5, 0, 5, 0]
    assert left["spread_node"].sum(axis=1)[:4].tolist() == [0, 5, 0, 5]
    np.testing.assert_array_equal(
        shaped.coord.host.pods_req, legacy.coord.host.pods_req)


def test_an_external_bind_of_a_constrained_pod_counts_on_the_device(lanes):
    """POD_HAS_NODE with a spread span, both nodeName forms: accounted
    with the shape's increments, which the next step scatters."""
    shaped, legacy = lanes(nodes=8, **CONS)
    ext = [_web(0, 0), _web(1, 0)]
    ext[0].node_name = "kwok-node-3"
    values = [
        encode_pod(ext[0]),
        coordinator_mod.splice_node_name(encode_pod(ext[1]), "kwok-node-5"),
    ]
    for lane in (shaped, legacy):
        lane.bootstrap()
        lane.store.put_batch([
            (pod_key(p.namespace, p.name), v) for p, v in zip(ext, values)
        ])
        lane.coord.drain_watches()
        assert not lane.coord.queue
        lane.coord.step()
    _assert_accounting_equal(shaped.coord, legacy.coord)
    tables = _assert_tables_equal(shaped.coord, legacy.coord)
    assert tables["spread_zone"].sum(axis=1)[:2].tolist() == [2, 0]
    assert tables["spread_node"][1].sum() == 2


def _refused(kind: str) -> tuple[list, int]:
    """(frame, pods of it the JSON lane refuses)."""
    if kind == "unsupported-topology-key":
        rack = _two({"app": "web-1"})
        rack[1]["topologyKey"] = "example.com/rack"
        return [_web(0, 0), _web(1, 1, spread_constraints=rack), _web(2, 0),
                _web(3, 1, spread_constraints=rack), _web(4, 2)], 2
    if kind == "thirty-third-slot":
        # Sixteen Deployments take the 32 slots; the seventeenth's first
        # constraint has none.
        return [_web(i, i % 18) for i in range(36)], 4
    assert kind == "constraint-is-no-object"
    odd = _web(1, 1)
    odd.topology_spread = [7]
    return [_web(0, 0), odd, _web(2, 0)], 1


@pytest.mark.parametrize("kind", [
    "unsupported-topology-key", "thirty-third-slot",
    "constraint-is-no-object",
])
def test_a_refused_template_counts_as_the_json_lane_counts_it(lanes, kind):
    """What decode_pod_obj raises on, the shaped lane refuses pod by pod
    with the same count, the same slots taken on the way, and the rest of
    the frame queued."""
    errors = REGISTRY.get("coordinator_decode_errors_total")
    shaped, legacy = lanes(nodes=64, **CONS)
    frame, bad = _refused(kind)
    counted = []
    for lane in (shaped, legacy):
        lane.bootstrap()
        before = errors.value(kind="pod")
        lane.put(frame)
        lane.coord.drain_watches()
        counted.append(errors.value(kind="pod") - before)
    assert counted == [bad, bad]
    assert _queue_view(shaped.coord) == _queue_view(legacy.coord)
    assert len(shaped.coord.queue) == len(frame) - bad
    assert shaped.coord.tracker._spread == legacy.coord.tracker._spread
    for lane in (shaped, legacy):
        assert lane.coord.run_until_idle() == len(frame) - bad
    _assert_waves_equal(shaped, legacy)
    _assert_accounting_equal(shaped.coord, legacy.coord)
    _assert_tables_equal(shaped.coord, legacy.coord)


def test_a_span_that_is_balanced_and_no_json_is_a_decode_error_on_both():
    errors = REGISTRY.get("coordinator_decode_errors_total")
    bad = encode_pod(_web(0, 0)).replace(b'"maxSkew":1', b'"maxSkew":one')
    for native in (True, False):
        lane = _Lane(native, nodes=8, **CONS)
        try:
            lane.bootstrap()
            before = errors.value(kind="pod")
            lane.store.put_batch([
                (pod_key("default", "bad"), bad),
                (pod_key("default", "good"),
                 encode_pod(dataclasses.replace(_web(1, 0), name="good"))),
            ])
            lane.coord.drain_watches()
            assert errors.value(kind="pod") - before == 1
            assert [p.key_str for p in lane.coord.queue] == ["default/good"]
        finally:
            lane.close()


# ---- the counters that say the lane engaged --------------------------


def _counts() -> dict:
    lanes_c = REGISTRY.get("coordinator_pod_intake_total")
    shapes_c = REGISTRY.get("coordinator_pod_shapes_total")
    out = {k[0]: lanes_c.value(lane=k[0]) for k in lanes_c.label_keys()}
    out.update(
        {k[0]: shapes_c.value(event=k[0]) for k in shapes_c.label_keys()}
    )
    return out


def _grown(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def test_a_build_pod_wave_counts_batch_fast_and_one_interned():
    lane = _Lane(True, nodes=8)
    try:
        lane.bootstrap()
        before = _counts()
        for lo in (0, WAVE):
            lane.put([build_pod(i) for i in range(lo, lo + WAVE)])
            lane.coord.drain_watches()
        assert _grown(before, _counts()) == \
            {"batch_fast": 2 * WAVE, "interned": 1}
    finally:
        lane.close()


def test_a_key_that_is_not_ascii_is_still_keyed_right():
    """The pure-create loop decodes a frame's keys in one piece when they
    are ASCII and one by one when not; either way a record's key is its
    store key."""
    lane = _Lane(True, nodes=8)
    try:
        lane.bootstrap()
        odd = pod_key("default", "p\u00f8d")
        lane.store.put_batch([
            (pod_key("default", "before"), encode_pod(build_pod(1))),
            (odd, encode_pod(build_pod(2))),
            (pod_key("default", "after"), encode_pod(PodInfo("after"))),
        ])
        lane.coord.drain_watches()
        assert [(p.key_str, p.key_bytes) for p in lane.coord.queue] == [
            ("default/before", pod_key("default", "before")),
            ("default/p\u00f8d", odd),
            ("default/after", pod_key("default", "after")),
        ]
        assert lane.coord.run_until_idle() == 3
    finally:
        lane.close()


def test_a_mixed_frame_counts_every_event_in_its_lane():
    """Three shapes, one bare pod, one pod with a priority and one
    delete in one frame."""
    lane = _Lane(True, nodes=8)
    try:
        lane.bootstrap()
        lane.put([PodInfo("gone")])
        lane.coord.drain_watches()
        before = _counts()
        lane.store.put_batch([
            (pod_key("default", "a"),
             encode_pod(build_pod(1, prefix="a")).replace(b"a-1", b"a")),
            (pod_key("default", "b"),
             encode_pod(PodInfo("b", labels={"app": "web"}))),
            (pod_key("default", "c"),
             encode_pod(PodInfo("c", tolerations=[Toleration(key="k")]))),
            (pod_key("default", "d"), encode_pod(PodInfo("d"))),
            (pod_key("default", "e"), encode_pod(PodInfo("e", priority=9))),
            (pod_key("default", "gone"), None),
        ])
        lane.coord.drain_watches()
        assert _grown(before, _counts()) == \
            {"canonical": 4, "json": 1, "delete": 1, "interned": 3}
        # (the deleted pod's record stays in the deque, its key does not)
        assert lane.coord._queued_keys == {f"default/{n}" for n in "abcde"}
        queued = {p.key_str: p for p in lane.coord.queue}
        assert queued["default/e"].priority == 9
        assert queued["default/d"].shape is None
        assert queued["default/b"].peek_pod().labels == {"app": "web"}
        assert queued["default/c"].peek_pod().tolerations == \
            [Toleration(key="k")]
    finally:
        lane.close()


def test_more_shapes_than_the_table_holds_evicts_and_still_decodes(
    monkeypatch,
):
    monkeypatch.setattr(coordinator_mod, "POD_SHAPES_MAX", 4)
    lane = _Lane(True, nodes=8)
    try:
        lane.bootstrap()
        before = _counts()
        pods = [
            PodInfo(f"u-{i}", cpu_milli=10, mem_kib=1024,
                    labels={"app": f"app-{i}"})
            for i in range(11)
        ]
        lane.put(pods)
        lane.coord.drain_watches()
        grown = _grown(before, _counts())
        assert grown == {"batch_fast": 11, "interned": 11, "evicted": 2}
        assert len(lane.coord._pod_shapes) == 3
        assert [p.peek_pod().labels for p in lane.coord.queue] == \
            [p.labels for p in pods]
        # A second sight of a live template is a hit, of an evicted one a
        # fresh decode: never a wrong one.
        lane.put([PodInfo("again-10", labels={"app": "app-10"}),
                  PodInfo("again-0", labels={"app": "app-0"})])
        lane.coord.drain_watches()
        assert _grown(before, _counts())["interned"] == 12
        assert lane.coord.queue[-1].peek_pod().labels == {"app": "app-0"}
        assert lane.coord.run_until_idle() == 13
    finally:
        lane.close()


def test_undecodable_shape_counts_a_decode_error_and_spares_the_rest():
    """Bytes that are no UTF-8 in a label: the JSON lane counts a decode
    error and skips the pod; so does the shaped lane, pod by pod."""
    errors = REGISTRY.get("coordinator_decode_errors_total")
    bad = encode_pod(PodInfo("bad", labels={"app": "X"})).replace(
        b'"app":"X"', b'"app":"\xff"')
    for native in (True, False):
        lane = _Lane(native, nodes=8)
        try:
            lane.bootstrap()
            before = errors.value(kind="pod")
            lane.store.put_batch([
                (pod_key("default", "bad"), bad),
                (pod_key("default", "good"),
                 encode_pod(PodInfo("good", labels={"app": "X"}))),
            ])
            lane.coord.drain_watches()
            assert errors.value(kind="pod") - before == 1
            assert [p.key_str for p in lane.coord.queue] == ["default/good"]
        finally:
            lane.close()


# ---- pods that carry a nodeSelector or an affinity -------------------


def _json_file(*rel):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, *rel)) as f:
        return json.load(f)


# affinity-100k's cluster and mix at tests/test_affinity_waves.py's size:
# every node tainted, the nodes of kwok-group 9 a dedicated pool, one
# node in sixteen cordoned.
AFF_CONFIG = _json_file("benchmark", "configs", "affinity-100k.json")
AFF_NODE_KW = {k: v for k, v in AFF_CONFIG["nodes"].items()
               if k not in ("count", "cordon_every")}
_AFF_MIX = _json_file("benchmark", "pods", "affinity.json")["shapes"]
AFF_SHAPES = {s.get("app", "plain"): {k: v for k, v in s.items()
                                      if k != "weight"}
              for s in _AFF_MIX}
AFF_PATTERN = [AFF_SHAPES[s.get("app", "plain")]
               for s in _AFF_MIX for _ in range(s["weight"])]
AFF_NODES = 2000


def _aff_node(i: int):
    node = build_node(i, **AFF_NODE_KW)
    node.unschedulable = i % 16 == 15
    return node


AFF = dict(
    build=_aff_node, nodes=AFF_NODES,
    spec=TableSpec(**{**AFF_CONFIG["table_spec"], "max_nodes": 2048}),
    pods=PodSpec(**{**AFF_CONFIG["pod_spec"], "batch": WAVE}),
    profile=Profile(**AFF_CONFIG["profile"]),
)
# The same cluster with room for inter-pod terms.
AFF_IPA = dict(
    AFF, with_constraints=True, profile=Profile(topology_spread=0),
    spec=TableSpec(**{**AFF_CONFIG["table_spec"], "max_nodes": 2048,
                      "max_zones": 9, "max_regions": 5, "spread_slots": 2,
                      "affinity_slots": 4}),
    pods=PodSpec(**{**AFF_CONFIG["pod_spec"], "batch": WAVE,
                    "affinity_refs": 2, "ipa_incs": 2}),
)

_REQUIRED_ALONE = {
    "requiredDuringSchedulingIgnoredDuringExecution":
        AFF_SHAPES["zone-pair-1"]["node_affinity"][
            "requiredDuringSchedulingIgnoredDuringExecution"],
}


def _ipa_term(match: dict, key: str = "kubernetes.io/hostname") -> dict:
    return {"topologyKey": key, "labelSelector": {"matchLabels": match}}


def _anti(match: dict) -> dict:
    return {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [_ipa_term(match)]}}


# kind -> (build_pod keywords, spec.affinity beside nodeAffinity, lanes')
SELECTOR_KINDS = {
    "node-selector": (AFF_SHAPES["group-3"], None, AFF),
    "required": (dict(app="zone-pair-1", node_affinity=_REQUIRED_ALONE),
                 None, AFF),
    "required-preferred": (AFF_SHAPES["zone-pair-0"], None, AFF),
    "toleration-node-selector": (AFF_SHAPES["dedicated"], None, AFF),
    "anti-affinity": (dict(app="one-a-node"), _anti({"app": "one-a-node"}),
                      AFF_IPA),
    "anti-affinity-and-required": (
        dict(app="one-a-node", node_affinity=_REQUIRED_ALONE),
        _anti({"app": "one-a-node"}), AFF_IPA),
}


@pytest.fixture()
def json_lane(monkeypatch):
    """_on_pod_put without its byte-scan twin: json.loads and
    decode_pod_obj for every event, which is what a shape is held to."""
    monkeypatch.setattr(
        coordinator_mod, "decode_pod_fast", lambda data, tracker=None: None
    )


def _mixed_wave(kw: dict, n: int = 32) -> list[PodInfo]:
    """Pods of one selector kind among make_pods' own and label-less
    ones (every node is tainted: each tolerates that)."""
    wave = []
    for i in range(n):
        if i % 8 == 7:
            wave.append(PodInfo(
                f"bare-{i}", cpu_milli=10, mem_kib=1024,
                tolerations=[Toleration(key="kwok.x-k8s.io/node")]))
        elif i % 2:
            wave.append(build_pod(i, cpu_milli=10, mem_kib=1024))
        else:
            wave.append(build_pod(i, cpu_milli=10, mem_kib=1024, **kw))
    return wave


def _retired() -> dict:
    c = REGISTRY.get("coordinator_bind_retire_total")
    return {k[0]: c.value(lane=k[0]) for k in c.label_keys()}


@pytest.mark.parametrize("kind", list(SELECTOR_KINDS))
def test_a_selector_shape_is_what_the_json_lane_decodes(
    lanes, json_lane, kind
):
    """One kind of pinned pod in a mixed wave, both lanes: no record of
    the shaped lane holds a PodInfo, each one's PodInfo and fingerprint
    are decode_pod_obj's, the packed batches are byte-equal and the
    binds the same."""
    kw, raw, cluster = SELECTOR_KINDS[kind]
    shaped, legacy = lanes(**cluster)
    wave = _mixed_wave(kw)
    # _mixed_wave pins the even pods.
    pinned_keys = {p.key for p in wave[::2]}
    raw_by_name = {p.name: raw for p in wave[::2]} if raw else None
    before = _counts()
    for lane in (shaped, legacy):
        lane.bootstrap()
        lane.put(wave, raw_by_name)
        lane.coord.drain_watches()
    assert _grown(before, _counts()) == {
        "batch_fast": len(wave), "json": len(wave), "interned": 3,
        **({"bound": 3} if raw else {}),
    }
    assert _queue_view(shaped.coord) == _queue_view(legacy.coord)
    assert shaped.coord.tracker._affinity == legacy.coord.tracker._affinity
    for rec, ref in zip(shaped.coord.queue, legacy.coord.queue):
        assert rec.pod is None and ref.pod is not None
        made = rec.peek_pod()
        for f in dataclasses.fields(PodInfo):
            assert getattr(made, f.name) == getattr(ref.pod, f.name), f.name
        fp = rec.shape.fp if rec.shape is not None else fingerprint(made)
        assert fp == fingerprint(ref.pod)
        assert Coordinator._delta_key(rec) == Coordinator._delta_key(ref)
    pinned = [p for p in shaped.coord.queue if p.key_str in pinned_keys]
    assert len(pinned) == len(wave) // 2
    assert len({id(p.shape) for p in pinned}) == 1
    retired = _retired()
    for lane in (shaped, legacy):
        assert lane.coord.run_until_idle() == len(wave)
    _assert_waves_equal(shaped, legacy)
    _assert_accounting_equal(shaped.coord, legacy.coord)
    # A pod that repels its own kind keeps a PodInfo once bound, on both
    # lanes; every other record of the shaped lane retired in columns.
    kept = {k for k, r in shaped.coord._bound.items() if r[5] is not None}
    assert kept == (pinned_keys if raw else set())
    grown = _grown(retired, _retired())
    assert grown == {
        "columnar": len(wave) - len(kept), "per_pod": len(wave) + len(kept)
    }
    # ... and every bind honours the pod's selector, terms and taints.
    for lane in (shaped, legacy):
        for p in wave:
            name = lane.coord._bound[p.key][0]
            assert oracle_feasible(
                _aff_node(int(name.rsplit("-", 1)[1])), p
            ), (p.key, name)


def test_the_cells_sixteen_unit_pattern_rides_batch_fast_and_retires_in_columns(
    lanes, json_lane
):
    """affinity-100k.fill's traffic: no pod on lane json, seven
    templates interned once, every record retired on lane columnar; the
    JSON lane binds the same pods to the same nodes."""
    shaped, legacy = lanes(**AFF)
    pattern = list(AFF_PATTERN)
    random.Random(38).shuffle(pattern)
    assert len(pattern) == 16
    waves = [
        [build_pod(w * WAVE + i, namespace="t", **pattern[i % 16])
         for i in range(WAVE)]
        for w in range(3)
    ]
    for lane in (shaped, legacy):
        lane.bootstrap()
    bound = [0, 0]
    for k, lane in enumerate((shaped, legacy)):
        before, retired = _counts(), _retired()
        for wave in waves:
            lane.put(wave)
            lane.coord.drain_watches()
            if lane is shaped:
                assert all(p.pod is None and p.shape is not None
                           for p in lane.coord.queue)
            bound[k] += lane.coord.run_until_idle()
        if lane is shaped:
            assert _grown(before, _counts()) == \
                {"batch_fast": 3 * WAVE, "interned": 7}
            assert _grown(retired, _retired()) == {"columnar": 3 * WAVE}
        else:
            assert _grown(before, _counts()) == {"json": 3 * WAVE}
            assert _grown(retired, _retired()) == {"per_pod": 3 * WAVE}
    assert bound == [3 * WAVE, 3 * WAVE]
    assert len(shaped.coord._pod_shapes) == 7
    _assert_waves_equal(shaped, legacy)
    _assert_accounting_equal(shaped.coord, legacy.coord)
    assert all(r[5] is None for r in shaped.coord._bound.values())
    for wave in waves:
        for p in wave:
            name = shaped.coord._bound[p.key][0]
            assert oracle_feasible(
                _aff_node(int(name.rsplit("-", 1)[1])), p), (p.key, name)


@pytest.mark.parametrize("affinity, keeps", [
    (_anti({"app": "x"}), True),
    ({"podAntiAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": 5, "podAffinityTerm": _ipa_term({"app": "x"})}]}}, False),
    ({"podAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": 2, "podAffinityTerm": _ipa_term({"app": "x"})}]}}, False),
    # Its selector takes the pod's own label: the increment keeps it.
    ({"podAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
        _ipa_term({"app": "bench-pod"}, "topology.kubernetes.io/zone")]}},
     True),
    ({"podAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": 2, "podAffinityTerm": _ipa_term({"app": "y"})}]},
      **_anti({"app": "z"})}, True),
], ids=["required-anti", "preferred-anti", "preferred-affinity",
        "required-affinity-to-its-own-kind", "affinity-and-anti"])
def test_an_inter_pod_term_in_the_span_registers_as_the_json_lane_registers_it(
    lanes, json_lane, affinity, keeps
):
    """With nothing registered, a frame whose shape carries podAffinity /
    podAntiAffinity still binds its shapes to the tracker: the records
    carry the term's slot, and a pod that repels (required) keeps its
    PodInfo once bound, as ``_constraintful`` says of the JSON lane's."""
    shaped, legacy = lanes(**dict(AFF_IPA, nodes=64))
    pods = [build_pod(i, cpu_milli=10, mem_kib=1024) for i in range(6)]
    raw = {p.name: affinity for p in pods[:4]}
    for lane in (shaped, legacy):
        lane.bootstrap()
        assert not lane.coord.tracker._affinity
        lane.put(pods, raw)
        lane.coord.drain_watches()
    assert _queue_view(shaped.coord) == _queue_view(legacy.coord)
    assert shaped.coord.tracker._affinity == legacy.coord.tracker._affinity
    assert len(shaped.coord.tracker._affinity) == len(affinity)
    assert all(p.pod is None for p in shaped.coord.queue)
    refs = [p.peek_pod().affinity_refs for p in shaped.coord.queue]
    assert [len(r) for r in refs] == [len(affinity)] * 4 + [0, 0]
    assert [p.shape.keeps for p in shaped.coord.queue][:4] == [keeps] * 4
    assert [Coordinator._constraintful(p.pod) for p in legacy.coord.queue][:4] \
        == [keeps] * 4
    for lane in (shaped, legacy):
        assert lane.coord.run_until_idle() == 6
    _assert_waves_equal(shaped, legacy)
    _assert_accounting_equal(shaped.coord, legacy.coord)
    kept = sum(r[5] is not None for r in shaped.coord._bound.values())
    # (the label app=bench-pod of the two plain pods matches the zone
    # term's selector: they carry its increment and keep a PodInfo too)
    assert kept == sum(
        Coordinator._constraintful(p) for p in
        (r[5] for r in legacy.coord._bound.values()) if p is not None
    ) >= (4 if keeps else 0)


def _undecodable(kind: str) -> bytes:
    pod = build_pod(0, cpu_milli=10, mem_kib=1024, **AFF_SHAPES["zone-pair-0"])
    if kind == "no-json":
        return encode_pod(pod).replace(b'"weight":1', b'"weight":one')
    if kind == "unknown-operator":
        return encode_pod(pod).replace(b'"operator":"In"', b'"operator":"Near"')
    if kind == "node-affinity-is-a-list":
        return encode_pod(build_pod(0)).replace(
            b'},"status"', b',"affinity":{"nodeAffinity":[]}},"status"')
    assert kind == "unsupported-topology-key"
    return encode_pod(pod, raw_affinity={"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [
            _ipa_term({"a": "b"}, "example.com/rack")]}})


@pytest.mark.parametrize("kind", [
    "no-json", "unknown-operator", "node-affinity-is-a-list",
    "unsupported-topology-key",
])
def test_an_affinity_span_the_json_lane_refuses_is_a_decode_error_on_both(
    json_lane, kind
):
    """A span that is balanced and no JSON, JSON that decode_pod_obj
    raises on, and a term the tracker refuses: one decode error a pod on
    either lane, and the rest of the frame queued."""
    errors = REGISTRY.get("coordinator_decode_errors_total")
    bad = _undecodable(kind)
    for native in (True, False):
        lane = _Lane(native, **dict(AFF_IPA, nodes=8))
        try:
            lane.bootstrap()
            before = errors.value(kind="pod")
            lane.store.put_batch([
                (pod_key("default", "bad-0"), bad),
                (pod_key("default", "good"), encode_pod(dataclasses.replace(
                    build_pod(1, **AFF_SHAPES["zone-pair-0"]), name="good"))),
                (pod_key("default", "bad-1"), bad),
            ])
            lane.coord.drain_watches()
            assert errors.value(kind="pod") - before == 2
            assert [p.key_str for p in lane.coord.queue] == ["default/good"]
        finally:
            lane.close()


@pytest.mark.parametrize("kind", list(SELECTOR_KINDS))
def test_decode_pod_takes_the_byte_scan_and_builds_decode_pod_objs_pod(kind):
    """``_retry``'s and ``resync``'s decode: the twin accepts the pod, in
    both nodeName forms, and what it builds against a tracker is what
    json.loads + decode_pod_obj build against one in the same state."""
    kw, raw, cluster = SELECTOR_KINDS[kind]
    pod = build_pod(3, namespace="t", **kw)
    values = [
        encode_pod(pod, raw_affinity=raw),
        encode_pod(dataclasses.replace(pod, node_name="n-1"),
                   raw_affinity=raw),
        coordinator_mod.splice_node_name(
            encode_pod(pod, raw_affinity=raw), "n-2"),
    ]
    for value in values:
        ours, theirs = (ConstraintTracker(cluster["spec"]) for _ in range(2))
        fast = decode_pod_fast(value, ours)
        assert fast is not None
        assert fast == decode_pod_obj(json.loads(value), theirs)
        assert fast == decode_pod(value, ConstraintTracker(cluster["spec"]))
        assert ours._affinity == theirs._affinity
        assert len(fast.affinity_refs) == (1 if raw else 0)
        assert decode_pod_fast(value) == decode_pod_obj(json.loads(value))


def test_an_external_bind_of_a_pinned_pod_is_accounted_as_the_json_lane_does(
    lanes, json_lane
):
    """POD_HAS_NODE with a selector, an affinity and an anti-affinity
    term, both nodeName forms: accounted with the shape's terms, and the
    pod that repels keeps its PodInfo."""
    shaped, legacy = lanes(**dict(AFF_IPA, nodes=16))
    ext = [
        build_pod(0, **AFF_SHAPES["dedicated"]),
        build_pod(1, app="one-a-node", node_affinity=_REQUIRED_ALONE),
    ]
    raw = _anti({"app": "one-a-node"})
    ext[0].node_name = "kwok-node-9"
    values = [
        encode_pod(ext[0]),
        coordinator_mod.splice_node_name(
            encode_pod(ext[1], raw_affinity=raw), "kwok-node-3"),
    ]
    before = _counts()
    for lane in (shaped, legacy):
        lane.bootstrap()
        lane.store.put_batch([
            (pod_key(p.namespace, p.name), v) for p, v in zip(ext, values)
        ])
        lane.coord.drain_watches()
        assert not lane.coord.queue
        lane.coord.step()
    grown = _grown(before, _counts())
    assert (grown["canonical"], grown["json"]) == (2, 2)
    _assert_accounting_equal(shaped.coord, legacy.coord)
    kept = shaped.coord._bound["default/bench-pod-1"][5]
    assert kept == legacy.coord._bound["default/bench-pod-1"][5]
    assert kept.affinity_refs and kept.required_terms
    assert shaped.coord._bound["default/bench-pod-0"][5] is None


def test_a_priority_keeps_a_pinned_pod_on_lane_json():
    lane = _Lane(True, **dict(AFF, nodes=16))
    try:
        lane.bootstrap()
        before = _counts()
        pods = [build_pod(i, **AFF_SHAPES["group-3"]) for i in range(3)]
        pods[1].priority = 7
        lane.put(pods)
        lane.coord.drain_watches()
        assert _grown(before, _counts()) == \
            {"canonical": 2, "json": 1, "interned": 1}
        assert [(p.priority, p.pod is None) for p in lane.coord.queue] == \
            [(0, True), (7, False), (0, True)]
        assert lane.coord.run_until_idle() == 3
    finally:
        lane.close()
