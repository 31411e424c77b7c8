"""The wave's retire in columns against the per-pod loop it replaced.

``Coordinator._bind_wave`` binds a wave's pods with one ``bind_batch``
call and does the bookkeeping of the plain pods (fast-lane records with
an observed revision, no fault plan) in columns; every other pod runs
the per-pod code.  ``_reference_bind_wave`` below is the per-pod loop as
it stood before (PR 28), kept as the plain reference: the same waves
through two coordinators, one with each, leave the same ``_bound``
(the bound-pod record with its preemption metadata, one record since
this PR), victims index, queue keys, dirty rows, host mirror,
``bound_ok`` / ``failed`` masks and counters.  The one difference allowed
is the order of ``_bind_seq`` (field 7 of the record) among the pods of
a wave that has exceptions (``_complete``'s docstring).
"""

import json
import struct
import time
import types

import jax
import numpy as np
import pytest

from k8s1m_tpu import faultline
from k8s1m_tpu.config import TOPO_ZONE, PodSpec, TableSpec
from k8s1m_tpu.control import coordinator as coordinator_mod
from k8s1m_tpu.control.coordinator import (
    Coordinator,
    PodShape,
    _wave_tenants,
    tenant_of_key,
    tenant_of_pod,
)
from k8s1m_tpu.control.objects import (
    encode_node,
    encode_pod,
    node_key,
    pod_key,
)
from k8s1m_tpu.faultline import FaultPlan, FaultSpec, RetryPolicy, install_plan
from k8s1m_tpu.obs.metrics import REGISTRY
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot.node_table import NodeInfo
from k8s1m_tpu.snapshot.pod_encoding import PodInfo
from k8s1m_tpu.store.native import BIND_INVALID, MemStore, pack_bind_frame
from k8s1m_tpu.tools.make_pods import build_pod

WAVE = 32
NODES = 16


# ---- the per-pod loop as it stood, the reference -----------------------------


def _reference_bind_wave(self, batch_pods, rows, bound_ok, failed) -> int:
    _PODS_SCHEDULED = coordinator_mod._PODS_SCHEDULED
    _BIND_LATENCY = coordinator_mod._BIND_LATENCY
    bind_batch = getattr(self.store, "bind_batch", None)
    host = self.host
    nbound = 0
    bound_idx = np.nonzero(rows >= 0)[0]
    brows = rows[bound_idx]
    if bound_idx.size:
        alive = host.valid[brows]
        if not alive.all():
            for i in bound_idx[~alive].tolist():
                failed[i] = True
                self._wave_fail(batch_pods[i])
            bound_idx = bound_idx[alive]
            brows = brows[alive]
    nv = host.vocab.node_names._to_val
    nbytes = [v.encode() if isinstance(v, str) else b"" for v in nv]
    ids_l = host.name_id[brows].tolist()
    brows_l = brows.tolist()
    zones = host.zone[brows].tolist()
    regions = host.region[brows].tolist()
    bound_l = bound_idx.tolist()

    wave_j: list[int] = []
    entries: list[tuple[bytes, int, bytes]] = []
    native = bind_batch is not None
    inj_active = bool(faultline.active_injector().plan.faults)
    for j, i in enumerate(bound_l):
        p = batch_pods[i]
        if native and p.mod_revision is not None:
            if inj_active and self._bind_fault():
                name = nbytes[ids_l[j]].decode()
                self._dirty_rows.add(host.row_of(name))
                failed[i] = True
                self._wave_fail(p)
                continue
            wave_j.append(j)
            entries.append((p.key_bytes, p.mod_revision, nbytes[ids_l[j]]))
            continue
        name = nbytes[ids_l[j]].decode()
        if self._bind(p, name):
            nbound += 1
            bound_ok[i] = True
            _BIND_LATENCY.observe(time.perf_counter() - p.enqueued_at)
            if brows_l[j] in self._midflight_rows:
                self._dirty_rows.add(brows_l[j])
            continue
        self._dirty_rows.add(host.row_of(name))
        failed[i] = True
        self._wave_fail(p)
    if entries:
        results = self._fenced_bind_batch(
            entries,
            self._pods_watch.id if self._bind_excludes else None,
        )
        now = time.perf_counter()
        ok_rows: list[int] = []
        ok_cpu: list[int] = []
        ok_mem: list[int] = []
        lats: list[float] = []
        bound_dict = self._bound
        for j, rev in zip(wave_j, results):
            i = bound_l[j]
            p = batch_pods[i]
            if rev > 0:
                bound_ok[i] = True
                ok_rows.append(brows_l[j])
                ok_cpu.append(p.cpu_milli)
                ok_mem.append(p.mem_kib)
                lats.append(now - p.enqueued_at)
                pod = p.pod
                if pod is not None:
                    keep = pod if self._constraintful(pod) else None
                elif p.shape is not None and p.shape.keeps:
                    # A fast-lane record whose shape carries constraint
                    # increments: the bound record keeps the shape.
                    keep = p.shape
                else:
                    keep = None
                node_name = nv[ids_l[j]]
                self._bind_seq += 1
                if pod is not None:
                    tenant = tenant_of_pod(pod)
                else:
                    sh = p.shape
                    tenant = (
                        sh.tenant if sh is not None else None
                    ) or tenant_of_key(p.key_str)
                bound_dict[p.key_str] = (
                    node_name, p.cpu_milli, p.mem_kib,
                    zones[j], regions[j], keep,
                    p.priority, self._bind_seq, tenant, p.gang_id,
                )
                self._victims_note(
                    p.key_str, node_name, p.cpu_milli, p.mem_kib,
                    p.priority, self._bind_seq, tenant, p.gang_id,
                )
                continue
            name = nbytes[ids_l[j]].decode()
            if rev == BIND_INVALID and self._bind(p, name):
                nbound += 1
                bound_ok[i] = True
                _BIND_LATENCY.observe(now - p.enqueued_at)
                if brows_l[j] in self._midflight_rows:
                    self._dirty_rows.add(brows_l[j])
                continue
            if rev != BIND_INVALID:
                _PODS_SCHEDULED.inc(outcome="conflict")
            self._dirty_rows.add(host.row_of(name))
            failed[i] = True
            self._wave_fail(p)
        if ok_rows:
            r = np.asarray(ok_rows, np.int32)
            np.add.at(host.cpu_req, r, np.asarray(ok_cpu, host.cpu_req.dtype))
            np.add.at(host.mem_req, r, np.asarray(ok_mem, host.mem_req.dtype))
            np.add.at(host.pods_req, r, 1)
            nbound += len(ok_rows)
            _PODS_SCHEDULED.inc(len(ok_rows), outcome="bound")
            _BIND_LATENCY.observe_many(lats)
            if self._midflight_rows:
                self._dirty_rows.update(
                    rr for rr in ok_rows if rr in self._midflight_rows
                )
    return nbound


# ---- two sides of one drive ---------------------------------------------------


def _scheduled() -> dict:
    c = REGISTRY.get("coordinator_pods_scheduled_total")
    return {k[0]: c.value(outcome=k[0]) for k in c.label_keys()}


def _retired() -> dict:
    c = REGISTRY.get("coordinator_bind_retire_total")
    return {lane: c.value(lane=lane) for lane in ("columnar", "per_pod")}


def _latencies() -> int:
    lat = REGISTRY.get("coordinator_schedule_to_bind_seconds")
    return sum(lat._totals.values())


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


class _NoBindBatch:
    """A store that has everything but ``bind_batch``."""

    def __init__(self, store) -> None:
        self._store = store

    def __getattr__(self, name):
        if name == "bind_batch":
            raise AttributeError(name)
        return getattr(self._store, name)


class _Side:
    """One store and one coordinator; ``reference`` swaps the per-pod
    loop in for ``_bind_wave``.  Every wave's masks and the pods the
    wave's own properties make plain are kept."""

    def __init__(self, reference: bool, *, slots: int = 110,
                 no_bind_batch: bool = False, prepare=None, **kw) -> None:
        self.store = MemStore()
        for i in range(NODES):
            self.store.put(node_key(f"n{i:03d}"), encode_node(NodeInfo(
                name=f"n{i:03d}", cpu_milli=64_000, mem_kib=64 << 20,
                pods=slots, labels={
                    "topology.kubernetes.io/zone": f"z{i % 4}",
                    "topology.kubernetes.io/region": f"r{i % 2}",
                },
            )))
        kw.setdefault("with_constraints", False)
        kw.setdefault(
            "profile",
            Profile(node_affinity=0, topology_spread=0, interpod_affinity=0),
        )
        profile = kw.pop("profile")
        self.coord = Coordinator(
            _NoBindBatch(self.store) if no_bind_batch else self.store,
            TableSpec(max_nodes=32, max_zones=8, max_regions=4),
            PodSpec(batch=WAVE), profile, chunk=32, k=4, pipeline=True,
            depth=2, seed=7, max_attempts=20,
            retry_policy=RetryPolicy(base_delay_s=0.0), **kw,
        )
        self.reference = reference
        self.masks: list = []
        self.reached = 0            # pods the device gave a row
        self.columnar_due = 0       # of them plain, and bound
        self.fell_back = 0          # of those, bound by _bind after BIND_INVALID
        # a wave's pods on lane per_pod and coord.bind.per_pod spans opened
        self.per_pod_waves: list = []
        self.per_pod_spans = 0
        inner = (
            types.MethodType(_reference_bind_wave, self.coord)
            if reference else self.coord._bind_wave
        )

        def bind_wave(batch_pods, rows, bound_ok, failed):
            c = self.coord
            at = np.nonzero(rows >= 0)[0].tolist()
            plain = [
                i for i in at
                if getattr(c.store, "bind_batch", None) is not None
                and batch_pods[i].mod_revision is not None
                and batch_pods[i].pod is None
                and not faultline.active_injector().plan.faults
                and c.host.valid[rows[i]]
            ]
            spans, retired = self.per_pod_spans, _retired()["per_pod"]
            n = inner(batch_pods, rows, bound_ok, failed)
            self.per_pod_waves.append((
                _retired()["per_pod"] - retired, self.per_pod_spans - spans))
            self.reached += len(at)
            self.columnar_due += sum(bool(bound_ok[i]) for i in plain)
            self.masks.append((
                [p.key_str for p in batch_pods],
                bound_ok.copy(), failed.copy(), n,
                set(c._dirty_rows), set(c._queued_keys),
            ))
            return n

        self.coord._bind_wave = bind_wave
        stage = self.coord._stage

        def spied(name, *rest, **kw):
            self.per_pod_spans += name == "per_pod"
            return stage(name, *rest, **kw)

        self.coord._stage = spied
        if prepare is not None:
            prepare(self.coord)
        self.coord.bootstrap()

    def put(self, pods) -> None:
        self.store.put_batch(
            [(pod_key(p.namespace, p.name), encode_pod(p)) for p in pods]
        )

    def plant(self, fn) -> None:
        """``benchmark/faults.py:_wrap_bind_batch``: ``fn(real, entries,
        rest, call)`` stands in for the store's ``bind_batch``."""
        real = self.store.bind_batch
        calls = []

        def bind_batch(entries, *rest):
            calls.append(None)
            return fn(real, list(entries), rest, len(calls))

        self.store.bind_batch = bind_batch

    def close(self) -> None:
        self.coord.close()
        self.store.close()


def _pods(first: int, n: int, **kw) -> list[PodInfo]:
    kw.setdefault("cpu_milli", 100)
    kw.setdefault("mem_kib", 1 << 10)
    return [build_pod(i, namespace="bench", **kw) for i in range(first, first + n)]


# ---- the waves ----------------------------------------------------------------
# Each drives one side to its end; what it returns is compared too.


def _all_plain(side: _Side):
    side.put(_pods(0, 3 * WAVE))
    return side.coord.run_until_idle()


def _two_namespaces_and_a_tenant_label(side: _Side):
    pods = _pods(0, WAVE)
    pods += [build_pod(i, namespace="other") for i in range(8)]
    pods += [
        PodInfo(f"t-{i}", namespace="bench", cpu_milli=10, mem_kib=1024,
                labels={"k8s1m.io/tenant": "team-x"})
        for i in range(8)
    ]
    side.put(pods)
    return side.coord.run_until_idle()


def _cas_conflicts(side: _Side):
    def fn(real, entries, rest, call):
        if call == 1:
            # a competing writer gets there first on every fifth pod
            for key, _mod, _node in entries[2::5]:
                cur = side.store.get(key)
                side.store.put(key, cur.value.replace(
                    b'"labels":{', b'"labels":{"touched":"yes",'))
        return real(entries, *rest)

    side.plant(fn)
    side.put(_pods(0, 2 * WAVE))
    return side.coord.run_until_idle()


def _bind_invalid(side: _Side):
    def fn(real, entries, rest, call):
        # every seventh record is answered "not spliceable" and left out
        kept = [e for i, e in enumerate(entries) if i % 7 != 3]
        side.fell_back += len(entries) - len(kept)
        revs = iter(real(kept, *rest))
        return [BIND_INVALID if i % 7 == 3 else next(revs)
                for i in range(len(entries))]

    side.plant(fn)
    side.put(_pods(0, 2 * WAVE))
    return side.coord.run_until_idle()


def _webhook_pods_mixed_in(side: _Side):
    pods = _pods(0, 2 * WAVE)
    for p in pods[1::3]:
        side.coord.submit_external(json.loads(encode_pod(p)))
    side.put(pods)
    return side.coord.run_until_idle()


def _retried_pods_carry_a_podinfo(side: _Side):
    def fn(real, entries, rest, call):
        if call == 1:
            return [0] * len(entries)      # the whole first wave loses
        return real(entries, *rest)

    side.plant(fn)
    side.put(_pods(0, WAVE + 8))
    return side.coord.run_until_idle()


def _constraint_pods(side: _Side):
    side.put(_pods(0, 24) + [
        PodInfo(f"other-{i}", namespace="bench", cpu_milli=10, mem_kib=1024,
                labels={"app": "other"})
        for i in range(8)
    ])
    n = side.coord.run_until_idle()
    assert sum(b[5] is not None for b in side.coord._bound.values()) == 24
    return n


def _keeping_shapes_beside_a_cas_loss_and_a_retry(side: _Side):
    """Fast-lane pods whose shape keeps its spread increment, plain ones,
    the first wave's CAS losses and, later, those pods again: retried,
    so decoded, each with a PodInfo.  Returns what lost."""
    lost: list = []

    def fn(real, entries, rest, call):
        if call == 1:
            for key, _mod, _node in entries[2::5]:
                lost.append(key.decode())
                cur = side.store.get(key)
                side.store.put(key, cur.value.replace(
                    b'"labels":{', b'"labels":{"touched":"yes",'))
        return real(entries, *rest)

    side.plant(fn)
    side.put(_pods(0, 3 * WAVE - 8) + [
        PodInfo(f"other-{i}", namespace="bench", cpu_milli=10, mem_kib=1024,
                labels={"app": "other"})
        for i in range(8)
    ])
    return side.coord.run_until_idle(), lost


def _keeping_shapes_under_a_fault_plan(side: _Side):
    """Under a fault plan every pod takes the per-pod loop: a fast-lane
    pod's record keeps its shape there too."""
    side.put(_pods(0, 2 * WAVE))
    install_plan(FaultPlan(
        [FaultSpec("coordinator.bind", "cas", kind="stale_revision",
                   probability=0.3)],
        seed=31,
    ))
    try:
        n = side.coord.run_until_idle()
    finally:
        install_plan(None)
    assert sum(isinstance(r[5], PodShape)
               for r in side.coord._bound.values()) > WAVE
    return n


def _gang_with_one_failing(side: _Side):
    def fn(real, entries, rest, call):
        revs = list(real(entries, *rest))
        if call == 1:
            # one member of the gang loses its CAS: the gang is released whole
            at = next(i for i, e in enumerate(entries) if b"/g-2" in e[0])
            side.store.put(entries[at][0], side.store.get(entries[at][0]).value
                           .replace(b'"nodeName":', b'"was":'))
            revs[at] = 0
        return revs

    side.plant(fn)
    gang = [
        PodInfo(f"g-{m}", namespace="bench", cpu_milli=10, mem_kib=1024,
                labels={"k8s1m.io/gang": "g", "k8s1m.io/gang-size": "4"})
        for m in range(4)
    ]
    side.put(_pods(0, 12) + gang)
    return side.coord.run_until_idle()


def _preemption(side: _Side):
    # fill every pod slot with plain pods, then one pod with a priority
    side.put(_pods(0, NODES * 4, cpu_milli=1000))
    n = side.coord.run_until_idle()
    assert n == NODES * 4
    assert sum(len(v) for v in side.coord._victims_by_node.values()) == n
    side.put([PodInfo("urgent", namespace="bench", cpu_milli=1000,
                      mem_kib=1 << 10, priority=10)])
    return n + side.coord.run_until_idle(), list(side.coord.preempt_log)


def _row_tombstoned_in_flight(side: _Side):
    side.put(_pods(0, WAVE))
    c = side.coord
    c.step()                        # wave launched, not retired
    rows = np.asarray(jax.device_get(c._inflights[0].rows_dev))
    gone = c.host.vocab.node_names._to_val[int(c.host.name_id[rows[0]])]
    side.store.delete(node_key(gone))
    return c.run_until_idle(), gone


def _fault_plan(side: _Side):
    side.put(_pods(0, 2 * WAVE))
    for p in _pods(2 * WAVE, 8):
        side.coord.submit_external(json.loads(encode_pod(p)))
    side.put(_pods(2 * WAVE, 8))
    install_plan(FaultPlan(
        [FaultSpec("coordinator.bind", "cas", kind="stale_revision",
                   probability=0.3)],
        seed=29,
    ))
    try:
        n = side.coord.run_until_idle()
        return n, faultline.active_injector().fire_counts()
    finally:
        install_plan(None)


def _no_bind_batch(side: _Side):
    side.put(_pods(0, WAVE + 4))
    return side.coord.run_until_idle()


def _tenancy(name: str, **policy):
    from k8s1m_tpu.loadshed import LoadshedConfig
    from k8s1m_tpu.tenancy import TenancyController, TenancyPolicy

    return TenancyController(
        TenancyPolicy(log_preemptions=True, **policy),
        loadshed_config=LoadshedConfig(queue_cap=1 << 16), name=name,
    )


def _spread_tracked(coord) -> None:
    coord.tracker.spread_slot("bench", {"app": "bench-pod"}, TOPO_ZONE)


def _constrained(_side: str) -> dict:
    """A spread constraint that selects the ``bench-pod`` pods."""
    return {"with_constraints": True, "profile": Profile(interpod_affinity=0),
            "prepare": _spread_tracked}


# name -> (drive, exceptions expected, keywords of _Side by side name)
WAVES = {
    "all_plain": (_all_plain, False, lambda s: {}),
    "two_namespaces_and_a_tenant_label": (
        _two_namespaces_and_a_tenant_label, False, lambda s: {}),
    "cas_conflicts": (_cas_conflicts, True, lambda s: {}),
    "bind_invalid": (_bind_invalid, True, lambda s: {}),
    "webhook_pods_mixed_in": (_webhook_pods_mixed_in, True, lambda s: {}),
    "retried_pods_carry_a_podinfo": (
        _retried_pods_carry_a_podinfo, True, lambda s: {}),
    "constraint_pods": (_constraint_pods, False, _constrained),
    "keeping_shapes_beside_a_cas_loss_and_a_retry": (
        _keeping_shapes_beside_a_cas_loss_and_a_retry, True, _constrained),
    "keeping_shapes_under_a_fault_plan": (
        _keeping_shapes_under_a_fault_plan, True, _constrained),
    "gang_with_one_failing": (_gang_with_one_failing, True, lambda s: {
        "tenancy": _tenancy(f"retire-gang-{s}", preempt_enabled=False),
    }),
    "preemption": (_preemption, True, lambda s: {
        "tenancy": _tenancy(f"retire-preempt-{s}"), "slots": 4,
    }),
    "row_tombstoned_in_flight": (
        _row_tombstoned_in_flight, True, lambda s: {}),
    "fault_plan": (_fault_plan, True, lambda s: {}),
    "no_bind_batch": (_no_bind_batch, True, lambda s: {"no_bind_batch": True}),
}


def _drive(name: str, reference: bool) -> dict:
    drive, _exceptions, keywords = WAVES[name]
    side = _Side(reference, **keywords("reference" if reference else "columnar"))
    try:
        sched0, ret0, lat0 = _scheduled(), _retired(), _latencies()
        returned = drive(side)
        c = side.coord
        return {
            "returned": returned,
            "bound": dict(c._bound),
            "victims": {n: dict(v) for n, v in c._victims_by_node.items()},
            "queued": set(c._queued_keys),
            "dirty": set(c._dirty_rows),
            "unschedulable": set(c.unschedulable),
            "mirror": (c.host.cpu_req.copy(), c.host.mem_req.copy(),
                       c.host.pods_req.copy()),
            "masks": side.masks,
            "scheduled": _delta(_scheduled(), sched0),
            "latencies": _latencies() - lat0,
            "retired": _delta(_retired(), ret0),
            "reached": side.reached,
            "columnar_due": side.columnar_due - side.fell_back,
            "per_pod_waves": side.per_pod_waves,
            "store": {
                kv.key: json.loads(kv.value)["spec"].get("nodeName")
                for kv in side.store.range(
                    b"/registry/pods/", b"/registry/pods0").kvs
            },
        }
    finally:
        side.close()


SEQ = 7     # _bind_seq's place in a _bound record


def _fields(keep):
    """Slot 5 field by field: a PodShape (the bound record of a fast-lane
    pod) has no equality of its own, and each side binds its own."""
    if isinstance(keep, PodShape):
        return ("shape", *(getattr(keep, f) for f in PodShape.__slots__))
    return keep


def _comparable(bound: dict) -> dict:
    return {k: r[:5] + (_fields(r[5]),) + r[6:] for k, r in bound.items()}


def _without_seq(bound: dict) -> dict:
    return {k: r[:SEQ] + r[SEQ + 1:] for k, r in _comparable(bound).items()}


@pytest.fixture(scope="module")
def drives():
    made: dict = {}

    def get(name: str):
        if name not in made:
            made[name] = (_drive(name, True), _drive(name, False))
        return made[name]

    return get


@pytest.mark.parametrize("name", list(WAVES))
def test_columnar_retire_equals_the_per_pod_loop(drives, name):
    ref, col = drives(name)
    assert col["bound"], "the drive bound nothing"
    assert col["returned"] == ref["returned"]
    assert col["store"] == ref["store"]
    assert _without_seq(col["bound"]) == _without_seq(ref["bound"])
    assert all(len(r) == 10 for r in col["bound"].values())
    # the same numbers are handed out; within a wave that has exceptions
    # their order may differ, across waves it may not
    assert sorted(r[SEQ] for r in col["bound"].values()) == \
        sorted(r[SEQ] for r in ref["bound"].values())
    for (keys, ok, *_rest) in col["masks"]:
        seqs = [(col["bound"][k][SEQ], ref["bound"][k][SEQ])
                for k, o in zip(keys, ok) if o and k in col["bound"]]
        if seqs:
            assert min(s[0] for s in seqs) == min(s[1] for s in seqs)
            assert max(s[0] for s in seqs) == max(s[1] for s in seqs)
    if not WAVES[name][1]:
        assert _comparable(col["bound"]) == _comparable(ref["bound"])
    victims = lambda d: {
        node: {k: (v.key, v.node, v.row, v.cpu_milli, v.mem_kib, v.priority,
                   v.tenant) for k, v in vs.items()}
        for node, vs in d.items()
    }
    assert victims(col["victims"]) == victims(ref["victims"])
    assert col["queued"] == ref["queued"]
    assert col["dirty"] == ref["dirty"]
    assert col["unschedulable"] == ref["unschedulable"]
    for a, b in zip(col["mirror"], ref["mirror"]):
        assert np.array_equal(a, b)
    assert len(col["masks"]) == len(ref["masks"])
    for (ka, oka, fa, *wave_a), (kb, okb, fb, *wave_b) in zip(
            col["masks"], ref["masks"]):
        # the wave's pods, masks, count bound, dirty rows and queued keys
        assert ka == kb and wave_a == wave_b
        assert np.array_equal(oka, okb) and np.array_equal(fa, fb)
    assert col["scheduled"] == ref["scheduled"]
    assert col["latencies"] == ref["latencies"] > 0


@pytest.mark.parametrize("name", list(WAVES))
def test_the_retire_counter_holds_exactly_the_exceptions(drives, name):
    """``coordinator_bind_retire_total``: lane ``columnar`` holds the pods
    the wave's own properties make plain and whose CAS won, ``per_pod``
    every other pod the device gave a row (a plain pod that _bind bound
    after a BIND_INVALID answer too); the reference counts none."""
    ref, col = drives(name)
    assert ref["retired"] == {}
    got = {"columnar": 0, "per_pod": 0, **col["retired"]}
    assert got["columnar"] + got["per_pod"] == col["reached"] > 0
    assert got["columnar"] == col["columnar_due"]
    if not WAVES[name][1]:
        assert got["per_pod"] == 0
    elif name != "preemption":       # its exception never reaches a row
        assert got["per_pod"] > 0


def test_keeping_shapes_retire_in_columns_beside_the_exceptions(drives):
    """A fast-lane pod whose shape keeps its increment retires in columns,
    and its record keeps that shape; the CAS losses and the retried pods
    (a PodInfo each) are the per-pod loop's alone: the span
    ``coord.bind.per_pod`` opens in a wave exactly where the wave retires
    a pod on lane ``per_pod``."""
    ref, col = drives("keeping_shapes_beside_a_cas_loss_and_a_retry")
    n, lost = col["returned"]
    lost = [key[len("/registry/pods/"):] for key in lost]
    assert n == 3 * WAVE and lost
    keeps = [r[5] for r in col["bound"].values()]
    assert sorted(k for k, r in col["bound"].items()
                  if isinstance(r[5], PodInfo)) == sorted(lost)
    shapes = [k for k in keeps if isinstance(k, PodShape)]
    assert len(shapes) == 3 * WAVE - 8 - len(lost)
    assert len({id(k) for k in shapes}) == 1
    assert col["retired"] == {
        "columnar": 3 * WAVE - len(lost), "per_pod": 2 * len(lost)}
    waves = col["per_pod_waves"]
    assert any(pods == 0 for pods, _ in waves)
    assert any(pods > 0 for pods, _ in waves)
    for pods, spans in waves:
        assert (spans > 0) == (pods > 0)
    assert all(spans == 0 for _, spans in ref["per_pod_waves"])


def test_a_make_pods_fill_is_all_columnar():
    side = _Side(False)
    try:
        before = _retired()
        side.put([build_pod(i) for i in range(4 * WAVE)])
        assert side.coord.run_until_idle() == 4 * WAVE
        assert _delta(_retired(), before) == {"columnar": 4 * WAVE}
    finally:
        side.close()


# ---- the seam to the store ----------------------------------------------------


def test_one_bind_batch_call_a_wave_with_a_list_of_tuples():
    """What ``benchmark/faults.py`` and every door-5 control hold on to:
    one call of ``store.bind_batch`` a wave, its argument a list of
    ``(key bytes, required_mod int, node name bytes)`` in wave order, its
    answer any sequence of revisions — a plain list with a 1 planted for
    an entry never written (``lazy_bind``) included."""
    side = _Side(False)
    seen = []

    def fn(real, entries, rest, call):
        seen.append((entries, rest))
        kept = [e for i, e in enumerate(entries) if i % 8]
        revs = iter(real(kept, *rest))
        return [next(revs) if i % 8 else 1 for i in range(len(entries))]

    try:
        waves = []
        launch = side.coord._launch

        def spy(batch_pods, batch):
            waves.append([p.key_bytes for p in batch_pods])
            return launch(batch_pods, batch)

        side.coord._launch = spy
        real_bind_batch = side.store.bind_batch

        def typed(entries, *rest):
            assert type(entries) is list
            return real_bind_batch(entries, *rest)

        side.store.bind_batch = typed
        side.plant(fn)
        side.put(_pods(0, 2 * WAVE))
        assert side.coord.run_until_idle() == 2 * WAVE
        assert len(seen) == len(waves) == 2
        for (entries, rest), keys in zip(seen, waves):
            assert [e[0] for e in entries] == keys
            assert rest == (side.coord._pods_watch.id,)
            for key, mod, node in entries:
                assert type(key) is bytes and type(node) is bytes
                assert type(mod) is int and mod > 0
                assert node.decode() in side.coord.host._row_of
        # the planted 1s were taken as binds: the coordinator counts all,
        # the store holds seven in eight
        assert len(side.coord._bound) == 2 * WAVE
        kvs = side.store.range(b"/registry/pods/", b"/registry/pods0").kvs
        assert sum(b'"nodeName"' in kv.value for kv in kvs) == 2 * WAVE * 7 // 8
    finally:
        side.close()


_REC = struct.Struct("<qII")


def _frame_by_record(binds) -> bytes:
    out = b""
    for key, mod, name in binds:
        out += _REC.pack(mod, len(key), len(name)) + key + name
    return out


@pytest.mark.parametrize("binds", [
    [],
    [(b"/registry/pods/default/p", 7, b"n-1")],
    [(b"/registry/pods/d/\xc3\xa9t\xc3\xa9", 1 << 40, "nøde".encode()),
     (b"", 0, b""), (b"k" * 300, (1 << 62) + 1, b"x" * 70)],
], ids=["empty", "one", "odd"])
def test_bind_frame_is_the_wire_form(binds):
    assert pack_bind_frame(binds) == _frame_by_record(binds)


def test_bind_frame_answers_with_one_array():
    with MemStore() as store:
        key = pod_key("default", "p")
        rev = store.put(key, encode_pod(PodInfo("p")))
        revs = store.bind_batch([(key, rev, b"n-1"), (b"/nope", 3, b"n-1")])
        assert isinstance(revs, np.ndarray) and revs.dtype == np.int64
        assert revs.tolist() == [rev + 1, -1]
        assert store.bind_batch([]).tolist() == []
        rc, none = store.bind_frame(b"short", 1)
        assert rc < 0 and none.tolist() == []


# ---- the tenant column --------------------------------------------------------


def _tenants_by_pod(key_strs, shapes) -> list:
    return [
        (sh.tenant if sh is not None else None) or tenant_of_key(k)
        for k, sh in zip(key_strs, shapes)
    ]


@pytest.mark.parametrize("keys,labels", [
    (["a/p0", "a/p1", "a/p2"], [None, None, None]),
    (["a/p0", "b/p1", "a/p2", "/p3"], [None, None, None, None]),
    (["a/p0", "a/p1", "b/p2"], [None, {"k8s1m.io/tenant": "t"}, {"app": "x"}]),
    (["a/p0", "ab/p1"], [{"k8s1m.io/tenant": "t"}, None]),
    (["noslash", "a/p"], [None, None]),
], ids=["one_namespace", "namespaces", "a_shape_names_one", "prefix", "no_slash"])
def test_wave_tenants_equal_the_per_pod_rule(keys, labels):
    shapes = [
        None if l is None else coordinator_mod.PodShape(l, [], [], "s")
        for l in labels
    ]
    assert _wave_tenants(keys, shapes) == _tenants_by_pod(keys, shapes)
