"""End-to-end coordinator tests: store -> snapshot -> schedule -> bind.

The differential analogue of the reference's cluster-scale test strategy
(SURVEY.md §4 item 3) at unit scale: seed the store with KWOK-style nodes
and pending pods, run coordinator cycles, assert on the *store* state
(spec.nodeName written back) and on capacity invariants.
"""

import json
import time

import numpy as np
import pytest

from k8s1m_tpu.config import PodSpec, TableSpec
from k8s1m_tpu.control.coordinator import Coordinator
from k8s1m_tpu.control.objects import (
    decode_node,
    decode_pod,
    encode_node,
    encode_pod,
    node_key,
    pod_key,
)
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot.node_table import NodeInfo, Taint
from k8s1m_tpu.snapshot.pod_encoding import PodInfo, Toleration
from k8s1m_tpu.store.native import MemStore, prefix_end

PROFILE = Profile(topology_spread=0, interpod_affinity=0)
SPEC = TableSpec(max_nodes=128, max_zones=16, max_regions=8)
PODS = PodSpec(batch=32)


@pytest.fixture()
def store():
    with MemStore() as s:
        yield s


def put_node(store, name, zone="z0", cpu=4000, mem=8 << 20, pods=16, **kw):
    labels = {"topology.kubernetes.io/zone": zone, **kw.pop("labels", {})}
    store.put(
        node_key(name),
        encode_node(NodeInfo(name=name, cpu_milli=cpu, mem_kib=mem,
                             pods=pods, labels=labels, **kw)),
    )


def put_pod(store, name, ns="default", cpu=100, mem=200 << 10, **kw):
    store.put(
        pod_key(ns, name),
        encode_pod(PodInfo(name=name, namespace=ns, cpu_milli=cpu,
                           mem_kib=mem, **kw)),
    )


def make_coord(store, **kw):
    kw.setdefault("with_constraints", False)
    return Coordinator(store, SPEC, PODS, PROFILE, chunk=64, k=4, **kw)


def node_of(store, ns, name):
    kv = store.get(pod_key(ns, name))
    return json.loads(kv.value)["spec"].get("nodeName")


def test_binds_all_pods_and_respects_capacity(store):
    for i in range(8):
        put_node(store, f"n{i}", pods=16)
    for i in range(100):
        put_pod(store, f"p{i}")
    c = make_coord(store)
    c.bootstrap()
    total = c.run_until_idle()
    assert total == 100
    per_node = {}
    for i in range(100):
        n = node_of(store, "default", f"p{i}")
        assert n is not None
        per_node[n] = per_node.get(n, 0) + 1
    # 8 nodes x 16 pod slots = 128 >= 100; no node may exceed its cap.
    assert all(v <= 16 for v in per_node.values())
    # cpu: 100 pods x 100m = 10000m over 8x4000m — feasible, and the host
    # mirror must agree with the store.
    assert c.host.pods_req.sum() == 100


def test_pods_exceeding_capacity_go_unschedulable(store):
    put_node(store, "n0", pods=4)
    for i in range(6):
        put_pod(store, f"p{i}")
    c = make_coord(store, max_attempts=2)
    c.bootstrap()
    total = c.run_until_idle()
    assert total == 4
    assert len(c.unschedulable) == 2
    unbound = [i for i in range(6) if node_of(store, "default", f"p{i}") is None]
    assert len(unbound) == 2


def test_node_added_mid_run_via_watch(store):
    put_node(store, "n0", labels={"disk": "hdd"})
    put_pod(store, "p0", node_selector={"disk": "ssd"})
    c = make_coord(store, max_attempts=100)
    c.bootstrap()
    assert c.step() == 0           # nothing feasible yet
    put_node(store, "n1", labels={"disk": "ssd"})   # arrives via watch
    bound = 0
    for _ in range(5):
        bound += c.step()
        if bound:
            break
        # The infeasible attempt parked p0 on the retry-backoff heap
        # (real-time delay); wait it out like the drivers do, or a
        # warm-kernel run steps 5 times before the pod re-enters.
        time.sleep(c.backoff_wait_s() or 0.001)
    assert bound == 1
    assert node_of(store, "default", "p0") == "n1"


def test_node_removed_mid_run(store):
    put_node(store, "n0")
    put_node(store, "n1")
    c = make_coord(store)
    c.bootstrap()
    store.delete(node_key("n0"))
    for i in range(4):
        put_pod(store, f"p{i}")
    c.run_until_idle()
    for i in range(4):
        assert node_of(store, "default", f"p{i}") == "n1"


def test_pod_delete_frees_capacity(store):
    put_node(store, "n0", pods=4)
    for i in range(4):
        put_pod(store, f"p{i}")
    c = make_coord(store)
    c.bootstrap()
    assert c.run_until_idle() == 4
    # Full. A new pod cannot bind.
    put_pod(store, "extra-a")
    c2 = c.run_until_idle()
    assert c2 == 0 or node_of(store, "default", "extra-a") is None
    # Delete two bound pods -> capacity returns -> retry succeeds.
    store.delete(pod_key("default", "p0"))
    store.delete(pod_key("default", "p1"))
    put_pod(store, "extra-b")
    c.unschedulable.clear()
    # extra-a exhausted attempts; re-trigger it by rewriting the object.
    kv = store.get(pod_key("default", "extra-a"))
    store.put(pod_key("default", "extra-a"), kv.value)
    total = c.run_until_idle()
    assert total == 2
    assert c.host.pods_req.sum() == 4


def test_bind_cas_conflict_retries_with_new_revision(store):
    put_node(store, "n0")
    put_pod(store, "p0")
    c = make_coord(store)
    c.bootstrap()
    # Mutate the pod after the coordinator queued it: its CAS must fail,
    # then the retry (with the re-read revision) must succeed.
    pend = c.queue[0]
    kv = store.get(pod_key("default", "p0"))
    store.put(pod_key("default", "p0"), kv.value)  # bump mod_revision
    assert pend.mod_revision == kv.mod_revision
    total = c.run_until_idle()
    assert total == 1
    assert node_of(store, "default", "p0") == "n0"
    assert c.host.pods_req.sum() == 1


def test_taints_respected_through_codec(store):
    put_node(store, "tainted", taints=[Taint("dedicated", "gpu")])
    put_node(store, "clean")
    put_pod(store, "plain")
    put_pod(store, "tolerant", tolerations=[Toleration(key="dedicated")])
    c = make_coord(store)
    c.bootstrap()
    c.run_until_idle()
    assert node_of(store, "default", "plain") == "clean"
    # The tolerant pod may land anywhere; the plain pod must avoid the taint.


def test_prebound_pods_accounted_at_bootstrap(store):
    put_node(store, "n0", pods=4)
    for i in range(3):
        put_pod(store, f"pre{i}", node_name="n0")
    for i in range(3):
        put_pod(store, f"new{i}")
    c = make_coord(store)
    c.bootstrap()
    assert c.host.pods_req.sum() == 3       # prebound accounted
    total = c.run_until_idle()
    assert total == 1                        # only one slot left
    assert c.host.pods_req.sum() == 4


def test_objects_roundtrip():
    node = NodeInfo(
        name="n", cpu_milli=2500, mem_kib=4 << 20, pods=110,
        labels={"a": "b", "topology.kubernetes.io/zone": "z1"},
        taints=[Taint("k", "v")], unschedulable=True,
    )
    back = decode_node(encode_node(node))
    assert back == node

    pod = PodInfo(
        name="p", namespace="ns", cpu_milli=250, mem_kib=512 << 10,
        labels={"app": "x"}, node_selector={"disk": "ssd"},
        tolerations=[Toleration(key="k", value="v")],
    )
    back = decode_pod(encode_pod(pod))
    assert back.name == pod.name and back.cpu_milli == 250
    assert back.mem_kib == 512 << 10
    assert back.node_selector == {"disk": "ssd"}
    assert back.tolerations[0].key == "k"


def test_quantity_parsing():
    from k8s1m_tpu.control.objects import parse_cpu, parse_mem

    assert parse_cpu("2") == 2000
    assert parse_cpu("500m") == 500
    assert parse_cpu(1.5) == 1500
    assert parse_mem("8Gi") == 8 << 20
    assert parse_mem("200Mi") == 200 << 10
    assert parse_mem("1024") == 1
    assert parse_mem("1M") == 976


def test_watch_overflow_triggers_resync(store):
    put_node(store, "n0")
    # Production uses a 1M-deep queue; a small cap here exercises the
    # overflow-resync path without 1M events.
    c = make_coord(store, watch_queue_cap=10_000)
    c.bootstrap()
    # Overflow the 10,000-event native watch queue without draining: the
    # coordinator must detect dropped events and relist (reflector 410
    # semantics) instead of silently diverging.
    for i in range(11_000):
        put_node(store, "churn", cpu=1000 + (i % 7))
    store.delete(node_key("churn"))
    put_node(store, "n1", labels={"fresh": "yes"})
    assert c._nodes_watch.dropped > 0
    c.drain_watches()
    # Post-resync state must match the store exactly.
    assert set(c.host._row_of) == {"n0", "n1"}
    assert c._nodes_watch.dropped == 0
    # And scheduling still works.
    put_pod(store, "after", node_selector={"fresh": "yes"})
    c.run_until_idle()
    assert node_of(store, "default", "after") == "n1"


def test_watch_cancel_triggers_resync(store):
    """A server-side watch cancel (compaction past our revision, tier
    restart) ends the stream without dropped events; the coordinator must
    resync rather than poll dead watchers forever (intake would silently
    stall — the canceled stream never delivers another event)."""
    put_node(store, "n0")
    c = make_coord(store)
    c.bootstrap()
    c._pods_watch.canceled = True
    put_node(store, "n1", labels={"fresh": "yes"})
    c.drain_watches()
    assert not c._pods_watch.canceled   # fresh watcher after resync
    assert set(c.host._row_of) == {"n0", "n1"}
    # Intake is live again end to end.
    put_pod(store, "after", node_selector={"fresh": "yes"})
    c.run_until_idle()
    assert node_of(store, "default", "after") == "n1"


def test_retry_after_spec_change_binds_fresh_bytes(store):
    """A CAS conflict caused by a spec update must retry with the NEW
    object bytes — splicing nodeName into the stale intake bytes would
    silently revert the update (and desync host accounting)."""
    put_node(store, "n0")
    put_pod(store, "p0", cpu=100)
    c = make_coord(store)
    c.bootstrap()
    # User updates the pod's requests after intake but before the bind.
    put_pod(store, "p0", cpu=250)
    assert c.run_until_idle() == 1
    obj = json.loads(store.get(pod_key("default", "p0")).value)
    assert obj["spec"]["nodeName"] == "n0"
    assert obj["spec"]["containers"][0]["resources"]["requests"]["cpu"] == "250m"
    assert c.host.cpu_req.sum() == 250


def test_pipelined_matches_unpipelined_accounting(store):
    """pipeline=True must end with identical store + host state: binds
    complete before the next dispatch's dirty-row sync, so device rows
    never lose in-flight usage."""
    for i in range(8):
        put_node(store, f"n{i}", pods=8)
    c = make_coord(store, pipeline=True)
    c.bootstrap()
    total = 0
    for wave in range(4):
        for i in range(16):
            put_pod(store, f"w{wave}-{i}", cpu=50)
        # Dirty some rows mid-flight the way kwok heartbeats would.
        put_node(store, f"n{wave % 8}", pods=8)
        total += c.step()
    total += c.run_until_idle()
    assert total == 64
    # Host mirror agrees with the store exactly.
    res = store.range(b"/registry/pods/", prefix_end(b"/registry/pods/"))
    per_node = {}
    for kv in res.kvs:
        node = json.loads(kv.value)["spec"].get("nodeName")
        assert node, kv.key
        per_node[node] = per_node.get(node, 0) + 1
    for name, count in per_node.items():
        assert c.host.pods_req[c.host.row_of(name)] == count
    assert c.host.pods_req.sum() == 64
    assert int(np.asarray(c.table.pods_req).sum()) == 64


def test_fast_lane_pending_pods_have_no_podinfo(store):
    """Canonical pods ride the native intake: the coordinator queues them
    without materializing PodInfo, and scheduling still binds them
    correctly."""
    for i in range(4):
        put_node(store, f"n{i}")
    c = make_coord(store)
    c.bootstrap()
    for i in range(8):
        put_pod(store, f"fast-{i}", cpu=10)
    c.drain_watches()
    assert len(c.queue) == 8
    assert all(p.pod is None for p in c.queue)
    assert {p.key_str for p in c.queue} == {
        f"default/fast-{i}" for i in range(8)
    }
    assert c.run_until_idle() == 8
    res = store.range(b"/registry/pods/", prefix_end(b"/registry/pods/"))
    for kv in res.kvs:
        assert json.loads(kv.value)["spec"].get("nodeName")


def test_fast_lane_respects_empty_selector_constraints(store):
    """A topologySpreadConstraint with an empty selector matches label-less
    pods; the fast lane must still record the constraint increments (the
    invariant: a record without a PodInfo refers to a shape bound to the
    tracker, which holds the matches of every pod of it)."""
    from k8s1m_tpu.config import SPREAD_DO_NOT_SCHEDULE, TOPO_ZONE
    from k8s1m_tpu.snapshot.pod_encoding import SpreadConstraintRef

    for i in range(4):
        put_node(store, f"n{i}", zone=f"z{i % 2}")
    c = Coordinator(store, SPEC, PODS, Profile(interpod_affinity=0),
                    chunk=64, k=4, with_constraints=True)
    # Register an empty-selector spread constraint before intake.
    slot = c.tracker.spread_slot("default", {}, TOPO_ZONE)
    c.bootstrap()
    for i in range(6):
        put_pod(store, f"sp-{i}")
    c.drain_watches()
    assert len(c.queue) == 6
    # The label-less pod's shape, bound once for the six of them.
    for p in c.queue:
        assert p.pod is None and p.shape is c.queue[0].shape
        assert p.shape.keeps
        assert (slot, TOPO_ZONE) in p.peek_pod().spread_incs
    assert c.run_until_idle() == 6
    assert all(rec[5] is not None for rec in c._bound.values())


def test_fast_lane_external_bind_accounting(store):
    """A bind written by an external writer (canonical spliced shape)
    arrives via the fast lane and is accounted exactly like the slow
    path: capacity assumed, _bound recorded, dedup against re-queue."""
    from k8s1m_tpu.control.coordinator import splice_node_name

    for i in range(2):
        put_node(store, f"n{i}")
    c = make_coord(store)
    c.bootstrap()
    raw = encode_pod(PodInfo("ext", cpu_milli=70, mem_kib=512))
    store.put(pod_key("default", "ext"), splice_node_name(raw, "n1"))
    c.drain_watches()
    assert not c.queue
    assert c._bound["default/ext"][0] == "n1"
    row = c.host.row_of("n1")
    assert c.host.cpu_req[row] == 70 and c.host.pods_req[row] == 1
    # The delete decrements it again.
    store.delete(pod_key("default", "ext"))
    c.drain_watches()
    assert c.host.pods_req[row] == 0 and c.host.cpu_req[row] == 0


def test_mid_batch_constraint_registration_reaches_later_fast_pods(store):
    """A constraint interned while decoding a non-canonical pod must be
    visible to canonical pods LATER IN THE SAME drained batch: the fast
    lane refreshes its tracker snapshot after every slow-path decode."""
    for i in range(4):
        put_node(store, f"n{i}", zone=f"z{i % 2}")
    c = Coordinator(store, SPEC, PODS, Profile(interpod_affinity=0),
                    chunk=64, k=4, with_constraints=True)
    c.bootstrap()
    # One labeled pod carrying an inline empty-selector spread constraint
    # and a priority (non-canonical -> slow decode interns the slot), then
    # plain pods — all in ONE batch of watch events.
    spread = [{
        "topologyKey": "topology.kubernetes.io/zone",
        "maxSkew": 1,
        "whenUnsatisfiable": "DoNotSchedule",
        "labelSelector": {"matchLabels": {}},
    }]
    from k8s1m_tpu.control.objects import encode_pod as enc

    store.put_batch(
        [(pod_key("default", "carrier"),
          enc(PodInfo("carrier", labels={"x": "y"}, priority=1),
              raw_spread=spread))]
        + [(pod_key("default", f"plain-{i}"),
            enc(PodInfo(f"plain-{i}"))) for i in range(4)]
    )
    c.drain_watches()
    assert len(c.queue) == 5
    plains = [p for p in c.queue if p.key_str.startswith("default/plain")]
    assert plains and all(p.pod is None for p in plains)
    assert all(p.peek_pod().spread_incs for p in plains)
