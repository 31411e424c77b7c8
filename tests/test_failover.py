"""ISSUE 9 warmspare: lease-epoch fencing, warm-standby takeover,
crash-consistent derived-state recovery, and the drill smoke lanes.

Layers:

1. Fencing units — a coordinator holding a stale reign's fence must
   have every bind/evict refused (draining in-flight waves to requeue,
   never to the store), counted in ``fencing_rejected_total{path}``.
2. Warm-standby units — the mirror follows the watch stream, promote
   is a bounded reconcile (pinned relist-from-revision diff), gangs
   the predecessor left half-bound recover all-or-none, and the
   no-leader webhook window is queue-or-429.
3. The tier-1 drill lanes — ``failover_drill --smoke`` (mid-wave kill
   warm vs cold + paused-leader split-brain) and the benchtrue part 3
   ``steady_drill --smoke --mesh 2x4`` over the virtual 8-device mesh.
"""

import json

import pytest

from k8s1m_tpu.config import PodSpec, TableSpec
from k8s1m_tpu.control.coordinator import Coordinator
from k8s1m_tpu.control.leader import HACoordinator, LeaderElector
from k8s1m_tpu.control.objects import encode_node, encode_pod, node_key, pod_key
from k8s1m_tpu.loadshed import Overloaded
from k8s1m_tpu.obs.metrics import REGISTRY
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot.node_table import NodeInfo
from k8s1m_tpu.snapshot.pod_encoding import PodInfo
from k8s1m_tpu.store.native import MemStore


@pytest.fixture
def store(tmp_path):
    s = MemStore(wal_dir=str(tmp_path / "wal"), wal_mode="none")
    yield s
    s.close()


def put_nodes(store, n=8):
    for i in range(n):
        node = NodeInfo(f"node-{i}", cpu_milli=400000, mem_kib=8 << 20,
                        pods=4096)
        store.put(node_key(node.name), encode_node(node))


def put_pods(store, n, prefix="pod", ns="default"):
    for i in range(n):
        p = PodInfo(f"{prefix}-{i}", namespace=ns, cpu_milli=100,
                    mem_kib=1 << 10)
        store.put(pod_key(ns, p.name), encode_pod(p))


def make_coord(store, **kw):
    kw.setdefault("with_constraints", False)
    return Coordinator(
        store,
        TableSpec(max_nodes=64, max_zones=16, max_regions=8),
        PodSpec(batch=16),
        Profile(topology_spread=0, interpod_affinity=0),
        chunk=64, k=4, **kw,
    )


def fence_rejects() -> float:
    m = REGISTRY.get("fencing_rejected_total")
    return sum(m.value(path=p) for p in ("bind", "evict", "preempt"))


# ---- lease-epoch fencing ------------------------------------------------


def test_fence_rejects_deposed_reigns_binds(store):
    """A coordinator fenced on a stolen reign's epoch binds NOTHING:
    every pod drains to the conflict/requeue machinery and the store
    keeps only the new reign's writes."""
    put_nodes(store)
    put_pods(store, 6)
    a = LeaderElector(store, "a")
    assert a.tick(0.0)
    coord = make_coord(store, fence=a.fence())
    coord.bootstrap()
    # Healthy reign: the fence admits, pods bind.
    assert coord.run_until_idle() == 6
    # The lease expires and b steals the epoch; a has not ticked since.
    b = LeaderElector(store, "b")
    assert b.tick(16.0)
    put_pods(store, 4, prefix="late")
    r0 = fence_rejects()
    bound = coord.run_until_idle(max_cycles=50)
    assert bound == 0
    assert fence_rejects() > r0
    for i in range(4):
        obj = json.loads(store.get(pod_key("default", f"late-{i}")).value)
        assert not obj["spec"].get("nodeName")
    coord.close()


def test_fence_rejects_mid_wave_on_local_expiry(store):
    """The LOCAL half of the fence: a leader whose own injected clock
    shows the lease expired refuses its writes even before observing a
    successor (crash-consistent: better to requeue than to write past
    your lease)."""
    put_nodes(store)
    put_pods(store, 4)
    a = LeaderElector(store, "a")
    assert a.tick(0.0)
    coord = make_coord(store, fence=a.fence())
    coord.bootstrap()
    # Clock runs out without a renew (ticks stopped reaching the
    # elector): last_now jumps past the duration.
    a.last_now = 20.0
    assert a.locally_expired()
    assert coord.run_until_idle(max_cycles=50) == 0
    coord.close()


def test_deposed_pipeline_drains_to_requeue_not_store(store):
    """In-flight pipelined waves of a deposed reign retire through the
    fence: flush() lands zero store writes and the pods re-enter the
    retry machinery."""
    put_nodes(store)
    put_pods(store, 16)
    a = LeaderElector(store, "a")
    assert a.tick(0.0)
    coord = make_coord(store, fence=a.fence(), pipeline=True, depth=2)
    coord.bootstrap()
    coord.step()                    # wave dispatched, not yet retired
    assert coord._inflights
    b = LeaderElector(store, "b")
    assert b.tick(16.0)             # depose a mid-wave
    r0 = fence_rejects()
    assert coord.flush() == 0
    assert fence_rejects() > r0
    for i in range(16):
        obj = json.loads(store.get(pod_key("default", f"pod-{i}")).value)
        assert not obj["spec"].get("nodeName")
    # The pods are requeued (backoff), not lost.
    assert len(coord._backoff) + len(coord.queue) == 16
    coord.close()


# ---- warm standby: follow, promote, reconcile ---------------------------


def test_warm_standby_promotes_and_drains_backlog(store):
    put_nodes(store)
    put_pods(store, 12, prefix="early")
    ha_a = HACoordinator(LeaderElector(store, "a"),
                         lambda: make_coord(store))
    ha_b = HACoordinator(
        LeaderElector(store, "b", retry_period_s=1.0),
        lambda: make_coord(store), warm_standby=True,
    )
    assert ha_a.tick(0.0) == 12
    for t in (0.5, 1.5, 2.5):
        ha_b.tick(t)
    assert ha_b._mirror is not None
    # The mirror tracked the leader's binds as store facts.
    assert len(ha_b._mirror._bound) == 12
    put_pods(store, 7, prefix="late")
    # a dies silently; b takes over at expiry with a WARM promote.
    t, total = 2.5, 0
    while t < 30.0:
        t += 1.0
        total += ha_b.tick(t)
    assert ha_b.elector.is_leader
    assert ha_b.takeover_mode == "warm"
    assert ha_b.last_promote_stats["resync"] == 0
    assert total == 7
    for prefix, n in (("early", 12), ("late", 7)):
        for i in range(n):
            obj = json.loads(
                store.get(pod_key("default", f"{prefix}-{i}")).value
            )
            assert obj["spec"].get("nodeName"), f"{prefix}-{i} unbound"
    ha_b.stop()


def test_promote_purges_stale_queue_entries(store):
    """A follower queues every pending pod, then learns the leader
    bound them: promote must purge the settled records so the first
    post-takeover waves are not a conflict storm of bound pods."""
    put_nodes(store)
    ha_a = HACoordinator(LeaderElector(store, "a"),
                         lambda: make_coord(store))
    ha_b = HACoordinator(
        LeaderElector(store, "b", retry_period_s=1.0),
        lambda: make_coord(store), warm_standby=True,
    )
    assert ha_a.tick(0.0) == 0      # a leads before any pod exists
    put_pods(store, 10)
    ha_b.tick(0.5)                  # mirror boots: queues all 10
    assert len(ha_b._mirror.queue) == 10
    assert ha_a.tick(1.0) == 10     # leader binds them
    ha_b.tick(1.5)                  # mirror applies the bind echoes
    t = 1.5
    while not ha_b.elector.is_leader and t < 30.0:
        t += 1.0
        ha_b.tick(t)
    assert ha_b.last_promote_stats["stale_queue_purged"] == 10
    assert not ha_b.coord.queue
    ha_b.stop()


def test_reconcile_at_adopts_missed_bind_and_dedupes(store):
    """_reconcile_at repairs a bind the watch never delivered (adopted
    as external, counted) and the later watch echo of the same bind
    must NOT double-account it."""
    put_nodes(store)
    coord = make_coord(store)
    coord.bootstrap()
    # A bind lands from elsewhere; the coordinator does NOT drain its
    # watch (the gap promote would inherit after a broken stream).
    p = PodInfo("ghost", cpu_milli=100, mem_kib=1 << 10, node_name="node-0")
    store.put(pod_key("default", p.name), encode_pod(p))
    rev = store.current_revision
    rep = coord._reconcile_at(rev)
    assert rep["binds_adopted"] == 1
    assert "default/ghost" in coord._bound
    row = coord.host.row_of("node-0")
    assert int(coord.host.pods_req[row]) == 1
    # Now the watch echo arrives: dedup, no double accounting.
    coord.drain_watches()
    assert int(coord.host.pods_req[row]) == 1
    # And a deletion the watch missed is dropped by the next reconcile.
    store.delete(pod_key("default", "ghost"))
    coord._pods_watch.poll(10000)   # discard the delete event (the gap)
    rep = coord._reconcile_at(store.current_revision)
    assert rep["pods_dropped"] == 1
    assert int(coord.host.pods_req[row]) == 0
    coord.close()


def test_recover_gangs_all_or_none(store):
    """A gang the predecessor left half-bound (died between its bind
    CASes and the gang settlement) recovers all-or-none: the bound
    members release, the gang re-stages whole, and one wave binds all
    of it."""
    from k8s1m_tpu.loadshed import LoadshedConfig
    from k8s1m_tpu.tenancy import TenancyController, TenancyPolicy

    put_nodes(store)
    for m in range(4):
        p = PodInfo(
            f"g-m{m}", cpu_milli=100, mem_kib=1 << 10,
            labels={"k8s1m.io/gang": "g", "k8s1m.io/gang-size": "4"},
            node_name="node-0" if m < 2 else "",
        )
        store.put(pod_key("default", p.name), encode_pod(p))
    tn = TenancyController(
        TenancyPolicy(weights={"default": 1}),
        loadshed_config=LoadshedConfig(queue_cap=1 << 16),
        name="recover-gangs-test",
    )
    coord = make_coord(store, tenancy=tn)
    coord.bootstrap()
    assert len(coord._bound) == 2          # the crash artifact
    assert coord._gang_staging             # 2 pending, staged
    released = coord.recover_gangs()
    assert released == 2
    # All-or-none: the released members re-staged and completed the
    # gang, so the whole group rides one wave.
    assert not coord._gang_staging
    coord.run_until_idle()
    for m in range(4):
        obj = json.loads(store.get(pod_key("default", f"g-m{m}")).value)
        assert obj["spec"].get("nodeName"), f"g-m{m} unbound"
    coord.close()


def test_fully_bound_gang_not_released_at_takeover(store):
    """recover_gangs must honor a COMPLETELY bound gang via the store:
    no spurious release."""
    from k8s1m_tpu.loadshed import LoadshedConfig
    from k8s1m_tpu.tenancy import TenancyController, TenancyPolicy

    put_nodes(store)
    for m in range(4):
        p = PodInfo(
            f"g-m{m}", cpu_milli=100, mem_kib=1 << 10,
            labels={"k8s1m.io/gang": "g", "k8s1m.io/gang-size": "4"},
            node_name="node-1",
        )
        store.put(pod_key("default", p.name), encode_pod(p))
    tn = TenancyController(
        TenancyPolicy(weights={"default": 1}),
        loadshed_config=LoadshedConfig(queue_cap=1 << 16),
        name="honor-gangs-test",
    )
    coord = make_coord(store, tenancy=tn)
    coord.bootstrap()
    assert coord.recover_gangs() == 0
    for m in range(4):
        obj = json.loads(store.get(pod_key("default", f"g-m{m}")).value)
        assert obj["spec"]["nodeName"] == "node-1"
    coord.close()


# ---- no-leader window: queue-or-429 ------------------------------------


def test_no_leader_submit_external_raises_overloaded(store):
    """Without a standby mirror, webhook intake during a no-leader
    window is an explicit 429 (Overloaded reason='no-leader'), never a
    silent drop."""
    ha = HACoordinator(LeaderElector(store, "a"),
                       lambda: make_coord(store))
    pod = json.loads(encode_pod(PodInfo("orphan")))
    with pytest.raises(Overloaded) as ei:
        ha.submit_external(pod)
    assert ei.value.reason == "no-leader"
    assert ei.value.retry_after_s > 0


def test_no_leader_queues_into_warm_standby_then_schedules(store):
    """With a warm standby the no-leader window QUEUES (bounded) into
    the mirror, and takeover schedules the staged pod."""
    put_nodes(store)
    ha = HACoordinator(
        LeaderElector(store, "b", retry_period_s=1.0),
        lambda: make_coord(store), warm_standby=True,
        standby_queue_cap=2,
    )
    # Elector can't acquire yet: another holder owns a fresh lease.
    other = LeaderElector(store, "other")
    assert other.tick(0.0)
    ha.tick(0.5)                     # standby: builds the mirror
    assert ha._mirror is not None and ha.coord is None
    p = PodInfo("staged-while-leaderless", cpu_milli=100, mem_kib=1 << 10)
    ha.submit_external(json.loads(encode_pod(p)))
    ha.submit_external(json.loads(encode_pod(PodInfo("second"))))
    # The bound: cap 2 reached -> explicit 429.
    with pytest.raises(Overloaded) as ei:
        ha.submit_external(json.loads(encode_pod(PodInfo("third"))))
    assert ei.value.reason == "no-leader"
    # The apiserver persists the admitted pod; the old holder dies and
    # this replica takes over: the staged pod schedules.
    store.put(pod_key("default", p.name), encode_pod(p))
    t, bound = 0.5, 0
    while t < 30.0:
        t += 1.0
        bound += ha.tick(t)
    assert ha.elector.is_leader
    assert bound >= 1
    obj = json.loads(store.get(pod_key("default", p.name)).value)
    assert obj["spec"].get("nodeName")
    ha.stop()


# ---- drill smoke lanes (tier-1) ----------------------------------------


def test_failover_drill_smoke_passes(tmp_path):
    """The composed ISSUE 9 drill at smoke scale: mid-wave kill (warm
    AND cold takeover), paused-leader split-brain under fencing — 0
    lost, 0 double-binds, byte-consistent recovery, the warm standby
    promoted and the cold one booted."""
    from k8s1m_tpu.tools.failover_drill import main

    out = tmp_path / "failover_drill.json"
    result = main(["--smoke", "--out", str(out)])
    assert result["passed"], result
    ev = result["evidence"]
    assert ev["split_brain"]["fencing_rejected"] > 0
    assert ev["mid_wave_kill_warm"]["mode"] == "warm"
    assert ev["mid_wave_kill_cold"]["mode"] == "cold"
    for k in ("mid_wave_kill_cold", "mid_wave_kill_warm", "split_brain"):
        assert ev[k]["lost"] == 0
        assert ev[k]["ledger"]["double_binds"] == 0
        assert ev[k]["consistency"]["byte_consistent"]


def test_steady_drill_mesh_smoke_passes(tmp_path):
    """benchtrue part 3: the composed steady-state drill over the
    dp x sp sharded cycle on the virtual 8-device CPU mesh."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    from k8s1m_tpu.tools.steady_drill import main

    out = tmp_path / "steady_mesh.json"
    result = main(["--smoke", "--mesh", "2x4", "--out", str(out)])
    assert result["passed"], result
    assert result["evidence"]["mesh"] == "2x4"
    assert result["evidence"]["mesh_sharded_scatters"]["cap"] > 0


def test_steady_drill_failover_smoke_passes(tmp_path):
    """ISSUE 15: the failover drill's kill scenarios folded into the
    composed steady drill — a mid-overload leader SIGKILL (warm standby
    takes over, still 0 lost) AND an upstream watch break against the
    tier sidecar (absorbed by diff-replay resume, zero client cancels)
    in ONE composed lane, same gates as ever on top."""
    from k8s1m_tpu.tools.steady_drill import main

    out = tmp_path / "steady_failover.json"
    result = main(["--smoke", "--failover", "--out", str(out)])
    assert result["passed"], result
    ev = result["evidence"]
    assert ev["lost"] == 0
    f = ev["failover"]
    assert f["kill_fired"] == 1
    assert f["beta_leader"] and f["takeover_mode"] == "warm"
    assert f["recovery_s"] is not None
    wt = f["watch_tier"]
    assert wt["events"] > 0
    assert wt["resumes"] >= 1
    assert wt["invalidations"] == 0
    assert wt["client_cancels"] == 0 and wt["client_errors"] == 0
