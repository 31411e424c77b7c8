"""Smoke coverage for the scale-proof tools (watch_scale, shard_bench).

Both tools exist to take headline measurements (100K-watch tier
residency; multi-process multi-shard e2e binds/s — reference
README.adoc:410-416 and 697-730); these tests run them at toy scale so
the suite pins their protocol end to end: real subprocesses, real wire,
machine-readable result line.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from _env import effective_cpus  # noqa: E402  (shared test-env probe)


def _run(cmd, timeout, drop_env=()):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for k in drop_env:
        env.pop(k, None)
    proc = subprocess.run(
        cmd, cwd=REPO, env=env, timeout=timeout,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    # Result is the last stdout line (tools may print progress above).
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_watch_fanout_storm_smoke_gates():
    """ISSUE 15 + ISSUE 20 tier-1 gate: the watchplane kill drill at
    10K watchers under the named watchstorm plan — zero event loss by
    ledger, every injected upstream break resolved by resume (not a
    relist storm), delivery-lag p99 and peak RSS inside the smoke
    budgets, the wiretier's shared-frame/compaction wire gates, and the
    replica SIGKILL warm-restart lane."""
    out = _run(
        [sys.executable, "-m", "k8s1m_tpu.tools.watch_fanout_ab",
         "--smoke"],
        timeout=300,
        # The RSS budget gates the WATCH TIER, not the 8-virtual-device
        # XLA arena the test harness's re-exec environment would make
        # an incidental jax import allocate (~3GB of non-tier memory).
        drop_env=("XLA_FLAGS",),
    )
    assert out["passed"] is True, json.dumps(out, indent=1)
    assert out["shape"]["watchers"] >= 9_900
    ev = out["evidence"]
    # Fan-out proof: 2 main-tier prefix watches + the replica's lease
    # slice watch, regardless of the 10K client watches.
    assert ev["store_watchers"] == 3
    assert ev["upstream_breaks"] > 0
    assert ev["resume_rate"] >= 0.9
    assert ev["lagging_at_quiesce"] == 0
    assert ev["seq_regressions"] == 0
    assert ev["idle_delivered"] == 0
    assert ev["lag_p99_ms"] <= ev["p99_budget_s"] * 1000
    assert ev["rss_mb_at_quiesce"] <= ev["rss_budget_mb"]
    # ISSUE 20 wire gates ride the pass bit; pin the evidence shape too.
    assert out["gates"]["wire_compaction"] is True
    assert out["gates"]["replica_warm_restart"] is True
    assert ev["frames_shared_ratio"] > 0.5    # hot frames actually share
    assert ev["bytes_per_delivered_event"] < ev["unshared_bytes_per_event"]
    assert ev["wire_compaction_drop"] >= ev["measured_fanout"]
    rep = ev["replica_drill"]
    assert rep["resumes"] >= 1 and rep["invalidations"] == 0
    assert rep["replica_delivered"] > 0


def test_shard_bench_smoke_two_workers_disjoint_and_done():
    out = _run(
        [
            sys.executable, "-m", "k8s1m_tpu.tools.shard_bench",
            "--nodes", "1024", "--pods", "300", "--shards", "2",
            "--batch", "64", "--score-pct", "100", "--json",
        ],
        timeout=420,
    )
    assert out["metric"] == "shard_e2e_binds_per_sec"
    assert out["value"] > 0
    assert sum(out["pod_share"]) == out["pods"] == 300
    workers = out["per_worker"]
    assert len(workers) == 2 and all(w is not None for w in workers)
    # Every worker finished its drain and said so (the done:true fix).
    assert all(w["done"] for w in workers)
    # The FNV intake split is disjoint and complete: each worker bound
    # exactly its share.
    assert [w["bound"] for w in workers] == out["pod_share"]


def test_sched_bench_churn_deletes_late_binders():
    """Config-5 shape: the delete frontier must also claim pods that
    bound AFTER it swept past (the pending set in _ChurnFrontier) —
    sustained create+delete, not a fill-up."""
    out = _run(
        [
            sys.executable, "-m", "k8s1m_tpu.tools.sched_bench",
            "--nodes", "4096", "--pods", "1500", "--batch", "256",
            "--chunk", "1024", "--score-pct", "100", "--backend", "xla",
            "--churn",
        ],
        timeout=420,
    )
    det = out["detail"]
    assert det["bound"] >= 1498
    # Everything older than the 2-wave emission lag got deleted.
    assert det["deleted"] >= 1500 - 3 * 256, det


def test_watch_scale_smoke_mux_and_fanout():
    idle, active, writes = 600, 80, 400
    out = _run(
        [
            sys.executable, "-m", "k8s1m_tpu.tools.watch_scale",
            "--idle", str(idle), "--active", str(active),
            "--writes", str(writes), "--streams", "2",
        ],
        timeout=420,
    )
    assert out["metric"] == "tier_concurrent_watches"
    assert out["value"] == idle + active
    # The tier multiplexes every client watch over its own store watches:
    # one per configured prefix, regardless of client-watch count.
    assert out["store_watchers"] == 2
    # Every hot write fanned out to exactly one active watch.
    assert out["delivered"] == writes
    assert out["canceled"] == 0
    assert out["create_per_sec"] > 0


def test_watch_scale_replicas_kill_one_no_loss():
    """Replicated fleet drill (ISSUE 20): 3 caches over one store, hot
    watches placed by the consistent-hash SubscriptionMap, one replica
    SIGKILLed mid-fan-out and WARM-RESTARTED with --resume-floor — its
    watch population re-attaches from per-watch resume revisions (a
    resume, never an invalidation) and every write is still delivered
    exactly once (the haproxy pulls-a-dead-backend contract, reference
    README.adoc:721-723)."""
    idle, active, writes = 600, 90, 600
    out = _run(
        [
            sys.executable, "-m", "k8s1m_tpu.tools.watch_scale",
            "--idle", str(idle), "--active", str(active),
            "--writes", str(writes), "--replicas", "3", "--kill-one",
        ],
        timeout=420,
    )
    assert out["replicas"] == 3
    assert out["store_watchers"] == 6       # 3 replicas x 2 prefixes
    assert out["delivered"] == writes       # no loss, no duplicates
    assert out["kill_one"]["no_event_loss"] is True
    wr = out["kill_one"]["warm_restart"]
    assert wr["resume_floor"] > 0
    assert wr["reattached_hot"] > 0 and wr["reattached_idle"] > 0
    assert wr["resumes"] >= 1 and wr["invalidations"] == 0
    # The tool's scaling lane (a wall-clock ratio spanning the SIGKILL
    # and the warm restart) is reported, not asserted: wall-clock gates
    # do not belong in a CPU test suite.
    assert "scaling" in out


def test_soak_smoke_secured_tier():
    """Short secured-tier soak: idle watches + canaries + churn through
    TLS+bearer, RSS sampled, zero cancels, zero stalls.  The committed
    10-minute artifact (artifacts/soak_secured_tier.json) is the real
    measurement; this pins the machinery."""
    import pytest

    if effective_cpus() < 2:
        # Keyed on the actual constraint, not a blanket skip: the soak
        # runs a TLS store tier + watch pumps + churn driver as
        # concurrent subprocesses, and on an effectively-1-core host
        # (affinity or cgroup quota) their event loops starve past the
        # 420s budget (known timing flake — ROADMAP re-anchor note).
        # Any multi-core host runs it for real.
        pytest.skip("effectively 1-core host: secured-tier soak "
                    "subprocesses starve the 420s budget")
    out = _run(
        [
            sys.executable, "-m", "k8s1m_tpu.tools.soak",
            "--seconds", "12", "--idle", "150", "--rate", "80",
            "--nodes", "4096", "--canaries", "8",
            # Fold the ISSUE 9 coordinator-failover phase in: the drill
            # (mid-wave kill + split-brain under fencing) runs alongside
            # the churn window and its gates ride the soak's pass bit.
            "--kill-coordinator-at", "3",
            "--out", "",            # no artifact from the smoke
        ],
        timeout=420,
    )
    assert out["canceled"] == 0
    assert out["stalls"] == 0
    assert out["churn"]["bound"] > 0
    assert out["churn"]["deleted"] > 0
    assert out["samples"] >= 2
    fo = out["coordinator_failover"]
    assert fo is not None and fo["passed"], fo
    assert fo["lost"] == 0
    assert fo["fencing_rejected"] > 0
    # rss_flat is NOT asserted: a 12s window is all startup transient.
