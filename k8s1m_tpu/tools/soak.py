"""Secured-tier churn soak: the week-long-watch scenario at bench scale.

The reference's apiserver findings are about what survives TIME: 18M
kubelet watches held for days over a control plane sustaining continuous
create/bind/delete churn (reference README.adoc:410-416, 721-730).  This
driver runs that shape end to end for ``--seconds`` (default 600):

  native store server  <-TLS+bearer-  watch-cache tier  <-TLS+bearer-
  { an idle watch population (mux streams, never written),
    a hot canary watch set,
    sched_bench --churn --rate  (create -> schedule -> CAS bind ->
    delete, the full coordinator loop) }

while sampling the tier's and the store server's RSS every
``--sample-every`` seconds.  Pass criteria, printed as one JSON line and
written (with the RSS series) to ``--out``:

- ``rss_flat``: neither process's RSS trend grows more than
  ``--max-growth-pct`` between the first and last thirds of the window
  (no per-watch or per-event leak);
- ``canceled == 0``: the idle population survives the whole soak (the
  round-4 flow-control hardening exists precisely so long-lived streams
  never stall out);
- ``stalls == 0``: after the churn window every canary watch still
  delivers a fresh write within ``--canary-timeout`` seconds — the
  streams are live, not just uncanceled;
- ``event_loss == 0``: every canary write issued during the soak is
  delivered exactly once, counted across any mid-soak failover (the
  watch-event-loss ledger).

    python -m k8s1m_tpu.tools.soak --seconds 600 --idle 5000 --rate 300

**Faultline mode** (the hour-scale robustness drill, ISSUE 1): run the
same shape under an active deterministic fault plan
(k8s1m_tpu/faultline) with a mid-soak tier-replica SIGKILL, WAL fsync
on, and a forced compaction right behind the kill:

    python -m k8s1m_tpu.tools.soak --seconds 3600 --rate 300 \
        --fault-plan default --tier-replicas 2 --kill-tier-at 1800 \
        --wal-mode fsync --out artifacts/soak_faultline.json

The canary population rides the victim replica; at ``--kill-tier-at``
the driver SIGKILLs it, then resumes every canary on the survivor from
its last delivered revision (the haproxy-pulls-a-dead-backend contract,
test_tier_replicas.py) and measures recovery time until the ledger is
caught up.  The fault plan itself reaches the churn bench via
``K8S1M_FAULT_PLAN`` — injected wire faults are retried by the shared
RetryPolicy and surface in the output as ``resilience`` (injected-fault
counts, retry totals, p50/p99 recovery per fault class).  Note: a
``watch.tier`` upstream fault cancels that replica's clients BY
CONTRACT (the cache cannot re-serve lost events), so the canned default
plan exercises the client-side classes and leaves tier failure to the
harsher SIGKILL drill.

**Overload phase** (``--overload-at`` / ``--overload-factor`` /
``--overload-seconds``): mid-soak the churn bench's offered rate steps
to ``rate x factor`` for the window, then back — the hour-scale
shed-and-recover counterpart of the deterministic tier-1
``tools/overload_drill.py``.  Composes with ``--fault-plan`` and the
tier SIGKILL, so one soak exercises faults, failover, and overload in
the same run.

**Coordinator-failover phase** (``--kill-coordinator-at``, ISSUE 9):
the kill drills above exercise the STORE side of the control plane (a
watch-cache tier replica dies; canaries resume on the survivor).  This
phase kills the *scheduler*: at the given second of the churn window
the composed ``tools/failover_drill`` runs alongside the soak —
kill-active-mid-wave (warm standby promote vs cold boot) and the
paused-leader split-brain, gated on 0 lost pods / 0 double-binds /
fencing rejects observed — so one soak covers both halves of "kill any
control-plane process and nothing is lost".  Its result is merged as
``coordinator_failover`` and folds into the run's pass gate.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

from grpc import aio

IDLE_PREFIX = b"/registry/configmaps/soak/"
CANARY_PREFIX = b"/registry/leases/soak/"

# The canned --fault-plan=default drill: every client-side fault class
# at rates an hour of churn turns into hundreds of firings, plus a
# schedule-driven coordinator watch loss.  Deterministic by seed.
DEFAULT_FAULT_PLAN = {
    "seed": 42,
    "faults": [
        {"component": "store.wire", "op": "put", "kind": "disconnect",
         "probability": 0.002},
        {"component": "store.wire", "op": "put_batch",
         "kind": "partial_write", "probability": 0.01},
        {"component": "store.wire", "op": "bind_batch",
         "kind": "disconnect", "probability": 0.005},
        {"component": "store.wire", "op": "range", "kind": "delay",
         "probability": 0.005, "delay_s": 0.02},
        {"component": "store.wire", "op": "watch.recv",
         "kind": "disconnect", "probability": 0.0005},
        {"component": "coordinator.bind", "op": "cas",
         "kind": "stale_revision", "probability": 0.002},
        {"component": "coordinator.watch", "op": "poll",
         "kind": "disconnect", "after": 10_000, "every_n": 200_000},
    ],
}


def _rss_mb(pid: int) -> float:
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmRSS"):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    return 0.0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="secured-tier churn soak")
    ap.add_argument("--seconds", type=float, default=600.0,
                    help="churn window length (the soak proper)")
    ap.add_argument("--idle", type=int, default=5000,
                    help="idle watch population held through the tier")
    ap.add_argument("--canaries", type=int, default=32,
                    help="hot watches probed for liveness at the end")
    ap.add_argument("--rate", type=int, default=300,
                    help="offered churn load (pods/s) for sched_bench")
    ap.add_argument("--nodes", type=int, default=16384)
    ap.add_argument("--sample-every", type=float, default=5.0)
    ap.add_argument("--compact-every", type=float, default=60.0,
                    help="periodic MVCC compaction interval (the "
                    "apiserver's --etcd-compaction-interval role; "
                    "without it sustained churn grows store history "
                    "unboundedly by design)")
    ap.add_argument("--max-growth-pct", type=float, default=10.0,
                    help="max allowed RSS growth, first vs last third "
                    "of the post-warmup series")
    ap.add_argument("--warmup", type=float, default=180.0,
                    help="seconds excluded from the RSS-flatness gate: "
                    "watch history windows, MVCC steady-state population "
                    "and allocator arenas legitimately fill during "
                    "ramp-up; a LEAK keeps growing after it")
    ap.add_argument("--canary-timeout", type=float, default=30.0)
    ap.add_argument("--out", default=None,
                    help="result path (default: artifacts/soak_secured_"
                    "tier.json, or artifacts/soak_faultline.json when a "
                    "fault plan is active)")
    ap.add_argument("--fault-plan", default=None,
                    help="faultline plan: inline JSON, @path, or "
                    "'default' for the canned client-side drill "
                    "(k8s1m_tpu/faultline; exported to the churn bench "
                    "via K8S1M_FAULT_PLAN)")
    ap.add_argument("--tier-replicas", type=int, default=1,
                    help="watch-cache tier replicas (>= 2 enables the "
                    "kill drill: canaries ride the last replica)")
    ap.add_argument("--kill-tier-at", type=float, default=0.0,
                    help="SIGKILL the last tier replica this many "
                    "seconds into the churn window (0 = no kill; "
                    "requires --tier-replicas >= 2)")
    ap.add_argument("--kill-coordinator-at", type=float, default=0.0,
                    help="run the coordinator-failover drill "
                    "(tools/failover_drill --smoke: mid-wave SIGKILL "
                    "with warm-standby takeover + paused-leader "
                    "split-brain under fencing) alongside the soak, "
                    "launched this many seconds into the churn window "
                    "(0 = off)")
    ap.add_argument("--wal-mode", default="buffered",
                    choices=["none", "buffered", "fsync"],
                    help="store WAL durability for the soak (the "
                    "faultline drill runs fsync)")
    ap.add_argument("--overload-at", type=float, default=0.0,
                    help="seconds into the churn window to start a "
                    "sustained overload phase: the churn bench's "
                    "offered rate jumps to rate x --overload-factor "
                    "for --overload-seconds, then drops back — the "
                    "hour-scale shed-and-recover counterpart of the "
                    "tier-1 overload_drill (0 = off)")
    ap.add_argument("--overload-seconds", type=float, default=300.0)
    ap.add_argument("--overload-factor", type=float, default=5.0)
    ap.add_argument("--tenants", type=int, default=0,
                    help="tenant-aware churn load: the churn bench "
                    "spreads its pods over N tenant namespaces with "
                    "zipf-skewed sizes (sched_bench --tenants)")
    ap.add_argument("--tenant-skew", type=float, default=1.0)
    ap.add_argument("--tenant-schedule", default="steady",
                    choices=("steady", "diurnal", "flash"),
                    help="tenant-mix arrival shape over the churn "
                    "window (diurnal day curves / a tenant-0 flash "
                    "crowd mid-window)")
    args = ap.parse_args(argv)
    if args.overload_at and (
        args.overload_at + args.overload_seconds >= args.seconds
    ):
        ap.error("the overload phase must end inside the churn window "
                 "(the recovery half of shed-and-recover needs runway)")
    if args.rate <= 0:
        ap.error("--rate must be > 0 (the soak is a paced-churn shape; "
                 "sched_bench's rate=0 branch reports different fields)")
    if args.kill_tier_at and args.tier_replicas < 2:
        ap.error("--kill-tier-at requires --tier-replicas >= 2 (the "
                 "bench and idle population need a survivor)")
    if args.kill_tier_at and args.kill_tier_at >= args.seconds:
        ap.error("--kill-tier-at must fall inside the churn window")
    if args.kill_coordinator_at and args.kill_coordinator_at >= args.seconds:
        ap.error("--kill-coordinator-at must fall inside the churn window")
    if args.out is None:
        args.out = ("artifacts/soak_faultline.json" if args.fault_plan
                    else "artifacts/soak_secured_tier.json")
    return args


async def _kill_and_resume(
    args, tier_procs, canary_keys, canary_muxes, canary_delivered,
    canary_written, survivor_channel, seed,
) -> dict:
    """The mid-soak failover drill: SIGKILL the tier replica the
    canaries ride, resume every canary on the survivor from its own
    last-delivered revision (per-watch — the stream-level max would skip
    events for a lagged watch; test_tier_replicas.py contract), force a
    compaction right behind the kill (failover and history-trim
    interacting is the case single-fault drills never see), and measure
    recovery: wall time from SIGKILL until the event ledger is caught
    up again.

    Never fatal: an hour of soak evidence must not be destroyed by the
    drill itself, so a failed resume is REPORTED (``caught_up: false``
    plus ``error``, which fails the run's gate) instead of raised."""
    from k8s1m_tpu.tools.watch_scale import MuxWatch

    victim_proc = tier_procs[-1]
    victim = canary_muxes[0]
    t_kill = time.monotonic()
    victim_proc.kill()                      # SIGKILL, not terminate
    # Let the broken stream drain: events the victim already handed to
    # the client library still land in `delivered`/`watch_rev`; reading
    # the resume points too early would replay them as duplicates.
    await asyncio.sleep(0.5)
    resume = MuxWatch(survivor_channel)
    starts = [
        victim.watch_rev.get(1 + i, victim.create_rev) + 1
        for i in range(len(canary_keys))
    ]
    try:
        await resume.create(canary_keys, 1, start_revision=starts)
        # Generous create window: the survivor shares one event loop
        # with its full watch fan-out, and on a small host every other
        # soak process competes for the same cores.
        await resume.wait_created(
            len(canary_keys), timeout=max(120.0, 4 * args.canary_timeout)
        )
    # Reported in the drill's structured result (recovery_s: None).
    except Exception as e:  # graftlint: disable=broad-except
        print(f"# tier kill drill: resume FAILED: {e!r}", file=sys.stderr)
        canary_muxes.append(resume)      # count whatever it delivers
        return {
            "at_s": round(args.kill_tier_at, 1),
            "recovery_s": None,
            "caught_up": False,
            "error": repr(e),
        }
    canary_muxes.append(resume)
    try:
        st = await seed.status()
        if st.header.revision - 2000 > 1:
            await seed.compact(st.header.revision - 2000)
    # Best-effort compaction pressure; the canary gate is the check.
    except Exception:  # graftlint: disable=broad-except
        pass
    deadline = time.monotonic() + args.canary_timeout
    while (
        canary_delivered() < canary_written()
        and time.monotonic() < deadline
    ):
        await asyncio.sleep(0.05)
    recovery_s = time.monotonic() - t_kill
    caught_up = canary_delivered() >= canary_written()
    print(
        f"# tier kill drill: recovery_s={recovery_s:.2f} "
        f"caught_up={caught_up}", file=sys.stderr,
    )
    return {
        "at_s": round(args.kill_tier_at, 1),
        "recovery_s": round(recovery_s, 3),
        "caught_up": caught_up,
    }


async def _wait_port(port: int, proc, deadline_s: float) -> None:
    deadline = time.monotonic() + deadline_s
    while True:
        if proc.poll() is not None:
            raise RuntimeError(f"subprocess exited rc={proc.returncode}")
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=0.2):
                return
        except OSError:
            if time.monotonic() > deadline:
                raise TimeoutError(f"port {port} never bound")
            # Deadline-bounded readiness poll, not an op retry.
            await asyncio.sleep(0.1)  # graftlint: disable=retry-through-policy


async def amain(args) -> dict:
    from k8s1m_tpu.cluster.certs import provision
    from k8s1m_tpu.cluster.harness import _free_port
    from k8s1m_tpu.store.etcd_client import EtcdClient, secure_channel_for
    from k8s1m_tpu.tools.watch_scale import MuxWatch

    certs_dir = tempfile.mkdtemp(prefix="soak-certs-")
    certs = provision(certs_dir)
    token = "soak-bearer-token"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    plan = None
    fault_env = env
    if args.fault_plan:
        from k8s1m_tpu.faultline import FaultPlan

        if args.fault_plan == "default":
            plan = FaultPlan.from_json(DEFAULT_FAULT_PLAN)
        else:
            plan = FaultPlan.from_arg(args.fault_plan)
        # The hooks live in the CLIENTS (bench coordinator + RemoteStore,
        # tier upstream pumps); the soak's own ledger writes stay clean.
        fault_env = {**env, "K8S1M_FAULT_PLAN": plan.to_json()}

    store_port = _free_port()
    wal_dir = tempfile.mkdtemp(prefix="soak-wal-")
    store_proc = subprocess.Popen(
        [sys.executable, "-m", "k8s1m_tpu.store.server_main",
         "--port", str(store_port), "--host", "127.0.0.1",
         "--metrics-port", "0", "--wal-dir", wal_dir,
         "--wal-default", args.wal_mode, "--wire", "native"],
        env=env,
    )
    procs = [store_proc]
    try:
        await _wait_port(store_port, store_proc, 60)
        # Seed the idle/canary objects BEFORE the tier primes.
        seed = EtcdClient(f"127.0.0.1:{store_port}")
        wave = []
        for i in range(args.idle):
            wave.append((IDLE_PREFIX + b"cm-%06d" % i, b'{"data":{}}'))
            if len(wave) == 4096:
                await seed.put_batch(wave)
                wave.clear()
        for i in range(args.canaries):
            wave.append((CANARY_PREFIX + b"canary-%03d" % i, b"0"))
        if wave:
            await seed.put_batch(wave)

        tier_ports = [_free_port() for _ in range(args.tier_replicas)]
        tier_procs = []
        for port in tier_ports:
            p = subprocess.Popen(
                [sys.executable, "-m", "k8s1m_tpu.store.watch_cache",
                 "--upstream", f"127.0.0.1:{store_port}",
                 "--host", "127.0.0.1", "--port", str(port),
                 "--prefix", "/registry/",
                 "--tls-cert", certs.cert_pem, "--tls-key", certs.key_pem,
                 "--auth-token", token],
                env=fault_env,
            )
            tier_procs.append(p)
            procs.append(p)
        for port, p in zip(tier_ports, tier_procs):
            await _wait_port(port, p, 120 + args.idle / 1000)
        tier_proc = tier_procs[0]       # survivor: RSS trend + bench target
        tier_port = tier_ports[0]
        # Canaries ride the LAST replica — the kill drill's victim.
        canary_port = tier_ports[-1]

        # Idle + canary populations through the SECURED tier.
        channel = secure_channel_for(
            f"127.0.0.1:{tier_port}", certs.ca_pem, token,
            options=[("grpc.max_receive_message_length", 64 << 20)],
        )
        muxes = [MuxWatch(channel) for _ in range(4)]
        per = (args.idle + len(muxes) - 1) // len(muxes)
        next_id = 1
        counts = []
        for m in muxes:
            lo = next_id - 1
            keys = [IDLE_PREFIX + b"cm-%06d" % (lo + i)
                    for i in range(max(0, min(per, args.idle - lo)))]
            await m.create(keys, next_id)
            counts.append(len(keys))
            next_id += len(keys)
        for m, n in zip(muxes, counts):
            await m.wait_created(n, timeout=120 + args.idle / 500)
        canary_channel = secure_channel_for(
            f"127.0.0.1:{canary_port}", certs.ca_pem, token,
            options=[("grpc.max_receive_message_length", 64 << 20)],
        )
        canary = MuxWatch(canary_channel)
        canary_keys = [CANARY_PREFIX + b"canary-%03d" % i
                       for i in range(args.canaries)]
        await canary.create(canary_keys, 1)
        await canary.wait_created(args.canaries, timeout=60)
        canary_muxes = [canary]         # victim stream [+ survivor resume]

        def canary_delivered() -> int:
            return sum(m.delivered for m in canary_muxes)

        # Churn through the tier: the full coordinator loop as a
        # subprocess (create -> watch -> schedule -> CAS bind -> delete)
        # at the offered rate for the whole window.
        pods = max(1000, int(args.rate * args.seconds))
        bench_cmd = [
            sys.executable, "-m", "k8s1m_tpu.tools.sched_bench",
            "--nodes", str(args.nodes), "--pods", str(pods),
            "--rate", str(args.rate), "--score-pct", "5",
            "--backend", "xla", "--churn",
            "--target", f"127.0.0.1:{tier_port}",
            "--ca-pem", certs.ca_pem, "--token", token,
        ]
        if args.overload_at:
            # The overload phase offers extra pods; size --pods so the
            # producer does not run dry before the window closes.
            pods += int(
                args.rate * (args.overload_factor - 1) * args.overload_seconds
            )
            bench_cmd[bench_cmd.index("--pods") + 1] = str(pods)
            bench_cmd += [
                "--overload-at", str(args.overload_at),
                "--overload-seconds", str(args.overload_seconds),
                "--overload-factor", str(args.overload_factor),
            ]
        if args.tenants:
            bench_cmd += [
                "--tenants", str(args.tenants),
                "--tenant-skew", str(args.tenant_skew),
                "--tenant-schedule", args.tenant_schedule,
            ]
        bench_proc = subprocess.Popen(
            bench_cmd, env=fault_env, stdout=subprocess.PIPE, text=True,
        )
        procs.append(bench_proc)

        # RSS sampler over the churn window, with periodic MVCC
        # compaction (keep a revision margin so the tier's watch
        # resume window stays usable).  Every sample tick also writes
        # one ledger value per canary key: `canary_written` vs
        # `canary_delivered()` is the exactly-once watch-event ledger
        # the `event_loss == 0` gate settles on.
        series = []
        canary_written = 0
        tick = 0
        kill_info = None
        failover_proc = None
        t0 = time.monotonic()
        next_compact = t0 + args.compact_every
        while bench_proc.poll() is None:
            if time.monotonic() >= next_compact:
                next_compact = time.monotonic() + args.compact_every
                try:
                    st = await seed.status()
                    target = st.header.revision - 5000
                    if target > 1:
                        await seed.compact(target)
                except Exception:  # graftlint: disable=broad-except
                    pass    # compaction is best-effort in the soak
            tick += 1
            try:
                for k in canary_keys:
                    await seed.put(k, b"tick-%06d" % tick)
                    # Counted per put, not per tick: a loop that dies
                    # after 3 of N puts DID write 3 events — counting 0
                    # would turn them into phantom negative event_loss.
                    canary_written += 1
            except Exception:  # graftlint: disable=broad-except
                pass        # ledger writes pause while the store restarts
            if (
                args.kill_coordinator_at
                and failover_proc is None
                and time.monotonic() - t0 >= args.kill_coordinator_at
            ):
                # The coordinator-failover phase rides its own process
                # (tick-driven, deterministic, own in-process store) so
                # the soak's wire ledger stays untouched while the
                # scheduler-kill scenarios run to their own gates.
                failover_proc = subprocess.Popen(
                    [sys.executable, "-m",
                     "k8s1m_tpu.tools.failover_drill", "--smoke"],
                    env=env, stdout=subprocess.PIPE, text=True,
                )
                procs.append(failover_proc)
            if (
                args.kill_tier_at
                and kill_info is None
                and time.monotonic() - t0 >= args.kill_tier_at
            ):
                kill_info = await _kill_and_resume(
                    args, tier_procs, canary_keys, canary_muxes,
                    canary_delivered, lambda: canary_written,
                    channel, seed,
                )
            series.append({
                "t_s": round(time.monotonic() - t0, 1),
                "tier_rss_mb": round(_rss_mb(tier_proc.pid), 1),
                "store_rss_mb": round(_rss_mb(store_proc.pid), 1),
                "idle_canceled": sum(m.canceled for m in muxes),
            })
            # Sleep in short slices so a finished bench is noticed
            # within ~0.5s, not a full sample interval late.
            slept = 0.0
            while slept < args.sample_every and bench_proc.poll() is None:
                await asyncio.sleep(0.5)
                slept += 0.5
            # Overload backlog legitimately drains past the window; give
            # the bench the extra runway before calling it hung.
            grace = 900 + (
                args.overload_factor * args.overload_seconds
                if args.overload_at else 0
            )
            if time.monotonic() - t0 > args.seconds + grace:
                bench_proc.kill()
                raise TimeoutError("churn bench overran the window")
        bench_out = bench_proc.stdout.read()
        if bench_proc.returncode != 0 or not bench_out.strip():
            raise RuntimeError(
                f"churn bench rc={bench_proc.returncode}, "
                f"stdout={bench_out!r}"
            )
        bench_line = json.loads(bench_out.strip().splitlines()[-1])
        soak_s = time.monotonic() - t0

        failover_info = None
        if failover_proc is not None:
            # Bound the wait WELL below the smoke test's 420s budget so
            # a slow/wedged drill reports as a failed gate instead of
            # timing out the whole soak (which would destroy both runs'
            # evidence); the drill itself is ~1-2 min at --smoke scale.
            try:
                fo_out, _ = failover_proc.communicate(timeout=240)
                fo = json.loads(fo_out.strip().splitlines()[-1])
                failover_info = {
                    "at_s": round(args.kill_coordinator_at, 1),
                    "passed": bool(fo.get("passed")),
                    "recovery_warm_s": fo["evidence"]["recovery_warm_s"],
                    "recovery_cold_s": fo["evidence"]["recovery_cold_s"],
                    "fencing_rejected": fo["evidence"]["split_brain"][
                        "fencing_rejected"],
                    "lost": max(
                        fo["evidence"][k]["lost"]
                        for k in ("mid_wave_kill_warm", "mid_wave_kill_cold",
                                  "split_brain")
                    ),
                }
            # A failed/hung drill must FAIL the gate, not destroy the
            # soak's own evidence.
            except Exception as e:  # graftlint: disable=broad-except
                failover_proc.kill()
                failover_info = {
                    "at_s": round(args.kill_coordinator_at, 1),
                    "passed": False,
                    "error": repr(e),
                }

        # Liveness probe: every canary stream must deliver a fresh write.
        base = canary_delivered()
        for i, k in enumerate(canary_keys):
            await seed.put(k, b"alive-%d" % i)
        canary_written += args.canaries
        deadline = time.monotonic() + args.canary_timeout
        while (
            canary_delivered() - base < args.canaries
            and time.monotonic() < deadline
        ):
            await asyncio.sleep(0.1)
        stalls = args.canaries - (canary_delivered() - base)

        # Event-loss ledger: every canary write issued after watch
        # registration must have been delivered exactly once, counted
        # across the victim stream and any failover resume.  Positive =
        # lost events; negative = duplicates (a resume that replayed).
        deadline = time.monotonic() + args.canary_timeout
        while (
            canary_delivered() < canary_written
            and time.monotonic() < deadline
        ):
            await asyncio.sleep(0.1)
        event_loss = canary_written - canary_delivered()

        canceled = (
            sum(m.canceled for m in muxes)
            + sum(m.canceled for m in canary_muxes)
        )

        # RSS trend: mean of the first vs last third of the POST-WARMUP
        # series (the ramp legitimately fills caches/arenas; a leak
        # keeps growing after it).
        # Short runs can't honor the full warmup; scale it down rather
        # than silently gating on the startup ramp (which would fail a
        # leak-free run).
        horizon = series[-1]["t_s"] if series else 0.0
        warmup = min(args.warmup, horizon / 3)

        def trend(key):
            vals = [
                s[key] for s in series
                if s[key] > 0 and s["t_s"] >= warmup
            ]
            if len(vals) < 6:
                return 0.0, 0.0
            third = len(vals) // 3
            first = sum(vals[:third]) / third
            last = sum(vals[-third:]) / third
            return first, last

        tier_first, tier_last = trend("tier_rss_mb")
        store_first, store_last = trend("store_rss_mb")
        growth = {
            "tier_pct": round(100 * (tier_last - tier_first)
                              / max(tier_first, 1e-9), 2),
            "store_pct": round(100 * (store_last - store_first)
                               / max(store_first, 1e-9), 2),
        }
        rss_flat = (
            growth["tier_pct"] <= args.max_growth_pct
            and growth["store_pct"] <= args.max_growth_pct
        )

        for m in muxes:
            await m.close()
        for m in canary_muxes:
            await m.close()
        await canary_channel.close()
        await channel.close()
        await seed.close()

        detail = bench_line["detail"]
        result = {
            "metric": ("soak_faultline_seconds" if plan
                       else "soak_secured_tier_seconds"),
            "value": round(soak_s, 1),
            "unit": "s",
            "vs_baseline": None,
            "passed": bool(
                rss_flat and canceled == 0 and stalls == 0
                and event_loss == 0
                and (kill_info is None or kill_info["caught_up"])
                and (failover_info is None or failover_info["passed"])
            ),
            "rss_flat": rss_flat,
            "rss_growth": growth,
            "canceled": canceled,
            "stalls": stalls,
            "event_loss": event_loss,
            "canary_writes": canary_written,
            "idle_watches": args.idle,
            "wal_mode": args.wal_mode,
            "tier_replicas": args.tier_replicas,
            "tier_kill": kill_info,
            "coordinator_failover": failover_info,
            "fault_plan": (
                {"seed": plan.seed, "specs": [f.to_obj() for f in plan.faults]}
                if plan else None
            ),
            # The churn bench's injected-fault + retry evidence (it is
            # the process the plan's client-side hooks fire in).
            "resilience": {
                k: detail[k]
                for k in ("faults_injected", "retry_attempts",
                          "give_ups", "recovery")
                if k in detail
            } or None,
            "overload": (
                {"at_s": args.overload_at,
                 "seconds": args.overload_seconds,
                 "factor": args.overload_factor}
                if args.overload_at else None
            ),
            "churn": {
                "rate": args.rate,
                "bound": detail["bound"],
                "deleted": detail["deleted"],
                "binds_per_sec": detail["binds_per_sec"],
                "p50_ms": detail["p50_ms"],
            },
            "samples": len(series),
        }
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({**result, "rss_series": series}, f, indent=1)
        return result
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        import shutil

        for d in (certs_dir, wal_dir):
            shutil.rmtree(d, ignore_errors=True)


def main(argv=None):
    args = parse_args(argv)
    print(json.dumps(asyncio.run(amain(args))))


if __name__ == "__main__":
    main()
