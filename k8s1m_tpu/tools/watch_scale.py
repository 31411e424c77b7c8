"""Watch-cache tier SCALE proof: hold >=100K concurrent client watches
on one core and measure what they cost.

The reference's finding is 18 apiserver watches per node -> 18M client
watches at 1M nodes, none reaching etcd (reference README.adoc:410-416).
`watch_fanout_ab.py` proves the amplification economics at bench scale;
this tool proves the TIER ITSELF holds six figures of concurrent
watches: creation rate, resident memory per watch, store-side watcher
count (constant), and live fan-out throughput with the idle population
attached.

Watches are MULTIPLEXED over a few bidi streams with explicit watch ids
— exactly how kube-apiserver talks to etcd (one stream, many watches),
and the only honest way to hold 100K watches from one client core.

With ``--replicas N`` the tier grows into a FLEET: hot keys pin to
replicas through the wiretier's consistent-hash ``SubscriptionMap``
(not round-robin slicing), and the ``--kill-one`` drill becomes a WARM
RESTART — the victim is relaunched with ``--resume-floor`` and its
watches re-attach to it from their own revisions (reprime diff replay),
instead of 100K clients relisting through the survivors.  When the
environment actually has >= 2 effective CPUs the fleet must also scale:
aggregate fan-out throughput is gated against a single-replica
calibration window; on a 1-core box the gate degrades to
correctness-only (zero loss + warm resume), reported as such.

    python -m k8s1m_tpu.tools.watch_scale --idle 100000 --active 2000
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import time

import grpc
from grpc import aio

from k8s1m_tpu.store.etcd_client import EtcdClient
from k8s1m_tpu.store.native import MemStore, decode_shared_tail
from k8s1m_tpu.store.proto import rpc_pb2
from k8s1m_tpu.store.watch_cache import serve_watch_cache
from k8s1m_tpu.store.wiretier import SubscriptionMap

IDLE_PREFIX = b"/registry/configmaps/scale/"
HOT_PREFIX = b"/registry/leases/scale/"


def _rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1]) / 1024.0
    return 0.0


def _effective_cpus() -> int:
    """CPUs this process can actually burn (cgroup quota wins over the
    host count): the knob that decides whether the replica fleet can
    honestly be gated on SCALING or only on correctness."""
    try:
        with open("/sys/fs/cgroup/cpu.max") as f:
            quota, period = f.read().split()
        if quota != "max":
            return max(1, int(int(quota) // int(period)))
    except (OSError, ValueError):
        pass
    return os.cpu_count() or 1


def _tier_rss_mb(pid: int) -> float:
    with open(f"/proc/{pid}/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1]) / 1024.0
    return 0.0


class MuxWatch:
    """One bidi Watch stream carrying many watches (client side)."""

    def __init__(self, channel: aio.Channel, replica: int = 0):
        self.replica = replica      # which tier replica this stream rides
        self._call = channel.stream_stream(
            "/etcdserverpb.Watch/Watch",
            request_serializer=rpc_pb2.WatchRequest.SerializeToString,
            # Raw frames: the reader decodes the wiretier shared-frame
            # tail itself and fans one frame's events to every watch id
            # riding it (index selection over shared bytes).
            response_deserializer=lambda b: b,
        )()
        self.created = 0
        self.delivered = 0
        self.canceled = 0
        self.last_rev = 0           # highest event revision seen (any watch)
        self.create_rev = 0         # revision at watch registration
        # Per-watch-id resume point: the stream-level max would SKIP
        # events for a watch whose delivery lagged the stream max.
        self.watch_rev: dict[int, int] = {}
        self._created_ev = asyncio.Event()
        self._reader = asyncio.create_task(self._read())

    async def create(
        self, keys: list[bytes], first_id: int,
        start_revision: int | list[int] = 0,
    ) -> None:
        for i, key in enumerate(keys):
            await self._call.write(
                rpc_pb2.WatchRequest(
                    create_request=rpc_pb2.WatchCreateRequest(
                        key=key, watch_id=first_id + i,
                        start_revision=(
                            start_revision[i]
                            if isinstance(start_revision, list)
                            else start_revision
                        ),
                    )
                )
            )

    async def wait_created(self, n: int, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while self.created < n:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"only {self.created}/{n} watches created"
                )
            await asyncio.sleep(0.05)

    async def _read(self) -> None:
        try:
            async for raw in self._call:
                extra, _from_rev, _core = decode_shared_tail(raw)
                resp = rpc_pb2.WatchResponse.FromString(raw)
                if resp.canceled:
                    self.canceled += 1
                elif resp.created:
                    self.created += 1
                    if resp.header.revision > self.create_rev:
                        self.create_rev = resp.header.revision
                else:
                    wids = (resp.watch_id, *extra)
                    self.delivered += len(resp.events) * len(wids)
                    for ev in resp.events:
                        if ev.kv.mod_revision > self.last_rev:
                            self.last_rev = ev.kv.mod_revision
                    if resp.events:
                        r = resp.events[-1].kv.mod_revision
                        for wid in wids:
                            if r > self.watch_rev.get(wid, 0):
                                self.watch_rev[wid] = r
        except (asyncio.CancelledError, grpc.RpcError):
            pass

    async def close(self) -> None:
        self._reader.cancel()
        try:
            await self._reader
        # Close-path cancel: the reader is being torn down either way.
        except (asyncio.CancelledError, Exception):  # graftlint: disable=broad-except
            pass


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="tier watch-scale proof")
    ap.add_argument("--idle", type=int, default=100_000)
    ap.add_argument("--active", type=int, default=2_000)
    ap.add_argument("--streams", type=int, default=8,
                    help="bidi streams the watches multiplex over")
    ap.add_argument("--writes", type=int, default=20_000)
    ap.add_argument("--index", choices=("hash", "btree"), default="hash")
    ap.add_argument("--lag-budget", type=int, default=0,
                    help="tier per-subscriber FIFO budget before "
                    "latest-only coalescing (watchplane; 0 = tier "
                    "default)")
    ap.add_argument("--pumps", type=int, default=0,
                    help="tier fan-out pump lanes per Watch stream "
                    "(watchplane; 0 = tier default)")
    ap.add_argument(
        "--replicas", type=int, default=1,
        help="tier replica processes over the ONE store; client streams "
        "round-robin across them — the reference's 11-apiserver fleet "
        "behind haproxy SRV round-robin (reference README.adoc:721-723, "
        "terraform/k8s-server/server.tf:230-251)",
    )
    ap.add_argument(
        "--kill-one", action="store_true",
        help="crash drill: SIGKILL the last replica halfway through the "
        "fan-out window, relaunch it with --resume-floor (warm restart) "
        "and re-attach its watches to it from their own revisions — "
        "no relist, no subscription reshuffle, zero event loss",
    )
    return ap.parse_args(argv)


async def amain(args) -> dict:
    import subprocess
    import sys

    from k8s1m_tpu.store.native import WireFront

    store = MemStore()
    # Native wire server: keeps the store off this event loop entirely
    # (the asyncio server would contend with the mux readers for it).
    wf = WireFront(store)
    store_port = wf.port
    seed = EtcdClient(f"127.0.0.1:{store_port}")
    # Idle objects exist but never change after creation.
    wave = []
    for i in range(args.idle):
        wave.append((IDLE_PREFIX + b"cm-%07d" % i, b'{"data":{}}'))
        if len(wave) == 8192:
            await seed.put_batch(wave)
            wave.clear()
    for i in range(args.active):
        wave.append((HOT_PREFIX + b"lease-%05d" % i, b"0"))
    if wave:
        await seed.put_batch(wave)

    # Tier replicas as SUBPROCESSES so their RSS is attributable.  N
    # replicas share the ONE store upstream (each holds its own cache +
    # upstream watch); client streams round-robin across them — the
    # reference's 11-apiserver fleet behind haproxy SRV round-robin
    # (reference README.adoc:721-723, server.tf:230-251).
    from k8s1m_tpu.cluster.harness import _free_port

    n_rep = max(1, args.replicas)
    if args.streams < n_rep:
        args.streams = n_rep        # at least one stream per replica
    tier_ports = [_free_port() for _ in range(n_rep)]
    tier_flags = []
    if args.lag_budget:
        tier_flags += ["--lag-budget", str(args.lag_budget)]
    if args.pumps:
        tier_flags += ["--pumps", str(args.pumps)]
    _env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    def _tier_cmd(port: int, extra=()) -> list:
        return [
            sys.executable, "-m", "k8s1m_tpu.store.watch_cache",
            "--upstream", f"127.0.0.1:{store_port}",
            "--host", "127.0.0.1", "--port", str(port),
            "--prefix", IDLE_PREFIX.decode(),
            "--prefix", HOT_PREFIX.decode(),
            "--index", args.index,
            *tier_flags, *extra,
        ]

    tier_procs = [
        subprocess.Popen(_tier_cmd(port), env=_env) for port in tier_ports
    ]
    channels = []
    try:
        # The in-process store server shares THIS event loop; a blocking
        # wait_for_port would starve it and deadlock the tier's priming.
        import socket as _socket

        deadline = time.monotonic() + 120 + n_rep * args.idle / 2000
        for proc, port in zip(tier_procs, tier_ports):
            while True:
                if proc.poll() is not None:
                    raise RuntimeError(f"tier exited rc={proc.returncode}")
                try:
                    with _socket.create_connection(
                        ("127.0.0.1", port), timeout=0.2
                    ):
                        break
                except OSError:
                    if time.monotonic() > deadline:
                        raise TimeoutError("tier did not bind")
                    # Deadline-bounded readiness poll, not an op retry.
                    await asyncio.sleep(0.05)  # graftlint: disable=retry-through-policy
        rss0 = sum(_tier_rss_mb(p.pid) for p in tier_procs)

        channels = [
            aio.insecure_channel(
                f"127.0.0.1:{port}",
                options=[("grpc.max_receive_message_length", 64 << 20)],
            )
            for port in tier_ports
        ]
        muxes = [
            MuxWatch(channels[i % n_rep], replica=i % n_rep)
            for i in range(args.streams)
        ]

        # Create idle watches round-robin over the streams.
        t0 = time.perf_counter()
        per = (args.idle + args.streams - 1) // args.streams
        next_id = 1
        creates = []
        for m in muxes:
            lo = next_id - 1
            keys = [
                IDLE_PREFIX + b"cm-%07d" % (lo + i)
                for i in range(min(per, args.idle - lo))
            ]
            creates.append((m, keys, next_id))
            next_id += len(keys)
        await asyncio.gather(
            *(m.create(keys, fid) for m, keys, fid in creates)
        )
        for m, keys, _ in creates:
            await m.wait_created(len(keys), timeout=240)
        create_s = time.perf_counter() - t0

        # Active watches on the hot keys, placed by the wiretier's
        # consistent-hash SubscriptionMap — each hot key subscribes to
        # exactly ONE replica, and the map is what makes a replica
        # restart a LOCAL event: survivors' subscriptions provably
        # never move (no fleet-wide reshuffle, no relist storm).
        hot_keys = [HOT_PREFIX + b"lease-%05d" % i for i in range(args.active)]
        smap = SubscriptionMap(range(n_rep))
        rep_keys: list[list[bytes]] = [[] for _ in range(n_rep)]
        for k in hot_keys:
            rep_keys[smap.replica_for(k)].append(k)

        async def attach_hot(r: int):
            nonlocal next_id
            keys = rep_keys[r]
            if not keys:
                return None
            first, m = next_id, muxes[r]
            next_id += len(keys)
            base = m.created
            await m.create(keys, first)
            await m.wait_created(base + len(keys), timeout=120)
            return (m, keys, first)

        async def burst_window(keys: list, writes: int) -> float:
            """Unpaced writes over ``keys``; returns delivered/s once
            every write's event has fanned out."""
            base = sum(m.delivered for m in muxes)
            t0 = time.perf_counter()
            written = 0
            while written < writes:
                n = min(2000, writes - written)
                await seed.put_batch([
                    (keys[(written + i) % len(keys)], b"c%d" % (written + i))
                    for i in range(n)
                ])
                written += n
            deadline = time.monotonic() + 120
            while (
                sum(m.delivered for m in muxes) - base < writes
                and time.monotonic() < deadline
            ):
                await asyncio.sleep(0.05)
            return round(
                (sum(m.delivered for m in muxes) - base)
                / (time.perf_counter() - t0), 1,
            )

        hot_slices = []             # (mux, keys, first_id) per replica
        calib_rate = None
        cpus = _effective_cpus()
        if n_rep > 1 and cpus >= 2:
            # SCALING lane — only honest with real parallelism: one
            # replica's fan-out alone first, the fleet's aggregate
            # after, gated on the ratio.  On a 1-core box the fleet
            # still runs (correctness-only) but no linearity is
            # claimed.
            s0 = await attach_hot(0)
            if s0 is not None:
                hot_slices.append(s0)
                calib_rate = await burst_window(
                    rep_keys[0], max(500, args.writes // 4)
                )
        for r in range(n_rep):
            if calib_rate is not None and r == 0:
                continue            # already attached for calibration
            s = await attach_hot(r)
            if s is not None:
                hot_slices.append(s)

        rss1 = sum(_tier_rss_mb(p.pid) for p in tier_procs)
        store_watchers = store.stats()["watchers"]

        # Live fan-out: write the hot keys while the idle watches sit
        # attached; every write fans to exactly one active watch.  With
        # --kill-one, SIGKILL the last replica halfway and re-attach its
        # hot watches to a survivor from the last delivered revision —
        # the haproxy-pulls-a-dead-backend drill.
        t0 = time.perf_counter()
        written = 0
        killed_at = None
        warm_restart = None
        victim_mport = 0
        base_delivered = sum(m.delivered for m in muxes)
        while written < args.writes:
            # Batch bounded by writes/4 so a --kill-one drill always
            # lands MID-stream, even on small smoke runs.
            n = min(2000, max(1, args.writes // 4), args.writes - written)
            await seed.put_batch([
                (hot_keys[(written + i) % args.active], b"%d" % (written + i))
                for i in range(n)
            ])
            written += n
            if (
                args.kill_one and n_rep > 1 and killed_at is None
                and written >= args.writes // 2
            ):
                killed_at = written
                victim = n_rep - 1
                t_kill = time.perf_counter()
                tier_procs[victim].kill()
                tier_procs[victim].wait()
                dead_muxes = [m for m in muxes if m.replica == victim]
                # Join the dead streams' readers BEFORE reading their
                # resume revisions: grpc may still hold buffered
                # responses the reader task hasn't processed — a
                # snapshot taken early would replay revisions the dead
                # stream then also counts (duplicates).
                for dm in dead_muxes:
                    await dm.close()
                # WARM RESTART (the fleet contract): relaunch the
                # victim on its own port with --resume-floor at the
                # weakest proven position of its hot watches.  The
                # SubscriptionMap is untouched — no key moves, no
                # survivor reshuffles — and every watch re-attaches to
                # the relaunched replica from its OWN revision (the
                # watch's last delivered revision, or its registration
                # revision when it never delivered; a stream-level max
                # would skip the laggards' events).  Resume is a diff
                # replay out of the rebuilt history window — not a
                # relist.
                hot = next(
                    (s for s in hot_slices if s[0].replica == victim),
                    None,
                )
                floor = 0
                resume_at: list[int] = []
                if hot is not None:
                    hot_m, rkeys, first = hot
                    resume_at = [
                        max(hot_m.watch_rev.get(first + i, 0),
                            hot_m.create_rev)
                        for i in range(len(rkeys))
                    ]
                    floor = min(resume_at)
                victim_mport = _free_port()
                tier_procs[victim] = subprocess.Popen(
                    _tier_cmd(
                        tier_ports[victim],
                        ["--resume-floor", str(floor),
                         "--metrics-port", str(victim_mport)],
                    ),
                    env=_env,
                )
                bind_by = time.monotonic() + 240
                while True:
                    if tier_procs[victim].poll() is not None:
                        raise RuntimeError(
                            "relaunched replica exited rc="
                            f"{tier_procs[victim].returncode}"
                        )
                    try:
                        with _socket.create_connection(
                            ("127.0.0.1", tier_ports[victim]), timeout=0.2
                        ):
                            break
                    except OSError:
                        if time.monotonic() > bind_by:
                            raise TimeoutError(
                                "relaunched replica did not bind"
                            )
                        # Deadline-bounded readiness poll, not an op retry.
                        await asyncio.sleep(0.05)  # graftlint: disable=retry-through-policy
                chan = aio.insecure_channel(
                    f"127.0.0.1:{tier_ports[victim]}",
                    options=[("grpc.max_receive_message_length", 64 << 20)],
                )
                channels.append(chan)
                if hot is not None:
                    resume = MuxWatch(chan, replica=victim)
                    await resume.create(
                        rkeys, first,
                        start_revision=[r + 1 for r in resume_at],
                    )
                    try:
                        await resume.wait_created(len(rkeys), timeout=120)
                    except TimeoutError as e:
                        raise TimeoutError(
                            f"{e}; canceled={resume.canceled} "
                            f"floor={floor}"
                        ) from None
                    muxes.append(resume)
                # The victim's idle watches re-register plain: their
                # keys never changed, so they carry no resume
                # obligation (nothing to replay, nothing to relist).
                reattached_idle = 0
                for mm, ikeys, ifirst in creates:
                    if mm not in dead_muxes:
                        continue
                    im = MuxWatch(chan, replica=victim)
                    await im.create(ikeys, ifirst)
                    await im.wait_created(len(ikeys), timeout=240)
                    muxes.append(im)
                    reattached_idle += len(ikeys)
                warm_restart = {
                    "resume_floor": floor,
                    "restart_seconds": round(
                        time.perf_counter() - t_kill, 2
                    ),
                    "reattached_hot": len(resume_at),
                    "reattached_idle": reattached_idle,
                }
        # Wait for deliveries to drain.
        deadline = time.monotonic() + 120
        while (
            sum(m.delivered for m in muxes) - base_delivered < args.writes
            and time.monotonic() < deadline
        ):
            await asyncio.sleep(0.05)
        window = time.perf_counter() - t0
        delivered = sum(m.delivered for m in muxes) - base_delivered

        if warm_restart is not None:
            # The relaunched replica's own counters are the warm-restart
            # receipt: resumes (reprime diff replay) moved, invalidations
            # (the relist-everyone path) did not.
            import urllib.request

            def _scrape():
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{victim_mport}/metrics", timeout=10
                ) as r:
                    return r.read().decode()

            counts: dict = {}
            for line in (await asyncio.to_thread(_scrape)).splitlines():
                if line.startswith("#") or not line.strip():
                    continue
                name, _, val = line.rpartition(" ")
                base_name = name.split("{", 1)[0]
                try:
                    counts[base_name] = counts.get(base_name, 0.0) + float(val)
                except ValueError:
                    continue
            warm_restart["resumes"] = int(
                counts.get("watchcache_resumes_total", 0)
            )
            warm_restart["invalidations"] = int(
                counts.get("watchcache_invalidations_total", 0)
            )

        for m in muxes:
            await m.close()
        for channel in channels:
            await channel.close()
    finally:
        for p in tier_procs:
            p.terminate()
        for p in tier_procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        await seed.close()
        wf.close()
        store.close()

    total_watches = args.idle + args.active
    out = {
        "metric": "tier_concurrent_watches",
        "value": total_watches,
        "unit": "watches",
        "vs_baseline": round(total_watches / 18_000_000, 4),
        "replicas": n_rep,
        "create_per_sec": round(args.idle / create_s, 1),
        "tier_rss_mb": round(rss1, 1),
        "kb_per_watch": round((rss1 - rss0) * 1024.0 / total_watches, 2),
        "store_watchers": store_watchers,
        "delivered": delivered,
        "delivered_per_sec": round(delivered / window, 1),
        "canceled": sum(m.canceled for m in muxes),
    }
    if n_rep > 1:
        agg = round(delivered / window, 1)
        if calib_rate is not None:
            out["scaling"] = {
                "effective_cpus": cpus,
                "single_replica_delivered_per_sec": calib_rate,
                "aggregate_delivered_per_sec": agg,
                "speedup": round(agg / max(1e-9, calib_rate), 2),
                # Linear-ish: the fleet must beat one replica by 1.5x
                # before we call the replicas a scaling story.
                "gate_linear_scaling": agg >= 1.5 * calib_rate,
            }
        else:
            out["scaling"] = {
                "effective_cpus": cpus,
                "mode": (
                    "correctness-only: <2 effective cpus, the replicas "
                    "timeshare one core so no linearity is claimed"
                ),
            }
    if killed_at is not None:
        out["kill_one"] = {
            "killed_after_writes": killed_at,
            "no_event_loss": delivered >= args.writes,
            "warm_restart": warm_restart,
        }
    return out


def main(argv=None):
    args = parse_args(argv)
    print(json.dumps(asyncio.run(amain(args))))


if __name__ == "__main__":
    main()
