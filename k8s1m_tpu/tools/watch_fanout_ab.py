"""Watch-amplification A/B — and the watchplane storm drill — through
the watch-cache tier.

A/B mode reproduces the shape of the reference's apiserver findings
(reference README.adoc:410-416, 495-499): every node holds several
watches on its own objects (18 per kubelet+kube-proxy in the reference;
``--watchers-per-node`` here), all served by the fan-out tier from ONE
store watch — the store sees the write load, never the watch load.  The
``--index both`` mode runs the experiment under the hash and btree cache
storages, the reference's ``BtreeWatchCache`` ceiling axis.

    python -m k8s1m_tpu.tools.watch_fanout_ab --nodes 50 --writes 20000

Prints one BENCH-style JSON line per index mode:
``store_events_per_sec`` (events entering the tier) vs
``delivered_per_sec`` (events fanned out to client watches), plus the
store-side watcher count proving the amplification never reaches it.

STORM mode (``--watchers`` / ``--fault-plan`` / ``--smoke``) is the
ISSUE 15 kill drill: six figures of multiplexed client watches on the
18-per-node profile (3 hot + 15 idle), a seq-stamped lease-flood write
load, and a composed fault plan (``--fault-plan watchstorm``: upstream
stream breaks + pump-lane stalls + subscriber wedges) — gated on

- **zero event loss by ledger**: every hot watch ends at its key's
  final written seq, monotonically (coalescing may elide, never
  reorder or lose net state; a canceled watch must recover it by
  relist);
- **resume rate**: >= 90% of injected upstream breaks resolved by
  diff-replay resume (``watchcache_resumes_total``), not a
  cancel-everyone relist storm (``watchcache_invalidations_total``);
- **bounded delivery lag**: p99 write->delivery under ``--p99-budget``
  across the composed churn + flood window;
- **bounded memory** (``--smoke``): peak RSS under ``--rss-budget-mb``.

    python -m k8s1m_tpu.tools.watch_fanout_ab --watchers 100000 \\
        --fault-plan watchstorm --out artifacts/watchstorm_cpu.json
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import resource
import time

from k8s1m_tpu.store.etcd_client import EtcdClient
from k8s1m_tpu.store.etcd_server import serve
from k8s1m_tpu.store.native import MemStore
from k8s1m_tpu.store.watch_cache import serve_watch_cache
from k8s1m_tpu.control.objects import lease_key
from k8s1m_tpu.tools.lease_flood import LEASE_NS, lease_value

_STREAMS_PER_CHANNEL = 80   # under the server's max_concurrent_streams=100


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="watch fan-out A/B + storm drill")
    ap.add_argument("--nodes", type=int, default=50)
    ap.add_argument("--watchers-per-node", type=int, default=3,
                    help="HOT client watches per node object (lease "
                         "updates fan out to these)")
    ap.add_argument("--idle-watches-per-node", type=int, default=0,
                    help="additional idle watches per node on objects "
                         "that never change (configmaps/secrets in the "
                         "reference's 18-watches-per-kubelet profile, "
                         "README.adoc:410-416) — they must cost the "
                         "store nothing and deliver nothing")
    ap.add_argument("--writes", type=int, default=10000)
    ap.add_argument("--batch", type=int, default=500,
                    help="producer batch size (BatchKV wave)")
    ap.add_argument("--index", choices=("hash", "btree", "both"),
                    default="both")
    ap.add_argument("--quiet", action="store_true")
    # ---- storm-drill mode ----
    ap.add_argument("--watchers", type=int, default=0,
                    help="STORM mode: total client watches on the "
                         "18-per-node profile (watchers-per-node hot + "
                         "15 idle per node)")
    ap.add_argument("--fault-plan", default=None,
                    help="faultline plan for the storm window: a named "
                         "plan ('watchstorm'), inline JSON, or @path")
    ap.add_argument("--streams", type=int, default=16,
                    help="storm mode: bidi streams the watches "
                         "multiplex over")
    ap.add_argument("--flood-factor", type=int, default=4,
                    help="storm mode: lease-flood burst multiplier for "
                         "the middle third of the write window")
    ap.add_argument("--rate", type=int, default=1000,
                    help="storm mode: steady offered write rate "
                         "(writes/s), sized to the 1-core in-process "
                         "lane's sustainable fan-out; the flood third "
                         "runs unpaced at flood-factor x the batch size")
    ap.add_argument("--lag-budget", type=int, default=32,
                    help="storm mode: the tier's per-subscriber FIFO "
                         "budget (tight by default so the flood third "
                         "actually exercises latest-only coalescing)")
    ap.add_argument("--p99-budget", type=float, default=5.0,
                    help="storm gate: write->delivery p99 seconds")
    ap.add_argument("--rss-budget-mb", type=float, default=0.0,
                    help="storm gate: peak process RSS (0 = report "
                         "only; --smoke sets a budget)")
    ap.add_argument("--replica-drill", action="store_true",
                    help="storm mode: run a watch-cache REPLICA as a "
                         "subprocess serving a slice of the hot keys, "
                         "SIGKILL it mid-storm, and relaunch it with "
                         "--resume-floor — its watches must resume "
                         "from revision (warm restart), not relist")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 storm shape: 10k watchers, same gates "
                         "plus the RSS budget and the replica "
                         "warm-restart drill")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.smoke:
        args.watchers = args.watchers or 10_000
        args.writes = 8_000 if args.writes == 10000 else args.writes
        args.fault_plan = args.fault_plan or "watchstorm"
        args.replica_drill = True
        if not args.rss_budget_mb:
            args.rss_budget_mb = 1500.0
    return args


async def run_one(index: str, args, store: MemStore, store_port: int) -> dict:
    lease_prefix = lease_key(LEASE_NS, "x")[:-1]    # .../kube-node-lease/
    cm_prefix = b"/registry/configmaps/kube-system/"
    prefixes = [lease_prefix]
    producer = EtcdClient(f"127.0.0.1:{store_port}")
    if args.idle_watches_per_node:
        # The idle population watches per-node config objects that are
        # written once and never again (the configmap/secret share of the
        # reference's 18-watches-per-kubelet profile).
        prefixes.append(cm_prefix)
        await producer.put_batch([
            (cm_prefix + f"node-cfg-{i}-{j}".encode(), b'{"data":{}}')
            for i in range(args.nodes)
            for j in range(args.idle_watches_per_node)
        ])
    tier = await serve_watch_cache(
        f"127.0.0.1:{store_port}", prefixes, port=0, index=index,
    )
    cache, cache_port = tier.cache, tier.port
    n_hot = args.nodes * args.watchers_per_node
    n_idle = args.nodes * args.idle_watches_per_node
    n_sessions = n_hot + n_idle
    n_channels = (n_sessions + _STREAMS_PER_CHANNEL - 1) // _STREAMS_PER_CHANNEL
    clients = [
        EtcdClient(f"127.0.0.1:{cache_port}",
                   options=[("grpc.use_local_subchannel_pool", 1)])
        for _ in range(max(1, n_channels))
    ]
    sessions = []
    idle_sessions = []
    for i in range(n_hot):
        node = f"kwok-node-{i % args.nodes}"
        s = clients[i % len(clients)].watch(lease_key(LEASE_NS, node))
        await s.__aenter__()
        sessions.append(s)
    for i in range(n_idle):
        key = cm_prefix + (
            f"node-cfg-{i % args.nodes}-{i // args.nodes}".encode()
        )
        s = clients[(n_hot + i) % len(clients)].watch(key)
        await s.__aenter__()
        idle_sessions.append(s)

    expected = args.writes * args.watchers_per_node
    delivered = 0
    stream_errors = 0
    done = asyncio.Event()

    async def drain(s):
        nonlocal delivered, stream_errors
        while not done.is_set():
            try:
                batch = await s.next(timeout=15)
            except asyncio.TimeoutError:
                return
            # Counted, not logged: stream_errors is the report's signal.
            except Exception:  # graftlint: disable=broad-except
                # A broken stream must surface as an error, not masquerade
                # as a fan-out throughput ceiling.
                stream_errors += 1
                return
            delivered += len(batch.events)
            if delivered >= expected:
                done.set()

    drainers = [asyncio.create_task(drain(s)) for s in sessions]

    idle_delivered = 0

    async def idle_drain(s):
        nonlocal idle_delivered, stream_errors
        while not done.is_set():
            try:
                batch = await s.next(timeout=15)
            except asyncio.TimeoutError:
                continue    # expected quiet — keep listening to the end
            # Counted, not logged: stream_errors is the report's signal.
            except Exception:  # graftlint: disable=broad-except
                # A broken idle stream must not masquerade as "idle
                # watches deliver nothing" — that's the claim under test.
                stream_errors += 1
                return
            idle_delivered += len(batch.events)

    drainers += [asyncio.create_task(idle_drain(s)) for s in idle_sessions]

    t0 = time.perf_counter()
    i = 0
    while i < args.writes:
        n = min(args.batch, args.writes - i)
        items = []
        for j in range(i, i + n):
            node = f"kwok-node-{j % args.nodes}"
            items.append(
                (lease_key(LEASE_NS, node), lease_value(node, j // args.nodes))
            )
        await producer.put_batch(items)
        i += n
    write_s = time.perf_counter() - t0
    try:
        await asyncio.wait_for(done.wait(), timeout=60)
    except asyncio.TimeoutError:
        pass
    total_s = time.perf_counter() - t0

    store_watchers = store.stats()["watchers"]
    st = cache.stats()
    for t in drainers:
        t.cancel()
    for s in sessions + idle_sessions:
        await s.cancel()
    for c in clients:
        await c.close()
    await producer.close()
    await tier.close()

    return {
        "index": index,
        "nodes": args.nodes,
        "client_watches": n_sessions,
        "idle_watches": n_idle,
        "store_watches": store_watchers,     # 1 per prefix: fan-out proof
        "writes": args.writes,
        "writes_per_sec": round(args.writes / write_s, 1),
        "store_events_per_sec": round(st["events_in"] / total_s, 1),
        "delivered": delivered,
        "idle_delivered": idle_delivered,    # must be 0: idle watches are free
        "delivered_per_sec": round(delivered / total_s, 1),
        "amplification": round(delivered / max(1, st["events_in"]), 2),
        "stream_errors": stream_errors,
    }


async def amain(args) -> list[dict]:
    store = MemStore()
    server, store_port = await serve(store, port=0)
    out = []
    try:
        modes = ("hash", "btree") if args.index == "both" else (args.index,)
        for index in modes:
            out.append(await run_one(index, args, store, store_port))
    finally:
        await server.stop(None)
        store.close()
    return out


# ---------------------------------------------------------------------------
# Storm mode (ISSUE 15 watchplane): the kill drill.

_IDLE_PER_NODE = 15          # reference profile: 3 hot + 15 idle = 18
_SEQ_W = 12                  # zero-padded seq prefix of every hot value
_PAD = b'|{"kind":"Lease","spec":{"renew":"' + b"x" * 140 + b'"}}'
_LAG_SAMPLE_CAP = 500_000
STORM_IDLE_PREFIX = b"/registry/configmaps/storm/"


def _rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1]) / 1024.0
    return 0.0


class _StormLedger:
    """The drill's exactly-once accounting: per-key final written seq,
    per-write stamp times, per-watch last delivered seq.  Coalescing
    may ELIDE intermediate seqs (latest-only is the contract) but may
    never regress one or miss the final state at quiesce."""

    def __init__(self, nkeys: int):
        self.final_seq = [0] * nkeys
        self.write_t: dict[tuple[int, int], float] = {}
        self.last_seq: dict[int, int] = {}    # wid -> newest seq seen
        self.key_of: dict[int, int] = {}      # hot wid -> key index
        # Watches excluded from the p99 population but NOT from the
        # loss/regression axes: the replica drill's watches sit behind
        # a deliberate mid-storm SIGKILL outage, and their catch-up lag
        # measures the restart window, not the fan-out path the p99
        # gate exists to bound.
        self.lag_exempt: set[int] = set()
        self.lags: list[float] = []
        self.regressions = 0
        self.idle_delivered = 0
        self.relisted = 0

    def on_event(self, wid: int, value: bytes, now: float) -> None:
        ki = self.key_of.get(wid)
        if ki is None:
            self.idle_delivered += 1
            return
        seq = int(value[:_SEQ_W])
        if seq < self.last_seq.get(wid, -1):
            self.regressions += 1
            return
        self.last_seq[wid] = seq
        if wid in self.lag_exempt:
            return
        t = self.write_t.get((ki, seq))
        if t is not None and len(self.lags) < _LAG_SAMPLE_CAP:
            self.lags.append(now - t)

    def lagging(self) -> int:
        n = 0
        for wid, ki in self.key_of.items():
            if self.last_seq.get(wid, 0) < self.final_seq[ki]:
                n += 1
        return n


class _StormMux:
    """One bidi Watch stream multiplexing many drill watches (the
    kube-apiserver-to-etcd shape; the only honest way to hold 100K
    watches from one core), feeding the ledger from its reader.

    The stream is read RAW (bytes deserializer): the reader decodes the
    wiretier shared-frame tail itself, fans one frame's events to every
    watch id riding it (index selection, never a re-parse per watch),
    and keeps the drill's wire accounting — actual bytes received vs
    what the unshared encoding would have cost for the same deliveries.
    """

    def __init__(self, channel, ledger: _StormLedger, cancels: asyncio.Queue):
        from k8s1m_tpu.store.proto import rpc_pb2

        self._pb = rpc_pb2
        self._call = channel.stream_stream(
            "/etcdserverpb.Watch/Watch",
            request_serializer=rpc_pb2.WatchRequest.SerializeToString,
            response_deserializer=lambda b: b,
        )()
        self.ledger = ledger
        self.cancels = cancels
        self.created = 0
        self.delivered = 0
        self.canceled = 0
        self.frames = 0
        self.shared_frames = 0
        self.bytes_on_wire = 0
        self.unshared_bytes = 0      # core bytes x watch ids sharing them
        self.create_rev = 0          # newest header revision on a create ack
        self.watch_rev: dict[int, int] = {}   # wid -> last delivered mod_rev
        self._reader = asyncio.create_task(self._read())

    async def create(self, pairs, start_revision: int = 0,
                     start_revisions: dict | None = None) -> None:
        """pairs: (wid, key) tuples to register on this stream.
        ``start_revisions`` overrides per wid (warm-restart reattach)."""
        pb = self._pb
        for wid, key in pairs:
            sr = start_revision
            if start_revisions is not None:
                sr = start_revisions.get(wid, start_revision)
            await self._call.write(
                pb.WatchRequest(
                    create_request=pb.WatchCreateRequest(
                        key=key, watch_id=wid,
                        start_revision=sr,
                    )
                )
            )

    async def wait_created(self, n: int, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while self.created < n:
            if time.monotonic() > deadline:
                raise TimeoutError(f"only {self.created}/{n} watches created")
            await asyncio.sleep(0.05)

    async def _read(self) -> None:
        import grpc

        from k8s1m_tpu.store.native import decode_shared_tail

        led = self.ledger
        pb = self._pb
        try:
            async for raw in self._call:
                extra, _from_rev, core_len = decode_shared_tail(raw)
                resp = pb.WatchResponse.FromString(raw)
                # canceled BEFORE created: a compact-cancel arrives as
                # ONE response with created=True AND canceled=True —
                # counting it as a successful create would leave the
                # watch silently dead (found by review).
                if resp.canceled:
                    self.canceled += 1
                    # Tier-initiated cancel (overflow / wedge break /
                    # invalidate / compact): the client's relist
                    # contract — hand the wid to the recreator.
                    await self.cancels.put((self, resp.watch_id))
                    continue
                if resp.created:
                    self.created += 1
                    if resp.header.revision > self.create_rev:
                        self.create_rev = resp.header.revision
                    continue
                if resp.events:
                    now = time.perf_counter()
                    wids = (resp.watch_id, *extra)
                    self.frames += 1
                    self.bytes_on_wire += len(raw)
                    # What len(wids) separate WatchResponses for the
                    # same events would have cost (each is the frame's
                    # core — header + watch_id + event chunks — minus
                    # the few extension varints the sharing adds).
                    self.unshared_bytes += core_len * len(wids)
                    if extra:
                        self.shared_frames += 1
                    self.delivered += len(resp.events) * len(wids)
                    last = resp.events[-1].kv.mod_revision
                    for wid in wids:
                        for ev in resp.events:
                            led.on_event(wid, ev.kv.value, now)
                        if last > self.watch_rev.get(wid, 0):
                            self.watch_rev[wid] = last
        except (asyncio.CancelledError, grpc.RpcError):
            pass

    async def close(self) -> None:
        self._reader.cancel()
        try:
            await self._reader
        # Close-path cancel: the reader is being torn down either way.
        except (asyncio.CancelledError, Exception):  # graftlint: disable=broad-except
            pass


class _ReplicaDrill:
    """The storm's fleet lane: a REAL watch-cache replica subprocess
    serving a slice of the hot keys, SIGKILLed mid-storm and relaunched
    with ``--resume-floor`` — the warm-restart contract under test is
    that its watch population resumes from revision (the relaunched
    replica catches its history window up from the floor and clients
    re-attach with per-watch start_revision) instead of relisting."""

    def __init__(self, upstream: str, lag_budget: int):
        self.upstream = upstream
        self.lag_budget = lag_budget
        self.proc = None
        self.port = 0
        self.metrics_port = 0
        self.chan = None
        self.mux: _StormMux | None = None
        self.keys: list = []        # (wid, key) pairs this replica serves
        self.report: dict = {}

    async def launch(self, resume_floor: int = 0) -> None:
        import socket
        import subprocess
        import sys

        from k8s1m_tpu.cluster.harness import _free_port

        self.port = _free_port()
        self.metrics_port = _free_port()
        cmd = [
            sys.executable, "-m", "k8s1m_tpu.store.watch_cache",
            "--upstream", self.upstream,
            "--host", "127.0.0.1", "--port", str(self.port),
            "--prefix", lease_key(LEASE_NS, "x")[:-1].decode(),
            "--lag-budget", str(self.lag_budget),
            "--metrics-port", str(self.metrics_port),
        ]
        if resume_floor:
            cmd += ["--resume-floor", str(resume_floor)]
        self.proc = subprocess.Popen(
            cmd, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        deadline = time.monotonic() + 180
        while True:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"replica exited rc={self.proc.returncode}"
                )
            try:
                with socket.create_connection(
                    ("127.0.0.1", self.port), timeout=0.2
                ):
                    return
            except OSError:
                if time.monotonic() > deadline:
                    raise TimeoutError("replica did not bind")
                # Deadline-bounded readiness poll, not an op retry.
                await asyncio.sleep(0.05)  # graftlint: disable=retry-through-policy

    async def attach(self, ledger, cancels, pairs,
                     start_revisions: dict | None = None) -> None:
        from grpc import aio

        self.chan = aio.insecure_channel(
            f"127.0.0.1:{self.port}",
            options=[("grpc.max_receive_message_length", 64 << 20),
                     ("grpc.use_local_subchannel_pool", 1)],
        )
        self.mux = _StormMux(self.chan, ledger, cancels)
        await self.mux.create(pairs, start_revisions=start_revisions)
        await self.mux.wait_created(len(pairs), timeout=180)

    async def kill_and_restart(self, ledger, cancels) -> None:
        t0 = time.perf_counter()
        self.proc.kill()            # SIGKILL: no goodbye, no flush
        await asyncio.to_thread(self.proc.wait)
        old = self.mux
        await old.close()
        await self.chan.close()
        # The floor is the weakest watch's proven position: everything
        # after it is owed to SOMEONE, so the relaunched replica must
        # rebuild history from there.  Per-watch re-attach points stay
        # individual (a stream-level max would skip events for the
        # laggards).
        resume_at = {
            wid: max(old.watch_rev.get(wid, 0), old.create_rev)
            for wid, _ in self.keys
        }
        floor = min(resume_at.values())
        await self.launch(resume_floor=floor)
        await self.attach(
            ledger, cancels, self.keys,
            start_revisions={w: r + 1 for w, r in resume_at.items()},
        )
        self.report = {
            "resume_floor": floor,
            "restart_seconds": round(time.perf_counter() - t0, 2),
        }

    async def scrape(self) -> dict:
        """The relaunched replica's own /metrics, summed per counter."""
        import urllib.request

        def _get():
            with urllib.request.urlopen(
                f"http://127.0.0.1:{self.metrics_port}/metrics", timeout=10
            ) as r:
                return r.read().decode()

        out: dict = {}
        for line in (await asyncio.to_thread(_get)).splitlines():
            if line.startswith("#") or not line.strip():
                continue
            name, _, val = line.rpartition(" ")
            base = name.split("{", 1)[0]
            try:
                out[base] = out.get(base, 0.0) + float(val)
            except ValueError:
                continue
        return out

    async def close(self) -> None:
        import subprocess

        if self.mux is not None:
            await self.mux.close()
        if self.chan is not None:
            await self.chan.close()
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                await asyncio.to_thread(self.proc.wait, 10)
            except subprocess.TimeoutExpired:
                self.proc.kill()


async def run_storm(args) -> dict:
    """The watchplane kill drill: 18-per-node watch profile at
    ``--watchers`` total, seq-ledgered lease flood, composed fault plan,
    gates on loss / resume rate / delivery p99 / RSS."""
    from k8s1m_tpu import faultline
    from k8s1m_tpu.faultline import FaultPlan, install_plan
    from k8s1m_tpu.obs.metrics import REGISTRY
    from k8s1m_tpu.store.native import WireFront
    from grpc import aio

    per_node = args.watchers_per_node + _IDLE_PER_NODE
    nodes = max(1, args.watchers // per_node)
    nkeys = nodes
    n_hot = nodes * args.watchers_per_node
    n_idle = nodes * _IDLE_PER_NODE
    total_watches = n_hot + n_idle

    resumes = REGISTRY.get("watchcache_resumes_total")
    invals = REGISTRY.get("watchcache_invalidations_total")
    coalesced = REGISTRY.get("watchcache_coalesced_events_total")
    r0, i0, c0 = resumes.value(), invals.value(), coalesced.value()

    if args.fault_plan:
        install_plan(FaultPlan.from_arg(args.fault_plan))

    store = MemStore()
    # Native wire server: keeps the store off this event loop (the
    # tier, the writers and the mux readers all share it already).
    wf = WireFront(store)
    seed = EtcdClient(f"127.0.0.1:{wf.port}")
    ledger = _StormLedger(nkeys)
    hot_keys = [lease_key(LEASE_NS, f"storm-{i:06d}") for i in range(nkeys)]
    tier = None
    muxes: list[_StormMux] = []
    channels = []
    relist_client = None
    recreator = None
    replica = (
        _ReplicaDrill(f"127.0.0.1:{wf.port}", args.lag_budget)
        if args.replica_drill else None
    )
    rep_restart = None
    rep_scrape: dict = {}
    try:
        wave = []
        for i in range(n_idle):
            wave.append((STORM_IDLE_PREFIX + b"cm-%07d" % i, b'{"data":{}}'))
            if len(wave) >= 8192:
                await seed.put_batch(wave)
                wave.clear()
        for ki in range(nkeys):
            wave.append((hot_keys[ki], b"%0*d" % (_SEQ_W, 0) + _PAD))
        if wave:
            await seed.put_batch(wave)

        t_prime = time.perf_counter()
        tier = await serve_watch_cache(
            f"127.0.0.1:{wf.port}", [STORM_IDLE_PREFIX,
                                     lease_key(LEASE_NS, "x")[:-1]],
            port=0, index="hash", lag_budget=args.lag_budget,
        )
        prime_s = time.perf_counter() - t_prime
        cancels: asyncio.Queue = asyncio.Queue()
        channels = [
            aio.insecure_channel(
                f"127.0.0.1:{tier.port}",
                options=[("grpc.max_receive_message_length", 64 << 20),
                         ("grpc.use_local_subchannel_pool", 1)],
            )
            for _ in range(max(1, args.streams // 8))
        ]
        muxes = [
            _StormMux(channels[i % len(channels)], ledger, cancels)
            for i in range(args.streams)
        ]
        relist_client = EtcdClient(
            f"127.0.0.1:{tier.port}",
            options=[("grpc.use_local_subchannel_pool", 1)],
        )

        async def recreate_canceled():
            """The client half of the relist contract: a canceled watch
            reads its key through the tier (progress-gated, so the read
            reflects every write the cancel postdates) and re-attaches
            from the read revision."""
            import grpc as _grpc

            while True:
                mux, wid = await cancels.get()
                ki = ledger.key_of.get(wid)
                if ki is None:
                    continue        # idle watch: count only (no loss axis)
                resp = await relist_client.range(hot_keys[ki])
                if resp.kvs:
                    seq = int(resp.kvs[0].value[:_SEQ_W])
                    if seq > ledger.last_seq.get(wid, 0):
                        ledger.last_seq[wid] = seq
                ledger.relisted += 1
                try:
                    await mux.create(
                        [(wid, hot_keys[ki])],
                        start_revision=resp.header.revision + 1,
                    )
                except _grpc.RpcError:
                    # A cancel racing the replica drill's SIGKILL: the
                    # stream died under us.  The warm-restart path
                    # re-attaches the replica's whole population from
                    # per-watch revisions — nothing to do here.
                    continue

        recreator = asyncio.create_task(recreate_canceled())

        # ---- create the watch population (idle first, then hot) ----
        t0 = time.perf_counter()
        next_wid = 1
        per_mux = (n_idle + len(muxes) - 1) // len(muxes)
        expect = [0] * len(muxes)
        for mi, m in enumerate(muxes):
            lo = mi * per_mux
            pairs = [
                (next_wid + j, STORM_IDLE_PREFIX + b"cm-%07d" % (lo + j))
                for j in range(min(per_mux, max(0, n_idle - lo)))
            ]
            next_wid += len(pairs)
            expect[mi] += len(pairs)
            await m.create(pairs)
        hot_pairs: list[list] = [[] for _ in muxes]
        for wi in range(n_hot):
            ki = wi % nkeys
            # Place a key's hot watchers on the SAME stream (keyed, not
            # round-robin by watcher): the kube shape — one apiserver
            # multiplexes all watches for an object over one etcd
            # stream — and the layout under which the tier's shared
            # frames actually share (a frame can only carry the watch
            # ids of one stream).
            mi = ki % len(muxes)
            wid = next_wid
            next_wid += 1
            ledger.key_of[wid] = ki
            hot_pairs[mi].append((wid, hot_keys[ki]))
        for mi, pairs in enumerate(hot_pairs):
            expect[mi] += len(pairs)
            await muxes[mi].create(pairs)
        for m, n in zip(muxes, expect):
            await m.wait_created(n, timeout=240 + total_watches / 500)
        create_s = time.perf_counter() - t0
        rss_after_create = _rss_mb()

        # ---- the replica fleet lane: a watch-cache replica subprocess
        # serves the TOP slice of the hot key range (disjoint from the
        # flood subset, keys [0, nkeys/8)), gets SIGKILLed as the flood
        # opens, and must come back warm.  Its watches ride the same
        # ledger (zero-loss and monotonicity axes) but are lag-exempt:
        # their catch-up lag measures the deliberate outage window.
        if replica is not None:
            await replica.launch()
            n_rep_keys = min(256, max(1, nkeys // 4))
            pairs = []
            for i in range(n_rep_keys):
                rki = nkeys - 1 - i
                wid = next_wid
                next_wid += 1
                ledger.key_of[wid] = rki
                ledger.lag_exempt.add(wid)
                pairs.append((wid, hot_keys[rki]))
            replica.keys = pairs
            await replica.attach(ledger, cancels, pairs)

        # ---- the storm window: steady -> flood -> steady writes.
        # Steady thirds pace at --rate over ALL keys (the kubelet-
        # renewal shape); the flood third bursts unpaced at
        # flood-factor x the batch onto a 1/8 key subset — a true
        # thundering herd, so the floodiest watchers' queues actually
        # cross the lag budget and degrade to latest-only while the
        # rest of the population stays on FIFO delivery.
        t0 = time.perf_counter()
        total = args.writes
        written = 0
        ki = 0
        base = max(64, min(1000, args.rate // 8))
        # The flood third must actually FLOOD: bound the hot subset so
        # each unpaced burst lands ~2x the tier's lag budget on every
        # flooded key, forcing the latest-only coalescing the wire and
        # p99 gates are about — not a polite elevated drizzle the pumps
        # absorb without ever degrading anyone.
        flood_keys = max(1, min(
            nkeys // 8,
            base * args.flood_factor // max(1, args.lag_budget * 2),
        ))
        while written < total:
            in_flood = total // 3 <= written < 2 * (total // 3)
            if replica is not None and rep_restart is None and in_flood:
                # SIGKILL the replica exactly as the flood opens — the
                # worst moment — and warm-restart it while the storm
                # keeps writing.
                rep_restart = asyncio.create_task(
                    replica.kill_and_restart(ledger, cancels)
                )
            n = min(base * (args.flood_factor if in_flood else 1),
                    total - written)
            t = time.perf_counter()
            items = []
            span = flood_keys if in_flood else nkeys
            for j in range(n):
                k = (ki + j) % span
                s = ledger.final_seq[k] + 1
                ledger.final_seq[k] = s
                ledger.write_t[(k, s)] = t
                items.append((hot_keys[k], b"%0*d" % (_SEQ_W, s) + _PAD))
            ki = (ki + n) % span
            await seed.put_batch(items)
            written += n
            if not in_flood:
                # Pace to the steady rate, net of time already spent.
                pause = n / args.rate - (time.perf_counter() - t)
                if pause > 0:
                    await asyncio.sleep(pause)
        write_s = time.perf_counter() - t0
        if rep_restart is not None:
            await asyncio.wait_for(rep_restart, timeout=300)

        rss_after_writes = _rss_mb()
        # ---- quiesce: every hot watch must reach its key's final seq
        deadline = time.monotonic() + 180
        lagging = ledger.lagging()
        while lagging and time.monotonic() < deadline:
            await asyncio.sleep(0.25)
            lagging = ledger.lagging()
        window_s = time.perf_counter() - t0
        store_watchers = store.stats()["watchers"]
        tier_stats = tier.cache.stats()
        rss_quiesce = _rss_mb()
        if replica is not None:
            rep_scrape = await replica.scrape()
    finally:
        if recreator is not None:
            recreator.cancel()
        if replica is not None:
            await replica.close()
        for m in muxes:
            await m.close()
        for ch in channels:
            await ch.close()
        if relist_client is not None:
            await relist_client.close()
        if tier is not None:
            await tier.close()
        await seed.close()
        fired = faultline.active_injector().fire_report()
        install_plan(None)
        wf.close()
        store.close()

    breaks = sum(
        f["fires"] for f in fired
        if f["op"] == "upstream.recv"
        and f["kind"] not in ("delay", "slow_cycle")
    )
    d_resumes = resumes.value() - r0
    d_invals = invals.value() - i0
    d_coalesced = coalesced.value() - c0
    resume_rate = (
        d_resumes / max(1, d_resumes + d_invals) if breaks else None
    )
    lags = sorted(ledger.lags)
    p50 = lags[len(lags) // 2] if lags else None
    p99 = lags[min(len(lags) - 1, int(len(lags) * 0.99))] if lags else None
    peak_rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    delivered = sum(m.delivered for m in muxes)
    # ---- wire accounting (main fan-out muxes; the replica lane is a
    # separate outage drill).  measured_fanout is the drill's ACTUAL
    # per-event delivery degree (nominal 3 hot watchers per key, net of
    # latest-only elisions and cancel->relist gaps); the shared-frame
    # wire must recoup at least that factor for bytes_per_delivered_event
    # to have dropped by the fan-out degree vs the unshared encoding.
    frames = sum(m.frames for m in muxes)
    shared_frames = sum(m.shared_frames for m in muxes)
    bytes_on_wire = sum(m.bytes_on_wire for m in muxes)
    unshared_bytes = sum(m.unshared_bytes for m in muxes)
    measured_fanout = delivered / max(1, tier_stats["events_in"])
    wire_drop = unshared_bytes / max(1, bytes_on_wire)
    rep_resumes = rep_scrape.get("watchcache_resumes_total", 0.0)
    rep_invals = rep_scrape.get("watchcache_invalidations_total", 0.0)
    gates = {
        "zero_loss": lagging == 0,
        "no_regressions": ledger.regressions == 0,
        "idle_silent": ledger.idle_delivered == 0,
        "lag_measured": bool(lags),
        "p99_bounded": p99 is not None and p99 <= args.p99_budget,
        # The named storm must actually have stormed: upstream breaks
        # injected, and >= 90% of them resolved by resume, not relist.
        "stormed": args.fault_plan != "watchstorm" or breaks > 0,
        "breaks_resolved": breaks == 0 or (d_resumes + d_invals) > 0,
        "resume_rate": resume_rate is None or resume_rate >= 0.9,
        # Gate on the steady resident footprint at quiesce — the
        # tier's actual cost at this watch population.  The ru_maxrss
        # peak is reported alongside but not gated: under CI
        # contention transient allocator spikes (glibc arena growth
        # across grpc's thread pool) poison the peak with non-tier
        # memory while the steady footprint stays flat.
        "rss_bounded": (
            not args.rss_budget_mb or rss_quiesce <= args.rss_budget_mb
        ),
        # Shared frames must recoup at least the measured fan-out
        # degree in bytes: what N unshared responses would have cost
        # for the SAME deliveries, over what actually crossed the wire.
        "wire_compaction": frames > 0 and wire_drop >= measured_fanout,
        # The killed replica must come back WARM: its own counters show
        # resume-from-revision (diff replay against the rebuilt history
        # window), and zero invalidations — no relist storm.
        "replica_warm_restart": (
            not args.replica_drill
            or (rep_resumes >= 1 and rep_invals == 0)
        ),
    }
    passed = all(gates.values())
    return {
        "metric": "watch_fanout_storm" + ("_smoke" if args.smoke else ""),
        "value": total_watches,
        "unit": "client watches under composed storm",
        "vs_baseline": round(total_watches / 18_000_000, 5),
        "passed": passed,
        "shape": {
            "watchers": total_watches, "hot": n_hot, "idle": n_idle,
            "keys": nkeys, "writes": args.writes, "streams": args.streams,
            "flood_factor": args.flood_factor,
            "fault_plan": args.fault_plan,
        },
        "gates": gates,
        "evidence": {
            "store_watchers": store_watchers,
            "prime_seconds": round(prime_s, 2),
            "create_per_sec": round(total_watches / create_s, 1),
            "write_seconds": round(write_s, 2),
            "window_seconds": round(window_s, 2),
            "delivered": delivered,
            "delivered_per_sec": round(delivered / window_s, 1),
            "frames": frames,
            "frames_shared_ratio": round(shared_frames / max(1, frames), 4),
            "bytes_on_wire_total": bytes_on_wire,
            "bytes_per_delivered_event": round(
                bytes_on_wire / max(1, delivered), 1
            ),
            "unshared_bytes_per_event": round(
                unshared_bytes / max(1, delivered), 1
            ),
            "wire_compaction_drop": round(wire_drop, 3),
            "measured_fanout": round(measured_fanout, 3),
            "coalesced_events": int(d_coalesced),
            "tier_backlog_at_end": tier_stats["backlog"],
            "upstream_breaks": breaks,
            "resumes": int(d_resumes),
            "invalidations": int(d_invals),
            "resume_rate": resume_rate,
            "watches_canceled": sum(m.canceled for m in muxes),
            "watches_relisted": ledger.relisted,
            "lagging_at_quiesce": lagging,
            "seq_regressions": ledger.regressions,
            "idle_delivered": ledger.idle_delivered,
            "lag_p50_ms": round(p50 * 1000, 1) if p50 is not None else None,
            "lag_p99_ms": round(p99 * 1000, 1) if p99 is not None else None,
            "p99_budget_s": args.p99_budget,
            "rss_mb_after_create": round(rss_after_create, 1),
            "rss_mb_after_writes": round(rss_after_writes, 1),
            "rss_mb_at_quiesce": round(rss_quiesce, 1),
            "peak_rss_mb": round(peak_rss_mb, 1),
            "rss_budget_mb": args.rss_budget_mb or None,
            "replica_drill": (
                {
                    **(replica.report if replica is not None else {}),
                    "replica_watches": (
                        len(replica.keys) if replica is not None else 0
                    ),
                    "replica_delivered": (
                        replica.mux.delivered
                        if replica is not None and replica.mux is not None
                        else 0
                    ),
                    "resumes": int(rep_resumes),
                    "invalidations": int(rep_invals),
                }
                if args.replica_drill else None
            ),
            "faults": fired,
        },
    }


def main(argv=None):
    args = parse_args(argv)
    if args.watchers or args.fault_plan or args.smoke:
        result = asyncio.run(run_storm(args))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(result, f, indent=1)
        print(json.dumps(result))
        return
    for line in asyncio.run(amain(args)):
        print(json.dumps(line))


if __name__ == "__main__":
    main()
