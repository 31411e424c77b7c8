"""etcd v3 gRPC wire layer over the native memstore.

This is the serving surface of the mem_etcd equivalent: the four services
the reference registers (reference mem_etcd/src/main.rs:106-109 — KV,
Watch, Lease, Maintenance) speaking the public etcd wire protocol, backed
by the C++ store (native/memstore).  Service semantics mirror the
reference component-for-component:

- **Txn supports exactly the one shape Kubernetes emits** — a single
  compare on MOD revision or VERSION, a single success Put-or-DeleteRange
  on the same key, an optional failure Range of the same key; anything
  else is InvalidArgument (reference mem_etcd/src/kv_service.rs:126-337).
- **Watch**: create -> ``created:true`` response, then past-changes batch,
  then a live loop delivering events in revision order, batched up to
  1000 per response (reference watch_service.rs:119-146); CancelRequest
  and ProgressRequest are handled, with the progress revision computed as
  max(store progress revision, last delivered) to close the same race the
  reference closes (watch_service.rs:172-176); a compacted start revision
  yields a response with ``compact_revision`` set (watch_service.rs:63-75).
  Event frames are composed from a per-stream shared chunk table
  (store/wiretier.py): an event fanning to several watches on one
  stream is proto-encoded once, and the composed bytes are
  byte-identical to the constructor path they replaced.
- **Lease is deliberately fake**: LeaseGrant returns an incrementing id
  and TTLs never expire — Kubernetes only uses etcd leases for Event TTLs
  (reference lease_service.rs:33-137, README.adoc:266-311).
- **Maintenance.Status** reports version "3.5.16" (>=3.5.13 so Kubernetes
  enables watch-progress support) and db size (reference
  maintenance_service.rs:29-117); Alarm/Defragment are stubs;
  Hash/Snapshot/MoveLeader are unimplemented, as in the reference.

The server writes a dummy key ``~`` on a fresh store so revisions start
at 1 exactly like etcd (reference main.rs:103-104).
"""

from __future__ import annotations

import asyncio
import logging
import time
import weakref

import grpc
from grpc import aio

from k8s1m_tpu.obs.metrics import CallbackMetric, Counter, Gauge, Histogram
from k8s1m_tpu.store import wiretier
from k8s1m_tpu.store.native import (
    CompactedError,
    FutureRevError,
    KeyValue,
    MemStore,
    WatchEvent,
    Watcher,
)
from k8s1m_tpu.store.proto import batch_pb2, mvcc_pb2, rpc_pb2

log = logging.getLogger("k8s1m.etcd")

ERR_COMPACTED = "etcdserver: mvcc: required revision has been compacted"
ERR_FUTURE_REV = "etcdserver: mvcc: required revision is a future revision"

_WATCH_BATCH = 1000          # events per WatchResponse (reference recv_many cap)
_WATCH_POLL_S = 0.005        # live-loop poll interval when idle

_REQ_COUNT = Counter(
    "memstore_requests_total", "gRPC requests by method", ("method",)
)
_REQ_LATENCY = Histogram(
    "memstore_request_seconds", "gRPC request latency by method", ("method",)
)
_STORE_GAUGE = Gauge("memstore_store", "Store-level gauges by stat", ("stat",))
_WATCH_COMPACT_CANCELS = Counter(
    "memstore_watch_compact_cancels_total",
    "watch creations canceled because start_revision predates the "
    "compaction window (client must relist, reflector-on-410)", ()
)
# Stores served with metrics enabled; the gauge aggregates over the live
# ones so a closed store neither pins memory nor clobbers stats.
_SERVED_STORES: weakref.WeakSet = weakref.WeakSet()
for _stat in ("num_keys", "db_size", "current_revision", "compact_revision"):
    _STORE_GAUGE.set_function(
        (lambda stat: lambda: sum(getattr(s, stat) for s in _SERVED_STORES))(_stat),
        stat=_stat.replace("current_", ""),
    )


# One scrape renders five callback metrics; without a snapshot each would
# re-serialize the full native stats JSON (taking the store read lock and
# inflating its own M_STATS counters five-fold).  A short TTL shares one
# snapshot across the metrics of a scrape without ever serving stale data
# to a real scrape interval (seconds).
_STATS_TTL_S = 0.25
_stats_snapshots: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _stats_of(s) -> dict:
    now = time.monotonic()
    ent = _stats_snapshots.get(s)
    if ent is not None and now - ent[0] < _STATS_TTL_S:
        return ent[1]
    st = s.stats()
    _stats_snapshots[s] = (now, st)
    return st


def _lock_samples(field: str, scale: float = 1.0):
    """Aggregate the native store's (method, structure, rw) lock cells
    across served stores (reference mem_etcd_lock_seconds/lock_count,
    metrics.rs:78-94)."""
    agg: dict[tuple, float] = {}
    for s in list(_SERVED_STORES):
        for cell in _stats_of(s).get("locks", ()):
            key = (cell["method"], cell["structure"], cell["rw"])
            agg[key] = agg.get(key, 0.0) + cell[field] * scale
    return [
        ({"method": m, "structure": st, "rw": rw}, v)
        for (m, st, rw), v in sorted(agg.items())
    ]


def _watch_samples(stat: str, agg=sum):
    vals = [
        _stats_of(s)["watch_pressure"][stat] for s in list(_SERVED_STORES)
    ]
    return [({}, agg(vals))] if vals else []


CallbackMetric(
    "memstore_lock_count_total",
    "store lock acquisitions by (method, structure, rw)",
    lambda: _lock_samples("count"), kind="counter",
)
CallbackMetric(
    "memstore_lock_wait_seconds_total",
    "time spent waiting on contended store locks",
    lambda: _lock_samples("wait_ns", 1e-9), kind="counter",
)
CallbackMetric(
    "memstore_watch_enqueued_total",
    "events enqueued to watcher queues",
    lambda: _watch_samples("enqueued"), kind="counter",
)
CallbackMetric(
    "memstore_watch_enqueue_batches_total",
    "watcher queue acquisitions by writers (one a watcher a frame)",
    lambda: _watch_samples("enqueue_batches"), kind="counter",
)
CallbackMetric(
    "memstore_watch_dropped_total",
    "events dropped at watcher queue caps (consumer must resync)",
    lambda: _watch_samples("dropped"), kind="counter",
)
CallbackMetric(
    "memstore_watch_queue_hwm",
    "high-water watcher queue depth",
    lambda: _watch_samples("queue_hwm", agg=max), kind="gauge",
)


def _kv_to_pb(kv: KeyValue) -> mvcc_pb2.KeyValue:
    return mvcc_pb2.KeyValue(
        key=kv.key,
        value=kv.value,
        create_revision=kv.create_revision,
        mod_revision=kv.mod_revision,
        version=kv.version,
        lease=kv.lease,
    )


def _encode_watch_event(ev: WatchEvent) -> bytes:
    """One native watch event as WatchResponse.events chunk bytes —
    byte-identical to the events.add()/CopyFrom path it replaced
    (protobuf serializes known fields in tag order)."""
    pb = mvcc_pb2.Event(
        type=(
            mvcc_pb2.Event.DELETE if ev.type == "DELETE"
            else mvcc_pb2.Event.PUT
        ),
        kv=_kv_to_pb(ev.kv),
    )
    if ev.prev_kv is not None:
        pb.prev_kv.CopyFrom(_kv_to_pb(ev.prev_kv))
    return wiretier.event_chunk(pb.SerializeToString())


class EtcdService:
    """All four etcd services over one MemStore."""

    def __init__(self, store: MemStore):
        self.store = store
        self._lease_id = 0
        self._lease_lock = asyncio.Lock()
        self._leases: dict[int, int] = {}  # id -> granted TTL (never expires)
        if store.current_revision == 0:
            # Fresh store: revisions must start at 1 like etcd.
            store.put(b"~", b"0")

    # ---- helpers -------------------------------------------------------

    def _header(self, revision: int | None = None) -> rpc_pb2.ResponseHeader:
        return rpc_pb2.ResponseHeader(
            cluster_id=1,
            member_id=1,
            revision=self.store.current_revision if revision is None else revision,
            raft_term=1,
        )

    @staticmethod
    def _end_of(req_end: bytes) -> bytes | None:
        return req_end if req_end else None

    # ---- KV ------------------------------------------------------------

    async def Range(self, req: rpc_pb2.RangeRequest, ctx) -> rpc_pb2.RangeResponse:
        _REQ_COUNT.inc(method="Range")
        with _REQ_LATENCY.time(method="Range"):
            try:
                res = self.store.range(
                    req.key,
                    self._end_of(req.range_end),
                    revision=req.revision,
                    limit=req.limit,
                    count_only=req.count_only,
                    keys_only=req.keys_only,
                )
            except CompactedError:
                await ctx.abort(grpc.StatusCode.OUT_OF_RANGE, ERR_COMPACTED)
            except FutureRevError:
                await ctx.abort(grpc.StatusCode.OUT_OF_RANGE, ERR_FUTURE_REV)
            return rpc_pb2.RangeResponse(
                header=self._header(res.revision),
                kvs=[_kv_to_pb(kv) for kv in res.kvs],
                more=res.more,
                count=res.count,
            )

    async def Put(self, req: rpc_pb2.PutRequest, ctx) -> rpc_pb2.PutResponse:
        _REQ_COUNT.inc(method="Put")
        with _REQ_LATENCY.time(method="Put"):
            if req.ignore_value or req.ignore_lease:
                await ctx.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    "ignore_value/ignore_lease not supported",
                )
            prev = self.store.get(req.key) if req.prev_kv else None
            rev = self.store.put(req.key, req.value, lease=req.lease)
            resp = rpc_pb2.PutResponse(header=self._header(rev))
            if prev is not None:
                resp.prev_kv.CopyFrom(_kv_to_pb(prev))
            return resp

    async def DeleteRange(
        self, req: rpc_pb2.DeleteRangeRequest, ctx
    ) -> rpc_pb2.DeleteRangeResponse:
        _REQ_COUNT.inc(method="DeleteRange")
        with _REQ_LATENCY.time(method="DeleteRange"):
            # NB: a multi-key range delete takes one revision per key (the
            # native store's set API is single-key, like the reference's
            # store.set — reference store.rs:189-382).  etcd proper uses a
            # single revision; Kubernetes never issues multi-key deletes on
            # its hot paths, so this divergence is accepted.
            prev_kvs = []
            if req.range_end:
                victims = self.store.range(
                    req.key, req.range_end, keys_only=not req.prev_kv
                ).kvs
                keys = [kv.key for kv in victims]
                if req.prev_kv:
                    prev_kvs = victims
            else:
                keys = [req.key]
                if req.prev_kv:
                    kv = self.store.get(req.key)
                    prev_kvs = [kv] if kv else []
            deleted = 0
            rev = self.store.current_revision
            for key in keys:
                r, ok = self.store.delete(key)
                if ok:
                    deleted += 1
                    rev = r
            return rpc_pb2.DeleteRangeResponse(
                header=self._header(rev),
                deleted=deleted,
                prev_kvs=[_kv_to_pb(kv) for kv in prev_kvs],
            )

    async def Txn(self, req: rpc_pb2.TxnRequest, ctx) -> rpc_pb2.TxnResponse:
        """The single Kubernetes Txn shape (reference kv_service.rs:126-337)."""
        _REQ_COUNT.inc(method="Txn")
        with _REQ_LATENCY.time(method="Txn"):
            if len(req.compare) != 1 or len(req.success) != 1 or len(req.failure) > 1:
                await ctx.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    "unsupported txn shape: want 1 compare, 1 success op, <=1 failure op",
                )
            cmp = req.compare[0]
            if cmp.result != rpc_pb2.Compare.EQUAL:
                await ctx.abort(
                    grpc.StatusCode.INVALID_ARGUMENT, "only EQUAL compares supported"
                )
            key = cmp.key
            if cmp.target == rpc_pb2.Compare.MOD:
                required_mod, required_version = cmp.mod_revision, None
            elif cmp.target == rpc_pb2.Compare.VERSION:
                required_mod, required_version = None, cmp.version
            else:
                await ctx.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    "only MOD/VERSION compare targets supported",
                )

            op = req.success[0]
            which = op.WhichOneof("request")
            if which == "request_put":
                if op.request_put.key != key:
                    await ctx.abort(
                        grpc.StatusCode.INVALID_ARGUMENT,
                        "txn success op must target the compared key",
                    )
                value, lease = op.request_put.value, op.request_put.lease
            elif which == "request_delete_range":
                if op.request_delete_range.key != key or op.request_delete_range.range_end:
                    await ctx.abort(
                        grpc.StatusCode.INVALID_ARGUMENT,
                        "txn delete must be single-key on the compared key",
                    )
                value, lease = None, 0
            else:
                await ctx.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    "txn success op must be Put or DeleteRange",
                )
            if req.failure:
                fail_op = req.failure[0]
                if (
                    fail_op.WhichOneof("request") != "request_range"
                    or fail_op.request_range.key != key
                ):
                    await ctx.abort(
                        grpc.StatusCode.INVALID_ARGUMENT,
                        "txn failure op must be a Range of the compared key",
                    )

            ok, rev, cur = self.store.cas(
                key,
                value,
                required_mod=required_mod,
                required_version=required_version,
                lease=lease,
            )
            resp = rpc_pb2.TxnResponse(header=self._header(rev if ok else None))
            resp.succeeded = ok
            if ok:
                rop = resp.responses.add()
                if which == "request_put":
                    rop.response_put.header.CopyFrom(self._header(rev))
                else:
                    rop.response_delete_range.header.CopyFrom(self._header(rev))
                    rop.response_delete_range.deleted = 1
            elif req.failure:
                rop = resp.responses.add()
                rop.response_range.header.CopyFrom(self._header())
                if cur is not None:
                    rop.response_range.kvs.append(_kv_to_pb(cur))
                    rop.response_range.count = 1
            return resp

    async def Compact(
        self, req: rpc_pb2.CompactionRequest, ctx
    ) -> rpc_pb2.CompactionResponse:
        _REQ_COUNT.inc(method="Compact")
        try:
            self.store.compact(req.revision)
        except CompactedError:
            await ctx.abort(grpc.StatusCode.OUT_OF_RANGE, ERR_COMPACTED)
        except FutureRevError:
            await ctx.abort(grpc.StatusCode.OUT_OF_RANGE, ERR_FUTURE_REV)
        return rpc_pb2.CompactionResponse(header=self._header())

    # ---- BatchKV (private pipelined-write extension, proto/batch.proto)

    async def PutFrame(
        self, req: batch_pb2.PutFrameRequest, ctx
    ) -> batch_pb2.PutFrameResponse:
        """A whole write wave as one native-format frame -> one FFI call.

        The asyncio interpreter cost (~300us/RPC) amortizes over the wave
        instead of repeating per put — the wire-side equivalent of the
        reference's per-core tonic workers (reference README.adoc:343-353).
        """
        _REQ_COUNT.inc(method="PutFrame")
        with _REQ_LATENCY.time(method="PutFrame"):
            # A record is >=8 bytes, so count must fit the frame; this
            # also keeps the client-controlled uint32 inside the FFI's
            # c_int before ctypes ever sees it.
            if req.count > len(req.frame) // 8:
                await ctx.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    "count exceeds frame capacity",
                )
            rev = self.store.put_frame(req.frame, req.count, req.lease)
            if rev < 0:
                await ctx.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    f"malformed put frame (rc={rev})",
                )
            return batch_pb2.PutFrameResponse(revision=rev)

    async def BindFrame(
        self, req: batch_pb2.BindFrameRequest, ctx
    ) -> batch_pb2.BindFrameResponse:
        _REQ_COUNT.inc(method="BindFrame")
        with _REQ_LATENCY.time(method="BindFrame"):
            # A bind record is >=16 bytes (see PutFrame's count check).
            if req.count > len(req.frame) // 16:
                await ctx.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    "count exceeds frame capacity",
                )
            bound, revisions = self.store.bind_frame(req.frame, req.count)
            if bound < 0:
                await ctx.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    f"malformed bind frame (rc={bound})",
                )
            return batch_pb2.BindFrameResponse(revisions=revisions, bound=bound)

    # ---- Watch ---------------------------------------------------------

    async def Watch(self, request_iterator, ctx):
        """Bidi watch stream: multiplexes many watches over one stream."""
        _REQ_COUNT.inc(method="Watch")
        watchers: dict[int, Watcher] = {}
        pumps: dict[int, asyncio.Task] = {}
        next_id = 1
        # Bounded reply queue (bounded-watch-buffer): a wedged client
        # socket backpressures this stream's pumps at the bound — their
        # native Watcher queues are themselves capped and cancel on
        # overflow — instead of buffering responses without limit.
        out: asyncio.Queue = asyncio.Queue(maxsize=1024)
        last_delivered = 0
        # Per-watch "delivered through" revision: every event <= cleared[wid]
        # matching the watch has been written to the stream.  Advances on
        # delivered events, and — for watches with nothing to say — on an
        # empty poll, using a revision snapshot taken BEFORE the poll (the
        # native queue is filled inside the store's write lock, so an empty
        # queue proves delivery through any revision committed before the
        # poll began).  This is what makes progress responses a true
        # barrier (etcd semantics: a progress notification promises the
        # client has seen everything at or below its revision).
        cleared: dict[int, int] = {}
        barriers: set = set()
        # Per-stream shared frame table (wiretier): an event fanning to
        # several watches on this stream is proto-encoded once, keyed
        # by its identity (prev_kv requests encode differently).
        ftable = wiretier.FrameTable(cap=4096)

        async def pump(wid: int, w: Watcher):
            nonlocal last_delivered
            loop = asyncio.get_running_loop()
            try:
                while True:
                    r0 = self.store.progress_revision
                    events = await loop.run_in_executor(
                        None, w.poll, _WATCH_BATCH, 0
                    )
                    if w.dropped:
                        # Queue overflow lost events; a silently gapped
                        # stream would corrupt client caches — cancel, as
                        # the store contract requires, so the client
                        # re-establishes from its last good revision.
                        w.cancel()
                        watchers.pop(wid, None)
                        await out.put(
                            rpc_pb2.WatchResponse(
                                header=self._header(),
                                watch_id=wid,
                                canceled=True,
                                cancel_reason="watcher overflowed; events dropped",
                            )
                        )
                        return
                    if w.canceled and not events:
                        await out.put(
                            rpc_pb2.WatchResponse(
                                header=self._header(),
                                watch_id=wid,
                                canceled=True,
                            )
                        )
                        return
                    if not events:
                        if cleared.get(wid, 0) < r0:
                            cleared[wid] = r0
                        await asyncio.sleep(_WATCH_POLL_S)
                        continue
                    chunks = [
                        ftable.bytes_for(
                            (ev.kv.mod_revision, ev.kv.key, ev.type,
                             ev.prev_kv is not None),
                            _encode_watch_event, ev,
                        )
                        for ev in events
                    ]
                    for ev in events:
                        last_delivered = max(last_delivered, ev.kv.mod_revision)
                    await out.put(
                        wiretier.compose_frame(
                            wiretier.header_bytes(self._header()),
                            [wid], chunks,
                        )
                    )
                    if cleared.get(wid, 0) < events[-1].kv.mod_revision:
                        cleared[wid] = events[-1].kv.mod_revision
            except asyncio.CancelledError:
                raise

        async def reader():
            nonlocal next_id
            async for req in request_iterator:
                which = req.WhichOneof("request_union")
                if which == "create_request":
                    cr = req.create_request
                    wid = cr.watch_id or next_id
                    next_id = max(next_id, wid) + 1
                    if wid in watchers:
                        # etcd rejects duplicate watch ids with a cancel
                        # response; silently replacing would orphan the old
                        # pump and leak its native event buffer.
                        await out.put(
                            rpc_pb2.WatchResponse(
                                header=self._header(),
                                watch_id=wid,
                                canceled=True,
                                cancel_reason="duplicate watch_id",
                            )
                        )
                        continue
                    try:
                        w = self.store.watch(
                            cr.key,
                            self._end_of(cr.range_end),
                            start_revision=cr.start_revision,
                            prev_kv=cr.prev_kv,
                        )
                    except CompactedError as e:
                        _WATCH_COMPACT_CANCELS.inc()
                        await out.put(
                            rpc_pb2.WatchResponse(
                                header=self._header(),
                                watch_id=wid,
                                created=True,
                                canceled=True,
                                compact_revision=e.compact_revision,
                            )
                        )
                        continue
                    watchers[wid] = w
                    await out.put(
                        rpc_pb2.WatchResponse(
                            header=self._header(), watch_id=wid, created=True
                        )
                    )
                    pumps[wid] = asyncio.create_task(pump(wid, w))
                elif which == "cancel_request":
                    wid = req.cancel_request.watch_id
                    w = watchers.pop(wid, None)
                    if w is not None:
                        w.cancel()
                        task = pumps.pop(wid, None)
                        if task:
                            task.cancel()
                        await out.put(
                            rpc_pb2.WatchResponse(
                                header=self._header(), watch_id=wid, canceled=True
                            )
                        )
                elif which == "progress_request":
                    # Progress must never regress below delivered events
                    # (reference watch_service.rs:172-176), and must not
                    # OVERTAKE them either: the response is a barrier —
                    # it goes out only after every watch on this stream
                    # has delivered through the progress revision (real
                    # etcd orders progress after prior events; the
                    # consistent-read-from-cache protocol depends on it).
                    rev = max(self.store.progress_revision, last_delivered)
                    t = asyncio.create_task(
                        progress_barrier(rev, list(watchers))
                    )
                    barriers.add(t)
                    t.add_done_callback(barriers.discard)
            await out.put(None)

        async def progress_barrier(rev: int, wids: list[int]) -> None:
            try:
                while not all(
                    wid not in watchers or cleared.get(wid, 0) >= rev
                    for wid in wids
                ):
                    await asyncio.sleep(_WATCH_POLL_S)
                await out.put(
                    rpc_pb2.WatchResponse(
                        header=self._header(rev), watch_id=-1
                    )
                )
            except asyncio.CancelledError:
                raise

        rtask = asyncio.create_task(reader())
        try:
            while True:
                resp = await out.get()
                if resp is None:
                    return
                yield resp
        finally:
            rtask.cancel()
            for task in pumps.values():
                task.cancel()
            for task in list(barriers):
                task.cancel()
            for w in watchers.values():
                w.cancel()

    # ---- Lease (deliberately fake, reference lease_service.rs) ---------

    async def LeaseGrant(self, req: rpc_pb2.LeaseGrantRequest, ctx):
        _REQ_COUNT.inc(method="LeaseGrant")
        async with self._lease_lock:
            self._lease_id += 1
            lid = req.ID or self._lease_id
            self._leases[lid] = req.TTL
        return rpc_pb2.LeaseGrantResponse(
            header=self._header(), ID=lid, TTL=req.TTL
        )

    async def LeaseRevoke(self, req: rpc_pb2.LeaseRevokeRequest, ctx):
        _REQ_COUNT.inc(method="LeaseRevoke")
        self._leases.pop(req.ID, None)
        return rpc_pb2.LeaseRevokeResponse(header=self._header())

    async def LeaseKeepAlive(self, request_iterator, ctx):
        async for req in request_iterator:
            yield rpc_pb2.LeaseKeepAliveResponse(
                header=self._header(),
                ID=req.ID,
                TTL=self._leases.get(req.ID, 0),
            )

    async def LeaseTimeToLive(self, req: rpc_pb2.LeaseTimeToLiveRequest, ctx):
        ttl = self._leases.get(req.ID)
        if ttl is None:
            return rpc_pb2.LeaseTimeToLiveResponse(
                header=self._header(), ID=req.ID, TTL=-1
            )
        return rpc_pb2.LeaseTimeToLiveResponse(
            header=self._header(), ID=req.ID, TTL=ttl, grantedTTL=ttl
        )

    async def LeaseLeases(self, req: rpc_pb2.LeaseLeasesRequest, ctx):
        return rpc_pb2.LeaseLeasesResponse(
            header=self._header(),
            leases=[rpc_pb2.LeaseStatus(ID=lid) for lid in self._leases],
        )

    # ---- Maintenance ---------------------------------------------------

    async def Status(self, req: rpc_pb2.StatusRequest, ctx):
        return rpc_pb2.StatusResponse(
            header=self._header(),
            version="3.5.16",
            dbSize=self.store.db_size,
            dbSizeInUse=self.store.db_size,
            leader=1,
            raftIndex=1,
            raftTerm=1,
        )

    async def Alarm(self, req: rpc_pb2.AlarmRequest, ctx):
        return rpc_pb2.AlarmResponse(header=self._header())

    async def Defragment(self, req: rpc_pb2.DefragmentRequest, ctx):
        return rpc_pb2.DefragmentResponse(header=self._header())

    async def Hash(self, req, ctx):
        await ctx.abort(grpc.StatusCode.UNIMPLEMENTED, "Hash not implemented")

    async def Snapshot(self, req, ctx):
        await ctx.abort(grpc.StatusCode.UNIMPLEMENTED, "Snapshot not implemented")
        yield  # pragma: no cover — makes this an async generator

    async def MoveLeader(self, req, ctx):
        await ctx.abort(grpc.StatusCode.UNIMPLEMENTED, "MoveLeader not implemented")


def _unary(fn, req_cls, resp_cls):
    return grpc.unary_unary_rpc_method_handler(
        fn,
        request_deserializer=req_cls.FromString,
        response_serializer=resp_cls.SerializeToString,
    )


def _stream_stream(fn, req_cls, resp_cls):
    return grpc.stream_stream_rpc_method_handler(
        fn,
        request_deserializer=req_cls.FromString,
        response_serializer=resp_cls.SerializeToString,
    )


def _unary_stream(fn, req_cls, resp_cls):
    return grpc.unary_stream_rpc_method_handler(
        fn,
        request_deserializer=req_cls.FromString,
        response_serializer=resp_cls.SerializeToString,
    )


def add_services(server: aio.Server, svc: EtcdService) -> None:
    pb = rpc_pb2
    kv = {
        "Range": _unary(svc.Range, pb.RangeRequest, pb.RangeResponse),
        "Put": _unary(svc.Put, pb.PutRequest, pb.PutResponse),
        "DeleteRange": _unary(
            svc.DeleteRange, pb.DeleteRangeRequest, pb.DeleteRangeResponse
        ),
        "Txn": _unary(svc.Txn, pb.TxnRequest, pb.TxnResponse),
        "Compact": _unary(svc.Compact, pb.CompactionRequest, pb.CompactionResponse),
    }
    watch = {
        # Event frames leave the pumps pre-composed (wiretier shared
        # chunk bytes); control responses stay proto objects.
        "Watch": grpc.stream_stream_rpc_method_handler(
            svc.Watch,
            request_deserializer=pb.WatchRequest.FromString,
            response_serializer=wiretier.serialize_frame_or_message,
        ),
    }
    lease = {
        "LeaseGrant": _unary(svc.LeaseGrant, pb.LeaseGrantRequest, pb.LeaseGrantResponse),
        "LeaseRevoke": _unary(
            svc.LeaseRevoke, pb.LeaseRevokeRequest, pb.LeaseRevokeResponse
        ),
        "LeaseKeepAlive": _stream_stream(
            svc.LeaseKeepAlive, pb.LeaseKeepAliveRequest, pb.LeaseKeepAliveResponse
        ),
        "LeaseTimeToLive": _unary(
            svc.LeaseTimeToLive, pb.LeaseTimeToLiveRequest, pb.LeaseTimeToLiveResponse
        ),
        "LeaseLeases": _unary(
            svc.LeaseLeases, pb.LeaseLeasesRequest, pb.LeaseLeasesResponse
        ),
    }
    maint = {
        "Alarm": _unary(svc.Alarm, pb.AlarmRequest, pb.AlarmResponse),
        "Status": _unary(svc.Status, pb.StatusRequest, pb.StatusResponse),
        "Defragment": _unary(svc.Defragment, pb.DefragmentRequest, pb.DefragmentResponse),
        "Hash": _unary(svc.Hash, pb.HashRequest, pb.HashResponse),
        "Snapshot": _unary_stream(svc.Snapshot, pb.SnapshotRequest, pb.SnapshotResponse),
        "MoveLeader": _unary(svc.MoveLeader, pb.MoveLeaderRequest, pb.MoveLeaderResponse),
    }
    batch = {
        "PutFrame": _unary(
            svc.PutFrame, batch_pb2.PutFrameRequest, batch_pb2.PutFrameResponse
        ),
        "BindFrame": _unary(
            svc.BindFrame, batch_pb2.BindFrameRequest, batch_pb2.BindFrameResponse
        ),
    }
    for name, handlers in (
        ("etcdserverpb.KV", kv),
        ("etcdserverpb.Watch", watch),
        ("etcdserverpb.Lease", lease),
        ("etcdserverpb.Maintenance", maint),
        ("k8s1m.BatchKV", batch),
    ):
        server.add_generic_rpc_handlers(
            (grpc.method_handlers_generic_handler(name, handlers),)
        )


async def serve(
    store: MemStore,
    port: int = 2379,
    host: str = "127.0.0.1",
    metrics_port: int = 0,
) -> tuple[aio.Server, int]:
    """Start the etcd-compatible server; returns (server, bound_port)."""
    server = aio.server(
        options=[
            # Mirror the reference's HTTP/2 tuning (main.rs:145-147).
            ("grpc.max_concurrent_streams", 100),
            ("grpc.max_receive_message_length", 64 * 1024 * 1024),
            ("grpc.max_send_message_length", 64 * 1024 * 1024),
        ]
    )
    add_services(server, EtcdService(store))
    bound = server.add_insecure_port(f"{host}:{port}")
    if bound == 0:
        raise OSError(f"failed to bind {host}:{port} (port in use?)")
    await server.start()
    if metrics_port:
        from k8s1m_tpu.obs.http import start_metrics_server

        _SERVED_STORES.add(store)
        start_metrics_server(metrics_port)
    return server, bound
