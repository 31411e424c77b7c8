"""The HBM-resident node table: a struct-of-arrays snapshot of every node.

This replaces the reference's 256 label-sharded Go informer caches
(reference cmd/dist-scheduler/scheduler.go:201-219, ~100KB/node in RAM per
RUNNING.adoc:193) with one padded tensor table: ~250 bytes/node, so a
million nodes is ~250MB — a fraction of one chip's HBM.  The table is a JAX
pytree; sharding it over the mesh's node axis is the TPU equivalent of the
reference's `dist-scheduler.dev/scheduler` label sharding
(reference cmd/dist-scheduler/leader_activities.go:227-343).

Mutation happens two ways, both jit-compatible scatters:
- ``apply_delta``   — coordinator-streamed node add/update/remove, the
  equivalent of informer events (revision-ordered by the coordinator the
  way mem_etcd's notify thread orders watch events, reference
  mem_etcd/src/store.rs:444-533).
- ``commit_binds``  — the engine folds its own bind decisions back into
  requested-resources before the next batch, the equivalent of the
  scheduler's assume/bind cache update.

**Wave epochs & the free-row quarantine.**  A pipelined coordinator keeps
several device waves in flight; a wave launched before a node removal may
still hold the removed row in its candidate lists.  Freeing the row id
immediately would let the next node allocation reuse it, and the in-flight
wave's bind would silently land on the *new* node (row aliasing).  So row
removal is two-phase: ``remove`` tombstones the row (``valid=0`` in the
host mirror; the coordinator scatters it to the device the same cycle)
and parks the row id in a quarantine stamped with the current
``wave_epoch`` — the count of waves launched so far (``begin_wave``).
``release_rows(before_epoch)`` returns quarantined rows to the free list
once every wave launched at or before their removal epoch has retired.
Fresh-row allocation appends past the high-water mark (or reuses a
*released* row), so structural adds never need the pipeline quiesced;
only quarantine exhaustion (``RowsExhausted`` with rows parked) does.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from k8s1m_tpu.config import (
    EFFECT_NO_SCHEDULE,
    NONE_ID,
    TableSpec,
)
from k8s1m_tpu.lint import THREAD_OWNER, guarded_by
from k8s1m_tpu.obs.metrics import Counter, Gauge
from k8s1m_tpu.snapshot.interning import Vocab, numeric_of

_BULK_ROWS = Counter(
    "megarow_bulk_ingest_rows_total",
    "Node rows ingested through the vectorized bulk lane "
    "(NodeTableHost.bulk_upsert / snapshot.bulkload) — rate vs wall "
    "clock is the bulk-ingest rows/s evidence", (),
)
_MIRROR_BYTES = Gauge(
    "megarow_host_mirror_bytes",
    "Host-mirror column bytes across live NodeTableHost instances "
    "(the int16/int8 mirror-width rule's budget gauge)", (),
)
_LIVE_HOSTS: weakref.WeakSet = weakref.WeakSet()
# The gauge callback runs on the metrics scrape thread while any other
# thread may be constructing a NodeTableHost; a bare WeakSet iteration
# concurrent with add() raises "set changed size during iteration", so
# both sides serialize on this lock (mirror_nbytes reads immutable
# array headers — cheap enough to hold it across the sum).
_HOSTS_LOCK = threading.Lock()


def _mirror_bytes_total() -> int:
    with _HOSTS_LOCK:
        return sum(h.mirror_nbytes() for h in _LIVE_HOSTS)


_MIRROR_BYTES.set_function(_mirror_bytes_total)


def mirror_dtype(bound: int) -> np.dtype:
    """Host-mirror column width for ids in ``[0, bound)``: the
    narrowest signed dtype that holds the TableSpec bound, mirroring
    snapshot/packing.py's packed-layout dtype decisions.  A million-row
    mirror must not spend 4 bytes on a 512-value zone column; the
    device-facing transfer paths (``to_device``, the coordinator's
    dirty-row deltas) re-widen to the canonical int32 so the unpacked
    device layout is byte-identical either way.  New columns MUST pick
    their width through this rule (MIGRATION: "Host-mirror dtypes")."""
    if bound <= 1 << 7:
        return np.dtype(np.int8)
    if bound <= 1 << 15:
        return np.dtype(np.int16)
    return np.dtype(np.int32)

class RowsExhausted(ValueError):
    """No allocatable row: the table is at ``max_nodes`` and the free
    list is empty.  ``quarantined`` says how many rows are parked in the
    wave-epoch quarantine — nonzero means a pipeline quiesce (retire all
    in-flight waves, then ``release_rows(None)``) recovers capacity;
    zero means the table is genuinely full (re-bucket TableSpec)."""

    def __init__(self, msg: str, quarantined: int = 0):
        super().__init__(msg)
        self.quarantined = quarantined


UNSCHEDULABLE_TAINT_KEY = "node.kubernetes.io/unschedulable"
ZONE_LABEL = "topology.kubernetes.io/zone"
REGION_LABEL = "topology.kubernetes.io/region"
HOSTNAME_LABEL = "kubernetes.io/hostname"


@dataclasses.dataclass
class Taint:
    key: str
    value: str = ""
    effect: int = EFFECT_NO_SCHEDULE


@dataclasses.dataclass
class NodeInfo:
    """Host-side description of one node (the parsed KWOK/real Node object)."""

    name: str
    cpu_milli: int = 4000
    mem_kib: int = 8 << 20          # 8 GiB
    pods: int = 110
    labels: dict[str, str] = dataclasses.field(default_factory=dict)
    taints: list[Taint] = dataclasses.field(default_factory=list)
    unschedulable: bool = False


@struct.dataclass
class NodeTable:
    """Device-resident snapshot. All arrays padded to spec.max_nodes rows."""

    valid: jax.Array        # bool[N] — row is a live node
    # Allocatable (reference: node.status.allocatable).
    cpu_alloc: jax.Array    # i32[N] milliCPU
    mem_alloc: jax.Array    # i32[N] KiB  (2 TiB/node cap; KWOK nodes are far below)
    pods_alloc: jax.Array   # i32[N]
    # Sum of requests of pods assumed/bound to the node.
    cpu_req: jax.Array      # i32[N]
    mem_req: jax.Array      # i32[N]
    pods_req: jax.Array     # i32[N]
    # Interned labels: padded (key,value) slots + pre-parsed numeric value
    # for Gt/Lt selector operators.
    label_key: jax.Array    # i32[N, L]
    label_val: jax.Array    # i32[N, L]
    label_num: jax.Array    # i32[N, L]
    # Taints as interned (key,value,effect)-triple ids plus the effect, so
    # the filter can distinguish hard (NoSchedule/NoExecute) from soft
    # (PreferNoSchedule) without re-deriving it.  node.spec.unschedulable is
    # folded in as the canonical node.kubernetes.io/unschedulable:NoSchedule
    # taint (upstream TaintNodeUnschedulable).
    taint_id: jax.Array     # i32[N, T] triple id in [0, max_taint_ids)
    taint_effect: jax.Array  # i32[N, T]
    # Dense topology-domain ids for the count tables.
    zone: jax.Array         # i32[N] in [0, max_zones)
    region: jax.Array       # i32[N] in [0, max_regions)
    name_id: jax.Array      # i32[N] interned node name (NodeName plugin)

    @property
    def num_rows(self) -> int:
        return self.valid.shape[0]

    def free(self):
        """(cpu, mem, pods) still unrequested, for Fit and LeastAllocated."""
        return (
            self.cpu_alloc - self.cpu_req,
            self.mem_alloc - self.mem_req,
            self.pods_alloc - self.pods_req,
        )


def empty_table(spec: TableSpec) -> NodeTable:
    n, l, t = spec.max_nodes, spec.label_slots, spec.taint_slots
    i32 = jnp.int32
    return NodeTable(
        valid=jnp.zeros((n,), jnp.bool_),
        cpu_alloc=jnp.zeros((n,), i32),
        mem_alloc=jnp.zeros((n,), i32),
        pods_alloc=jnp.zeros((n,), i32),
        cpu_req=jnp.zeros((n,), i32),
        mem_req=jnp.zeros((n,), i32),
        pods_req=jnp.zeros((n,), i32),
        label_key=jnp.zeros((n, l), i32),
        label_val=jnp.zeros((n, l), i32),
        label_num=jnp.zeros((n, l), i32),
        taint_id=jnp.zeros((n, t), i32),
        taint_effect=jnp.zeros((n, t), i32),
        zone=jnp.zeros((n,), i32),
        region=jnp.zeros((n,), i32),
        name_id=jnp.zeros((n,), i32),
    )


@guarded_by(
    # The wave-epoch quarantine and the row mapping are the no-aliasing
    # core of quiesce-free pipelining (PR 3): both are cycle-thread-
    # confined, and a foreign thread touching either could hand an
    # in-flight wave's row to a new node.  Audited under
    # lint/guards.py's instrumentation mode.
    _quarantine=THREAD_OWNER,
    _free_rows=THREAD_OWNER,
    _row_of=THREAD_OWNER,
    wave_epoch=THREAD_OWNER,
)
class NodeTableHost:
    """Host-side builder/mirror of the node table (numpy, mutable).

    The coordinator owns one of these: informer-style deltas mutate it and
    are batched into device scatters.  It is also the feature compiler —
    the only place node strings are parsed and interned.
    """

    def __init__(self, spec: TableSpec, vocab: Vocab | None = None) -> None:
        self.spec = spec
        self.vocab = vocab or Vocab()
        n, l, t = spec.max_nodes, spec.label_slots, spec.taint_slots
        self.valid = np.zeros((n,), np.bool_)
        self.cpu_alloc = np.zeros((n,), np.int32)
        self.mem_alloc = np.zeros((n,), np.int32)
        self.pods_alloc = np.zeros((n,), np.int32)
        self.cpu_req = np.zeros((n,), np.int32)
        self.mem_req = np.zeros((n,), np.int32)
        self.pods_req = np.zeros((n,), np.int32)
        # Label/name ids are unbounded by TableSpec (a 1M-node cluster
        # interns ~1M hostname label values), so they stay int32; the
        # spec-bounded columns take the narrow mirror width.
        self.label_key = np.zeros((n, l), np.int32)
        self.label_val = np.zeros((n, l), np.int32)
        self.label_num = np.zeros((n, l), np.int32)
        self.taint_id = np.zeros((n, t), mirror_dtype(spec.max_taint_ids))
        # Effects are the 2-bit EFFECT_* range, checked at upsert the
        # same way pack_meta_np fail-closes past the packed budget.
        self.taint_effect = np.zeros((n, t), np.int8)
        self.zone = np.zeros((n,), mirror_dtype(spec.max_zones))
        self.region = np.zeros((n,), mirror_dtype(spec.max_regions))
        self.name_id = np.zeros((n,), np.int32)
        self._row_of: dict[str, int] = {}
        self._free_rows: list[int] = []
        self._next_row = 0
        # Pipelined-scheduler wave clock: bumped by begin_wave() at every
        # device dispatch.  0 = no consumer pipelines waves, and removes
        # free their row immediately (standalone/tool users of this
        # class never quarantine).
        self.wave_epoch = 0
        # Removed rows awaiting release: (removal wave_epoch, row),
        # epoch-ordered by construction (the clock is monotone).  A row
        # here is tombstoned (valid=0, columns zeroed) but NOT reusable —
        # a wave launched before the removal may still bind into it.
        self._quarantine: collections.deque[tuple[int, int]] = (
            collections.deque()
        )
        # Bumped on every row->name mapping change (new node, removal,
        # row reuse) — consumers holding derived per-row state (the shard
        # set's ownership mask) refresh when this moves.
        self.epoch = 0
        # Opt-in delta journal of those changes: (name, row, alive)
        # appended in order, so a consumer can update per-row state
        # incrementally instead of re-scanning 1M rows per change.  The
        # consumer owns draining it (enable_row_journal returns the list;
        # clear after consuming).
        self._row_journal: list[tuple[str, int, bool]] | None = None
        with _HOSTS_LOCK:
            _LIVE_HOSTS.add(self)

    def mirror_nbytes(self) -> int:
        """Total bytes held by the mirror's column arrays (the
        megarow_host_mirror_bytes evidence; excludes the row mapping
        and vocab, which are Python dicts)."""
        return sum(
            getattr(self, c).nbytes
            for c in (
                "valid", "cpu_alloc", "mem_alloc", "pods_alloc",
                "cpu_req", "mem_req", "pods_req",
                "label_key", "label_val", "label_num",
                "taint_id", "taint_effect", "zone", "region", "name_id",
            )
        )

    def enable_row_journal(self) -> list[tuple[str, int, bool]]:
        if self._row_journal is None:
            self._row_journal = []
        return self._row_journal

    # ---- row management -------------------------------------------------

    @property
    def high_water(self) -> int:
        """Rows that have ever held a node: fresh rows are handed out in
        order, so every live node's row lies below this mark."""
        return self._next_row

    def row_of(self, name: str) -> int:
        return self._row_of[name]

    def _alloc_row(self, name: str) -> int:
        if name in self._row_of:
            return self._row_of[name]
        if self._free_rows:
            row = self._free_rows.pop()
        else:
            row = self._next_row
            if row >= self.spec.max_nodes:
                raise RowsExhausted(
                    f"node table full ({self.spec.max_nodes}); re-bucket "
                    "TableSpec" + (
                        f" ({len(self._quarantine)} rows quarantined; a "
                        "pipeline quiesce releases them)"
                        if self._quarantine else ""
                    ),
                    quarantined=len(self._quarantine),
                )
            self._next_row += 1
        self._row_of[name] = row
        self.epoch += 1
        if self._row_journal is not None:
            self._row_journal.append((name, row, True))
        return row

    def bulk_alloc(self, names) -> np.ndarray:
        """Allocate (or resolve) a row per name, with the capacity
        check front-loaded: either every name gets a row, or
        RowsExhausted raises BEFORE any allocation — a mid-batch raise
        would leave names mapped to rows whose columns were never
        written (the bulk lanes write columns only after every row is
        allocated)."""
        row_of = self._row_of
        fresh = {n for n in names if n not in row_of}
        free = len(self._free_rows) + (self.spec.max_nodes - self._next_row)
        if len(fresh) > free:
            raise RowsExhausted(
                f"bulk ingest needs {len(fresh)} fresh rows but only "
                f"{free} are allocatable (max_nodes="
                f"{self.spec.max_nodes})" + (
                    f" ({len(self._quarantine)} rows quarantined; a "
                    "pipeline quiesce releases them)"
                    if self._quarantine else ""
                ),
                quarantined=len(self._quarantine),
            )
        rows = np.empty((len(names),), np.int64)
        alloc = self._alloc_row
        for i, name in enumerate(names):
            rows[i] = alloc(name)
        return rows

    def alloc_rows(self, names: list[str]) -> np.ndarray:
        """Bulk-allocate contiguous-ish rows for many new nodes.

        Fast path for load generators (the make_nodes equivalent,
        reference kwok/make_nodes/main.go:116-182): callers fill the table
        columns vectorized; per-row python dispatch would dominate at 1M.
        """
        rows = np.empty((len(names),), np.int64)
        for i, name in enumerate(names):
            rows[i] = self._alloc_row(name)
        self.valid[rows] = True
        return rows

    # ---- deltas ---------------------------------------------------------

    def upsert(self, node: NodeInfo) -> int:
        """Add or update a node; returns its row."""
        v = self.vocab
        row = self._alloc_row(node.name)

        labels = dict(node.labels)
        labels.setdefault(HOSTNAME_LABEL, node.name)
        if len(labels) > self.spec.label_slots:
            raise ValueError(
                f"node {node.name}: {len(labels)} labels > "
                f"label_slots={self.spec.label_slots}"
            )
        lk = np.zeros((self.spec.label_slots,), np.int32)
        lv = np.zeros_like(lk)
        ln = np.zeros_like(lk)
        for i, (k, val) in enumerate(sorted(labels.items())):
            lk[i] = v.label_keys.intern(k)
            lv[i] = v.label_values.intern(val)
            ln[i] = numeric_of(val)

        taints = list(node.taints)
        if node.unschedulable:
            taints.append(Taint(UNSCHEDULABLE_TAINT_KEY, "", EFFECT_NO_SCHEDULE))
        if len(taints) > self.spec.taint_slots:
            raise ValueError(
                f"node {node.name}: {len(taints)} taints > "
                f"taint_slots={self.spec.taint_slots}"
            )
        tk = np.zeros((self.spec.taint_slots,), self.taint_id.dtype)
        te = np.zeros((self.spec.taint_slots,), self.taint_effect.dtype)
        for i, taint in enumerate(taints):
            if not 0 <= taint.effect < 4:
                # Same fail-closed contract as pack_meta_np's 2-bit
                # budget: an out-of-range effect must raise here, not
                # truncate into the int8 mirror.
                raise ValueError(
                    f"node {node.name}: taint effect {taint.effect} "
                    "outside the EFFECT_* range [0, 4)"
                )
            tid = v.taints.intern((taint.key, taint.value, taint.effect))
            if tid >= self.spec.max_taint_ids:
                raise ValueError(
                    "distinct taint triples overflow TableSpec.max_taint_ids"
                )
            tk[i] = tid
            te[i] = taint.effect

        zone_id = v.zones.intern(labels.get(ZONE_LABEL)) if ZONE_LABEL in labels else NONE_ID
        region_id = (
            v.regions.intern(labels.get(REGION_LABEL)) if REGION_LABEL in labels else NONE_ID
        )
        if zone_id >= self.spec.max_zones or region_id >= self.spec.max_regions:
            raise ValueError("zone/region id overflow; grow TableSpec.max_zones/max_regions")

        self.valid[row] = True
        self.cpu_alloc[row] = node.cpu_milli
        self.mem_alloc[row] = node.mem_kib
        self.pods_alloc[row] = node.pods
        self.label_key[row], self.label_val[row], self.label_num[row] = lk, lv, ln
        self.taint_id[row], self.taint_effect[row] = tk, te
        self.zone[row] = zone_id
        self.region[row] = region_id
        self.name_id[row] = v.node_names.intern(node.name)
        return row

    def bulk_upsert(self, nodes) -> np.ndarray:
        """Vectorized ``upsert`` over many nodes; returns their rows.

        Byte-identical to ``[self.upsert(n) for n in nodes]`` — same
        column bytes, same row mapping, same vocab contents in the same
        intern order, same row-journal entries — but the per-node numpy
        allocations and scattered row writes collapse into block fills
        and one fancy-indexed write per column, the first wall a 1M-row
        cold build hits (ISSUE 14).  A name repeated within the batch
        resolves like repeated upserts: the later entry wins (numpy
        fancy assignment applies in order).

        Validation is front-loaded: any per-node error (label/taint
        overflow, id past a TableSpec bound) raises BEFORE any table
        column, row mapping or journal mutation — strictly cleaner than
        the loop's partial application (interned strings from the batch
        may remain; interners are append-only and ids are data).
        """
        spec = self.spec
        v = self.vocab
        b, nslots, tslots = len(nodes), spec.label_slots, spec.taint_slots
        lk = np.zeros((b, nslots), np.int32)
        lv = np.zeros((b, nslots), np.int32)
        ln = np.zeros((b, nslots), np.int32)
        tk = np.zeros((b, tslots), self.taint_id.dtype)
        te = np.zeros((b, tslots), self.taint_effect.dtype)
        zone = np.zeros((b,), np.int32)
        region = np.zeros((b,), np.int32)
        name_id = np.zeros((b,), np.int32)
        cpu = np.zeros((b,), np.int32)
        mem = np.zeros((b,), np.int32)
        pods = np.zeros((b,), np.int32)
        # Interner internals bound once per batch: the per-label
        # ``intern`` method-call overhead is a measured slice of the 1M
        # ingest wall (same-package access, mirrors Interner.intern).
        lk_id, lk_val = v.label_keys._to_id, v.label_keys._to_val
        lv_id, lv_val = v.label_values._to_id, v.label_values._to_val
        # numeric_of memo keyed by interned value id: repeated label
        # values (zones, groups) pay the parse once per distinct value.
        num_of: dict[int, int] = {}
        for i, node in enumerate(nodes):
            labels = dict(node.labels)
            labels.setdefault(HOSTNAME_LABEL, node.name)
            if len(labels) > nslots:
                raise ValueError(
                    f"node {node.name}: {len(labels)} labels > "
                    f"label_slots={nslots}"
                )
            for j, (k, val) in enumerate(sorted(labels.items())):
                ik = lk_id.get(k)
                if ik is None:
                    ik = len(lk_val)
                    lk_id[k] = ik
                    lk_val.append(k)
                if val is None:
                    # Interner.intern's None -> NONE_ID mapping (a JSON
                    # null label value reaches here via decode_node);
                    # the inlined fast path must not intern None as a
                    # fresh id or the bulk lane diverges from upsert.
                    iv, num = NONE_ID, numeric_of(val)
                else:
                    iv = lv_id.get(val)
                    if iv is None:
                        iv = len(lv_val)
                        lv_id[val] = iv
                        lv_val.append(val)
                    num = num_of.get(iv)
                    if num is None:
                        num = numeric_of(val)
                        num_of[iv] = num
                lk[i, j] = ik
                lv[i, j] = iv
                ln[i, j] = num
            taints = list(node.taints)
            if node.unschedulable:
                taints.append(
                    Taint(UNSCHEDULABLE_TAINT_KEY, "", EFFECT_NO_SCHEDULE)
                )
            if len(taints) > tslots:
                raise ValueError(
                    f"node {node.name}: {len(taints)} taints > "
                    f"taint_slots={tslots}"
                )
            for j, taint in enumerate(taints):
                if not 0 <= taint.effect < 4:
                    raise ValueError(
                        f"node {node.name}: taint effect {taint.effect} "
                        "outside the EFFECT_* range [0, 4)"
                    )
                tid = v.taints.intern((taint.key, taint.value, taint.effect))
                if tid >= spec.max_taint_ids:
                    raise ValueError(
                        "distinct taint triples overflow "
                        "TableSpec.max_taint_ids"
                    )
                tk[i, j] = tid
                te[i, j] = taint.effect
            zid = (
                v.zones.intern(labels[ZONE_LABEL])
                if ZONE_LABEL in labels else NONE_ID
            )
            rid = (
                v.regions.intern(labels[REGION_LABEL])
                if REGION_LABEL in labels else NONE_ID
            )
            if zid >= spec.max_zones or rid >= spec.max_regions:
                raise ValueError(
                    "zone/region id overflow; grow "
                    "TableSpec.max_zones/max_regions"
                )
            zone[i] = zid
            region[i] = rid
            name_id[i] = v.node_names.intern(node.name)
            cpu[i] = node.cpu_milli
            mem[i] = node.mem_kib
            pods[i] = node.pods
        # Every node validated: allocate rows (capacity pre-checked;
        # journal + epoch side effects in batch order, exactly like the
        # loop) and land the blocks in one write per column.
        rows = self.bulk_alloc([node.name for node in nodes])
        self.valid[rows] = True
        self.cpu_alloc[rows] = cpu
        self.mem_alloc[rows] = mem
        self.pods_alloc[rows] = pods
        self.label_key[rows] = lk
        self.label_val[rows] = lv
        self.label_num[rows] = ln
        self.taint_id[rows] = tk
        self.taint_effect[rows] = te
        self.zone[rows] = zone
        self.region[rows] = region
        self.name_id[rows] = name_id
        _BULK_ROWS.inc(b)
        return rows

    def remove(self, name: str) -> int:
        row = self._row_of.pop(name)
        self.valid[row] = False
        # Zero the row so stale ids can't match future queries.
        for arr in (
            self.cpu_alloc, self.mem_alloc, self.pods_alloc,
            self.cpu_req, self.mem_req, self.pods_req,
            self.zone, self.region, self.name_id,
        ):
            arr[row] = 0
        for arr in (
            self.label_key, self.label_val, self.label_num,
            self.taint_id, self.taint_effect,
        ):
            arr[row] = 0
        if self.wave_epoch:
            # Two-phase free: the row is tombstoned now (the caller
            # scatters valid=0 immediately) but its id stays quarantined
            # until every wave launched at or before this epoch retires
            # (see release_rows) — the row-aliasing guard that lets a
            # pipelined coordinator apply removes without a quiesce.
            self._quarantine.append((self.wave_epoch, row))
        else:
            self._free_rows.append(row)
        self.epoch += 1
        if self._row_journal is not None:
            self._row_journal.append((name, row, False))
        return row

    # ---- wave epochs ----------------------------------------------------

    def begin_wave(self) -> int:
        """Stamp one device-wave launch; returns the wave's epoch."""
        self.wave_epoch += 1
        return self.wave_epoch

    def release_rows(self, before_epoch: int | None = None) -> int:
        """Return quarantined rows to the free list.

        ``before_epoch`` is the oldest still-in-flight wave's epoch: a
        row removed at epoch E is only referenced by waves launched at
        epoch <= E, so it is safe once ``E < before_epoch``.  ``None``
        (no wave in flight) releases everything.  Returns the count.
        """
        n = 0
        q = self._quarantine
        while q and (before_epoch is None or q[0][0] < before_epoch):
            self._free_rows.append(q.popleft()[1])
            n += 1
        return n

    @property
    def quarantined(self) -> int:
        return len(self._quarantine)

    def add_pod(self, name: str, cpu_milli: int, mem_kib: int) -> None:
        """Account an already-bound pod (host mirror of commit_binds)."""
        row = self._row_of[name]
        self.cpu_req[row] += cpu_milli
        self.mem_req[row] += mem_kib
        self.pods_req[row] += 1

    def remove_pod(self, name: str, cpu_milli: int, mem_kib: int) -> None:
        row = self._row_of[name]
        self.cpu_req[row] -= cpu_milli
        self.mem_req[row] -= mem_kib
        self.pods_req[row] -= 1

    @property
    def num_nodes(self) -> int:
        return len(self._row_of)

    # ---- device transfer ------------------------------------------------

    def to_device(self, sharding=None) -> NodeTable:
        def put(x):
            if x.dtype != np.bool_:
                # Narrow mirror columns (mirror_dtype rule) widen back
                # to the canonical device int32; no-copy when already
                # int32, so the wide columns transfer as before.
                x = np.asarray(x, np.int32)
            return jax.device_put(jnp.asarray(x), sharding) if sharding else jnp.asarray(x)

        return NodeTable(
            valid=put(self.valid),
            cpu_alloc=put(self.cpu_alloc),
            mem_alloc=put(self.mem_alloc),
            pods_alloc=put(self.pods_alloc),
            # Copies: on the CPU backend jnp.asarray takes a 64-byte
            # aligned numpy buffer zero-copy, and the device table would
            # then read the retire's in-place np.add.at on these three
            # until its first donated step, and not by its own commit.
            cpu_req=put(self.cpu_req.copy()),
            mem_req=put(self.mem_req.copy()),
            pods_req=put(self.pods_req.copy()),
            label_key=put(self.label_key),
            label_val=put(self.label_val),
            label_num=put(self.label_num),
            taint_id=put(self.taint_id),
            taint_effect=put(self.taint_effect),
            zone=put(self.zone),
            region=put(self.region),
            name_id=put(self.name_id),
        )


class RowVersions:
    """Monotone per-row mutation journal: the dirty bookkeeping that
    feeds the delta-plane cache's invalidation (engine/deltacache.py).

    Every batch of device-table row mutations — dirty-row scatters,
    retired bind commits, eviction repairs — is noted here with one
    version stamp; a consumer holding per-row derived state (a cached
    feasibility/score plane) records the version it was computed at and
    asks ``rows_since(v)`` for exactly the rows that moved afterwards.
    The journal is bounded: when it outgrows ``cap`` the oldest entries
    compact away and ``floor`` rises — a consumer whose recorded
    version sits below ``floor`` can no longer enumerate its delta and
    must treat its state as wholly stale (recompute, don't guess).
    That is the fail-closed direction: compaction can only ever force
    extra recompute, never hide a moved row.
    """

    def __init__(self, cap: int = 1 << 16) -> None:
        self.cap = cap
        self.ver = 0
        # Versions below this are compacted out of the journal: a
        # consumer stamped older than floor cannot enumerate its delta.
        self.floor = 0
        self._journal: collections.deque[tuple[int, int]] = (
            collections.deque()
        )

    def note(self, rows) -> int:
        """Stamp one mutation batch; returns the new version."""
        self.ver += 1
        v = self.ver
        self._journal.extend((v, int(r)) for r in rows)
        if len(self._journal) > self.cap:
            self.compact(keep=self.cap // 2)
        return v

    def compact(self, keep: int) -> None:
        """Drop the oldest entries down to ``keep``, raising ``floor``
        to the newest dropped version (consumers below it go stale)."""
        q = self._journal
        while len(q) > keep:
            v, _ = q.popleft()
            self.floor = max(self.floor, v)

    def release(self, before_ver: int) -> None:
        """Drop entries at versions < ``before_ver`` WITHOUT staling
        consumers at or past it (the caller proved every live consumer
        is stamped >= before_ver)."""
        q = self._journal
        while q and q[0][0] < before_ver:
            q.popleft()
        self.floor = max(self.floor, before_ver - 1)

    def rows_since(self, ver: int) -> set | None:
        """Rows mutated at versions > ``ver``; None when ``ver`` is
        below the compaction floor (the delta is unenumerable — treat
        everything as dirty)."""
        if ver < self.floor:
            return None
        out: set[int] = set()
        for v, r in reversed(self._journal):
            if v <= ver:
                break
            out.add(r)
        return out

    def __len__(self) -> int:
        return len(self._journal)


# ---- jit-side mutation ----------------------------------------------------


def commit_binds(
    table: NodeTable,
    node_idx: jax.Array,   # i32[B] row of the node each pod bound to (or any row if invalid)
    cpu_milli: jax.Array,  # i32[B]
    mem_kib: jax.Array,    # i32[B]
    bound: jax.Array,      # bool[B] — pod actually bound this cycle
) -> NodeTable:
    """Fold this batch's bind decisions into requested-resources.

    The reference achieves the same feedback through the scheduler cache's
    AssumePod immediately after Permit (the bind write to the apiserver is
    async); here the batch commit *is* the assume step.
    """
    cpu = jnp.where(bound, cpu_milli, 0)
    mem = jnp.where(bound, mem_kib, 0)
    one = jnp.where(bound, 1, 0).astype(jnp.int32)
    return table.replace(
        cpu_req=table.cpu_req.at[node_idx].add(cpu),
        mem_req=table.mem_req.at[node_idx].add(mem),
        pods_req=table.pods_req.at[node_idx].add(one),
    )


def apply_delta(table: NodeTable, rows: jax.Array, delta: NodeTable) -> NodeTable:
    """Scatter a batch of changed rows (host-compiled) into the device table.

    ``delta`` holds D rows of freshly-compiled node features; ``rows`` are
    their destinations.  This is the device half of the coordinator's
    revision-ordered informer stream.
    """
    return jax.tree.map(lambda t, d: t.at[rows].set(d), table, delta)


# Column split for the coordinator's dirty-row scatters: capacity/feature
# columns carry what the node object says (host always authoritative);
# the request columns carry bind accounting, which in a pipelined
# coordinator includes in-flight assumes the host mirror does not know
# yet.  A capacity-only node update therefore scatters CAP_COLUMNS alone,
# leaving the device's running request totals (the assume chain) intact.
CAP_COLUMNS = (
    "valid", "cpu_alloc", "mem_alloc", "pods_alloc",
    "label_key", "label_val", "label_num",
    "taint_id", "taint_effect", "zone", "region", "name_id",
)
REQ_COLUMNS = ("cpu_req", "mem_req", "pods_req")
ALL_COLUMNS = CAP_COLUMNS + REQ_COLUMNS


def scatter_rows(table: NodeTable, rows, delta: dict) -> NodeTable:
    """Scatter per-column host values into ``rows`` of the device table
    (the keys of ``delta`` select the columns — see CAP_COLUMNS)."""
    updates = {
        name: getattr(table, name).at[rows].set(arr)
        for name, arr in delta.items()
    }
    return table.replace(**updates)
