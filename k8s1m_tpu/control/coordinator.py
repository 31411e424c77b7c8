"""The host coordinator: store watches -> snapshot deltas -> TPU cycle -> binds.

This is the process the reference runs as dist-scheduler (289 replicas of
it): watch nodes and pods, keep a node cache current, schedule pending
pods, write binds back (reference SURVEY.md §3.2).  Here one coordinator
drives the whole cluster:

- **Intake** — a store watch on /registry/pods/ replaces both intake paths
  of the reference (the ValidatingWebhook and the fieldSelector pod watch,
  reference pkg/webhook/webhook.go:71-126, cmd/dist-scheduler/pod_watcher.go:20-71):
  every Pending pod with schedulerName=dist-scheduler enters the queue.
- **Node cache** — a watch on /registry/minions/ streams adds/updates/
  removes into NodeTableHost and scatters compiled rows to the device
  table (the informer-cache equivalent, reference scheduler.go:201-219).
  Bound-pod resource accounting is folded in the same way a scheduler
  cache assumes pods.
- **Cycle** — pending pods are drained in batches of PodSpec.batch, padded,
  encoded, and run through engine.schedule_batch; winners are written back
  as spec.nodeName via Txn CAS on the pod's mod revision — the optimistic
  concurrency of the reference's DefaultBinder (conflict -> pod re-queued,
  reference README.adoc:558-560).
- **Ordering** — watch events are applied in revision order (the native
  store's watch dispatch is revision-ordered by construction, like
  mem_etcd's notify thread, reference store.rs:444-533), and binds are
  CAS-guarded, so a concurrent pod update between intake and bind loses
  nothing: the CAS fails and the newer pod revision re-enters via watch.

A pod whose bind CAS fails or that finds no feasible node is re-queued
under the ``coordinator.bind`` RetryPolicy (k8s1m_tpu/faultline/policy.py):
capped exponential backoff with jitter, then parked as unschedulable
after ``max_attempts`` tries (the reference admits first-attempt failures
are not reliably retried, reference RUNNING.adoc:206 — this does better).
Backoff means a CAS-conflict storm surfaces as queue backpressure (pods
waiting out their delay) instead of the same pods tight-looping through
every consecutive wave.  The bind and watch-drain paths are faultline
injection hooks (components ``coordinator.bind`` / ``coordinator.watch``),
so conflict storms and watch loss are reproducible by seed.

**Overload control** (k8s1m_tpu/loadshed, opt-in via the ``loadshed`` /
``breaker`` constructor args): a HealthController ticked once per cycle
turns queue/backoff depth, conflict rate, cycle latency and resyncs
into HEALTHY/DEGRADED/SHEDDING; DEGRADED shrinks the score window and
drops constraint *scoring* (filtering always stays) and widens batch
windows, SHEDDING additionally makes ``submit_external`` reject
lowest-priority pods first; a CircuitBreaker around device dispatch
falls back to the host-side oracle scheduler while open, so scheduling
never fully stops (see tools/overload_drill.py for the drill that
proves all of it).

**Snapshot epochs & quiesce-free pipelining**: node churn no longer
retires the pipeline.  Node events classify at the row level
(_drain_node_events): capacity-only updates scatter feature columns
into the live device table between in-flight waves, structural adds
append fresh rows, and removes tombstone their row into a wave-epoch
quarantine (snapshot/node_table.py) so no in-flight wave can alias a
reused row; a wave that retires onto a tombstoned row retries the pod
like a CAS conflict.  The pipeline quiesces only for resync, a tripped
breaker, adaptive partial buckets, or quarantine exhaustion —
``pipeline_quiesce_total{reason}`` counts each, and under pure
capacity churn the structural reason stays 0 (tier-1 asserted via
``sched_bench --node-churn``).

**Host feed & encode cache** (snapshot/hotfeed.py): every encoder this
coordinator owns shares one shape-keyed template cache (invalidated by
``Vocab.generation()``), so batches full of shape-sharing pods fill in
vectorized per-shape writes rather than per-pod Python; with
``hotfeed`` on (default: follows ``pipeline``) a worker thread encodes
the NEXT full batch while the current wave is in flight and the
dispatch claims the pre-staged ``PackedPodBatch`` — discarded, never
trusted, if the queue prefix or the vocab generation moved
(``hotfeed_stale_batches_total{reason}``).  The degraded loadshed path
and ``_process_adjusts`` re-encodes ride the same cache, so CAS-
rollback storms re-encode against warm templates.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import heapq
import itertools
import json
import logging
import operator
import random
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from k8s1m_tpu import faultline
from k8s1m_tpu.config import DEFAULT_SCHEDULER, PodSpec, TableSpec
from k8s1m_tpu.faultline import RetryPolicy, note_give_up, note_retry, policy_for
from k8s1m_tpu.lint import THREAD_OWNER, guarded_by, racy_read
from k8s1m_tpu.control.objects import (
    bind_pod_constraints,
    decode_node,
    decode_pod,
    decode_pod_fast,
    decode_pod_obj,
    decode_pod_shape,
    node_key,
    pod_affinity_of,
    pod_key,
    pod_key_str_of_obj,
)
from k8s1m_tpu.engine.assign import SETTLED_BY, UNBOUND_REASONS
from k8s1m_tpu.engine.cycle import (
    Wave,
    adjust_constraints,
    adjust_constraints_impl,
    candidates_kernel,
    commit_fields_np,
    sample_offset_for,
    sample_rows_for,
    schedule_batch_packed,
)
from k8s1m_tpu.loadshed import CircuitBreaker, HealthController, Signals
from k8s1m_tpu.loadshed import CLOSED as BREAKER_CLOSED
from k8s1m_tpu.loadshed.breaker import FALLBACK_BINDS
from k8s1m_tpu.obs import gcspan
from k8s1m_tpu.obs.metrics import Counter, Gauge, Histogram, LevelTimer
from k8s1m_tpu.obs.podtrace import NULL_TRACER
from k8s1m_tpu.ops.priority import pod_priority_of
from k8s1m_tpu.oracle import oracle_feasible, oracle_score
from k8s1m_tpu.plugins.registry import Profile, degraded_profile
from k8s1m_tpu.snapshot.constraints import ConstraintTracker, empty_constraints
from k8s1m_tpu.snapshot.hotfeed import (
    EncodeCache,
    HostFeed,
    HotPodBatchHost,
    ShardedHostFeed,
    cache_counts,
    encode_batch,
    fingerprint,
    widen_packed,
)
from k8s1m_tpu.snapshot.node_table import (
    ALL_COLUMNS,
    CAP_COLUMNS,
    NodeTableHost,
    RowsExhausted,
    scatter_rows,
)
from k8s1m_tpu.snapshot.packing import (
    PackingOverflow,
    build_packing_spec,
    donation_inplace,
    donation_probe,
    hbm_bytes,
    is_packed,
    pack_row_delta,
    pack_table_host,
    resolve_packing,
)
from k8s1m_tpu.snapshot.pod_encoding import PodBatchHost, PodInfo
from k8s1m_tpu.tenancy.gang import note_gang
from k8s1m_tpu.tenancy.policy import (
    gang_of_labels,
    tenant_of_key,
    tenant_of_obj,
    tenant_of_pod,
    tenant_override,
)
from k8s1m_tpu.tenancy.preempt import (
    Victim,
    note_eviction,
    select_preemption,
)
from k8s1m_tpu.snapshot.bulkload import BulkNodeLoader
from k8s1m_tpu.store.native import (
    BIND_INVALID,
    POD_CANONICAL,
    POD_HAS_NODE,
    POD_SCHED_MATCH,
    CompactedError,
    FutureRevError,
    MemStore,
    Watcher,
    drain_events_light,
    list_prefix,
    list_prefix_sharded,
    list_prefix_values,
    prefix_end,
)

log = logging.getLogger("k8s1m.coordinator")

NODES_PREFIX = b"/registry/minions/"
# Tick-driven consumers drain once per cycle, so the watch queue must
# absorb a full inter-cycle burst (creates + deletes + bind echoes);
# the native default of 10K (reference store.rs:27) assumes a
# continuously-draining consumer.
DEEP_WATCH_QUEUE = 1 << 20
PODS_PREFIX = b"/registry/pods/"

_PODS_SCHEDULED = Counter(
    "coordinator_pods_scheduled_total", "Pods bound, by outcome", ("outcome",)
)
_DECODE_ERRORS = Counter(
    "coordinator_decode_errors_total", "Objects that failed to decode", ("kind",)
)
# Counted where the intake forks (_apply_pod_batch, _on_pod_put), one
# inc per lane per batch: batch_fast = a whole poll of canonical pending
# pods taken column-wise, labels, tolerations and spread constraints by
# their interned shape;
# canonical = per event, parsed natively (shaped or not); decode_fast /
# json = a put the native parser did not take (or a watcher without
# poll_pods) through decode_pod_fast or json.loads + decode_pod_obj;
# echo = a non-canonical bind echo known by its key, not decoded; delete.
_POD_INTAKE = Counter(
    "coordinator_pod_intake_total",
    "Pod watch/list events applied, by intake lane", ("lane",),
)
# Once per wave (_bind_wave): columnar = pods bound wholly in columns
# (a fast-lane record whose batch CAS won), per_pod = every other pod
# the device gave a row (decoded or webhook pods, CAS losses, rows
# tombstoned in flight, any pod under a fault plan).
_BIND_RETIRE = Counter(
    "coordinator_bind_retire_total",
    "Pods that reached a wave's bind stage, by the lane that retired them",
    ("lane",),
)
# Once per wave (_complete), from four sums the device step returns with
# the wave's rows; only a wave that counts skew (in_wave_skew) or runs the
# inter-pod gate (engine/cycle.has_interpod_gate) counts them.
# capacity = every candidate that was legal had lost its room to earlier
# pods of the wave; skew = it had candidates, and none in a zone its
# spread constraints allowed at its turn; no_candidate = the candidates
# stage found no feasible row; interpod = every candidate with room lay in
# a domain an earlier pod of the wave had closed to it (required
# anti-affinity, its symmetry, a bootstrapping affinity term).  A pod
# counted here goes on to _retry.
_WAVE_UNBOUND = Counter(
    "coordinator_wave_unbound_total",
    "Valid pods a wave left unbound, by why", ("reason",),
)
# Once per wave (_complete), from the three sums every device step returns
# with the wave's rows (engine/assign.greedy_assign): the wave's valid
# pods by what settled their choice -- rounds = the parallel conflict
# rounds, scan = the sequential step (the tail the rounds left, or the
# whole of a wave that counts skew) -- and how often the rounds evaluated
# the whole wave (a wave without contention: once).
_ASSIGN_PODS = Counter(
    "coordinator_assign_pods_total",
    "Valid pods of dispatched waves, by what settled their choice among "
    "their candidates", ("path",),
)
_ASSIGN_ROUNDS = Counter(
    "coordinator_assign_rounds_total",
    "Evaluations of a whole wave's conflicts by the parallel rounds", (),
)
# Once per dispatched wave (_launch): the candidates kernel its step was
# built with, as engine/cycle.candidates_kernel names it from the wave's
# packed field groups -- on the pallas backend the name the kernel has in
# HLO and in a device trace (fused_topk, fused_topk_affinity,
# fused_topk_constraints, ...).
_WAVES = Counter(
    "coordinator_waves_total",
    "Waves dispatched to the device, by the candidates kernel of their step",
    ("kernel",),
)
# Once per frame, never per pod: pods taken natively over `interned` is
# the shape table's hit share, over `bound` the binding cache's.
_POD_SHAPES = Counter(
    "coordinator_pod_shapes_total",
    "Distinct (label map, toleration list, spread constraints) byte spans "
    "of natively parsed pods decoded into the intake's shape table "
    "(interned), resets of the full table (evicted), and shapes bound to "
    "a namespace and a state of the constraint tracker (bound)",
    ("event",),
)
# Bound of the shape table, like _gang_oversize's: a stream of unique
# label sets degrades to one decode a pod (the JSON lane's cost), and
# clearing only re-decodes a live template once more.
POD_SHAPES_MAX = 4096
_CYCLE_TIME = Histogram(
    "coordinator_cycle_seconds", "Scheduling cycle latency by stage", ("stage",)
)
_QUEUE_DEPTH = Gauge("coordinator_queue_depth", "Pending pods queued", ())
_BACKOFF_DEPTH = Gauge(
    "coordinator_backoff_depth",
    "Pods waiting out a retry backoff (conflict-storm backpressure)", (),
)
_RESYNCS = Counter(
    "coordinator_resyncs_total", "Full relist+rewatch recoveries", ()
)
_NODE_COUNT = Gauge("coordinator_node_count", "Nodes in the snapshot", ())
_COLD_BUILD = Gauge(
    "megarow_cold_build_seconds",
    "Wall seconds of the last store->watch->table cold build "
    "(bootstrap's node relist + bulk ingest + device table build) — "
    "a first-class metric so a 1M-row build is a number, not a silent "
    "multi-minute stall", (),
)
# All live coordinators in this process; gauges aggregate over them so a
# discarded instance neither pins memory nor clobbers the live one's stats.
# Scrape-thread reads of cycle-thread-owned state go through racy_read:
# a deliberate, audited-as-exempt torn-snapshot read (a monitoring len()
# must neither block on the cycle nor count as a discipline violation).
# Follower mirrors (warm standby, control/leader.py) shadow the leader's
# whole intake — summing them would double every depth, so the
# aggregates skip them; the standby's own health is standby_mirror_lag.
_LIVE: weakref.WeakSet = weakref.WeakSet()


def _live_primaries():
    return (c for c in _LIVE if not racy_read(c, "_follower"))


_NODE_COUNT.set_function(
    lambda: sum(len(racy_read(c.host, "_row_of")) for c in _live_primaries())
)
_QUEUE_DEPTH.set_function(
    lambda: sum(len(racy_read(c, "queue")) for c in _live_primaries())
)
_BACKOFF_DEPTH.set_function(
    lambda: sum(len(racy_read(c, "_backoff")) for c in _live_primaries())
)

_PIPE_QUIESCE = Counter(
    "pipeline_quiesce_total",
    "Forced full pipeline retires, by reason (capacity-only node churn "
    "never quiesces; structural = free-row quarantine exhausted)",
    ("reason",),
)
_PIPE_DEPTH = Gauge(
    "pipeline_inflight_depth", "Device waves currently in flight", ()
)
_PIPE_DEPTH.set_function(
    lambda: sum(len(racy_read(c, "_inflights")) for c in _LIVE)
)
_PIPE_OVERLAP = Counter(
    "pipeline_stage_overlap_seconds_total",
    "Host-stage seconds split by whether device waves were in flight "
    "(inflight=yes means the stage's cost hid behind device work)",
    ("stage", "inflight"),
)
# Stages instrumented with the overlap split (drives the bench's
# overlap-ratio report; keep in sync with _stage call sites).
_OVERLAP_STAGES = ("drain", "encode", "sync", "sync_out", "bind")

# ---- mesh execution (parallel/): the dp x sp sharded cycle ------------
_MESH_DEVICES = Gauge(
    "mesh_devices",
    "Devices along each mesh axis across live mesh coordinators "
    "(0 = every coordinator runs single-device)",
    ("axis",),
)
for _axis in ("dp", "sp"):
    _MESH_DEVICES.set_function(
        lambda _a=_axis: sum(
            c.mesh.shape[_a] for c in _LIVE if c.mesh is not None
        ),
        axis=_axis,
    )
_MESH_SCATTER = Counter(
    "mesh_sharded_scatter_total",
    "Dirty-row scatters dispatched against the sp-sharded device table, "
    "by column class (full = host-authoritative row re-upload, cap = "
    "capacity/feature columns only) — each one lands mid-flight with no "
    "quiesce and no reshard (make_sharded_scatter pins the row sharding)",
    ("cols",),
)
# Once per wave dispatched to a mesh (_launch): how its step merged the
# node shards' candidates (engine/cycle.merge_shards) -- zone = zone id by
# zone id, the best of the shards' rows in each (in_wave_skew); topk = the
# k best of all.
_MESH_WAVES = Counter(
    "mesh_waves_total",
    "Waves dispatched to the device mesh, by how their step merged the "
    "node shards' candidates",
    ("merge",),
)
_MESH_FEED_DEPTH = Gauge(
    "mesh_feed_staged_depth",
    "Batches staged or encoding across per-dp-shard host feeds "
    "(snapshot/hotfeed.ShardedHostFeed; up to dp per mesh coordinator)",
    (),
)
_MESH_FEED_DEPTH.set_function(
    lambda: sum(
        c._feed.depth() for c in _LIVE
        if isinstance(getattr(c, "_feed", None), ShardedHostFeed)
    )
)

# ---- device memory (devicestate): packed snapshot + donation evidence --
_TABLE_BYTES = Gauge(
    "device_table_bytes",
    "HBM bytes of the device node table by layout (snapshot/packing.py; "
    "the packed production layout holds the cold columns bit/byte-packed "
    "so more nodes fit per chip)",
    ("layout",),
)
_DONATION = Counter(
    "commit_donation_total",
    "Per-wave table commits through the donating executable, split by "
    "whether the runtime honored the donation in place (inplace=no means "
    "the buffers were copied — e.g. another live reference pinned them)",
    ("inplace",),
)
_PACKING_FALLBACK = Counter(
    "device_packing_fallback_total",
    "Fail-closed packed-layout rebuilds, by reason (the field that "
    "overflowed its static bit budget — vocab drift — or 'taint_slots' "
    "for a spec the meta word cannot hold); the coordinator widens the "
    "layout ONCE, host-side and mesh-global, never truncates and never "
    "decides per-shard",
    ("reason",),
)

# ---- failover (ISSUE 9): fencing + warm-standby evidence ---------------
_FENCE_REJECTED = Counter(
    "fencing_rejected_total",
    "Store writes refused by the lease-epoch fence, by path — a deposed "
    "or paused reign's in-flight waves draining to requeue instead of "
    "the store (control/leader.LeaseFence)",
    ("path",),
)
_MIRROR_LAG = Gauge(
    "standby_mirror_lag_rows",
    "Watch events the warm-standby mirror had not yet applied at its "
    "last follow tick (0 = the mirror tracks the store tick-for-tick; "
    "bounds the takeover reconcile)",
    (),
)
_RECONCILE_REPAIRS = Counter(
    "failover_reconcile_repairs_total",
    "Mirror-vs-store divergences repaired during takeover reconcile, by "
    "kind (normally 0: the watch stream already carried every fact)",
    ("kind",),
)

_BIND_LATENCY = Histogram(
    "coordinator_schedule_to_bind_seconds",
    "Intake-to-bind latency per pod",
    (),
    # Finer than the default pow2 ladder in the SLO range: the default's
    # 164ms -> 328ms jump makes a ~170ms p50 report as 328.
    buckets=(
        0.005, 0.01, 0.02, 0.03, 0.04, 0.05, 0.06, 0.07, 0.08, 0.09,
        0.1, 0.11, 0.13, 0.165, 0.2, 0.25, 0.33, 0.42, 0.55, 0.7, 0.9,
        1.2, 1.6, 2.1, 2.8, 3.7, 5.0, 8.0, 15.0, 30.0, 60.0,
    ),
)


def _constraintful(pod: "PodInfo | PodShape") -> bool:
    """Whether a bound pod's record keeps what a later delete takes its
    increments back with (slot 5 of ``_bound``: a fast-lane pod's bound
    PodShape, any other pod's PodInfo)."""
    return bool(
        pod.spread_incs
        or pod.ipa_incs
        or any(r.required and r.anti for r in pod.affinity_refs)
    )


class PodShape:
    """What the pods of one template share, decoded once per distinct
    quintuple of byte spans of the native parser's frame (label map,
    nodeSelector, toleration list, affinity, spread constraints) and not
    once per pod: exactly what the JSON lane's PodInfo would hold of it.
    As decoded it is bound to no tracker; ``bind`` gives the shape the
    pods of one namespace refer to, with the constraint slots and
    increments the JSON lane would have set on each of them.  Immutable
    after construction (the hotfeed worker reads it)."""

    __slots__ = ("labels", "node_selector", "tolerations", "required_terms",
                 "preferred_terms", "affinity", "topology_spread",
                 "scheduler_name", "spread_refs", "affinity_refs",
                 "spread_incs", "ipa_incs", "fp", "keeps",
                 "registers", "gang", "tenant")

    def __init__(self, labels: dict, tolerations: list, topology_spread: list,
                 scheduler_name: str, *, node_selector: dict | None = None,
                 required_terms: list | None = None,
                 preferred_terms: list | None = None,
                 affinity: dict | None = None, spread_refs: tuple = (),
                 affinity_refs: tuple = (), spread_incs: tuple = (),
                 ipa_incs: tuple = ()) -> None:
        self.labels = labels
        self.node_selector = node_selector or {}
        self.tolerations = tolerations
        self.required_terms = required_terms or []
        self.preferred_terms = preferred_terms or []
        # Raw spec.affinity: its podAffinity / podAntiAffinity terms are
        # interned per namespace (bind); nodeAffinity is the two lists
        # above.
        self.affinity = affinity or {}
        self.topology_spread = topology_spread
        self.scheduler_name = scheduler_name
        self.spread_refs = spread_refs
        self.affinity_refs = affinity_refs
        self.spread_incs = spread_incs
        self.ipa_incs = ipa_incs
        # hotfeed.fingerprint of every pod that refers to this shape
        # (labels are not structural; PLAIN for a shape of labels alone).
        self.fp = fingerprint(self.pod("/", 0, 0))
        self.keeps = _constraintful(self)
        # Binding it registers constraints of its own with the tracker.
        self.registers = bool(
            topology_spread or "podAffinity" in self.affinity
            or "podAntiAffinity" in self.affinity
        )
        # Whether the labels name a gang (tenancy/policy.gang_of_labels).
        self.gang = gang_of_labels(labels, "") is not None
        # The tenant label's override, None = the namespace is the tenant.
        self.tenant = tenant_override(labels)

    def pod(self, key_str: str, cpu_milli: int, mem_kib: int,
            node_name: str | None = None) -> PodInfo:
        ns, name = key_str.split("/", 1)
        return PodInfo(
            name=name, namespace=ns, cpu_milli=cpu_milli, mem_kib=mem_kib,
            scheduler_name=self.scheduler_name, node_name=node_name,
            node_selector=dict(self.node_selector),
            tolerations=list(self.tolerations),
            required_terms=list(self.required_terms),
            preferred_terms=list(self.preferred_terms),
            labels=dict(self.labels),
            topology_spread=list(self.topology_spread),
            pod_affinity=pod_affinity_of(self.affinity),
            spread_refs=list(self.spread_refs),
            affinity_refs=list(self.affinity_refs),
            spread_incs=list(self.spread_incs), ipa_incs=list(self.ipa_incs),
        )

    def _binding(self) -> tuple:
        """What binding to a tracker sets."""
        return (self.spread_refs, self.affinity_refs, self.spread_incs,
                self.ipa_incs)

    def bind(self, namespace: str, tracker: ConstraintTracker) -> "PodShape":
        """The shape of this template's pods in ``namespace`` as
        ``tracker`` stands once they have registered their constraints:
        the JSON lane's own code on one pod of it (this shape itself
        where that sets nothing).  Raises what decode_pod_obj raises."""
        pod = PodInfo(
            name="", namespace=namespace, labels=self.labels,
            topology_spread=self.topology_spread,
        )
        bind_pod_constraints(pod, tracker, self.affinity)
        bound = (tuple(pod.spread_refs), tuple(pod.affinity_refs),
                 tuple(pod.spread_incs), tuple(pod.ipa_incs))
        if not any(bound):
            return self
        return PodShape(
            self.labels, self.tolerations, self.topology_spread,
            self.scheduler_name, node_selector=self.node_selector,
            required_terms=self.required_terms,
            preferred_terms=self.preferred_terms, affinity=self.affinity,
            spread_refs=bound[0], affinity_refs=bound[1],
            spread_incs=bound[2], ipa_incs=bound[3],
        )

    def same_binding(self, other) -> bool:
        """Whether ``other`` is a shape that says of the tracker what
        this one says (two bindings of one template)."""
        return isinstance(other, PodShape) and (
            self._binding() == other._binding()
        )


@dataclasses.dataclass(slots=True)
class PendingPod:
    # None = native-intake fast lane: the pod is canonical
    # (store/native.py poll_pods parsed it in C) and what it holds beyond
    # its scalars is its ``shape``, so the full PodInfo is materialized
    # only if a slow path actually needs it (ensure_pod).
    pod: PodInfo | None
    # None = webhook intake: the object wasn't persisted at admission
    # time, so the bind path resolves the live revision instead.
    mod_revision: int | None
    enqueued_at: float
    # Scheduling-relevant scalars, always populated (from the native
    # parse or from the PodInfo) so the hot bind path never touches pod.
    cpu_milli: int = 0
    mem_kib: int = 0
    key_str: str = ""        # "<ns>/<name>"
    attempts: int = 0
    # Raw stored bytes at intake revision — lets the bind CAS splice
    # nodeName into the bytes without a JSON decode/encode round trip.
    raw: bytes | None = None
    # Store key bytes, captured at intake so the bind wave never
    # re-formats /registry/pods/<ns>/<name> per pod.
    key_bytes: bytes = b""
    # Earliest perf_counter() time this pod may re-enter a batch after a
    # retry (RetryPolicy backoff; 0 = immediately eligible).
    not_before: float = 0.0
    # spec.priority — admission/preemption only (never encoded).  0 for
    # native fast-lane pods: the canonical shape cannot carry a
    # priority, so the hot path needs no decode to know it.
    priority: int = 0
    # Gang membership (tenancy/gang.py): namespace-qualified gang id and
    # declared size; "" / 0 = not a gang pod.
    gang_id: str = ""
    gang_size: int = 0
    # What a native fast-lane pod holds beyond its scalars — labels,
    # selectors, tolerations, affinity, spread constraints and the
    # tracker's slots and increments — interned per template and
    # namespace (Coordinator._bound_shape);
    # None = it has none of them.  Read only while ``pod`` is None: a
    # materialized or re-decoded PodInfo supersedes it.
    shape: PodShape | None = None

    def peek_pod(self) -> PodInfo:
        """The PodInfo WITHOUT caching it on the record — the hotfeed
        worker's form (a peeked pod still belongs to the cycle thread's
        queue; assigning ``self.pod`` there would be a cross-thread
        write on shared state)."""
        if self.pod is not None:
            return self.pod
        if self.shape is not None:
            return self.shape.pod(self.key_str, self.cpu_milli, self.mem_kib)
        ns, name = self.key_str.split("/", 1)
        return PodInfo(
            name=name, namespace=ns,
            cpu_milli=self.cpu_milli, mem_kib=self.mem_kib,
        )

    def ensure_pod(self) -> PodInfo:
        if self.pod is None:
            self.pod = self.peek_pod()
        return self.pod


# A wave's records as columns (_bind_columns): one pass in C a column,
# which is cheaper than one pass over all ten and a transpose.
_WAVE_COLS = tuple(map(operator.attrgetter, (
    "key_bytes", "mod_revision", "cpu_milli", "mem_kib", "key_str",
    "enqueued_at", "priority", "gang_id", "pod", "shape",
)))


def _is_none(col) -> np.ndarray:
    """bool[len(col)]: which entries of a column are None."""
    return np.fromiter(
        map(operator.is_, col, itertools.repeat(None)), bool, len(col)
    )


def _picker(mask: np.ndarray):
    """``pick(col)``: the column's entries under the mask, in wave order
    (the column itself where the mask takes all of it)."""
    if mask.all():
        return lambda col: col
    flags = mask.tolist()
    return lambda col: list(itertools.compress(col, flags))


def _wave_tenants(key_strs, shapes) -> list[str]:
    """Tenant of each fast-lane record (no PodInfo) of a wave: its
    shape's where the labels name one, else the namespace of its key —
    one lookup per distinct namespace of the wave, not one per pod."""
    n = len(key_strs)
    first = key_strs[0]
    cut = first.find("/")
    if cut >= 0 and all(
        map(str.startswith, key_strs, itertools.repeat(first[: cut + 1]))
    ):
        tenants = [tenant_of_key(first)] * n
    else:
        spaces = list(map(
            operator.itemgetter(0),
            map(str.partition, key_strs, itertools.repeat("/")),
        ))
        of_space = {ns: tenant_of_key(ns) for ns in set(spaces)}
        tenants = list(map(of_space.__getitem__, spaces))
    named = {
        sh: sh.tenant for sh in set(shapes) if sh is not None and sh.tenant
    }
    if named:
        own = list(map(named.get, shapes))
        tenants = np.where(
            _is_none(own),
            np.fromiter(tenants, object, n), np.fromiter(own, object, n),
        ).tolist()
    return tenants


# Structural splice marker: encode_pod always opens spec with
# schedulerName, and this byte pattern cannot occur inside any JSON
# string literal (the quotes would be \"-escaped), so its first
# occurrence is the real spec object.
_SPEC_MARK = b'"spec":{"schedulerName":'


def splice_node_name(raw: bytes, node_name: str) -> bytes | None:
    """Insert spec.nodeName into encoded pod bytes; None if the object
    isn't in our canonical shape (caller falls back to the JSON path)."""
    idx = raw.find(_SPEC_MARK)
    if idx < 0 or b'"nodeName"' in raw:
        return None
    cut = idx + 8  # len(b'"spec":{')
    return b'%s"nodeName":%s,%s' % (
        raw[:cut], json.dumps(node_name).encode(), raw[cut:]
    )


_UNSPLICE_MARK = b'"spec":{"nodeName":"'


def unsplice_node_name(raw: bytes) -> bytes | None:
    """Inverse of ``splice_node_name``: remove the spliced spec.nodeName,
    restoring the pre-bind bytes EXACTLY — the eviction path's byte-
    identity half (an evicted pod's stored object equals its pre-bind
    encoding, so evict+rebind replays are bytewise checkable).  None if
    the object isn't in the spliced canonical shape (escaped name,
    nodeName written elsewhere) — the caller falls back to the JSON
    path."""
    idx = raw.find(_UNSPLICE_MARK)
    if idx < 0:
        return None
    start = idx + 8                    # keep b'"spec":{'
    i = idx + len(_UNSPLICE_MARK)      # first byte of the name
    j = raw.find(b'"', i)
    if j < 0 or raw[j + 1 : j + 2] != b"," or b"\\" in raw[i:j]:
        return None
    return raw[:start] + raw[j + 2:]


class _VictimRows:
    """Row-keyed view over the coordinator's incremental by-node victim
    index — the ``victims_by_row`` mapping ``select_preemption``
    consumes, built per wave in O(nodes-with-victims) instead of the
    old O(bound pods) ledger scan.

    Only the row -> node-name resolution is materialized up front
    (ints; victims whose node left the snapshot drop out exactly like
    the old scan's ``row_of.get``).  ``get`` reads the live per-node
    dict fresh on every call, so evictions during the same wave
    (``_evict_bound`` pops the index) are visible to later preemptors
    with no manual bookkeeping; rows are patched into the returned
    Victims for the replay log's benefit.
    """

    __slots__ = ("_by_node", "_name_at", "_max_seq")

    def __init__(self, by_node: dict, row_of: dict, max_seq: int) -> None:
        self._by_node = by_node
        self._name_at = {
            row_of[name]: name for name in by_node if name in row_of
        }
        # Bind-sequence fence: only pods bound BEFORE this view was
        # built are victims.  Without it, a preemptor's own host-side
        # bind (inserted live into the by-node index) would be visible
        # to later preemptors of the SAME wave — same-wave eviction
        # thrash the old snapshot index structurally excluded.
        self._max_seq = max_seq

    def get(self, row: int, default=()):
        name = self._name_at.get(row)
        if name is None:
            return default
        d = self._by_node.get(name)
        if not d:
            return default
        out = [
            dataclasses.replace(v, row=row)
            for v in d.values() if v.seq <= self._max_seq
        ]
        return out or default

    def items(self):
        """Materialized (row, victims) pairs — the replay-log dump."""
        return [(row, self.get(row)) for row in sorted(self._name_at)]

    def values(self):
        return [vs for _row, vs in self.items()]

    def __eq__(self, other):
        # Dict-shaped for consumers (and tests) that compare against
        # the materialized per-row index.
        if isinstance(other, (dict, _VictimRows)):
            return dict(self.items()) == (
                other if isinstance(other, dict) else dict(other.items())
            )
        return NotImplemented

    __hash__ = None


def window_rows_of(used_rows: int, table_rows: int, chunk: int, rows: int) -> int:
    """The rows a sampled wave's window rotates over: those that have ever
    held a node (the host table's high-water mark, rounded up to whole
    chunks), not the table's capacity -- a window over rows no node has
    had finds no candidate and sends its whole wave back.  At least one
    window, at most the table.  (Upstream's percentageOfNodesToScore walks
    the nodes of the snapshot; it has no empty slots to walk.)"""
    covered = -(-used_rows // chunk) * chunk
    return min(table_rows, max(rows, covered))


@guarded_by(
    # Webhook-thread <-> cycle-thread boundary: the staging list is the
    # ONLY coordinator state server threads may touch, and only under
    # its lock (lint/guards.py; audited by tests/test_guard_stress.py).
    _external="_external_lock",
    # Cycle-thread-confined state: the wave pipeline, the backoff heap,
    # the pod queue and the dirty-row sets all belong to whichever
    # thread drives step()/flush() — never to a server thread.
    _inflights=THREAD_OWNER,
    _backoff=THREAD_OWNER,
    queue=THREAD_OWNER,
    _queued_keys=THREAD_OWNER,
    _dirty_rows=THREAD_OWNER,
    _dirty_caps=THREAD_OWNER,
    _midflight_rows=THREAD_OWNER,
    # Tenancy state (gang staging/parking): cycle-thread-owned like the
    # queue it feeds.
    _gang_staging=THREAD_OWNER,
    _gang_parked=THREAD_OWNER,
    # The incremental preemption-victims index mirrors _bound (same
    # insert/delete sites, same cycle-thread confinement).
    _victims_by_node=THREAD_OWNER,
    # The incremental fallback NodeInfo index is maintained at the node
    # watch-drain sites (cycle-thread) and read by _fallback_nodes.
    _node_infos=THREAD_OWNER,
    _trace_gaveup=THREAD_OWNER,
)
class Coordinator:
    """Single-process scheduling coordinator over an in-process store."""

    def __init__(
        self,
        store: MemStore,
        table_spec: TableSpec,
        pod_spec: PodSpec,
        profile: Profile,
        *,
        chunk: int = 16384,
        k: int = 4,
        with_constraints: bool = True,
        max_attempts: int = 5,
        retry_policy: RetryPolicy | None = None,
        scheduler_name: str = DEFAULT_SCHEDULER,
        seed: int = 0,
        # Per-pod lifecycle tracing (obs/podtrace.py): a PodTracer
        # head-samples 1-in-N pods (deterministic by pod-key hash) and
        # records their whole journey as a contiguous span chain —
        # admit, gang staging, queue wait, encode (cache attrs),
        # dispatch wait, device (wave epoch / depth),
        # bind CAS incl. retries, preemption/eviction, failover
        # requeue.  None (the default) installs the null tracer: every
        # emit site is behind a single ``enabled`` read, so tracing off
        # is free (enforced by the trace-lazy-emit lint pass).
        tracer=None,
        backend: str = "xla",
        pipeline: bool = False,
        depth: int = 2,
        adaptive_batch: bool = False,
        watch_queue_cap: int = DEEP_WATCH_QUEUE,
        score_pct: int = 100,
        intake_filter=None,
        mesh=None,
        # Overload control (k8s1m_tpu/loadshed): a HealthController makes
        # submit_external shed past its watermarks and degrades the cycle
        # (smaller score window, filter-only constraint plugins, widened
        # batch windows) while pressure lasts; a CircuitBreaker guards
        # device dispatch and falls back to the host-side oracle
        # scheduler while open.  None (the default) = none of that runs.
        loadshed: HealthController | None = None,
        breaker: CircuitBreaker | None = None,
        # Tenancy (k8s1m_tpu/tenancy.TenancyController): weighted-fair
        # per-tenant admission at submit_external (replacing loadshed's
        # global priority floor), priority preemption (evict + requeue
        # lower-priority bound pods when a high-priority pod finds no
        # feasible row), and all-or-none gang scheduling.  When set
        # without an explicit ``loadshed``, its HealthController is
        # adopted as the loadshed controller too — one state machine
        # drives degraded knobs and per-tenant gates.
        tenancy=None,
        # Host feed (snapshot/hotfeed.py): encode batch N+1 in a worker
        # thread while batch N's wave is in flight, so encode_packed
        # leaves the cycle's serial section whenever the queue is deep
        # enough to stage a full batch ahead.  None = follow `pipeline`
        # (the overlap only pays when waves overlap host work).  The
        # shape-keyed encode CACHE is always on — it is byte-identical
        # to the uncached encode by construction (tests/test_hotfeed.py).
        hotfeed: bool | None = None,
        # Lease-epoch fencing token (control/leader.LeaseFence): when
        # set, every bind/evict/preempt store write flows through the
        # fenced helpers and is refused once the reign is deposed —
        # in-flight waves drain to requeue, never to the store.  None
        # (standalone coordinators, tests) = writes always admitted.
        fence=None,
        # Device-snapshot layout (snapshot/packing.py): "packed" holds
        # the cold node-table columns bit/byte-packed in HBM (labels
        # fused, taint effects + validity in one meta word, narrow
        # zone/region/pods planes) and decodes per chunk on device —
        # byte-identical binds, >=2x less cold-column HBM.  None is
        # "off".  Fail-closed: vocab drift past the static bit budget
        # rebuilds under a wider layout (device_packing_fallback_total)
        # — the widening decision is made ONCE on the host, so a mesh
        # coordinator never diverges per-shard.  Composes with ``mesh``
        # (meshpack): the packed planes shard over sp like the plain
        # columns and decode inside the shard-local chunk slice.
        packing: str | None = None,
        # PodTopologySpread's hard zone and region constraints counted
        # inside the wave, pod by pod in wave order (engine/assign.py),
        # so that they hold at every bind and not only between waves.
        # A pod then brings one candidate a zone id, so ``k`` is
        # ``table_spec.max_zones`` whatever was passed — which is why
        # this is a choice and not the default (the default TableSpec
        # has 512 zone ids).  On a mesh the shards' candidates then merge
        # zone by zone.  False = the wave-start counts alone decide.
        in_wave_skew: bool = False,
    ):
        self.store = store
        self.table_spec = table_spec
        self.pod_spec = pod_spec
        self.profile = profile
        self.chunk = chunk
        self.k = table_spec.max_zones if in_wave_skew else k
        # One resilience policy for the bind/requeue path; max_attempts
        # stays the constructor-level knob (it predates the policy and
        # every harness passes it), overriding the default's budget.
        self.retry_policy = dataclasses.replace(
            retry_policy or policy_for("coordinator.bind"),
            max_attempts=max_attempts,
        )
        self.max_attempts = max_attempts
        self.scheduler_name = scheduler_name
        self._tracer = tracer if tracer is not None else NULL_TRACER
        # Pods that spent their retry budget THIS wave (populated only
        # while tracing): the wave-retire pass closes their chains
        # AFTER the device/bind spans land, so an unschedulable pod's
        # final wave is attributed to device/bind, not lumped into its
        # terminal requeue span (the give-up sites run mid-bind-loop,
        # before the retire pass, and cannot stamp those spans).
        self._trace_gaveup: set[str] = set()
        self.backend = backend
        self.pipeline = pipeline
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.depth = depth
        self.watch_queue_cap = watch_queue_cap
        self._inflights: list = []
        # percentageOfNodesToScore (the reference's production config
        # scores 5% of nodes per pod at 1M scale, README.adoc:525-531;
        # terraform tfvars percentageOfNodesToScore: 5).  Each cycle
        # filters+scores one rotating chunk-aligned window of the table.
        if not 1 <= score_pct <= 100:
            raise ValueError(f"score_pct must be in [1, 100], got {score_pct}")
        # Mesh scale-out (the reference's "more replicas" axis): the node
        # table's rows shard over ``sp`` devices, the pod batch over
        # ``dp``; the device step becomes the shard_mapped
        # make_sharded_packed_step and percentageOfNodesToScore windows
        # rotate SHARD-LOCALLY (each device samples its own rows, like
        # each dist-scheduler replica samples the nodes it owns).
        # ``mesh`` accepts a built jax Mesh, a spec string ("2x4",
        # "auto", "none"), or None (single device).
        if mesh is None or isinstance(mesh, str):
            from k8s1m_tpu.parallel.mesh import resolve_mesh

            mesh = resolve_mesh(
                mesh, batch=pod_spec.batch,
                max_nodes=table_spec.max_nodes, chunk=chunk,
            )
        self.mesh = mesh
        if in_wave_skew and not with_constraints:
            raise ValueError("in_wave_skew takes with_constraints=True")
        self.in_wave_skew = in_wave_skew
        if mesh is not None:
            dp_size, sp_size = mesh.shape["dp"], mesh.shape["sp"]
            local_rows = table_spec.max_nodes // sp_size
            if local_rows * sp_size != table_spec.max_nodes:
                raise ValueError(
                    f"max_nodes {table_spec.max_nodes} not divisible by "
                    f"sp={sp_size}"
                )
            if local_rows % chunk:
                raise ValueError(
                    f"rows-per-shard {local_rows} not divisible by "
                    f"chunk {chunk}"
                )
            if pod_spec.batch % dp_size:
                raise ValueError(
                    f"batch {pod_spec.batch} not divisible by dp={dp_size}"
                )
            self._window_nodes = local_rows
        else:
            self._window_nodes = table_spec.max_nodes
        self._sample_rows = sample_rows_for(
            self._window_nodes, score_pct, chunk
        )
        self._window_i = 0
        # Overload control: degraded-mode knobs are precomputed so the
        # mode switch is a cached-executable swap, never a reconfigure
        # (warm both modes before a latency-sensitive window — each is
        # its own compiled step).
        self.tenancy = tenancy
        if tenancy is not None:
            if loadshed is None:
                loadshed = tenancy.controller
            elif loadshed is not tenancy.controller:
                # A second controller would never be ticked: its
                # _admitted_since_tick would grow forever and hard-fail
                # every admission with "cap" once it crossed queue_cap,
                # while its state stayed HEALTHY so per-tenant fairness
                # silently never engaged.
                raise ValueError(
                    "tenancy and loadshed must share one "
                    "HealthController: pass loadshed=tenancy.controller "
                    "or omit loadshed"
                )
        self.loadshed = loadshed
        self.breaker = breaker
        if loadshed is not None:
            self._sample_rows_degraded = sample_rows_for(
                self._window_nodes,
                min(score_pct, loadshed.config.degraded_score_pct),
                chunk,
            )
            self._profile_degraded = degraded_profile(profile)
        else:
            self._sample_rows_degraded = self._sample_rows
            self._profile_degraded = profile
        self._last_cycle_s = 0.0
        # Signal baselines for the per-cycle controller tick.  The
        # counters are process-global: with several live coordinators the
        # deltas mix their traffic, which only ever over-reports pressure
        # (the conservative direction for an overload signal).
        self._sig_conflicts = _PODS_SCHEDULED.value(outcome="conflict")
        self._sig_resyncs = _RESYNCS.value()
        # Breaker-open oracle fallback: decoded NodeInfo cache, generation-
        # keyed on applied node events so node churn invalidates it.
        self._fallback_cache: tuple[int, list] | None = None
        self._node_gen = 0
        # Incremental NodeInfo index under it: maintained at the watch-
        # drain decode sites (zero added decode cost — the NodeInfo is
        # already in hand there), lazily seeded from one store decode
        # for rows that arrived via the bulk ingest lane (bootstrap and
        # resync never build per-node objects), cleared on resync (the
        # bulk relist refreshes rows without decoding).  Keeps the
        # emergency path off the O(N)-per-node-gen store decode
        # (ROADMAP item 1 leftover).
        self._node_infos: dict[str, object] = {}

        # Packed snapshot mode; the PackingSpec itself is built lazily at
        # first table upload so the label-fusion fail-closed decision
        # sees the bootstrap vocab, not an empty one.
        self._packing_mode = resolve_packing(packing)
        self._packing_spec = None
        # Buffer donation: every execution path donates the table (and
        # constraint) buffers so per-wave commits are in-place in HBM —
        # the mesh executables pin their out_shardings AND donate
        # (pinning and donation compose; XLA aliases shard-by-shard).
        self._donate = True
        self._donation_inplace: bool | None = None
        self._packing_rebuilding = False

        self.host = NodeTableHost(table_spec)
        # Bulk cold-relist lane (snapshot/bulkload.py): templates and
        # the bytes->str memo persist across bootstrap and resyncs.
        self._bulk = BulkNodeLoader(self.host)
        self.tracker = ConstraintTracker(table_spec)
        # One shape-keyed template cache shared by every encoder this
        # coordinator owns (inline buckets, the feed's worker, the
        # adjust path) — templates carry no batch dimension, and cache
        # reuse across the paths is what makes a CAS-rollback storm's
        # re-encodes near-free (the shapes were all seen at intake).
        self.encode_cache = EncodeCache()
        self.encoder = HotPodBatchHost(
            pod_spec, table_spec, self.host.vocab, cache=self.encode_cache
        )
        if hotfeed is None:
            hotfeed = pipeline
        dp_shards = self.mesh.shape["dp"] if self.mesh is not None else 1
        if not hotfeed:
            self._feed = None
        elif dp_shards > 1:
            # One HostFeed per dp shard: dp workers encode the wave's
            # contiguous batch slices concurrently (sharing the one
            # template cache) and claim() merges them byte-identically
            # to the inline encode — the overlap survives sharding AND
            # the fill parallelizes like the device work it hides behind.
            self._feed = ShardedHostFeed([
                HotPodBatchHost(
                    dataclasses.replace(
                        pod_spec, batch=pod_spec.batch // dp_shards
                    ),
                    table_spec, self.host.vocab,
                    cache=self.encode_cache, path="feed",
                )
                for _ in range(dp_shards)
            ])
        else:
            self._feed = HostFeed(HotPodBatchHost(
                pod_spec, table_spec, self.host.vocab,
                cache=self.encode_cache, path="feed",
            ))
        if self._feed is not None:
            # A coordinator dropped without close() must not leak the
            # parked worker thread (the thread's bound target pins the
            # feed, encoder, and arena forever otherwise).
            weakref.finalize(self, self._feed.close)
        # Reusable scratch for _process_adjusts (allocated lazily at
        # first use; zeroed per chunk) — the per-call np.zeros were
        # measurable during rollback storms.
        self._adjust_scratch: dict | None = None
        # Adaptive batch buckets: a shallow queue schedules in a smaller
        # power-of-two batch instead of waiting out a full wave's worth
        # of padding — the lever that keeps p50 schedule-to-bind low at
        # light load while deep queues still ride the big batch.  Each
        # bucket is its own compiled executable, so this is opt-in: warm
        # EVERY bucket before a latency-sensitive window or a mid-run
        # compile (tens of seconds on TPU) lands in the tail.
        # min 64: wave cost is ~linear in B down to a small fixed floor
        # (measured round 5: 31ms at B=64 vs 82ms at B=256, 131K/pct5
        # CPU), so smaller buckets directly cut the sub-knee p50.
        self.adaptive_batch = adaptive_batch
        self.min_batch = min(64, pod_spec.batch)
        self._encoders = {pod_spec.batch: self.encoder}
        self.table = None           # device NodeTable, built lazily
        self.constraints = (
            empty_constraints(table_spec) if with_constraints else None
        )
        self._table_sharding = None
        # The field-group sets of the waves dispatched so far, by the
        # (profile, sample_rows) of their step (_built_step_for).
        self._built_groups: dict = {}
        # Dirty-row scatters donate on both paths (in-place updates);
        # the mesh override below additionally pins the row sharding.
        self._scatter = _scatter_rows_donated
        self._adjust = adjust_constraints
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            if adaptive_batch and self.min_batch % mesh.shape["dp"]:
                raise ValueError(
                    f"adaptive min batch {self.min_batch} not divisible "
                    f"by dp={mesh.shape['dp']}"
                )
            self._table_sharding = NamedSharding(mesh, P("sp"))
            # Dirty-row scatters must not let the partitioner drift the
            # table off its row sharding (a replicated output here would
            # silently serialize every later wave).
            from k8s1m_tpu.parallel.sharded_cycle import make_sharded_scatter

            self._scatter = make_sharded_scatter(self._table_sharding)
            if self.constraints is not None:
                from k8s1m_tpu.parallel.mesh import constraint_specs

                cons_shardings = jax.tree.map(
                    lambda s: NamedSharding(mesh, s),
                    constraint_specs(self.constraints),
                )
                self.constraints = jax.device_put(
                    self.constraints, cons_shardings
                )
                # Same drift guard as _scatter: out-of-step constraint
                # corrections (deletes, CAS rollbacks) must hand the
                # state back sharded, or every later wave reshards it —
                # and, like the scatter, they donate the constraint
                # buffers (the coordinator always reassigns
                # self.constraints from the return).
                self._adjust = jax.jit(
                    adjust_constraints_impl, static_argnames=("sign",),
                    donate_argnums=(0,),
                    out_shardings=cons_shardings,
                )
        self.key = jax.random.key(seed)

        self.queue: collections.deque[PendingPod] = collections.deque()
        self._queued_keys: set[str] = set()
        # Retrying pods waiting out their backoff: (not_before, seq, pod)
        # min-heap, released into the queue by _release_backoff.  Their
        # keys stay in _queued_keys so watch echoes don't re-add them.
        self._backoff: list[tuple[float, int, PendingPod]] = []
        self._backoff_seq = 0
        # Gang staging (tenancy/gang.py): gid -> (declared size, members
        # by key).  Members enter the queue contiguously only when the
        # whole gang is present; incomplete gangs hold no capacity.
        self._gang_staging: dict[str, tuple[int, dict[str, PendingPod]]] = {}
        # Gangs waiting out a whole-group retry backoff:
        # (not_before, seq, members) min-heap, released contiguously.
        self._gang_parked: list[tuple[float, int, list[PendingPod]]] = []
        self._gang_oversize: set[str] = set()
        # Binds counted in the order they were entered into _bound.
        self._bind_seq = 0
        # Incremental preemption-victims index: node name -> {pod key ->
        # Victim}, maintained at the same insert/delete sites as _bound
        # (_victims_note/_victims_drop) so victim selection never scans
        # the full bound-pod ledger per wave — the O(bound pods) scan
        # the 1M-pod shape cannot afford (ISSUE 14).  Only maintained
        # when preemption can actually run; rows resolve lazily at wave
        # time (_VictimRows) so node remove/re-add never stales it.
        self._track_victims = bool(
            tenancy is not None and tenancy.policy.preempt_enabled
        )
        self._victims_by_node: dict[str, dict[str, Victim]] = {}
        # Replayable preemption evidence (populated only when
        # tenancy.policy.log_preemptions; bounded).
        self.preempt_log: list[dict] = []
        # Seeded jitter stream so a replayed fault plan replays the same
        # backoff schedule (determinism-by-seed, faultline contract).
        self._retry_rng = random.Random(seed ^ 0xFA017)
        self._sched_bytes = scheduler_name.encode()
        self._name_bytes: list[bytes] = []
        # The five byte spans of natively parsed pods (PodEventBatch
        # .shapes) -> their PodShape (_frame_shapes); at most
        # POD_SHAPES_MAX entries.
        self._pod_shapes: dict[tuple[bytes, ...], PodShape] = {}
        # (shape, namespace) -> (the tracker's registration counts, the
        # shape bound at them) (_bound_shape).  The shape None is the
        # label-less pod's, which can still match a constraint whose
        # selector is empty: the fast lane must not lose those.
        self._bare_shape = PodShape({}, [], [], scheduler_name)
        self._shape_bindings: dict[tuple, tuple] = {}
        # Webhook-intake staging: appended from server threads, drained
        # into the queue at the top of each cycle (deque+set aren't
        # thread-safe to mutate from the handler directly).
        self._external: list[dict] = []
        self._external_lock = threading.Lock()
        # Bound-pod record per pod key: (node, cpu, mem, zone, region, keep,
        # priority, bind seq, tenant, gang id) — ONE record and one dict
        # insert a bind (the retire enters a wave's with one update).
        # ``keep`` (_constraintful) is None but for a constraint-carrying
        # pod: what decrements the count tables on deletion, its bound
        # PodShape (shared by its template) or, off the fast lane, its
        # PodInfo.  The last four are the preemption metadata, kept here
        # so victim selection never decodes stored objects: the tenant is
        # captured at bind time (the label override would otherwise be
        # lost), and a nonempty gang id marks the pod
        # unpreemptable — evicting one member would strand the rest of
        # its gang bound, the exact state gangs exist to prevent.
        self._bound: dict[str, tuple] = {}
        # Constraint-count corrections awaiting a batched device scatter:
        # (keep, node_name, zone, region, sign).  sign=+1 for externally
        # bound pods entering the snapshot, -1 for deletions.
        self._pending_adjusts: list[tuple] = []
        # Bound pods whose node is not in the snapshot yet (bootstrap
        # list/watch interleaving); accounted when the node arrives.
        self._orphan_bound: dict[str, PodInfo] = {}
        # Two dirty classes (snapshot/node_table.py column split):
        # _dirty_rows re-uploads the FULL row (host authoritative for
        # request totals too: CAS rollbacks, external binds, deletes,
        # tombstones, fresh/reused rows); _dirty_caps re-uploads only the
        # capacity/feature columns — a node update for a row the table
        # already holds — leaving the device's in-flight assume chain on
        # the request columns intact, which is what makes capacity churn
        # scatter-safe while waves are in flight.
        self._dirty_rows: set[int] = set()
        self._dirty_caps: set[int] = set()
        # Rows whose FULL scatter happened while waves were in flight:
        # the upload erased those waves' device-side assumes, so each
        # retiring wave re-dirties the rows it bound here (the host
        # mirror, which just learned the binds, repairs the device).
        # Cleared when the pipeline fully drains.
        self._midflight_rows: set[int] = set()
        # Whether the LAST node drain actually applied anything — the
        # pending probe for watcher types without a cheap .pending.
        self._last_node_drain = 0
        # Time-weighted in-flight depth (obs/metrics.py LevelTimer):
        # sched_bench reads this for the sustained-depth evidence.
        self.depth_timer = LevelTimer()
        # Binds retired by a flush OUTSIDE step()'s own accounting (the
        # exhaustion quiesce inside _drain_node_events, a defensive
        # resync flush): credited to the next step() so drivers summing
        # its return value never lose them.
        self._deferred_binds = 0
        # Seconds of nested out-of-band work to subtract from the
        # enclosing _stage observation (see _stage).
        self._stage_excluded = 0.0
        # _on_pod_put's lanes since the last _flush_lanes (plain adds
        # per pod; the Counter is touched once a batch).
        self._put_lanes = dict.fromkeys(("echo", "decode_fast", "json"), 0)
        self._nodes_watch: Watcher | None = None
        self._pods_watch: Watcher | None = None
        # True when the store's bind_batch can suppress our own watch
        # echo (native store only; set at bootstrap once the pods watch
        # exists — its id is read at every bind so resync stays correct).
        self._bind_excludes = False
        self.unschedulable: dict[str, PodInfo] = {}
        # Shard-set hooks (control/shardset.py): pods whose key fails the
        # intake filter are another shard's to schedule (their binds are
        # still tracked as external); the row mask restricts candidate
        # rows to this shard's slice of the node space.
        self.intake_filter = intake_filter
        self._row_mask_np: np.ndarray | None = None
        self._row_mask_dev = None
        # Failover state (ISSUE 9): the reign's fencing token, the
        # warm-standby follower flag (mirrors never schedule and are
        # excluded from the depth gauges), and the one-shot device-step
        # pre-compile latch the standby warms ahead of takeover.
        self.fence = fence
        self._follower = False
        self._warmed = False

        # The collector pauses the loop wherever it strikes: from here on
        # a collection is a coord.gc span and two counters (obs/gcspan).
        gcspan.install()
        _LIVE.add(self)

    def set_row_mask(self, mask: np.ndarray | None) -> None:
        """Install (or clear) the owned-node mask for sharded scheduling.

        The mask is a traced argument of the packed step, so rebalancing
        (flipping bits) never recompiles — the TPU re-expression of the
        reference's node-label rebalancer moving nodes between replicas
        (reference cmd/dist-scheduler/leader_activities.go:227-343)."""
        if self.mesh is not None and mask is not None:
            raise ValueError(
                "row masks (process-level node sharding) and a device "
                "mesh are different scale-out axes; compose them across "
                "processes, not inside one coordinator"
            )
        # The breaker-fallback node cache bakes the mask in: a rebalance
        # must invalidate it or an open-breaker wave binds onto rows
        # this shard no longer owns.
        self._fallback_cache = None
        if mask is None:
            self._row_mask_np = None
            self._row_mask_dev = None
            return
        mask = np.ascontiguousarray(np.asarray(mask, bool))
        if mask.shape != (self.table_spec.max_nodes,):
            raise ValueError(
                f"row mask shape {mask.shape} != ({self.table_spec.max_nodes},)"
            )
        self._row_mask_np = mask
        self._row_mask_dev = jax.device_put(mask)

    # ---- bootstrap -----------------------------------------------------

    def _relist_nodes(self) -> tuple[list, int]:
        """Full node relist for bootstrap/resync, returning ``(values,
        revision)`` — the bulk ingest lane reads node names out of the
        objects, so the keys (and their per-KV wrappers) are never
        materialized.  The in-process store takes the values-only light
        parse serially (its page parse is GIL-bound — sharding buys
        nothing); wire stores fan the value fetch over key-range shards
        so round trips and proto decode overlap
        (store/native.list_prefix_sharded)."""
        if isinstance(self.store, MemStore):
            return list_prefix_values(self.store, NODES_PREFIX)
        kvs, rev = list_prefix_sharded(self.store, NODES_PREFIX, shards=8)
        return [kv.value for kv in kvs], rev

    def bootstrap(self) -> None:
        """List+watch: load current state, then stream deltas from there.

        The watch starts at the list revision + 1, the same
        resourceVersion handoff kube informers perform.  The node
        relist feeds the bulk ingest lane (snapshot/bulkload.py) —
        byte-identical to the per-node upsert loop it replaced, minus
        the per-node wall — and the whole store->table build is timed
        into ``megarow_cold_build_seconds``.
        """
        t_cold = time.perf_counter()
        with self._stage("bootstrap"):
            with self._stage("relist", "bootstrap"):
                values, rev = self._relist_nodes()
            with self._stage("ingest", "bootstrap"):
                self._bulk.ingest(values)
            del values
            self._nodes_watch = self.store.watch(
                NODES_PREFIX, prefix_end(NODES_PREFIX),
                start_revision=rev + 1, queue_cap=self.watch_queue_cap,
            )
            pod_kvs, pod_rev = list_prefix(self.store, PODS_PREFIX)
            for kv in pod_kvs:
                self._on_pod_put(kv.value, kv.mod_revision)
            self._flush_lanes()
            self._pods_watch = self.store.watch(
                PODS_PREFIX, prefix_end(PODS_PREFIX),
                start_revision=pod_rev + 1, queue_cap=self.watch_queue_cap,
            )
            self._bind_excludes = isinstance(self._pods_watch, Watcher)
            with self._stage("to_device", "bootstrap"):
                self.table = self._table_to_device()
        _COLD_BUILD.set(time.perf_counter() - t_cold)

    # ---- watch delta application --------------------------------------

    _constraintful = staticmethod(_constraintful)

    def _victims_note(
        self, key: str, node_name: str, cpu: int, mem: int,
        priority: int, seq: int, tenant: str, gang: str,
    ) -> None:
        """Insert one bound pod into the incremental victims index —
        called at BOTH _bound insert sites (_note_bound and the native
        bind-batch retire).  Gang members are excluded exactly like the
        old per-wave scan: evicting one would strand its gang bound.
        ``row`` is carried as -1; _VictimRows resolves it lazily against
        the live row mapping at wave time."""
        if not self._track_victims or gang:
            return
        self._victims_by_node.setdefault(node_name, {})[key] = Victim(
            key, node_name, -1, cpu, mem, priority, seq, tenant,
        )

    def _victims_drop(self, key: str, node_name: str) -> None:
        if not self._track_victims:
            return
        d = self._victims_by_node.get(node_name)
        if d is not None and d.pop(key, None) is not None and not d:
            del self._victims_by_node[node_name]

    def _note_bound(self, pod: PodInfo, node_name: str, *, external: bool) -> None:
        row = self.host.row_of(node_name)
        zone, region = int(self.host.zone[row]), int(self.host.region[row])
        keep = pod if self._constraintful(pod) else None
        self._bind_seq += 1
        gang = gang_of_labels(pod.labels, pod.namespace)
        gang_id = gang[0] if gang is not None else ""
        tenant = tenant_of_pod(pod)
        self._bound[pod.key] = (
            node_name, pod.cpu_milli, pod.mem_kib, zone, region, keep,
            pod.priority, self._bind_seq, tenant, gang_id,
        )
        self._victims_note(
            pod.key, node_name, pod.cpu_milli, pod.mem_kib,
            pod.priority, self._bind_seq, tenant, gang_id,
        )
        if external and keep is not None and self.constraints is not None:
            # An externally bound pod contributes to domain counts exactly
            # like upstream's cache AddPod feeds plugin pre-state.
            self._pending_adjusts.append((keep, node_name, zone, region, 1))

    def _on_pod_put(self, data: bytes, mod_revision: int, key: bytes = b"") -> None:
        # Fast path for the watch echo of our own binds: the object has a
        # nodeName and its key is in _bound — half of all pod events in
        # steady state.  Skip the JSON decode entirely (the byte pattern
        # check is conservative: a false positive just takes the slow
        # path below).
        if key and b'"nodeName"' in data:
            pod_key_str = key[len(PODS_PREFIX):].decode()
            if pod_key_str in self._bound:
                self._queued_keys.discard(pod_key_str)
                self._put_lanes["echo"] += 1
                return
        try:
            # decode_pod's two lanes, forked here so each is counted.
            pod = decode_pod_fast(data, self.tracker)
            if pod is not None:
                self._put_lanes["decode_fast"] += 1
            else:
                self._put_lanes["json"] += 1
                pod = decode_pod_obj(json.loads(data), self.tracker)
        except Exception:
            # One malformed object must not poison the event stream — the
            # rest of the polled batch would be lost and the snapshot
            # would silently diverge.  Quarantine and move on.
            _DECODE_ERRORS.inc(kind="pod")
            log.exception("undecodable pod object; skipping")
            return
        if pod.node_name:
            # Someone's bind (ours echoing back, or an external writer):
            # account it if we haven't already.
            if pod.key not in self._bound:
                if pod.node_name in self.host._row_of:
                    self._orphan_bound.pop(pod.key, None)
                    self.host.add_pod(pod.node_name, pod.cpu_milli, pod.mem_kib)
                    self._dirty_rows.add(self.host.row_of(pod.node_name))
                    self._note_bound(pod, pod.node_name, external=True)
                else:
                    # Bound to a node we have not seen yet (list/watch
                    # interleaving at bootstrap); account when it arrives.
                    self._orphan_bound[pod.key] = pod
            self._queued_keys.discard(pod.key)
            return
        if pod.scheduler_name != self.scheduler_name:
            # Not ours to schedule (the reference's webhook/watch intake
            # applies the same schedulerName filter, webhook.go:102-125).
            return
        if self.intake_filter is not None and not self.intake_filter(pod.key):
            # Another shard's pod (pod-hash intake partition); its bind
            # arrives via watch and is accounted as external above.
            return
        if pod.key in self._queued_keys or pod.key in self._bound:
            # _bound: a webhook-intake pod can bind before its original
            # create event arrives via watch; re-enqueuing that stale
            # revision would double-account the pod in the batch it rides
            # (commit_binds assumes, CAS rolls back — but batch-mates
            # would have been placed against inflated usage meanwhile).
            return
        self._queued_keys.add(pod.key)
        self._stage_or_queue(
            PendingPod(
                pod, mod_revision, time.perf_counter(),
                cpu_milli=pod.cpu_milli, mem_kib=pod.mem_kib,
                key_str=pod.key, raw=data,
                key_bytes=key or pod_key(pod.namespace, pod.name),
                priority=pod.priority,
            ),
            pod,
        )

    def _flush_lanes(self, **lanes: int) -> None:
        """Count one batch's intake into coordinator_pod_intake_total:
        the caller's own lanes plus whatever _on_pod_put has taken since
        the last flush."""
        put = self._put_lanes
        for lane, n in (*lanes.items(), *put.items()):
            if n:
                _POD_INTAKE.inc(n, lane=lane)
        for lane in put:
            put[lane] = 0

    def _on_pod_delete(self, key: bytes) -> None:
        pod_key_str = key[len(PODS_PREFIX):].decode()
        tracer = self._tracer
        if tracer.enabled:
            # A pod deleted while pending closes its chain here (a
            # bound pod's trace already closed at bind; this no-ops).
            tracer.finish(pod_key_str, "requeue", outcome="deleted")
        self._queued_keys.discard(pod_key_str)
        self._orphan_bound.pop(pod_key_str, None)
        if self._gang_staging:
            # A deleted member must leave gang staging too: a leaked
            # record would count into the load signal forever and, if
            # the gang later completed, ride a wave as a dead pod.
            for gid, (_size, members) in list(self._gang_staging.items()):
                if members.pop(pod_key_str, None) is not None:
                    if not members:
                        del self._gang_staging[gid]
                    break
        bound = self._bound.pop(pod_key_str, None)
        if bound is not None:
            node_name, cpu, mem, zone, region, keep = bound[:6]
            self._victims_drop(pod_key_str, node_name)
            if node_name in self.host._row_of:
                self.host.remove_pod(node_name, cpu, mem)
                self._dirty_rows.add(self.host.row_of(node_name))
            if keep is not None and self.constraints is not None:
                self._pending_adjusts.append((keep, node_name, zone, region, -1))

    def _adopt_orphans(self, node_name: str) -> None:
        for key, pod in list(self._orphan_bound.items()):
            if pod.node_name == node_name:
                del self._orphan_bound[key]
                self.host.add_pod(node_name, pod.cpu_milli, pod.mem_kib)
                self._dirty_rows.add(self.host.row_of(node_name))
                self._note_bound(pod, node_name, external=True)

    def drain_watches(self, max_events: int = 10000) -> int:
        """Apply pending node/pod deltas; returns number of events.

        A watcher that overflowed its native queue (10,000 events) has
        silently lost deltas — the snapshot would diverge from the store
        forever.  Detect it and relist, the same way a kube reflector
        handles 410 Gone.
        """
        if self._watch_fault():
            # Injected watch loss (disconnect / drop / stale_revision):
            # the graceful-degradation contract is relist from current
            # state — exactly the overflow response below.
            return self.resync()
        if self._nodes_watch.dropped or self._pods_watch.dropped:
            log.warning(
                "watch overflow (nodes dropped=%d pods dropped=%d); resyncing",
                self._nodes_watch.dropped, self._pods_watch.dropped,
            )
            return self.resync()
        # A server-side cancel (compaction past our revision, shutdown,
        # tier restart) ends the stream without setting dropped; without a
        # resync the drains below would poll empty batches forever and
        # intake would silently stall.
        if getattr(self._nodes_watch, "canceled", False) or getattr(
            self._pods_watch, "canceled", False
        ):
            log.warning(
                "watch canceled server-side (nodes=%s pods=%s); resyncing",
                getattr(self._nodes_watch, "canceled", False),
                getattr(self._pods_watch, "canceled", False),
            )
            return self.resync()
        n = self._drain_node_events(max_events)
        n += self._drain_pod_events(max_events)
        return n

    @staticmethod
    def _watch_fault() -> bool:
        """Faultline hook on the intake watch drain (component
        ``coordinator.watch``, op ``poll``).  ``delay`` sleeps; any
        failure kind means the watch tier is gone from this consumer's
        perspective — True tells the caller to resync (relist from
        current store state + rewatch), which recovers every lost event
        by construction."""
        d = faultline.decide("coordinator.watch", "poll")
        if d is None:
            return False
        if d.kind == "delay":
            time.sleep(d.delay_s)
            return False
        log.warning("injected %s on watch drain; resyncing", d.kind)
        return True

    @contextlib.contextmanager
    def _stage(self, stage: str, parent: str | None = None):
        """The one place a stage is timed: ``coordinator_cycle_seconds
        {stage}`` and a ``coord.<stage>`` span on the profiler's clock
        (jax.profiler.TraceAnnotation — a TraceMe check when no profiler
        session runs) for the same interval, so a stage cannot be timed
        without being a span.  A child (``parent`` given) is the label
        ``<parent>_<stage>`` and the span ``coord.<parent>.<stage>``,
        once a wave (twice where a stage's work lies on both sides of
        another's), never once a pod.

        What is timed where, a pipelined step in order: ``drain``
        (children ``poll``, ``apply``); ``take`` (backoff release, the
        wave's records popped off the queue, their keys out of the
        queued set); ``encode``; ``sync_out``; ``bind`` (children
        ``gather``, ``cas``, ``account``, ``per_pod``, ``nofit``:
        _bind_wave); ``settle`` (the retire's tail: rollback scatter,
        quarantine release, breaker, and the retired wave's records
        freed); ``sync``; ``prep`` (fault draw, knobs, the wave's PRNG
        key, the delta plan); ``device`` (the dispatch).  The step's own
        time is what is left, and a collection that strikes inside any
        of them is its own ``coord.gc`` span (obs/gcspan.py), no stage.

        The _OVERLAP_STAGES also feed the overlap split: host-stage
        seconds labeled by whether device waves were in flight when the
        stage ran (inflight=yes time is hidden behind device work).
        Out-of-band work that runs nested inside a stage (the exhaustion
        quiesce's flush mid-drain) adds its duration to _stage_excluded
        so the same seconds are not counted into two stages; the inflight
        label is latched at entry (a rare-path approximation)."""
        if parent is None:
            label, span = stage, "coord." + stage
        else:
            label, span = f"{parent}_{stage}", f"coord.{parent}.{stage}"
        inflight = "yes" if self._inflights else "no"
        with jax.profiler.TraceAnnotation(span):
            t0 = time.perf_counter()
            excl0 = self._stage_excluded
            try:
                yield
            finally:
                dt = time.perf_counter() - t0 - (self._stage_excluded - excl0)
                _CYCLE_TIME.observe(dt, stage=label)
                if label in _OVERLAP_STAGES:
                    _PIPE_OVERLAP.inc(dt, stage=label, inflight=inflight)

    def _upsert_node(self, node) -> int:
        """host.upsert with the one structural quiesce left: allocation
        hitting a full table whose only free rows sit in the wave-epoch
        quarantine retires the pipeline, releases them, and retries."""
        try:
            return self.host.upsert(node)
        except RowsExhausted as e:
            if not e.quarantined:
                raise           # genuinely full; re-bucket TableSpec
            if self._inflights:
                _PIPE_QUIESCE.inc(reason="structural")
                # Retiring releases the quarantine; credit the binds to
                # the next step()/flush() return.  Plain assignment:
                # flush() already folds prior deferred credit into its
                # return (+= would re-add the stale loaded value).  The
                # flush runs nested inside the drain stage timer, so its
                # wall time is excluded from the drain observation (the
                # retired waves' sync_out/bind stages record it).
                t0 = time.perf_counter()
                self._deferred_binds = self.flush()
                self._stage_excluded += time.perf_counter() - t0
            self.host.release_rows(None)
            return self.host.upsert(node)

    def _drain_node_events(self, max_events: int = 10000) -> int:
        """Apply node deltas — pipeline-safe.

        Events classify at the row level: an update to a node the table
        already holds (capacity, labels, taints, zone — same row, same
        name) is capacity-only and lands in _dirty_caps, scattered into
        the live device table while waves are in flight; a new node
        allocates a fresh row past the high-water mark (or reuses a
        quarantine-released one) and a remove tombstones its row into
        the wave-epoch quarantine (node_table.py) — both structural
        shapes that no longer need the pipeline quiesced.  Only
        quarantine exhaustion (_upsert_node) still retires it."""
        if not self._inflights:
            # Idle pipeline: every launched wave has retired, so all
            # quarantined rows are past their hazard window.
            self.host.release_rows(None)
        n = 0
        row_of = self.host._row_of
        with self._stage("drain"):
            for etype, key, value, _mrev in drain_events_light(
                self._nodes_watch, max_events
            ):
                n += 1
                if etype == 0:
                    try:
                        node = decode_node(value)
                    except Exception:
                        _DECODE_ERRORS.inc(kind="node")
                        log.exception("undecodable node object; skipping")
                        continue
                    if node.name in row_of:
                        self._dirty_caps.add(self._upsert_node(node))
                    else:
                        self._dirty_rows.add(self._upsert_node(node))
                        self._adopt_orphans(node.name)
                    self._node_infos[node.name] = node
                else:
                    name = key[len(NODES_PREFIX):].decode()
                    self._node_infos.pop(name, None)
                    if name in row_of:
                        self._dirty_rows.add(self.host.remove(name))
        self._node_gen += n
        self._last_node_drain = n
        return n

    def _drain_pod_events(self, max_events: int = 10000) -> int:
        """Apply pod deltas.  Touches capacity accounting only — never
        the row->node mapping — so it is safe to run while a wave is in
        flight.  Drain to (momentarily) empty: a single capped poll per
        cycle would let backlog accumulate into an overflow resync under
        heavy churn; the per-call bound keeps the cycle live against a
        producer that outruns the decode pass.

        Both watcher types expose poll_pods — the native store drains
        AND parses in one C call; RemoteWatcher runs its buffered wire
        events through the same parser (ms_parse_pod_events) — so the
        columnar fast lane serves in-process and deployed topologies
        alike.  The per-event fallback below remains for third-party
        watcher implementations without poll_pods."""
        if getattr(self._pods_watch, "poll_pods", None) is not None:
            n = 0
            batch = min(max_events, 10000)
            with self._stage("drain"):
                while True:
                    with self._stage("poll", "drain"):
                        evb = self._pods_watch.poll_pods(
                            batch, self._sched_bytes
                        )
                    if evb.n:
                        with self._stage("apply", "drain"):
                            self._apply_pod_batch(evb)
                        n += evb.n
                    if evb.n < batch or n >= 20 * max_events:
                        return n
        n = deletes = 0
        with self._stage("drain"):
            for etype, key, value, mrev in drain_events_light(
                self._pods_watch, max_events
            ):
                n += 1
                if etype == 0:
                    self._on_pod_put(value, mrev, key)
                else:
                    deletes += 1
                    self._on_pod_delete(key)
            self._flush_lanes(delete=deletes)
        return n

    def _frame_shapes(self, evb) -> list:
        """The PodShape of every entry of one frame's shape table, at the
        index the frame's events name it by (0 = None: none of the five
        spans).  A span quintuple not seen before is decoded by the JSON
        lane's own code
        (objects.decode_pod_shape); one that cannot be decoded is False,
        and its pods count as decode errors just as _on_pod_put would
        have counted them."""
        shapes: list = [None]
        table = self._pod_shapes
        interned = 0
        for spans in evb.shapes:
            sh = table.get(spans)
            if sh is None:
                try:
                    sh = PodShape(
                        scheduler_name=self.scheduler_name,
                        **decode_pod_shape(*spans),
                    )
                except Exception:
                    log.exception("undecodable pod shape")
                    sh = False
                else:
                    if len(table) >= POD_SHAPES_MAX:
                        table.clear()
                        _POD_SHAPES.inc(event="evicted")
                    table[spans] = sh
                    interned += 1
            shapes.append(sh)
        if interned:
            _POD_SHAPES.inc(interned, event="interned")
        return shapes

    def _bound_shape(self, shape: PodShape | None, namespace: str):
        """``shape`` (None = the label-less pod's) as the pods of
        ``namespace`` refer to it: bound to the tracker (PodShape.bind)
        once per registration state and not once per pod.  An entry
        holds the tracker's registration counts, which only grow, so a
        constraint registered since — by this template or any other —
        reaches the template's next pod, as the JSON lane's per-pod
        decode would have it.  None = nothing but scalars to carry;
        False = the JSON lane would have refused the pod (an unsupported
        topologyKey, a full slot pool).  Cycle thread only: binding
        registers the template's own constraints."""
        tr = self.tracker
        cache = self._shape_bindings
        key = (shape, namespace)
        entry = cache.get(key)
        if entry is not None and entry[0] == (len(tr._spread), len(tr._affinity)):
            return entry[1]
        try:
            bound = (shape or self._bare_shape).bind(namespace, tr)
        except Exception:
            log.exception("pod shape refused by the constraint tracker")
            bound = False
        else:
            if bound is self._bare_shape:
                bound = None
            elif entry is not None and bound and bound.same_binding(entry[1]):
                # The registrations since changed nothing for it: its
                # pods go on referring to one shape, one fingerprint.
                bound = entry[1]
        if len(cache) >= 1024:
            # Bounded like _gang_oversize: namespaces and templates churn
            # on long soaks.  Clearing just binds a live one once more.
            cache.clear()
        # With the counts as binding left them: what the template's next
        # pod compares.
        cache[key] = ((len(tr._spread), len(tr._affinity)), bound)
        _POD_SHAPES.inc(event="bound")
        return bound

    def _apply_pod_batch(self, evb) -> None:
        """Apply one columnar poll_pods drain (store/native.py
        PodEventBatch).  Flag semantics decided natively: CANONICAL means
        the C parser accepted the exact encode_pod shape (scalars, plus a
        label map, a nodeSelector, a toleration list, an affinity object
        and spread constraints that arrive as the index of an interned
        PodShape); everything else falls back to _on_pod_put's full
        decode."""
        plen = len(PODS_PREFIX)
        koff = evb.koff.tolist()
        kb = evb.key_blob
        etype = evb.etype
        flags = evb.flags
        # The fast lane: canonical pending pods for this scheduler.
        fast = POD_CANONICAL | POD_SCHED_MATCH
        fastmask = (etype == 0) & (
            (flags & (fast | POD_HAS_NODE)) == fast
        )
        now = time.perf_counter()
        tracer = self._tracer
        tr_on = tracer.enabled
        tr = self.tracker
        # Whether a pod's shape depends on the tracker: a constraint is
        # registered, or a shape of this frame is about to register one.
        binding = bool(tr._spread or tr._affinity)
        tn = self.tenancy
        gangs_on = tn is not None and tn.policy.gang_enabled
        if evb.shapes:
            shapes = self._frame_shapes(evb)
            shape_l = [shapes[s] for s in evb.shape.tolist()]
            # False with a shape that must be looked at pod by pod:
            # undecodable, or gang labels while gangs are staged.
            shapes_columnar = all(
                sh and not (gangs_on and sh.gang) for sh in shapes[1:]
            )
            binding = binding or any(
                sh and sh.registers for sh in shapes[1:]
            )
        else:
            shape_l = [None] * evb.n
            shapes_columnar = True
        bound_shape = self._bound_shape
        if fastmask.all() and shapes_columnar:
            # Pure create wave (the make_pods steady state): one batched
            # tolist per column, no per-event branching.
            cpu_l = evb.cpu.tolist()
            mem_l = evb.mem.tolist()
            mrev_l = evb.mrev.tolist()
            queued = self._queued_keys
            bound = self._bound
            q = self.queue
            filt = self.intake_filter
            # Keys are ASCII but for the odd name: decoded in one piece,
            # byte offsets are then string offsets too.
            spans = zip(koff, koff[1:])
            if kb.isascii():
                ka = kb.decode()
                keys = [ka[lo + plen : hi] for lo, hi in spans]
            else:
                keys = [kb[lo + plen : hi].decode() for lo, hi in spans]
            todo = range(evb.n)
            if binding:
                # In frame order, and ahead of the checks below as the
                # JSON lane decodes (and registers) ahead of them.
                shape_l = [
                    bound_shape(sh, ks.partition("/")[0])
                    for sh, ks in zip(shape_l, keys)
                ]
                refused = sum(sh is False for sh in shape_l)
                if refused:
                    _DECODE_ERRORS.inc(refused, kind="pod")
                    todo = [i for i in todo if shape_l[i] is not False]
            for i in todo:
                ks = keys[i]
                if ks in queued or ks in bound:
                    continue
                if filt is not None and not filt(ks):
                    continue
                queued.add(ks)
                q.append(PendingPod(
                    None, mrev_l[i], now, cpu_l[i], mem_l[i], ks,
                    key_bytes=kb[koff[i] : koff[i + 1]], shape=shape_l[i],
                ))
                if tr_on:
                    tracer.begin(ks, now, source="intake")
            self._flush_lanes(batch_fast=evb.n)
            return
        aoff = evb.aoff.tolist()
        ab = evb.aux_blob
        cpu_l = evb.cpu.tolist()
        mem_l = evb.mem.tolist()
        mrev_l = evb.mrev.tolist()
        flags_l = flags.tolist()
        etype_l = etype.tolist()
        deletes = slow = 0
        for i in range(evb.n):
            key = kb[koff[i] : koff[i + 1]]
            if etype_l[i] == 1:
                deletes += 1
                self._on_pod_delete(key)
                continue
            f = flags_l[i]
            if not f & POD_CANONICAL:
                slow += 1
                self._on_pod_put(ab[aoff[i] : aoff[i + 1]], mrev_l[i], key)
                # decode_pod may have interned a new constraint whose
                # selector matches later canonical pods in this same
                # batch.
                binding = binding or bool(tr._spread or tr._affinity)
                continue
            ks = key[plen:].decode()
            sh = shape_l[i]
            if sh is not False and binding:
                sh = bound_shape(sh, ks.partition("/")[0])
            if sh is False:
                _DECODE_ERRORS.inc(kind="pod")
                continue
            if f & POD_HAS_NODE:
                # A bind: ours echoing back (suppressed at the store for
                # native binds, but the slow _bind path still echoes), or
                # an external writer's.
                if ks in self._bound:
                    self._queued_keys.discard(ks)
                    continue
                node_name = ab[aoff[i] : aoff[i + 1]].decode()
                pod = (sh or self._bare_shape).pod(
                    ks, cpu_l[i], mem_l[i], node_name
                )
                if node_name in self.host._row_of:
                    self._orphan_bound.pop(ks, None)
                    self.host.add_pod(node_name, pod.cpu_milli, pod.mem_kib)
                    self._dirty_rows.add(self.host.row_of(node_name))
                    self._note_bound(pod, node_name, external=True)
                else:
                    self._orphan_bound[ks] = pod
                self._queued_keys.discard(ks)
                continue
            if not f & POD_SCHED_MATCH:
                continue
            if ks in self._queued_keys or ks in self._bound:
                continue
            if self.intake_filter is not None and not self.intake_filter(ks):
                continue
            self._queued_keys.add(ks)
            rec = PendingPod(
                None, mrev_l[i], now,
                cpu_milli=cpu_l[i], mem_kib=mem_l[i],
                key_str=ks, key_bytes=key, shape=sh,
            )
            if gangs_on and sh is not None and sh.gang:
                # Gang staging reads the labels off a PodInfo.
                self._stage_or_queue(rec, rec.ensure_pod())
                continue
            self.queue.append(rec)
            if tr_on:
                tracer.begin(ks, now, source="intake")
        self._flush_lanes(
            delete=deletes, canonical=evb.n - deletes - slow
        )

    def _node_name_bytes(self) -> list:
        """Encoded node names, index-parallel with vocab.node_names
        (extended lazily; names never leave the vocab)."""
        nb = self._name_bytes
        tv = self.host.vocab.node_names._to_val
        while len(nb) < len(tv):
            v = tv[len(nb)]
            nb.append(v.encode() if isinstance(v, str) else b"")
        return nb

    def resync(self) -> int:
        """Full relist after watch overflow: reconcile host state against
        the store and restart both watches from the list revisions."""
        _RESYNCS.inc()
        self._node_gen += 1
        # The bulk relist below refreshes every row WITHOUT building
        # per-node objects; a kept index would serve pre-outage
        # NodeInfos for rows whose values changed while the watch was
        # broken.  Drop it wholesale — the next fallback call re-seeds
        # lazily from the store.
        self._node_infos.clear()
        if self._inflights:
            # Call sites quiesce first; this is the defensive backstop
            # (a driver calling drain_watches mid-flight) — the relist
            # below rebuilds the row mapping, which no wave may straddle.
            # Plain assignment — _quiesce's flush() already folds prior
            # deferred credit into its return (+= would double-count it),
            # and the inflights guard above means it really flushes.
            self._deferred_binds = self._quiesce("resync")
        # The pipeline is idle: the quarantine's hazard window is over,
        # and the relist may need rows.
        self.host.release_rows(None)
        self._midflight_rows.clear()
        with self._stage("resync"):
            self._nodes_watch.cancel()
            self._pods_watch.cancel()

            values, rev = self._relist_nodes()
            rows = self._bulk.ingest(values)
            del values
            self._dirty_rows.update(rows.tolist())
            # Listed names read back from the ingested rows (the
            # object's metadata.name, exactly what the old decode loop
            # collected), so a writer whose key disagrees with its
            # object cannot desync the removal sweep.
            nv = self.host.vocab.node_names._to_val
            listed = {nv[i] for i in self.host.name_id[rows].tolist()}
            stale = [
                name for name in self.host._row_of if name not in listed
            ]
            for name in stale:
                self._dirty_rows.add(self.host.remove(name))
            self._nodes_watch = self.store.watch(
                NODES_PREFIX, prefix_end(NODES_PREFIX),
                start_revision=rev + 1, queue_cap=self.watch_queue_cap,
            )

            pod_kvs, pod_rev = list_prefix(self.store, PODS_PREFIX)
            seen = set()
            for kv in pod_kvs:
                seen.add(kv.key[len(PODS_PREFIX):].decode())
                self._on_pod_put(kv.value, kv.mod_revision)
            self._flush_lanes()
            for key in list(self._bound):
                if key not in seen:
                    ns, name = key.split("/", 1)
                    self._on_pod_delete(pod_key(ns, name))
            self._orphan_bound = {
                k: v for k, v in self._orphan_bound.items() if k in seen
            }
            self._pods_watch = self.store.watch(
                PODS_PREFIX, prefix_end(PODS_PREFIX),
                start_revision=pod_rev + 1, queue_cap=self.watch_queue_cap,
            )
        return len(listed) + len(seen)

    # ---- warm standby: follow / promote / crash-consistent recovery ----
    # (ISSUE 9; driven by control/leader.HACoordinator)

    def follow(self) -> int:
        """One standby-mirror tick: apply the world's deltas and keep
        every cache warm — NEVER schedule, never write to the store.

        The mirror's derived state (queue, bound-pod ledger with its
        preemption metadata, gang staging, host mirror, device table, encode
        templates, compiled step) is thereby a CONTINUOUS reconstruction
        from store facts + intake replay — exactly the state
        ``promote()`` inherits at takeover, which is why takeover is a
        bounded reconcile instead of a cold boot.  Returns events
        applied this tick."""
        lag = 0
        for w in (self._nodes_watch, self._pods_watch):
            p = getattr(w, "pending", None)
            if p:
                lag += int(p)
        _MIRROR_LAG.set(lag)
        self._drain_external()
        n = self.drain_watches()
        self._sync_table()
        self._process_adjusts()
        # Keep the mirror's queue ≈ the TRUE pending backlog: entries
        # the leader already bound would otherwise accumulate all
        # standby long and poison the load signal below (and promote's
        # first waves).  Thresholded so steady follow ticks stay O(1).
        if len(self.queue) >= 2 * max(
            self.pod_spec.batch, len(self._queued_keys) - len(self._backoff)
        ):
            self._purge_settled_queue()
        # Tick the overload/tenancy chain too: HACoordinator stages
        # no-leader webhook pods into this mirror THROUGH admission, so
        # the per-tenant buckets must keep refilling (and the health
        # state must track the real backlog) while standby.
        self._loadshed_tick()
        self.warm_compile()
        return n

    def _purge_settled_queue(self) -> int:
        """Drop queue records whose pods are already settled: a
        follower learns of the leader's binds AFTER queueing the same
        pods, so its queue holds stale records for bound keys
        (``_queued_keys`` was discarded; the deque entry was not).
        Returns the number purged."""
        stale = sum(
            1 for p in self.queue
            if p.key_str not in self._queued_keys or p.key_str in self._bound
        )
        if stale:
            self.queue = collections.deque(
                p for p in self.queue
                if p.key_str in self._queued_keys
                and p.key_str not in self._bound
            )
        return stale

    def warm_compile(self) -> bool:
        """Pre-compile the device step ahead of takeover: run one wave
        over the live table shapes and DISCARD every output — no store
        write, no host accounting, no RNG stream consumed.  Encodes the
        mirror's own queued pods (peeked, never popped) so the compiled
        (groups, shape) executable variant matches the traffic the
        first post-takeover wave will actually carry; retries each
        follow tick until representative pods exist, then latches."""
        if self._warmed or self.table is None:
            return False
        pods = []
        for p in self.queue:
            pods.append(p.peek_pod())
            if len(pods) >= self.pod_spec.batch:
                break
        if not pods:
            return False
        batch = self._built_step_for(
            self.encoder.encode_packed(pods), self.profile, self._sample_rows
        )
        # The production executable donates its inputs: warm it against
        # throwaway COPIES so the live mirror table (and constraint
        # state) survive this discarded dispatch.
        tbl, cons = self.table, self.constraints
        if self._donate:
            tbl = jax.tree.map(jnp.array, tbl)
            if cons is not None:
                cons = jax.tree.map(jnp.array, cons)
        _t, _c, _asg, rows_dev = schedule_batch_packed(
            tbl, batch, jax.random.key(0),
            profile=self.profile, constraints=cons,
            chunk=self.chunk, k=self.k, backend=self.backend,
            sample_rows=self._sample_rows, sample_offset=0,
            row_mask=self._row_mask_dev, mesh=self.mesh,
            donate=self._donate, in_wave_skew=self.in_wave_skew,
        )
        jax.block_until_ready(rows_dev)
        self._warmed = True
        return True

    def promote(self, *, acquire_revision: int = 0) -> dict:
        """Warm-standby takeover: turn a following mirror into the
        leader with a bounded reconcile.

        1. Drain the watch backlog (bounded by the mirror's lag; a
           broken/overflowed watch falls back to a full ``resync`` —
           still warm: vocab, encode templates and the compiled step
           survive).
        2. Diff the mirror against the store pinned at the
           lease-acquire revision (``_reconcile_at``): every divergence
           is repaired through the ordinary intake paths and counted —
           crash consistency does not depend on the watch stream having
           been perfect.
        3. Settle gangs the predecessor left partially bound
           all-or-none (``recover_gangs``).
        4. Push repairs to the device and drop follower status.

        Rows whose accounting changed during the reconcile ride the
        normal dirty-row machinery, and the mirror has no in-flight
        waves by construction — so the wave-epoch quarantine starts the
        new reign empty: nothing the predecessor's unretired waves
        touched can alias a row (their store writes were fenced; their
        device-side assumes died with their table).

        Returns the evidence dict drivers commit (repair counts)."""
        stats: dict = {"resync": 0, "repairs": {}, "gangs_released": 0}
        nw, pw = self._nodes_watch, self._pods_watch
        broken = (
            nw is None or pw is None
            or nw.dropped or pw.dropped
            or getattr(nw, "canceled", False)
            or getattr(pw, "canceled", False)
        )
        if broken:
            self.resync()
            stats["resync"] = 1
        else:
            for _ in range(64):
                n = self.drain_watches()
                if n:
                    continue
                # Remote watchers expose the highest revision BUFFERED
                # off the wire (RemoteWatcher.seen_revision): keep
                # pumping while the stream demonstrably has not covered
                # the acquire revision yet (events can be in flight
                # with pending == 0).  A quiet prefix never reaches the
                # acquire revision — the loop cap bounds that, and the
                # current-state reads in _reconcile_at repair whatever
                # a still-in-flight event would have delivered.
                seen = getattr(self._pods_watch, "seen_revision", None)
                if seen is None or seen >= acquire_revision:
                    break
            self._drain_external()
            repairs = self._reconcile_at(acquire_revision)
            stats["resync"] = repairs.pop("resync", 0)
            stats["repairs"] = repairs
        # Purge queue entries the predecessor already settled: dropping
        # them spares the first post-takeover waves a conflict storm of
        # already-bound pods — and keeps recover_gangs from reading a
        # fully-bound gang as still pending.
        stats["stale_queue_purged"] = self._purge_settled_queue()
        stats["gangs_released"] = self.recover_gangs()
        self._sync_table()
        self._process_adjusts()
        self._follower = False
        _MIRROR_LAG.set(0)
        return stats

    def _reconcile_at(self, revision: int) -> dict:
        """Crash-consistency audit: list both prefixes PINNED at the
        lease-acquire revision (follow-mode relist-from-revision,
        store/native.list_prefix) and diff against the mirror.

        The mirror has already drained its watches PAST the pin, so a
        pin-vs-mirror mismatch is ambiguous on its own: either the
        watch stream missed the fact (repair it) or the mirror
        legitimately advanced beyond the pin (leave it alone).  Every
        candidate repair therefore re-reads the store's CURRENT state
        before mutating — the pin bounds WHAT to audit (a stable
        iteration set as of acquisition), the current read decides the
        repair.  Facts the watch already delivered cost a set probe
        each; actual repairs go through the ordinary intake handlers
        (``_on_pod_put`` / ``_on_pod_delete`` / ``_upsert_node``) so
        repair and live intake can never disagree, and each is counted
        in ``failover_reconcile_repairs_total``."""
        rep = {"nodes_added": 0, "nodes_removed": 0, "pods_replayed": 0,
               "binds_adopted": 0, "pods_dropped": 0}
        try:
            kvs, _ = list_prefix(
                self.store, NODES_PREFIX, revision=revision
            )
            pod_kvs, _ = list_prefix(
                self.store, PODS_PREFIX, revision=revision
            )
        except (CompactedError, FutureRevError):
            # The acquire revision is outside the store's window (long
            # pause + compaction): the pinned diff is impossible, fall
            # back to the full relist.
            self.resync()
            return {"resync": 1}
        row_of = self.host._row_of
        listed = set()
        for kv in kvs:
            name = kv.key[len(NODES_PREFIX):].decode()
            listed.add(name)
            if name in row_of:
                continue
            # In the pin but not the mirror: a missed add — unless the
            # node was deleted after the pin (the mirror is right).
            cur = self.store.get(kv.key)
            if cur is None:
                continue
            try:
                node = decode_node(cur.value)
            except Exception:
                _DECODE_ERRORS.inc(kind="node")
                log.exception("undecodable node in reconcile; skipping")
                continue
            self._dirty_rows.add(self._upsert_node(node))
            self._node_infos[node.name] = node
            self._adopt_orphans(name)
            rep["nodes_added"] += 1
        for name in list(row_of):
            if name in listed:
                continue
            # In the mirror but not the pin: a missed delete — unless
            # the node was created after the pin (the mirror is right).
            if self.store.get(node_key(name)) is not None:
                continue
            self._node_infos.pop(name, None)
            self._dirty_rows.add(self.host.remove(name))
            rep["nodes_removed"] += 1
        seen = set()
        for kv in pod_kvs:
            k = kv.key[len(PODS_PREFIX):].decode()
            seen.add(k)
            pinned_bound = b'"nodeName"' in kv.value
            mirror_bound = k in self._bound
            if pinned_bound == mirror_bound:
                continue
            # Pin and mirror disagree: the CURRENT store state decides
            # whether the watch missed a fact or the mirror advanced.
            cur = self.store.get(kv.key)
            if cur is None:
                continue        # deleted meanwhile; the delete echo or
                                # the _bound sweep below settles it
            cur_bound = b'"nodeName"' in cur.value
            if cur_bound and not mirror_bound:
                # A bind the mirror never saw: adopt it as external.
                self._on_pod_put(cur.value, cur.mod_revision, kv.key)
                rep["binds_adopted"] += 1
            elif not cur_bound and mirror_bound:
                # An eviction echo the mirror never saw: undo the
                # accounting and replay the pending object.
                self._on_pod_delete(kv.key)
                self._on_pod_put(cur.value, cur.mod_revision, kv.key)
                rep["pods_replayed"] += 1
        # Intake the mirror missed entirely (pinned pending, tracked
        # nowhere) — replay only if the pod still exists and is still
        # pending NOW.
        for kv in pod_kvs:
            k = kv.key[len(PODS_PREFIX):].decode()
            if (
                b'"nodeName"' in kv.value
                or k in self._queued_keys or k in self._bound
            ):
                continue
            cur = self.store.get(kv.key)
            if cur is None or b'"nodeName"' in cur.value:
                continue
            self._on_pod_put(cur.value, cur.mod_revision, kv.key)
            rep["pods_replayed"] += 1
        for k in list(self._bound):
            if k in seen:
                continue
            ns, name = k.split("/", 1)
            kb = pod_key(ns, name)
            # Absent from the PINNED list but maybe newer than the pin
            # (bound after acquisition): only the store's CURRENT state
            # decides a drop.
            if self.store.get(kb) is None:
                self._on_pod_delete(kb)
                rep["pods_dropped"] += 1
        for kind, n in rep.items():
            if n:
                _RECONCILE_REPAIRS.inc(n, kind=kind)
        return rep

    def recover_gangs(self) -> int:
        """Crash half of gang all-or-none (takeover): a predecessor
        that died between a wave's bind CASes and its gang settlement
        leaves a gang PARTIALLY bound in the store.  Any gang with both
        bound members and pending members releases the bound ones
        (fenced evict — we hold the lease now) back through gang
        staging, so the whole gang re-rides one wave; gangs whose every
        member is bound are honored via the store untouched.  Returns
        binds released."""
        if self.tenancy is None or not self.tenancy.policy.gang_enabled:
            return 0
        bound_gangs: dict[str, list[str]] = {}
        for key, rec in self._bound.items():
            if rec[9]:
                bound_gangs.setdefault(rec[9], []).append(key)
        if not bound_gangs:
            return 0
        pending_gangs = set(self._gang_staging)
        for p in self.queue:
            # Only genuinely-pending members count: a follower's queue
            # can hold stale records for keys the predecessor already
            # bound (settled gangs must read as fully bound, not split).
            if (
                p.gang_id and p.key_str in self._queued_keys
                and p.key_str not in self._bound
            ):
                pending_gangs.add(p.gang_id)
        for _, _, members in self._gang_parked:
            for p in members:
                if p.gang_id:
                    pending_gangs.add(p.gang_id)
        released = 0
        for gid, keys in bound_gangs.items():
            if gid not in pending_gangs:
                continue        # fully bound: store facts are honored
            for key in keys:
                evicted, rec = self._evict_bound(
                    key, count_eviction=False, path="evict"
                )
                if not evicted:
                    log.warning(
                        "gang %s member %s could not be released at "
                        "takeover (CAS lost); leaving it bound", gid, key,
                    )
                    continue
                released += 1
                if rec is not None:
                    pod = rec.pod
                    g = gang_of_labels(pod.labels, pod.namespace)
                    if g is not None:
                        rec.gang_id, rec.gang_size = g
                    tracer = self._tracer
                    if tracer.enabled:
                        # Takeover requeue: the released member's chain
                        # re-anchors under the new reign before
                        # _stage_or_queue's generic begin can label it
                        # as ordinary intake.
                        tracer.begin(
                            rec.key_str, rec.enqueued_at,
                            source="failover",
                        )
                    self._stage_or_queue(rec, pod)
            note_gang("recovered")
            log.info(
                "takeover released partially-bound gang %s "
                "(%d members back to staging)", gid, len(keys),
            )
        return released

    @staticmethod
    def _pad_rows(rows: np.ndarray) -> np.ndarray:
        """Sorted, power-of-two-padded scatter indices.  Sorted first:
        np.fromiter over a set is arbitrary-order, which would make the
        padded scatter input nondeterministic across runs (and hurt
        gather locality); padding then repeats the last row — scattering
        identical values to the same index is idempotent.  The pow2
        bucket keeps jax.jit at a handful of shapes, not one trace per
        distinct dirty-row count."""
        rows.sort()
        cap = 1 << max(0, int(rows.size - 1).bit_length())
        if cap != rows.size:
            rows = np.concatenate(
                [rows, np.repeat(rows[-1:], cap - rows.size)]
            )
        return rows

    def _sync_table(self) -> None:
        """Scatter dirty host rows into the device table — safe to run
        while waves are in flight.

        The scatter consumes the latest table future, so it executes
        on-stream after every dispatched wave (no host sync, no
        quiesce).  Capacity-only rows (_dirty_caps) upload the feature
        columns alone, leaving the device's in-flight request assumes
        intact; full rows (_dirty_rows) upload everything — host
        authoritative — and are noted in _midflight_rows so retiring
        waves can repair the assumes the upload erased (see _complete).
        """
        if self.table is None:
            self.table = self._table_to_device()
            self._dirty_rows.clear()
            self._dirty_caps.clear()
            return
        if self._packing_rebuilding:
            # Mid-rebuild retires re-enter here; the wholesale re-upload
            # at the end of _packing_rebuild subsumes every dirty row.
            return
        if not self._dirty_rows and not self._dirty_caps:
            return
        with self._stage("sync"):
            if self._dirty_rows:
                # A row needing the full upload supersedes its
                # capacity-only entry (the full delta includes CAP cols).
                self._dirty_caps -= self._dirty_rows
                rows = self._pad_rows(
                    np.fromiter(self._dirty_rows, np.int32)
                )
                try:
                    delta = self._row_delta(rows, ALL_COLUMNS)
                except PackingOverflow as e:
                    self._packing_rebuild(e)
                    return
                if self._inflights:
                    self._midflight_rows.update(self._dirty_rows)
                self._dirty_rows.clear()
                self.table = self._scatter(self.table, rows, delta)
                if self.mesh is not None:
                    _MESH_SCATTER.inc(cols="full")
            if self._dirty_caps:
                rows = self._pad_rows(
                    np.fromiter(self._dirty_caps, np.int32)
                )
                try:
                    delta = self._row_delta(rows, CAP_COLUMNS)
                except PackingOverflow as e:
                    self._packing_rebuild(e)
                    return
                self._dirty_caps.clear()
                self.table = self._scatter(self.table, rows, delta)
                if self.mesh is not None:
                    _MESH_SCATTER.inc(cols="cap")

    # ---- device-snapshot layout (snapshot/packing.py) ------------------

    def _table_to_device(self):
        """Build (or rebuild) the device table under the active layout,
        recording the HBM evidence gauge."""
        if self._packing_mode == "packed":
            if self._packing_spec is None:
                # Built against the CURRENT vocab so the label-fusion
                # fail-closed decision is made with real ids in view.
                self._packing_spec = build_packing_spec(
                    self.table_spec, self.host.vocab
                )
                if self._packing_spec is None:
                    # taint_slots too wide for the meta word.
                    _PACKING_FALLBACK.inc(reason="taint_slots")
                    self._packing_mode = "off"
            if self._packing_spec is not None:
                try:
                    table = pack_table_host(
                        self.host, self._packing_spec, self._table_sharding
                    )
                    self._note_table_bytes(table)
                    return table
                except PackingOverflow as e:
                    self._packing_fallback(e)
                    if self._packing_mode == "packed":
                        # Widened (label words split) — one retry.  A
                        # SECOND overflow on another field (e.g. a node
                        # past the int16 pods budget in the same rebuild
                        # window) must also fail closed to unpacked, not
                        # escape into the cycle loop.
                        try:
                            table = pack_table_host(
                                self.host, self._packing_spec,
                                self._table_sharding,
                            )
                            self._note_table_bytes(table)
                            return table
                        except PackingOverflow as e2:
                            self._packing_fallback(e2)
        table = self.host.to_device(self._table_sharding)
        self._note_table_bytes(table)
        return table

    @property
    def donation_inplace(self) -> bool | None:
        """Whether the runtime honored per-wave buffer donation in place
        (None until the first donating wave's probe runs).  On the mesh
        the probe is per-shard: it collects every shard's buffer
        pointers before the first wave and reports in-place when ANY
        shard's post-step buffer set overlaps the probed set
        (snapshot/packing.donation_probe).  The public read for bench/
        report surfaces — `commit_donation_total{inplace}` is the
        per-wave counter."""
        return self._donation_inplace

    def _note_table_bytes(self, table) -> None:
        layout = "packed" if is_packed(table) else "unpacked"
        other = "unpacked" if layout == "packed" else "packed"
        _TABLE_BYTES.set(hbm_bytes(table), layout=layout)
        _TABLE_BYTES.set(0, layout=other)

    def _row_delta(self, rows, columns) -> dict:
        """Dirty-row scatter payload under the live table's layout.
        Raises PackingOverflow when a packed width no longer fits
        (vocab drift) — the caller rebuilds fail-closed."""
        if is_packed(self.table):
            return pack_row_delta(self.host, rows, self.table.spec, columns)
        out = {}
        for c in columns:
            arr = getattr(self.host, c)[rows]
            if arr.dtype != np.bool_ and arr.dtype != np.int32:
                # Narrow mirror columns (node_table.mirror_dtype) widen
                # back to the unpacked device layout's int32.
                arr = arr.astype(np.int32)
            out[c] = arr
        return out

    def _packing_fallback(self, e: PackingOverflow) -> None:
        """Fail-closed layout widening (the vocab-drift gate, hotfeed's
        shape): label overflow splits the fused words (still packed);
        anything else drops to the unpacked layout.  Never truncates —
        the cost is one recompile under the wider layout."""
        _PACKING_FALLBACK.inc(reason=e.field)
        if (
            e.field in ("label_key", "label_val")
            and self._packing_spec is not None
            and self._packing_spec.fuse_labels
        ):
            log.warning("packed snapshot: %s; splitting label words", e)
            self._packing_spec = dataclasses.replace(
                self._packing_spec, fuse_labels=False
            )
        else:
            log.warning("packed snapshot: %s; falling back to unpacked", e)
            self._packing_mode = "off"
            self._packing_spec = None

    def _packing_rebuild(self, e: PackingOverflow) -> None:
        """A dirty-row delta no longer fits the packed layout: widen the
        layout, retire the pipeline (the host mirror is authoritative
        for everything EXCEPT the in-flight assume chain, so the waves
        must land before a wholesale re-upload), and rebuild.

        Cross-shard widening protocol (meshpack): the widening decision
        — split label words vs drop to unpacked — happens ONCE, here on
        the host (_packing_fallback mutates the one PackingSpec every
        shard shares), never per-shard; the quiesce retires every
        in-flight donating wave, and on the mesh the rebuild then
        BLOCKS on the retired table so every shard's in-flight donated
        buffers have settled before the wholesale re-upload replaces
        them — a shard still executing against donated HBM while the
        re-upload lands would be a per-shard layout skew."""
        self._packing_fallback(e)
        self._packing_rebuilding = True
        try:
            self._quiesce("packing")
        finally:
            self._packing_rebuilding = False
        if self.mesh is not None and self.table is not None:
            jax.block_until_ready(jax.tree.leaves(self.table))
        self._dirty_rows.clear()
        self._dirty_caps.clear()
        self.table = self._table_to_device()

    # ---- the cycle -----------------------------------------------------

    def _process_adjusts(self) -> None:
        """Batch-apply queued constraint-count corrections.

        Runs through the hotfeed encode cache (the pods being adjusted
        were all encoded at intake, so a CAS-rollback storm's re-encodes
        are template hits) and reuses one scratch arena instead of five
        fresh ``np.zeros`` per chunk — this path fires exactly when the
        system is already struggling (rollback storms, deletions), so
        its constant cost matters most."""
        if not self._pending_adjusts or self.constraints is None:
            return
        b = self.pod_spec.batch
        pending, self._pending_adjusts = self._pending_adjusts, []
        scr = self._adjust_scratch
        if scr is None:
            scr = self._adjust_scratch = {
                "node_row": np.zeros(b, np.int32),
                "zone": np.zeros(b, np.int32),
                "region": np.zeros(b, np.int32),
                "mask_node": np.zeros(b, bool),
                "mask_dom": np.zeros(b, bool),
            }
        for sign in (1, -1):
            group = [a for a in pending if a[4] == sign]
            for off in range(0, len(group), b):
                chunk = group[off : off + b]
                batch = self.encoder.encode_packed(*_adjusted(chunk))
                fields = commit_fields_np(batch.fields)
                for arr in scr.values():
                    arr[:] = 0
                node_row = scr["node_row"]
                zone = scr["zone"]
                region = scr["region"]
                mask_node = scr["mask_node"]
                mask_dom = scr["mask_dom"]
                for i, (_, node_name, z, r, _s) in enumerate(chunk):
                    row = self.host._row_of.get(node_name)
                    if row is not None:
                        node_row[i] = row
                        mask_node[i] = True
                    zone[i], region[i] = z, r
                    mask_dom[i] = True
                # jnp.array (copy=True), NOT asarray: CPU jax may alias
                # numpy memory zero-copy, and the scratch is mutated for
                # the next chunk while this dispatch is still in flight.
                self.constraints = self._adjust(
                    self.constraints, fields,
                    jnp.array(node_row), jnp.array(zone), jnp.array(region),
                    jnp.array(mask_node), jnp.array(mask_dom), sign=sign,
                )

    def submit_external(self, obj: dict, *, admitted: bool = False) -> None:
        """Thread-safe webhook-intake sink (control/webhook.py).

        The pod is staged and enters the queue at the next cycle; the
        store watch remains the fallback intake, deduplicated by key.

        With a loadshed controller installed this is an admission point:
        past the overload watermarks it raises ``loadshed.Overloaded``
        (lowest ``spec.priority`` shed first, hard ``queue_cap`` bound).
        ``admitted=True`` is the webhook's already-ran-admission marker
        (it checks pre-response so it can answer 429) — one pod must
        never draw, and count, two admission decisions.

        With a tenancy controller installed, admission is the
        weighted-fair per-tenant form (tenancy/admission.py): the
        global priority floor is replaced by token buckets, so overload
        degrades the over-share tenant instead of the cluster.
        """
        tracer = self._tracer
        t_in = time.perf_counter() if tracer.enabled else 0.0
        if not admitted:
            if self.tenancy is not None:
                self.tenancy.admission.check_admit_obj(
                    obj, point="coordinator"
                )
            elif self.loadshed is not None:
                self.loadshed.check_admit(
                    pod_priority_of(obj), point="coordinator"
                )
        if tracer.enabled:
            # The admit span anchors the trace at intake entry and
            # covers the admission decision; the tenant's bucket level
            # is the "how close to shed" evidence.  begin() no-ops when
            # the webhook already opened this trace at receipt — the
            # admit span is emitted EITHER way (it closes against
            # whichever anchor is live).
            key = pod_key_str_of_obj(obj)
            tracer.begin(key, t_in, source="external")
            attrs = {"point": "webhook" if admitted else "coordinator"}
            if self.tenancy is not None:
                tenant = tenant_of_obj(obj)
                attrs["tenant"] = tenant
                attrs["bucket"] = self.tenancy.admission.bucket_level(
                    tenant
                )
            tracer.emit(key, "admit", **attrs)
        with self._external_lock:
            self._external.append(obj)

    def _external_pending(self) -> int:
        """Staged webhook pods (locked read — the unlocked peek this
        replaced was a benign race on CPython, but the guard audit is
        only meaningful if the annotated discipline has no exceptions)."""
        with self._external_lock:
            return len(self._external)

    def _drain_external(self) -> None:
        with self._external_lock:
            if not self._external:
                return
            staged, self._external = self._external, []
        for obj in staged:
            try:
                pod = decode_pod_obj(obj, self.tracker)
            except Exception:
                _DECODE_ERRORS.inc(kind="pod")
                log.exception("undecodable webhook pod; skipping")
                continue
            if pod.node_name or pod.scheduler_name != self.scheduler_name:
                continue
            if self.intake_filter is not None and not self.intake_filter(
                pod.key
            ):
                continue
            if pod.key in self._queued_keys or pod.key in self._bound:
                continue
            self._queued_keys.add(pod.key)
            self._stage_or_queue(
                PendingPod(
                    pod, None, time.perf_counter(),
                    cpu_milli=pod.cpu_milli, mem_kib=pod.mem_kib,
                    key_str=pod.key,
                    key_bytes=pod_key(pod.namespace, pod.name),
                    priority=pod.priority,
                ),
                pod,
            )

    # ---- tenancy: gang staging, eviction, preemption --------------------

    def _stage_or_queue(self, rec: PendingPod, pod: PodInfo | None) -> None:
        """Queue a decoded intake pod — via gang staging when it carries
        gang labels and tenancy is on.  A gang's members enter the queue
        contiguously only once ALL are present; until then they hold no
        queue slot and no capacity.  Oversize gangs (bigger than one
        wave) degrade to plain scheduling, counted once per gang."""
        tracer = self._tracer
        if tracer.enabled:
            # No-op for a webhook pod (its trace opened at admission).
            tracer.begin(rec.key_str, rec.enqueued_at, source="intake")
        tn = self.tenancy
        if tn is not None and tn.policy.gang_enabled and pod is not None:
            g = gang_of_labels(pod.labels, pod.namespace)
            if g is not None:
                gid, size = g
                if size > self.pod_spec.batch:
                    if gid not in self._gang_oversize:
                        if len(self._gang_oversize) >= 1024:
                            # Bounded dedup memory: gang ids churn with
                            # namespaces; resetting just re-counts a
                            # repeat offender once more.
                            self._gang_oversize.clear()
                        self._gang_oversize.add(gid)
                        note_gang("oversize")
                        log.warning(
                            "gang %s size %d exceeds wave batch %d; "
                            "scheduling its pods as plain",
                            gid, size, self.pod_spec.batch,
                        )
                else:
                    rec.gang_id, rec.gang_size = gid, size
                    st = self._gang_staging.get(gid)
                    if st is None:
                        st = self._gang_staging[gid] = (size, {})
                    st[1][rec.key_str] = rec
                    if len(st[1]) >= st[0]:
                        del self._gang_staging[gid]
                        if tracer.enabled:
                            # Staging wait ends for every member the
                            # moment the last one completes the gang.
                            for m in st[1].values():
                                tracer.emit(
                                    m.key_str, "gang_stage",
                                    gang=gid, size=st[0],
                                )
                        self.queue.extend(st[1].values())
                    return
        self.queue.append(rec)

    def _gang_staged(self) -> int:
        """Pods parked in gang staging (counts toward the load signal —
        they are demand the cluster has accepted but not yet queued)."""
        return sum(len(st[1]) for st in self._gang_staging.values())

    def _evict_bound(
        self,
        key_str: str,
        *,
        into: PendingPod | None = None,
        adjust: bool = True,
        count_eviction: bool = True,
        path: str = "evict",
    ) -> PendingPod | None:
        """CAS a bound pod's stored object back to pending and undo its
        host-mirror accounting — the eviction half of preemption and of
        gang all-or-none release.

        The byte-level inverse of the bind: a spliced object is
        un-spliced (stored bytes return EXACTLY to their pre-bind
        encoding), anything else takes the JSON path.  The freed row is
        marked dirty so the next sync re-uploads host truth — in-flight
        waves keep their pipedream guarantees (a reclaimed row is never
        aliased: rows are not removed here, only their usage shrinks,
        which is the conservative direction for any wave in flight).

        Returns ``(evicted, rec)``: ``evicted`` reports whether the
        bind was actually reverted (callers MUST account on this flag —
        a post-eviction deletion still reverted the bind even though no
        requeue record exists); ``rec`` is the requeue-ready PendingPod
        at the post-eviction revision (``into`` refreshed in place when
        given), or None when there is nothing left to requeue (already
        unbound, deleted, or a persistent concurrent writer — the watch
        stream settles whatever remains).  The CAS retries a few times
        against fresh revisions so a racing status writer cannot leave
        a gang member half-released.  ``adjust=False`` is for
        wave-local gang release, where the caller rolls the device
        constraint commit back through the wave's own failed-mask
        instead.
        """
        rec = self._bound.get(key_str)
        if rec is None:
            return False, None
        node_name, cpu, mem, zone, region, keep = rec[:6]
        ns, name = key_str.split("/", 1)
        kb = pod_key(ns, name)
        ok = False
        for _attempt in range(3):
            cur = self.store.get(kb)
            if cur is None:
                return False, None
            value = unsplice_node_name(cur.value)
            if value is None:
                try:
                    obj = json.loads(cur.value)
                except Exception:
                    _DECODE_ERRORS.inc(kind="pod")
                    log.exception(
                        "undecodable bound pod at eviction; skipping"
                    )
                    return False, None
                obj.get("spec", {}).pop("nodeName", None)
                value = json.dumps(obj, separators=(",", ":")).encode()
            ok, _, _ = self._fenced_cas(
                kb, value, required_mod=cur.mod_revision, path=path
            )
            if ok:
                break
        if not ok:
            return False, None
        self._bound.pop(key_str, None)
        self._victims_drop(key_str, node_name)
        if node_name in self.host._row_of:
            self.host.remove_pod(node_name, cpu, mem)
            self._dirty_rows.add(self.host.row_of(node_name))
        if adjust and keep is not None and self.constraints is not None:
            self._pending_adjusts.append((keep, node_name, zone, region, -1))
        if count_eviction:
            note_eviction()
        fresh = self.store.get(kb)
        if fresh is None:
            # Deleted between the CAS and the re-get: the bind WAS
            # reverted; there is just nothing to requeue.
            return True, None
        p = into
        if p is None:
            pod = decode_pod(fresh.value, self.tracker)
            p = PendingPod(
                pod, fresh.mod_revision, time.perf_counter(),
                cpu_milli=pod.cpu_milli, mem_kib=pod.mem_kib,
                key_str=key_str, raw=fresh.value, key_bytes=kb,
                priority=pod.priority,
            )
        else:
            p.mod_revision = fresh.mod_revision
            p.raw = fresh.value
        self._queued_keys.add(key_str)
        return True, p

    def _preempt_eligible(self, p: PendingPod) -> bool:
        """Cheap gates before any preemption work happens for a pod."""
        tn = self.tenancy
        return (
            tn is not None
            and tn.policy.preempt_enabled
            and p.priority >= tn.policy.preempt_min_priority
            and p.attempts + 1 >= tn.policy.preempt_after_attempts
        )

    def _victims_index(self) -> _VictimRows:
        """Per-wave view of all preemptable bound pods grouped by row —
        built at most ONCE per wave from the incrementally-maintained
        by-node index (select_preemption applies the per-preemptor
        priority filter itself).  Gang-bound pods were excluded at
        insert time: evicting one member would strand its gang bound —
        the exact partial state gangs exist to prevent.  The current
        bind sequence fences the view: this wave's own preemption
        binds (noted later) never become victims within the wave."""
        return _VictimRows(
            self._victims_by_node, self.host._row_of, self._bind_seq,
        )

    def _victims_index_full(self) -> dict[int, list[Victim]]:
        """The pre-megarow full ``_bound.items()`` scan, kept as the
        differential reference: the incremental index must materialize
        to exactly this (tests/test_megarow.py gates it under a
        preemption drill).  Never called on the wave path."""
        victims_by_row: dict[int, list[Victim]] = {}
        row_of = self.host._row_of
        for key, rec in self._bound.items():
            prio, seq, tenant, gang = rec[6:]
            if gang:
                continue
            node_name = rec[0]
            row = row_of.get(node_name)
            if row is None:
                continue
            victims_by_row.setdefault(row, []).append(Victim(
                key, node_name, row, rec[1], rec[2], prio, seq, tenant,
            ))
        return victims_by_row

    def _try_preempt(
        self, p: PendingPod, victims_by_row: _VictimRows
    ) -> bool:
        """Preemption for a pod the wave found no feasible row for:
        select victims (tenancy/preempt.py — lowest priority first,
        other-tenant before same-tenant, newest bind first; gang-bound
        pods never selected), evict them through the store CAS +
        dirty-row machinery, bind the preemptor host-side on the
        cleared node (argmax-free: the selected node IS the placement,
        a pure function of the host mirror, which is what makes the
        drill's byte-identical replay possible), and requeue every
        victim.  ``victims_by_row`` is the caller's per-wave index
        (_victims_index); successfully evicted victims are removed from
        it so later preemptors in the same wave see current state.
        Returns True when the preemptor bound."""
        tn = self.tenancy
        pod = p.ensure_pod()
        tenant = tenant_of_pod(pod)
        nodes = self._fallback_nodes()
        if not nodes:
            return False
        host = self.host
        usage = {
            row: (
                int(host.cpu_req[row]), int(host.mem_req[row]),
                int(host.pods_req[row]),
            )
            for row, _ in nodes
        }
        choice = select_preemption(
            pod, tenant, p.priority, nodes, usage, victims_by_row,
        )
        if choice is None:
            return False
        if tn.policy.log_preemptions and len(self.preempt_log) < 1024:
            self.preempt_log.append({
                "pod": p.key_str,
                "priority": p.priority,
                "tenant": tenant,
                "node": choice.node,
                "row": choice.row,
                "victims": [v.key for v in choice.victims],
                "usage": {str(r): list(u) for r, u in usage.items()},
                "candidates": {
                    str(r): [dataclasses.astuple(v) for v in vs]
                    for r, vs in victims_by_row.items()
                },
            })
        tracer = self._tracer
        for v in choice.victims:
            evicted, rec = self._evict_bound(v.key, path="preempt")
            if not evicted:
                # A persistent concurrent writer beat the eviction CAS:
                # abort this attempt (capacity already freed stays
                # freed — the requeued victims rebind elsewhere); the
                # preemptor retries through the normal path.
                return False
            if rec is not None:
                if tracer.enabled:
                    # The evicted victim re-enters the lifecycle: a
                    # fresh chain anchored at its requeue time.
                    tracer.begin(
                        rec.key_str, rec.enqueued_at, source="evict",
                    )
                self.queue.append(rec)
            # The eviction already dropped this pod from the by-node
            # index (_evict_bound -> _victims_drop), and the per-wave
            # _VictimRows view reads that index live — later preemptors
            # in the same wave see current state with no manual repair.
        if not self._bind(p, choice.node):
            return False
        _BIND_LATENCY.observe(time.perf_counter() - p.enqueued_at)
        if tracer.enabled:
            # Host-side preemption bind: the chain closes here (the
            # wave's retire pass will find no live trace and skip it).
            tracer.finish(
                p.key_str, "bind", outcome="preempt",
                victims=len(choice.victims),
            )
        # The device never committed this bind: same repair contract as
        # the breaker fallback — dirty the row, queue the constraint
        # correction a device commit would have applied.
        self._dirty_rows.add(choice.row)
        if self.constraints is not None:
            rec = self._bound.get(p.key_str)
            if rec is not None and rec[5] is not None:
                self._pending_adjusts.append(
                    (rec[5], rec[0], rec[3], rec[4], 1)
                )
        return True

    def _wave_fail(self, p: PendingPod) -> None:
        """Per-pod wave failure: gang members defer to the gang's
        all-or-none settlement (_resolve_gangs requeues the group as a
        unit); everything else takes the normal retry/backoff path."""
        if self.tenancy is not None and p.gang_id:
            return
        self._retry(p)

    def _resolve_gangs(self, batch_pods, bound_ok, rows, failed) -> int:
        """All-or-none gang settlement at wave retire: a gang with every
        member bound is admitted; any failure releases every provisional
        bind (store CAS back to pending, host accounting undone) and
        requeues the gang as a unit — partial capacity never survives
        the wave-epoch window this wave retired in.  Returns the number
        of reverted binds (the caller subtracts them from its bound
        count so drivers' ledgers stay truthful).

        ``rows`` distinguishes device-committed binds (row >= 0: the
        wave's constraint commit is rolled back via ``failed``) from
        host-side preemption binds (row < 0: rolled back through the
        queued-adjust path, mirroring the +1 the preempt bind queued).
        """
        if self.tenancy is None or not self.tenancy.policy.gang_enabled:
            return 0
        gangs: dict[str, list[int]] = {}
        for i, p in enumerate(batch_pods):
            if p.gang_id:
                gangs.setdefault(p.gang_id, []).append(i)
        reverted = 0
        for idxs in gangs.values():
            if all(bound_ok[i] for i in idxs):
                note_gang("bound")
                continue
            members = []
            for i in idxs:
                p = batch_pods[i]
                if bound_ok[i]:
                    device_committed = bool(rows[i] >= 0)
                    evicted, _rec = self._evict_bound(
                        p.key_str, into=p,
                        adjust=not device_committed,
                        count_eviction=False,
                    )
                    if evicted:
                        # Settle on the FLAG, not the requeue record: a
                        # member deleted right after the eviction CAS
                        # still had its bind (and constraint commit)
                        # reverted and must not stay counted as bound.
                        reverted += 1
                        bound_ok[i] = False
                        if device_committed:
                            failed[i] = True
                    elif p.key_str in self._bound:
                        # Eviction persistently lost: the member stays
                        # bound — keep it OUT of the requeue so the
                        # all-or-none contract degrades loudly instead
                        # of double-scheduling a still-bound pod.
                        log.warning(
                            "gang member %s could not be released "
                            "(eviction CAS lost); leaving it bound",
                            p.key_str,
                        )
                        continue
                members.append(p)
            self._requeue_gang(members)
        return reverted

    def _requeue_gang(self, members: list[PendingPod]) -> None:
        """Requeue a failed gang as a unit: refresh every member from
        the store (same contract as _retry — a stale revision or an
        external bind must not ride into the next wave), then either
        park the whole gang unschedulable (retry budget spent) or heap
        it for a shared backoff and contiguous re-entry."""
        alive: list[PendingPod] = []
        for p in members:
            p.attempts += 1
            cur = self.store.get(p.key_bytes)
            if cur is None:
                self._queued_keys.discard(p.key_str)
                continue
            fresh = decode_pod(cur.value, self.tracker)
            if fresh.node_name:
                # Bound externally while we were settling: theirs now.
                self._queued_keys.discard(p.key_str)
                continue
            p.pod = fresh
            p.cpu_milli = fresh.cpu_milli
            p.mem_kib = fresh.mem_kib
            p.mod_revision = cur.mod_revision
            p.raw = cur.value
            p.priority = fresh.priority
            alive.append(p)
        if not alive:
            return
        pol = self.retry_policy
        worst = max(p.attempts for p in alive)
        if worst >= pol.max_attempts:
            for p in alive:
                if self._tracer.enabled:
                    self._trace_gaveup.add(p.key_str)
                _PODS_SCHEDULED.inc(outcome="unschedulable")
                note_give_up("coordinator.bind")
                self.unschedulable[p.key_str] = p.ensure_pod()
                # Keys stay held: the eviction echo of a released
                # provisional bind must not resurrect a parked gang
                # member as a plain pod (deletion still clears the key).
                self._queued_keys.add(p.key_str)
            note_gang("parked")
            return
        for p in alive:
            _PODS_SCHEDULED.inc(outcome="retry")
            note_retry("coordinator.bind")
            self._queued_keys.add(p.key_str)
        self._backoff_seq += 1
        heapq.heappush(self._gang_parked, (
            time.perf_counter() + pol.delay_for(worst, self._retry_rng),
            self._backoff_seq, alive,
        ))
        note_gang("requeued")

    def _encoder_for(self, n: int) -> PodBatchHost:
        """Smallest power-of-two batch bucket holding n pods (clamped to
        pod_spec.batch, which need not be a power of two)."""
        if not self.adaptive_batch:
            return self.encoder
        if self.loadshed is not None and self.loadshed.degraded:
            # Overload: widen the batch window.  Small buckets buy p50
            # latency at the cost of waves-per-pod — exactly the wrong
            # trade while the queue is the problem.
            return self.encoder
        b = self.min_batch
        while b < n:
            b <<= 1
        if b > self.pod_spec.batch:
            return self.encoder
        enc = self._encoders.get(b)
        if enc is None:
            enc = HotPodBatchHost(
                dataclasses.replace(self.pod_spec, batch=b),
                self.table_spec, self.host.vocab,
                cache=self.encode_cache,
            )
            self._encoders[b] = enc
        return enc

    def _release_backoff(self) -> None:
        """Move retrying pods (and whole parked gangs) whose backoff has
        expired into the queue; gang members re-enter contiguously so
        they still ride one wave."""
        if not self._backoff and not self._gang_parked:
            return
        now = time.perf_counter()
        while self._backoff and self._backoff[0][0] <= now:
            _, _, p = heapq.heappop(self._backoff)
            self.queue.append(p)
        while self._gang_parked and self._gang_parked[0][0] <= now:
            _, _, members = heapq.heappop(self._gang_parked)
            self.queue.extend(members)

    def backoff_wait_s(self) -> float | None:
        """Seconds until the earliest parked retry (pod or gang) is due
        (None when nothing is backing off) — drivers idle-wait on this
        instead of spinning cycles against an empty queue."""
        heads = []
        if self._backoff:
            heads.append(self._backoff[0][0])
        if self._gang_parked:
            heads.append(self._gang_parked[0][0])
        if not heads:
            return None
        return max(0.0, min(heads) - time.perf_counter())

    def _take_batch(self):
        """Pop and encode up to one batch of pending pods; (None, None)
        when the queue is empty.  A feed-staged batch (encoded in the
        worker while the last wave was in flight) is claimed first; the
        claim fails closed — queue prefix changed, vocab generation
        moved, worker error — and the inline cached encode covers it."""
        with self._stage("take"):
            self._release_backoff()
            if not self.queue:
                return None, None
            batch_pods: list[PendingPod] = []
            cur_gang = ""
            while self.queue and len(batch_pods) < self.pod_spec.batch:
                head = self.queue[0]
                if (
                    head.gang_id
                    and head.gang_id != cur_gang
                    and head.gang_size > self.pod_spec.batch - len(batch_pods)
                ):
                    # A gang never splits across a batch boundary: close
                    # the batch early and let the gang open the next wave
                    # whole.
                    break
                cur_gang = head.gang_id
                batch_pods.append(self.queue.popleft())
            if not batch_pods:
                return None, None
            # graftlint: disable=hotfeed-no-per-pod-python (O(pods) set bookkeeping for popped keys)
            for p in batch_pods:
                self._queued_keys.discard(p.key_str)
        tracer = self._tracer
        tr_on = tracer.enabled
        if tr_on:
            t_pop = time.perf_counter()
            hits0, miss0 = cache_counts()
        claimed = False
        with self._stage("encode"):
            batch = None
            if self._feed is not None:
                batch = self._feed.claim(
                    batch_pods, self.host.vocab.feed_generation()
                )
                claimed = batch is not None
            if batch is None:
                batch = encode_batch(
                    self._encoder_for(len(batch_pods)), batch_pods
                )
        if tr_on:
            t_enc = time.perf_counter()
            hits1, miss1 = cache_counts()
            path = "feed" if claimed else "inline"
            dh, dm = hits1 - hits0, miss1 - miss0
            # graftlint: disable=hotfeed-no-per-pod-python (behind the tracer.enabled guard; O(pods) span bookkeeping on sampled runs only)
            for p in batch_pods:
                tracer.emit(
                    p.key_str, "queue_wait", t=t_pop, attempts=p.attempts
                )
                tracer.emit(
                    p.key_str, "encode", t=t_enc, path=path,
                    cache_hits=dh, cache_misses=dm,
                )
        return batch_pods, batch

    def _next_window(self, rows: int) -> int:
        i = self._window_i
        self._window_i += 1
        nodes = self._window_nodes
        if self.mesh is None:
            nodes = window_rows_of(self.host.high_water, nodes, self.chunk, rows)
        return sample_offset_for(i, nodes, rows)

    def _active_knobs(self):
        """(profile, sample_rows) for the next wave: the configured pair
        when HEALTHY, the degraded pair (filter-only constraint plugins,
        shrunken score window) while the controller reports pressure."""
        if self.loadshed is not None and self.loadshed.degraded:
            self.loadshed.note_degraded_cycle()
            return self._profile_degraded, self._sample_rows_degraded
        return self.profile, self._sample_rows

    def _built_step_for(self, batch, profile, sample_rows):
        """The packed wave to dispatch: ``batch`` itself, or, where no
        wave with its field groups has run yet but one with a wider set
        has, ``batch`` packed with that set (the smallest such), so that
        it runs a step already built instead of compiling one of its own
        (a few minutes, for the combined Mosaic kernel).  The groups it
        lacks go in as the zeros an absent group unpacks to
        (hotfeed.widen_packed), so the step sees the values its own would
        have seen and binds what it would have bound: only which
        executable runs changes.  The wave that needs this is the one of
        a few pods the gate or the skew sent back, whose shapes are a
        part of the mix."""
        built = self._built_groups.setdefault((profile, sample_rows), set())
        if batch.groups in built:
            return batch
        wider = [g for g in built if batch.groups < g]
        if not wider:
            built.add(batch.groups)
            return batch
        return widen_packed(batch, min(wider, key=lambda g: (len(g), sorted(g))))

    def _launch(self, batch_pods, batch):
        """Enqueue the device step for an encoded batch (async — no
        device→host transfer is forced).  Faultline hook
        ``coordinator.cycle``/``dispatch`` fires here: ``slow_cycle`` /
        ``delay`` lengthen the cycle (feeding the loadshed latency
        signal); every failure kind — ``stall`` is the canonical one —
        raises before the device is touched, so the caller's breaker
        accounting sees a clean dispatch failure with no state to roll
        back."""
        t_start = time.perf_counter()
        with self._stage("prep"):
            if faultline.active_injector().plan.faults:
                d = faultline.decide("coordinator.cycle", "dispatch")
                if d is not None:
                    if d.kind in ("delay", "slow_cycle"):
                        time.sleep(d.delay_s)
                    else:
                        raise faultline.InjectedFault(d)
            profile, sample_rows = self._active_knobs()
            batch = self._built_step_for(batch, profile, sample_rows)
            self.key, subkey = jax.random.split(self.key)
            probe_ptr = None
            if self._donate and self._donation_inplace is None:
                # One-time donation probe (first wave): did the runtime
                # alias the donated hot planes in place?  Reading the
                # output pointers below syncs on that wave once — never
                # again.
                try:
                    probe_ptr = donation_probe(self.table)
                except Exception:  # graftlint: disable=broad-except (probe is evidence-only; any exotic array type just reports inplace=no)
                    self._donation_inplace = False
        with self._stage("device"):
            self.table, self.constraints, asg, rows_dev = schedule_batch_packed(
                self.table, batch, subkey,
                profile=profile, constraints=self.constraints,
                chunk=self.chunk, k=self.k, backend=self.backend,
                sample_rows=sample_rows,
                sample_offset=(
                    self._next_window(sample_rows) if sample_rows else 0
                ),
                row_mask=self._row_mask_dev,
                mesh=self.mesh,
                donate=self._donate,
                in_wave_skew=self.in_wave_skew,
            )
        if probe_ptr is not None:
            try:
                self._donation_inplace = donation_inplace(
                    self.table, probe_ptr
                )
            except Exception:  # graftlint: disable=broad-except (probe is evidence-only)
                self._donation_inplace = False
        if self._donate:
            _DONATION.inc(
                inplace="yes" if self._donation_inplace else "no"
            )
        _WAVES.inc(kernel=candidates_kernel(
            self.backend, batch.groups, self.constraints is not None,
        ))
        if self.mesh is not None:
            _MESH_WAVES.inc(merge="zone" if self.in_wave_skew else "topk")
        # Start the device->host copy of the bind decision now: by the
        # time _complete runs (a drain + encode later), the bytes are
        # already on the host and device_get returns without waiting
        # on the transfer.
        try:
            rows_dev.copy_to_host_async()
            asg.settled.copy_to_host_async()
            if asg.unbound is not None:
                asg.unbound.copy_to_host_async()
        # Best-effort prefetch: some array types/backends simply lack the
        # async copy; the sync device_get in _complete is the fallback.
        except Exception:  # graftlint: disable=broad-except
            pass
        # begin_wave stamps the snapshot epoch AFTER the dispatch above:
        # rows removed from here on quarantine until this wave retires.
        wave = Wave(
            batch_pods, batch, asg, rows_dev, t_start,
            epoch=self.host.begin_wave(),
            depth=len(self._inflights) + 1,
        )
        tracer = self._tracer
        if tracer.enabled:
            # Encode end -> dispatch: the pipeline-slot wait (in the
            # pipelined cycle this includes retiring the oldest wave).
            for p in batch_pods:
                tracer.emit(p.key_str, "dispatch_wait", t=t_start)
        return wave

    def _loadshed_tick(self) -> None:
        """Feed the health controller one cycle's signals (no-op without
        a controller).  Runs after the intake drains so queue depth is
        current, before _take_batch so this wave already schedules with
        the state the signals imply."""
        ls = self.loadshed
        if ls is None:
            return
        conflicts = _PODS_SCHEDULED.value(outcome="conflict")
        resyncs = _RESYNCS.value()
        ls.tick(Signals(
            # Staged gang members are accepted demand too — a thousand
            # half-assembled gangs must register as load, not hide.
            queue_depth=(
                len(self.queue) + self._external_pending()
                + self._gang_staged()
            ),
            backoff_depth=(
                len(self._backoff)
                + sum(len(m) for _, _, m in self._gang_parked)
            ),
            conflicts=int(conflicts - self._sig_conflicts),
            resyncs=int(resyncs - self._sig_resyncs),
            cycle_s=self._last_cycle_s,
        ))
        self._sig_conflicts = conflicts
        self._sig_resyncs = resyncs
        if self.tenancy is not None:
            # Refill the per-tenant admission buckets: this cycle's
            # admit budget is one wave's worth of pods, split by weight
            # over the tenants that actually offered load.
            self.tenancy.admission.tick(capacity=self.pod_spec.batch)

    def _requeue_front(self, batch_pods) -> None:
        """Put an un-launched batch back at the head of the queue (the
        pods were popped by _take_batch but never reached a device wave,
        so no accounting exists to undo)."""
        for p in reversed(batch_pods):
            self._queued_keys.add(p.key_str)
            self.queue.appendleft(p)

    def _take_pods(self, n: int) -> list[PendingPod]:
        """Pop up to ``n`` pending pods WITHOUT encoding them — the
        open-breaker fallback path never touches the device, so paying
        a full-batch encode only to discard it would tax exactly the
        cycles where the system is already struggling."""
        self._release_backoff()
        pods: list[PendingPod] = []
        cur_gang = ""
        rotated: set[str] = set()
        while self.queue and len(pods) < n:
            head = self.queue[0]
            if (
                head.gang_id
                and head.gang_id != cur_gang
                and head.gang_size > n - len(pods)
            ):
                if pods or head.gang_id in rotated:
                    break
                # Emergency lane: a gang that can NEVER fit this cap
                # (fallback_batch < gang size) must not wedge the queue
                # behind it for the whole breaker-open window — rotate
                # it to the back intact and keep draining.  Once per
                # gang per call, so a gang-only queue still terminates.
                rotated.add(head.gang_id)
                moved: list[PendingPod] = []
                while self.queue and self.queue[0].gang_id == head.gang_id:
                    moved.append(self.queue.popleft())
                self.queue.extend(moved)
                continue
            cur_gang = head.gang_id
            p = self.queue.popleft()
            self._queued_keys.discard(p.key_str)
            pods.append(p)
        return pods

    def _fallback_nodes(self) -> list:
        """Decoded ``(row, NodeInfo)`` candidates for the breaker-open
        oracle fallback, ascending row (ties break earlier-row like the
        device path's earlier-index rule).

        Built from the incremental ``_node_infos`` index (maintained at
        the watch-drain decode sites), so a node-gen bump costs
        O(changed rows), not an O(N) store decode per generation.  Rows
        the index has never seen — the bulk-ingest remainder from
        bootstrap/resync — are seeded from ONE store decode, paid once
        ever (per resync), after which churn keeps the index current
        event by event.  Differentially gated against the full decode
        (``_fallback_nodes_full``) in tests/test_loadshed.py."""
        if (
            self._fallback_cache is not None
            and self._fallback_cache[0] == self._node_gen
        ):
            return self._fallback_cache[1]
        row_of = self.host._row_of
        infos = self._node_infos
        missing = {name for name in row_of if name not in infos}
        if missing:
            kvs, _ = list_prefix(self.store, NODES_PREFIX)
            for kv in kvs:
                name = kv.key[len(NODES_PREFIX):].decode()
                if name not in missing:
                    continue
                try:
                    infos[name] = decode_node(kv.value)
                except Exception:
                    # Same quarantine contract as the watch drains: one
                    # malformed object must not silently shrink the
                    # emergency fallback's candidate set.
                    _DECODE_ERRORS.inc(kind="node")
                    log.exception(
                        "undecodable node in fallback seed; skipping"
                    )
        out = []
        mask = self._row_mask_np
        for name, row in row_of.items():
            nd = infos.get(name)
            if nd is None:
                continue
            if mask is not None and not mask[row]:
                continue
            out.append((row, nd))
        out.sort(key=lambda t: t[0])
        self._fallback_cache = (self._node_gen, out)
        return out

    def _fallback_nodes_full(self) -> list:
        """The pre-watchplane full store decode, kept UNCACHED as the
        differential oracle for the incremental index (the victims-
        index precedent: megarow's ``_victims_index_full``)."""
        out = []
        kvs, _ = list_prefix(self.store, NODES_PREFIX)
        mask = self._row_mask_np
        for kv in kvs:
            try:
                nd = decode_node(kv.value)
            except Exception:
                _DECODE_ERRORS.inc(kind="node")
                log.exception("undecodable node in fallback list; skipping")
                continue
            row = self.host._row_of.get(nd.name)
            if row is None:
                continue
            if mask is not None and not mask[row]:
                continue
            out.append((row, nd))
        out.sort(key=lambda t: t[0])
        return out

    def _fallback_schedule(self, batch_pods) -> int:
        """Breaker-open path: bind a small batch through the host-side
        oracle scheduler (k8s1m_tpu/oracle) so scheduling never fully
        stops while the device is wedged.  Greedy and sequential against
        the live host usage — for a given snapshot the choices are a
        pure function of the pod order (argmax oracle_score, earlier row
        wins ties), which is what makes the drill's byte-identical
        replay check possible.  Pods past ``fallback_batch`` go back to
        the queue head; binds mark their row dirty so the device table
        learns the usage at the next sync (the device never saw these
        binds commit)."""
        cap = (
            self.breaker.config.fallback_batch
            if self.breaker is not None else len(batch_pods)
        )
        take = batch_pods[:cap]
        self._requeue_front(batch_pods[len(take):])
        nodes = self._fallback_nodes()
        host = self.host
        weights = (
            self.profile.least_allocated, self.profile.balanced_allocation,
            self.profile.taint_toleration, self.profile.node_affinity,
        )
        nbound = 0
        bound_ok = np.zeros(len(take), bool)
        with self._stage("fallback"):
            for pi, p in enumerate(take):
                pod = p.ensure_pod()
                best_row, best_score, best_name = -1, -1, None
                for row, nd in nodes:
                    req = (
                        int(host.cpu_req[row]), int(host.mem_req[row]),
                        int(host.pods_req[row]),
                    )
                    if not oracle_feasible(nd, pod, req):
                        continue
                    s = oracle_score(
                        nd, pod, req,
                        taint_slots=self.table_spec.taint_slots,
                        weights=weights,
                    )
                    if s > best_score:
                        best_row, best_score, best_name = row, s, nd.name
                if best_name is None or not self._bind(p, best_name):
                    self._wave_fail(p)
                    continue
                nbound += 1
                bound_ok[pi] = True
                FALLBACK_BINDS.inc()
                _BIND_LATENCY.observe(time.perf_counter() - p.enqueued_at)
                tracer = self._tracer
                if tracer.enabled:
                    # Breaker-open oracle bind: no wave ever launched,
                    # so the whole journey settles in one bind span.
                    tracer.finish(p.key_str, "bind", outcome="fallback")
                # The device table never committed this bind: dirty the
                # row so the next sync re-uploads the host truth, and
                # queue the constraint-count correction a device commit
                # would have applied.
                self._dirty_rows.add(best_row)
                if self.constraints is not None:
                    rec = self._bound.get(p.key_str)
                    if rec is not None and rec[5] is not None:
                        self._pending_adjusts.append(
                            (rec[5], rec[0], rec[3], rec[4], 1)
                        )
            # Fallback binds are host-side (no device commit): gang
            # settlement releases through the queued-adjust path.
            nbound -= self._resolve_gangs(
                take, bound_ok,
                np.full(len(take), -1, np.int64),
                np.zeros(len(take), bool),
            )
            tracer = self._tracer
            if tracer.enabled:
                # No wave-retire pass runs on the breaker path: close
                # the chains of pods that spent their retry budget here.
                for p in take:
                    if p.key_str in self._trace_gaveup:
                        self._trace_gaveup.discard(p.key_str)
                        tracer.finish(
                            p.key_str, "requeue",
                            outcome="unschedulable", attempts=p.attempts,
                        )
        return nbound

    def _complete(self, inflight: Wave) -> int:
        """Bind half: sync the assignment to host, CAS the binds back,
        roll back conflicts (CAS losses, rows tombstoned mid-flight).

        The binds are retired by the wave, in columns (_bind_wave); only
        the pods that are exceptions run per-pod code.  So in a wave that
        has exceptions the pods take their ``_bind_seq`` numbers in three
        runs, each in wave order, instead of interleaved: the pods bound
        one by one ahead of the batch CAS, then the columnar pods, then
        the batch's exceptions.  The order among the binds of one wave
        decides only which of two same-priority victims goes first."""
        batch_pods, batch, asg, rows_dev, t_start = (
            inflight.batch_pods, inflight.batch, inflight.asg,
            inflight.rows_dev, inflight.t_start,
        )
        with self._stage("sync_out"):
            # ONE device_get per wave: each fetch is a device->host
            # sync, so the bind decision comes back as a single packed
            # i32[B] (-1 = unbound) — and three sums with it: what
            # settled the wave's pods; where the wave counted skew or ran
            # the inter-pod gate, four more: why its unbound pods stayed
            # so, with no look at any one pod.
            node_row, settled, unbound = jax.device_get(
                (rows_dev, asg.settled, asg.unbound)
            )
            *by_path, rounds = settled.tolist()
            for path, n in zip(SETTLED_BY, by_path):
                if n:
                    _ASSIGN_PODS.inc(n, path=path)
            if rounds:
                _ASSIGN_ROUNDS.inc(rounds)
            if unbound is not None:
                for reason, n in zip(UNBOUND_REASONS, unbound.tolist()):
                    if n:
                        _WAVE_UNBOUND.inc(n, reason=reason)
        t_sync = time.perf_counter()

        failed = np.zeros(batch.batch, bool)
        # Per-pod settled outcome (True = the bind stuck), consumed by
        # the gang all-or-none settlement after the bind stage.
        bound_ok = np.zeros(batch.batch, bool)
        with self._stage("bind"):
            rows = node_row[: len(batch_pods)]
            # No-feasible-row pods are settled AFTER the wave's binds
            # land in the host mirror: preemption's usage snapshot must
            # include this wave's own placements, or the preemptor can
            # overcommit a node the wave is about to fill.
            nofit = np.nonzero(rows < 0)[0].tolist()
            nbound = self._bind_wave(batch_pods, rows, bound_ok, failed)
            # Preemption pass — after every CAS bind above, so the host
            # mirror (and so the feasibility snapshot) reflects this
            # wave's placements.  The victims index is built lazily, at
            # most once per wave, and kept current across this wave's
            # preemptions.
            if nofit:
                nbound += self._settle_nofit(batch_pods, nofit, bound_ok)
            # Gang all-or-none settlement — inside the wave-epoch window
            # (before this retire returns): partially-bound gangs release
            # every provisional bind and requeue whole.  Runs before the
            # failed-mask rollback below so released device-committed
            # binds ride the same signed constraint scatter.
            nbound -= self._resolve_gangs(batch_pods, bound_ok, rows, failed)
        with self._stage("settle"):
            if failed.any() and self.constraints is not None:
                m = jnp.asarray(failed)
                self.constraints = self._adjust(
                    self.constraints, commit_fields_np(batch.fields),
                    asg.node_row, asg.zone, asg.region, m, m, sign=-1,
                )
            if self._tracer.enabled:
                self._trace_retire(inflight, rows, bound_ok, t_sync)

            self._last_cycle_s = time.perf_counter() - t_start
            # This wave retired: rows removed at or before the oldest
            # still-in-flight wave's launch are past their aliasing hazard.
            if self._inflights:
                self.host.release_rows(self._inflights[0].epoch)
            else:
                self.host.release_rows(None)
                # Every wave a mid-flight scatter could have clobbered has
                # now retired and repaired; stop tracking those rows.
                self._midflight_rows.clear()
            self.depth_timer.set_level(len(self._inflights))
            if self.breaker is not None:
                # Success is a RETIRED wave — the device returned data —
                # not an accepted dispatch (async dispatch accepts work a
                # wedged runtime never finishes).  A half-open probe still
                # resolves promptly: while the breaker is not CLOSED,
                # step() quiesces the pipeline, which completes the probe
                # right here.
                self.breaker.record_success()
            # Nothing reads the retired wave's records from here on: they
            # die inside this stage, and not unnamed as the caller's frame
            # drops the wave (a full wave's records take ~0.3 ms to free).
            inflight.batch_pods = batch_pods = None
        return nbound

    def _settle_nofit(self, batch_pods, nofit, bound_ok) -> int:
        """The pods the device gave no row (``nofit``, at least one),
        one by one: preempt for the pod if it may, else send it back for
        another wave (_wave_fail: _retry re-reads and re-decodes it).
        Returns the pods bound by preemption.  The span
        ``coord.bind.nofit``."""
        nbound = 0
        with self._stage("nofit", "bind"):
            vindex = None
            for i in nofit:
                p = batch_pods[i]
                if self._preempt_eligible(p):
                    if vindex is None:
                        vindex = self._victims_index()
                    if self._try_preempt(p, vindex):
                        bound_ok[i] = True
                        nbound += 1
                        continue
                self._wave_fail(p)
        return nbound

    def _bind_wave(self, batch_pods, rows, bound_ok, failed) -> int:
        """CAS every pod the device gave a row into the store and account
        the binds that stuck; returns their number.  ``bound_ok`` and
        ``failed`` are filled in place.

        One native call binds the whole wave: splice + CAS happen inside
        the store against the bytes it already holds (ms_bind_batch).
        The bookkeeping around it is done by the wave, in columns, for
        the pods that are plain — read from the wave itself: the store
        has ``bind_batch``, the record has an observed revision and no
        PodInfo (a fast-lane record: the tenant is its shape's or its
        namespace's, and what its bound record keeps is its shape), and
        no fault plan is installed.
        Every other pod is an exception and runs the per-pod code, over
        the exception indices only: webhook intake (no revision) and a
        store without ``bind_batch`` bind one by one through _bind ahead
        of the batch; decoded pods (constraints, gangs, every retried
        pod) ride the batch and are entered one by one after it; under a
        fault plan every pod draws its decision in wave order; CAS
        losses and BIND_INVALID answers are found by mask.

        The bind stage accounts for itself in five children, each a
        ``coord.bind.<child>`` span and a ``bind_<child>`` label once a
        wave (twice at most: _stage): ``gather`` (everything up to the
        CAS that is done by the wave), ``cas`` (the native call),
        ``account`` (everything after it that is done by the wave),
        ``per_pod`` (the two loops over the exceptions, each opened only
        where it has a pod) here, and ``nofit`` (_settle_nofit) beside
        them; _resolve_gangs is the stage's own time."""
        reached, columnar, nbound = self._bind_columns(
            batch_pods, rows, bound_ok, failed
        )
        if reached:
            _BIND_RETIRE.inc(columnar, lane="columnar")
            _BIND_RETIRE.inc(reached - columnar, lane="per_pod")
        return nbound

    def _bind_columns(
        self, batch_pods, rows, bound_ok, failed,
    ) -> tuple[int, int, int]:
        """_bind_wave's work.  Returns (pods the device gave a row,
        pods retired wholly in columns, pods bound)."""
        host = self.host
        with self._stage("gather", "bind"):
            bound_idx = np.nonzero(rows >= 0)[0]
            reached = bound_idx.size
            if not reached:
                return 0, 0, 0
            brows = rows[bound_idx]
            # Rows tombstoned while this wave was in flight: the node is
            # gone (quarantine guarantees no reuse before this retire, so
            # an invalid row can't alias a new node) — treat like a CAS
            # conflict: retry the pod, roll back the wave's optimistic
            # constraint commit.  No dirty-marking: the tombstone scatter
            # already uploaded the zeroed row.
            alive = host.valid[brows]
            if not alive.all():
                for i in bound_idx[~alive].tolist():
                    failed[i] = True
                    self._wave_fail(batch_pods[i])
                bound_idx = bound_idx[alive]
                brows = brows[alive]
            n = bound_idx.size
            if not n:
                return reached, 0, 0
            bound_l = bound_idx.tolist()
            wave = list(map(batch_pods.__getitem__, bound_l))
            (keys, mods, cpus, mems, key_strs, enqueued, prios, gangs,
             infos, shapes) = (list(map(get, wave)) for get in _WAVE_COLS)
            ids_l = host.name_id[brows].tolist()
            name_bytes = list(map(self._node_name_bytes().__getitem__, ids_l))
            names = list(map(host.vocab.node_names._to_val.__getitem__, ids_l))
            zones = host.zone[brows].tolist()
            regions = host.region[brows].tolist()

            # Hot path stays injection-free unless a plan is installed.
            inj_active = bool(faultline.active_injector().plan.faults)
            # sent: rides the batch CAS; plain: and is retired in columns.
            if getattr(self.store, "bind_batch", None) is not None:
                sent = ~_is_none(mods)
            else:
                sent = np.zeros(n, bool)
            plain = np.zeros(n, bool) if inj_active else sent & _is_none(infos)
            # Each shape with constraint increments (there is none while
            # the tracker holds no constraint), mapped to itself: what the
            # bound record of a fast-lane pod of it keeps (_constraintful).
            tr = self.tracker
            keeping = (
                {sh: sh for sh in set(shapes) if sh is not None and sh.keeps}
                if tr._spread or tr._affinity else {}
            )
            exceptions = np.nonzero(~plain)[0].tolist()

        def bind_singly(j: int, now: float) -> bool:
            """One pod through _bind, with what follows a bind there."""
            if not self._bind(wave[j], names[j]):
                return False
            bound_ok[bound_l[j]] = True
            _BIND_LATENCY.observe(now - enqueued[j])
            if int(brows[j]) in self._midflight_rows:
                # A mid-flight full scatter erased this wave's
                # device-side assume on the row; the host mirror
                # just learned the bind — re-upload repairs it.
                self._dirty_rows.add(int(brows[j]))
            return True

        def conflict(j: int) -> None:
            """CAS conflict: the device table already assumed this bind
            (commit_binds), but the host mirror — which is authoritative
            — was never incremented.  Marking the row dirty re-uploads
            the host values, undoing the device-side assume; the
            constraint-count commit is rolled back by the caller in one
            signed scatter."""
            self._dirty_rows.add(host.row_of(names[j]))
            failed[bound_l[j]] = True
            self._wave_fail(wave[j])

        nbound = 0
        if exceptions:
            with self._stage("per_pod", "bind"):
                for j in exceptions:
                    if sent[j]:
                        # One fault decision per CAS attempt: the batch's
                        # records are checked here (their CAS runs inside
                        # bind_batch); slow-path pods are checked inside
                        # _bind so they never consume two draws per attempt.
                        if inj_active and self._bind_fault():
                            sent[j] = False
                            conflict(j)
                    elif bind_singly(j, time.perf_counter()):
                        nbound += 1
                    else:
                        conflict(j)

        with self._stage("gather", "bind"):
            sent_j = np.nonzero(sent)[0]
            if not sent_j.size:
                return reached, 0, nbound
            pick = _picker(sent)
            entries = list(zip(pick(keys), pick(mods), pick(name_bytes)))
        with self._stage("cas", "bind"):
            answer = self._fenced_bind_batch(
                entries,
                self._pods_watch.id if self._bind_excludes else None,
            )
        with self._stage("account", "bind"):
            now = time.perf_counter()
            revs = np.zeros(n, np.int64)
            revs[sent_j] = np.asarray(answer, np.int64)
            ok = revs > 0

            ok_j = np.nonzero(ok)[0]
            if ok_j.size:
                bound_ok[bound_idx[ok_j]] = True
                # Duplicate rows (two pods on one node) accumulate
                # correctly under np.add.at.
                r = brows[ok_j]
                np.add.at(
                    host.cpu_req, r,
                    np.fromiter(cpus, host.cpu_req.dtype, n)[ok_j],
                )
                np.add.at(
                    host.mem_req, r,
                    np.fromiter(mems, host.mem_req.dtype, n)[ok_j],
                )
                np.add.at(host.pods_req, r, 1)
                nbound += ok_j.size
                _PODS_SCHEDULED.inc(ok_j.size, outcome="bound")
                _BIND_LATENCY.observe_many(
                    now - np.fromiter(enqueued, float, n)[ok_j]
                )
                if self._midflight_rows:
                    # Same repair as the slow path: rows a mid-flight
                    # full scatter clobbered get the host truth (now
                    # including this wave's binds) re-uploaded.
                    self._dirty_rows.update(
                        self._midflight_rows.intersection(r.tolist())
                    )

            col = plain & ok
            columnar = int(np.count_nonzero(col))
            if columnar:
                pick = _picker(col)
                k, on, cpu, mem, prio, gang = map(
                    pick, (key_strs, names, cpus, mems, prios, gangs)
                )
                seqs = range(
                    self._bind_seq + 1, self._bind_seq + columnar + 1
                )
                self._bind_seq += columnar
                sh = pick(shapes)
                tenants = _wave_tenants(k, sh)
                self._bound.update(zip(k, zip(
                    on, cpu, mem, pick(zones), pick(regions),
                    map(keeping.get, sh) if keeping
                    else itertools.repeat(None),
                    prio, seqs, tenants, gang,
                )))
                if self._track_victims:
                    for note in zip(
                        k, on, cpu, mem, prio, seqs, tenants, gang
                    ):
                        self._victims_note(*note)
            exceptions = np.nonzero(sent & ~col)[0].tolist()

        if not exceptions:
            return reached, columnar, nbound
        with self._stage("per_pod", "bind"):
            for j in exceptions:
                if not ok[j]:
                    if revs[j] != BIND_INVALID:
                        _PODS_SCHEDULED.inc(outcome="conflict")
                        conflict(j)
                    elif bind_singly(j, now):
                        nbound += 1
                    else:
                        conflict(j)
                    continue
                p = wave[j]
                pod = p.pod
                self._bind_seq += 1
                # bind_batch takes ANY pod with an observed revision,
                # decoded or not: a decoded PodInfo supplies the
                # label-aware tenant and is what its record keeps, a
                # fast-lane pod's shape knows whether its labels name
                # one and is what it keeps; failing both the key's
                # namespace IS the tenant.
                if pod is not None:
                    keep = pod if self._constraintful(pod) else None
                    tenant = tenant_of_pod(pod)
                else:
                    sh = p.shape
                    keep = keeping.get(sh)
                    tenant = (
                        sh.tenant if sh is not None else None
                    ) or tenant_of_key(p.key_str)
                self._bound[p.key_str] = (
                    names[j], p.cpu_milli, p.mem_kib, zones[j], regions[j],
                    keep, p.priority, self._bind_seq, tenant, p.gang_id,
                )
                self._victims_note(
                    p.key_str, names[j], p.cpu_milli, p.mem_kib,
                    p.priority, self._bind_seq, tenant, p.gang_id,
                )
        return reached, columnar, nbound

    def _trace_retire(self, inflight: Wave, rows, bound_ok, t_sync: float) -> None:
        """Wave-retire observability pass (runs only while tracing is
        enabled): close every sampled pod's span chain — the device
        span stamped with the wave's epoch and pipeline depth, the bind
        span with the settled outcome."""
        tracer = self._tracer
        if not tracer.enabled:
            return
        now = time.perf_counter()
        for i, p in enumerate(inflight.batch_pods):
            ok = bool(bound_ok[i])
            tracer.emit(
                p.key_str, "device", t=t_sync,
                wave_epoch=inflight.epoch, depth=inflight.depth,
            )
            if ok:
                tracer.finish(p.key_str, "bind", t=now, outcome="bound")
            else:
                tracer.emit(
                    p.key_str, "bind", t=now,
                    outcome="nofit" if rows[i] < 0 else "conflict",
                    attempts=p.attempts,
                )
                if p.key_str in self._trace_gaveup:
                    # Retry budget spent during this wave's settlement:
                    # close the chain HERE, after its device/bind spans.
                    self._trace_gaveup.discard(p.key_str)
                    tracer.finish(
                        p.key_str, "requeue",
                        outcome="unschedulable", attempts=p.attempts,
                    )

    def step(self) -> int:
        """One scheduling cycle; returns number of pods bound.

        With ``pipeline=True`` the returned count is the *previous*
        dispatch's binds: batch N's device work executes while the
        caller does its inter-step work (producers, kwok ticks), hiding
        the device→host sync latency.  Snapshot churn no longer drains
        the pipeline: capacity-only node deltas scatter on-stream while
        waves are in flight, removes tombstone into the wave-epoch
        quarantine, and the oldest wave is still completed BEFORE this
        step's sync+dispatch so its bind accounting lands in the host
        mirror ahead of the dirty-row re-upload the next launch
        consumes.  Call ``flush()`` (or ``run_until_idle``) to retire
        the tail.

        The whole step is the ``coord.step`` span, the root of the
        ``coord.<stage>`` spans (_stage) in a profiler trace.
        """
        with jax.profiler.TraceAnnotation("coord.step"):
            return self._step()

    def _step(self) -> int:
        if not self.pipeline:
            self._drain_external()
            self.drain_watches()
            self._sync_table()
            self._process_adjusts()
            self._loadshed_tick()
            if (
                self.breaker is not None
                and self.breaker.state != BREAKER_CLOSED
            ):
                self._release_backoff()
                if not self.queue:
                    return 0
                if not self.breaker.allow():
                    # Open: bind a small slice through the oracle —
                    # popped WITHOUT encoding (the wave would only be
                    # discarded).
                    return self._fallback_schedule(self._take_pods(
                        self.breaker.config.fallback_batch
                    ))
                # Half-open probe: fall through to a normal device wave.
            batch_pods, batch = self._take_batch()
            if batch_pods is None:
                return 0
            try:
                inflight = self._launch(batch_pods, batch)
            except Exception:
                if self.breaker is None:
                    raise
                log.exception("cycle dispatch failed; breaker accounting")
                self.breaker.record_failure()
                self._requeue_front(batch_pods)
                return 0
            if self._feed is not None:
                # Encode the NEXT full batch while _complete below waits
                # out the device round trip (the one overlap window the
                # unpipelined cycle has).
                self._feed.stage(self.queue, self.pod_spec.batch)
            return self._complete(inflight)
        # Pipelined: up to ``depth`` waves in flight, so each wave's
        # device compute AND its result-fetch round trip overlap the host
        # work of later cycles.  The snapshot mutates WITHOUT
        # retiring the pipeline (wave cadence decouples from watch
        # cadence):
        #  - pod events touch capacity accounting only;
        #  - capacity-only node deltas scatter feature columns into the
        #    live table (_dirty_caps), structural adds append past the
        #    high-water mark, and removes tombstone into the wave-epoch
        #    quarantine — all on-stream, no host sync (_drain_node_events);
        #  - _complete lands its bind accounting (and CAS-rollback dirty
        #    rows) in the host mirror before _sync_table re-uploads rows
        #    for the next launch.
        # Only resync (row mapping rebuilt), a tripped breaker, adaptive
        # partial buckets, and quarantine exhaustion still retire it —
        # each counted in pipeline_quiesce_total.
        done = self._deferred_binds
        self._deferred_binds = 0
        if self._nodes_watch.dropped or self._pods_watch.dropped:
            done += self._quiesce("resync")
            log.warning(
                "watch overflow (nodes dropped=%d pods dropped=%d); resyncing",
                self._nodes_watch.dropped, self._pods_watch.dropped,
            )
            self.resync()
        elif self._watch_fault():
            # Injected watch loss: quiesce the pipeline (resync mutates
            # the row->node mapping) and relist, same as an overflow.
            done += self._quiesce("resync")
            self.resync()
        self._drain_external()
        self._drain_pod_events()
        self._drain_node_events()
        self._loadshed_tick()
        if self.breaker is not None and self.breaker.state != BREAKER_CLOSED:
            # A tripped breaker serializes the pipeline: quiesce so (a)
            # no in-flight device wave can land placements computed
            # against pre-fallback usage after the oracle binds
            # host-side, and (b) the half-open probe resolves at its own
            # dispatch instead of starving behind the depth gate.
            done += self._quiesce("breaker")
            self._sync_table()
            self._process_adjusts()
            self._release_backoff()
            if not self.queue:
                return done
            if not self.breaker.allow():
                done += self._fallback_schedule(self._take_pods(
                    self.breaker.config.fallback_batch
                ))
                return done
            # Half-open probe: launched below through the normal path
            # (the pipeline is empty, so it dispatches this step).
        batch_pods, batch = self._take_batch()
        if len(self._inflights) >= (self.depth if batch_pods else 1):
            done += self._complete(self._inflights.pop(0))
        # After the retire, before the launch: the retired wave's bind
        # accounting and rollback rows are in the host mirror, so the
        # scatter the next launch consumes carries them.
        self._sync_table()
        self._process_adjusts()
        if batch_pods is not None:
            try:
                inflight = self._launch(batch_pods, batch)
            except Exception:
                if self.breaker is None:
                    raise
                log.exception("cycle dispatch failed; breaker accounting")
                self.breaker.record_failure()
                self._requeue_front(batch_pods)
                return done
            self._inflights.append(inflight)
            self.depth_timer.set_level(len(self._inflights))
            if self._feed is not None:
                # Wave N is in flight: peek (never pop) the next full
                # batch and let the worker encode it behind the device.
                self._feed.stage(self.queue, self.pod_spec.batch)
            if self.adaptive_batch and batch.batch < self.pod_spec.batch:
                # Light load (partial bucket): pipelining buys no
                # throughput — the queue is draining faster than it
                # fills — but holding the wave until the NEXT step adds
                # 1-2 extra wave times to every pod's latency.  This was
                # the round-4 "flat 288ms p50 at every sub-knee rate":
                # 3x the 82ms bucket-256 wave, not the wave itself.
                # Retire immediately; full buckets keep the deep
                # pipeline (saturation is where overlap pays).
                done += self._quiesce("adaptive")
        return done

    def flush(self) -> int:
        """Retire every in-flight pipelined batch.  Also surfaces any
        deferred bind credit (exhaustion/resync flushes) so a driver's
        final flush never under-reports."""
        done = self._deferred_binds
        self._deferred_binds = 0
        while self._inflights:
            done += self._complete(self._inflights.pop(0))
        return done

    def _quiesce(self, reason: str) -> int:
        """Retire the whole pipeline for a structural/control event and
        count it (no-op, uncounted, when nothing is in flight)."""
        if not self._inflights:
            return 0
        _PIPE_QUIESCE.inc(reason=reason)
        return self.flush()

    def _nodes_pending(self) -> int:
        """Queued node events.  No longer a quiesce trigger (node deltas
        apply while waves are in flight) — kept as the intake probe for
        drivers and tests.  Watchers without a cheap pending probe
        report whether the LAST drain actually applied anything, instead
        of a permanent 1 (which, when this gated the quiesce, collapsed
        the pipeline to depth-1 on every cycle)."""
        p = getattr(self._nodes_watch, "pending", None)
        return self._last_node_drain if p is None else p

    # ---- fenced store writes (ISSUE 9) ---------------------------------
    #
    # Every store put/CAS reachable from the bind/evict/preempt paths
    # MUST flow through these two funnels (enforced statically by the
    # graftlint ``fenced-store-write`` pass): they consult the reign's
    # LeaseFence before touching the store, so a deposed or paused
    # leader's in-flight waves retire into the ordinary conflict/requeue
    # machinery instead of landing writes behind the new leader.

    def _fence_admit(self, path: str) -> bool:
        f = self.fence
        if f is None or f.admit():
            return True
        _FENCE_REJECTED.inc(path=path)
        return False

    def _fenced_cas(self, key: bytes, value: bytes, *, required_mod: int,
                    path: str):
        """The bind/evict/preempt CAS funnel: shaped exactly like
        ``store.cas`` so a fence refusal reads as a CAS conflict — the
        one failure every caller already absorbs (requeue/backoff)."""
        if not self._fence_admit(path):
            return False, 0, None
        return self.store.cas(key, value, required_mod=required_mod)

    def _fenced_bind_batch(self, entries, watch_id=None):
        """The native-wave bind funnel: a fence refusal fails every
        entry as a conflict (rev 0) without touching the store."""
        if not self._fence_admit("bind"):
            return [0] * len(entries)
        if watch_id is not None:
            return self.store.bind_batch(entries, watch_id)
        return self.store.bind_batch(entries)

    def _bind(self, p: PendingPod, node_name: str) -> bool:
        """CAS spec.nodeName into the pod object; False on conflict
        (including a fence refusal — a deposed reign must not bind;
        every path below terminates in a ``_fenced_cas``, so the fence
        is consulted exactly once per store-write attempt)."""
        if self._bind_fault():
            return False
        key = p.key_bytes
        if p.mod_revision is not None and p.raw is not None:
            # Fast path: splice nodeName into the intake-revision bytes.
            # The CAS itself proves the object hasn't changed since, so
            # no re-read or JSON round trip is needed.
            value = splice_node_name(p.raw, node_name)
            if value is not None:
                ok, _, _ = self._fenced_cas(
                    key, value, required_mod=p.mod_revision, path="bind"
                )
                if not ok:
                    _PODS_SCHEDULED.inc(outcome="conflict")
                    return False
                self.host.add_pod(node_name, p.cpu_milli, p.mem_kib)
                self._note_bound(p.ensure_pod(), node_name, external=False)
                _PODS_SCHEDULED.inc(outcome="bound")
                return True
        cur = self.store.get(key)
        if cur is None:
            _PODS_SCHEDULED.inc(outcome="conflict")
            return False
        if p.mod_revision is None:
            # Webhook intake: no revision was observed at admission.  Bind
            # against the live revision — unless someone already bound it.
            obj = json.loads(cur.value)
            if obj.get("spec", {}).get("nodeName"):
                _PODS_SCHEDULED.inc(outcome="conflict")
                return False
            required = cur.mod_revision
        elif cur.mod_revision != p.mod_revision:
            _PODS_SCHEDULED.inc(outcome="conflict")
            return False
        else:
            # Intake revision still live but no raw bytes captured (the
            # native fast lane keeps PendingPod compact): splice into
            # the store's current bytes — same output as the raw-bytes
            # fast path above, no JSON round trip.
            value = splice_node_name(cur.value, node_name)
            if value is not None:
                ok, _, _ = self._fenced_cas(
                    key, value, required_mod=p.mod_revision, path="bind"
                )
                if not ok:
                    _PODS_SCHEDULED.inc(outcome="conflict")
                    return False
                self.host.add_pod(node_name, p.cpu_milli, p.mem_kib)
                self._note_bound(p.ensure_pod(), node_name, external=False)
                _PODS_SCHEDULED.inc(outcome="bound")
                return True
            obj = json.loads(cur.value)
            required = p.mod_revision
        obj["spec"]["nodeName"] = node_name
        ok, _, _ = self._fenced_cas(
            key,
            json.dumps(obj, separators=(",", ":")).encode(),
            required_mod=required, path="bind",
        )
        if not ok:
            _PODS_SCHEDULED.inc(outcome="conflict")
            return False
        # Keep host accounting; the watch echo of our own write is
        # deduped via _bound.
        self.host.add_pod(node_name, p.cpu_milli, p.mem_kib)
        self._note_bound(p.ensure_pod(), node_name, external=False)
        _PODS_SCHEDULED.inc(outcome="bound")
        return True

    @staticmethod
    def _bind_fault() -> bool:
        """Faultline hook on the bind CAS (component ``coordinator.bind``,
        op ``cas``).  ``delay`` sleeps; every failure kind maps to a
        forced CAS conflict — the one failure this path owns (wire-level
        failures are the store.wire hooks' domain) — which drives the pod
        through the same conflict/requeue machinery a concurrent writer
        would.  Returns True when the bind must report conflict."""
        d = faultline.decide("coordinator.bind", "cas")
        if d is None:
            return False
        if d.kind == "delay":
            time.sleep(d.delay_s)
            return False
        _PODS_SCHEDULED.inc(outcome="conflict")
        return True

    def _retry(self, p: PendingPod) -> None:
        p.attempts += 1
        pol = self.retry_policy
        if p.attempts >= pol.max_attempts:
            # Give-up degrades gracefully: the pod is parked (the
            # reference reports unschedulable the same way), never
            # tight-looped.
            if self._tracer.enabled:
                # Chain closes in the wave-retire pass (after the
                # device/bind spans), not here mid-bind-loop.
                self._trace_gaveup.add(p.key_str)
            _PODS_SCHEDULED.inc(outcome="unschedulable")
            note_give_up("coordinator.bind")
            self.unschedulable[p.key_str] = p.ensure_pod()
            return
        _PODS_SCHEDULED.inc(outcome="retry")
        note_retry("coordinator.bind")
        # Re-read AND re-decode: the CAS may have failed because an external
        # writer bound the pod (retrying would overwrite their bind and
        # double-account) or changed its spec (retrying with stale
        # cpu/mem would overcommit the node).
        cur = self.store.get(p.key_bytes)
        if cur is None:
            return
        fresh = decode_pod(cur.value, self.tracker)
        if fresh.node_name:
            return  # bound externally; the watch echo handles accounting
        p.pod = fresh
        p.cpu_milli = fresh.cpu_milli
        p.mem_kib = fresh.mem_kib
        p.key_str = fresh.key
        p.priority = fresh.priority
        p.mod_revision = cur.mod_revision
        # Refresh the splice-source bytes too — stale raw at the new
        # revision would CAS the OLD object body back in, silently
        # reverting whatever spec change made the first CAS fail.
        p.raw = cur.value
        self._queued_keys.add(p.key_str)
        # Backoff requeue (RetryPolicy): the pod sits out a jittered,
        # attempt-scaled delay instead of re-entering the very next wave
        # — a conflict storm becomes visible backpressure
        # (coordinator_backoff_depth) rather than a tight loop.
        p.not_before = time.perf_counter() + pol.delay_for(
            p.attempts, self._retry_rng
        )
        self._backoff_seq += 1
        heapq.heappush(self._backoff, (p.not_before, self._backoff_seq, p))

    def close(self) -> None:
        """Cancel store watches (native watchers are registered until
        explicitly cancelled — dropping the object alone would leave the
        store dispatching into a 10,000-event queue forever) and stop
        the host-feed worker."""
        for w in (self._nodes_watch, self._pods_watch):
            if w is not None:
                w.cancel()
        self._nodes_watch = self._pods_watch = None
        if self._feed is not None:
            self._feed.close()

    def run_until_idle(self, max_cycles: int = 10000) -> int:
        """Drive cycles until no pending pods remain; returns total binds."""
        total = 0
        idle = 0
        for _ in range(max_cycles):
            n = self.step()
            total += n
            if not self.queue and not self._inflights:
                if self._backoff or self._gang_parked:
                    # Retrying pods (and parked gangs) are on a timer,
                    # not idle: wait out the earliest backoff instead of
                    # burning empty cycles (or worse, exiting with work
                    # pending).
                    time.sleep(min(self.backoff_wait_s() or 0.0, 0.05))
                    idle = 0
                    continue
                idle += 1
                if (
                    idle > 1
                    and self.drain_watches() == 0
                    and not self._external_pending()
                ):
                    break
            else:
                idle = 0
        total += self.flush()
        return total


# Single-device dirty-row scatter (snapshot/node_table.scatter_rows),
# DONATING: the coordinator always reassigns self.table from the
# return, so the churn scatter updates HBM in place instead of
# copy-on-write.  The mesh path swaps in
# parallel.sharded_cycle.make_sharded_scatter — equally donating, with
# the row sharding pinned on top; a replay caller that keeps its input
# table alive must jit its own non-donating wrapper.
_scatter_rows_donated = jax.jit(scatter_rows, donate_argnums=(0,))


def _adjusted(chunk) -> tuple[list, list]:
    """``(pods, fps)`` of ``HotPodBatchHost.encode_packed`` for a chunk of
    ``Coordinator._pending_adjusts``: each entry's slot 5 of a bound
    record, a PodInfo as itself, a bound PodShape on the encoder's shape
    path (an adjustment reads the constraint increments alone, which the
    shape fixes)."""
    pods, fps = [], []
    for keep, *_ in chunk:
        if isinstance(keep, PodShape):
            pods.append(PendingPod(None, None, 0.0, key_str="/", shape=keep))
            fps.append(keep.fp)
        else:
            pods.append(keep)
            fps.append(None)
    return pods, fps
