"""The scheduling cycle: filter -> score -> top-k -> assign -> commit.

One call schedules a whole batch of pods against the whole node table.
This is the TPU replacement for the reference's entire scatter/gather
pipeline: relay-tree broadcast, 256 shards running filter+score, the
CollectScore gather, DistPermit, and the bind-conflict rollback
(reference SURVEY.md §3.2).  ~560us of fleet CPU per pod becomes a few
microseconds of TPU time amortized over the batch.

The node axis is processed in fixed-size chunks with a lax.scan carrying a
running top-k: HBM traffic stays streaming (the table is read once per
batch), compute per chunk stays in VMEM-sized tiles, and peak memory is
O(B * chunk) instead of O(B * N).  Candidates carry their free-capacity
and topology-domain payload so the greedy conflict scan (engine/assign.py),
the constraint commit, and the sharded all-gather (parallel/) never have
to re-gather from the (possibly sharded) table.

In-batch semantics note: the greedy conflict scan re-checks *capacity* for
pods later in the batch and, with ``in_wave_skew``, PodTopologySpread's
hard zone and region constraints as well — it carries the count tables
through the wave pod by pod, so no bind leaves a constraint's count in the
bound domain more than maxSkew above its least-populated domain, at every
bind and in wave order (engine/assign.py).  For that the candidates stage
drops its own skew filter on those constraints, which could only see the
counts of the wave's start, and keeps the best row of every zone instead
of the k best rows (``candidates(in_wave_skew=True)``): 256 pods of one
Deployment in a wave have to land in all eight zones, whatever stood at
the minimum when the wave began.  Required inter-pod anti-affinity
(and its symmetry) holds inside the wave as well, wherever a wave's pods
carry a required inter-pod term (``has_interpod_gate``, from the packed
groups): the conflict scan closes to each pod the domains earlier pods
of the wave it conflicts with took, and holds a required affinity term
that bootstraps (upstream's rule) to the domains of the pods it selects
(engine/assign.py).  Hostname-keyed spread constraints and every score
still read the wave-start tables; so does spread when ``in_wave_skew`` is
off (the default), and two same-batch pods can then exceed maxSkew by the
batch size in the worst case.  The reference has exactly that window (256
shards bind optimistically and only capacity conflicts roll back,
reference README.adoc:558-560);
constraint counts are exact again at the next batch boundary.  The mesh
step does all of this as one device does: its shards' candidates merge
zone by zone under ``in_wave_skew``, and the count and the gate run
replicated (parallel/sharded_cycle.py).  The
pipelined coordinator widens the wave-start window across waves:
capacity-only node deltas (allocatable, labels, taints, zone — same row,
same name) scatter into the live table while earlier waves are still in
flight, so a wave may score against capacity a heartbeat just changed.
That is the identical optimism — every bind is still CAS-verified against
the store, capacity conflicts still roll back through the dirty-row path,
and a wave that retires onto a row tombstoned mid-flight retries the pod —
so correctness is unchanged; only the staleness window is (bounded by
pipeline depth) wider.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax import lax

from k8s1m_tpu.config import SPREAD_DO_NOT_SCHEDULE, TOPO_HOSTNAME
from k8s1m_tpu.engine.assign import (
    WaveInterpod,
    WaveSkew,
    greedy_assign,
    unbound_by_reason,
)
from k8s1m_tpu.ops.priority import pack_hashed, seed_of
from k8s1m_tpu.plugins.registry import Profile, score_and_filter
from k8s1m_tpu.snapshot.constraints import (
    ConstraintState,
    commit_constraint_binds,
    slice_constraints,
)
from k8s1m_tpu.snapshot.node_table import NodeTable, commit_binds
from k8s1m_tpu.snapshot.packing import is_packed, mask_rows_packed, unpack_chunk
from k8s1m_tpu.snapshot.pod_encoding import (
    GATE_GROUP,
    SELECTOR_GROUPS,
    PodBatch,
    carries_required_ipa,
)


@dataclasses.dataclass
class Wave:
    """One in-flight pipelined dispatch: everything the coordinator needs
    to retire the wave later (CAS the binds back, roll back conflicts).

    ``epoch`` is the snapshot wave-epoch stamped at launch
    (NodeTableHost.begin_wave): a node row removed at epoch E stays
    quarantined until every wave with ``epoch <= E`` has retired, which
    is what makes structural removes safe to apply while this wave is
    still in flight — no row the wave may still bind can be reused.
    """

    batch_pods: list
    batch: object       # PackedPodBatch as dispatched
    asg: "Assignment"   # device-resident; fetched only on rollback
    rows_dev: jax.Array  # i32[B] bound row per pod (-1 = unbound)
    t_start: float
    epoch: int
    # Podtrace span attribute stamped at launch (obs/podtrace.py):
    # in-flight depth including this wave.
    depth: int = 1


@struct.dataclass
class Candidates:
    """Top-K bind candidates per pod, with payload gathered at score time."""

    idx: jax.Array    # i32[B, K] global node rows (-1 = none)
    prio: jax.Array   # i32[B, K] packed priorities, descending (-1 = infeasible)
    cpu: jax.Array    # i32[B, K] candidate free cpu at batch start
    mem: jax.Array    # i32[B, K]
    pods: jax.Array   # i32[B, K]
    zone: jax.Array   # i32[B, K] candidate's topology domains
    region: jax.Array  # i32[B, K]


@struct.dataclass
class Assignment:
    node_row: jax.Array  # i32[B] (-1 = unbound, retry next batch)
    bound: jax.Array     # bool[B]
    score: jax.Array     # i32[B] integer plugin score of the chosen node
    zone: jax.Array      # i32[B] domain of the chosen node
    region: jax.Array    # i32[B]
    # i32[4], engine.assign.UNBOUND_REASONS: the wave's valid pods left
    # unbound, by why.  Only a wave with in-wave skew or the inter-pod
    # gate counts them.
    unbound: jax.Array | None = None
    # i32[3]: the wave's valid pods by engine.assign.SETTLED_BY, and how
    # often the conflict rounds evaluated the whole wave.
    settled: jax.Array | None = None


@struct.dataclass
class CommitFields:
    """The slice of a PodBatch the post-candidate epilogue needs.

    In the sharded cycle only these leaves cross the dp all-gather — the
    selector tensors (req_vals, tolerated, ...) never leave their home
    device, keeping the hop at O(B*K) candidate records as the module doc
    promises."""

    cpu: jax.Array           # i32[B]
    mem: jax.Array           # i32[B]
    valid: jax.Array         # bool[B]
    sinc_valid: jax.Array    # spread-constraint commit increments
    sinc_cid: jax.Array
    sinc_topo: jax.Array
    iinc_valid: jax.Array    # affinity-term commit increments
    iinc_tid: jax.Array
    iinc_topo: jax.Array
    ipa_own_valid: jax.Array  # pod's own required anti-affinity terms
    ipa_tid: jax.Array
    ipa_topo: jax.Array


def commit_fields_np(fields: dict) -> CommitFields:
    """CommitFields from a PackedPodBatch's host field dict (np arrays are
    valid jit inputs; used on the rare CAS-rollback path)."""
    return CommitFields(
        cpu=fields["cpu"],
        mem=fields["mem"],
        valid=fields["valid"],
        sinc_valid=fields["sinc_valid"],
        sinc_cid=fields["sinc_cid"],
        sinc_topo=fields["sinc_topo"],
        iinc_valid=fields["iinc_valid"],
        iinc_tid=fields["iinc_tid"],
        iinc_topo=fields["iinc_topo"],
        ipa_own_valid=fields["ipa_valid"]
        & fields["ipa_required"]
        & fields["ipa_anti"],
        ipa_tid=fields["ipa_tid"],
        ipa_topo=fields["ipa_topo"],
    )


def commit_fields_of(batch: PodBatch) -> CommitFields:
    return CommitFields(
        cpu=batch.cpu,
        mem=batch.mem,
        valid=batch.valid,
        sinc_valid=batch.sinc_valid,
        sinc_cid=batch.sinc_cid,
        sinc_topo=batch.sinc_topo,
        iinc_valid=batch.iinc_valid,
        iinc_tid=batch.iinc_tid,
        iinc_topo=batch.iinc_topo,
        ipa_own_valid=batch.ipa_valid & batch.ipa_required & batch.ipa_anti,
        ipa_tid=batch.ipa_tid,
        ipa_topo=batch.ipa_topo,
    )


def _slice_table(table: NodeTable, start, chunk: int) -> NodeTable:
    """Chunk slice of the node table; a PACKED table decodes here, inside
    the jitted scan body, so HBM holds only the packed planes and the
    i32-wide decode lives in the same fused pass as the plugins
    (snapshot/packing.py — the devicestate layout contract)."""
    sliced = jax.tree.map(
        lambda a: lax.dynamic_slice_in_dim(a, start, chunk, axis=0), table
    )
    return unpack_chunk(sliced) if is_packed(sliced) else sliced


def prologue_stats(table, constraints, axis_name: str | None = None):
    """topology.prologue over either layout: the prologue needs only the
    full valid/zone/region columns, which a packed table decodes ONCE per
    wave (global domain statistics don't belong in a chunk decode).
    ``axis_name`` is the shard_map node-shard axis ("sp") so the sharded
    cycle shares this decode: domain reductions cross shards while the
    DomainView decode stays shard-local.

    Domain statistics are GLOBAL by semantics (a spread constraint's
    min/max is over the whole cluster): built from the commit table,
    never from the candidate view — an ownership mask or a window narrows
    candidate selection, not the skew baseline, or shards would disagree
    on feasibility.  Only the per-node count columns follow the window.

    Part of the candidates stage wherever it is called from, under a
    scope of its own inside that stage's (``candidates/cons_prologue``;
    the per-pod statistics of ops/pallas_topk.fused_topk share it)."""
    from k8s1m_tpu.plugins import topology

    with jax.named_scope("candidates"), jax.named_scope("cons_prologue"):
        view = table.domain_view() if is_packed(table) else table
        return topology.prologue(view, constraints, axis_name=axis_name)


# spread_max_skew of a constraint the wave counts itself: the candidates
# stage's own test, on the wave-start counts, passes every count
_NO_SKEW_LIMIT = 1 << 29


def _counted_in_wave(batch: PodBatch):
    """bool[B, S]: the pod's hard zone and region constraints, which
    ``in_wave_skew`` re-checks in wave order (engine/assign.py)."""
    return (
        batch.spread_valid
        & (batch.spread_mode == SPREAD_DO_NOT_SCHEDULE)
        & (batch.spread_topo != TOPO_HOSTNAME)
    )


def wave_skew(batch: PodBatch, constraints: ConstraintState, stats) -> WaveSkew:
    """greedy_assign's ``skew`` for one wave: the wave-start count tables,
    the domains there, the pods' constraint references and increments, as
    arrays of the trace (a wave without spread unpacks them numpy)."""
    return jax.tree.map(jnp.asarray, WaveSkew(
        counts=jnp.concatenate(
            [constraints.spread_zone, constraints.spread_region], axis=1
        ),
        present=jnp.concatenate(
            [stats.zone_present, stats.region_present]
        ) > 0,
        zones=constraints.spread_zone.shape[1],
        cid=batch.spread_cid, topo=batch.spread_topo,
        max_skew=batch.spread_max_skew,
        self_inc=batch.spread_self.astype(jnp.int32),
        hard=_counted_in_wave(batch),
        inc_valid=batch.sinc_valid, inc_cid=batch.sinc_cid,
        inc_topo=batch.sinc_topo,
    ))


def has_interpod_gate(groups, with_constraints: bool) -> bool:
    """Whether the step of a wave with these packed field groups is built
    with the in-wave inter-pod gate: a pod of the wave carries a required
    inter-pod term (``GATE_GROUP``, from ``carries_required_ipa``), and
    the deployment keeps the count tables the terms need."""
    return bool(with_constraints) and GATE_GROUP in groups


def wave_interpod(batch: PodBatch, constraints: ConstraintState, stats) -> WaveInterpod:
    """greedy_assign's ``interpod`` for one wave: the pods' term
    references and increments, and every term's wave-start total."""
    return WaveInterpod(
        slots=constraints.tgt_zone.shape[0],
        ipa_valid=batch.ipa_valid, ipa_tid=batch.ipa_tid,
        ipa_topo=batch.ipa_topo, ipa_required=batch.ipa_required,
        ipa_anti=batch.ipa_anti, ipa_self=batch.ipa_self,
        inc_valid=batch.iinc_valid, inc_tid=batch.iinc_tid,
        tgt_total=stats.tgt_total,
    )


def topk_by_argmax(prio, k: int):
    """``lax.top_k`` semantics (descending values, earlier index wins
    ties) as k argmax knock-out passes — the CPU-backend form.

    The chunk scan only ever needs tiny k (4) over wide rows (the node
    chunk): a full TopK sort is the wrong primitive — XLA CPU's TopK
    custom-call runs ~200ns/element on [4096, 16384] int32 (13.4s per
    wave!) where an argmax pass is ~2ns/element; the fused pallas kernel
    already extracts its running top-k by repeated max for the same
    reason (ops/pallas_topk.py).  k linear passes beat one sort on both
    backends whenever k is small.

    On TPU this form is the wrong one: XLA-TPU hung >30min compiling the
    1M-node scan built on it (2026-07-31 chip run; the same program
    compiles in 14.5s and runs fine on XLA CPU), while `lax.top_k` — a
    native TPU primitive — compiled the identical scan in ~40s pre-round-4.
    `chunk_topk` below picks per backend; both forms implement exactly
    top_k's tie rule (descending, earlier index wins), so backend parity
    (pallas vs xla bit-identical, tests/test_pallas_topk.py) is
    unaffected by the switch.

    A grouped tournament variant (one max pass + per-extraction rescans
    of only the winning 128-wide group) measured 8x faster standalone
    but 12x SLOWER inside the jitted wave — XLA CPU handles the
    per-extraction dynamic gathers pathologically in context, with or
    without an optimization_barrier on the fused producer.  Keep the
    knock-out form: it fuses cleanly with the filter+score producer.
    """
    iota = lax.broadcasted_iota(jnp.int32, prio.shape, prio.ndim - 1)
    lowest = (
        jnp.iinfo(prio.dtype).min
        if jnp.issubdtype(prio.dtype, jnp.integer) else -jnp.inf
    )
    vals, idxs = [], []
    p = prio
    for _ in range(k):
        i = jnp.argmax(p, axis=-1).astype(jnp.int32)
        # Values come from the ORIGINAL array (the knock-out sentinel
        # must never surface), and duplicates extract in increasing
        # index order — both exactly top_k's tie rule.
        vals.append(jnp.take_along_axis(prio, i[..., None], axis=-1))
        idxs.append(i[..., None])
        p = jnp.where(iota == i[..., None], lowest, p)
    return (
        jnp.concatenate(vals, axis=-1),
        jnp.concatenate(idxs, axis=-1),
    )


def chunk_topk(prio, k: int):
    """Per-backend top-k over the chunk axis (see topk_by_argmax doc).

    CPU: k argmax knock-out passes (TopK custom-call is ~100x slower).
    TPU/other: native ``lax.top_k`` (the knock-out form hangs XLA-TPU's
    compiler at 1M-node scan sizes).  Identical semantics either way
    PROVIDED the input never contains int32 min — the knock-out's own
    sentinel; ``pack_hashed`` emits {-1} ∪ [0, int32max], so the packed
    -priority domain satisfies this (asserted by
    test_topk_by_argmax_matches_lax_top_k).  The backend choice is
    trace-time static, so this costs nothing inside jit.

    Coverage: the two forms' equivalence — including the
    earlier-index-wins tie-break — is asserted by the CPU tier-1 suite
    where BOTH forms run on the CPU backend.  The TPU branch's tie
    semantics (``lax.top_k`` on silicon) are covered by phase B of
    chip_smoke.py, which compares this scan bit for bit with the fused
    kernel's first-position rule on the chip (they agreed on the v5e at
    1M rows of heavily tied KWOK scores, PR 21).
    """
    if jax.default_backend() == "cpu":
        return topk_by_argmax(prio, k)
    top, idx = lax.top_k(prio, k)
    return top, idx.astype(jnp.int32)


def merge_topk(a: Candidates, b: Candidates, k: int) -> Candidates:
    """Merge two candidate sets, keeping the k highest priorities."""
    prio = jnp.concatenate([a.prio, b.prio], axis=-1)
    top_prio, sel = lax.top_k(prio, k)

    def take(xa, xb):
        return jnp.take_along_axis(jnp.concatenate([xa, xb], axis=-1), sel, axis=-1)

    return jax.tree.map(take, a, b).replace(prio=top_prio)


def chunk_top_per_zone(prio, zone, k: int):
    """The best priority of every zone id 0..k-1 over the chunk axis and
    where it stands (first position wins a tie): ``(i32[B, k], i32[B, k])``,
    -1 for a zone with no row in the chunk.  ``zone`` is the chunk's
    ``[C]`` zone ids.  What ops/pallas_topk._merge_running_per_zone does
    to one chunk."""
    by_zone = jnp.where(
        zone.astype(jnp.int32)[None, None, :]
        == jnp.arange(k, dtype=jnp.int32)[None, :, None],
        prio[:, None, :], -1,
    )                                                           # [B, k, C]
    return by_zone.max(axis=-1), jnp.argmax(by_zone, axis=-1).astype(jnp.int32)


def merge_per_zone(a: Candidates, b: Candidates) -> Candidates:
    """Slot for slot the better of two per-zone candidate sets; ``a``,
    the earlier rows, wins a tie."""
    keep = a.prio >= b.prio
    return jax.tree.map(lambda xa, xb: jnp.where(keep, xa, xb), a, b)


def merge_shards(cand: Candidates, axis_name: str, k: int,
                 per_zone: bool) -> Candidates:
    """Inside shard_map: the node shards' candidates merged over
    ``axis_name``, so that every device holds the whole table's.  The
    lists gather shard-major and a shard's rows follow the one before
    it, so the lower shard holds the lower rows.  With ``per_zone`` the
    slot of each zone id takes the best of the shards' rows there, the
    lowest shard's where priorities tie (argmax keeps the first): what
    ``merge_per_zone`` folds the chunks of one device to.  Otherwise the
    ``k`` best of all (``lax.top_k`` keeps the earlier of equal
    priorities: again the lower row)."""
    gathered = jax.tree.map(lambda x: lax.all_gather(x, axis_name), cand)
    if per_zone:
        best = jnp.argmax(gathered.prio, axis=0)[None]          # [1, B, k]
        return jax.tree.map(
            lambda x: jnp.take_along_axis(x, best, axis=0)[0], gathered
        )
    flat = jax.tree.map(                                        # [B, SP*k]
        lambda x: jnp.moveaxis(x, 0, 1).reshape(x.shape[1], -1), gathered
    )
    top_prio, sel = lax.top_k(flat.prio, k)
    return jax.tree.map(
        lambda x: jnp.take_along_axis(x, sel, axis=-1), flat
    ).replace(prio=top_prio)


def empty_candidates(b: int, k: int) -> Candidates:
    zeros = jnp.zeros((b, k), jnp.int32)
    return Candidates(
        idx=jnp.full((b, k), -1, jnp.int32),
        prio=jnp.full((b, k), -1, jnp.int32),
        cpu=zeros, mem=zeros, pods=zeros, zone=zeros, region=zeros,
    )


def filter_score_topk(
    table: NodeTable,
    batch: PodBatch,
    key: jax.Array,
    profile: Profile,
    *,
    chunk: int,
    k: int,
    constraints: ConstraintState | None = None,
    stats=None,
    row_offset=0,
    pod_offset=0,
    per_zone: bool = False,
) -> Candidates:
    """Stream the node table in chunks, keeping each pod's top-k candidates
    or, with ``per_zone``, its best candidate of every zone id 0..k-1, in
    zone order (``candidates()`` sorts them).

    ``row_offset`` biases emitted node rows — under shard_map each shard
    passes its global row offset so candidate indices stay global.  It
    also biases the tie-break hash's node coordinate, so a shard hashing
    its local rows draws the SAME jitter a single device drew for those
    global rows.  ``pod_offset`` does the same for the pod coordinate (a
    dp shard passes its batch-block offset).  Together they make the
    sharded cycle's priorities a pure function of (seed, global pod row,
    global node row) — the byte-identity contract the mesh differential
    gate rests on (tests/test_mesh_differential.py).
    """
    n = table.num_rows
    if n % chunk:
        raise ValueError(f"table rows {n} not divisible by chunk {chunk}")
    num_chunks = n // chunk
    b = batch.batch
    if constraints is not None and stats is None:
        # Single-device convenience: build the batch prologue here.  Under
        # shard_map callers MUST pass stats from topology.prologue(...,
        # axis_name=...) — the auto-built one would be shard-local.
        stats = prologue_stats(table, constraints)

    # ONE scalar threefry draw per wave; per-element jitter comes from the
    # separable hash over (pod row, view-local node column) — the same
    # stream the pallas kernel computes, so the two backends produce
    # identical priorities for the same wave (and the counter-mode PRNG,
    # ~1.8s per [4096,16384] wave on XLA CPU, leaves the hot loop).
    seed = seed_of(key)
    pod_rows = lax.broadcasted_iota(jnp.int32, (b, 1), 0) + pod_offset

    def body(carry, _):
        carry, ci = carry
        start = ci * chunk
        tchunk = _slice_table(table, start, chunk)
        cchunk = (
            slice_constraints(constraints, start, chunk)
            if constraints is not None else None
        )
        mask, score = score_and_filter(tchunk, batch, profile, cchunk, stats)
        node_cols = (
            lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
            + start + row_offset
        )
        prio = pack_hashed(score, seed, mask, pod_rows, node_cols)
        if per_zone:
            top_prio, idx = chunk_top_per_zone(prio, tchunk.zone, k)
        else:
            top_prio, idx = chunk_topk(prio, k)                 # [B, k]
        free_cpu, free_mem, free_pods = tchunk.free()
        local = Candidates(
            idx=(idx + start + row_offset).astype(jnp.int32),
            prio=top_prio,
            cpu=jnp.take(free_cpu, idx),
            mem=jnp.take(free_mem, idx),
            pods=jnp.take(free_pods, idx),
            zone=jnp.take(tchunk.zone, idx),
            region=jnp.take(tchunk.region, idx),
        )
        merged = (
            merge_per_zone(carry, local) if per_zone
            else merge_topk(carry, local, k)
        )
        return (merged, ci + 1), None

    # NB: scan without an xs array — a `jnp.arange(num_chunks)` here gets
    # lifted to an executable constant, which the pjit fast-path cache
    # mishandles when one function owns multiple executables ("supplied 66
    # buffers but compiled program expected 67").
    init = (empty_candidates(b, k), jnp.int32(0))
    if num_chunks == 1:
        (cand, _), _ = body(init, None)
    else:
        (cand, _), _ = lax.scan(body, init, None, length=num_chunks)
    # Mark infeasible candidates' rows as -1 so downstream never binds them.
    return cand.replace(idx=jnp.where(cand.prio >= 0, cand.idx, -1))


def commit_constraints_for_batch(
    constraints: ConstraintState,
    fields: CommitFields,
    asg: "Assignment",
    node_row,       # i32[B] rows to scatter node-domain counts into
    bound_node,     # bool[B] gate for node-domain tables (shard-local mask)
    bound_domain,   # bool[B] gate for zone/region tables (global mask)
) -> ConstraintState:
    return commit_constraint_binds(
        constraints,
        bound_node, bound_domain, node_row, asg.zone, asg.region,
        fields.sinc_valid, fields.sinc_cid, fields.sinc_topo,
        fields.iinc_valid, fields.iinc_tid, fields.iinc_topo,
        fields.ipa_own_valid, fields.ipa_tid, fields.ipa_topo,
    )


def finalize_batch(
    table: NodeTable,
    constraints: ConstraintState | None,
    cand: Candidates,
    fields: CommitFields,
    *,
    row_offset: int | jax.Array = 0,
    rows: int | None = None,
    skew: WaveSkew | None = None,
    interpod: WaveInterpod | None = None,
):
    """Shared epilogue: greedy conflict resolution + capacity/constraint
    commit.  ``rows=None`` means the whole table is local (single device);
    otherwise only binds landing in [row_offset, row_offset+rows) update
    this shard's node-row tables, while zone/region count tables (replicated
    in the sharded cycle) take the full global update.  ``skew``
    (``wave_skew``) makes the conflict scan count the hard zone and region
    spread constraints through the wave, and the Assignment say why pods
    stayed unbound; ``interpod`` (``wave_interpod``) closes, pod by pod in
    wave order, the domains an earlier pod of the wave took from it
    (required anti-affinity and its symmetry, the bootstrap of required
    affinity), and does the same.

    Returns (table, constraints, Assignment).

    The two phases carry ``jax.named_scope`` names (``assign``,
    ``commit``; ``candidates()`` carries the third), so a device trace
    can be read by phase whatever the ops under them become."""
    with jax.named_scope("assign"):
        node_row, bound, score, chosen_k, legal, settled, *blocked = (
            greedy_assign(
                cand.idx, cand.prio, cand.cpu, cand.mem, cand.pods,
                fields.cpu, fields.mem, fields.valid,
                skew, cand.zone, cand.region, interpod,
            )
        )
        unbound = None if skew is None and interpod is None else (
            unbound_by_reason(
                bound, legal, cand.idx, cand.prio, fields.valid, *blocked
            )
        )
    take1 = lambda x: jnp.take_along_axis(x, chosen_k[:, None], axis=1)[:, 0]
    asg = Assignment(
        node_row=node_row, bound=bound, score=score,
        zone=jnp.where(bound, take1(cand.zone), 0),
        region=jnp.where(bound, take1(cand.region), 0),
        unbound=unbound, settled=settled,
    )
    if rows is None:
        local = bound
        local_row = jnp.where(bound, node_row, 0)
    else:
        local = bound & (node_row >= row_offset) & (node_row < row_offset + rows)
        local_row = jnp.where(local, node_row - row_offset, 0)
    with jax.named_scope("commit"):
        table = commit_binds(table, local_row, fields.cpu, fields.mem, local)
        if constraints is not None:
            constraints = commit_constraints_for_batch(
                constraints, fields, asg, local_row, local, bound
            )
    return table, constraints, asg


def adjust_constraints_impl(
    constraints: ConstraintState,
    fields: CommitFields,
    node_row,      # i32[B] (clipped to a valid row where mask_node is off)
    zone,          # i32[B]
    region,        # i32[B]
    mask_node,     # bool[B] gate for node-domain tables
    mask_domain,   # bool[B] gate for zone/region tables
    sign: int = -1,
) -> ConstraintState:
    """Signed constraint-count correction outside the scheduling step.

    Used by the coordinator for bind-CAS conflicts (sign=-1: the step's
    optimistic commit must be rolled back for pods whose store write lost)
    and for pod deletions (sign=-1 against the recorded bind placement;
    mask_node is off when the node has since been removed, while the
    zone/region decrement still applies via mask_domain).
    """
    return commit_constraint_binds(
        constraints,
        mask_node, mask_domain, jnp.where(mask_node, node_row, 0), zone, region,
        fields.sinc_valid, fields.sinc_cid, fields.sinc_topo,
        fields.iinc_valid, fields.iinc_tid, fields.iinc_topo,
        fields.ipa_own_valid, fields.ipa_tid, fields.ipa_topo,
        sign=sign,
    )


# Correction path, not the per-wave hot loop: callers (tests, the
# coordinator's rollback batches) may replay against the same state.
adjust_constraints = jax.jit(  # graftlint: disable=undonated-device-update (replayable correction path; per-wave commits donate via _jitted_schedule_packed)
    adjust_constraints_impl, static_argnames=("sign",)
)


def candidates(
    table: NodeTable,
    batch: PodBatch,
    key: jax.Array,
    constraints: ConstraintState | None,
    profile: Profile,
    *,
    chunk: int,
    k: int,
    backend: str = "xla",
    with_affinity: bool = True,
    src: NodeTable | None = None,
    window=None,
    row_offset=0,
    pod_offset=0,
    axis_name: str | None = None,
    stats=None,
    in_wave_skew: bool = False,
) -> Candidates:
    """The candidates stage of every full step — which rows are scanned,
    by which kernel, under which hash coordinates.  Every step shell
    calls this; nothing else reaches ``filter_score_topk`` or
    ``pallas_candidates``.

    ``src`` (default: the table) is the candidate-selection view; binds
    always commit into ``table`` — the split that makes ownership masks
    (mask_rows) work without touching commit state.  ``window`` is None
    or ``(offset, rows)``, percentageOfNodesToScore: only rows [offset,
    offset+rows) of ``src`` are filtered+scored (the reference scores 5%
    of nodes per pod at 1M scale, README.adoc:525-531); ``offset`` may be
    traced, and the emitted rows are table rows either way.  Under
    shard_map ``axis_name`` is the node-shard axis and ``row_offset`` /
    ``pod_offset`` the shard's global bases (sharded_cycle.mesh_offsets).
    ``backend="pallas"`` is the fused kernel (ops/pallas_topk.py),
    constraint plugins included when ``constraints`` is passed;
    ``with_affinity=False`` compiles its cheaper selector-free form.
    ``stats`` is ``prologue_stats(table, constraints)`` where the caller
    has taken it already.

    Under shard_map the shards' candidates are merged over ``axis_name``
    (``merge_shards``, scope ``sp_merge``): what this returns there is the
    whole table's, on every device.

    ``in_wave_skew`` (``k`` = ``TableSpec.max_zones``) hands the hard zone
    and region constraints over to the in-wave count (``wave_skew`` +
    engine/assign.py): here they lose their skew test against the
    wave-start counts (a node still has to carry the label), and a pod's
    candidates are the best row of every zone, sorted by priority, so
    that the zone that is legal at its turn has one; a mesh merges them
    zone by zone before the sort."""
    src = table if src is None else src
    if constraints is not None and stats is None:
        stats = prologue_stats(table, constraints, axis_name)
    with jax.named_scope("candidates"):
        if in_wave_skew:
            if constraints is None or k != constraints.spread_zone.shape[1]:
                raise ValueError(
                    "in_wave_skew takes constraint state and one candidate "
                    f"a zone id: k = TableSpec.max_zones (k={k})"
                )
            batch = batch.replace(spread_max_skew=jnp.where(
                _counted_in_wave(batch), _NO_SKEW_LIMIT, batch.spread_max_skew
            ))
        view, view_cons, remap = src, constraints, None
        if window is not None:
            offset, rows = window
            view = jax.tree.map(
                lambda a: lax.dynamic_slice_in_dim(a, offset, rows, 0), src
            )
            if constraints is not None:
                view_cons = slice_constraints(constraints, offset, rows)
            # Both surfaces keep the hash columns they had: one device
            # hashes WINDOW-LOCAL columns and remaps the rows afterwards,
            # the mesh hashes GLOBAL columns (it must, for an unsampled
            # wave to equal the single-device wave byte for byte, and its
            # windows inherited that base).  So a sampled wave differs
            # between the two; making them agree would change every bind
            # of kwok-1m-pct5 (ROADMAP D12).
            if axis_name is None:
                remap = offset
            else:
                row_offset = row_offset + offset
        if backend == "pallas":
            from k8s1m_tpu.ops import pallas_topk

            if constraints is None and not pallas_topk.supports(profile):
                raise ValueError(
                    "profile enables constraint plugins but no constraint "
                    "state was passed (see ops/pallas_topk.py)"
                )
            cand = pallas_topk.pallas_candidates(
                view, batch, key, profile, chunk=chunk, k=k,
                row_offset=row_offset, pod_offset=pod_offset,
                with_affinity=with_affinity,
                constraints=view_cons, stats=stats, per_zone=in_wave_skew,
            )
        else:
            cand = filter_score_topk(
                view, batch, key, profile,
                chunk=chunk, k=k, constraints=view_cons, stats=stats,
                row_offset=row_offset, pod_offset=pod_offset,
                per_zone=in_wave_skew,
            )
        if remap is not None:
            cand = cand.replace(
                idx=jnp.where(cand.idx >= 0, cand.idx + remap, -1)
            )
        if axis_name is not None:
            with jax.named_scope("sp_merge"):
                cand = merge_shards(cand, axis_name, k, in_wave_skew)
        if in_wave_skew:
            # zone order -> priority order (the lower zone id wins a tie):
            # greedy_assign takes the first candidate that is ok
            top_prio, sel = lax.top_k(cand.prio, k)
            cand = jax.tree.map(
                lambda x: jnp.take_along_axis(x, sel, axis=-1), cand
            ).replace(prio=top_prio)
    return cand


def _schedule_batch_impl(
    table: NodeTable,
    batch: PodBatch,
    key: jax.Array,
    constraints: ConstraintState | None,
    profile: Profile,
    chunk: int,
    k: int,
    backend: str = "xla",
    with_affinity: bool = True,
    src: NodeTable | None = None,
    window=None,
    in_wave_skew: bool = False,
    interpod: bool = False,
):
    stats = skew = gate = None
    if constraints is not None and (in_wave_skew or interpod):
        # the candidates stage's prologue, taken here: the in-wave count
        # starts from the same tables and the same present domains, the
        # gate from the same term totals
        stats = prologue_stats(table, constraints)
    if in_wave_skew and constraints is not None:    # candidates() refuses None
        skew = wave_skew(batch, constraints, stats)
    if interpod and constraints is not None:
        gate = wave_interpod(batch, constraints, stats)
    cand = candidates(
        table, batch, key, constraints, profile, chunk=chunk, k=k,
        backend=backend, with_affinity=with_affinity, src=src,
        window=window, stats=stats,
        in_wave_skew=in_wave_skew,
    )
    return finalize_batch(
        table, constraints, cand, commit_fields_of(batch), skew=skew,
        interpod=gate,
    )


@functools.lru_cache(maxsize=64)
def _jitted_schedule(
    profile: Profile, chunk: int, k: int, with_constraints: bool,
    backend: str = "xla", with_affinity: bool = True,
    in_wave_skew: bool = False, interpod: bool = False,
):
    # One jax.jit function object per static configuration.  Routing every
    # configuration through a single jitted function trips a pjit fast-path
    # cache bug in this environment once the function owns several
    # executables ("Execution supplied 66 buffers but compiled program
    # expected 67 buffers"); distinct function identities sidestep it.
    if with_constraints:
        fn = lambda table, batch, key, constraints: _schedule_batch_impl(
            table, batch, key, constraints, profile, chunk, k, backend,
            with_affinity=with_affinity,
            in_wave_skew=in_wave_skew, interpod=interpod,
        )
    else:
        fn = lambda table, batch, key: _schedule_batch_impl(
            table, batch, key, None, profile, chunk, k, backend,
            with_affinity=with_affinity,
        )
    # schedule_batch is the unpacked replay/test surface (differential
    # suites re-run one table); the production path is schedule_batch_
    # packed with donate=True.
    return jax.jit(fn)  # graftlint: disable=undonated-device-update (replay surface; production donates via _jitted_schedule_packed)


def schedule_batch(
    table: NodeTable,
    batch: PodBatch,
    key: jax.Array,
    *,
    profile: Profile,
    constraints: ConstraintState | None = None,
    chunk: int = 16384,
    k: int = 4,
    backend: str = "xla",
    with_affinity: bool = True,
    in_wave_skew: bool = False,
):
    """Schedule one pod batch end-to-end on a single device.

    Returns (new_table, new_constraints, Assignment).  The table and
    constraint counts come back with this batch's binds already folded in
    (the assume step), so back-to-back batches see each other's placements.

    ``backend="pallas"`` routes filter+score+top-k through the fused
    Pallas kernel (ops/pallas_topk.py), including the constraint stage
    when ``constraints`` is passed (BASELINE configs 3-4 fused).
    ``with_affinity=False`` compiles the cheaper selector-free kernel;
    pass it only when the caller knows no pod in the batch carries
    nodeSelector/affinity terms (the packed path derives this per wave
    from the field groups).  ``in_wave_skew`` (with ``constraints``,
    ``k`` = ``TableSpec.max_zones``): the hard zone and region spread
    constraints hold at every bind of the wave, in wave order (module
    doc), and ``Assignment.unbound`` says why pods stayed unbound.  With
    ``constraints``, a batch whose pods carry a required inter-pod term is
    scheduled under the in-wave gate (``carries_required_ipa``, the rule
    the packed path's ``has_interpod_gate`` reads from its groups).
    """
    interpod = constraints is not None and carries_required_ipa(
        batch.ipa_valid, batch.ipa_required
    )
    step = _jitted_schedule(
        profile, chunk, k, constraints is not None, backend, with_affinity,
        in_wave_skew, interpod,
    )
    if constraints is None:
        table, cons, asg = step(table, batch, key)
    else:
        table, cons, asg = step(table, batch, key, constraints)
    return table, cons, asg


def sample_rows_for(nodes: int, score_pct: int, chunk: int) -> int | None:
    """percentageOfNodesToScore -> chunk-aligned window rows (None = the
    rounded window covers the whole table, i.e. scan everything)."""
    if score_pct >= 100:
        return None
    rows = -(-nodes * score_pct // 100)          # ceil
    rows = -(-rows // chunk) * chunk             # round up to chunk
    return None if rows >= nodes else rows


def sample_offset_for(i: int, nodes: int, rows: int) -> int:
    """Rotating window offset covering every row over ceil(N/S) steps
    (the tail window is anchored at N-S)."""
    w = nodes // rows
    total = w + (1 if nodes % rows else 0)
    i %= total
    return nodes - rows if i == w else i * rows


def mask_rows(table, row_mask):
    """A candidate-selection view where rows outside ``row_mask`` are
    infeasible on both backends: ``valid`` feeds the XLA filter chain and
    ``pods_alloc == 0`` is the fused kernel's row-validity convention.
    Commit state is untouched — binds land in the unmasked table."""
    if is_packed(table):
        return mask_rows_packed(table, row_mask)
    return table.replace(
        valid=table.valid & row_mask,
        pods_alloc=jnp.where(row_mask, table.pods_alloc, 0),
    )


@functools.lru_cache(maxsize=256)
def _jitted_schedule_packed(
    profile: Profile, chunk: int, k: int, with_constraints: bool,
    backend: str, pod_spec, table_spec, groups: frozenset,
    sample_rows: int | None, with_mask: bool = False,
    donate: bool = False, in_wave_skew: bool = False,
):
    from k8s1m_tpu.snapshot.pod_encoding import unpack_pod_batch

    # Waves whose pods carry no selectors skip the affinity stage of the
    # fused kernel entirely, and waves whose pods carry no required
    # inter-pod term build no gate; the packed field groups already say so.
    aff = has_selectors(groups)
    interpod = has_interpod_gate(groups, with_constraints)

    def impl(table, ints, bools, key, offset, row_mask, constraints):
        batch = unpack_pod_batch(ints, bools, pod_spec, table_spec, groups)
        table, cons, asg = _schedule_batch_impl(
            table, batch, key, constraints, profile, chunk, k, backend,
            with_affinity=aff,
            src=None if row_mask is None else mask_rows(table, row_mask),
            window=None if sample_rows is None else (offset, sample_rows),
            in_wave_skew=in_wave_skew, interpod=interpod,
        )
        # One fetchable result array: the bound node row per pod, -1 for
        # unbound.  Every device_get is a device->host sync; the
        # coordinator reads this single array per wave.
        rows = jnp.where(asg.bound, asg.node_row, -1).astype(jnp.int32)
        return table, cons, asg, rows

    if with_constraints and with_mask:
        fn = impl
    elif with_constraints:
        fn = lambda table, ints, bools, key, offset, constraints: impl(
            table, ints, bools, key, offset, None, constraints
        )
    elif with_mask:
        fn = lambda table, ints, bools, key, offset, row_mask: impl(
            table, ints, bools, key, offset, row_mask, None
        )
    else:
        fn = lambda table, ints, bools, key, offset: impl(
            table, ints, bools, key, offset, None, None
        )
    if donate:
        # The production (coordinator) executable: the input table's —
        # and constraint state's — buffers are donated, so the wave's
        # commit_binds/constraint commit update HBM in place instead of
        # copy-on-write.  Callers MUST drop their reference (the
        # coordinator reassigns self.table from the return): a donated
        # array is deleted, and stale host references raise.
        donate_idx = (0, 6) if (with_constraints and with_mask) else (
            (0, 5) if with_constraints else (0,)
        )
        return jax.jit(fn, donate_argnums=donate_idx)
    # Replay/differential callers (tests, oracle comparisons, bench A/B
    # lanes) re-run the same input table; donation would delete it.
    return jax.jit(fn)  # graftlint: disable=undonated-device-update (non-donating replay variant; production passes donate=True)


def schedule_batch_packed(
    table,
    packed,
    key: jax.Array,
    *,
    profile: Profile,
    constraints: ConstraintState | None = None,
    chunk: int = 16384,
    k: int = 4,
    backend: str = "xla",
    sample_rows: int | None = None,
    sample_offset: int = 0,
    row_mask=None,
    mesh=None,
    donate: bool = False,
    in_wave_skew: bool = False,
):
    """schedule_batch over a PackedPodBatch: the pod features cross the
    host->device boundary as two buffers and the bind decision comes back
    as one i32[B] row array (-1 = unbound) — 3 transfers per cycle total
    instead of ~40, each of which would pay its own dispatch and sync.

    ``mesh`` (a (dp, sp) jax.sharding.Mesh) routes the step through
    parallel/sharded_cycle.make_sharded_packed_step: the table must be
    placed with its rows sharded over ``sp`` and ``sample_rows`` /
    ``sample_offset`` become SHARD-LOCAL (each shard scores a rotating
    window of its own rows).  Mutually exclusive with ``row_mask``
    (node-space process sharding and mesh sharding are different axes
    of scale-out; compose them across processes, not inside one step).

    ``sample_rows``/``sample_offset`` implement percentageOfNodesToScore:
    only rows [offset, offset+sample_rows) are filtered+scored this cycle
    (the caller rotates the offset).  The offset is a traced scalar — no
    recompile per window.  Works with constraint state: domain statistics
    are global prologue reductions over the full count tables, so only
    the per-node count columns follow the window (the reference's
    production config runs the full plugin set at pct 5 the same way,
    dist-scheduler.tf:551-570).

    ``row_mask`` (bool[N] device array) restricts candidate selection to
    the masked rows — the node-space sharding predicate of a scheduler
    shard set (control/shardset.py): every shard holds the full table,
    ownership is a mask, rebalancing flips mask bits instead of moving
    table data.  Traced, so reassignment never recompiles.

    ``donate=True`` donates the table's (and constraint state's) buffers
    to the step so the per-wave commit is in-place in HBM instead of
    copy-on-write — the production coordinator path, on BOTH execution
    paths: the single-device step and the mesh step donate alike (the
    sharded executables pin their out_specs, so each shard's buffers
    alias in place).  The caller's input references are DEAD afterwards
    (reassign from the return value); replay/differential callers that
    re-run the same table must keep the default.

    ``table`` may be a snapshot.packing.PackedNodeTable (the packed
    production layout): chunks decode on-device inside the scan slice on
    both backends, and binds are byte-identical to the unpacked layout
    (tests/test_packing.py differential gate).

    ``in_wave_skew``: as ``schedule_batch``'s, on one device and on a
    mesh alike.

    Returns (new_table, new_constraints, Assignment, rows).
    """
    if mesh is not None:
        if row_mask is not None:
            raise ValueError("mesh and row_mask are mutually exclusive")
        from k8s1m_tpu.parallel.sharded_cycle import make_sharded_packed_step

        step = make_sharded_packed_step(
            mesh, profile, chunk=chunk, k=k,
            pod_spec=packed.spec, table_spec=packed.table_spec,
            groups=packed.groups, sample_rows=sample_rows, backend=backend,
            donate=donate, in_wave_skew=in_wave_skew,
            interpod=has_interpod_gate(packed.groups, constraints is not None),
        )
        offset = np.int32(sample_offset)
        if constraints is not None:
            return step(
                table, packed.ints, packed.bools, key, offset, constraints
            )
        return step(table, packed.ints, packed.bools, key, offset)
    step = _jitted_schedule_packed(
        profile, chunk, k, constraints is not None, backend,
        packed.spec, packed.table_spec, packed.groups, sample_rows,
        row_mask is not None, donate, in_wave_skew,
    )
    offset = np.int32(sample_offset)
    args = (table, packed.ints, packed.bools, key, offset)
    if row_mask is not None:
        args += (row_mask,)
    if constraints is not None:
        args += (constraints,)
    return step(*args)


# ---- which candidates kernel a wave's step is built with ------------------


def has_selectors(groups) -> bool:
    """Whether a wave's packed field groups carry a nodeSelector or a
    nodeAffinity term: the step of such a wave is built with the affinity
    stage, every other without it."""
    return bool(groups & SELECTOR_GROUPS)


def candidates_kernel(backend: str, groups, with_constraints: bool) -> str:
    """The name of the candidates kernel the step of a wave with these
    packed field groups is built with (``coordinator_waves_total``'s
    label): on the pallas backend the string ``pallas_call(name=)`` gets,
    on the XLA backend the scan's name with the same suffixes (its filter
    chain skips an absent group at trace time)."""
    aff = has_selectors(groups)
    if backend == "pallas":
        from k8s1m_tpu.ops.pallas_topk import kernel_name

        return kernel_name(aff, with_constraints)
    return (
        "filter_score_topk" + "_affinity" * aff
        + "_constraints" * bool(with_constraints)
    )
