"""deltasched: incremental filter+score via shape-keyed plane reuse.

The steady-state regime is heavy traffic at low churn: millions of
template-shaped pods per hour against a table whose rows barely move
(hotfeed's template hit rate is 1.0 at 90%-hot pools).  Yet every wave
recomputes filter+score over ALL N rows even when the pod's structural
shape was seen last wave and <0.1% of rows changed.  This module keeps,
per pod *shape* (snapshot/hotfeed.shape_key: structural fingerprint +
request scalars), the HBM-resident *plane* that pass produces — the
feasibility mask ``bool[N]`` and the pre-greedy integer score ``i32[N]``
— and lets a wave whose every pod hits the cache run the full kernel
only over the rows that actually moved:

    dirty rows (the coordinator's _dirty_rows/_dirty_caps scatters,
    retired bind commits, eviction repairs — journaled through
    snapshot/node_table.RowVersions)
  ∪ rows touched by in-flight binds (each unretired wave's device-
    resident ``rows_dev`` array, consumed on-stream — the host never
    syncs to learn them)

then scatter-merge the recomputed columns into the cached planes and
proceed straight to the per-pod hashed top-k over the merged plane.
Per-wave device work drops from O(batch × N × plugin-chain) toward
O(batch × dirty) plus a cheap O(batch × N) hash/top-k tail.

**The cache is an invisible replay, never a semantic.**  Binds must be
BYTE-IDENTICAL to full recompute under churn, pipelining, preemption,
gangs, mesh sharding and donation (tests/test_deltasched.py).  The
contract that makes that hold:

- a plane is keyed on ``(shape_key, vocab generation)``; pods whose
  mask/score reads the live constraint count tables (spread/affinity
  refs or incs) are NOT cacheable — their key is None and the wave
  takes the full pass (the constraint stage is an exact identity for
  termless pods, so delta waves may skip it entirely);
- row-level invalidation is version-journaled (RowVersions): every
  device-table row mutation is noted when its scatter/commit is
  *dispatched*, so a delta wave enqueued later recomputes those rows
  from the post-mutation table — stream order does the rest;
- capacity-delta rows and structural rows ride the same recompute
  (recomputing both planes for a dirty row is conservative and exact);
- vocab generation movement, packing rebuilds, resync and mesh/table
  rebuilds drop the cache WHOLESALE (``drop_all``) — those events
  change what encoded ids *mean*, which no row set can bound;
- HBM is bounded: a fixed slot count with LRU shape eviction
  (``deltasched_evictions_total``).

Sharding (parallel/sharded_cycle.make_sharded_delta_step): the planes
shard on ``sp`` along the row axis exactly like every packed table
plane; the dirty-slice gather and the plane top-k stay shard-local and
tie-breaks hash over global coordinates, so the mesh delta wave is
byte-identical to the single-device delta wave — which is byte-identical
to full recompute.

Host-side reads of the plane buffers outside this module MUST flow
through the epoch-checked accessor ``DeltaPlaneCache.planes(gen)``
(enforced statically by the ``deltacache-epoch-keyed`` graftlint pass):
raw attribute access would let a stale-generation plane reach a wave.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from k8s1m_tpu.obs.metrics import Counter, Gauge
from k8s1m_tpu.plugins.registry import Profile, score_and_filter
from k8s1m_tpu.snapshot.node_table import RowVersions
from k8s1m_tpu.snapshot.packing import is_packed, unpack_chunk

log = logging.getLogger("k8s1m.deltasched")

_WAVES = Counter(
    "deltasched_waves_total",
    "Coordinator waves by execution path (delta = plane-cached step over "
    "the dirty slice; full = the ordinary full filter+score pass)",
    ("path",),
)
_SHAPE_HITS = Counter(
    "deltasched_shape_hits_total",
    "Per-pod shape lookups answered by a live cached plane", (),
)
_SHAPE_MISSES = Counter(
    "deltasched_shape_misses_total",
    "Per-pod shape lookups that missed (cold shape, evicted, "
    "generation-dropped, or an uncacheable constraint-coupled shape)",
    (),
)
_EVICTIONS = Counter(
    "deltasched_evictions_total",
    "Cached shape planes evicted by the LRU slot bound "
    "(the HBM-budget pressure signal)", (),
)
_FILLS = Counter(
    "deltasched_fills_total",
    "Plane fills dispatched (cold recurring shapes populated, or stale "
    "slots refilled after journal compaction / oversized dirty sets)", (),
)
_DIRTY_ROWS = Counter(
    "deltasched_dirty_rows_total",
    "Host-journaled dirty rows recomputed across delta waves (mean "
    "dirty fraction = this / (delta waves x table rows))", (),
)
_PLANES_RESIDENT = Gauge(
    "deltasched_planes_resident",
    "Shape planes currently resident across live delta caches", (),
)
_INDEX_WAVES = Counter(
    "deltasched_index_waves_total",
    "Delta waves by candidate-index outcome (index = per-pod candidates "
    "derived from the score-stratified top-K index, O(dirty + K*batch); "
    "plane = the index failed closed and the wave fell back to the full "
    "O(batch * N) merged-plane top-k scan)",
    ("path",),
)
_INDEX_DROPS = Counter(
    "deltasched_index_drops_total",
    "Candidate-index invalidations by cause: underflow = eviction-floor "
    "underflow (more candidates invalidated than K spares), "
    "oversized-dirty = the wave's dirty slice exceeded the in-step "
    "index-update budget, fill = slot (re)filled so its index must "
    "rebuild, plus every wholesale cache drop reason (generation / "
    "resync / packing / fill-error / dispatch-error)",
    ("reason",),
)
_INDEX_TOUCHED = Counter(
    "deltasched_index_touched_rows_total",
    "Rows the delta wave's candidate derivation actually visited, by "
    "path (index: dirty slice + K index entries; plane: the full N-row "
    "scan plus the dirty slice) — divide by deltasched_index_waves_total "
    "x table rows for the sublinearity ratio the index exists to buy",
    ("path",),
)
_LIVE_CACHES: weakref.WeakSet = weakref.WeakSet()
_PLANES_RESIDENT.set_function(
    lambda: sum(len(c._slot_of) for c in _LIVE_CACHES)
)


def resolve_deltasched(arg: str | bool | None = None) -> str:
    """Delta-cache mode: "off" (also ``None``/``False``) or "on" (also
    ``True``).  Unknown values fail loudly (a typo silently running full
    recompute would invalidate every steady-state number downstream)."""
    if isinstance(arg, bool):
        return "on" if arg else "off"
    mode = "off" if arg is None else arg
    if mode not in ("off", "on"):
        raise ValueError(f"deltacache must be off|on, got {mode!r}")
    return mode


# ---- device-side plane ops (traced inside the delta/fill executables) ----


def combine_dirty(host_dirty, inflight_rows, sentinel: int):
    """One global dirty-row vector: the host-journaled rows (already
    sentinel-padded) plus every in-flight wave's bind rows, with their
    -1 unbound markers remapped to the out-of-bounds sentinel so the
    scatter-merge drops them."""
    parts = [host_dirty]
    for r in inflight_rows:
        parts.append(jnp.where(r >= 0, r, sentinel).astype(jnp.int32))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def gather_rows(table, idx):
    """A decoded mini-table of the rows at ``idx`` (clipped; callers
    drop out-of-range entries at scatter time).  A packed table decodes
    the gathered rows here — unpack_chunk is row-elementwise, so it
    applies to an arbitrary gathered row set just like a chunk slice."""
    n = table.num_rows
    safe = jnp.clip(idx, 0, n - 1)
    sub = jax.tree.map(lambda a: a[safe], table)
    return unpack_chunk(sub) if is_packed(sub) else sub


def merge_dirty_planes(
    table, batch, profile: Profile, slot_ids, pmask, pscore, rows
):
    """Recompute filter+score for ``rows`` against the CURRENT table and
    scatter-merge the columns into the cached planes at each pod's slot.

    ``rows`` are plane-local (shard-local on the mesh) with the
    out-of-bounds sentinel for padding/unowned entries; ``slot_ids``
    carry the slot-count sentinel for padded pods.  Duplicate (slot,
    row) targets always carry identical values — two pods share a slot
    only when they share the full shape key, and a row listed twice
    recomputes the same column — so the scatter is deterministic.

    Constraints are deliberately absent: a delta wave only ever carries
    constraint-termless pods, for which the constraint stage is an
    exact identity (plugins/topology.filter_and_score masks nothing and
    scores zero when no term is valid).
    """
    mask_d, score_d = score_and_filter(
        gather_rows(table, rows), batch, profile, None, None
    )
    at = (slot_ids[:, None], rows[None, :])
    pmask = pmask.at[at].set(mask_d, mode="drop")
    pscore = pscore.at[at].set(score_d, mode="drop")
    # The recomputed columns come back alongside the merged planes: the
    # candidate-index update (update_index) keys on exactly these values
    # and recomputing them there would double the dirty gather.
    return pmask, pscore, mask_d, score_d


def plane_topk(
    pmask, pscore, slot_ids, seed, *, chunk: int, k: int,
    row_offset=0, pod_offset=0, stratum_bits: int = 0,
):
    """Per-pod hashed top-k over the merged planes — the delta wave's
    replacement for the full filter+score chunk scan.

    Mirrors engine/cycle.filter_score_topk's scan EXACTLY (same chunk
    walk, same pack_hashed jitter over global (pod row, node column)
    coordinates, same merge_topk carry) so the surviving candidates are
    bit-identical to the full pass over an equal mask/score field —
    the byte-identity contract's tail half.  Payload columns come back
    zeroed; ``attach_payload`` gathers them from the live table (the
    values are gated by feasibility downstream, so end-gather equals
    the full pass's per-chunk gather byte-for-byte).
    """
    from k8s1m_tpu.engine.cycle import (
        Candidates,
        chunk_topk,
        empty_candidates,
        merge_topk,
    )
    from k8s1m_tpu.ops.priority import pack_hashed

    n = pmask.shape[1]
    if n % chunk:
        raise ValueError(f"plane rows {n} not divisible by chunk {chunk}")
    num_chunks = n // chunk
    b = slot_ids.shape[0]
    pod_rows = lax.broadcasted_iota(jnp.int32, (b, 1), 0) + pod_offset
    zeros = jnp.zeros((b, k), jnp.int32)

    def body(carry, _):
        carry, ci = carry
        start = ci * chunk
        m = jnp.take(
            lax.dynamic_slice_in_dim(pmask, start, chunk, 1), slot_ids, 0
        )
        sc = jnp.take(
            lax.dynamic_slice_in_dim(pscore, start, chunk, 1), slot_ids, 0
        )
        node_cols = (
            lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
            + start + row_offset
        )
        prio = pack_hashed(sc, seed, m, pod_rows, node_cols, stratum_bits)
        top_prio, idx = chunk_topk(prio, k)
        local = Candidates(
            idx=(idx + start + row_offset).astype(jnp.int32),
            prio=top_prio,
            cpu=zeros, mem=zeros, pods=zeros, zone=zeros, region=zeros,
        )
        return (merge_topk(carry, local, k), ci + 1), None

    init = (empty_candidates(b, k), jnp.int32(0))
    if num_chunks == 1:
        (cand, _), _ = body(init, None)
    else:
        (cand, _), _ = lax.scan(body, init, None, length=num_chunks)
    return cand.replace(idx=jnp.where(cand.prio >= 0, cand.idx, -1))


# ---- score-stratified candidate index (device half) -----------------------
#
# Per resident shape slot, an HBM top-K candidate set over the cached
# plane: ``idx_row i32[S, K]`` (global rows, stored ASCENDING — the
# storage order IS the earlier-row-wins tie-break of the full chunk
# scan), ``idx_class i32[S, K]`` (ops/priority.class_key: the top
# 11 + stratum_bits priority bits, the part independent of seed and pod
# row; -1 = empty entry, whose row holds the out-of-bounds sentinel N),
# and ``idx_floor i32[S]``.  The floor invariant everything rests on:
#
#     every feasible row NOT in a slot's index has class_key <= floor.
#
# floor == -1 means the index is EXHAUSTIVE (never evicted: it holds
# every feasible row); floor == INDEX_FLOOR_UNBUILT means the slot has
# no index yet (fresh fill, reset) and fails closed.  A wave may derive
# its candidates from the index iff every used slot has >= k entries
# STRICTLY above its floor (or is exhaustive): those entries beat every
# unindexed row for every wave seed and every pod row (class_key doc),
# so the true top-k is a subset of the index and the reconstructed
# priorities — (class << low) | per-pod jitter low bits — are
# bit-identical to pack_hashed over the full plane.  Anything else
# fails closed to plane_topk, counted in deltasched_index_*.

INDEX_FLOOR_UNBUILT = np.iinfo(np.int32).max


def dedup_rows(rows, n: int):
    """First-occurrence filter over the combined dirty vector: entries
    whose row repeats earlier collapse to the out-of-bounds sentinel.
    The plane scatter-merge tolerates duplicates (same row recomputes
    the same column), but the index update must not insert one row
    twice — a duplicate entry would shadow a real candidate out of the
    top K and break the floor invariant's counting."""
    d = rows.shape[0]
    iota = lax.iota(jnp.int32, d)
    first = jnp.full(n + 1, d, jnp.int32).at[rows].min(iota)
    keep = (rows < n) & (first[rows] == iota)
    return jnp.where(keep, rows, n)


def _sort_desc_class(cls, row, keep: int):
    """Two-key sort of candidate entries — descending class, ties by
    ASCENDING row (deterministic, and the kept boundary then matches
    the full scan's earlier-row-wins order) — returning the first
    ``keep`` entries re-sorted to ascending-row storage order plus the
    class of the first DISCARDED entry (the eviction-floor raise)."""
    neg, row_s = lax.sort((-cls, row), num_keys=2, dimension=1)
    kept_cls, kept_row = -neg[:, :keep], row_s[:, :keep]
    spill = neg[:, keep] * -1
    kept_row, kept_cls = lax.sort((kept_row, kept_cls), num_keys=1, dimension=1)
    return kept_row, kept_cls, spill


def update_index(
    idx_row, idx_class, idx_floor, rep_idx, rows, mask_d, score_d, n: int,
    *, stratum_bits: int,
):
    """Apply one wave's dirty slice to the candidate index, in-step.

    ``rows`` is the deduped dirty vector (sentinel = ``n``, the plane
    row count); ``mask_d`` / ``score_d`` are merge_dirty_planes'
    recomputed per-pod columns ([B, D]) and ``rep_idx i32[S]`` names
    one batch position per slot USED this wave (sentinel = batch size)
    — any pod of the slot's shape scores identically, so one
    representative row of the recompute is the slot's entire dirty
    view.  Per used slot: invalidate entries whose row went dirty,
    re-insert dirty rows that are feasible and STRICTLY above the
    floor, keep the top K by (class desc, row asc), and raise the
    floor to the best evicted class.  Slots without a representative
    (not used this wave) are untouched — their stale rows stay covered
    by the same freshness-stamp dirty-slice discipline that covers
    their planes."""
    from k8s1m_tpu.ops.priority import class_key

    b = mask_d.shape[0]
    rep = jnp.clip(rep_idx, 0, b - 1)
    valid_rep = rep_idx < b
    m = jnp.take(mask_d, rep, 0)          # [S, D]
    sc = jnp.take(score_d, rep, 0)        # [S, D]

    cls_d = class_key(sc, rows[None, :], stratum_bits)
    qualify = m & (rows < n)[None, :] & (cls_d > idx_floor[:, None])
    cand_cls = jnp.where(qualify, cls_d, -1)
    cand_row = jnp.where(qualify, jnp.broadcast_to(rows[None, :], cls_d.shape), n)

    flag = jnp.zeros((n + 1,), jnp.bool_).at[rows].set(True)
    inv = flag[idx_row]
    old_cls = jnp.where(inv, -1, idx_class)
    old_row = jnp.where(inv, n, idx_row)

    k_idx = idx_row.shape[1]
    merged_cls = jnp.concatenate([old_cls, cand_cls], axis=1)
    merged_row = jnp.concatenate([old_row, cand_row], axis=1)
    new_row, new_cls, spill = _sort_desc_class(merged_cls, merged_row, k_idx)
    new_floor = jnp.maximum(idx_floor, spill)

    vr = valid_rep[:, None]
    return (
        jnp.where(vr, new_row, idx_row),
        jnp.where(vr, new_cls, idx_class),
        jnp.where(valid_rep, new_floor, idx_floor),
    )


def index_usable(idx_class, idx_floor, slot_ids, k: int):
    """Device scalar: may THIS wave derive candidates from the index?
    Per slot: >= k entries strictly above the floor, or exhaustive
    (floor -1, never evicted — then the index IS the feasible set and
    fewer than k entries reproduces the full scan's padding exactly).
    The padding slot (sentinel = slot count) is always usable.  The
    decision stays on device (lax.cond selects the tail), so failing
    closed costs no host sync."""
    above = jnp.sum((idx_class > idx_floor[:, None]).astype(jnp.int32), axis=1)
    ok = (above >= k) | (idx_floor == -1)
    ok = jnp.concatenate([ok, jnp.ones((1,), jnp.bool_)])
    return jnp.all(ok[slot_ids])


def index_topk(
    idx_row, idx_class, slot_ids, seed, *, k: int, stratum_bits: int,
):
    """plane_topk's sublinear twin: per-pod hashed top-k over the K
    index entries instead of the N plane columns.  Priorities
    reconstruct as (class << low) | (per-pod jitter & low-mask) — by
    the class_key decomposition this is bit-identical to pack_hashed
    over the same (seed, pod row, node column), and the ascending-row
    storage order makes chunk_topk's earlier-index-wins tie rule
    coincide with the full scan's earlier-row-wins.  Single-device
    only: the index is not maintained under a mesh (the sharded delta
    step always runs the plane tail)."""
    from k8s1m_tpu.engine.cycle import Candidates, chunk_topk
    from k8s1m_tpu.ops.priority import JITTER_BITS, hash_jitter

    b = slot_ids.shape[0]
    s = idx_row.shape[0]
    sl = jnp.clip(slot_ids, 0, s - 1)  # padding pods read slot S-1, like jnp.take
    rows = idx_row[sl]                 # [B, K] global rows (sentinel = N)
    cls = idx_class[sl]
    pod_rows = lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    low = JITTER_BITS - stratum_bits
    j = hash_jitter(seed, pod_rows, rows)
    prio = jnp.where(cls >= 0, (cls << low) | (j & ((1 << low) - 1)), -1)
    top_prio, sel = chunk_topk(prio, k)
    idx = jnp.take_along_axis(rows, sel, axis=1)
    zeros = jnp.zeros((b, k), jnp.int32)
    cand = Candidates(
        idx=idx.astype(jnp.int32), prio=top_prio,
        cpu=zeros, mem=zeros, pods=zeros, zone=zeros, region=zeros,
    )
    return cand.replace(idx=jnp.where(cand.prio >= 0, cand.idx, -1))


def rebuild_index(
    pmask, pscore, rebuild_slots, rep_idx, idx_row, idx_class, idx_floor,
    *, chunk: int, stratum_bits: int, batch_b: int,
):
    """The plane tail's index maintenance: rebuild the candidate index
    from the merged planes for the (host-rotated, fill_batch-bounded)
    ``rebuild_slots``, and fail every OTHER slot used this wave closed
    (floor = INDEX_FLOOR_UNBUILT).  The wave's freshness stamps advance
    for all used slots at commit, so a used slot that neither rebuilt
    nor invalidated would hold entries the dirty-slice discipline will
    never revisit — a silent byte-identity break.  Chunked running
    top-K: per chunk, class the feasible columns, two-key sort against
    the carry, track the best discarded class as the floor."""
    from k8s1m_tpu.ops.priority import class_key

    s, n = pmask.shape
    k_idx = idx_row.shape[1]
    r = rebuild_slots.shape[0]
    rs = jnp.clip(rebuild_slots, 0, s - 1)
    num_chunks = n // chunk

    def body(carry, _):
        crow, ccls, cfloor, ci = carry
        start = ci * chunk
        pm = jnp.take(lax.dynamic_slice_in_dim(pmask, start, chunk, 1), rs, 0)
        sc = jnp.take(lax.dynamic_slice_in_dim(pscore, start, chunk, 1), rs, 0)
        cols = lax.broadcasted_iota(jnp.int32, (1, chunk), 1) + start
        cls = jnp.where(pm, class_key(sc, cols, stratum_bits), -1)
        rows = jnp.where(pm, cols + jnp.zeros((r, 1), jnp.int32), n)
        mrow = jnp.concatenate([crow, rows], axis=1)
        mcls = jnp.concatenate([ccls, cls], axis=1)
        nrow, ncls, spill = _sort_desc_class(mcls, mrow, k_idx)
        return (nrow, ncls, jnp.maximum(cfloor, spill), ci + 1), None

    init = (
        jnp.full((r, k_idx), n, jnp.int32),
        jnp.full((r, k_idx), -1, jnp.int32),
        jnp.full((r,), -1, jnp.int32),
        jnp.int32(0),
    )
    if num_chunks == 1:
        (crow, ccls, cfloor, _), _ = body(init, None)
    else:
        (crow, ccls, cfloor, _), _ = lax.scan(body, init, None, length=num_chunks)

    # Used-but-not-rebuilt slots fail closed; rebuilt slots scatter in
    # (the padding sentinel in rebuild_slots drops out of range).
    used = rep_idx < batch_b
    rebuilt = jnp.zeros((s + 1,), jnp.bool_).at[rebuild_slots].set(True)[:s]
    idx_floor = jnp.where(used & ~rebuilt, INDEX_FLOOR_UNBUILT, idx_floor)
    idx_row = idx_row.at[rebuild_slots].set(crow, mode="drop")
    idx_class = idx_class.at[rebuild_slots].set(ccls, mode="drop")
    idx_floor = idx_floor.at[rebuild_slots].set(cfloor, mode="drop")
    return idx_row, idx_class, idx_floor


def note_index_oversized() -> None:
    """Host stamp at launch for an index-enabled wave whose dirty slice
    exceeded index_dirty_cap: the step compiled the plane-only variant,
    so the in-step index update never ran (trace-time shape decision,
    engine/cycle._jitted_schedule_delta)."""
    _INDEX_DROPS.inc(reason="oversized-dirty")


def note_index_wave(
    flag: int, attempted: bool, touched_index: int, touched_plane: int
) -> None:
    """Host stamp at wave retire for one index-enabled delta wave:
    ``flag`` is the device path flag the step returned (1 = candidates
    came from the index, 0 = plane tail), ``attempted`` the host-side
    dirty-cap decision, and the touched counts feed the sublinearity
    ratio.  An attempted wave that still ran the plane tail is an
    eviction-floor underflow — the fail-closed path the index metric
    family exists to make visible."""
    if flag:
        _INDEX_WAVES.inc(path="index")
        _INDEX_TOUCHED.inc(touched_index, path="index")
    else:
        _INDEX_WAVES.inc(path="plane")
        _INDEX_TOUCHED.inc(touched_plane, path="plane")
        if attempted:
            _INDEX_DROPS.inc(reason="underflow")


def attach_payload(table, cand, row_offset=0):
    """Gather the candidate payload (free capacity at batch start,
    topology domains) from the live table at the surviving top-k rows.

    The full pass gathers these per chunk during the scan; the table
    does not change within a step, so gathering at the end reads the
    identical values — and infeasible candidates' payload (clipped
    garbage) is unread downstream (greedy_assign gates on prio >= 0,
    the assignment gates on bound)."""
    local = cand.idx - row_offset
    sub = gather_rows(table, local.reshape(-1))
    free_cpu, free_mem, free_pods = sub.free()
    shape = cand.idx.shape
    return cand.replace(
        cpu=free_cpu.reshape(shape),
        mem=free_mem.reshape(shape),
        pods=free_pods.reshape(shape),
        zone=sub.zone.reshape(shape),
        region=sub.region.reshape(shape),
    )


def fill_planes_scan(
    table, batch, profile: Profile, fill_slots, pmask, pscore, *, chunk: int
):
    """Populate plane rows for a batch of shape representatives: one
    full chunked filter+score pass over the (shard-local) table, each
    chunk's columns scattered into the representatives' slots.  The
    sentinel slot (out of bounds) drops padded representatives."""
    from k8s1m_tpu.engine.cycle import _slice_table

    n = pmask.shape[1]
    if n % chunk:
        raise ValueError(f"plane rows {n} not divisible by chunk {chunk}")
    num_chunks = n // chunk

    def body(carry, _):
        pmask, pscore, ci = carry
        start = ci * chunk
        tchunk = _slice_table(table, start, chunk)
        mask, score = score_and_filter(tchunk, batch, profile, None, None)
        cols = start + lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        at = (fill_slots[:, None], cols)
        pmask = pmask.at[at].set(mask, mode="drop")
        pscore = pscore.at[at].set(score, mode="drop")
        return (pmask, pscore, ci + 1), None

    init = (pmask, pscore, jnp.int32(0))
    if num_chunks == 1:
        (pmask, pscore, _), _ = body(init, None)
    else:
        (pmask, pscore, _), _ = lax.scan(body, init, None, length=num_chunks)
    return pmask, pscore


# ---- host-side cache controller -------------------------------------------


@dataclasses.dataclass
class WavePlan:
    """One wave's delta decision (DeltaPlaneCache.plan).

    ``fill_idx``/``fill_slots`` name the batch positions whose shapes
    must be plane-filled BEFORE the wave dispatches (recurring shapes
    being promoted, or stale slots being refreshed) — the coordinator
    encodes those representatives and runs the fill executable whether
    or not the wave itself goes delta.  ``slot_ids`` is None for a full
    wave (some shape stayed unresolvable); otherwise the wave runs the
    delta step with ``dirty`` (sentinel-padded global rows) and the
    stamps in ``stamp_slots`` applied at commit time."""

    fill_idx: list[int]
    fill_slots: list[int]
    slot_ids: np.ndarray | None = None
    dirty: np.ndarray | None = None
    stamp_slots: tuple[int, ...] = ()
    stamp_ver: int = 0
    # Candidate-index plumbing (index_k > 0 caches only): one
    # representative batch position per slot (sentinel = batch size)
    # for the in-step index update, and the fill_batch-bounded,
    # host-rotated slot list the plane tail rebuilds when the index
    # fails closed.  None when the cache runs without an index.
    rep_idx: np.ndarray | None = None
    rebuild_slots: np.ndarray | None = None


class DeltaPlaneCache:
    """Host controller of the HBM-resident per-shape plane cache.

    Owns the device plane buffers (``bool[S, N]`` mask + ``i32[S, N]``
    score, sharded over ``sp`` on the row axis under a mesh), the shape
    key → slot map with LRU eviction, the per-slot freshness stamps,
    and the row-version journal consumers invalidate through.  All
    state is cycle-thread-confined, like the dirty-row sets it mirrors.
    """

    def __init__(
        self,
        num_rows: int,
        *,
        slots: int = 64,
        fill_batch: int = 16,
        journal_cap: int | None = None,
        seen_cap: int = 1 << 16,
        dirty_cap: int | None = None,
        sharding=None,
        index_k: int = 0,
        stratum_bits: int = 0,
        index_dirty_cap: int | None = None,
    ) -> None:
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if index_k < 0:
            raise ValueError(f"index_k must be >= 0, got {index_k}")
        if index_k and sharding is not None:
            # The index is a single-device structure: under a mesh the
            # delta wave always runs the (shard-local) plane tail, and a
            # silently-ignored index flag would report index-path waves
            # that never happened.
            raise ValueError(
                "the candidate index does not compose with sharded "
                "planes; run index_k=0 under a mesh"
            )
        self.num_rows = num_rows
        self.slots = slots
        self.fill_batch = fill_batch
        # Score-stratified candidate index (index_k > 0): per-slot
        # top-index_k candidate set over the cached plane, letting an
        # all-hit wave skip the O(batch x N) plane scan.  stratum_bits
        # must match the coordinator's (every pack_hashed call in the
        # system must draw the same jitter, or the index's class
        # algebra diverges from the real priorities).
        self.index_k = index_k
        self.stratum_bits = stratum_bits
        # Past this many combined dirty rows the in-step [S, K+D] sort
        # stops being a bargain; the wave takes the plane tail (and its
        # chunked rebuild) instead.  Trace-static: the dirty vector is
        # power-of-two padded, so this is a shape cutoff, not a value.
        self.index_dirty_cap = (
            index_dirty_cap if index_dirty_cap is not None
            else max(index_k, 1 << 12)
        )
        self._rebuild_rot = 0
        # Past this many dirty rows the delta recompute stops being a
        # bargain; the plan refreshes the used slots wholesale instead
        # (a fill is one F-pod pass, far cheaper than a B-pod full wave)
        # and the wave still runs delta over an empty dirty set.
        self.dirty_cap = (
            dirty_cap if dirty_cap is not None else max(num_rows // 4, 1)
        )
        if journal_cap is None:
            # Scale-aware journal bound (ISSUE 14): the cap tracks the
            # TABLE SIZE, not a fixed row budget — at the old 1<<16 a
            # 1M-row churn burst compacted the journal every wave and
            # fail-closed the whole cache to wholesale refills.  Half
            # the table (compacting down to dirty_cap, a quarter) keeps
            # the enumerable window a constant FRACTION of rows: the
            # delta lane stays plannable right up to the dirty_cap
            # break-even it would abandon anyway.  At 131072 rows this
            # derives exactly the old 1<<16 — the fixed-cap
            # differential gate (tests/test_megarow.py).
            journal_cap = max(1 << 16, num_rows // 2)
        self.versions = RowVersions(cap=journal_cap)
        self._sharding = sharding
        self._mask = None           # bool[S, N] device plane
        self._score = None          # i32[S, N] device plane
        self._idx_row = None        # i32[S, K] candidate rows (ascending)
        self._idx_class = None      # i32[S, K] candidate class keys
        self._idx_floor = None      # i32[S] eviction floors
        self._slot_of: collections.OrderedDict = collections.OrderedDict()
        self._free: list[int] = list(range(slots - 1, -1, -1))
        self._fresh: dict[int, int] = {}     # slot -> version stamp
        self._gen = -1                       # vocab generation of planes
        # Shapes seen once before (promotion gate: a shape plane-fills
        # only on its SECOND sighting, so one-shot shapes — the cold/
        # high-churn lane — never pay a fill).  Bounded like the
        # coordinator's _gang_oversize set: clearing just re-requires
        # one extra sighting from a repeat shape.
        self._seen: set = set()
        self._seen_cap = seen_cap
        _LIVE_CACHES.add(self)

    # -- device buffers ---------------------------------------------------

    def ensure_device(self) -> None:
        if self._mask is not None:
            return
        s, n = self.slots, self.num_rows
        mask = jnp.zeros((s, n), jnp.bool_)
        score = jnp.zeros((s, n), jnp.int32)
        if self._sharding is not None:
            mask = jax.device_put(mask, self._sharding)
            score = jax.device_put(score, self._sharding)
        self._mask, self._score = mask, score
        if self.index_k:
            # Fresh index buffers fail closed by construction: every
            # floor starts at the unbuilt sentinel, so no slot is
            # usable until the plane tail rebuilds it.
            self._idx_row = jnp.full((s, self.index_k), n, jnp.int32)
            self._idx_class = jnp.full((s, self.index_k), -1, jnp.int32)
            self._idx_floor = jnp.full((s,), INDEX_FLOOR_UNBUILT, jnp.int32)

    def planes(self, gen: int):
        """THE epoch-checked plane accessor (deltacache-epoch-keyed
        lint contract): hands out the device buffers only against the
        generation they were computed at.  A mismatch is a caller bug —
        the cache must be generation-checked (check_generation) before
        any wave planning touches it."""
        if gen != self._gen:
            raise RuntimeError(
                f"delta plane access at generation {gen} but planes are "
                f"stamped {self._gen}; call check_generation first"
            )
        self.ensure_device()
        return self._mask, self._score

    def index_state(self, gen: int):
        """The candidate-index twin of ``planes``: the epoch-checked
        accessor for the (idx_row, idx_class, idx_floor) device buffers
        (deltacache-index-keyed lint contract — raw attribute reads
        outside this module would let a stale-generation index reach a
        wave)."""
        if not self.index_k:
            raise RuntimeError("index_state on a cache built with index_k=0")
        if gen != self._gen:
            raise RuntimeError(
                f"candidate-index access at generation {gen} but planes "
                f"are stamped {self._gen}; call check_generation first"
            )
        self.ensure_device()
        return self._idx_row, self._idx_class, self._idx_floor

    def commit(self, mask, score, plan: WavePlan | None = None,
               index=None) -> None:
        """Store the (donated-through) plane buffers back and apply the
        plan's freshness stamps — called only after the dispatch that
        consumed the old buffers succeeded.  ``index`` is the donated-
        through (idx_row, idx_class, idx_floor) triple for index-enabled
        caches (the index shares the planes' freshness stamps: both are
        updated together for every used slot, in both tails)."""
        self._mask, self._score = mask, score
        if index is not None:
            self._idx_row, self._idx_class, self._idx_floor = index
        if plan is not None:
            for s in plan.stamp_slots:
                self._fresh[s] = plan.stamp_ver

    # -- invalidation -----------------------------------------------------

    def note_rows(self, rows) -> None:
        """Journal one batch of device-table row mutations (called when
        the mutating scatter/commit is DISPATCHED, so stream order
        guarantees later delta waves recompute from the new values)."""
        if self._slot_of or self._seen:
            self.versions.note(rows)

    def check_generation(self, gen: int) -> None:
        """Drop everything when the vocab generation moved: cached
        planes bake interned ids (tolerated taint sets, selector value
        ids), and a new id can change what an identical shape encodes."""
        if gen != self._gen:
            if self._slot_of:
                self.drop_all("generation")
            self._gen = gen

    def drop_all(self, reason: str) -> None:
        """Wholesale invalidation: table rebuilds (packing widening,
        mesh/device re-upload), resync, vocab generation movement.  The
        device buffers stay allocated — only the host keying drops, so
        the next fills simply overwrite."""
        if self._slot_of:
            log.info(
                "deltasched: dropping %d cached shape planes (%s)",
                len(self._slot_of), reason,
            )
        self._free = list(range(self.slots - 1, -1, -1))
        self._slot_of.clear()
        self._fresh.clear()
        self._seen.clear()
        if self.index_k:
            # The candidate index dies with the keying: a dropped slot
            # can only come back through a fill, and note_fill stamps
            # its floor to the unbuilt sentinel before any wave reads
            # it — so no device work is needed here, just the count.
            _INDEX_DROPS.inc(reason=reason)
        # Everything before this point is unenumerable by construction.
        self.versions.release(self.versions.ver + 1)

    def reset(self, reason: str) -> None:
        """drop_all PLUS discard the device buffers (a failed donating
        dispatch leaves them in an unknown consumed state); the next
        ensure_device reallocates zeros."""
        self.drop_all(reason)
        self._mask = self._score = None
        self._idx_row = self._idx_class = self._idx_floor = None

    # -- wave planning ----------------------------------------------------

    def _note_seen(self, key) -> None:
        if len(self._seen) >= self._seen_cap:
            self._seen.clear()
        self._seen.add(key)

    def _alloc_slot(self, key, busy) -> int | None:
        """A slot for ``key``: a free one, else LRU-evict — but NEVER a
        slot in ``busy`` (already assigned to a pod of the CURRENT
        wave): evicting one would refill it with this key's plane and
        the earlier pod would silently read the wrong shape's mask/
        score — a byte-identity break with no error.  Returns None when
        every resident slot is busy (the wave takes the full pass)."""
        if self._free:
            slot = self._free.pop()
        else:
            victim = next(
                (
                    (k, s) for k, s in self._slot_of.items()  # LRU first
                    if s not in busy
                ),
                None,
            )
            if victim is None:
                return None
            del self._slot_of[victim[0]]
            slot = victim[1]
            self._fresh.pop(slot, None)
            _EVICTIONS.inc()
        self._slot_of[key] = slot
        return slot

    def plan(self, keys, batch_b: int) -> WavePlan:
        """Decide this wave's path from the pods' shape keys.

        ``keys`` is one entry per real pod (None = uncacheable shape);
        ``batch_b`` is the encoded batch size (padding gets the slot
        sentinel).  Returns a WavePlan: fills to dispatch first, and —
        when every shape resolved to a live slot — the delta step's
        slot ids, sentinel-padded dirty rows, and commit stamps.
        """
        fills_idx: list[int] = []
        fills_slot: list[int] = []
        if any(k is None for k in keys):
            # Constraint-coupled shapes poison the whole wave (their
            # pods need the real constraint stage); no fills either —
            # mixed waves are the cold lane, keep it zero-overhead.
            _SHAPE_MISSES.inc(len(keys))
            _WAVES.inc(path="full")
            return WavePlan([], [])
        slot_ids = np.full(batch_b, self.slots, np.int32)
        hits = misses = 0
        missing = False
        filled_keys: dict = {}
        busy: set[int] = set()   # slots assigned to THIS wave so far
        for i, key in enumerate(keys):
            slot = self._slot_of.get(key)
            if slot is not None:
                self._slot_of.move_to_end(key)
                slot_ids[i] = slot
                busy.add(slot)
                hits += 1
                continue
            misses += 1
            prior = filled_keys.get(key)
            if prior is not None:
                slot_ids[i] = prior
                continue
            if key in self._seen and len(fills_idx) < self.fill_batch:
                slot = self._alloc_slot(key, busy)
                if slot is None:
                    # Every resident slot belongs to a pod of this very
                    # wave: no evictable victim.  Full pass.
                    missing = True
                    continue
                fills_idx.append(i)
                fills_slot.append(slot)
                filled_keys[key] = slot
                slot_ids[i] = slot
                busy.add(slot)
            else:
                self._note_seen(key)
                missing = True
        _SHAPE_HITS.inc(hits)
        if misses:
            _SHAPE_MISSES.inc(misses)
        if missing:
            _WAVES.inc(path="full")
            return WavePlan(fills_idx, fills_slot)

        # Dirty slice: rows mutated since the stalest used slot's fill.
        used = sorted({int(s) for s in slot_ids if s < self.slots})
        fresh_fills = set(fills_slot)
        stale = [
            s for s in used
            if s not in fresh_fills
            and self._fresh.get(s, -1) < self.versions.floor
        ]
        dirty: set[int] | None = set()
        live = [s for s in used if s not in fresh_fills and s not in stale]
        if live:
            vmin = min(self._fresh[s] for s in live)
            dirty = self.versions.rows_since(vmin)
        if dirty is None or len(dirty) > self.dirty_cap or stale:
            # Unenumerable or oversized delta (journal compaction, a
            # churn burst): refresh every used slot wholesale — one
            # F-shape fill pass — and run delta over the in-flight rows
            # alone.  Slots past the fill budget force the full pass.
            refresh = [s for s in used if s not in fresh_fills]
            if len(fills_idx) + len(refresh) > self.fill_batch:
                _WAVES.inc(path="full")
                return WavePlan(fills_idx, fills_slot)
            slot_at = {int(s): i for i, s in enumerate(slot_ids) if s < self.slots}
            for s in refresh:
                fills_idx.append(slot_at[s])
                fills_slot.append(s)
            dirty = set()
        _WAVES.inc(path="delta")
        _DIRTY_ROWS.inc(len(dirty))
        rep_idx = rebuild = None
        if self.index_k:
            rep_idx = np.full(self.slots, batch_b, np.int32)
            for i, s in enumerate(slot_ids.tolist()):
                if s < self.slots and rep_idx[s] == batch_b:
                    rep_idx[s] = i
            # Plane-tail rebuild list: fresh fills first (their floors
            # just failed closed), then the other used slots rotated so
            # a wave using more than fill_batch slots still converges
            # over consecutive underflow waves instead of starving a
            # fixed suffix.
            others = [s for s in used if s not in fresh_fills]
            if others:
                r = self._rebuild_rot % len(others)
                self._rebuild_rot += 1
                others = others[r:] + others[:r]
            order = list(fills_slot) + others
            rebuild = np.full(self.fill_batch, self.slots, np.int32)
            take = order[: self.fill_batch]
            rebuild[: len(take)] = take
        return WavePlan(
            fills_idx, fills_slot,
            slot_ids=slot_ids,
            dirty=self._pad_dirty(dirty),
            stamp_slots=tuple(used),
            stamp_ver=self.versions.ver,
            rep_idx=rep_idx,
            rebuild_slots=rebuild,
        )

    def _pad_dirty(self, rows: set) -> np.ndarray:
        """Sorted, power-of-two-padded dirty rows with the out-of-bounds
        sentinel (= num_rows) as padding, so the jitted step sees a
        handful of shapes instead of one trace per dirty count."""
        arr = np.fromiter(rows, np.int32, len(rows))
        arr.sort()
        cap = 1 << max(0, int(max(arr.size, 1) - 1).bit_length())
        out = np.full(cap, self.num_rows, np.int32)
        out[: arr.size] = arr
        return out

    def note_fill(self, plan: WavePlan) -> None:
        """Stamp freshly-filled slots at the journal version their fill
        dispatch observed (called right after the fill executable is
        enqueued)."""
        _FILLS.inc(len(plan.fill_slots))
        if self.index_k and plan.fill_slots:
            # A refilled slot's plane is brand new; its candidate index
            # is not.  Fail it closed (unbuilt floor) so the first wave
            # that uses it takes the plane tail and rebuilds — one tiny
            # host-dispatched scatter, ordered before the wave on the
            # same stream.
            self.ensure_device()
            self._idx_floor = self._idx_floor.at[
                np.asarray(plan.fill_slots, np.int32)
            ].set(INDEX_FLOOR_UNBUILT)
            _INDEX_DROPS.inc(len(plan.fill_slots), reason="fill")
        for s in plan.fill_slots:
            self._fresh[s] = self.versions.ver

    def abort_fills(self, plan: WavePlan) -> None:
        """Un-allocate the plan's fill slots (the representative encode
        failed, e.g. a query-key overflow across fill shapes): the keys
        drop back to seen-once and the wave takes the full pass."""
        for s in plan.fill_slots:
            self._fresh.pop(s, None)
            self._free.append(s)
        for key, slot in list(self._slot_of.items()):
            if slot in set(plan.fill_slots):
                del self._slot_of[key]
        plan.fill_idx.clear()
        plan.fill_slots.clear()

    @property
    def resident(self) -> int:
        return len(self._slot_of)
