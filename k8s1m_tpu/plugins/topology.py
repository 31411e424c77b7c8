"""PodTopologySpread + InterPodAffinity as count-table kernels.

Semantics (upstream parity, with documented divergences):

- Spread filter (whenUnsatisfiable=DoNotSchedule): placing the pod in
  domain d must keep ``count(d) + self - min_over_domains(count)`` within
  maxSkew; nodes missing the topology key fail the constraint.  Divergence:
  the global minimum is taken over all domains that currently contain at
  least one schedulable node, not over the pod's node-affinity-filtered
  subset (upstream computes the min after NodeAffinity pre-filtering).
- Spread score: constraints of both modes score; per constraint the least
  crowded domain gets 100 and the most crowded 0 (linear in count), then
  constraints average.  Upstream's normalization differs in shape but
  ranks domains identically (monotone decreasing in matching-pod count).
- Affinity required: a domain must contain a pod matching the term; the
  bootstrap exception (upstream's "no pod in the cluster matches" rule for
  self-matching terms) admits the first replica anywhere.
- Anti-affinity required: the domain must contain no matching pod, and —
  symmetry — no existing pod whose own required anti-affinity term matches
  the incoming pod may share a domain with it (own_* tables).
- Affinity score: preferred terms contribute weight x matching-pod-count
  (negated for anti), linearly rescaled to [0, 100] by the batch-static
  bound (see plugins/scores.py module doc for why static bounds).

The count tables make all of this O(B x N) gathers instead of upstream's
O(pods x nodes) selector walks — config 4 of BASELINE.json is the point.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax

from k8s1m_tpu.config import (
    SPREAD_DO_NOT_SCHEDULE,
    TOPO_HOSTNAME,
    TOPO_REGION,
    TOPO_ZONE,
)
from k8s1m_tpu.snapshot.constraints import ConstraintState
from k8s1m_tpu.snapshot.node_table import NodeTable
from k8s1m_tpu.snapshot.pod_encoding import PodBatch

# Python int, NOT jnp.int32: a module-level device array becomes a live
# buffer that jax captures as an executable *parameter* when other traces
# embed an equal constant, and the pjit fast path then drops it on cached
# re-execution ("supplied 66 buffers but compiled program expected 67").
_BIG = 1 << 30


@struct.dataclass
class TopoStats:
    """Batch-global reductions over the count tables (the prologue)."""

    spread_min: jax.Array   # i32[3, C] min count per topo granularity
    spread_max: jax.Array   # i32[3, C]
    tgt_max: jax.Array      # i32[A] max count over the term's domains
    tgt_total: jax.Array    # i32[A] total matching pods cluster-wide
    # i32[Z] / i32[R]: 1 where a zone / region holds a valid node (id 0,
    # "label missing", never): the domains spread_min ranges over, kept
    # for the in-wave skew count (engine/assign.py).
    zone_present: jax.Array
    region_present: jax.Array


def _domain_presence(table: NodeTable, size: int, ids, axis_name=None):
    # A [size, N] compare reduced along N: one streaming pass over the
    # columns, fused with a packed table's decode.  A scatter into so few
    # bins serialises on its collisions (~10 ms a column at 1M rows on a
    # TPU v5e, against ~1 ms for both columns compared).
    hit = (ids[None, :] == jnp.arange(size, dtype=ids.dtype)[:, None]) & table.valid[None, :]
    present = hit.any(axis=1).astype(jnp.int32)
    if axis_name is not None:
        present = lax.pmax(present, axis_name)
    return present.at[0].set(0)  # domain 0 = "label missing", never a domain


def _masked_min(tab, present):  # tab [C, D], present [D]
    m = jnp.where(present[None, :] > 0, tab, _BIG).min(axis=1)
    return jnp.where(m == _BIG, 0, m)


def _masked_max(tab, present):
    return jnp.where(present[None, :] > 0, tab, 0).max(axis=1)


def prologue(
    table: NodeTable,
    cons: ConstraintState,
    *,
    axis_name: str | None = None,
) -> TopoStats:
    """Global reductions before the chunk scan.  Under shard_map, pass the
    node-shard axis name so node-domain reductions cross shards."""
    valid = table.valid
    node_present = valid.astype(jnp.int32)

    def node_min(tab):
        m = jnp.where(node_present[None, :] > 0, tab, _BIG).min(axis=1)
        if axis_name is not None:
            m = lax.pmin(m, axis_name)
        return jnp.where(m == _BIG, 0, m)

    def node_max(tab):
        m = jnp.where(node_present[None, :] > 0, tab, 0).max(axis=1)
        if axis_name is not None:
            m = lax.pmax(m, axis_name)
        return m

    zone_present = _domain_presence(table, cons.spread_zone.shape[1], table.zone, axis_name)
    region_present = _domain_presence(table, cons.spread_region.shape[1], table.region, axis_name)

    spread_min = jnp.stack([
        node_min(cons.spread_node),
        _masked_min(cons.spread_zone, zone_present),
        _masked_min(cons.spread_region, region_present),
    ])
    spread_max = jnp.stack([
        node_max(cons.spread_node),
        _masked_max(cons.spread_zone, zone_present),
        _masked_max(cons.spread_region, region_present),
    ])

    tgt_max = jnp.maximum(
        node_max(cons.tgt_node),
        jnp.maximum(
            _masked_max(cons.tgt_zone, zone_present),
            _masked_max(cons.tgt_region, region_present),
        ),
    )
    tgt_node_total = cons.tgt_node.sum(axis=1)
    if axis_name is not None:
        tgt_node_total = lax.psum(tgt_node_total, axis_name)
    tgt_total = tgt_node_total + cons.tgt_zone.sum(axis=1) + cons.tgt_region.sum(axis=1)
    return TopoStats(
        spread_min=spread_min, spread_max=spread_max,
        tgt_max=tgt_max, tgt_total=tgt_total,
        zone_present=zone_present, region_present=region_present,
    )


def _counts_for(node_tab, zone_tab, region_tab, slot, topo, table: NodeTable):
    """Gather per-node domain counts for [B, S] (slot, topo) refs -> [B, S, N]."""
    cnt_node = jnp.take(node_tab, slot, axis=0)                      # [B,S,N]
    cnt_zone = jnp.take(
        jnp.take(zone_tab, slot, axis=0), table.zone, axis=-1
    )
    cnt_region = jnp.take(
        jnp.take(region_tab, slot, axis=0), table.region, axis=-1
    )
    t = topo[:, :, None]
    cnt = jnp.where(
        t == TOPO_HOSTNAME, cnt_node,
        jnp.where(t == TOPO_ZONE, cnt_zone, cnt_region),
    )
    domain_ok = jnp.where(
        t == TOPO_HOSTNAME, True,
        jnp.where(
            t == TOPO_ZONE, (table.zone != 0)[None, None, :],
            (table.region != 0)[None, None, :],
        ),
    )
    return cnt, domain_ok


def _stat_for(stat, slot, topo):
    """Select a [3, C] per-topo stat for [B, S] refs -> [B, S]."""
    by_topo = jnp.take(stat, slot, axis=1)                            # [3,B,S]
    t = topo[None, :, :]
    sel = jnp.where(
        t == TOPO_HOSTNAME, by_topo[0:1],
        jnp.where(t == TOPO_ZONE, by_topo[1:2], by_topo[2:3]),
    )
    return sel[0]


def filter_and_score(
    table: NodeTable,
    batch: PodBatch,
    cons: ConstraintState,
    stats: TopoStats,
    spread_weight: float,
    ipa_weight: float,
):
    """(mask bool[B, N], score i32[B, N]) over one node chunk.

    A zero ``spread_weight`` / ``ipa_weight`` skips that plugin's
    *scoring* arithmetic at trace time — the weights arrive as static
    Python ints from the Profile — while the hard-constraint filtering
    (spread maxSkew, required [anti-]affinity, the symmetry mask)
    always runs: degraded overload modes (k8s1m_tpu/loadshed) trade
    placement quality, never correctness.
    """
    n = table.num_rows

    # ---- topology spread ----
    cnt, domain_ok = _counts_for(
        cons.spread_node, cons.spread_zone, cons.spread_region,
        batch.spread_cid, batch.spread_topo, table,
    )                                                                 # [B,S,N]
    min_c = _stat_for(stats.spread_min, batch.spread_cid, batch.spread_topo)
    self_inc = batch.spread_self.astype(jnp.int32)
    skew_ok = (cnt + self_inc[:, :, None] - min_c[:, :, None]) <= (
        batch.spread_max_skew[:, :, None]
    )
    hard = batch.spread_valid & (batch.spread_mode == SPREAD_DO_NOT_SCHEDULE)
    spread_mask = (~hard[:, :, None] | (domain_ok & skew_ok)).all(axis=1)

    spread_score = None
    if spread_weight:
        # score: least-crowded domain 100, most-crowded 0, avg over refs.
        max_c = _stat_for(
            stats.spread_max, batch.spread_cid, batch.spread_topo
        )
        denom = jnp.maximum(max_c - min_c, 1)[:, :, None]
        s_ref = 100.0 * (max_c[:, :, None] - cnt) / denom
        s_ref = jnp.where(domain_ok, jnp.clip(s_ref, 0.0, 100.0), 0.0)
        live = batch.spread_valid
        num_refs = jnp.maximum(live.sum(axis=1), 1)
        spread_score = (
            (s_ref * live[:, :, None]).sum(axis=1) / num_refs[:, None]
        )

    # ---- inter-pod affinity: the pod's own terms ----
    tcnt, t_domain_ok = _counts_for(
        cons.tgt_node, cons.tgt_zone, cons.tgt_region,
        batch.ipa_tid, batch.ipa_topo, table,
    )                                                                 # [B,A,N]
    total = jnp.take(stats.tgt_total, batch.ipa_tid)                  # [B,A]
    bootstrap = (total == 0) & batch.ipa_self
    req_aff_ok = t_domain_ok & ((tcnt > 0) | bootstrap[:, :, None])
    req_anti_ok = ~t_domain_ok | (tcnt == 0)
    live_req = batch.ipa_valid & batch.ipa_required
    term_ok = jnp.where(
        (live_req & ~batch.ipa_anti)[:, :, None], req_aff_ok,
        jnp.where((live_req & batch.ipa_anti)[:, :, None], req_anti_ok, True),
    )
    ipa_mask = term_ok.all(axis=1)

    # symmetry: existing pods' required anti-affinity (own_* only contains
    # required-anti owners) blocks domains for pods their selector matches.
    ocnt, o_domain_ok = _counts_for(
        cons.own_node, cons.own_zone, cons.own_region,
        batch.iinc_tid, batch.iinc_topo, table,
    )                                                                 # [B,AI,N]
    sym_ok = (~batch.iinc_valid[:, :, None] | ~o_domain_ok | (ocnt == 0)).all(axis=1)
    ipa_mask = ipa_mask & sym_ok

    ipa_score = None
    if ipa_weight:
        # preferred terms: weight x count, rescaled by the static bound.
        pref = batch.ipa_valid & ~batch.ipa_required
        sign = jnp.where(batch.ipa_anti, -1, 1) * batch.ipa_weight    # [B,A]
        raw = (jnp.where(pref[:, :, None] & t_domain_ok, tcnt, 0)
               * sign[:, :, None]).sum(axis=1)                        # [B,N]
        bound = (
            jnp.abs(batch.ipa_weight)
            * jnp.take(stats.tgt_max, batch.ipa_tid) * pref
        ).sum(axis=1)                                                 # [B]
        has_pref = pref.any(axis=1)
        ipa_score = jnp.where(
            has_pref[:, None],
            50.0 + 50.0 * raw / jnp.maximum(bound, 1)[:, None],
            0.0,
        )
        ipa_score = jnp.clip(ipa_score, 0.0, 100.0)

    mask = spread_mask & ipa_mask
    score = jnp.zeros(mask.shape, jnp.int32)
    if spread_weight:
        score += jnp.floor(spread_score).astype(jnp.int32) * int(spread_weight)
    if ipa_weight:
        score += jnp.floor(ipa_score).astype(jnp.int32) * int(ipa_weight)
    return mask, score
