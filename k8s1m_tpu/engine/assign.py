"""Greedy in-batch conflict resolution over per-pod bind candidates.

The reference schedules pods concurrently and lets two pods race for one
node; the loser's bind fails at the apiserver and rolls back (reference
README.adoc:558-560, "optimistic concurrency").  Batched on TPU, the same
problem is solved *before* binding: every pod brings its top-K candidate
nodes (already sorted by packed priority), and a sequential lax.scan over
the batch commits pods in order, re-checking candidate capacity against
what earlier pods in the batch just took.  A pod whose K candidates are all
exhausted leaves the batch unbound and is retried next cycle — exactly the
reference's conflict-rollback, but at O(B*K) cost with no apiserver
round-trip.

With ``skew`` the same scan also keeps PodTopologySpread's hard zone and
region constraints (whenUnsatisfiable: DoNotSchedule) exact *inside* the
wave: it carries the matching-pod count of every (constraint slot, domain)
forward from the wave-start tables, pod by pod in wave order, and a
candidate is legal for pod i only if binding it keeps ``count + self -
min over the present domains`` within maxSkew on the counts as pods j < i
left them — upstream's filter at every bind, not at wave boundaries.  The
candidates' own zone filter is switched off for those constraints
(engine/cycle.candidates) and they arrive one per zone, so whichever zone
is legal at a pod's turn has a candidate.  Hostname-keyed constraints are
not re-checked here: their domains are nodes, and the kernel's filter on
the wave-start counts stands for them.

The scan is tiny (B x K integers) and runs replicated on every device in
the sharded cycle, so no cross-device coordination is needed at commit time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax

from k8s1m_tpu.config import TOPO_HOSTNAME, TOPO_ZONE
from k8s1m_tpu.ops.priority import unpack_score

# Python int (see plugins/topology._BIG): above any count, below overflow.
_BIG = 1 << 30

# Why a valid pod left the wave unbound (``WaveSkew`` waves report the
# three sums): every candidate that was legal had lost its capacity to
# earlier pods of the wave; it had candidates and none was legal under
# the in-wave counts; the candidates stage found it no feasible row.
UNBOUND_REASONS = ("capacity", "skew", "no_candidate")


@struct.dataclass
class WaveSkew:
    """What the in-wave count needs.  Zone and region share one domain
    axis, region ids behind the zone ids (``D = Z + R``), so that a
    constraint of either key is one gather and one scatter."""

    counts: jax.Array     # i32[C, D] wave-start matching pods per (slot, domain)
    present: jax.Array    # bool[D] domains that hold a valid node (never id 0)
    zones: int = struct.field(pytree_node=False)   # Z
    cid: jax.Array        # i32[B, S] the pod's constraints: slot,
    topo: jax.Array       # i32[B, S] topology key,
    max_skew: jax.Array   # i32[B, S]
    self_inc: jax.Array   # i32[B, S] 1 where the pod matches its own selector
    hard: jax.Array       # bool[B, S] valid, DoNotSchedule, zone or region
    inc_valid: jax.Array  # bool[B, SI] constraints whose selector the pod
    inc_cid: jax.Array    # i32[B, SI]  matches (what a bind increments)
    inc_topo: jax.Array   # i32[B, SI]


def greedy_assign(
    cand_idx,   # i32[B, K] global node rows, priority-descending (-1 = none)
    cand_prio,  # i32[B, K] packed priorities (-1 = infeasible)
    cand_cpu,   # i32[B, K] candidate's free cpu at batch start
    cand_mem,   # i32[B, K]
    cand_pods,  # i32[B, K] candidate's free pod slots at batch start
    pod_cpu,    # i32[B]
    pod_mem,    # i32[B]
    pod_valid,  # bool[B]
    skew: WaveSkew | None = None,
    cand_zone=None,    # i32[B, K] the candidates' zone and region ids
    cand_region=None,  # (read with ``skew`` alone)
):
    """Returns (node_row i32[B] (-1 unbound), bound bool[B], score i32[B],
    chosen_k i32[B] — index of the winning candidate slot, legal — with
    ``skew``, bool[B]: whether a feasible candidate was legal under the
    in-wave counts at the pod's turn; without, None: each is).

    ``skew=None`` traces the capacity scan alone: nothing of the skew
    path is in the program."""
    b, k = cand_idx.shape
    arange_b = jnp.arange(b)
    if skew is not None:
        feasible = (cand_prio >= 0) & (cand_idx >= 0)         # [B, K]
        z = skew.zones
        is_zone = skew.topo == TOPO_ZONE                      # [B, S]
        # each (ref, candidate)'s column of the shared domain axis
        ref_dom = jnp.where(
            is_zone[:, :, None], cand_zone[:, None, :],
            z + cand_region[:, None, :],
        )                                                     # [B, S, K]
        ref_dom_ok = jnp.where(
            is_zone[:, :, None], cand_zone[:, None, :] != 0,
            cand_region[:, None, :] != 0,
        )
        d = skew.counts.shape[1]
        seg_zone = jnp.arange(d) < z                          # [D]
        # absent domains read _BIG: they never are the minimum
        counts0 = jnp.where(skew.present[None, :], skew.counts, _BIG)

    def step(carry, _):
        node_of, bound, i, *counts = carry
        # Resources already taken from pod i's candidates by pods j < i.
        prev = (arange_b < i) & bound                       # [B]
        eq = cand_idx[i][:, None] == node_of[None, :]       # [K, B]
        taken = eq & prev[None, :]
        dcpu = (taken * pod_cpu[None, :]).sum(axis=-1)
        dmem = (taken * pod_mem[None, :]).sum(axis=-1)
        dpods = taken.sum(axis=-1)

        ok = (
            (cand_prio[i] >= 0)
            & (cand_idx[i] >= 0)
            & (pod_cpu[i] <= cand_cpu[i] - dcpu)
            & (pod_mem[i] <= cand_mem[i] - dmem)
            & (cand_pods[i] - dpods >= 1)
        )
        if skew is not None:
            (counts,) = counts
            rows = counts[skew.cid[i]]                          # [S, D]
            seg = seg_zone[None, :] == is_zone[i][:, None]      # [S, D]
            least = jnp.where(seg, rows, _BIG).min(axis=1)      # [S]
            cnt = jnp.take_along_axis(rows, ref_dom[i], axis=1)  # [S, K]
            within = ref_dom_ok[i] & (
                cnt + skew.self_inc[i][:, None] - least[:, None]
                <= skew.max_skew[i][:, None]
            )
            lawful = (~skew.hard[i][:, None] | within).all(axis=0)   # [K]
            legal = (feasible[i] & lawful).any()
            ok = ok & lawful
        any_ok = ok.any() & pod_valid[i]
        # Candidates are priority-sorted, so the first feasible one is the
        # winner (argmax of bool returns the first True).
        kstar = jnp.argmax(ok)
        node = jnp.where(any_ok, cand_idx[i, kstar], -1)
        score = jnp.where(any_ok, unpack_score(cand_prio[i, kstar]), -1)
        carry = (node_of.at[i].set(node), bound.at[i].set(any_ok), i + 1)
        out = (node, any_ok, score, kstar.astype(jnp.int32))
        if skew is not None:
            it = skew.inc_topo[i]                               # [SI]
            dom = jnp.where(
                it == TOPO_ZONE, cand_zone[i, kstar], z + cand_region[i, kstar]
            )
            inc = any_ok & skew.inc_valid[i] & (it != TOPO_HOSTNAME)
            carry += (counts.at[skew.inc_cid[i], dom].add(inc.astype(jnp.int32)),)
            out += (legal,)
        return carry, out

    # xs=None + carried index: see engine/cycle.py on lifted-constant scans.
    init = (jnp.full((b,), -1, jnp.int32), jnp.zeros((b,), bool), jnp.int32(0))
    if skew is not None:
        init += (counts0,)
    _, (node_row, bound, score, chosen_k, *legal) = lax.scan(
        step, init, None, length=b
    )
    return node_row, bound, score, chosen_k, legal[0] if legal else None


def unbound_by_reason(bound, legal, cand_idx, cand_prio, pod_valid):
    """i32[3], in the order of ``UNBOUND_REASONS``: how many valid pods of
    the wave stayed unbound, by why (``greedy_assign``'s ``legal``)."""
    feasible = ((cand_prio >= 0) & (cand_idx >= 0)).any(axis=1)
    left = pod_valid & ~bound
    return jnp.stack([
        (left & legal).sum(), (left & feasible & ~legal).sum(),
        (left & ~feasible).sum(),
    ]).astype(jnp.int32)
