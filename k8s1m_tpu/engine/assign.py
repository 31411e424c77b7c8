"""Greedy in-batch conflict resolution over per-pod bind candidates.

The reference schedules pods concurrently and lets two pods race for one
node; the loser's bind fails at the apiserver and rolls back (reference
README.adoc:558-560, "optimistic concurrency").  Batched on TPU, the same
problem is solved *before* binding: every pod brings its top-K candidate
nodes (already sorted by packed priority), and the batch commits pods in
order, re-checking candidate capacity against what earlier pods in the
batch just took.  A pod whose K candidates are all exhausted leaves the
batch unbound and is retried next cycle — exactly the reference's
conflict-rollback, but at O(B*K) cost with no apiserver round-trip.

The order is a sequential scan's: pod i takes the first of its candidates
that still has room after pods j < i.  Without ``skew`` it is not *run* as
one.  Write the scan as a function ``F`` of the whole wave's choices
(pod i's entry of ``F(c)`` is what the scan's step gives pod i when the
pods before it chose as ``c`` says).  Entry i reads ``c[j]`` for j < i
alone, so the scan's result is ``F``'s only fixed point, and where two
successive iterates agree below index m both equal it below m and the
later one at m as well.  ``F`` for all pods at once is one fused
``[B, B*K]`` compare-select-reduce; it is iterated from "every pod takes
its first candidate with room at wave start" until nothing changes (a wave
without contention: once), and where rounds stop paying (``_go_on``) the
scan's own step finishes the pods that have not settled, from m to the
last pod that has a candidate.

With ``skew`` the sequential scan runs as it always has, and it also keeps
PodTopologySpread's hard zone and region constraints (whenUnsatisfiable:
DoNotSchedule) exact *inside* the wave: it carries the matching-pod count of every (constraint slot, domain)
forward from the wave-start tables, pod by pod in wave order, and a
candidate is legal for pod i only if binding it keeps ``count + self -
min over the present domains`` within maxSkew on the counts as pods j < i
left them — upstream's filter at every bind, not at wave boundaries.  The
candidates' own zone filter is switched off for those constraints
(engine/cycle.candidates) and they arrive one per zone, so whichever zone
is legal at a pod's turn has a candidate.  Hostname-keyed constraints are
not re-checked here: their domains are nodes, and the kernel's filter on
the wave-start counts stands for them.

All of it is integer work on B x K candidates and runs replicated on every
device in the sharded cycle (every device settles the same m and runs the
same trip counts), so no cross-device coordination is needed at commit time.
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

# What settled a valid pod's choice (``greedy_assign``'s ``settled`` holds
# the two sums, and behind them the evaluations of ``F``): the rounds, or
# the sequential step (the tail the rounds left; a ``WaveSkew`` wave whole).
SETTLED_BY = ("rounds", "scan")

# The scan's step is launch latency, ~11 us whatever the wave; a round is
# arithmetic over B x K x B and ~0.2 ms at 4096 x 4, what 18 steps cost
# (TPU v5e, PR 36's chip runs): two steps' worth of small operations at any
# size and ``_ROUND_STEPS`` for the pass itself at 4096 x 4.
_ROUND_STEPS = 16
_ROUND_FIXED_STEPS = 2
# Rounds that may pass before the settled prefix has to have paid for them.
_GRACE_ROUNDS = 8


def _round_steps(b: int, k: int) -> int:
    """A round's cost over a wave of ``b`` x ``k``, in steps of the scan."""
    return _ROUND_FIXED_STEPS + _ROUND_STEPS * b * b * k // (4096 * 4096 * 4)


def _go_on(m, rounds, last, b: int, k: int):
    """Whether another round is worth more than the scan's step from pod m
    to pod ``last`` (behind it no pod has a candidate): more pods are left
    than a round costs in steps, and the prefix the rounds have settled has
    paid for all of them but ``_GRACE_ROUNDS`` (a wave whose bumps are
    scattered shows a short prefix after its first round and nothing left
    after its second; a bump chain as long as the wave settles a pod a
    round, and the scan has it after the grace)."""
    steps = _round_steps(b, k)
    return (last - m > steps) & (m >= (rounds - _GRACE_ROUNDS) * steps)


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
    in-wave counts at the pod's turn; without, None: each is — and
    settled i32[3]: the wave's valid pods by ``SETTLED_BY`` and how often
    ``F`` was evaluated).

    ``skew=None`` traces the rounds and the capacity step alone; with
    ``skew`` the one sequential scan alone: nothing of the other path is
    in either program."""
    b, k = cand_idx.shape
    arange_b = jnp.arange(b)
    feasible = (cand_prio >= 0) & (cand_idx >= 0)             # [B, K]
    if skew is not None:
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

    valid_pods = pod_valid.sum().astype(jnp.int32)
    if skew is not None:
        # xs=None + carried index: see engine/cycle.py on lifted-constant scans.
        init = (
            jnp.full((b,), -1, jnp.int32), jnp.zeros((b,), bool), jnp.int32(0),
            counts0,
        )
        _, (node_row, bound, score, chosen_k, legal) = lax.scan(
            step, init, None, length=b
        )
        settled = jnp.stack([jnp.int32(0), valid_pods, jnp.int32(0)])
        return node_row, bound, score, chosen_k, legal, settled

    def choose(dcpu, dmem, dpods):
        """``step``'s ``ok`` / ``any_ok`` / ``argmax`` for every pod, given
        what earlier pods took from each of its candidates ([B, K])."""
        ok = (
            feasible
            & (pod_cpu[:, None] <= cand_cpu - dcpu)
            & (pod_mem[:, None] <= cand_mem - dmem)
            & (cand_pods - dpods >= 1)
        )
        any_ok = ok.any(axis=1) & pod_valid
        kstar = jnp.argmax(ok, axis=1).astype(jnp.int32)
        node = jnp.take_along_axis(cand_idx, kstar[:, None], axis=1)[:, 0]
        return jnp.where(any_ok, node, -1), any_ok, kstar

    flat_idx = cand_idx.reshape(-1)                 # [B*K] candidate rows,
    flat_pod = jnp.repeat(arange_b, k)              # and whose each is

    def evaluate(node_of):
        """``F``: one fused compare-select-reduce over [B, B*K], earlier
        pods down the reduced axis — never an array of that shape in
        memory.  An unbound pod's -1 meets no candidate that ``feasible``
        lets through."""
        taken = (node_of[:, None] == flat_idx[None, :]) & (
            arange_b[:, None] < flat_pod[None, :]
        )
        took = lambda what: jnp.where(taken, what[:, None], 0).sum(axis=0)
        return choose(*(t.reshape(b, k) for t in (
            took(pod_cpu), took(pod_mem), taken.sum(axis=0, dtype=jnp.int32),
        )))

    # Behind its last pod with a candidate a wave has nothing to settle
    # (the padding of a short wave, pods the cluster has no room for): no
    # choice there depends on an earlier one, and every iterate has it.
    last = jnp.max(jnp.where(feasible.any(axis=1), arange_b + 1, 0))

    def another_round(state):
        _, _, _, m, rounds = state
        return _go_on(m, rounds, last, b, k)

    def round_(state):
        node_of, _, _, _, rounds = state
        node, any_ok, kstar = evaluate(node_of)
        # The first pod the round moved: below it both iterates are the
        # scan's, and so is the new one's entry at it.
        same = node == node_of
        m = jnp.where(same.all(), b, jnp.argmin(same) + 1).astype(jnp.int32)
        return node, any_ok, kstar, m, rounds + 1

    zeros = jnp.zeros((b, k), jnp.int32)
    node_of, bound, chosen_k, m, rounds = lax.while_loop(
        another_round, round_,
        (*choose(zeros, zeros, zeros), jnp.int32(1), jnp.int32(0)),
    )

    def tail(i, carry):
        node_of, bound, chosen_k = carry
        (node_of, bound, _), (_, _, _, kstar) = step((node_of, bound, i), None)
        return node_of, bound, chosen_k.at[i].set(kstar)

    node_row, bound, chosen_k = lax.fori_loop(
        m, last, tail, (node_of, bound, chosen_k)
    )
    prio = jnp.take_along_axis(cand_prio, chosen_k[:, None], axis=1)[:, 0]
    score = jnp.where(bound, unpack_score(prio), -1)
    by_scan = (pod_valid & (arange_b >= m) & (arange_b < last)).sum()
    by_scan = by_scan.astype(jnp.int32)
    settled = jnp.stack([valid_pods - by_scan, by_scan, rounds])
    return node_row, bound, score, chosen_k, None, settled


def unbound_by_reason(bound, legal, cand_idx, cand_prio, pod_valid):
    """i32[3], in the order of ``UNBOUND_REASONS``: how many valid pods of
    the wave stayed unbound, by why (``greedy_assign``'s ``legal``)."""
    feasible = ((cand_prio >= 0) & (cand_idx >= 0)).any(axis=1)
    left = pod_valid & ~bound
    return jnp.stack([
        (left & legal).sum(), (left & feasible & ~legal).sum(),
        (left & ~feasible).sum(),
    ]).astype(jnp.int32)
