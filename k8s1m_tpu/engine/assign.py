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

With ``skew`` the same order also keeps PodTopologySpread's hard zone and
region constraints (whenUnsatisfiable: DoNotSchedule) exact *inside* the
wave: the matching-pod count of every (constraint slot, domain) is carried
forward from the wave-start tables, pod by pod in wave order, and a
candidate is legal for pod i only if binding it keeps ``count + self -
min over the present domains`` within maxSkew on the counts as pods j < i
left them — upstream's filter at every bind, not at wave boundaries.  The
candidates' own zone filter is switched off for those constraints
(engine/cycle.candidates) and they arrive one per zone, so whichever zone
is legal at a pod's turn has a candidate.  Hostname-keyed constraints are
not re-checked here: their domains are nodes, and the kernel's filter on
the wave-start counts stands for them.  Such a wave is settled a block of
W pods at a time (``_skew_blocks``): every pod of a Deployment reads the
zone the one before it took, so rounds over the whole wave would settle
about a pod of each Deployment a round, while a block holds a few of each.
``F`` over a block reads the exact state at its start — what the pods
before it took from the block's candidates and which domains they closed,
once a block as ``[W*K, B]`` compares, and the count table — and, for the
block's own pods, the capacity they took and the gate they close
(``[W*K, W]``) and the counts they added (the table plus the prefix sum of
their one-hot increments).  Rounds run from "nothing of the block bound"
until nothing changes; where they stop paying (``_go_on`` over W) the
scan's own step settles the block from m.  The block's final choices are
folded into the state and the next block starts.

With ``interpod`` (a wave whose pods carry a required inter-pod term)
required inter-pod anti-affinity is exact inside the wave too, on both
paths.
The candidates stage read the wave-start count tables, where every pod
of the wave counts 0; here a candidate is closed to pod i if an earlier
pod j of the wave that conflicts with i chose a node in the candidate's
domain of the conflict's topology (hostname, zone, region).  They
conflict where j matches a required anti-affinity term of i or i one of
j's (upstream's symmetry rule): a property of the wave's pods, one
boolean ``[B, B]`` a topology built once (``interpod_relation``, scope
``interpod_gate``), and in ``F`` one more AND in the same fused reduce,
so entry i still reads ``c[j]`` for j < i alone.  Required affinity is
safe on the wave-start counts (a count only grows in a wave) but for
upstream's bootstrap: a self-selecting term no pod matched at wave start
lets the first such pod go anywhere, and the pods after it that carry
the term must then share a domain with a pod it selects.  The rounds do
not see that rule; the scan's step does, and settles the wave from the
first pod that may bootstrap.  A pod whose every candidate with room is
closed goes back under ``interpod``.

All of it is integer work on B x K candidates and runs replicated on every
device in the sharded cycle (every device settles the same m and runs the
same trip counts), so no cross-device coordination is needed at commit time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax

from k8s1m_tpu.config import TOPO_HOSTNAME, TOPO_REGION, TOPO_ZONE
from k8s1m_tpu.ops.priority import unpack_score

# Python int (see plugins/topology._BIG): above any count, below overflow.
_BIG = 1 << 30

# Why a valid pod left the wave unbound (``WaveSkew`` waves and waves
# under the inter-pod gate report the four sums): every candidate that was
# legal had lost its capacity to earlier pods of the wave; it had
# candidates and none was legal under the in-wave counts; the candidates
# stage found it no feasible row; every candidate with room lay in a
# domain the gate closed (an earlier pod of the wave it conflicts with
# chose it, or a bootstrapping term's pods were elsewhere).
UNBOUND_REASONS = ("capacity", "skew", "no_candidate", "interpod")

# What settled a valid pod's choice (``greedy_assign``'s ``settled`` holds
# the two sums, and behind them the evaluations of ``F``): the rounds (a
# ``WaveSkew`` wave's: of its blocks), or the sequential step (the tail the
# rounds left: of the wave, or of a block).
SETTLED_BY = ("rounds", "scan")

# The scan's step is launch latency, ~11 us whatever the wave; a round is
# arithmetic over B x K x B and ~0.2 ms at 4096 x 4, what 18 steps cost
# (TPU v5e, PR 36's chip runs): two steps' worth of small operations at any
# size and ``_ROUND_STEPS`` for the pass itself at 4096 x 4.
_ROUND_STEPS = 16
_ROUND_FIXED_STEPS = 2
# Rounds that may pass before the settled prefix has to have paid for them.
_GRACE_ROUNDS = 8
# Pods a block of a ``WaveSkew`` wave holds at most: a power of two, so that
# a block divides the wave.  Fewer blocks cost fewer set-ups and confirming
# rounds, a larger block more rounds where chains are long; over a wave of
# spread-1m-pct5's interleave and one of full-1m-pct5's mix at 4096 x 9 the
# sum was least at 128 (TPU v5e: 11.1 + 15.0 ms, against 9.8 + 20.1 at 64
# and 16.6 + 15.3 at 256; the sequential scan 69.4 + 105.1).
_BLOCK_PODS = 128


def _block_pods(b: int) -> int:
    """W for a ``WaveSkew`` wave of ``b`` pods: the largest power of two up
    to ``_BLOCK_PODS`` that divides it (a short wave is one block)."""
    return math.gcd(b, _BLOCK_PODS)


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


@struct.dataclass
class WaveInterpod:
    """What the in-wave inter-pod gate needs: the pods' term references,
    the terms each pod matches, and the wave-start total of every term
    (``TopoStats.tgt_total``: where it is 0 a self-selecting required
    affinity term bootstraps)."""

    slots: int = struct.field(pytree_node=False)   # A, affinity term slots
    ipa_valid: jax.Array     # bool[B, AR] the pod's own terms:
    ipa_tid: jax.Array       # i32[B, AR]
    ipa_topo: jax.Array      # i32[B, AR]
    ipa_required: jax.Array  # bool[B, AR]
    ipa_anti: jax.Array      # bool[B, AR]
    ipa_self: jax.Array      # bool[B, AR]
    inc_valid: jax.Array     # bool[B, AI] terms whose selector the pod
    inc_tid: jax.Array       # i32[B, AI]  matches
    tgt_total: jax.Array     # i32[A]


# A topology's bit in the conflict relation, in TOPO_* order
_TOPO_BITS = ((TOPO_HOSTNAME, 1), (TOPO_ZONE, 2), (TOPO_REGION, 4))


def _term_rows(tid, valid, slots: int):
    """f32[B, A]: 1 where one of the pod's ``valid`` references names the
    term slot (exact in any precision: 0 and 1, sums of a few)."""
    hot = (tid[:, :, None] == jnp.arange(slots)[None, None, :]) & valid[:, :, None]
    return hot.any(axis=1).astype(jnp.float32)


def interpod_relation(ip: WaveInterpod):
    """The wave's pods against each other, once before assignment.
    Returns ``(rel, inc, boot)``: ``rel`` i8[B, B] holds, for pods i and j,
    the bit of every topology (hostname 1, zone 2, region 4) in which they
    may not share a domain — j matches a required anti-affinity term of
    i, or i one of j's (upstream's symmetry rule), so ``rel`` is
    symmetric; ``inc`` bool[B, A] the terms each pod matches; ``boot``
    bool[B, AR] the pod's required affinity terms that select itself and
    that no pod matched at wave start (upstream's bootstrap: such a pod
    goes anywhere, and fixes the term's domain for the pods after it)."""
    inc = _term_rows(ip.inc_tid, ip.inc_valid, ip.slots)
    anti = ip.ipa_valid & ip.ipa_required & ip.ipa_anti
    rel = jnp.zeros((inc.shape[0],) * 2, jnp.int8)
    for topo, bit in _TOPO_BITS:
        carries = _term_rows(ip.ipa_tid, anti & (ip.ipa_topo == topo), ip.slots)
        hits = jnp.dot(carries, inc.T, preferred_element_type=jnp.float32)
        rel = rel | jnp.where((hits + hits.T) > 0, jnp.int8(bit), jnp.int8(0))
    boot = (
        ip.ipa_valid & ip.ipa_required & ~ip.ipa_anti & ip.ipa_self
        & (jnp.take(ip.tgt_total, ip.ipa_tid) == 0)
    )
    return rel, inc > 0, boot


def _domains_of(node_of, zone_of, region_of):
    """Where each pod's choice lies, per topology: its row, and its zone
    and region ids where it has them (-1: unbound, or no such label)."""
    dom = lambda d: jnp.where((node_of >= 0) & (d > 0), d, -1)
    return node_of, dom(zone_of), dom(region_of)


def _skew_blocks(step, per_pod, counts, zones: int, first_boot):
    """A ``WaveSkew`` wave settled a block of W pods at a time.

    ``per_pod`` holds the wave's per-pod arrays (``rel``, the gate's
    relation, where the wave has one), ``counts`` the wave-start table
    [C, D] (absent domains ``_BIG``), ``step`` the scan's own step over the
    whole wave, which settles what the rounds leave of a block and every pod
    from ``first_boot`` on.  Returns (node_row, bound, chosen_k, legal,
    blocked — None without the gate —, the table after the wave, the valid
    pods the step settled, the evaluations of ``F``)."""
    b, k = per_pod["idx"].shape
    w = _block_pods(b)
    gate = "rel" in per_pod
    d = counts.shape[1]
    arange_w = jnp.arange(w)
    earlier = arange_w[None, :] < arange_w[:, None]          # [W, W]: p' < p
    take1 = lambda x, kstar: jnp.take_along_axis(x, kstar[:, None], axis=1)[:, 0]
    pod_cpu, pod_mem = per_pod["pod_cpu"], per_pod["pod_mem"]
    last = jnp.max(jnp.where(per_pod["feasible"].any(axis=1), jnp.arange(b) + 1, 0))

    def domains(node, at, kstar):
        return _domains_of(node, take1(at["zone"], kstar), take1(at["region"], kstar))

    def increments(at, node, kstar):
        """Each pod's constraint increments as the scan's step adds them:
        (slot, domain column, 0 or 1), [W, SI] each."""
        dom = jnp.where(
            at["inc_topo"] == TOPO_ZONE, take1(at["zone"], kstar)[:, None],
            zones + take1(at["region"], kstar)[:, None],
        )
        add = (node >= 0)[:, None] & at["inc_valid"] & (at["inc_topo"] != TOPO_HOSTNAME)
        return at["inc_cid"], dom, add

    def block(blk, state):
        node_of, bound, chosen_k, legal, blocked, counts, by_scan, rounds = state
        s = blk * w
        at = {name: lax.dynamic_slice_in_dim(x, s, w) for name, x in per_pod.items()}
        # Once a block: what the pods before it (every bound pod so far) took
        # from its candidates, and which of them they closed.
        before = (at["idx"][:, :, None] == node_of[None, None, :]) & bound  # [W, K, B]
        took = lambda what: jnp.where(before, what, 0).sum(axis=2)
        pre = (took(pod_cpu), took(pod_mem), before.sum(axis=2, dtype=jnp.int32))
        cand_doms = (at["idx"], at["zone"], at["region"])
        if gate:
            doms = domains(node_of, per_pod, chosen_k)
            hit = jnp.zeros(before.shape, jnp.int8)
            for (_topo, bit), dom, cand in zip(_TOPO_BITS, doms, cand_doms):
                hit = hit | jnp.where(cand[:, :, None] == dom, jnp.int8(bit), jnp.int8(0))
            pre_closed = (((at["rel"][:, None, :] & hit) != 0) & bound).any(axis=2)
            rel_w = lax.dynamic_slice_in_dim(at["rel"], s, w, axis=1)        # [W, W]
        rows0 = counts[at["cid"]]                                           # [W, S, D]
        seg = (jnp.arange(d) < zones)[None, None, :] == at["is_zone"][:, :, None]
        target = at["cid"][:, :, None] * d + jnp.arange(d)                  # [W, S, D]

        def evaluate(node, kstar):
            """``F`` over the block: every pod's choice given the choices
            ``node`` / ``kstar`` of the block's pods before it."""
            prev = earlier & (node >= 0)[None, :]                           # [W, W]
            taken = (at["idx"][:, :, None] == node) & prev[:, None, :]     # [W, K, W]
            took = lambda what: jnp.where(taken, what, 0).sum(axis=2)
            ok = (
                at["feasible"]
                & (at["pod_cpu"][:, None] <= at["cpu"] - pre[0] - took(at["pod_cpu"]))
                & (at["pod_mem"][:, None] <= at["mem"] - pre[1] - took(at["pod_mem"]))
                & (at["pods"] - pre[2] - taken.sum(axis=2, dtype=jnp.int32) >= 1)
            )
            cid, dom, add = increments(at, node, kstar)
            src = jnp.where(add, cid * d + dom, -1)                         # [W, SI]
            added = (
                (target[:, :, :, None, None] == src[None, None, None, :, :])
                & earlier[:, None, None, :, None]
            ).sum(axis=(3, 4), dtype=jnp.int32)
            rows = rows0 + added                                            # [W, S, D]
            least = jnp.where(seg, rows, _BIG).min(axis=2)                  # [W, S]
            cnt = jnp.take_along_axis(rows, at["ref_dom"], axis=2)          # [W, S, K]
            within = at["ref_dom_ok"] & (
                cnt + at["self_inc"][:, :, None] - least[:, :, None]
                <= at["max_skew"][:, :, None]
            )
            lawful = (~at["hard"][:, :, None] | within).all(axis=1)         # [W, K]
            legal = (at["feasible"] & lawful).any(axis=1)
            ok = ok & lawful
            blocked = None
            if gate:
                hit = jnp.zeros(taken.shape, jnp.int8)
                for (_topo, bit), dom, cand in zip(
                        _TOPO_BITS, domains(node, at, kstar), cand_doms):
                    hit = hit | jnp.where(cand[:, :, None] == dom, jnp.int8(bit), jnp.int8(0))
                closed = pre_closed | (
                    ((rel_w[:, None, :] & hit) != 0) & prev[:, None, :]
                ).any(axis=2)
                blocked = at["valid"] & ok.any(axis=1) & ~(ok & ~closed).any(axis=1)
                ok = ok & ~closed
            any_ok = ok.any(axis=1) & at["valid"]
            kstar = jnp.argmax(ok, axis=1).astype(jnp.int32)
            return jnp.where(any_ok, take1(at["idx"], kstar), -1), kstar, legal, blocked

        last_w = jnp.clip(last - s, 0, w)
        boot_w = jnp.clip(first_boot - s, 0, w)

        def another_round(st):
            m, n = st[4], st[5]
            return _go_on(m, n, last_w, w, k) & (m < boot_w)

        def round_(st):
            node, kstar, _legal, _blocked, _m, n = st
            new, new_k, legal, blocked = evaluate(node, kstar)
            # the first pod the round moved: below it both iterates are final
            same = (new == node) & (new_k == kstar)
            m = jnp.where(same.all(), w, jnp.argmin(same) + 1).astype(jnp.int32)
            return new, new_k, legal, blocked, m, n + 1

        none = jnp.full((w,), -1, jnp.int32)
        node, kstar, legal_w, blocked_w, m, n = lax.while_loop(
            another_round, round_,
            (none, jnp.zeros((w,), jnp.int32), jnp.zeros((w,), bool),
             jnp.zeros((w,), bool) if gate else None, jnp.int32(0), jnp.int32(0)),
        )
        m = jnp.minimum(m, boot_w)
        # Below m the rounds' choices are the scan's: fold them in.
        node = jnp.where(arange_w < m, node, -1)
        cid, dom, add = increments(at, node, kstar)
        counts = counts.at[cid, dom].add(add.astype(jnp.int32))
        put = lambda whole, part: lax.dynamic_update_slice_in_dim(whole, part, s, 0)
        node_of, bound = put(node_of, node), put(bound, node >= 0)
        chosen_k, legal = put(chosen_k, kstar), put(legal, legal_w)
        if gate:
            blocked = put(blocked, blocked_w)

        def settle(i, st):
            node_of, bound, chosen_k, legal, blocked, counts, doms = st
            (node_of, bound, _, counts, doms), (_, _, _, kstar, ok_, shut) = step(
                (node_of, bound, i, counts, doms), None
            )
            if gate:
                blocked = blocked.at[i].set(shut)
            return (node_of, bound, chosen_k.at[i].set(kstar), legal.at[i].set(ok_),
                    blocked, counts, doms)

        doms = domains(node_of, per_pod, chosen_k) if gate else None
        node_of, bound, chosen_k, legal, blocked, counts, _ = lax.fori_loop(
            s + m, s + last_w, settle,
            (node_of, bound, chosen_k, legal, blocked, counts, doms),
        )
        in_step = (arange_w >= m) & (arange_w < last_w) & at["valid"]
        by_scan = by_scan + in_step.sum(dtype=jnp.int32)
        return node_of, bound, chosen_k, legal, blocked, counts, by_scan, rounds + n

    false = jnp.zeros((b,), bool)
    return lax.fori_loop(
        0, (last + w - 1) // w, block,
        (jnp.full((b,), -1, jnp.int32), false, jnp.zeros((b,), jnp.int32),
         false, false if gate else None, counts, jnp.int32(0), jnp.int32(0)),
    )


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
    cand_region=None,  # (read with ``skew`` or ``interpod``)
    interpod: WaveInterpod | None = None,
):
    """Returns (node_row i32[B] (-1 unbound), bound bool[B], score i32[B],
    chosen_k i32[B] — index of the winning candidate slot, legal — with
    ``skew``, bool[B]: whether a feasible candidate was legal under the
    in-wave counts at the pod's turn; without, None: each is — and
    settled i32[3]: the wave's valid pods by ``SETTLED_BY`` and how often
    ``F`` was evaluated); with ``interpod`` one more, blocked bool[B]:
    the pods that had candidates with room at their turn, every one of
    them in a domain the gate closed.

    ``skew=None`` traces the rounds and the capacity step alone; with
    ``skew`` the blocks (``_skew_blocks``) alone: nothing of the other path
    is in either program.  ``interpod=None`` traces no gate."""
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
    if interpod is not None:
        with jax.named_scope("interpod_gate"):
            rel, inc, boot = interpod_relation(interpod)
            # the bit of each of the pods' own terms' topology
            term_bits = jnp.zeros_like(interpod.ipa_topo, dtype=jnp.int8)
            for topo, bit in _TOPO_BITS:
                term_bits = jnp.where(
                    interpod.ipa_topo == topo, jnp.int8(bit), term_bits
                )
            # the first pod that may bootstrap: the rounds do not see the
            # rule, so the scan's step settles it and every pod after it
            first_boot = jnp.min(
                jnp.where(boot.any(axis=1) & pod_valid, arange_b, b)
            ).astype(jnp.int32)
        cand_doms = (cand_idx, cand_zone, cand_region)

    def gate(i, prev, doms):
        """bool[K]: pod i's candidates in a domain the gate closes, given
        the earlier pods ``prev`` [B] and where their choices lie."""
        eq = jnp.zeros((k, b), jnp.int8)
        for (_topo, bit), dom, cand in zip(_TOPO_BITS, doms, cand_doms):
            eq = eq | jnp.where(
                cand[i][:, None] == dom[None, :], jnp.int8(bit), jnp.int8(0)
            )
        eq = jnp.where(prev[None, :], eq, jnp.int8(0))            # [K, B]
        closed = ((rel[i][None, :] & eq) != 0).any(axis=1)
        # a bootstrapping term: once an earlier pod it selects is bound,
        # the candidate has to share a domain with one of them
        sel = jnp.where(
            boot[i][:, None] & jnp.take(inc.T, interpod.ipa_tid[i], axis=0),
            term_bits[i][:, None], jnp.int8(0),
        )[:, None, :]                                             # [AR, 1, B]
        seen = ((sel != 0) & prev[None, None, :]).any(axis=2)     # [AR, 1]
        met = ((sel & eq[None, :, :]) != 0).any(axis=2)           # [AR, K]
        return closed | (seen & ~met).any(axis=0)

    def step(carry, _):
        node_of, bound, i, counts, doms = carry
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
        out_legal = out_blocked = None
        if skew is not None:
            rows = counts[skew.cid[i]]                          # [S, D]
            seg = seg_zone[None, :] == is_zone[i][:, None]      # [S, D]
            least = jnp.where(seg, rows, _BIG).min(axis=1)      # [S]
            cnt = jnp.take_along_axis(rows, ref_dom[i], axis=1)  # [S, K]
            within = ref_dom_ok[i] & (
                cnt + skew.self_inc[i][:, None] - least[:, None]
                <= skew.max_skew[i][:, None]
            )
            lawful = (~skew.hard[i][:, None] | within).all(axis=0)   # [K]
            out_legal = (feasible[i] & lawful).any()
            ok = ok & lawful
        if interpod is not None:
            open_ = ~gate(i, prev, doms)
            out_blocked = pod_valid[i] & ok.any() & ~(ok & open_).any()
            ok = ok & open_
        any_ok = ok.any() & pod_valid[i]
        # Candidates are priority-sorted, so the first feasible one is the
        # winner (argmax of bool returns the first True).
        kstar = jnp.argmax(ok)
        node = jnp.where(any_ok, cand_idx[i, kstar], -1)
        score = jnp.where(any_ok, unpack_score(cand_prio[i, kstar]), -1)
        if skew is not None:
            it = skew.inc_topo[i]                               # [SI]
            dom = jnp.where(
                it == TOPO_ZONE, cand_zone[i, kstar], z + cand_region[i, kstar]
            )
            inc_ = any_ok & skew.inc_valid[i] & (it != TOPO_HOSTNAME)
            counts = counts.at[skew.inc_cid[i], dom].add(inc_.astype(jnp.int32))
        node_of = node_of.at[i].set(node)
        if interpod is not None:
            _, zone_of, region_of = doms
            doms = _domains_of(
                node_of,
                zone_of.at[i].set(cand_zone[i, kstar]),
                region_of.at[i].set(cand_region[i, kstar]),
            )
        carry = (node_of, bound.at[i].set(any_ok), i + 1, counts, doms)
        return carry, (node, any_ok, score, kstar.astype(jnp.int32),
                       out_legal, out_blocked)

    valid_pods = pod_valid.sum().astype(jnp.int32)
    if skew is not None:
        per_pod = dict(
            idx=cand_idx, feasible=feasible, cpu=cand_cpu, mem=cand_mem,
            pods=cand_pods, pod_cpu=pod_cpu, pod_mem=pod_mem, valid=pod_valid,
            zone=cand_zone, region=cand_region, cid=skew.cid, is_zone=is_zone,
            max_skew=skew.max_skew, self_inc=skew.self_inc, hard=skew.hard,
            inc_valid=skew.inc_valid, inc_cid=skew.inc_cid,
            inc_topo=skew.inc_topo, ref_dom=ref_dom, ref_dom_ok=ref_dom_ok,
        )
        if interpod is not None:
            per_pod["rel"] = rel
        node_row, bound, chosen_k, legal, blocked, _counts, by_scan, rounds = (
            _skew_blocks(
                step, per_pod, counts0, z, b if interpod is None else first_boot,
            )
        )
        prio = jnp.take_along_axis(cand_prio, chosen_k[:, None], axis=1)[:, 0]
        score = jnp.where(bound, unpack_score(prio), -1)
        settled = jnp.stack([valid_pods - by_scan, by_scan, rounds])
        out = (node_row, bound, score, chosen_k, legal, settled)
        return out if interpod is None else (*out, blocked)

    def choose(dcpu, dmem, dpods, closed=None):
        """``step``'s ``ok`` / ``any_ok`` / ``argmax`` for every pod, given
        what earlier pods took from each of its candidates ([B, K]) and,
        with the gate, which of them it closed."""
        ok = (
            feasible
            & (pod_cpu[:, None] <= cand_cpu - dcpu)
            & (pod_mem[:, None] <= cand_mem - dmem)
            & (cand_pods - dpods >= 1)
        )
        blocked = None
        if closed is not None:
            blocked = pod_valid & ok.any(axis=1) & ~(ok & ~closed).any(axis=1)
            ok = ok & ~closed
        any_ok = ok.any(axis=1) & pod_valid
        kstar = jnp.argmax(ok, axis=1).astype(jnp.int32)
        node = jnp.take_along_axis(cand_idx, kstar[:, None], axis=1)[:, 0]
        return jnp.where(any_ok, node, -1), any_ok, kstar, blocked

    flat_idx = cand_idx.reshape(-1)                 # [B*K] candidate rows,
    flat_pod = jnp.repeat(arange_b, k)              # and whose each is
    if interpod is not None:
        flat_doms = tuple(c.reshape(-1) for c in cand_doms)
        flat_rel = jnp.repeat(rel, k, axis=1)       # [B, B*K]: rel[j, i]
    take1 = lambda x, kstar: jnp.take_along_axis(x, kstar[:, None], axis=1)[:, 0]

    def evaluate(node_of, kstar):
        """``F``: one fused compare-select-reduce over [B, B*K], earlier
        pods down the reduced axis — never an array of that shape in
        memory.  An unbound pod's -1 meets no candidate that ``feasible``
        lets through.  With the gate one more AND in the same reduce:
        an earlier pod that conflicts with the candidate's pod chose its
        domain (``rel`` is symmetric, so its row j is pod j's column)."""
        taken = (node_of[:, None] == flat_idx[None, :]) & (
            arange_b[:, None] < flat_pod[None, :]
        )
        took = lambda what: jnp.where(taken, what[:, None], 0).sum(axis=0)
        closed = None
        if interpod is not None:
            earlier = arange_b[:, None] < flat_pod[None, :]
            doms = _domains_of(
                node_of, take1(cand_zone, kstar), take1(cand_region, kstar)
            )
            eq = jnp.zeros((b, b * k), jnp.int8)
            for (_topo, bit), dom, cand in zip(_TOPO_BITS, doms, flat_doms):
                eq = eq | jnp.where(
                    dom[:, None] == cand[None, :], jnp.int8(bit), jnp.int8(0)
                )
            closed = (earlier & ((flat_rel & eq) != 0)).any(axis=0)
            closed = closed.reshape(b, k)
        return choose(*(t.reshape(b, k) for t in (
            took(pod_cpu), took(pod_mem), taken.sum(axis=0, dtype=jnp.int32),
        )), closed)

    # Behind its last pod with a candidate a wave has nothing to settle
    # (the padding of a short wave, pods the cluster has no room for): no
    # choice there depends on an earlier one, and every iterate has it.
    last = jnp.max(jnp.where(feasible.any(axis=1), arange_b + 1, 0))

    def another_round(state):
        _, _, _, _, m, rounds = state
        go = _go_on(m, rounds, last, b, k)
        # Without the gate's bootstrap rule the rounds are ``F`` up to the
        # first pod that may bootstrap; from there the scan's step settles.
        return go if interpod is None else go & (m < first_boot)

    def round_(state):
        node_of, _, kstar, _, _, rounds = state
        node, any_ok, kstar, blocked = evaluate(node_of, kstar)
        # The first pod the round moved: below it both iterates are the
        # scan's, and so is the new one's entry at it.
        same = node == node_of
        m = jnp.where(same.all(), b, jnp.argmin(same) + 1).astype(jnp.int32)
        return node, any_ok, kstar, blocked, m, rounds + 1

    zeros = jnp.zeros((b, k), jnp.int32)
    node, any_ok, kstar, _ = choose(zeros, zeros, zeros)
    node_of, bound, chosen_k, blocked, m, rounds = lax.while_loop(
        another_round, round_,
        (node, any_ok, kstar, None if interpod is None else jnp.zeros((b,), bool),
         jnp.int32(1), jnp.int32(0)),
    )
    if interpod is not None:
        m = jnp.minimum(m, first_boot)

    def tail(i, carry):
        node_of, bound, chosen_k, blocked = carry
        doms = None if interpod is None else _domains_of(
            node_of, take1(cand_zone, chosen_k), take1(cand_region, chosen_k)
        )
        (node_of, bound, _, _, _), (_, _, _, kstar, _, out_blocked) = step(
            (node_of, bound, i, None, doms), None
        )
        if interpod is not None:
            blocked = blocked.at[i].set(out_blocked)
        return node_of, bound, chosen_k.at[i].set(kstar), blocked

    node_row, bound, chosen_k, blocked = lax.fori_loop(
        m, last, tail, (node_of, bound, chosen_k, blocked)
    )
    prio = jnp.take_along_axis(cand_prio, chosen_k[:, None], axis=1)[:, 0]
    score = jnp.where(bound, unpack_score(prio), -1)
    by_scan = (pod_valid & (arange_b >= m) & (arange_b < last)).sum()
    by_scan = by_scan.astype(jnp.int32)
    settled = jnp.stack([valid_pods - by_scan, by_scan, rounds])
    out = (node_row, bound, score, chosen_k, None, settled)
    return out if interpod is None else (*out, blocked)


def unbound_by_reason(bound, legal, cand_idx, cand_prio, pod_valid, blocked=None):
    """i32[4], in the order of ``UNBOUND_REASONS``: how many valid pods of
    the wave stayed unbound, by why (``greedy_assign``'s ``legal`` — None
    where no skew was counted — and ``blocked``, None without the gate)."""
    feasible = ((cand_prio >= 0) & (cand_idx >= 0)).any(axis=1)
    left = pod_valid & ~bound
    legal = feasible if legal is None else legal
    gated = jnp.zeros_like(left) if blocked is None else left & blocked
    return jnp.stack([
        (left & legal & ~gated).sum(), (left & feasible & ~legal).sum(),
        (left & ~feasible).sum(), gated.sum(),
    ]).astype(jnp.int32)
