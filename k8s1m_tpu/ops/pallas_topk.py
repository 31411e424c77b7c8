"""Pallas TPU kernel: fused filter+score+pack+top-k over the node table.

This is the hot loop of the whole framework — the work the reference
spreads over 8,670 CPU cores (256 scheduler shards x filter+score per pod,
~560us/pod, reference README.adoc:783-787) — as one Pallas kernel:

- streams the node table HBM -> VMEM once per batch (grid over node
  chunks), never materializing any [B, N] intermediate in HBM; the XLA
  scan path writes the packed-priority matrix per chunk and re-reads it
  inside ``lax.top_k``;
- recasts the taint-toleration gather (``tolerated[b, taint_id[n, t]]``,
  awkward on TPU) as a one-hot matmul on the MXU: per chunk a dense
  [max_taint_ids, C] taint-incidence matrix is built from the (TS, C)
  taint slots, and ``untolerated @ incidence`` yields per-(pod, node)
  untolerated-taint counts for both the hard filter and the soft score;
- carries a running top-k per pod in VMEM across the chunk grid
  (accumulator-output pattern), merged by K max-extract passes — no sort.

Plugin coverage: NodeResourcesFit + NodeName + TaintToleration
(+NodeUnschedulable) + **NodeAffinity** (spec.nodeSelector, required
terms, preferred-term scoring — all six selector ops).  The NodeAffinity
gathers (per-expression lookups into the per-chunk label resolution)
become one-hot matmuls on the MXU, like the taint trick: the [Q, C]
query-key resolution is packed as a selection plane with the columns
found, value-id hi/lo half, numeric hi/lo half, and each expression slot
selects its rows with one dot.  The 16-bit halves are recombined in
int32, so In/NotIn equality and Gt/Lt compares are bit-exact for every
int32 id and numeric, beyond f32's 2^24 integer range too.

**Every selection dot is one exact bf16 pass.**  Mosaic contracts in
bf16 (8 significant bits: measured on v5e, every score above 255 sent
through an f32 dot at the default precision came back off by a few
units), and an fp32 contraction costs six bf16 passes.  Neither is
needed, because the operands are made exact in bf16: the one-hot side
holds 0 and powers of two (1, 256, 65,536), and a wide value travels
as its bytes — each below 256, the top one signed.  A byte times a
power of two is exact in the MXU's f32 accumulator, and so is the sum
of a value's two or three such terms, every partial sum being an
integer below 2^24.  So a label half is two byte rows of a [2Q, 5C]
plane, selected by a [TB, 2Q] row weighted 256 / 1; a domain count is
three byte rows of a [9*SLOTS, C] plane (below), selected by a
[TB, 9*SLOTS] row weighted 1 / 256 / 65,536 — the exact count for any
count of magnitude below 2^24, f32's own integer range, in the
topology the ref names, in ceil(9*SLOTS / 128) passes of the MXU's
128-deep contraction.  No dot of the kernel asks for more than the
default precision.  The taint dots carry only 0/1 and
counts <= taint_slots, and their f32 operands stay as they were.
Constraint plugins (PodTopologySpread, InterPodAffinity) run fused too
when the caller passes the count tables and their prologue (the
``with_cons`` stage below, compiled as ``fused_topk_constraints``); with
``per_zone`` that variant keeps the best row of every zone instead of
the k best rows, for the in-wave skew count of engine/assign.py.

**Size the PodSpec slot dims to the workload.** The affinity stage
unrolls one evaluation per selector slot (aff_exprs + aff_terms*aff_exprs
+ pref_terms*aff_exprs), and Mosaic compile time AND step time scale with
that count: measured on v5e, 6 slots compile in ~13s and run ~3x faster
than the XLA path, while the worst-case default spec (36 slots) takes
minutes to compile and loses its advantage.  Like every other static dim
on TPU, aff_terms/aff_exprs/aff_values/pref_terms should be the batch's
actual shape, not the schema maximum; ``fused_topk`` warns past
``_SLOT_WARN`` slots.

Tie-break parity: priorities pack ``score << JITTER_BITS | jitter`` like
ops/priority.py, but jitter comes from a stateless integer hash of
(seed, pod, node) — identical in compiled and interpreter mode, so tests
can compare CPU-interpreted and TPU-compiled runs bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s1m_tpu.config import (
    EFFECT_NO_EXECUTE,
    EFFECT_NO_SCHEDULE,
    EFFECT_PREFER_NO_SCHEDULE,
    NO_NUMERIC,
    NONE_ID,
    SEL_OP_DOES_NOT_EXIST,
    SEL_OP_EXISTS,
    SEL_OP_GT,
    SEL_OP_IN,
    SEL_OP_LT,
    SEL_OP_NOT_IN,
    SPREAD_DO_NOT_SCHEDULE,
    TOPO_HOSTNAME,
    TOPO_ZONE,
)
from k8s1m_tpu.ops.priority import (
    JITTER_BITS,
    MAX_SCORE,
    hash_jitter,
    mix32,
    seed_of as _priority_seed_of,
)
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot.node_table import NodeTable
from k8s1m_tpu.snapshot.pod_encoding import PodBatch


def _bf16(x):
    """A selection dot's operand in bf16.  Every value cast here is a
    0/1, a power of two up to 2^16 or a byte, which bf16 holds exactly
    (see the module doc)."""
    return x.astype(jnp.float32).astype(jnp.bfloat16)


def _count_bytes(x):
    """i32[S, W] counts -> i32[3S, W]: bytes 0 and 1 unsigned, then the
    signed rest ``x >> 16``, each exact in bf16 for |x| < 2^24 — the
    rows byte 0, byte 1, byte 2 of every slot."""
    return jnp.concatenate([x & 255, (x >> 8) & 255, x >> 16], axis=0)


def supports(profile: Profile) -> bool:
    """True if the fused kernel computes this profile exactly."""
    return profile.topology_spread == 0 and profile.interpod_affinity == 0


# Above this many unrolled selector-slot evaluations the Mosaic compile
# takes minutes and the kernel loses to the XLA path (module doc).
_SLOT_WARN = 16
_slot_warned = False


def _check_slots(batch: PodBatch) -> None:
    global _slot_warned
    s = batch.sel_valid.shape[1]
    t, e = batch.req_expr_valid.shape[1], batch.req_expr_valid.shape[2]
    p = batch.pref_expr_valid.shape[1]
    n = s + (t + p) * e
    if n > _SLOT_WARN and not _slot_warned:
        _slot_warned = True
        import logging

        logging.getLogger("k8s1m.pallas").warning(
            "affinity kernel unrolls %d selector slots (PodSpec aff_exprs=%d"
            " aff_terms=%d pref_terms=%d); compile and step time scale with"
            " this — size the PodSpec to the workload's selector shape",
            n, s, t, p,
        )


# The separable hash lives in ops/priority.py now — it is shared by this
# kernel, the XLA scan path (pack_hashed), and the numpy oracle, so every
# backend produces IDENTICAL tie-breaks for the same wave.  The
# correlated-tie trade-off note: two pods' orderings over an equal-score
# candidate set are XOR-translates of each other, i.e. tied waves get
# correlated (not independent) tie-breaks.  Assignment runs greedily with
# capacity re-checks, so correlated picks cost at most extra conflict
# retries, never correctness.  If measured bind-conflict rates on tied
# waves ever rise above a full-width-hash baseline, the fix is ONE extra
# full-width mixing step over (rh ^ ch), not a revert.
_mix32 = mix32
_hash_jitter = hash_jitter


def _kernel(
    *refs,
    chunk: int,
    k: int,
    w_la: int,
    w_ba: int,
    w_tt: int,
    w_na: int,
    w_ts: int,
    w_ipa: int,
    with_aff: bool,
    with_cons: bool,
    pack: tuple | None = None,
    per_zone: bool = False,
):
    """``per_zone`` (with_cons only): slot z of the K outputs is the best
    row of zone id z, not the z-th best row (_merge_running_per_zone).

    Base refs (always):
        seed_ref   i32[1, 3] SMEM — (seed, pod hash base, node hash base)
        cpu_alloc, mem_alloc, pods_alloc,
        cpu_req, mem_req, pods_req, name_id   i32[1, C]
          (packed layout: pods_alloc is int16[1, C], decoded in-kernel)
        taint_id, taint_eff                    i32[TS, C]
          (packed layout: taint_id int16[TS, C]; taint_eff replaced by
           the meta word i32[1, C] — bit 0 row validity, bits 1+2t..2+2t
           the 2-bit effect of taint slot t; see snapshot/packing.py)
        p_cpu, p_mem, p_valid, p_nnid          i32[TB, 1]
        untol      f32[TB, M]  1.0 where pod does NOT tolerate taint id m
    Affinity refs (with_aff only):
        lkey, lval, lnum                       i32[L, C]  node label slots
          (packed+fused layout: lkey holds the fused val<<kb|key words
           and the lval ref is ABSENT — keys/values decode in-kernel)
        qkey       i32[Q, 1]   batch query-key table
    ``pack`` is the static packing config (fuse_labels, key_bits) or
    None for the plain i32 layout.
        sel_valid, sel_qidx, sel_val           i32[TB, S]
        req_tv     i32[TB, T]
        req_ev, req_qidx, req_op, req_num      i32[TB, T*E]
        req_vals   i32[TB, T*E*V]
        pref_tv, pref_w                        i32[TB, P]
        pref_ev, pref_qidx, pref_op, pref_num  i32[TB, P*E]
        pref_vals  i32[TB, P*E*V]
    Constraint refs (with_cons only; see _cons_kernel_stage):
        zone_c, region_c                       i32[1, C]
        sn (spread_node), tn (tgt_node),
        on_ (own_node)                         i32[SS|AS, C] chunked cols
        sz, sr, tz, tr, oz, orr                i32[SS|AS, Z|R] whole tables
        sp_* [TB, S], ia_* [TB, A], ii_* [TB, AI], cs_* [TB, 1]
    Outputs/scratch:
        out_idx, out_prio  i32[TB, K] accumulator outputs
        run_prio, run_idx  i32[TB, 128] VMEM scratch (lane-aligned top-k)
    """
    fused_labels = bool(pack and pack[0])
    it = iter(refs)
    nxt = lambda: next(it)
    (seed_ref, cpu_alloc, mem_alloc, pods_alloc, cpu_req, mem_req,
     pods_req, name_id, taint_id, taint_eff) = (nxt() for _ in range(10))
    if with_aff:
        if fused_labels:
            lkey, lnum, qkey = (nxt() for _ in range(3))
            lval = None
        else:
            lkey, lval, lnum, qkey = (nxt() for _ in range(4))
    if with_cons:
        (zone_c, region_c, sn, tn, on_,
         sz, sr, tz, tr, oz, orr) = (nxt() for _ in range(11))
    p_cpu, p_mem, p_valid, p_nnid, untol = (nxt() for _ in range(5))
    if with_aff:
        (sel_valid, sel_qidx, sel_val, req_tv, req_ev, req_qidx, req_op,
         req_num, req_vals, pref_tv, pref_w, pref_ev, pref_qidx, pref_op,
         pref_num, pref_vals) = (nxt() for _ in range(16))
    if with_cons:
        (sp_cid, sp_topo, sp_skew, sp_hard, sp_live, sp_self, sp_min,
         sp_max, ia_tid, ia_topo, ia_reqaff, ia_reqanti, ia_boot,
         ia_prefsign, ii_tid, ii_topo, ii_valid,
         cs_bound, cs_haspref, cs_nrefs) = (nxt() for _ in range(20))
    out_idx, out_prio, run_prio, run_idx = (nxt() for _ in range(4))
    b_i = pl.program_id(0)
    c_i = pl.program_id(1)

    @pl.when(c_i == 0)
    def _():
        run_prio[:] = jnp.full(run_prio.shape, -1, jnp.int32)
        run_idx[:] = jnp.full(run_idx.shape, -1, jnp.int32)

    tb = p_cpu.shape[0]
    ts, c = taint_id.shape
    m = untol.shape[1]

    # ---- NodeResourcesFit (+ row validity via pods_alloc==0 on dead rows).
    free_cpu = cpu_alloc[:] - cpu_req[:]              # [1, C]
    free_mem = mem_alloc[:] - mem_req[:]
    free_pods = pods_alloc[:].astype(jnp.int32) - pods_req[:]
    fits = (
        (p_cpu[:] <= free_cpu)                        # [TB, C]
        & (p_mem[:] <= free_mem)
        & (free_pods >= 1)
    )

    # ---- NodeName.
    nn_ok = (p_nnid[:] == NONE_ID) | (p_nnid[:] == name_id[:])

    # ---- TaintToleration via one-hot matmul (see module doc).
    tid = taint_id[:].astype(jnp.int32)               # [TS, C]
    if pack is not None:
        # Packed layout: decode the 2-bit per-slot effects out of the
        # meta word, per chunk in VMEM — HBM only ever holds the word.
        meta_row = taint_eff[:]                       # [1, C] i32
        teff = jnp.concatenate(
            [(meta_row >> (1 + 2 * t)) & 3 for t in range(taint_id.shape[0])],
            axis=0,
        )                                             # [TS, C]
    else:
        teff = taint_eff[:]
    live = tid != NONE_ID
    hard = live & (
        (teff == EFFECT_NO_SCHEDULE) | (teff == EFFECT_NO_EXECUTE)
    )
    soft = live & (teff == EFFECT_PREFER_NO_SCHEDULE)
    iota_m = lax.broadcasted_iota(jnp.int32, (m, c), 0)
    inc_hard = jnp.zeros((m, c), jnp.float32)
    inc_soft = jnp.zeros((m, c), jnp.float32)
    for t in range(ts):
        onehot = iota_m == tid[t : t + 1, :]          # [M, C]
        inc_hard += jnp.where(onehot & hard[t : t + 1, :], 1.0, 0.0)
        inc_soft += jnp.where(onehot & soft[t : t + 1, :], 1.0, 0.0)
    hard_cnt = jnp.dot(untol[:], inc_hard, preferred_element_type=jnp.float32)
    soft_cnt = jnp.dot(untol[:], inc_soft, preferred_element_type=jnp.float32)
    taint_ok = hard_cnt < 0.5
    tt_score = 100.0 * (1.0 - soft_cnt / ts)

    # ---- LeastAllocated / BalancedAllocation (formulas mirror
    # plugins/scores.py so the two backends agree digit for digit).
    cpu_after = (cpu_req[:] + p_cpu[:]).astype(jnp.float32)       # [TB, C]
    mem_after = (mem_req[:] + p_mem[:]).astype(jnp.float32)
    alloc_cpu = jnp.maximum(cpu_alloc[:], 1).astype(jnp.float32)  # [1, C]
    alloc_mem = jnp.maximum(mem_alloc[:], 1).astype(jnp.float32)
    la = 50.0 * (
        jnp.clip((alloc_cpu - cpu_after) / alloc_cpu, 0.0)
        + jnp.clip((alloc_mem - mem_after) / alloc_mem, 0.0)
    )
    f_cpu = jnp.clip(cpu_after / alloc_cpu, 0.0, 1.0)
    f_mem = jnp.clip(mem_after / alloc_mem, 0.0, 1.0)
    ba = 100.0 * (1.0 - jnp.abs(f_cpu - f_mem) / 2.0)

    # ---- NodeAffinity (with_aff): resolve the batch's query keys against
    # this chunk's label slots, then evaluate every selector slot via a
    # one-hot [TB, Q] x [Q, 4C] dot on the MXU (see module doc).
    if with_aff:
        # All affinity logic runs on i32 0/1 masks (AND = *, OR = max,
        # NOT = 1-x): Mosaic rejects selects/reductions over i1 vectors
        # ("unsupported target bitwidth for truncation"), and the int
        # form vectorizes the same.
        q = qkey.shape[0]
        kq = qkey[:]                                  # [Q, 1]
        found = jnp.zeros((q, c), jnp.int32)
        val = jnp.zeros((q, c), jnp.int32)
        num = jnp.zeros((q, c), jnp.int32)
        for l in range(lkey.shape[0]):
            if fused_labels:
                # Fused word: val << key_bits | key (snapshot/packing.py).
                # Decoded per chunk in VMEM; the bit budget keeps the
                # word non-negative so the shifts are exact.
                w = lkey[l : l + 1, :]
                lk = w & ((1 << pack[1]) - 1)         # [1, C]
                lv = w >> pack[1]
            else:
                lk = lkey[l : l + 1, :]               # [1, C]
                lv = lval[l : l + 1, :]
            eq = (kq == lk) & (lk != NONE_ID)         # [Q, C]
            found = jnp.where(eq, 1, found)
            val = jnp.where(eq, lv, val)
            num = jnp.where(eq, lnum[l : l + 1, :], num)
        # The selection's right-hand side (module doc): columns found,
        # value hi/lo half, numeric hi/lo half; rows the high byte of
        # each half (the value's top byte signed), then the low byte.
        zero = jnp.zeros((q, c), jnp.int32)
        planes = _bf16(jnp.concatenate([
            jnp.concatenate(
                [zero, val >> 24, (val >> 8) & 255,
                 num >> 24, (num >> 8) & 255], axis=1),
            jnp.concatenate(
                [found, (val >> 16) & 255, val & 255,
                 (num >> 16) & 255, num & 255], axis=1),
        ], axis=0))                                   # [2Q, 5C]
        # Lane j of the selection row stands for query index j mod Q,
        # weighted 256 on the high-byte rows and 1 on the low-byte rows.
        lane_q = lax.broadcasted_iota(jnp.int32, (1, 2 * q), 1)
        q_of = jnp.where(lane_q < q, lane_q, lane_q - q)
        w_of = jnp.where(lane_q < q, 256.0, 1.0)
        one_i = jnp.int32(1)

        def gather_slot(qidx_c):
            """One expression slot's per-node view: (found 0/1, value id
            i32, numeric i32 — both recombined from their 16-bit halves,
            each an exact one-pass selection of two bytes)."""
            sel = _bf16(jnp.where(q_of == qidx_c, w_of, 0.0))  # [TB, 2Q]
            g = jnp.dot(sel, planes, preferred_element_type=jnp.float32)
            fi = (g[:, :c] > 0.5).astype(jnp.int32)
            v = (
                g[:, c : 2 * c].astype(jnp.int32) * 65536
                + g[:, 2 * c : 3 * c].astype(jnp.int32)
            )
            x = (
                g[:, 3 * c : 4 * c].astype(jnp.int32) * 65536
                + g[:, 4 * c :].astype(jnp.int32)
            )
            return fi, v, x

        def eval_slot(qidx_c, op_c, num_c, vals_c):
            """match_expressions semantics (ops/label_match.py) for one
            [TB, 1] expression slot against the chunk; returns i32 0/1."""
            fi, v, x = gather_slot(qidx_c)
            in_set = jnp.zeros((tb, c), jnp.int32)
            for vi in range(vals_c.shape[1]):
                in_set = jnp.maximum(
                    in_set,
                    (v == vals_c[:, vi : vi + 1]).astype(jnp.int32),
                )
            num_ok = (
                fi
                * (x != NO_NUMERIC).astype(jnp.int32)
                * (num_c != NO_NUMERIC).astype(jnp.int32)
            )
            return jnp.where(
                op_c == SEL_OP_IN, fi * in_set,
                jnp.where(
                    op_c == SEL_OP_NOT_IN, one_i - fi * in_set,
                    jnp.where(
                        op_c == SEL_OP_EXISTS, fi,
                        jnp.where(
                            op_c == SEL_OP_DOES_NOT_EXIST, one_i - fi,
                            jnp.where(
                                op_c == SEL_OP_GT,
                                num_ok * (x > num_c).astype(jnp.int32),
                                jnp.where(
                                    op_c == SEL_OP_LT,
                                    num_ok * (x < num_c).astype(jnp.int32),
                                    jnp.zeros((tb, c), jnp.int32),
                                ),
                            ),
                        ),
                    ),
                ),
            )

        # spec.nodeSelector: ANDed exact matches.
        sel_pass = jnp.ones((tb, c), jnp.int32)
        for si in range(sel_qidx.shape[1]):
            fi, v, _ = gather_slot(sel_qidx[:, si : si + 1])
            ok = fi * (v == sel_val[:, si : si + 1]).astype(jnp.int32)
            inactive = (sel_valid[:, si : si + 1] == 0).astype(jnp.int32)
            sel_pass = sel_pass * jnp.maximum(ok, inactive)

        # required terms: OR of ANDed-expression terms.
        t_slots = req_tv.shape[1]
        e_slots = req_ev.shape[1] // t_slots
        v_slots = req_vals.shape[1] // req_ev.shape[1]
        aff_any = jnp.zeros((tb, c), jnp.int32)
        for t in range(t_slots):
            tm = jnp.ones((tb, c), jnp.int32)
            he = jnp.zeros((tb, 1), jnp.int32)
            for e in range(e_slots):
                j = t * e_slots + e
                r = eval_slot(
                    req_qidx[:, j : j + 1],
                    req_op[:, j : j + 1],
                    req_num[:, j : j + 1],
                    req_vals[:, j * v_slots : (j + 1) * v_slots],
                )
                ev = (req_ev[:, j : j + 1] != 0).astype(jnp.int32)
                tm = tm * jnp.maximum(r, one_i - ev)
                he = jnp.maximum(he, ev)
            live = (req_tv[:, t : t + 1] != 0).astype(jnp.int32) * he
            aff_any = jnp.maximum(aff_any, tm * live)
        has_terms = jnp.sum(
            (req_tv[:] != 0).astype(jnp.int32), axis=1, keepdims=True
        )
        aff_pass = jnp.where(has_terms > 0, aff_any, jnp.ones((tb, c), jnp.int32))

        # preferred terms: matched-weight sum, normalized (scores.py
        # node_affinity_score).
        p_slots = pref_tv.shape[1]
        pe_slots = pref_ev.shape[1] // p_slots
        pv_slots = pref_vals.shape[1] // pref_ev.shape[1]
        na_acc = jnp.zeros((tb, c), jnp.float32)
        wtot = jnp.zeros((tb, 1), jnp.float32)
        for p in range(p_slots):
            tm = jnp.ones((tb, c), jnp.int32)
            he = jnp.zeros((tb, 1), jnp.int32)
            for e in range(pe_slots):
                j = p * pe_slots + e
                r = eval_slot(
                    pref_qidx[:, j : j + 1],
                    pref_op[:, j : j + 1],
                    pref_num[:, j : j + 1],
                    pref_vals[:, j * pv_slots : (j + 1) * pv_slots],
                )
                ev = (pref_ev[:, j : j + 1] != 0).astype(jnp.int32)
                tm = tm * jnp.maximum(r, one_i - ev)
                he = jnp.maximum(he, ev)
            live = (pref_tv[:, p : p + 1] != 0).astype(jnp.int32) * he
            w = (live * pref_w[:, p : p + 1]).astype(jnp.float32)  # [TB, 1]
            na_acc = na_acc + (tm * live).astype(jnp.float32) * w
            wtot = wtot + w
        na_score = 100.0 * na_acc / jnp.maximum(wtot, 1.0)

    # ---- constraint plugins (with_cons): PodTopologySpread +
    # InterPodAffinity count-table lookups as one-hot matmuls.  The
    # domain-count gathers of the XLA path (plugins/topology.py
    # _counts_for) become: per chunk and family, one [9*SLOTS, C] bf16
    # plane of count bytes — the node columns split on the VPU, the
    # byte-split [SLOTS, Z] zone and [SLOTS, R] region tables projected
    # onto the chunk's nodes with a domain one-hot ([3*SLOTS, Z] x
    # [Z, C], one nonzero term a column, so each result is a byte
    # again) — and per pod ref one [TB, 9*SLOTS] selection whose
    # weights sit at the byte rows of the ref's own topology and slot:
    # one default-precision dot returns the count in that topology,
    # exact for |count| < 2^24 (module doc).  Batch-global statistics
    # (min/max per domain, target totals, preferred-score bounds) are
    # [TB, *] inputs precomputed by the caller from topology.prologue —
    # global reductions don't belong in a chunk-local kernel.
    if with_cons:
        zdim = sz.shape[1]
        rdim = sr.shape[1]
        zc_ids = zone_c[:]                                    # [1, C]
        rc_ids = region_c[:]
        onehot_z = _bf16(
            lax.broadcasted_iota(jnp.int32, (zdim, c), 0) == zc_ids
        )                                                     # [Z, C]
        onehot_r = _bf16(
            lax.broadcasted_iota(jnp.int32, (rdim, c), 0) == rc_ids
        )
        dom_z = (zc_ids != 0).astype(jnp.int32)               # [1, C]
        dom_r = (rc_ids != 0).astype(jnp.int32)

        def count_planes(node_cols, ztab, rtab):
            """The family's counts as one [9*SLOTS, C] selection
            right-hand side (module doc): rows (hostname, zone, region)
            x (byte 0, 1, 2) x slot.  The zone and region bytes are the
            byte-split tables projected onto the chunk's nodes by their
            domain one-hot, one nonzero term a column."""
            def project(tab, onehot):
                return jnp.dot(_bf16(_count_bytes(tab[:])), onehot,
                               preferred_element_type=jnp.float32)
            return _bf16(jnp.concatenate([
                _count_bytes(node_cols[:]).astype(jnp.float32),
                project(ztab, onehot_z),
                project(rtab, onehot_r),
            ], axis=0))

        def ref_counts(planes, slot_col, topo_col):
            """One [TB, 1] (slot, topo) ref -> (cnt i32[TB, C],
            domain_ok i32[TB, C]): one selection row a pod, weighted
            1 / 256 / 65,536 at the three byte rows of its topology
            and slot, so the dot returns the count itself."""
            slots = planes.shape[0] // 9
            lane = lax.broadcasted_iota(jnp.int32, (1, 9 * slots), 1)
            slot_of, topo_of, w_of = lane, jnp.zeros_like(lane), 1.0
            for blk in range(1, 9):
                past = lane >= blk * slots
                slot_of = jnp.where(past, lane - blk * slots, slot_of)
                topo_of = jnp.where(past, blk // 3, topo_of)
                w_of = jnp.where(past, float(256 ** (blk % 3)), w_of)
            is_h = topo_col == TOPO_HOSTNAME
            is_z = topo_col == TOPO_ZONE
            tix = jnp.where(is_h, 0, jnp.where(is_z, 1, 2))   # [TB, 1]
            sel = _bf16(jnp.where(
                (slot_of == slot_col) & (topo_of == tix), w_of, 0.0
            ))                                                # [TB, 9*SLOTS]
            cnt = jnp.dot(sel, planes, preferred_element_type=jnp.float32)
            dok = jnp.where(
                is_h, jnp.ones((tb, c), jnp.int32),
                jnp.where(is_z, dom_z, dom_r),
            )
            return cnt.astype(jnp.int32), dok

        s_planes = count_planes(sn, sz, sr)
        t_planes = count_planes(tn, tz, tr)
        o_planes = count_planes(on_, oz, orr)

        cons_ok = jnp.ones((tb, c), jnp.int32)
        spread_acc = jnp.zeros((tb, c), jnp.float32)
        for j in range(sp_cid.shape[1]):
            cnt, dok = ref_counts(
                s_planes, sp_cid[:, j : j + 1], sp_topo[:, j : j + 1]
            )
            minc = sp_min[:, j : j + 1]
            maxc = sp_max[:, j : j + 1]
            skew_ok = (
                (cnt + sp_self[:, j : j + 1] - minc)
                <= sp_skew[:, j : j + 1]
            ).astype(jnp.int32)
            hard = sp_hard[:, j : j + 1]
            cons_ok = cons_ok * jnp.maximum(dok * skew_ok, 1 - hard)
            denom = jnp.maximum(maxc - minc, 1).astype(jnp.float32)
            s_ref = 100.0 * (maxc - cnt).astype(jnp.float32) / denom
            s_ref = jnp.clip(s_ref, 0.0, 100.0) * dok.astype(jnp.float32)
            spread_acc = spread_acc + s_ref * sp_live[:, j : j + 1].astype(
                jnp.float32
            )
        spread_score = spread_acc / cs_nrefs[:].astype(jnp.float32)

        raw_pref = jnp.zeros((tb, c), jnp.float32)
        for j in range(ia_tid.shape[1]):
            tcnt, tdok = ref_counts(
                t_planes, ia_tid[:, j : j + 1], ia_topo[:, j : j + 1]
            )
            aff_ok = jnp.maximum(
                tdok
                * jnp.maximum(
                    (tcnt > 0).astype(jnp.int32), ia_boot[:, j : j + 1]
                ),
                1 - ia_reqaff[:, j : j + 1],
            )
            anti_ok = jnp.maximum(
                jnp.maximum(1 - tdok, (tcnt == 0).astype(jnp.int32)),
                1 - ia_reqanti[:, j : j + 1],
            )
            cons_ok = cons_ok * aff_ok * anti_ok
            raw_pref = raw_pref + (
                (tcnt * tdok).astype(jnp.float32)
                * ia_prefsign[:, j : j + 1].astype(jnp.float32)
            )
        for j in range(ii_tid.shape[1]):
            ocnt, odok = ref_counts(
                o_planes, ii_tid[:, j : j + 1], ii_topo[:, j : j + 1]
            )
            sym_ok = jnp.maximum(
                jnp.maximum(1 - odok, (ocnt == 0).astype(jnp.int32)),
                1 - ii_valid[:, j : j + 1],
            )
            cons_ok = cons_ok * sym_ok
        ipa_score = jnp.where(
            cs_haspref[:] != 0,
            jnp.clip(
                50.0 + 50.0 * raw_pref / cs_bound[:].astype(jnp.float32),
                0.0, 100.0,
            ),
            0.0,
        )

    score = jnp.zeros((tb, c), jnp.int32)
    if w_la:
        score += jnp.floor(la).astype(jnp.int32) * w_la
    if w_ba:
        score += jnp.floor(ba).astype(jnp.int32) * w_ba
    if w_tt:
        score += jnp.floor(tt_score).astype(jnp.int32) * w_tt
    if with_aff and w_na:
        score += jnp.floor(na_score).astype(jnp.int32) * w_na
    if with_cons:
        if w_ts:
            score += jnp.floor(spread_score).astype(jnp.int32) * w_ts
        if w_ipa:
            score += jnp.floor(ipa_score).astype(jnp.int32) * w_ipa

    # ---- pack priority (ops/priority.py semantics, hash jitter).
    # seed_ref[0, 1]/[0, 2] are the pod/node hash-coordinate bases: a
    # mesh shard passes its global offsets so the jitter it draws for a
    # (pod, node) pair is identical to what a single device draws for
    # the same global pair (the sharded byte-identity contract).
    cols = lax.broadcasted_iota(jnp.int32, (tb, c), 1) + c_i * chunk
    rows_n = (
        lax.broadcasted_iota(jnp.int32, (tb, 1), 0)
        + b_i * tb + seed_ref[0, 1]
    )
    cols_n = (
        lax.broadcasted_iota(jnp.int32, (1, c), 1)
        + c_i * chunk + seed_ref[0, 2]
    )
    jitter = _hash_jitter(seed_ref[0, 0], rows_n, cols_n)
    mask = fits & nn_ok & taint_ok & (p_valid[:] != 0)
    if pack is not None:
        # Packed layout carries row validity explicitly (meta bit 0) —
        # matching the XLA filter chain's table.valid term exactly.
        mask = mask & ((taint_eff[:] & 1) != 0)
    if with_aff:
        mask = mask & (sel_pass > 0) & (aff_pass > 0)
    if with_cons:
        mask = mask & (cons_ok > 0)
    prio = jnp.where(
        mask,
        (jnp.clip(score, 0, MAX_SCORE) << JITTER_BITS) | jitter,
        -1,
    )

    if per_zone:
        _merge_running_per_zone(
            prio, c_i * chunk, zone_c[:], k, c_i, run_prio, run_idx,
            out_prio, out_idx,
        )
    else:
        _merge_running_topk(
            prio, cols, k, c_i, run_prio, run_idx, out_prio, out_idx
        )


def _merge_running_per_zone(prio, base, zone_row, k, c_i, run_prio,
                            run_idx, out_prio, out_idx):
    """Merge one chunk's [TB, C] priorities into the running best row of
    every zone id 0..K-1 (``zone_row`` i32[1, C], the chunk's zone ids;
    ``base`` the chunk's first row): one masked max-extract a zone, its
    result put into lane z of the 128-wide running list by a lane select
    (no slice, no concat: every shape stays lane-aligned).  A pod whose
    spread constraint the wave re-checks in order (engine/assign.py)
    needs a candidate in whatever zone is legal at its turn, which no
    count taken when the wave began can name; the best row of each zone
    covers them all.  The running entry wins a tie and within the chunk
    the first position does, as in _merge_running_topk: the earlier row
    wins."""
    tb, c = prio.shape
    pos_iota = lax.broadcasted_iota(jnp.int32, (tb, c), 1)
    lane = lax.broadcasted_iota(jnp.int32, (tb, 128), 1)
    new_p = run_prio[:]                                           # [TB, 128]
    new_i = run_idx[:]
    for z in range(k):
        m = jnp.where(zone_row == z, prio, -1)                    # [TB, C]
        mx = jnp.max(m, axis=1, keepdims=True)                    # [TB, 1]
        pos = jnp.min(
            jnp.where(m == mx, pos_iota, c), axis=1, keepdims=True
        )
        better = (lane == z) & (new_p < mx)
        new_p = jnp.where(better, mx, new_p)
        new_i = jnp.where(better, base + pos, new_i)
    run_prio[:] = new_p
    run_idx[:] = new_i
    last = pl.num_programs(1) - 1

    @pl.when(c_i == last)
    def _():
        out_prio[:] = new_p[:, :k]
        out_idx[:] = new_i[:, :k]


def _merge_running_topk(prio, cols, k, c_i, run_prio, run_idx,
                        out_prio, out_idx):
    """Merge one chunk's [TB, C] priorities into the running top-k: K
    max-extract passes, all shapes lane-aligned (the running list lives
    in a 128-wide scratch so the concat below is 128-aligned; a
    (K+C)-wide ragged concat relayouts every op in the loop and
    dominated the kernel's runtime).  The running entries sit at
    positions 0..127 so earlier chunks win ties, and within the chunk
    first-position wins — together the full scan's earlier-row-wins
    rule, bit-compatible with chunk_topk + merge_topk."""
    tb, c = prio.shape
    all_prio = jnp.concatenate([run_prio[:], prio], axis=1)       # [TB, 128+C]
    all_idx = jnp.concatenate([run_idx[:], cols], axis=1)
    width = 128 + c
    pos_iota = lax.broadcasted_iota(jnp.int32, (tb, width), 1)
    big = jnp.int32(width)
    top_p = []
    top_i = []
    for _ in range(k):
        mx = jnp.max(all_prio, axis=1, keepdims=True)             # [TB, 1]
        at_max = all_prio == mx
        pos = jnp.min(
            jnp.where(at_max, pos_iota, big), axis=1, keepdims=True
        )
        first = pos_iota == pos                                   # one-hot
        chosen = jnp.sum(jnp.where(first, all_idx, 0), axis=1)    # [TB]
        top_p.append(mx[:, 0])
        top_i.append(jnp.where(mx[:, 0] >= 0, chosen, -1))
        all_prio = jnp.where(first, -2, all_prio)
    new_p = jnp.stack(top_p, axis=1)                              # [TB, K]
    new_i = jnp.stack(top_i, axis=1)
    pad = jnp.full((tb, 128 - k), -1, jnp.int32)
    run_prio[:] = jnp.concatenate([new_p, pad], axis=1)
    run_idx[:] = jnp.concatenate([new_i, pad], axis=1)
    last = pl.num_programs(1) - 1

    @pl.when(c_i == last)
    def _():
        out_prio[:] = new_p
        out_idx[:] = new_i


@functools.partial(
    jax.jit,
    static_argnames=(
        "chunk", "k", "w_la", "w_ba", "w_tt", "w_na", "w_ts", "w_ipa",
        "with_aff", "with_cons", "interpret", "pack", "per_zone",
    ),
)
def _call(
    seed,
    cpu_alloc, mem_alloc, pods_alloc, cpu_req, mem_req, pods_req, name_id,
    taint_id_t, taint_eff_t,
    p_cpu, p_mem, p_valid, p_nnid, untol,
    aff_args,       # () or the 20-tuple of affinity arrays (see below)
    cons_args,      # () or the constraint tuple (see fused_topk)
    *,
    chunk: int,
    k: int,
    w_la: int,
    w_ba: int,
    w_tt: int,
    w_na: int,
    w_ts: int,
    w_ipa: int,
    with_aff: bool,
    with_cons: bool,
    interpret: bool,
    pack: tuple | None = None,
    per_zone: bool = False,
):
    n = cpu_alloc.shape[0]
    b = p_cpu.shape[0]
    ts = taint_id_t.shape[0]
    m = untol.shape[1]
    tb = b if (b <= 256 or b % 256) else 256
    grid = (b // tb, n // chunk)

    col = pl.BlockSpec(
        (1, chunk), lambda bi, ci: (0, ci), memory_space=pltpu.VMEM
    )
    taint = pl.BlockSpec(
        (ts, chunk), lambda bi, ci: (0, ci), memory_space=pltpu.VMEM
    )
    pod = pl.BlockSpec(
        (tb, 1), lambda bi, ci: (bi, 0), memory_space=pltpu.VMEM
    )

    def podw(w):    # [TB, W] pod-row block of width w
        return pl.BlockSpec(
            (tb, w), lambda bi, ci: (bi, 0), memory_space=pltpu.VMEM
        )

    def cols(rows):  # [rows, C] chunked slot-table columns
        return pl.BlockSpec(
            (rows, chunk), lambda bi, ci: (0, ci), memory_space=pltpu.VMEM
        )

    def whole(a):    # small replicated table, full block
        return pl.BlockSpec(
            a.shape, lambda bi, ci: (0, 0), memory_space=pltpu.VMEM
        )

    out = pl.BlockSpec((tb, k), lambda bi, ci: (bi, 0), memory_space=pltpu.VMEM)

    in_specs = [
        pl.BlockSpec((1, 3), lambda bi, ci: (0, 0), memory_space=pltpu.SMEM),
        col, col, col, col, col, col, col,
        # Packed layout: taint_eff_t is the [1, N] meta word, a col
        # plane; plain layout streams the full [TS, N] effect plane.
        taint, taint if pack is None else col,
    ]
    args = [
        seed.reshape(1, 3),
        cpu_alloc.reshape(1, n), mem_alloc.reshape(1, n),
        pods_alloc.reshape(1, n),
        cpu_req.reshape(1, n), mem_req.reshape(1, n), pods_req.reshape(1, n),
        name_id.reshape(1, n),
        taint_id_t,
        taint_eff_t if pack is None else taint_eff_t.reshape(1, n),
    ]
    if with_aff:
        if pack and pack[0]:
            # Fused label words: one [L, N] plane instead of key+value.
            (lkey_t, lnum_t, qkey,
             sel_valid, sel_qidx, sel_val,
             req_tv, req_ev, req_qidx, req_op, req_num, req_vals,
             pref_tv, pref_w, pref_ev, pref_qidx, pref_op, pref_num,
             pref_vals) = aff_args
            label_planes = [lkey_t, lnum_t]
        else:
            (lkey_t, lval_t, lnum_t, qkey,
             sel_valid, sel_qidx, sel_val,
             req_tv, req_ev, req_qidx, req_op, req_num, req_vals,
             pref_tv, pref_w, pref_ev, pref_qidx, pref_op, pref_num,
             pref_vals) = aff_args
            label_planes = [lkey_t, lval_t, lnum_t]
        l = lkey_t.shape[0]
        label = pl.BlockSpec(
            (l, chunk), lambda bi, ci: (0, ci), memory_space=pltpu.VMEM
        )
        qn = qkey.shape[0]
        in_specs += [label] * len(label_planes) + [
            pl.BlockSpec((qn, 1), lambda bi, ci: (0, 0), memory_space=pltpu.VMEM),
        ]
        args += label_planes + [qkey.reshape(qn, 1)]
    if with_cons:
        (zone, region, sn, tn, on_, sz, sr, tz, tr, oz, orr,
         cons_pod) = cons_args
        in_specs += [
            col, col, cols(sn.shape[0]), cols(tn.shape[0]),
            cols(on_.shape[0]),
            whole(sz), whole(sr), whole(tz), whole(tr), whole(oz),
            whole(orr),
        ]
        args += [
            zone.reshape(1, n), region.reshape(1, n), sn, tn, on_,
            sz, sr, tz, tr, oz, orr,
        ]
    in_specs += [pod, pod, pod, pod, podw(m)]
    args += [
        p_cpu.reshape(b, 1), p_mem.reshape(b, 1),
        p_valid.reshape(b, 1).astype(jnp.int32),
        p_nnid.reshape(b, 1),
        untol,
    ]
    if with_aff:
        aff_pod = [
            sel_valid, sel_qidx, sel_val,
            req_tv, req_ev, req_qidx, req_op, req_num, req_vals,
            pref_tv, pref_w, pref_ev, pref_qidx, pref_op, pref_num, pref_vals,
        ]
        aff_pod = [a.astype(jnp.int32) for a in aff_pod]
        in_specs += [podw(a.shape[1]) for a in aff_pod]
        args += aff_pod
    if with_cons:
        cons_pod = [a.astype(jnp.int32) for a in cons_pod]
        in_specs += [podw(a.shape[1]) for a in cons_pod]
        args += cons_pod

    kernel = functools.partial(
        _kernel, chunk=chunk, k=k,
        w_la=w_la, w_ba=w_ba, w_tt=w_tt, w_na=w_na, w_ts=w_ts, w_ipa=w_ipa,
        with_aff=with_aff, with_cons=with_cons, pack=pack, per_zone=per_zone,
    )
    idx, prio = pl.pallas_call(
        kernel,
        # The kernel's name in HLO and in a device trace; the affinity
        # and the constraint variants are different (and far dearer)
        # programs, each read under a name of its own.
        name=kernel_name(with_aff, with_cons),
        grid=grid,
        in_specs=in_specs,
        out_specs=(out, out),
        out_shape=(
            jax.ShapeDtypeStruct((b, k), jnp.int32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ),
        scratch_shapes=[
            pltpu.VMEM((tb, 128), jnp.int32),
            pltpu.VMEM((tb, 128), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(*args)
    return idx, prio


def fused_topk(
    table: NodeTable,
    batch: PodBatch,
    seed: jax.Array,
    profile: Profile,
    *,
    chunk: int,
    k: int,
    with_affinity: bool = True,
    constraints=None,
    stats=None,
    interpret: bool | None = None,
    row_base=0,
    col_base=0,
    per_zone: bool = False,
):
    """(idx i32[B,K], prio i32[B,K]) — global-row candidates, -1 = none.

    ``seed`` is an i32 scalar (fold the batch counter in host-side).
    ``row_base``/``col_base`` bias the tie-break hash's pod/node
    coordinates (traced i32 scalars): a mesh shard passes its global
    batch-block and row offsets so its jitter stream matches the
    single-device stream for the same global (pod, node) pair — the
    sharded byte-identity contract (see engine.filter_score_topk).
    ``with_affinity=False`` compiles the cheaper base kernel for waves
    whose pods carry no selectors (the coordinator knows from the packed
    field groups); it changes cost, never semantics, for such waves.
    ``constraints``+``stats`` (a ConstraintState and its
    topology.prologue) enable the fused constraint stage — BASELINE
    configs 3-4 on the pallas path.  Size TableSpec.max_zones/max_regions
    and the slot/ref dims to the workload: the constraint stage
    materializes [max_zones, chunk] one-hot planes in VMEM and unrolls
    one evaluation per ref slot, so worst-case schema dims cost real
    VMEM and compile time (same rule as the affinity slots).
    ``per_zone`` (constraints only, ``k`` = the zone table's width):
    candidate slot z is the best row of zone id z, in zone order and not
    in priority order (_merge_running_per_zone).
    ``interpret=None`` auto-selects interpreter mode off-TPU so the same
    tests run on the CPU mesh.
    """
    with_cons = constraints is not None
    if per_zone and not (
        with_cons and k == constraints.spread_zone.shape[1] <= 128
    ):
        raise ValueError(
            "per_zone candidates take constraints and k = max_zones <= 128"
        )
    if with_cons and stats is None:
        raise ValueError(
            "constraints require stats=topology.prologue(table, constraints)"
        )
    if not with_cons and not supports(profile):
        raise ValueError(
            "profile has constraint plugins enabled; pass constraints= "
            f"and stats= to run them fused (got {profile})"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = table.num_rows
    if n % chunk:
        raise ValueError(f"table rows {n} not divisible by chunk {chunk}")
    from k8s1m_tpu.snapshot.packing import is_packed

    # Packed snapshot (snapshot/packing.py): the kernel streams the
    # packed planes and decodes per chunk in VMEM — same HBM layout as
    # the XLA scan path, byte-identical candidates.
    pack = None
    if is_packed(table):
        pack = (table.spec.fuse_labels, table.spec.key_bits)
    if with_affinity:
        _check_slots(batch)
        b = batch.batch
        label_planes = (
            (jnp.transpose(table.label_key), jnp.transpose(table.label_num))
            if pack and pack[0] else
            (
                jnp.transpose(table.label_key),
                jnp.transpose(table.label_val),
                jnp.transpose(table.label_num),
            )
        )
        aff_args = (
            *label_planes,
            batch.qkey,
            batch.sel_valid, batch.sel_qidx, batch.sel_val,
            batch.req_term_valid,
            batch.req_expr_valid.reshape(b, -1),
            batch.req_qidx.reshape(b, -1),
            batch.req_op.reshape(b, -1),
            batch.req_num.reshape(b, -1),
            batch.req_vals.reshape(b, -1),
            batch.pref_term_valid, batch.pref_weight,
            batch.pref_expr_valid.reshape(b, -1),
            batch.pref_qidx.reshape(b, -1),
            batch.pref_op.reshape(b, -1),
            batch.pref_num.reshape(b, -1),
            batch.pref_vals.reshape(b, -1),
        )
    else:
        aff_args = ()
    if with_cons:
        from k8s1m_tpu.plugins import topology as topo

        # Per-pod statistics of the batch prologue: read from a trace under
        # the scope engine.cycle.candidates opens for the prologue itself.
        with jax.named_scope("cons_prologue"):
            i32 = jnp.int32
            b = batch.batch
            sp_min = topo._stat_for(
                stats.spread_min, batch.spread_cid, batch.spread_topo
            )
            sp_max = topo._stat_for(
                stats.spread_max, batch.spread_cid, batch.spread_topo
            )
            sp_hard = (
                batch.spread_valid & (batch.spread_mode == SPREAD_DO_NOT_SCHEDULE)
            )
            total = jnp.take(stats.tgt_total, batch.ipa_tid)
            boot = (total == 0) & batch.ipa_self
            reqaff = batch.ipa_valid & batch.ipa_required & ~batch.ipa_anti
            reqanti = batch.ipa_valid & batch.ipa_required & batch.ipa_anti
            pref = batch.ipa_valid & ~batch.ipa_required
            prefsign = jnp.where(
                pref, jnp.where(batch.ipa_anti, -1, 1) * batch.ipa_weight, 0
            )
            bound = (
                jnp.abs(batch.ipa_weight)
                * jnp.take(stats.tgt_max, batch.ipa_tid)
                * pref
            ).sum(axis=1)
            cons_pod = [
                batch.spread_cid, batch.spread_topo, batch.spread_max_skew,
                sp_hard, batch.spread_valid, batch.spread_self, sp_min, sp_max,
                batch.ipa_tid, batch.ipa_topo, reqaff, reqanti, boot, prefsign,
                batch.iinc_tid, batch.iinc_topo, batch.iinc_valid,
                jnp.maximum(bound, 1).reshape(b, 1),
                pref.any(axis=1).reshape(b, 1),
                jnp.maximum(batch.spread_valid.sum(axis=1), 1).reshape(b, 1),
            ]
            c = constraints
            cons_args = (
                # Packed layout: the constraint stage's one-hot domain planes
                # need i32 ids (two full-column casts per wave, fused by XLA).
                table.zone.astype(i32), table.region.astype(i32),
                c.spread_node.astype(i32), c.tgt_node.astype(i32),
                c.own_node.astype(i32),
                c.spread_zone, c.spread_region, c.tgt_zone, c.tgt_region,
                c.own_zone, c.own_region,
                cons_pod,
            )
    else:
        cons_args = ()
    return _call(
        jnp.stack([
            jnp.asarray(seed, jnp.int32),
            jnp.asarray(row_base, jnp.int32),
            jnp.asarray(col_base, jnp.int32),
        ]),
        table.cpu_alloc, table.mem_alloc, table.pods_alloc,
        table.cpu_req, table.mem_req, table.pods_req, table.name_id,
        jnp.transpose(table.taint_id),
        # Packed: the meta word replaces the [N, TS] effect plane.
        table.meta if pack is not None else jnp.transpose(table.taint_effect),
        batch.cpu, batch.mem, batch.valid, batch.node_name_id,
        1.0 - batch.tolerated.astype(jnp.float32),
        aff_args,
        cons_args,
        chunk=chunk, k=k,
        w_la=profile.least_allocated,
        w_ba=profile.balanced_allocation,
        w_tt=profile.taint_toleration,
        w_na=profile.node_affinity,
        w_ts=profile.topology_spread if with_cons else 0,
        w_ipa=profile.interpod_affinity if with_cons else 0,
        with_aff=with_affinity,
        with_cons=with_cons,
        interpret=interpret,
        pack=pack,
        per_zone=per_zone,
    )


# Shared with the XLA path (ops/priority.py) so both backends derive the
# same per-wave seed from the same key.
seed_of = _priority_seed_of


def pallas_candidates(
    table: NodeTable,
    batch: PodBatch,
    key: jax.Array,
    profile: Profile,
    *,
    chunk: int,
    k: int,
    row_offset=0,
    pod_offset=0,
    with_affinity: bool = True,
    constraints=None,
    stats=None,
    interpret: bool | None = None,
    per_zone: bool = False,
):
    """Drop-in for engine.filter_score_topk.

    Returns engine.cycle.Candidates with the same payload columns (free
    capacity + topology domains gathered at the candidate rows).
    ``constraints``/``stats`` run the stateful plugins fused (fused_topk).
    ``row_offset``/``pod_offset`` follow filter_score_topk's contract:
    they globalize the emitted rows AND the tie-break hash coordinates,
    keeping mesh shards bit-identical to the single-device stream.
    """
    from k8s1m_tpu.engine.cycle import Candidates

    idx, prio = fused_topk(
        table, batch, seed_of(key), profile,
        chunk=chunk, k=k, with_affinity=with_affinity,
        constraints=constraints, stats=stats, interpret=interpret,
        row_base=pod_offset, col_base=row_offset, per_zone=per_zone,
    )
    safe = jnp.clip(idx, 0)
    free_cpu, free_mem, free_pods = table.free()
    feasible = prio >= 0
    return Candidates(
        idx=jnp.where(feasible, idx + row_offset, -1),
        prio=prio,
        cpu=jnp.take(free_cpu, safe),
        mem=jnp.take(free_mem, safe),
        pods=jnp.take(free_pods, safe),
        # astype: the packed layout's narrow zone/region planes widen to
        # the i32 candidate payload (no-op on the plain layout).
        zone=jnp.take(table.zone, safe).astype(jnp.int32),
        region=jnp.take(table.region, safe).astype(jnp.int32),
    )


def kernel_name(with_affinity: bool, with_constraints: bool) -> str:
    """The fused kernel's name by the stages it was built with: what
    ``pallas_call(name=)`` gets, and so what HLO and a device trace call
    it."""
    return (
        "fused_topk" + "_affinity" * bool(with_affinity)
        + "_constraints" * bool(with_constraints)
    )


def np_reference_topk(
    table, batch, seed: int, profile: Profile, k: int,
    with_affinity: bool = True,
):
    """Pure-numpy oracle of the kernel (for differential tests): same
    filters, scores, hash jitter, and first-position tie rule."""
    ca = np.asarray(table.cpu_alloc, np.int64)
    ma = np.asarray(table.mem_alloc, np.int64)
    pa = np.asarray(table.pods_alloc, np.int64)
    cr = np.asarray(table.cpu_req, np.int64)
    mr = np.asarray(table.mem_req, np.int64)
    pr = np.asarray(table.pods_req, np.int64)
    nid = np.asarray(table.name_id)
    tid = np.asarray(table.taint_id)
    teff = np.asarray(table.taint_effect)
    pc = np.asarray(batch.cpu, np.int64)[:, None]
    pm = np.asarray(batch.mem, np.int64)[:, None]
    pv = np.asarray(batch.valid)[:, None]
    nn = np.asarray(batch.node_name_id)[:, None]
    tol = np.asarray(batch.tolerated)

    fits = (pc <= (ca - cr)) & (pm <= (ma - mr)) & ((pa - pr) >= 1)
    nn_ok = (nn == NONE_ID) | (nn == nid[None, :])
    live = tid != NONE_ID
    hard = live & np.isin(teff, (EFFECT_NO_SCHEDULE, EFFECT_NO_EXECUTE))
    soft = live & (teff == EFFECT_PREFER_NO_SCHEDULE)
    untol = ~tol[:, tid]                                  # [B, N, TS]
    hard_cnt = (untol & hard[None]).sum(-1)
    soft_cnt = (untol & soft[None]).sum(-1)
    ts = tid.shape[1]

    cpu_after = (cr[None] + pc).astype(np.float32)
    mem_after = (mr[None] + pm).astype(np.float32)
    f_ca = np.maximum(ca, 1).astype(np.float32)[None]
    f_ma = np.maximum(ma, 1).astype(np.float32)[None]
    la = 50.0 * (
        np.clip((f_ca - cpu_after) / f_ca, 0.0, None)
        + np.clip((f_ma - mem_after) / f_ma, 0.0, None)
    )
    ba = 100.0 * (
        1.0
        - np.abs(
            np.clip(cpu_after / f_ca, 0, 1) - np.clip(mem_after / f_ma, 0, 1)
        )
        / 2.0
    )
    tt = 100.0 * (1.0 - soft_cnt.astype(np.float32) / ts)
    score = (
        np.floor(la).astype(np.int64) * profile.least_allocated
        + np.floor(ba).astype(np.int64) * profile.balanced_allocation
        + np.floor(tt).astype(np.int64) * profile.taint_toleration
    )

    if with_affinity:
        lk = np.asarray(table.label_key)
        lv = np.asarray(table.label_val)
        ln = np.asarray(table.label_num)
        qk = np.asarray(batch.qkey)
        leq = (qk[:, None, None] == lk[None]) & (lk[None] != NONE_ID)
        found = leq.any(-1)                               # [Q, N]
        val = np.where(leq, lv[None], 0).sum(-1)
        num = np.where(leq, ln[None], 0).sum(-1).astype(np.int32)

        def match(expr_valid, qidx, op, vals, numo):
            f = found[qidx]                               # [..., E, N]
            v = val[qidx]
            x = num[qidx]
            in_set = (v[..., None] == vals[..., None, :]).any(-1)
            ok_num = (
                f
                & (x != NO_NUMERIC)
                & (numo[..., None] != NO_NUMERIC)
            )
            o = op[..., None]
            r = np.select(
                [o == SEL_OP_IN, o == SEL_OP_NOT_IN, o == SEL_OP_EXISTS,
                 o == SEL_OP_DOES_NOT_EXIST, o == SEL_OP_GT, o == SEL_OP_LT],
                [f & in_set, ~(f & in_set), f, ~f,
                 ok_num & (x > numo[..., None]), ok_num & (x < numo[..., None])],
                default=False,
            )
            tm = (r | ~expr_valid[..., None]).all(axis=-2)
            return tm, expr_valid.any(-1)

        sv = np.asarray(batch.sel_valid)
        f = found[np.asarray(batch.sel_qidx)]
        v = val[np.asarray(batch.sel_qidx)]
        ok = f & (v == np.asarray(batch.sel_val)[..., None])
        sel_pass = (ok | ~sv[..., None]).all(axis=1)

        tm, he = match(
            np.asarray(batch.req_expr_valid), np.asarray(batch.req_qidx),
            np.asarray(batch.req_op), np.asarray(batch.req_vals),
            np.asarray(batch.req_num),
        )
        live = np.asarray(batch.req_term_valid) & he
        any_term = (tm & live[..., None]).any(axis=1)
        has_terms = np.asarray(batch.req_term_valid).any(axis=1)
        aff_pass = np.where(has_terms[:, None], any_term, True)

        ptm, phe = match(
            np.asarray(batch.pref_expr_valid), np.asarray(batch.pref_qidx),
            np.asarray(batch.pref_op), np.asarray(batch.pref_vals),
            np.asarray(batch.pref_num),
        )
        plive = np.asarray(batch.pref_term_valid) & phe
        w = np.where(plive, np.asarray(batch.pref_weight), 0)
        matched = (ptm & plive[..., None]) * w[..., None]
        total = np.maximum(w.sum(axis=1), 1)
        na = 100.0 * matched.sum(axis=1).astype(np.float32) / total[:, None]
        score = score + np.floor(na).astype(np.int64) * profile.node_affinity

    b, n = score.shape

    def mix32(h):
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x7FEB352D)
        h ^= h >> np.uint32(15)
        h *= np.uint32(0x846CA68B)
        h ^= h >> np.uint32(16)
        return h

    s32 = np.uint32(seed & 0xFFFFFFFF)   # seed_of() draws negatives too
    rh = mix32(
        s32 ^ (np.arange(b, dtype=np.uint32)[:, None] * np.uint32(0x9E3779B9))
    )
    ch = mix32(
        s32 ^ (np.arange(n, dtype=np.uint32)[None, :] * np.uint32(0x85EBCA6B))
    )
    jitter = ((rh ^ ch) & np.uint32((1 << JITTER_BITS) - 1)).astype(np.int64)

    mask = fits & nn_ok & (hard_cnt == 0) & pv
    if with_affinity:
        mask = mask & sel_pass & aff_pass
    prio = np.where(
        mask, (np.clip(score, 0, MAX_SCORE) << JITTER_BITS) | jitter, -1
    ).astype(np.int64)

    out_i = np.full((b, k), -1, np.int32)
    out_p = np.full((b, k), -1, np.int32)
    work = prio.copy()
    for j in range(k):
        best = work.argmax(axis=1)
        mx = work[np.arange(b), best]
        out_p[:, j] = mx
        out_i[:, j] = np.where(mx >= 0, best, -1)
        work[np.arange(b), best] = -2
    return out_i, out_p
