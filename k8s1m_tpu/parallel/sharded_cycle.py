"""The multi-device scheduling cycle: shard_map over the (dp, sp) mesh.

Dataflow per cycle (replacing reference SURVEY.md §3.2's process hops):

1. each (dp, sp) device runs the chunked filter+score+top-k over its
   [B/dp, N/sp] block — the hot loop, purely local;
2. candidates all-gather over ``sp`` and merge (engine/cycle.merge_shards,
   scope ``sp_merge``): a re-top-k, or, where the wave counts skew
   (``in_wave_skew``), zone by zone, the best of the shards' rows in each
   zone id — the ICI replacement for the CollectScore gRPC gather +
   ScoreEvaluator rendezvous (reference
   pkg/scoreevaluator/scoreevaluator.go:45-126);
3. candidates (and pod resources) all-gather over ``dp``, giving every
   device the full batch's candidate lists — a few KB;
4. the greedy conflict-resolution scan runs *replicated* on every device
   (identical inputs -> identical result, no coordination), replacing the
   reference's optimistic bind-and-rollback — replicated by
   construction, not by inference, which is why every shard_map here
   passes ``check_vma=False``.  The in-wave skew count and the inter-pod
   gate run inside it as on one device: their count tables are the
   replicated zone / region tables, their domain statistics the global
   prologue's (``prologue_stats(axis_name="sp")``), their pod fields the
   whole wave's, which every device unpacks;
5. each sp shard commits the binds that landed in its row range to its
   slice of the table and of the hostname-domain count tables; zone /
   region count tables are replicated and take the full (identical)
   update on every device.

Total ICI traffic per cycle is O(B * K) candidate records — independent
of node count; the reference moves O(shards) gRPC messages per pod.

Byte-identity contract: every device uses the SAME per-wave PRNG seed
and hashes tie-break jitter over GLOBAL (pod row, node row) coordinates
(mesh_offsets), per-shard top-k lists keep ties in ascending-global-row
order, and the sp/dp gathers concatenate shard-major — so the merged
candidate lists, the replicated conflict scan, and the bind rows are
bit-identical to the single-device cycle for the same wave.  This is
what lets the coordinator promote the mesh to the production execution
path with a differential gate instead of a statistical one
(tests/test_mesh_differential.py; sampled windows are the one
exception — they rotate SHARD-locally by design, and hash global
columns where one device hashes window-local ones:
engine/cycle.candidates).

Pipelined snapshot mutation: the coordinator's dirty-row scatters
(make_sharded_scatter) consume the *latest* table future, so they are
stream-ordered after every dispatched wave by data dependency — a
capacity delta applied while waves are in flight lands between wave N
and wave N+1 with no host sync and no quiesce.  The scatter is pinned to
the table's row sharding (out_shardings) for the same reason the
coordinator pins its single-device scatter: a replicated output here
would silently serialize every later wave behind a reshard.

Donation (meshpack): the production step, scatter, and adjust
executables all donate the table/constraint buffers — pinning and
donation compose (inputs arrive sp-sharded, outputs are pinned
sp-sharded, XLA aliases shard-by-shard), so per-wave bind commits and
dirty-row churn scatters update sharded HBM in place instead of paying
a copy-on-write table per wave.  The packed snapshot layout
(snapshot/packing.py) rides the same specs: packed planes shard on sp
and decode inside the shard-local chunk slice, identical to the
single-device scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

import functools

from k8s1m_tpu.engine.cycle import (
    candidates,
    commit_fields_of,
    finalize_batch,
    has_selectors,
    prologue_stats,
    wave_interpod,
    wave_skew,
)
from k8s1m_tpu.parallel.mesh import batch_specs, constraint_specs, table_specs
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot.constraints import ConstraintState
from k8s1m_tpu.snapshot.node_table import NodeTable, scatter_rows
from k8s1m_tpu.snapshot.pod_encoding import PodBatch

# greedy_assign runs replicated: every leaf of its Assignment is whole on
# every device, ``unbound`` too where the wave counts skew or runs the gate.
_ASG_SPECS = P()


def make_sharded_scatter(table_sharding):
    """Dirty-row scatter pinned to the table's row sharding — the mesh
    form of the coordinator's donating jitted
    snapshot.node_table.scatter_rows.  Safe to enqueue while waves are
    in flight: it consumes the latest table future, so it executes
    after every dispatched wave (see the module doc's pipelined-mutation
    note).

    Donation + pinning compose (meshpack): the input table arrives
    already placed on ``table_sharding`` and the output is pinned to
    the same sharding, so XLA aliases each shard's buffers in place —
    the churn scatter updates sharded HBM without a copy-on-write
    table, and without letting the partitioner drift the table onto a
    replicated layout (which would serialize every later wave behind a
    reshard).  The coordinator always reassigns ``self.table`` from the
    return; a replay caller that keeps its input table alive must jit
    its own non-donating wrapper."""
    return jax.jit(
        scatter_rows, donate_argnums=(0,), out_shardings=table_sharding
    )


def mesh_offsets(table, b_local: int):
    """(pod_offset, row_offset) for this device (call inside shard_map).

    The tie-break hash is a pure function of (seed, global pod row,
    global node row) — ops/priority.hash_jitter over GLOBAL coordinates
    with the SAME per-wave seed on every device.  A dp shard therefore
    passes its batch-block offset and an sp shard its row offset, and
    the priorities each shard computes are bit-identical to the slice a
    single device would compute: the sharded cycle is byte-identical to
    the single-device cycle, bind for bind (the mesh differential gate,
    tests/test_mesh_differential.py).  Earlier revisions folded the mesh
    coordinates into the PRNG key instead, which decorrelated tie-breaks
    across shards and made the mesh path only statistically equivalent.
    """
    return lax.axis_index("dp") * b_local, lax.axis_index("sp") * table.num_rows


def gather_and_finalize(table, batch, cand, constraints, *, skew=None,
                        interpod=None):
    """The shared sharded epilogue (call inside shard_map over (dp, sp)),
    on candidates ``candidates()`` has already merged over ``sp``:

    1. gather candidates and commit fields across ``dp`` (pods stay in
       batch order: dp shards are contiguous blocks) — only CommitFields
       crosses this hop, the selector tensors never leave home;
    2. replicated greedy conflict resolution (identical inputs ->
       identical result on every device, no coordination), with the
       in-wave count (``skew``) and the inter-pod gate (``interpod``) over
       the whole wave where given, then commit the binds landing in this
       shard's row range; zone/region count tables are replicated and
       take the full identical update.

    Returns (table, constraints|None, Assignment).
    """
    rows = table.num_rows
    row_offset = lax.axis_index("sp") * rows

    def gather_dp(x):
        g = lax.all_gather(x, "dp")
        return g.reshape(-1, *x.shape[1:])

    cand = jax.tree.map(gather_dp, cand)
    fields = jax.tree.map(gather_dp, commit_fields_of(batch))

    return finalize_batch(
        table, constraints, cand, fields, row_offset=row_offset, rows=rows,
        skew=skew, interpod=interpod,
    )


def make_sharded_step(mesh, profile: Profile, *, chunk: int, k: int):
    """Build the jitted multi-device scheduling step for a fixed mesh.

    Returns step(table, batch, key[, constraints]):
    -> (table, constraints|None, Assignment); table (and hostname-domain
    count tables) sharded over sp, batch over dp, assignment replicated.
    """
    def _local_step(table: NodeTable, batch: PodBatch, key: jax.Array,
                    constraints: ConstraintState | None = None):
        pod_offset, row_offset = mesh_offsets(table, batch.batch)
        # Local filter+score+top-k over this device's block — same key
        # on every device, global hash coordinates (see mesh_offsets).
        cand = candidates(
            table, batch, key, constraints, profile, chunk=chunk, k=k,
            row_offset=row_offset, pod_offset=pod_offset, axis_name="sp",
        )
        return gather_and_finalize(table, batch, cand, constraints)

    def step(table, batch, key, constraints=None):
        cons_specs = constraint_specs(constraints) if constraints is not None else None
        return jax.shard_map(
            _local_step,
            mesh=mesh,
            in_specs=(table_specs(table), batch_specs(batch), P(), cons_specs),
            out_specs=(table_specs(table), cons_specs, _ASG_SPECS),
            check_vma=False,
        )(table, batch, key, constraints)

    # Replay/dev surface (tests, dryruns, multihost smokes re-run one
    # table): the production mesh executable is make_sharded_packed_step
    # with donate=True.
    return jax.jit(step)  # graftlint: disable=undonated-device-update (replay/dev surface; production donates via make_sharded_packed_step)


@functools.lru_cache(maxsize=64)
def make_sharded_packed_step(
    mesh,
    profile: Profile,
    *,
    chunk: int,
    k: int,
    pod_spec,
    table_spec,
    groups: frozenset,
    sample_rows: int | None = None,
    backend: str = "xla",
    donate: bool = False,
    in_wave_skew: bool = False,
    interpod: bool = False,
):
    """The mesh analogue of engine.cycle._jitted_schedule_packed: the
    coordinator's production step — packed two-buffer pod upload,
    percentageOfNodesToScore windows, one i32[B] bind-row result — run
    as a shard_map over the (dp, sp) mesh so the e2e loop (store ->
    watch -> schedule -> CAS bind) drives every chip, not one.

    ``table`` may be either snapshot layout.  A
    snapshot.packing.PackedNodeTable (the production layout) shards its
    packed planes — meta word, fused label words, int16/int8 scalars —
    over ``sp`` exactly like the plain columns, and each shard decodes
    inside its local chunk slice (engine/cycle._slice_table →
    unpack_chunk), so the decode shares the single-device code path and
    HBM holds only the packed layout on every device.

    ``donate=True`` is the production coordinator form: the table's
    (and constraint state's) buffers are donated to the step, so the
    per-wave commit updates each shard's HBM in place instead of
    copy-on-write — the caller MUST reassign from the return (the
    donated input is dead).  Replay/differential callers keep the
    non-donating default.

    This is the TPU re-expression of the reference's scheduler fan-out:
    "more replicas" (reference pkg/schedulerset/schedulerset.go:161-193,
    289 Go replicas at 1M nodes) becomes "more mesh devices", with the
    CollectScore gRPC gather replaced by an ICI all-gather.

    Sharding layout (parallel/mesh.py):
    - node table rows over ``sp`` (each shard owns N/sp rows);
    - the pod batch over ``dp`` — the packed buffers are replicated
      (they are a flat field concatenation, a few KB) and each dp rank
      unpacks the full wave then slices its contiguous pod block, so the
      O(B*N) filter+score work is dp-sharded even though the upload is
      not;
    - ``sample_rows`` is SHARD-LOCAL: each shard filters+scores a
      rotating chunk-aligned window of its own rows (the reference's
      percentageOfNodesToScore works the same way per replica —
      dist-scheduler samples 5% of the nodes *it owns*).

    ``in_wave_skew`` and ``interpod`` (engine.cycle.has_interpod_gate of
    the wave's groups, decided by the caller) build the in-wave count and
    the inter-pod gate into the replicated conflict scan, as the
    single-device step builds them: an unsampled wave binds what one
    device binds, and ``Assignment.unbound`` says why pods stayed unbound.

    Overload note: ``sample_rows`` and ``profile`` are cache keys, so a
    coordinator flipping to its degraded mode (k8s1m_tpu/loadshed:
    smaller window, filter-only constraint plugins) selects a DIFFERENT
    cached executable here.  Warm both mode pairs before a
    latency-sensitive window — the first degraded wave otherwise pays a
    mid-overload compile, the worst possible moment for one.

    Returns step(table, ints, bools, key, offset[, constraints])
    -> (table, constraints|None, Assignment, rows i32[B]); table and
    constraint node tables sharded, everything else replicated.
    """
    from k8s1m_tpu.snapshot.pod_encoding import unpack_pod_batch

    dp_size = mesh.shape["dp"]
    b_full = pod_spec.batch
    if b_full % dp_size:
        raise ValueError(f"batch {b_full} not divisible by dp={dp_size}")
    b_local = b_full // dp_size
    aff = has_selectors(groups)

    def _local_step(table, ints, bools, key, offset, constraints=None):
        pod_offset, row_offset = mesh_offsets(table, b_local)
        dp = lax.axis_index("dp")

        full = unpack_pod_batch(ints, bools, pod_spec, table_spec, groups)

        def slice_dp(x):
            if not (x.ndim >= 1 and x.shape[0] == b_full):
                return x
            if isinstance(x, np.ndarray) and not x.any():
                # Absent packed group (numpy zeros): any dp slice of an
                # all-zeros array is zeros, so rebuild at local shape
                # instead of dynamic-slicing with the traced dp index —
                # slicing would turn the constant into a tracer and
                # defeat the filter plugins' trace-time skip
                # (plugins/filters._statically_empty) on the mesh path.
                return np.zeros((b_local,) + x.shape[1:], x.dtype)
            return lax.dynamic_slice_in_dim(x, dp * b_local, b_local, 0)

        batch = jax.tree.map(slice_dp, full).replace(
            qkey=full.qkey          # qkey is [Q]; stays whole on every rank
        )

        stats = skew = gate = None
        if constraints is not None and (in_wave_skew or interpod):
            # the global prologue, taken once for the candidates, the
            # in-wave count and the gate, as one device takes it; the
            # count and the gate read the whole wave's pods
            stats = prologue_stats(table, constraints, "sp")
            if in_wave_skew:
                skew = wave_skew(full, constraints, stats)
            if interpod:
                gate = wave_interpod(full, constraints, stats)

        # Same key on every device; the tie-break jitter globalizes via
        # the (pod_offset, row_offset) hash bases instead (mesh_offsets) —
        # an unsampled wave is byte-identical to the single-device wave.
        cand = candidates(
            table, batch, key, constraints, profile, chunk=chunk, k=k,
            backend=backend, with_affinity=aff,
            window=None if sample_rows is None else (offset, sample_rows),
            row_offset=row_offset, pod_offset=pod_offset, axis_name="sp",
            stats=stats, in_wave_skew=in_wave_skew,
        )

        table, cons, asg = gather_and_finalize(
            table, batch, cand, constraints, skew=skew, interpod=gate
        )
        rows_out = jnp.where(asg.bound, asg.node_row, -1).astype(jnp.int32)
        return table, cons, asg, rows_out

    def _step_cons(table, ints, bools, key, offset, constraints):
        cons_specs = constraint_specs(constraints)
        fn = jax.shard_map(
            _local_step,
            mesh=mesh,
            in_specs=(table_specs(table), P(), P(), P(), P(), cons_specs),
            out_specs=(table_specs(table), cons_specs, _ASG_SPECS, P()),
            check_vma=False,
        )
        return fn(table, ints, bools, key, offset, constraints)

    def _step_plain(table, ints, bools, key, offset):
        fn = jax.shard_map(
            lambda t, i, bl, kk, off: _local_step(t, i, bl, kk, off, None),
            mesh=mesh,
            in_specs=(table_specs(table), P(), P(), P(), P()),
            out_specs=(table_specs(table), None, _ASG_SPECS, P()),
            check_vma=False,
        )
        return fn(table, ints, bools, key, offset)

    if donate:
        # The production coordinator executables: table (and constraint
        # state) buffers are donated, so per-wave bind commits land in
        # each shard's HBM in place.  Donation composes with the
        # shard_map: the inputs arrive sp-sharded, the out_specs keep
        # the outputs sp-sharded, and XLA aliases shard-by-shard.
        step_cons = jax.jit(_step_cons, donate_argnums=(0, 5))
        step_plain = jax.jit(_step_plain, donate_argnums=(0,))
    else:
        # Replay/differential variants (mesh gate tests, bench A/B
        # lanes re-run one table); production passes donate=True.
        step_cons = jax.jit(_step_cons)  # graftlint: disable=undonated-device-update (non-donating replay variant; production passes donate=True)
        step_plain = jax.jit(_step_plain)  # graftlint: disable=undonated-device-update (non-donating replay variant; production passes donate=True)

    def step(table, ints, bools, key, offset, constraints=None):
        if constraints is not None:
            return step_cons(table, ints, bools, key, offset, constraints)
        return step_plain(table, ints, bools, key, offset)

    return step
