"""Packed device snapshot: bit/byte-packed cold node-table columns.

The devicestate ceiling (ROADMAP item 1): every ``NodeTable`` column is a
full ``i32`` plane, so the cold columns — labels, taint effects, row
validity, small-cardinality scalars — cost 4 bytes per entry for values
that need 2 bits.  This module defines the **packed** production layout:

- ``meta`` word  — row validity (bit 0) and all ``taint_slots`` 2-bit
  taint effects (bits ``1+2t``..``2+2t``) in ONE ``i32`` per node; the
  separate ``valid`` bool plane and the ``i32[N, T]`` ``taint_effect``
  plane disappear.
- label fusion   — ``label_key``/``label_val`` fused into one ``i32``
  word per slot (``val << key_bits | key``) while the vocab fits the
  static bit budget; **fail-closed**: a vocab that outgrows the budget
  (the hotfeed vocab-drift shape) falls back to split words via
  ``PackingOverflow`` — never a silently-aliased id.
- narrow planes  — ``zone``/``region``/``pods_alloc``/``taint_id`` drop
  to ``int16``/``int8`` where the TableSpec bounds (or a runtime range
  check, for ``pods_alloc``) permit.

Unpacking happens ON DEVICE inside the chunk slice (``unpack_chunk``):
both the XLA scan path (engine/cycle._slice_table) and the fused Pallas
kernel (ops/pallas_topk) consume the same packed planes, so HBM holds
only the packed layout and the decode cost rides in VMEM-sized tiles.
Decode∘encode is the identity for every in-range column (property-tested
in tests/test_packing.py), which is what makes the packed cycle
byte-identical to the unpacked one — the same bar as the PR 6
mesh↔single-device gate.

The hot columns (cpu/mem allocatable + the request accounting the assume
chain mutates every wave) stay plain ``i32``: they are scatter/donation
targets, and commit_binds' in-place adds must not pay a decode.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from k8s1m_tpu.config import TableSpec
from k8s1m_tpu.snapshot.node_table import (
    REQ_COLUMNS,
    NodeTable,
    NodeTableHost,
)

# Columns the packed layout compresses, in NodeTable naming.  The
# bytes/node evidence in bench.py / sched_bench compares exactly this
# set between layouts (BENCH acceptance: >= 2x reduction).
COLD_COLUMNS = (
    "label_key", "label_val", "taint_id", "taint_effect", "valid",
    "zone", "region", "pods_alloc",
)

# Default label-fusion bit budget: 4096 distinct label keys and 512K
# distinct label values before the fail-closed split.  key + val bits
# must stay <= 31 so the fused word never touches the sign bit.
DEFAULT_KEY_BITS = 12
DEFAULT_VAL_BITS = 19


class PackingOverflow(ValueError):
    """A value no longer fits its packed width (vocab drift, a node with
    > int16 pods).  The coordinator treats this as the fail-closed
    signal: rebuild the device table under a wider layout (split label
    words, or packing off) — never truncate."""

    def __init__(self, field: str, msg: str):
        super().__init__(msg)
        self.field = field


# Every reason device_packing_fallback_total can carry: the
# PackingOverflow field names (pack_columns_np's range checks) plus the
# coordinator's static fallback (meta word too narrow).  The packed
# layout composes with the mesh path since meshpack, so "mesh" is no
# longer a fallback reason — the sharded table holds the packed planes.
FALLBACK_REASONS = (
    "label_key", "label_val", "taint_id", "taint_effect",
    "zone", "region", "pods_alloc", "taint_slots",
)


def _np_dtype(name: str):
    return np.dtype(name)


@dataclasses.dataclass(frozen=True)
class PackingSpec:
    """Static description of the packed layout (jit cache key material).

    ``mode`` is "packed" here by construction — "off" is represented by
    the absence of a spec (``build_packing_spec`` returning None), so a
    plain ``NodeTable`` never carries dead packing state.
    """

    fuse_labels: bool = True
    key_bits: int = DEFAULT_KEY_BITS
    val_bits: int = DEFAULT_VAL_BITS
    taint_slots: int = 8
    zone_dtype: str = "int16"
    region_dtype: str = "int8"
    pods_dtype: str = "int16"
    taint_id_dtype: str = "int16"


def resolve_packing(arg: str | None = None) -> str:
    """Packing mode: "off" (also ``None``) or "packed".  Unknown values
    fail loudly (a typo silently running unpacked would invalidate every
    bytes/node number downstream)."""
    mode = "off" if arg is None else arg
    if mode not in ("off", "packed"):
        raise ValueError(f"packing must be off|packed, got {mode!r}")
    return mode


def build_packing_spec(
    table_spec: TableSpec,
    vocab=None,
    *,
    fuse_labels: bool = True,
    key_bits: int = DEFAULT_KEY_BITS,
    val_bits: int = DEFAULT_VAL_BITS,
) -> PackingSpec | None:
    """The packed layout this TableSpec (and current vocab) supports.

    Fail-closed decisions happen HERE, statically: a taint_slots count
    whose 2-bit effects don't fit the meta word disables packing
    entirely (None); a vocab already past the label bit budget disables
    fusion (split words).  Runtime drift past these choices surfaces as
    ``PackingOverflow`` at pack time and the coordinator rebuilds.
    """
    if 1 + 2 * table_spec.taint_slots > 31:
        return None     # meta word cannot hold the effects: packing off
    if key_bits + val_bits > 31:
        raise ValueError(
            f"key_bits {key_bits} + val_bits {val_bits} > 31 (sign bit)"
        )
    if vocab is not None:
        # len() is the next id to be interned: fusion is safe only while
        # every PRESENT id fits, with the next intern still in range.
        if (len(vocab.label_keys) >= (1 << key_bits)
                or len(vocab.label_values) >= (1 << val_bits)):
            fuse_labels = False
    return PackingSpec(
        fuse_labels=fuse_labels,
        key_bits=key_bits,
        val_bits=val_bits,
        taint_slots=table_spec.taint_slots,
        zone_dtype="int16" if table_spec.max_zones <= (1 << 15) else "int32",
        region_dtype=(
            "int8" if table_spec.max_regions <= (1 << 7)
            else "int16" if table_spec.max_regions <= (1 << 15)
            else "int32"
        ),
        pods_dtype="int16",
        taint_id_dtype=(
            "int16" if table_spec.max_taint_ids <= (1 << 15) else "int32"
        ),
    )


@struct.dataclass
class DomainView:
    """The three full columns topology.prologue needs, decoded once per
    wave (global domain statistics don't belong in a chunk decode)."""

    valid: jax.Array    # bool[N]
    zone: jax.Array     # i32[N]
    region: jax.Array   # i32[N]


@struct.dataclass
class PackedNodeTable:
    """Device-resident packed snapshot (the production layout).

    Field names are chosen so the pieces the rest of the engine touches
    WITHOUT decoding keep their NodeTable names: ``commit_binds`` updates
    cpu_req/mem_req/pods_req via ``.replace`` and the dirty-row scatter
    addresses columns by name — both work on either layout unchanged.

    When ``spec.fuse_labels`` is True, ``label_key`` holds the fused
    ``val << key_bits | key`` words and ``label_val`` is an empty
    ``i32[N, 0]`` plane (zero HBM; keeps the field set static).
    """

    # Hot i32 planes (donation/scatter targets — never packed).
    cpu_alloc: jax.Array    # i32[N]
    mem_alloc: jax.Array    # i32[N]
    cpu_req: jax.Array      # i32[N]
    mem_req: jax.Array      # i32[N]
    pods_req: jax.Array     # i32[N]
    name_id: jax.Array      # i32[N]
    label_num: jax.Array    # i32[N, L] (numeric parse — full range)
    # Packed cold planes.
    meta: jax.Array         # i32[N] valid bit + 2-bit taint effects
    label_key: jax.Array    # i32[N, L] fused words (or plain keys)
    label_val: jax.Array    # i32[N, L] plain values (or [N, 0])
    taint_id: jax.Array     # int16/i32[N, T]
    zone: jax.Array         # int16/i32[N]
    region: jax.Array       # int8/int16/i32[N]
    pods_alloc: jax.Array   # int16[N]
    spec: PackingSpec = struct.field(pytree_node=False)

    @property
    def num_rows(self) -> int:
        return self.meta.shape[0]

    def free(self):
        """(cpu, mem, pods) still unrequested — NodeTable.free() parity
        (pods decodes from the narrow plane)."""
        return (
            self.cpu_alloc - self.cpu_req,
            self.mem_alloc - self.mem_req,
            self.pods_alloc.astype(jnp.int32) - self.pods_req,
        )

    def domain_view(self) -> DomainView:
        return DomainView(
            valid=(self.meta & 1) != 0,
            zone=self.zone.astype(jnp.int32),
            region=self.region.astype(jnp.int32),
        )


def is_packed(table) -> bool:
    return isinstance(table, PackedNodeTable)


# ---- host-side packing -----------------------------------------------------


def _check_range(field: str, arr: np.ndarray, hi: int) -> None:
    if arr.size and int(arr.max(initial=0)) >= hi:
        raise PackingOverflow(
            field,
            f"{field} id {int(arr.max())} >= packed bound {hi} "
            "(vocab drift past the static bit budget; fail closed and "
            "rebuild under a wider layout)",
        )


def pack_meta_np(valid: np.ndarray, taint_effect: np.ndarray) -> np.ndarray:
    """(valid bool[R], taint_effect i32[R, T]) -> meta i32[R].  Same
    fail-closed contract as every other packed column: an effect value
    past the 2-bit budget raises, never aliases (the current EFFECT_*
    range 0-3 is exactly full — the next constant someone adds must
    widen the layout, not silently bind to tainted nodes)."""
    _check_range("taint_effect", taint_effect, 4)
    meta = valid.astype(np.int32)
    for t in range(taint_effect.shape[1]):
        meta = meta | ((taint_effect[:, t].astype(np.int32) & 3) << (1 + 2 * t))
    return meta


def pack_columns_np(cols: dict, pspec: PackingSpec) -> dict:
    """Pack a dict of host (numpy) NodeTable columns into the packed
    column dict (PackedNodeTable field names).  ``cols`` must hold every
    NodeTable column name present in the output's source set; partial
    dicts (dirty-row deltas) pack whatever subset their keys imply.

    Range checks are the fail-closed gate: ids past the static budget
    raise PackingOverflow instead of aliasing.
    """
    out: dict = {}
    for name in ("cpu_alloc", "mem_alloc", "cpu_req", "mem_req",
                 "pods_req", "name_id", "label_num"):
        if name in cols:
            out[name] = cols[name]
    if "valid" in cols:
        out["meta"] = pack_meta_np(cols["valid"], cols["taint_effect"])
    if "label_key" in cols:
        lk = cols["label_key"]
        lv = cols["label_val"]
        if pspec.fuse_labels:
            _check_range("label_key", lk, 1 << pspec.key_bits)
            _check_range("label_val", lv, 1 << pspec.val_bits)
            out["label_key"] = (
                (lv.astype(np.int32) << pspec.key_bits) | lk.astype(np.int32)
            )
            out["label_val"] = np.zeros((lk.shape[0], 0), np.int32)
        else:
            out["label_key"] = lk
            out["label_val"] = lv
    if "taint_id" in cols:
        dt = _np_dtype(pspec.taint_id_dtype)
        _check_range("taint_id", cols["taint_id"], 1 << (8 * dt.itemsize - 1))
        out["taint_id"] = cols["taint_id"].astype(dt)
    for name, dtype in (
        ("zone", pspec.zone_dtype),
        ("region", pspec.region_dtype),
        ("pods_alloc", pspec.pods_dtype),
    ):
        if name in cols:
            dt = _np_dtype(dtype)
            _check_range(name, cols[name], 1 << (8 * dt.itemsize - 1))
            out[name] = cols[name].astype(dt)
    return out


def pack_table_host(
    host: NodeTableHost, pspec: PackingSpec, sharding=None
) -> PackedNodeTable:
    """Pack the full host mirror into a device-resident PackedNodeTable
    (the packed-mode counterpart of NodeTableHost.to_device).

    ``sharding`` is the sharded entry point (meshpack): pass the
    coordinator's ``NamedSharding(mesh, P("sp"))`` and every packed
    plane — meta word, fused label words, the int16/int8 scalars —
    lands with its row axis sharded over ``sp``, exactly like the plain
    layout; the sharded cycle decodes inside the shard-local chunk
    slice (engine/cycle._slice_table)."""
    cols = {
        name: getattr(host, name)
        for name in (
            "valid", "cpu_alloc", "mem_alloc", "pods_alloc",
            "cpu_req", "mem_req", "pods_req",
            "label_key", "label_val", "label_num",
            "taint_id", "taint_effect", "zone", "region", "name_id",
        )
    }
    for name in REQ_COLUMNS:
        # Copies the device owns (NodeTableHost.to_device says why).
        cols[name] = cols[name].copy()
    packed = pack_columns_np(cols, pspec)

    def put(x):
        return jax.device_put(jnp.asarray(x), sharding) if sharding else jnp.asarray(x)

    return PackedNodeTable(spec=pspec, **{k: put(v) for k, v in packed.items()})


def pack_table_auto(host: NodeTableHost, table_spec: TableSpec, sharding=None):
    """Bench/tool convenience: pack the host mirror under the layout
    this TableSpec + current vocab support, falling back LOUDLY to the
    plain layout when packing cannot apply (taint_slots too wide for
    the meta word).  The coordinator has its own richer path
    (_table_to_device: metrics, mid-run widening); tools that just need
    "a packed table or the closest thing" use this — and must report
    the layout they actually got (is_packed), not the one requested,
    or the committed bytes/node evidence lies."""
    pspec = build_packing_spec(table_spec, host.vocab)
    if pspec is None:
        import logging

        logging.getLogger(__name__).warning(
            "packing requested but taint_slots=%d does not fit the meta "
            "word; building the UNPACKED layout", table_spec.taint_slots,
        )
        return host.to_device(sharding)
    return pack_table_host(host, pspec, sharding)


def pack_row_delta(
    host: NodeTableHost, rows: np.ndarray, pspec: PackingSpec, columns
) -> dict:
    """Packed dirty-row delta for ``scatter_rows``: the packed-layout
    equivalent of ``{c: getattr(host, c)[rows] for c in columns}``.
    ``columns`` is CAP_COLUMNS or ALL_COLUMNS (NodeTable naming); the
    returned dict uses PackedNodeTable field names.  Layout-agnostic on
    the device side by construction: the same delta dict feeds the
    single-device donating scatter and the mesh's sharding-pinned
    donating scatter (parallel/sharded_cycle.make_sharded_scatter) —
    the delta rides replicated and the scatter lands it into the
    sp-sharded packed planes in place."""
    cols = {c: getattr(host, c)[rows] for c in columns}
    return pack_columns_np(cols, pspec)


# ---- device-side unpacking -------------------------------------------------


def unpack_chunk(chunk: PackedNodeTable) -> NodeTable:
    """Decode a packed chunk (or any packed row slice) into the plain
    NodeTable layout the filter/score plugins consume.  Pure jnp — runs
    inside the jitted chunk scan, so the decode lives in the same fused
    pass as the plugins and nothing i32-wide ever lands back in HBM."""
    p = chunk.spec
    meta = chunk.meta
    taint_effect = jnp.stack(
        [(meta >> (1 + 2 * t)) & 3 for t in range(p.taint_slots)], axis=1
    )
    if p.fuse_labels:
        label_key = chunk.label_key & ((1 << p.key_bits) - 1)
        label_val = chunk.label_key >> p.key_bits
    else:
        label_key = chunk.label_key
        label_val = chunk.label_val
    return NodeTable(
        valid=(meta & 1) != 0,
        cpu_alloc=chunk.cpu_alloc,
        mem_alloc=chunk.mem_alloc,
        pods_alloc=chunk.pods_alloc.astype(jnp.int32),
        cpu_req=chunk.cpu_req,
        mem_req=chunk.mem_req,
        pods_req=chunk.pods_req,
        label_key=label_key,
        label_val=label_val,
        label_num=chunk.label_num,
        taint_id=chunk.taint_id.astype(jnp.int32),
        taint_effect=taint_effect,
        zone=chunk.zone.astype(jnp.int32),
        region=chunk.region.astype(jnp.int32),
        name_id=chunk.name_id,
    )


def mask_rows_packed(table: PackedNodeTable, row_mask) -> PackedNodeTable:
    """engine.cycle.mask_rows for the packed layout: rows outside the
    mask become infeasible on both backends (valid bit cleared for the
    XLA filter chain, pods_alloc zeroed for the fused kernel's row-
    validity convention) without touching commit state."""
    return table.replace(
        meta=jnp.where(row_mask, table.meta, table.meta & ~1),
        pods_alloc=jnp.where(
            row_mask, table.pods_alloc,
            jnp.zeros((), table.pods_alloc.dtype),
        ),
    )


# ---- donation evidence -----------------------------------------------------

# The donated hot planes every layout shares (i32[N] scatter/commit
# targets).  XLA's input-output aliasing pairs donated buffers by
# shape/dtype, NOT by field name, so an output column can legitimately
# land in a DIFFERENT donated input's buffer — the in-place signal is
# overlap of the pointer sets, never pointer identity of one column.
_HOT_PLANES = (
    "cpu_alloc", "mem_alloc", "cpu_req", "mem_req", "pods_req", "name_id",
)


def _plane_ptrs(arr):
    """Per-shard buffer pointers of one plane.  A table sharded over the
    mesh's sp axis holds one buffer per (addressable) device, and XLA
    aliases donated buffers shard-by-shard — so the probe must collect
    EVERY shard's pointer, not call the single-device accessor (which
    raises on multi-shard arrays)."""
    shards = getattr(arr, "addressable_shards", None)
    if shards:
        return [s.data.unsafe_buffer_pointer() for s in shards]
    return [arr.unsafe_buffer_pointer()]


def donation_probe(table) -> frozenset:
    """Buffer pointers of the table's donated hot planes — every shard
    of every plane, so the probe covers single-device AND mesh-sharded
    tables — read BEFORE a donating dispatch (evidence probe; reading a
    pointer syncs on the buffer — keep it out of timed windows)."""
    return frozenset(
        p for c in _HOT_PLANES for p in _plane_ptrs(getattr(table, c))
    )


def donation_inplace(table, probe: frozenset) -> bool:
    """True when the post-step table reuses ANY probed input buffer (on
    any shard) — the runtime honored the donation in place; False means
    every plane was copied (e.g. another live reference pinned the
    inputs)."""
    return any(
        p in probe
        for c in _HOT_PLANES for p in _plane_ptrs(getattr(table, c))
    )


# ---- HBM accounting --------------------------------------------------------


def _leaf_bytes(arr) -> int:
    return int(np.prod(arr.shape)) * np.dtype(arr.dtype).itemsize


def hbm_bytes(table) -> int:
    """Total device bytes of a NodeTable or PackedNodeTable."""
    return sum(_leaf_bytes(leaf) for leaf in jax.tree.leaves(table))


_PACKED_COLD = (
    "label_key", "label_val", "taint_id", "meta", "zone", "region",
    "pods_alloc",
)


def cold_bytes_per_node(table) -> float:
    """Bytes/node of the COLD_COLUMNS set under the table's layout —
    the number the >=2x packing acceptance gate compares."""
    names = _PACKED_COLD if is_packed(table) else COLD_COLUMNS
    n = table.num_rows
    return sum(_leaf_bytes(getattr(table, c)) for c in names) / max(n, 1)


def unpacked_cold_bytes(table_spec: TableSpec) -> float:
    """COLD_COLUMNS bytes/node under the plain i32 layout — the fixed
    denominator every packed run's reduction ratio is taken against."""
    l, t = table_spec.label_slots, table_spec.taint_slots
    #      label_key+label_val  taint_id+effect  valid  zone+region+pods
    return 8 * l + 8 * t + 1 + 4 + 4 + 4


def bytes_report(table, table_spec: TableSpec | None = None) -> dict:
    """Layout evidence for bench JSON: layout name, total and cold
    bytes/node, and (given the TableSpec) the reduction ratio against
    the unpacked cold baseline — the >=2x acceptance number."""
    n = max(table.num_rows, 1)
    out = {
        "layout": "packed" if is_packed(table) else "unpacked",
        "hbm_bytes_per_node": round(hbm_bytes(table) / n, 2),
        "cold_bytes_per_node": round(cold_bytes_per_node(table), 3),
    }
    if table_spec is not None:
        out["cold_bytes_reduction"] = round(
            unpacked_cold_bytes(table_spec) / max(out["cold_bytes_per_node"], 1e-9),
            3,
        )
    return out
