"""Host-side pod feature compiler: PodInfo -> fixed-shape PodBatch tensors.

The reference scatters each pod's raw protobuf to 256 shards over a relay
tree (reference cmd/dist-scheduler/relay.go:23-178); here a *batch* of pods
is compiled to padded int tensors once and broadcast to the mesh as data.

Two host-side precomputations keep the device hot loop free of string-ish
inner dimensions:

- **Tolerations** are evaluated on the host against every *distinct* taint
  triple the cluster has ever seen (Vocab.taints) and shipped as a
  ``tolerated[B, max_taint_ids]`` bitmask; the device filter is a gather +
  reduce over taint slots, never a (toleration x taint) comparison.
- **Query keys**: every label key referenced by this batch's selectors is
  collected into a per-batch table ``qkey[Q]``.  The device resolves each
  node's (found, value, numeric) for those Q keys once per node chunk, and
  all selector expressions index into that [Q, N] resolution by position —
  the per-node label-slot scan happens once, not once per expression.

Padding conventions (relied on by the kernels):
- an affinity term/expr slot is live iff term_valid/expr_valid;
- expr value sets are padded with NONE_ID, which never equals a live label
  value id (values never seen on any node also encode to NONE_ID, which is
  exactly upstream's "cannot match" behavior).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from k8s1m_tpu.config import (
    DEFAULT_SCHEDULER,
    EFFECT_NONE,
    NO_NUMERIC,
    NONE_ID,
    PodSpec,
    SEL_OP_GT,
    SEL_OP_LT,
    SPREAD_DO_NOT_SCHEDULE,
    TOL_OP_EXISTS,
    TOPO_HOSTNAME,
    TableSpec,
)
from k8s1m_tpu.semantics import pod_tolerates_taint
from k8s1m_tpu.snapshot.interning import Vocab, numeric_of
from k8s1m_tpu.snapshot.node_table import Taint


@dataclasses.dataclass
class Toleration:
    key: str = ""                  # "" tolerates every key (with op Exists)
    op: int = TOL_OP_EXISTS
    value: str = ""
    effect: int = EFFECT_NONE      # EFFECT_NONE tolerates every effect


@dataclasses.dataclass
class SelectorRequirement:
    key: str
    op: int                        # SEL_OP_*
    values: list[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class NodeSelectorTerm:
    match_expressions: list[SelectorRequirement] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class PreferredSchedulingTerm:
    weight: int
    term: NodeSelectorTerm


@dataclasses.dataclass
class SpreadConstraintRef:
    """A pod's reference to an interned topologySpreadConstraint slot."""

    cid: int                       # constraint slot in ConstraintState
    topo: int                      # TOPO_* key
    max_skew: int = 1
    mode: int = SPREAD_DO_NOT_SCHEDULE
    self_match: bool = True        # pod matches the constraint's own selector


@dataclasses.dataclass
class AffinityTermRef:
    """A pod's reference to an interned (anti)affinity term slot."""

    tid: int                       # term slot in ConstraintState
    topo: int = TOPO_HOSTNAME
    required: bool = False
    anti: bool = False
    weight: int = 1                # for preferred terms (1-100)
    self_match: bool = False       # bound pod will itself match this term's selector


@dataclasses.dataclass
class PodInfo:
    """Host-side description of one pending pod."""

    name: str
    namespace: str = "default"
    cpu_milli: int = 100
    mem_kib: int = 200 << 10       # 200 MiB
    scheduler_name: str = DEFAULT_SCHEDULER
    node_name: str | None = None
    node_selector: dict[str, str] = dataclasses.field(default_factory=dict)
    tolerations: list[Toleration] = dataclasses.field(default_factory=list)
    required_terms: list[NodeSelectorTerm] = dataclasses.field(default_factory=list)
    preferred_terms: list[PreferredSchedulingTerm] = dataclasses.field(default_factory=list)
    spread_refs: list[SpreadConstraintRef] = dataclasses.field(default_factory=list)
    affinity_refs: list[AffinityTermRef] = dataclasses.field(default_factory=list)
    # (slot, topo) pairs of constraints/terms whose selector matches this
    # pod's labels — computed host-side (ConstraintTracker.*_matches) and
    # used by the commit scatter to keep domain counts current.
    spread_incs: list[tuple[int, int]] = dataclasses.field(default_factory=list)
    ipa_incs: list[tuple[int, int]] = dataclasses.field(default_factory=list)
    labels: dict[str, str] = dataclasses.field(default_factory=dict)
    # spec.priority (PriorityClass value).  Host-side only: consumed by
    # admission shedding and tenancy preemption, never encoded into the
    # device batch.  A nonzero priority makes the stored object
    # non-canonical (the native fast lane is for the plain-pod
    # firehose; priority-bearing pods take the full decode path).
    priority: int = 0
    # spec.topologySpreadConstraints as written (label selectors and all):
    # what encode_pod puts on the wire.  spread_refs above is the same
    # thing compiled against one coordinator's tracker, and only decode
    # fills it.
    topology_spread: list[dict] = dataclasses.field(default_factory=list)

    @property
    def key(self) -> str:
        return f"{self.namespace}/{self.name}"


@struct.dataclass
class PodBatch:
    """Fixed-shape encoded pod batch (B pods, padded)."""

    valid: jax.Array         # bool[B]
    cpu: jax.Array           # i32[B] milliCPU requested
    mem: jax.Array           # i32[B] KiB requested
    node_name_id: jax.Array  # i32[B] spec.nodeName (NONE_ID = unset)
    # Host-evaluated toleration results per distinct taint triple.
    tolerated: jax.Array     # bool[B, max_taint_ids]
    # Per-batch query-key table: global label-key ids; expressions below
    # store *indices* into this table.
    qkey: jax.Array          # i32[Q]
    # spec.nodeSelector — ANDed exact-match pairs.
    sel_valid: jax.Array     # bool[B, S]   (S = aff_exprs slots reused)
    sel_qidx: jax.Array      # i32[B, S] index into qkey
    sel_val: jax.Array       # i32[B, S] required label value id
    # requiredDuringSchedulingIgnoredDuringExecution — OR of terms, AND of exprs.
    req_term_valid: jax.Array  # bool[B, T]
    req_expr_valid: jax.Array  # bool[B, T, E]
    req_qidx: jax.Array        # i32[B, T, E]
    req_op: jax.Array          # i32[B, T, E]
    req_vals: jax.Array        # i32[B, T, E, V]
    req_num: jax.Array         # i32[B, T, E] parsed value for Gt/Lt
    # preferredDuringScheduling terms (single-term each, weighted 1-100).
    pref_term_valid: jax.Array  # bool[B, P]
    pref_weight: jax.Array      # i32[B, P]
    pref_expr_valid: jax.Array  # bool[B, P, E]
    pref_qidx: jax.Array        # i32[B, P, E]
    pref_op: jax.Array          # i32[B, P, E]
    pref_vals: jax.Array        # i32[B, P, E, V]
    pref_num: jax.Array         # i32[B, P, E]
    # Topology-spread constraint references (slots in ConstraintState).
    spread_valid: jax.Array     # bool[B, SR]
    spread_cid: jax.Array       # i32[B, SR]
    spread_topo: jax.Array      # i32[B, SR]
    spread_max_skew: jax.Array  # i32[B, SR]
    spread_mode: jax.Array      # i32[B, SR]
    spread_self: jax.Array      # bool[B, SR]
    # Inter-pod (anti)affinity term references.
    ipa_valid: jax.Array        # bool[B, AR]
    ipa_tid: jax.Array          # i32[B, AR]
    ipa_topo: jax.Array         # i32[B, AR]
    ipa_required: jax.Array     # bool[B, AR]
    ipa_anti: jax.Array         # bool[B, AR]
    ipa_weight: jax.Array       # i32[B, AR]
    ipa_self: jax.Array         # bool[B, AR]
    # Constraints/terms whose selector matches this pod (commit increments).
    sinc_valid: jax.Array       # bool[B, SI]
    sinc_cid: jax.Array         # i32[B, SI]
    sinc_topo: jax.Array        # i32[B, SI]
    iinc_valid: jax.Array       # bool[B, AI]
    iinc_tid: jax.Array         # i32[B, AI]
    iinc_topo: jax.Array        # i32[B, AI]

    @property
    def batch(self) -> int:
        return self.valid.shape[0]


# SHAPE-ONLY by construction — deliberately NOT keyed by vocab
# generation: every shape below derives from PodSpec/TableSpec static
# bounds alone (batch, term/expr/value slot counts, max_taint_ids).  No
# interned id ever flows into a shape — ids are array *contents*, sized
# by the static bounds — so vocab growth can never make a cached spec
# stale.  Anything content-dependent (the hotfeed template cache) must
# key on Vocab.generation() instead; see snapshot/hotfeed.py.
@functools.lru_cache(maxsize=16)
def batch_field_specs(
    s: PodSpec, t: TableSpec
) -> tuple[tuple[str, bool, tuple[int, ...]], ...]:
    """(name, is_bool, shape) for every PodBatch leaf, in field order.

    Single source of truth for the host-side allocation (encode), the
    packed host->device transfer (pack/unpack), and PodBatch itself —
    the packed layout cannot drift from the dataclass.
    """
    b = s.batch
    shapes: dict[str, tuple[bool, tuple[int, ...]]] = dict(
        valid=(True, (b,)), cpu=(False, (b,)), mem=(False, (b,)),
        node_name_id=(False, (b,)),
        tolerated=(True, (b, t.max_taint_ids)),
        qkey=(False, (s.query_keys,)),
        sel_valid=(True, (b, s.aff_exprs)),
        sel_qidx=(False, (b, s.aff_exprs)),
        sel_val=(False, (b, s.aff_exprs)),
        req_term_valid=(True, (b, s.aff_terms)),
        req_expr_valid=(True, (b, s.aff_terms, s.aff_exprs)),
        req_qidx=(False, (b, s.aff_terms, s.aff_exprs)),
        req_op=(False, (b, s.aff_terms, s.aff_exprs)),
        req_vals=(False, (b, s.aff_terms, s.aff_exprs, s.aff_values)),
        req_num=(False, (b, s.aff_terms, s.aff_exprs)),
        pref_term_valid=(True, (b, s.pref_terms)),
        pref_weight=(False, (b, s.pref_terms)),
        pref_expr_valid=(True, (b, s.pref_terms, s.aff_exprs)),
        pref_qidx=(False, (b, s.pref_terms, s.aff_exprs)),
        pref_op=(False, (b, s.pref_terms, s.aff_exprs)),
        pref_vals=(False, (b, s.pref_terms, s.aff_exprs, s.aff_values)),
        pref_num=(False, (b, s.pref_terms, s.aff_exprs)),
        spread_valid=(True, (b, s.spread_refs)),
        spread_cid=(False, (b, s.spread_refs)),
        spread_topo=(False, (b, s.spread_refs)),
        spread_max_skew=(False, (b, s.spread_refs)),
        spread_mode=(False, (b, s.spread_refs)),
        spread_self=(True, (b, s.spread_refs)),
        ipa_valid=(True, (b, s.affinity_refs)),
        ipa_tid=(False, (b, s.affinity_refs)),
        ipa_topo=(False, (b, s.affinity_refs)),
        ipa_required=(True, (b, s.affinity_refs)),
        ipa_anti=(True, (b, s.affinity_refs)),
        ipa_weight=(False, (b, s.affinity_refs)),
        ipa_self=(True, (b, s.affinity_refs)),
        sinc_valid=(True, (b, s.spread_incs)),
        sinc_cid=(False, (b, s.spread_incs)),
        sinc_topo=(False, (b, s.spread_incs)),
        iinc_valid=(True, (b, s.ipa_incs)),
        iinc_tid=(False, (b, s.ipa_incs)),
        iinc_topo=(False, (b, s.ipa_incs)),
    )
    names = [f.name for f in dataclasses.fields(PodBatch)]
    assert set(names) == set(shapes), set(names) ^ set(shapes)
    return tuple((n, *shapes[n]) for n in names)


# Field groups for sparse transfer.  A group is included in the packed
# buffers only when some pod in the wave actually sets it (detected from
# its sentinel array); excluded groups materialize as zeros inside the
# jitted step.  A wave of plain pods — the 1M-KWOK steady state — then
# uploads ~70 KB instead of ~6.5 MB per wave over the host->device link.
_GROUP_FIELDS: dict[str, tuple[str, ...]] = {
    "tol": ("tolerated",),
    "sel": ("sel_valid", "sel_qidx", "sel_val"),
    "req": ("req_term_valid", "req_expr_valid", "req_qidx", "req_op",
            "req_vals", "req_num"),
    "pref": ("pref_term_valid", "pref_weight", "pref_expr_valid",
             "pref_qidx", "pref_op", "pref_vals", "pref_num"),
    "spread": ("spread_valid", "spread_cid", "spread_topo",
               "spread_max_skew", "spread_mode", "spread_self"),
    "ipa": ("ipa_valid", "ipa_tid", "ipa_topo", "ipa_required", "ipa_anti",
            "ipa_weight", "ipa_self"),
    "sinc": ("sinc_valid", "sinc_cid", "sinc_topo"),
    "iinc": ("iinc_valid", "iinc_tid", "iinc_topo"),
    "qkey": ("qkey",),
}
_GROUP_SENTINEL: dict[str, str] = {
    "tol": "tolerated", "sel": "sel_valid", "req": "req_term_valid",
    "pref": "pref_term_valid", "spread": "spread_valid",
    "ipa": "ipa_valid", "sinc": "sinc_valid", "iinc": "iinc_valid",
}
_GROUP_OF: dict[str, str] = {
    f: g for g, fs in _GROUP_FIELDS.items() for f in fs
}
ALL_GROUPS: frozenset = frozenset(_GROUP_FIELDS)
# The groups of a nodeSelector and of nodeAffinity terms: a wave that
# carries one of them carries the query-key table, and its step is built
# with the affinity stage (engine/cycle.has_selectors).
SELECTOR_GROUPS: frozenset = frozenset({"sel", "req", "pref"})


@dataclasses.dataclass
class PackedPodBatch:
    """A PodBatch as two host buffers (all-int32, all-bool) holding only
    the field groups this wave uses, plus the full host field dict.

    Every array argument of a jitted call is its own host->device
    transfer with its own dispatch cost; two small buffers instead of
    ~40 leaves is what makes the per-cycle upload cheap.
    ``unpack_pod_batch`` reverses the packing inside the jitted step
    (``groups`` must be passed through as a static argument — each
    distinct group set is its own compiled executable).
    """

    ints: np.ndarray    # i32[sum of included int field sizes]
    bools: np.ndarray   # bool[sum of included bool field sizes]
    fields: dict        # name -> host np array (full set, zero-filled)
    spec: PodSpec
    table_spec: TableSpec
    groups: frozenset   # included group names
    # Vocab.feed_generation() the encode ran against, stamped by the
    # hotfeed encoder (snapshot/hotfeed.py) — the batch stamp includes
    # node_names because the node_name_id column bakes its lookups.
    # None = vocab-independent (the plain fast lane touches no interned
    # namespace) or a legacy encode; the double-buffered feed compares
    # this against the live feed_generation before handing a pre-staged
    # batch to a wave.
    vocab_gen: int | None = None

    @property
    def batch(self) -> int:
        return self.spec.batch


def unpack_pod_batch(
    ints,
    bools,
    spec: PodSpec,
    table_spec: TableSpec,
    groups: frozenset = ALL_GROUPS,
) -> PodBatch:
    """Rebuild a PodBatch from the packed buffers (jit-traceable).
    Fields of groups not in ``groups`` become zeros."""
    out = {}
    io = bo = 0
    for name, is_bool, shape in batch_field_specs(spec, table_spec):
        group = _GROUP_OF.get(name)
        if group is not None and group not in groups:
            # NUMPY zeros on purpose: under jit these lift to the same
            # XLA constants jnp.zeros would, but they stay statically
            # visible to the filter plugins' _statically_empty check
            # (a jnp.zeros inside a trace is a tracer) — which is what
            # lets absent groups skip at trace time instead of XLA
            # constant-folding a [B, S, N] chain for minutes on CPU.
            out[name] = np.zeros(shape, np.bool_ if is_bool else np.int32)
            continue
        n = math.prod(shape)
        if is_bool:
            out[name] = bools[bo : bo + n].reshape(shape)
            bo += n
        else:
            out[name] = ints[io : io + n].reshape(shape)
            io += n
    return PodBatch(**out)


class PodBatchHost:
    """Compiles a list of PodInfo into one PodBatch."""

    def __init__(self, spec: PodSpec, table_spec: TableSpec, vocab: Vocab) -> None:
        self.spec = spec
        self.table_spec = table_spec
        self.vocab = vocab

    def encode_packed(self, pods: list[PodInfo]) -> PackedPodBatch:
        """Encode into the sparse two-buffer packed form (the
        coordinator's hot path)."""
        specs = batch_field_specs(self.spec, self.table_spec)
        out = {
            name: np.zeros(shape, np.bool_ if is_bool else np.int32)
            for name, is_bool, shape in specs
        }
        self._fill(out, pods)
        groups = {
            g for g, sentinel in _GROUP_SENTINEL.items() if out[sentinel].any()
        }
        if groups & SELECTOR_GROUPS:
            groups.add("qkey")
        groups = frozenset(groups)
        int_parts, bool_parts = [], []
        for name, is_bool, _shape in specs:
            g = _GROUP_OF.get(name)
            if g is not None and g not in groups:
                continue
            (bool_parts if is_bool else int_parts).append(out[name].ravel())
        ints = (
            np.concatenate(int_parts) if int_parts else np.zeros(0, np.int32)
        )
        bools = (
            np.concatenate(bool_parts) if bool_parts else np.zeros(0, np.bool_)
        )
        return PackedPodBatch(
            ints, bools, out, self.spec, self.table_spec, groups
        )

    def encode_packed_plain(self, cpu, mem) -> PackedPodBatch:
        """Packed encode of a wave of *plain* pods (no selectors,
        tolerations, affinity, or constraint refs) given just their
        cpu/mem columns — fully vectorized, no per-pod Python.

        This is the native-intake fast lane (store/native.py poll_pods):
        canonical label-less pods arrive from the watch as int columns,
        and a wave of them needs exactly two array writes here.  The
        result is identical to encode_packed on the equivalent PodInfos:
        a plain pod tolerates nothing (``tolerated`` stays False, like
        pod_tolerates_taint on an empty toleration list) and sets no
        selector groups.
        """
        specs = batch_field_specs(self.spec, self.table_spec)
        out = {
            name: np.zeros(shape, np.bool_ if is_bool else np.int32)
            for name, is_bool, shape in specs
        }
        n = len(cpu)
        if n > self.spec.batch:
            raise ValueError(f"{n} pods > batch {self.spec.batch}")
        out["valid"][:n] = True
        out["cpu"][:n] = cpu
        out["mem"][:n] = mem
        groups: frozenset = frozenset()
        int_parts, bool_parts = [], []
        for name, is_bool, _shape in specs:
            if _GROUP_OF.get(name) is not None:
                continue
            (bool_parts if is_bool else int_parts).append(out[name].ravel())
        return PackedPodBatch(
            np.concatenate(int_parts), np.concatenate(bool_parts), out,
            self.spec, self.table_spec, groups,
        )

    def encode(self, pods: list[PodInfo]) -> PodBatch:
        specs = batch_field_specs(self.spec, self.table_spec)
        out = {
            name: np.zeros(shape, np.bool_ if is_bool else np.int32)
            for name, is_bool, shape in specs
        }
        self._fill(out, pods)
        return PodBatch(**{k: jnp.asarray(a) for k, a in out.items()})

    def _fill(self, out: dict, pods: list[PodInfo]) -> None:
        s = self.spec
        b = s.batch
        if len(pods) > b:
            raise ValueError(f"{len(pods)} pods > batch {b}")
        v = self.vocab

        # Per-batch query-key table.  Index 0 is reserved for "key NONE"
        # (qkey[0] == NONE_ID, never found on any node) so padded
        # expression slots resolve harmlessly.
        qidx_of: dict[str, int] = {}

        def qidx(key: str) -> int:
            i = qidx_of.get(key)
            if i is None:
                i = len(qidx_of) + 1
                if i >= s.query_keys:
                    raise ValueError(
                        f"batch references >{s.query_keys - 1} distinct selector "
                        "keys; grow PodSpec.query_keys"
                    )
                qidx_of[key] = i
                out["qkey"][i] = v.label_keys.lookup(key)
            return i

        # Scalar columns vectorized (one numpy fancy-write per column, not
        # one per pod): at 10K+ binds/s the per-pod `arr[i] = x` writes in
        # this loop were a measurable slice of the whole pipeline.
        n = len(pods)
        out["valid"][:n] = True
        out["cpu"][:n] = np.fromiter((p.cpu_milli for p in pods), np.int32, n)
        out["mem"][:n] = np.fromiter((p.mem_kib for p in pods), np.int32, n)
        taints = list(v.taints.items())

        for i, pod in enumerate(pods):
            # spec.nodeName naming a node we've never seen must match
            # nothing (not "unset"), hence the -1 sentinel.
            if pod.node_name is not None:
                nid = v.node_names.lookup(pod.node_name)
                out["node_name_id"][i] = nid if nid != NONE_ID else -1
            self._fill_pod(out, i, pod, qidx, taints)

    def _fill_pod(self, out: dict, i: int, pod: PodInfo, qidx, taints) -> None:
        """Encode one pod's structural features into row ``i`` of ``out``.

        Shared between the batch loop above and the hotfeed template
        encoder (snapshot/hotfeed.py encodes each distinct shape ONCE
        through this body, then replays the cached rows with vectorized
        writes) — one source of truth is what makes cached encodes
        byte-identical to uncached by construction."""
        s = self.spec
        v = self.vocab

        # Evaluate this pod's tolerations against every distinct taint
        # triple (upstream: v1.Toleration.ToleratesTaint per node taint).
        # A pod with no tolerations tolerates nothing — skip the
        # per-triple scan instead of evaluating an empty list per triple.
        if taints and pod.tolerations:
            for tid, (tkey, tval, teffect) in taints:
                out["tolerated"][i, tid] = pod_tolerates_taint(
                    pod.tolerations, Taint(tkey, tval, teffect)
                )

        if not (
            pod.node_selector or pod.required_terms or pod.preferred_terms
            or pod.spread_refs or pod.affinity_refs or pod.spread_incs
            or pod.ipa_incs
        ):
            return    # plain pod: everything below stays zero

        if len(pod.node_selector) > s.aff_exprs:
            raise ValueError(f"pod {pod.key}: nodeSelector too large")
        for j, (k, val) in enumerate(sorted(pod.node_selector.items())):
            out["sel_valid"][i, j] = True
            out["sel_qidx"][i, j] = qidx(k)
            out["sel_val"][i, j] = v.label_values.lookup(val)

        if len(pod.required_terms) > s.aff_terms:
            raise ValueError(f"pod {pod.key}: too many required affinity terms")
        for j, term in enumerate(pod.required_terms):
            out["req_term_valid"][i, j] = True
            self._encode_exprs(
                qidx, i, j, term.match_expressions, out["req_expr_valid"],
                out["req_qidx"], out["req_op"], out["req_vals"], out["req_num"],
            )
        if len(pod.preferred_terms) > s.pref_terms:
            raise ValueError(f"pod {pod.key}: too many preferred terms")
        for j, pt in enumerate(pod.preferred_terms):
            out["pref_term_valid"][i, j] = True
            out["pref_weight"][i, j] = pt.weight
            self._encode_exprs(
                qidx, i, j, pt.term.match_expressions, out["pref_expr_valid"],
                out["pref_qidx"], out["pref_op"], out["pref_vals"], out["pref_num"],
            )

        if len(pod.spread_refs) > s.spread_refs:
            raise ValueError(f"pod {pod.key}: too many spread constraints")
        for j, ref in enumerate(pod.spread_refs):
            out["spread_valid"][i, j] = True
            out["spread_cid"][i, j] = ref.cid
            out["spread_topo"][i, j] = ref.topo
            out["spread_max_skew"][i, j] = ref.max_skew
            out["spread_mode"][i, j] = ref.mode
            out["spread_self"][i, j] = ref.self_match
        if len(pod.affinity_refs) > s.affinity_refs:
            raise ValueError(f"pod {pod.key}: too many affinity terms")
        for j, ref in enumerate(pod.affinity_refs):
            out["ipa_valid"][i, j] = True
            out["ipa_tid"][i, j] = ref.tid
            out["ipa_topo"][i, j] = ref.topo
            out["ipa_required"][i, j] = ref.required
            out["ipa_anti"][i, j] = ref.anti
            out["ipa_weight"][i, j] = ref.weight
            out["ipa_self"][i, j] = ref.self_match

        if len(pod.spread_incs) > s.spread_incs:
            raise ValueError(f"pod {pod.key}: too many spread increments")
        for j, (cid, topo) in enumerate(pod.spread_incs):
            out["sinc_valid"][i, j] = True
            out["sinc_cid"][i, j] = cid
            out["sinc_topo"][i, j] = topo
        if len(pod.ipa_incs) > s.ipa_incs:
            raise ValueError(f"pod {pod.key}: too many affinity increments")
        for j, (tid, topo) in enumerate(pod.ipa_incs):
            out["iinc_valid"][i, j] = True
            out["iinc_tid"][i, j] = tid
            out["iinc_topo"][i, j] = topo

    def _encode_exprs(self, qidx, i, j, exprs, expr_valid, qidx_arr, op, vals, num):
        s = self.spec
        v = self.vocab
        if len(exprs) > s.aff_exprs:
            raise ValueError("too many match expressions in a term")
        for e, req in enumerate(exprs):
            expr_valid[i, j, e] = True
            qidx_arr[i, j, e] = qidx(req.key)
            op[i, j, e] = req.op
            if req.op in (SEL_OP_GT, SEL_OP_LT):
                # Missing/unparseable operand -> unsatisfiable (NO_NUMERIC).
                num[i, j, e] = (
                    numeric_of(req.values[0]) if req.values else NO_NUMERIC
                )
            else:
                if len(req.values) > s.aff_values:
                    raise ValueError("too many values in a match expression")
                for k, val in enumerate(req.values):
                    vals[i, j, e, k] = v.label_values.lookup(val)
