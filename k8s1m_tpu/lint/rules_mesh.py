"""mesh-purity: the byte-identity contract, enforced before any wave runs.

PR 6 made mesh↔single-device BYTE-identity the invariant PR authors
must not break (MIGRATION.md "Sharded execution"): the sharded cycle's
tie-break hash runs over GLOBAL (pod row, node row) coordinates with one
shared per-wave seed, and the host-side merge replays query keys in dp
order — so the differential gate can demand bit-equality, not
statistics.  The gate only runs in the differential suite though; this
pass checks the purity rules that make it hold on every file, at lint
time:

1. **no per-shard PRNG folding** — ``jax.random.fold_in`` is banned in
   shard_map-mapped code (``parallel/``, ``ops/``, ``plugins/``).
   Folding shard coordinates into the key is the exact regression PR 6
   removed (the old ``fold_mesh_key``): it decorrelates tie-breaks
   across shards and demotes the mesh to statistical equivalence.
2. **axis-derived values stay out of tie-break hashes** — values
   data-flowing from ``lax.axis_index``/``lax.psum`` must not reach
   ``hash_jitter`` / ``pack_hashed`` / ``pack`` / ``seed_of`` arguments
   or any ``key=``/``seed=`` keyword, except via the blessed
   ``mesh_offsets`` helper (whose whole point is that the hash *base*
   globalizes, the key does not vary).  Tracked per function through
   local assignments; a tuple-unpack from ``mesh_offsets(...)`` is the
   sanctioned laundering point.
3. **top-k tie-breaks reference global offsets** — inside ``parallel/``,
   every ``candidates``/``filter_score_topk``/``pallas_candidates``
   call must pass BOTH ``row_offset=`` and ``pod_offset=``; omitting
   either silently falls back to shard-local coordinates and
   byte-identity dies at the first cross-shard tie.
4. **no set iteration in encode/merge paths** — in
   ``snapshot/hotfeed*.py`` and ``snapshot/pod_encoding.py`` (the paths
   whose output ``merge_packed`` must rebuild byte-identically),
   iterating a Python ``set``/``frozenset`` injects hash-seed ordering
   into encoded bytes.  ``sorted(...)`` over a set is fine; dict
   iteration is insertion-ordered (deterministic) and exempt.

Every rule has the standard escape hatches: a ``# graftlint: disable=
mesh-purity`` pragma with a reason, or a baseline entry.
"""

from __future__ import annotations

import ast

from k8s1m_tpu.lint import flow
from k8s1m_tpu.lint.base import (
    Finding,
    Rule,
    SourceFile,
    call_name as _call_name,
)

MESH_DIRS = ("k8s1m_tpu/parallel/", "k8s1m_tpu/ops/", "k8s1m_tpu/plugins/")
TOPK_DIR = "k8s1m_tpu/parallel/"
MERGE_PATHS = ("k8s1m_tpu/snapshot/pod_encoding.py",)

_TAINT_SOURCES = {"axis_index", "psum"}
_HASH_SINKS = {"hash_jitter", "pack_hashed", "pack", "seed_of"}
_TOPK_CALLS = {"candidates", "filter_score_topk", "pallas_candidates"}
_BLESSED = "mesh_offsets"


def _contains_taint_source(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and _call_name(sub) in _TAINT_SOURCES:
            return True
    return False


def _launders(value: ast.AST) -> bool:
    """``mesh_offsets(...)`` is the sanctioned laundering point."""
    return isinstance(value, ast.Call) and _call_name(value) == _BLESSED


# The binding/taint/set walking lives on the flow.py chassis now; the
# aliases keep this module reading the way the docstring describes it.
_own_body = flow.own_body
_mentions = flow.mentions


def _is_merge_path(path: str) -> bool:
    base = path.rsplit("/", 1)[-1]
    if path.startswith("k8s1m_tpu/snapshot/") and "hotfeed" in base:
        return True
    return path in MERGE_PATHS


class MeshPurity(Rule):
    id = "mesh-purity"

    def check_file(self, f: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        if f.path.startswith(MESH_DIRS):
            out.extend(self._check_mesh(f))
        if _is_merge_path(f.path):
            out.extend(self._check_merge(f))
        out.sort(key=lambda fd: fd.line)
        return out

    # -- shard_map purity (rules 1-3) ------------------------------------

    def _check_mesh(self, f: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(f.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if node.name == _BLESSED:
                    continue
                out.extend(self._check_mesh_func(f, node))
        return out

    def _check_mesh_func(self, f: SourceFile, fn) -> list[Finding]:
        out: list[Finding] = []
        # Bindings in source order, closed to a fixpoint so chains like
        # `idx = axis_index(...); off = idx * 128` taint through any
        # number of intermediates (and loops) — flow.py layer 1, which
        # this rule's private engine became.
        tainted = flow.taint_fixpoint(
            flow.collect_bindings(fn),
            contains_source=_contains_taint_source,
            launders=_launders,
        )
        for node in _own_body(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if name == "fold_in":
                out.append(self.finding(
                    f, node,
                    "per-shard PRNG key folding in shard_map-mapped code "
                    "breaks the mesh byte-identity contract; derive "
                    "tie-breaks from mesh_offsets + hash_jitter over "
                    "global coordinates instead (the PR 6 regression)",
                ))
                continue
            if name in _HASH_SINKS:
                args = list(node.args) + [kw.value for kw in node.keywords]
            else:
                args = [
                    kw.value for kw in node.keywords
                    if kw.arg in ("key", "seed")
                ]
                if not args:
                    continue
            for a in args:
                if _contains_taint_source(a) or _mentions(a, tainted):
                    out.append(self.finding(
                        f, node,
                        f"axis_index/psum-derived value flows into "
                        f"{name}() — shard-varying tie-break/PRNG input "
                        f"breaks byte identity; route global coordinates "
                        f"through mesh_offsets",
                    ))
                    break
        if f.path.startswith(TOPK_DIR):
            for node in _own_body(fn):
                if (
                    isinstance(node, ast.Call)
                    and _call_name(node) in _TOPK_CALLS
                ):
                    kws = {kw.arg for kw in node.keywords}
                    missing = {"row_offset", "pod_offset"} - kws
                    if missing:
                        out.append(self.finding(
                            f, node,
                            f"{_call_name(node)}() without "
                            f"{'/'.join(sorted(missing))} — top-k "
                            f"tie-breaks must hash GLOBAL coordinates or "
                            f"the sharded cycle is only statistically "
                            f"equivalent to the single-device cycle",
                        ))
        return out

    # -- encode/merge determinism (rule 4) -------------------------------

    def _check_merge(self, f: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(f.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for sub, _target in flow.iterations_over_sets(node):
                out.append(self.finding(
                    f, sub,
                    "iteration over a set in an encode/merge path "
                    "feeding merge_packed byte-identity — set "
                    "order is hash-seed-dependent; iterate "
                    "sorted(...) or a list/dict instead",
                ))
        return out
