"""nondet-to-placement: no nondeterministic value reaches a placement
decision.

The byte-identity contract (mesh == single-device, packed == unpacked,
delta/index == full recompute) holds because every placement input is a
deterministic function of (store state, wave seed).  mesh-purity
enforces one corner of that — axis-derived values in shard_map code —
but every regression so far entered through a DIFFERENT corner:
fold_mesh_key (PR 6), the stratum-width collapse (PR 18), wall-stamp
tie-breaks.  This pass is the general statement, on the flow.py
chassis: taint from any **nondeterminism source**

- wall/monotonic clock reads (``time.time``/``monotonic``/
  ``perf_counter`` and friends, argless ``datetime.now``),
- unseeded module-global RNG (``random.*``, ``np.random.*``,
  ``os.urandom``, ``uuid.uuid4``, ``secrets.*``),
- object identity (``id()``) and thread-timing values (``qsize()``),
- set-iteration order (a for/comprehension target over a provably-set
  value; ``sorted(...)`` launders this one, and only this one),

must not flow — through any chain of local bindings, or through an
intra-repo helper whose RETURN derives from a source — into a
**placement sink** inside ``engine/ parallel/ ops/ snapshot/
tenancy/``:

- ``candidates`` / ``filter_score_topk`` / ``pallas_candidates``
  (candidate selection),
- ``hash_jitter`` / ``seed_of`` (tie-break hashing),
- ``commit_binds`` / ``bind_batch`` / ``_fenced_cas`` /
  ``_fenced_bind_batch`` (store-visible placement writes),
- ``select_preemption`` / ``victim_sort_key`` (victim selection),
- any ``seed=`` / ``key=`` keyword argument anywhere in scope.

One level of helper propagation runs on the sink side too: passing a
tainted value to an intra-repo helper that forwards that parameter
into a sink within its own body is flagged at the call site.

Blessed sources: ``mesh_offsets(...)`` (the sanctioned laundering
point — the hash *base* globalizes, the key does not vary) and seeded
draws on rng objects (``self._rng.random()`` — receiver-qualified
calls never match the module-global patterns by construction).
Timestamps kept for telemetry are fine: taint only matters when it
reaches a sink.  Escapes: ``# graftlint: disable=nondet-to-placement``
with a reason, or a baseline entry.
"""

from __future__ import annotations

import ast

from k8s1m_tpu.lint import flow
from k8s1m_tpu.lint.base import (
    Finding,
    Rule,
    SourceFile,
    call_name,
    dotted_name,
)

SCOPE_DIRS = (
    "k8s1m_tpu/engine/", "k8s1m_tpu/parallel/", "k8s1m_tpu/ops/",
    "k8s1m_tpu/snapshot/", "k8s1m_tpu/tenancy/",
)

_CLOCK_CALLS = {
    "time.time", "time.time_ns", "time.monotonic", "time.monotonic_ns",
    "time.perf_counter", "time.perf_counter_ns",
    "datetime.now", "datetime.datetime.now",
    "datetime.utcnow", "datetime.datetime.utcnow",
}
# Module-global RNG prefixes; the leaf exemptions are the *seeded*
# constructors (random.Random(s), np.random.default_rng(s)).
_RNG_PREFIXES = ("random.", "np.random.", "numpy.random.")
_RNG_EXEMPT_LEAVES = {"Random", "default_rng", "seed"}
_MISC_SOURCES = {"os.urandom", "uuid.uuid4", "uuid.uuid1"}

_SINK_CALLS = {
    "candidates", "filter_score_topk", "pallas_candidates", "hash_jitter",
    "seed_of",
    "commit_binds", "bind_batch", "_fenced_cas", "_fenced_bind_batch",
    "select_preemption", "victim_sort_key",
}
_SINK_KWARGS = {"seed", "key"}
_BLESSED = "mesh_offsets"


def _source_kind(node: ast.AST) -> str | None:
    """The nondeterminism kind a single node introduces, else None."""
    if not isinstance(node, ast.Call):
        return None
    d = dotted_name(node.func)
    if d in _CLOCK_CALLS:
        return f"clock read {d}()"
    if d in _MISC_SOURCES:
        return f"{d}()"
    if d is not None and d.startswith(_RNG_PREFIXES):
        if d.rsplit(".", 1)[-1] not in _RNG_EXEMPT_LEAVES:
            return f"unseeded global RNG {d}()"
    if d is not None and d.startswith("secrets."):
        return f"{d}()"
    if isinstance(node.func, ast.Name) and node.func.id == "id" and (
        node.args
    ):
        return "id() (object identity varies per process)"
    if call_name(node) == "qsize":
        return "qsize() (thread-timing value)"
    return None


def _launders_value(value: ast.AST) -> bool:
    return isinstance(value, ast.Call) and call_name(value) == _BLESSED


def _launders_order(value: ast.AST) -> bool:
    if _launders_value(value):
        return True
    return isinstance(value, ast.Call) and call_name(value) == "sorted"


class NondetToPlacement(Rule):
    id = "nondet-to-placement"

    def check_tree(self, files: list[SourceFile]) -> list[Finding]:
        cg = flow.CallGraph(files)
        memo: dict[str, bool] = {}

        def node_is_source(node: ast.AST) -> bool:
            return _source_kind(node) is not None

        def contains_source(expr: ast.AST) -> bool:
            """Directly nondeterministic, or a call into an intra-repo
            helper whose return value derives from a source."""
            for sub in ast.walk(expr):
                if node_is_source(sub):
                    return True
                if isinstance(sub, ast.Call):
                    callee = cg.target_of(sub)
                    if callee is not None and cg.returns_matching(
                        callee, node_is_source, _memo=memo
                    ):
                        return True
            return False

        # One-level helper propagation on the sink side: which params
        # of a callee flow into a sink inside its own body?
        sink_params_memo: dict[str, frozenset[str]] = {}

        def sink_params(key: str) -> frozenset[str]:
            got = sink_params_memo.get(key)
            if got is not None:
                return got
            sink_params_memo[key] = frozenset()     # cycle guard
            fn = cg.funcs.get(key)
            if fn is None:
                return frozenset()
            params = [a.arg for a in fn.node.args.args
                      if a.arg not in ("self", "cls")]
            hit: set[str] = set()
            for node in flow.own_body(fn.node):
                if not isinstance(node, ast.Call):
                    continue
                for arg in self._sink_args(node):
                    for p in params:
                        if flow.mentions(arg, {p}):
                            hit.add(p)
            out = frozenset(hit)
            sink_params_memo[key] = out
            return out

        out: list[Finding] = []
        for f in files:
            if not f.path.startswith(SCOPE_DIRS):
                continue
            for node in ast.walk(f.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    out.extend(self._check_func(
                        f, node, contains_source, cg, sink_params
                    ))
        out.sort(key=lambda fd: (fd.path, fd.line))
        return out

    # -- per-function analysis -------------------------------------------

    def _sink_args(self, call: ast.Call) -> list[ast.AST]:
        """The arguments of ``call`` that feed a placement decision."""
        name = call_name(call)
        if name in _SINK_CALLS:
            return list(call.args) + [kw.value for kw in call.keywords]
        return [
            kw.value for kw in call.keywords if kw.arg in _SINK_KWARGS
        ]

    def _check_func(
        self, f: SourceFile, fn, contains_source, cg, sink_params
    ) -> list[Finding]:
        out: list[Finding] = []
        bindings = flow.collect_bindings(fn)
        # Value nondeterminism: clocks, RNG, id(), thread timing.
        value_tainted = flow.taint_fixpoint(
            bindings,
            contains_source=contains_source,
            launders=_launders_value,
        )
        # Order nondeterminism: names bound by iterating a set.
        # sorted(...) launders THIS taint (a sorted set is
        # deterministic); it does not launder a clock value.
        order_seeds: set[str] = set()
        for _node, tgt in flow.iterations_over_sets(fn):
            for sub in ast.walk(tgt):
                if isinstance(sub, ast.Name):
                    order_seeds.add(sub.id)
        order_tainted = flow.taint_fixpoint(
            bindings,
            contains_source=lambda e: False,
            launders=_launders_order,
            seeds=order_seeds,
        )

        def taint_of(expr: ast.AST) -> str | None:
            if flow.expr_tainted(expr, value_tainted, contains_source):
                return "a nondeterministic value (clock/RNG/identity)"
            if flow.mentions(expr, order_tainted):
                return "set-iteration order"
            return None

        for node in flow.own_body(fn):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            for arg in self._sink_args(node):
                why = taint_of(arg)
                if why is not None:
                    out.append(self.finding(
                        f, node,
                        f"{why} flows into {name}() — placement "
                        f"decisions must be a deterministic function of "
                        f"(store state, wave seed) or byte-identity "
                        f"dies; derive the input from seeded state, or "
                        f"pragma with the reason",
                    ))
                    break
            else:
                # One-level helper propagation: tainted value handed to
                # a helper that forwards that parameter into a sink.
                key = cg.target_of(node)
                if key is None:
                    continue
                fwd = sink_params(key)
                if not fwd:
                    continue
                callee = cg.funcs[key]
                params = [a.arg for a in callee.node.args.args
                          if a.arg not in ("self", "cls")]
                hit = None
                for i, arg in enumerate(node.args):
                    if i < len(params) and params[i] in fwd:
                        hit = taint_of(arg)
                        if hit is not None:
                            break
                if hit is None:
                    for kw in node.keywords:
                        if kw.arg in fwd:
                            hit = taint_of(kw.value)
                            if hit is not None:
                                break
                if hit is not None:
                    out.append(self.finding(
                        f, node,
                        f"{hit} flows through helper "
                        f"{callee.qual}() into a placement sink — same "
                        f"contract as a direct sink call; seed the "
                        f"input or pragma with the reason",
                    ))
        return out
