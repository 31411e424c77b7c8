"""undonated-device-update: jitted table updates must donate their buffers.

The devicestate contract (ISSUE 10): every per-wave mutation of the
device node table / constraint tables — commit_binds' request-column
adds, the dirty-row churn scatter, constraint-count corrections — flows
through a jitted function that RETURNS the updated table.  Without
``donate_argnums`` each such call is copy-on-write: XLA materializes a
second full table in HBM per wave, which at 1M rows is both the memory
ceiling and a per-wave bandwidth tax.  This rule keeps the donation
funnel airtight statically: inside the production device-update modules
(engine/, snapshot/, control/, parallel/), a ``jax.jit(...)`` call whose
wrapped callable (transitively, within the file) reaches one of the
table-update primitives must pass ``donate_argnums``/``donate_argnames``
— or carry the usual pragma with a reason.

Legitimate non-donating variants exist and are pragma'd where they
live: replay/differential surfaces (tests re-run one table; donation
would delete it).  The mesh executables are NOT among them since
meshpack — out_shardings pinning and donation compose (XLA aliases
shard-by-shard), so the production sharded step/scatter/adjust all
donate.  The pragma forces each remaining exception to say WHY, which
is the point.

Resolution is name-based and file-local (the graftlint house style —
see rules_fence.py): the wrapped callable is resolved through direct
names, named lambdas, aliases, and ``functools.partial``; a function is
"table-updating" when its body (or anything it calls, to a file-local
fixpoint) calls one of UPDATE_PRIMITIVES.
"""

from __future__ import annotations

import ast

from k8s1m_tpu.lint.base import (
    Finding,
    Rule,
    SourceFile,
    call_name,
    dotted_name,
)

SCOPE_PREFIXES = (
    "k8s1m_tpu/engine/",
    "k8s1m_tpu/snapshot/",
    "k8s1m_tpu/control/",
    "k8s1m_tpu/parallel/",
)

# Callables that produce an UPDATED NodeTable / constraint table.  The
# cross-module links (finalize_batch -> commit_binds etc.) are encoded
# here by name so a file that imports and jits them is still covered.
UPDATE_PRIMITIVES = {
    "commit_binds",
    "scatter_rows",
    "apply_delta",
    "commit_constraint_binds",
    "adjust_constraints_impl",
    "finalize_batch",
    "_schedule_batch_impl",
}

DONATE_KWARGS = {"donate_argnums", "donate_argnames"}


def _called_names(node: ast.AST) -> set[str]:
    out: set[str] = set()
    for c in ast.walk(node):
        if isinstance(c, ast.Call):
            n = call_name(c)
            if n is not None:
                out.add(n)
    return out


def _callable_slots(call: ast.Call) -> list[ast.expr]:
    """The argument positions that can hold a wrapped callable: first
    positional, or jit's keyword spelling (``jax.jit(fun=impl)``).
    Shared by alias resolution and jit-site detection so the slot rule
    can never desynchronize between them."""
    return list(call.args[:1]) + [
        kw.value for kw in call.keywords if kw.arg == "fun"
    ]


class UndonatedDeviceUpdate(Rule):
    id = "undonated-device-update"

    def check_file(self, f: SourceFile) -> list[Finding]:
        if not f.path.startswith(SCOPE_PREFIXES):
            return []
        # name -> names it calls (defs, named lambdas, plain aliases).
        calls_of: dict[str, set[str]] = {}
        for node in ast.walk(f.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                calls_of.setdefault(node.name, set()).update(
                    _called_names(node)
                )
            elif isinstance(node, ast.Assign):
                names = [
                    t.id for t in node.targets if isinstance(t, ast.Name)
                ]
                if not names:
                    continue
                if isinstance(node.value, ast.Lambda):
                    got = _called_names(node.value)
                elif isinstance(node.value, ast.Name):
                    got = {node.value.id}      # alias: fn = impl
                elif isinstance(node.value, ast.Call):
                    # Wrapper aliasing: fn = jax.shard_map(impl, ...)
                    # / step = jax.jit(impl, ...) — the bound name
                    # reaches the wrapped callable, so a later jit of
                    # the wrapper is still covered.  Only the callable
                    # SLOT aliases (first positional, or jit's ``fun=``
                    # spelling) — treating every argument as the
                    # wrapped callable would make plain-data uses of an
                    # updater name (`make_runner(cfg, scatter_rows)`)
                    # false-positive.
                    got = set()
                    for a in _callable_slots(node.value):
                        if isinstance(a, ast.Name):
                            got.add(a.id)
                        elif isinstance(a, ast.Lambda):
                            got |= _called_names(a)
                    if not got:
                        continue
                else:
                    continue
                for n in names:
                    calls_of.setdefault(n, set()).update(got)
        # File-local fixpoint over "reaches an update primitive".
        updaters = set(UPDATE_PRIMITIVES)
        changed = True
        while changed:
            changed = False
            for name, calls in calls_of.items():
                if name not in updaters and calls & updaters:
                    updaters.add(name)
                    changed = True

        def wraps_updater(arg: ast.AST) -> bool:
            if isinstance(arg, ast.Name):
                return arg.id in updaters
            if isinstance(arg, ast.Lambda):
                return bool(_called_names(arg) & updaters)
            if isinstance(arg, ast.Call) and call_name(arg) == "partial":
                return any(
                    isinstance(a, ast.Name) and a.id in updaters
                    for a in arg.args
                )
            return False

        MSG = (
            "jitted function returns an updated device table but "
            "does not donate its input buffers (donate_argnums): "
            "every wave pays a full copy-on-write table in HBM.  "
            "Donate (out_shardings pinning composes with donation), "
            "or pragma with the reason this call site must keep its "
            "inputs alive (replay surface)"
        )

        def jit_decorator(dec) -> tuple[bool, bool]:
            """(is_jit, donates) for a decorator node — the @jax.jit,
            @jax.jit(...), and @functools.partial(jax.jit, ...) house
            spellings all count; a bare decorator can never donate."""
            if isinstance(dec, (ast.Name, ast.Attribute)):
                return dotted_name(dec) in ("jax.jit", "jit"), False
            if isinstance(dec, ast.Call):
                donates = any(
                    kw.arg in DONATE_KWARGS for kw in dec.keywords
                )
                if dotted_name(dec.func) in ("jax.jit", "jit"):
                    return True, donates
                if call_name(dec) == "partial" and any(
                    isinstance(a, (ast.Name, ast.Attribute))
                    and dotted_name(a) in ("jax.jit", "jit")
                    for a in dec.args
                ):
                    return True, donates
            return False, False

        out: list[Finding] = []
        for node in ast.walk(f.tree):
            if isinstance(node, ast.Call):
                if dotted_name(node.func) not in ("jax.jit", "jit"):
                    continue
                if any(kw.arg in DONATE_KWARGS for kw in node.keywords):
                    continue
                if not any(wraps_updater(a) for a in _callable_slots(node)):
                    continue
                out.append(self.finding(f, node, MSG))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # Decorator spellings: @jax.jit / @functools.partial(
                # jax.jit, ...) over a table-updating def is the same
                # copy-on-write hole as the call form.
                if node.name not in updaters:
                    continue
                for dec in node.decorator_list:
                    is_jit, donates = jit_decorator(dec)
                    if is_jit and not donates:
                        out.append(self.finding(f, dec, MSG))
        return out
