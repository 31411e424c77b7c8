"""graftlint driver: run the passes, apply pragmas + baseline, report.

    python -m k8s1m_tpu.lint                 # lint the repo, honor baseline
    python -m k8s1m_tpu.lint --check-baseline  # also fail on stale entries
    python -m k8s1m_tpu.lint path/to/file.py   # lint specific files
    python -m k8s1m_tpu.lint --write-baseline  # regenerate (keeps comments out)
    python -m k8s1m_tpu.lint --json            # machine-readable report
    python -m k8s1m_tpu.lint --jobs 4          # per-file rules across 4
                                               # processes (byte-identical
                                               # to --jobs 1)
    python -m k8s1m_tpu.lint --write-lockgraph # refresh artifacts/lockgraph.json

Exit codes: 0 clean (every finding baselined/pragma'd), 1 new findings
(or stale baseline entries under ``--check-baseline``, or stale pragmas
under ``--strict-pragmas``), 2 usage error.

Stale pragmas: a ``# graftlint: disable=<rule>`` on a line where that
rule no longer fires is reported as a warning (the pragma is dead weight
and, worse, would silently swallow a FUTURE finding on that line);
``--strict-pragmas`` promotes the warning to a failure.  The summary
counts pragma suppressions per rule so coverage stays visible as the
rule count grows.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import os
import sys
import time

from k8s1m_tpu.lint import baseline as baseline_mod
from k8s1m_tpu.lint.base import (
    Finding,
    Rule,
    SourceFile,
    iter_py_files,
    load_file,
    suppressed,
)
from k8s1m_tpu.lint.lockgraph import (
    LockModel,
    LockOrderCycle,
    cycle_findings,
    sanctioned,
    write_artifact,
)
from k8s1m_tpu.lint.rules_blocking import BlockingUnderLock
from k8s1m_tpu.lint.rules_clock import NoWallClock
from k8s1m_tpu.lint.rules_donate import UndonatedDeviceUpdate
from k8s1m_tpu.lint.rules_except import BroadExcept
from k8s1m_tpu.lint.rules_fallback import FallbackAccounting
from k8s1m_tpu.lint.rules_fence import FencedStoreWrite
from k8s1m_tpu.lint.rules_guards import StaticGuardedBy
from k8s1m_tpu.lint.rules_hotfeed import HotfeedNoPerPodPython
from k8s1m_tpu.lint.rules_jax import HotPathHostSync, TraceTimeBranch
from k8s1m_tpu.lint.rules_mesh import MeshPurity
from k8s1m_tpu.lint.rules_metrics import MetricsRegistry
from k8s1m_tpu.lint.rules_nondet import NondetToPlacement
from k8s1m_tpu.lint.rules_retry import RetryThroughPolicy
from k8s1m_tpu.lint.rules_trace import TraceLazyEmit
from k8s1m_tpu.lint.rules_watchbuf import BoundedWatchBuffer
from k8s1m_tpu.lint.rules_wiretier import SharedFrameNoPerWatchEncode

ALL_RULES: tuple[type[Rule], ...] = (
    HotPathHostSync,
    NoWallClock,
    RetryThroughPolicy,
    MetricsRegistry,
    BroadExcept,
    TraceTimeBranch,
    HotfeedNoPerPodPython,
    StaticGuardedBy,
    LockOrderCycle,
    MeshPurity,
    FencedStoreWrite,
    UndonatedDeviceUpdate,
    TraceLazyEmit,
    BoundedWatchBuffer,
    NondetToPlacement,
    BlockingUnderLock,
    FallbackAccounting,
    SharedFrameNoPerWatchEncode,
)

# --json reports carry this so consumers can gate on shape changes.
SCHEMA_VERSION = 1

# The linted slice of the repo (everything else is docs/artifacts).
DEFAULT_SUBDIRS = ("k8s1m_tpu", "tests")

LOCKGRAPH_ARTIFACT = os.path.join("artifacts", "lockgraph.json")


def repo_root() -> str:
    """The directory holding the k8s1m_tpu package (= repo root)."""
    import k8s1m_tpu

    return os.path.dirname(os.path.dirname(os.path.abspath(
        k8s1m_tpu.__file__
    )))


@dataclasses.dataclass
class LintResult:
    findings: list[Finding]                    # after pragma suppression
    new: list[Finding]                         # not covered by baseline
    stale: list[tuple[str, str, str]]          # baseline entries unmatched
    files: int
    # (path, line, rule): declared pragmas that suppressed nothing.
    stale_pragmas: list[tuple[str, int, str]] = dataclasses.field(
        default_factory=list
    )
    # rule -> number of findings a pragma suppressed.
    pragma_counts: dict[str, int] = dataclasses.field(default_factory=dict)
    # rule -> wall seconds spent in its hooks (summed across workers
    # under --jobs, so it reads as cost, not as a latency breakdown).
    rule_times: dict[str, float] = dataclasses.field(default_factory=dict)


def default_jobs() -> int:
    return min(4, os.cpu_count() or 1)


def _per_file_worker(payload):
    """Pool worker: run the per-file rules over one chunk of files.

    Findings come back tagged (rule position, file position) so the
    parent can replay them in exactly the order a sequential run
    produces them — byte-identical output is the contract ``--jobs``
    is gated on in tests.
    """
    root, chunk, rule_classes = payload
    instances = [cls() for cls in rule_classes]
    out: list[tuple[int, int, Finding]] = []
    times: dict[str, float] = {}
    for fidx, rel in chunk:
        f = load_file(root, rel)
        if f is None:
            continue
        for ridx, rule in enumerate(instances):
            t0 = time.perf_counter()
            fds = rule.check_file(f)
            times[rule.id] = (
                times.get(rule.id, 0.0) + time.perf_counter() - t0
            )
            for fd in fds:
                out.append((ridx, fidx, fd))
    return out, times


def run_lint(
    root: str | None = None,
    paths: list[str] | None = None,
    baseline_path: str | None = None,
    rules: tuple[type[Rule], ...] = ALL_RULES,
    jobs: int = 1,
) -> LintResult:
    """Run every pass; returns findings split against the baseline.

    ``baseline_path=None`` means "use <root>/lint_baseline.txt if it
    exists"; pass ``baseline_path=""`` to ignore any baseline.
    ``jobs>1`` fans the per-file rules out over a process pool (the
    cross-file rules stay a single pass in the parent — they need the
    whole tree anyway); output is byte-identical to ``jobs=1``.
    """
    root = root or repo_root()
    rels = paths if paths else iter_py_files(root, DEFAULT_SUBDIRS)
    files: list[SourceFile] = []
    for rel in rels:
        f = load_file(root, rel)
        if f is not None:
            files.append(f)
    # Cross-file rules (metrics registry, lock graph) need the WHOLE
    # tree for context even when only a subset is being reported — a
    # changed-only run must not think a dashboard prefix lost its
    # metric because the declaring file didn't change.  Findings are
    # still reported only for the requested subset.
    if paths:
        linted_set = {f.path for f in files}
        tree_files = list(files)
        seen = set(linted_set)
        for rel in iter_py_files(root, DEFAULT_SUBDIRS):
            f = load_file(root, rel)
            if f is not None and f.path not in seen:
                seen.add(f.path)
                tree_files.append(f)
    else:
        linted_set = None
        tree_files = files

    instances = [cls() for cls in rules]
    known_rules = {r.id for r in instances}
    findings: list[Finding] = []
    # (path, line, rule) pragmas that matched a finding — the live set.
    used_pragmas: set[tuple[str, int, str]] = set()
    pragma_counts: dict[str, int] = {}
    by_path = {f.path: f for f in tree_files}

    def consider(src: SourceFile | None, fd: Finding) -> None:
        if src is not None and suppressed(src, fd):
            if linted_set is None or fd.path in linted_set:
                used_pragmas.add((fd.path, fd.line, fd.rule))
                pragma_counts[fd.rule] = pragma_counts.get(fd.rule, 0) + 1
            return
        if linted_set is None or fd.path in linted_set:
            findings.append(fd)

    rule_times: dict[str, float] = {r.id: 0.0 for r in instances}
    per_file = [
        r for r in instances
        if type(r).check_file is not Rule.check_file
    ]
    per_file_results: dict[int, list[tuple[int, Finding]]] = {}
    if jobs > 1 and per_file and len(files) > 1:
        nchunks = min(jobs, len(files))
        chunks: list[list[tuple[int, str]]] = [[] for _ in range(nchunks)]
        for fidx, f in enumerate(files):
            chunks[fidx % nchunks].append((fidx, f.path))
        rule_classes = tuple(type(r) for r in per_file)
        with concurrent.futures.ProcessPoolExecutor(
            max_workers=nchunks
        ) as pool:
            for out, times in pool.map(
                _per_file_worker,
                [(root, chunk, rule_classes) for chunk in chunks],
            ):
                for rid, t in times.items():
                    rule_times[rid] += t
                for ridx, fidx, fd in out:
                    per_file_results.setdefault(ridx, []).append((fidx, fd))
        for acc in per_file_results.values():
            acc.sort(key=lambda t: t[0])        # stable: file order, then
    else:                                       # the rule's own order
        for ridx, rule in enumerate(per_file):
            acc = per_file_results.setdefault(ridx, [])
            for fidx, f in enumerate(files):
                t0 = time.perf_counter()
                fds = rule.check_file(f)
                rule_times[rule.id] += time.perf_counter() - t0
                for fd in fds:
                    acc.append((fidx, fd))

    per_file_pos = {id(r): i for i, r in enumerate(per_file)}
    for rule in instances:
        ridx = per_file_pos.get(id(rule))
        if ridx is not None:
            for _fidx, fd in per_file_results.get(ridx, ()):
                consider(by_path.get(fd.path), fd)
        t0 = time.perf_counter()
        tree_fds = rule.check_tree(tree_files)
        rule_times[rule.id] += time.perf_counter() - t0
        for fd in tree_fds:
            consider(by_path.get(fd.path), fd)
    findings.sort(key=lambda fd: (fd.path, fd.line, fd.rule))

    # Pragma staleness is judged against the FULL registry: an id not
    # in ALL_RULES is a typo (always stale); an id whose rule simply
    # did not run this invocation (rules= subset) is not evaluated —
    # otherwise run_lint(rules=(OneRule,)) would report every other
    # rule's live pragma as stale.
    all_ids = {cls.id for cls in ALL_RULES}
    stale_pragmas: list[tuple[str, int, str]] = []
    for f in files:
        for line, rule_ids in sorted(f.pragmas.items()):
            for rid in sorted(rule_ids):
                if rid not in all_ids:
                    stale_pragmas.append((f.path, line, rid))
                elif rid in known_rules and (
                    (f.path, line, rid) not in used_pragmas
                ):
                    stale_pragmas.append((f.path, line, rid))

    entries: list[tuple[str, str, str]] = []
    if baseline_path != "":
        bp = baseline_path or os.path.join(
            root, baseline_mod.BASELINE_NAME
        )
        if os.path.exists(bp):
            with open(bp, encoding="utf-8") as fh:
                entries = baseline_mod.parse_baseline(fh.read())
        if paths:
            # Explicit file subset: entries for files outside it were
            # never given a chance to match — reporting them stale
            # would fail every single-file invocation.
            linted = {f.path for f in files}
            entries = [e for e in entries if e[0] in linted]
    new, stale = baseline_mod.split_findings(findings, entries)
    return LintResult(
        findings, new, stale, len(files), stale_pragmas, pragma_counts,
        rule_times,
    )


def _json_report(result: LintResult, check_baseline: bool) -> dict:
    """Machine-readable report: rule -> count -> files (the CI shape)."""
    rules: dict[str, dict] = {}
    for fd in result.new:
        r = rules.setdefault(fd.rule, {"count": 0, "files": []})
        r["count"] += 1
        if fd.path not in r["files"]:
            r["files"].append(fd.path)
    return {
        "schema_version": SCHEMA_VERSION,
        "files": result.files,
        "new": [
            {"path": fd.path, "line": fd.line, "rule": fd.rule,
             "message": fd.message}
            for fd in result.new
        ],
        "rules": {k: rules[k] for k in sorted(rules)},
        "baselined": len(result.findings) - len(result.new),
        "stale_baseline": (
            [list(e) for e in result.stale] if check_baseline else None
        ),
        "stale_pragmas": [
            {"path": p, "line": ln, "rule": r}
            for p, ln, r in result.stale_pragmas
        ],
        "pragma_counts": {
            k: result.pragma_counts[k] for k in sorted(result.pragma_counts)
        },
        "rule_times": {
            k: round(result.rule_times[k], 4)
            for k in sorted(result.rule_times)
        },
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m k8s1m_tpu.lint",
        description="graftlint: project-native static analysis",
    )
    ap.add_argument("paths", nargs="*",
                    help="repo-relative .py files (default: whole tree)")
    ap.add_argument("--root", default=None,
                    help="repo root (default: derived from the package)")
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default: <root>/lint_baseline.txt)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, baseline ignored")
    ap.add_argument("--check-baseline", action="store_true",
                    help="also fail on stale baseline entries (drift gate)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="print current findings in baseline format")
    ap.add_argument("--strict-pragmas", action="store_true",
                    help="fail on pragmas whose rule no longer fires there")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report (rule -> count -> files)")
    ap.add_argument("--jobs", type=int, default=None, metavar="N",
                    help="run per-file rules across N processes "
                         "(default min(4, cpus); output is byte-identical "
                         "to --jobs 1)")
    ap.add_argument("--write-lockgraph", nargs="?", const=LOCKGRAPH_ARTIFACT,
                    default=None, metavar="PATH",
                    help="write the lock acquisition-order graph artifact "
                         f"(default {LOCKGRAPH_ARTIFACT}) and exit")
    args = ap.parse_args(argv)

    root = args.root or repo_root()
    if args.write_lockgraph is not None:
        rels = iter_py_files(root, DEFAULT_SUBDIRS)
        files = [f for f in (load_file(root, r) for r in rels)
                 if f is not None]
        model = LockModel(files)
        out = os.path.join(root, args.write_lockgraph)
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        write_artifact(model, out, files)
        # Pragma'd cycles (the documented escape hatch) are recorded in
        # the artifact as sanctioned and do not fail the write.
        bad = sum(
            1 for _cyc, fds in cycle_findings(model, files)
            if not sanctioned(files, fds)
        )
        ncyc = len(model.cycles())
        print(f"lockgraph: {len(model.edges)} edge(s), {ncyc} cycle(s) "
              f"({bad} unsanctioned) -> {out}")
        return 1 if bad else 0

    result = run_lint(
        root=args.root,
        paths=args.paths or None,
        baseline_path="" if args.no_baseline else args.baseline,
        jobs=args.jobs if args.jobs is not None else default_jobs(),
    )
    if args.write_baseline:
        print("# graftlint baseline — one 'path|rule|fingerprint' per "
              "line; comment WHY above each entry")
        for fd in result.findings:
            print(baseline_mod.format_entry(fd))
        return 0

    if args.json:
        print(json.dumps(
            _json_report(result, args.check_baseline), indent=2
        ))
    else:
        for fd in result.new:
            print(fd.render())
        if args.check_baseline:
            for path, rule, fp in result.stale:
                print(f"{path} {rule} STALE baseline entry (fixed? remove "
                      f"it): {fp!r}")
        known = {cls.id for cls in ALL_RULES}
        for path, line, rid in result.stale_pragmas:
            why = (
                "suppresses nothing" if rid in known
                else "names an unknown rule id (typo?)"
            )
            print(f"{path}:{line} stale-pragma '{rid}' {why} "
                  f"(remove it{'' if args.strict_pragmas else ' — warning'})")
    failed = (
        bool(result.new)
        or (args.check_baseline and bool(result.stale))
        or (args.strict_pragmas and bool(result.stale_pragmas))
    )
    if not args.json:
        grandfathered = len(result.findings) - len(result.new)
        coverage = ", ".join(
            f"{k}={v}" for k, v in sorted(result.pragma_counts.items())
        )
        print(
            f"graftlint: {result.files} files, {len(result.new)} new "
            f"finding(s), {grandfathered} baselined"
            + (f", {len(result.stale)} stale" if args.check_baseline else "")
            + f", {len(result.stale_pragmas)} stale pragma(s)"
            + (f"; pragma coverage: {coverage}" if coverage else "")
        )
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
