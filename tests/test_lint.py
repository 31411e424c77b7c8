"""graftlint: fixture true-positives, pragma twins, baseline drift gate.

Layers:

1. Per-rule fixtures — ``tests/lint_fixtures/`` mirrors the real tree's
   layout (the hot-path and wall-clock rules are dir-scoped); each
   ``bad_*.py`` violates exactly ONE rule and each ``ok_*.py`` is the
   same violation behind a ``# graftlint: disable=`` pragma.
2. The baseline machinery — parse/format round trip, counted matching,
   both drift directions.
3. The tier-1 gate — the REAL repo tree lints clean against the
   committed ``lint_baseline.txt`` with zero new findings and zero
   stale entries, and the ``python -m k8s1m_tpu.lint`` CLI agrees.
   Every future PR inherits this check: a new violation fails here
   until it is fixed, pragma'd with a reason, or consciously baselined.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from k8s1m_tpu.lint.base import Finding
from k8s1m_tpu.lint.baseline import (
    format_entry,
    parse_baseline,
    split_findings,
)
from k8s1m_tpu.lint.cli import ALL_RULES, repo_root, run_lint

FIXTURES = os.path.join(os.path.dirname(__file__), "lint_fixtures")

EXPECTED = {
    "hot-path-host-sync": "k8s1m_tpu/engine/bad_hot_path.py",
    "trace-time-branch": "k8s1m_tpu/engine/bad_trace_branch.py",
    "no-wall-clock": "k8s1m_tpu/faultline/bad_wall_clock.py",
    "retry-through-policy": "k8s1m_tpu/tools/bad_retry.py",
    "broad-except": "k8s1m_tpu/store/bad_broad_except.py",
    "metrics-registry": "k8s1m_tpu/obs/bad_metrics.py",
    "hotfeed-no-per-pod-python": "k8s1m_tpu/snapshot/bad_hotfeed.py",
    "static-guarded-by": "k8s1m_tpu/control/bad_guards.py",
    "lock-order-cycle": "k8s1m_tpu/control/bad_lockorder.py",
    "mesh-purity": "k8s1m_tpu/parallel/bad_mesh.py",
    "fenced-store-write": "k8s1m_tpu/control/bad_fenced_write.py",
    "undonated-device-update": "k8s1m_tpu/engine/bad_donate.py",
    "trace-lazy-emit": "k8s1m_tpu/control/bad_trace_emit.py",
    "bounded-watch-buffer": "k8s1m_tpu/store/bad_watchbuf.py",
    "nondet-to-placement": "k8s1m_tpu/engine/bad_nondet.py",
    "blocking-under-lock": "k8s1m_tpu/control/bad_blocking_lock.py",
    "fallback-counts-or-raises": "k8s1m_tpu/store/bad_fallback.py",
    "shared-frame-no-per-watch-encode": "k8s1m_tpu/store/bad_shared_frame.py",
}


@pytest.fixture(scope="module")
def fixture_result():
    return run_lint(root=FIXTURES, baseline_path="")


def test_every_rule_has_a_true_positive_fixture(fixture_result):
    got = {(f.rule, f.path) for f in fixture_result.findings}
    assert got == {(rule, path) for rule, path in EXPECTED.items()}
    # Exactly one finding per rule: each fixture violates ONE rule.
    assert len(fixture_result.findings) == len(EXPECTED)


def test_rule_ids_cover_expectations():
    assert {r.id for r in ALL_RULES} == set(EXPECTED)


def test_donate_rule_covers_decorator_spellings():
    """undonated-device-update must catch the decorator forms too —
    @jax.jit and @functools.partial(jax.jit, ...) are the house idiom
    (ops/pallas_topk._call), and a bare decorator can never donate."""
    import ast
    import textwrap

    from k8s1m_tpu.lint.base import SourceFile
    from k8s1m_tpu.lint.rules_donate import UndonatedDeviceUpdate

    src = textwrap.dedent('''
        import functools
        import jax
        from k8s1m_tpu.snapshot.node_table import scatter_rows

        @jax.jit
        def bare(table, rows, delta):
            return scatter_rows(table, rows, delta)

        @functools.partial(jax.jit, static_argnames=("k",))
        def parted(table, rows, delta, k):
            return scatter_rows(table, rows, delta)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def donated(table, rows, delta):
            return scatter_rows(table, rows, delta)
    ''')
    f = SourceFile(
        path="k8s1m_tpu/engine/synthetic.py", abspath="synthetic.py",
        tree=ast.parse(src), lines=src.splitlines(), pragmas={},
    )
    lines = {x.line for x in UndonatedDeviceUpdate().check_file(f)}
    # bare + parted flagged (on their decorator lines); donated clean.
    assert len(lines) == 2


def test_trace_rule_polarity_and_early_return_dominator():
    """trace-lazy-emit must accept the early-return dominator form
    (`if not tracer.enabled: return` heading a function) and the
    hoisted-name guard, and must NOT accept a wrong-polarity guard
    (`if not tracer.enabled:` body runs exactly when tracing is off)."""
    import ast
    import textwrap

    from k8s1m_tpu.lint.base import SourceFile
    from k8s1m_tpu.lint.rules_trace import TraceLazyEmit

    src = textwrap.dedent('''
        def dominated(tracer, pod):
            if not tracer.enabled:
                return
            tracer.emit(pod.key, "bind")          # guarded (dominator)

        def wrong_polarity(tracer, pod):
            if not tracer.enabled:
                tracer.emit(pod.key, "bind")      # NOT guarded
            else:
                tracer.finish(pod.key, "bind")    # guarded (else arm)

        def hoisted(tracer, pod):
            tr_on = tracer.enabled
            if tr_on:
                tracer.emit(pod.key, "bind")      # guarded (hoisted name)

        def short_circuit(tracer, pod):
            tracer.enabled and tracer.emit(pod.key, "bind")  # guarded

        def compound_negation(tracer, pod, pods):
            if pods and not tracer.enabled:
                tracer.emit(pod.key, "bind")      # NOT guarded (off-branch)

        def wrong_order(tracer, pod):
            tracer.emit(pod.key, "bind") and tracer.enabled  # NOT guarded
    ''')
    f = SourceFile(
        path="k8s1m_tpu/control/synthetic.py", abspath="synthetic.py",
        tree=ast.parse(src), lines=src.splitlines(), pragmas={},
    )
    findings = TraceLazyEmit().check_file(f)
    assert len(findings) == 3, [x.render() for x in findings]
    flagged = {x.source for x in findings}
    assert 'tracer.emit(pod.key, "bind")      # NOT guarded' in flagged
    assert 'tracer.emit(pod.key, "bind")      # NOT guarded (off-branch)' in (
        flagged
    )
    assert 'tracer.emit(pod.key, "bind") and tracer.enabled  # NOT guarded' in (
        flagged
    )


def test_pragma_twins_pass(fixture_result):
    ok_files = {
        f.path for f in fixture_result.findings
        if "/ok_" in f.path
    }
    assert ok_files == set()
    # And the twins were actually linted (not skipped).
    assert fixture_result.files == 2 * len(EXPECTED)
    # Every twin's pragma suppressed a live finding: none are stale.
    assert fixture_result.stale_pragmas == []
    assert sum(fixture_result.pragma_counts.values()) == len(EXPECTED)


# ---- baseline machinery ----------------------------------------------


def test_baseline_round_trip_and_counted_matching():
    f1 = Finding("a.py", 3, "broad-except", "msg", "except Exception:")
    f2 = Finding("a.py", 9, "broad-except", "msg", "except Exception:")
    entry = format_entry(f1)
    entries = parse_baseline(f"# why\n{entry}\n")
    assert entries == [("a.py", "broad-except", "except Exception:")]
    # One entry absorbs exactly one of two identical findings.
    new, stale = split_findings([f1, f2], entries)
    assert len(new) == 1 and stale == []
    # Two entries absorb both; a third is stale.
    new, stale = split_findings([f1, f2], entries * 3)
    assert new == [] and len(stale) == 1


def test_baseline_rejects_malformed_lines():
    with pytest.raises(ValueError):
        parse_baseline("not a valid entry\n")


# ---- the tier-1 gate over the real tree ------------------------------


def test_repo_lints_clean_against_committed_baseline():
    """No new findings AND no stale entries: the baseline matches the
    tree exactly, so drift in either direction fails tier-1."""
    result = run_lint()
    assert [f.render() for f in result.new] == []
    assert result.stale == []
    # The baseline stays small by policy (<= 10 grandfathered findings).
    grandfathered = len(result.findings) - len(result.new)
    assert grandfathered <= 10
    # And no pragma is dead weight: every `# graftlint: disable=` in
    # the tree suppresses a live finding (the stale-pragma gate).
    assert result.stale_pragmas == []


def test_stale_pragma_detected(tmp_path):
    """A pragma on a line where its rule no longer fires is reported
    (and a typo'd rule id is always stale)."""
    pkg = tmp_path / "k8s1m_tpu"
    pkg.mkdir()
    (pkg / "clean.py").write_text(
        "def f():\n"
        "    return 1  # graftlint: disable=broad-except (nothing here)\n"
        "\n"
        "def g():\n"
        "    return 2  # graftlint: disable=no-such-rule\n"
    )
    result = run_lint(root=str(tmp_path), baseline_path="")
    assert result.findings == []
    assert result.stale_pragmas == [
        ("k8s1m_tpu/clean.py", 2, "broad-except"),
        ("k8s1m_tpu/clean.py", 5, "no-such-rule"),
    ]
    # Warn-by-default: exit 0 without --strict-pragmas, 1 with it.
    from k8s1m_tpu.lint.cli import main

    assert main(["--root", str(tmp_path), "--no-baseline"]) == 0
    assert main(
        ["--root", str(tmp_path), "--no-baseline", "--strict-pragmas"]
    ) == 1


def test_broad_except_not_satisfied_by_nested_function(tmp_path):
    """A raise/log.exception inside a nested def the handler merely
    DEFINES must not make a silent swallow pass the rule."""
    pkg = tmp_path / "k8s1m_tpu"
    pkg.mkdir()
    (pkg / "sneaky.py").write_text(
        "def f(op):\n"
        "    try:\n"
        "        op()\n"
        "    except Exception:\n"
        "        def helper():\n"
        "            raise ValueError('never called')\n"
        "        pass\n"
    )
    result = run_lint(root=str(tmp_path), baseline_path="")
    assert [f.rule for f in result.findings] == ["broad-except"]


def test_single_file_run_ignores_unrelated_baseline_entries():
    """`tools/lint.sh path/to/file.py` must not report the whole
    baseline as stale: entries for files outside the linted subset were
    never given a chance to match."""
    result = run_lint(paths=["k8s1m_tpu/tools/soak.py"])
    assert result.new == [] and result.stale == []
    # A subset that CONTAINS a baselined file still matches its entries.
    result = run_lint(paths=["k8s1m_tpu/control/shardset.py"])
    assert result.new == [] and result.stale == []
    assert len(result.findings) == 3     # the grandfathered lease writes


def test_cli_entry_point_agrees():
    proc = subprocess.run(
        [sys.executable, "-m", "k8s1m_tpu.lint", "--check-baseline"],
        capture_output=True,
        text=True,
        cwd=repo_root(),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=180,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 new finding(s)" in proc.stdout


def test_cli_json_output_and_bounded_time():
    """``--json`` is the machine-readable CI shape (rule -> count ->
    files) with a stable ``schema_version`` and per-rule wall-time, and
    the FULL run (all 19 passes, interprocedural lockgraph and flow
    call graph included) stays under the 60s budget on this env — the
    bound that keeps the gate usable as a pre-commit check while the
    rule count grows."""
    import json
    import time

    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "k8s1m_tpu.lint", "--json",
         "--check-baseline", "--strict-pragmas"],
        capture_output=True,
        text=True,
        cwd=repo_root(),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=90,
    )
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["new"] == [] and doc["rules"] == {}
    assert doc["stale_pragmas"] == [] and doc["stale_baseline"] == []
    assert doc["files"] > 100
    assert set(doc["pragma_counts"]) >= {"broad-except"}
    # Schema round-trip: version pinned, every registered rule timed,
    # and the document re-serializes to the same bytes (no NaN/inf or
    # unstable key ordering hiding in the report).
    from k8s1m_tpu.lint.cli import SCHEMA_VERSION

    assert doc["schema_version"] == SCHEMA_VERSION
    assert set(doc["rule_times"]) == {r.id for r in ALL_RULES}
    assert all(
        isinstance(v, (int, float)) and v >= 0
        for v in doc["rule_times"].values()
    )
    assert json.loads(json.dumps(doc)) == doc
    # The <60s budget assumes a working core or two; an effectively-
    # 1-core host (affinity/cgroup quota — same condition the soak
    # smoke keys on) gets a proportionally relaxed bound rather than a
    # spurious red.
    from _env import effective_cpus

    budget = 60.0 if effective_cpus() >= 2 else 240.0
    assert elapsed < budget, f"full lint took {elapsed:.1f}s (budget {budget}s)"


def test_jobs_output_byte_identical():
    """``--jobs N`` must be a pure speedup: the parallel run's stdout is
    byte-for-byte the sequential run's stdout.  Exercised over the
    fixture corpus (cheap, and every rule fires there) plus --json so
    ordering, counts, and rule timing keys all participate."""

    def run(jobs: int) -> str:
        proc = subprocess.run(
            [sys.executable, "-m", "k8s1m_tpu.lint", "--root", FIXTURES,
             "--no-baseline", "--jobs", str(jobs)],
            capture_output=True,
            text=True,
            cwd=repo_root(),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            timeout=180,
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        return proc.stdout

    assert run(1) == run(4)


def test_changed_only_mode_smoke():
    """``tools/lint.sh --changed-only`` exits clean on a clean tree and
    accepts a changed-file subset without tripping over baseline
    entries for files outside it."""
    proc = subprocess.run(
        ["bash", "tools/lint.sh", "--changed-only"],
        capture_output=True,
        text=True,
        cwd=repo_root(),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=180,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
