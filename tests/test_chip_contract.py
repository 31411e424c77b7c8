"""The contract the chip entry points keep (ISSUE 21).

- one placeable compile cache (envboot.place_compile_cache);
- chip_smoke.py and bench.py refuse anything but a TPU and print no
  metric — no path falls back to the CPU;
- chip_smoke's drive functions, imported and run here at a tiny size
  with the pallas kernels interpreted, so tier-1 exercises the smoke's
  own logic while the command itself refuses a CPU;
- the native store is rebuilt on a content hash, never on mtimes.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_path, *, cwd=REPO, env_extra=None, env_drop=(), script=False):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, *filter(None, [env.get("PYTHONPATH")])]
    )
    for k in env_drop:
        env.pop(k, None)
    env.update(env_extra or {})
    cmd = [sys.executable, code_or_path] if script else [
        sys.executable, "-c", code_or_path
    ]
    return subprocess.run(
        cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=300
    )


# ---- the compile cache -----------------------------------------------------

_CACHE_PROBE = (
    "import os\n"
    "from k8s1m_tpu.envboot import place_compile_cache\n"
    "before = os.environ.get('JAX_COMPILATION_CACHE_DIR')\n"
    "got = place_compile_cache()\n"
    "import jax\n"
    "print(repr((before, got, os.environ['JAX_COMPILATION_CACHE_DIR'],"
    " jax.config.jax_compilation_cache_dir)))\n"
)


def test_compile_cache_env_set_nothing_set_in_code(tmp_path):
    want = str(tmp_path / "outside-cache")
    proc = _run(_CACHE_PROBE, env_extra={"JAX_COMPILATION_CACHE_DIR": want})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert eval(proc.stdout.strip()) == (want, want, want, want)


def test_compile_cache_unset_is_one_path_in_the_checkout(tmp_path):
    """Two processes, different cwd and pid: the same in-checkout path."""
    want = os.path.join(REPO, ".jax_cache")
    seen = set()
    for cwd in (REPO, str(tmp_path)):
        proc = _run(
            _CACHE_PROBE, cwd=cwd, env_drop=("JAX_COMPILATION_CACHE_DIR",)
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        before, got, env_after, cfg = eval(proc.stdout.strip())
        assert before is None
        seen.add((got, env_after, cfg))
    assert seen == {(want, want, want)}


def test_compile_cache_reaches_an_already_imported_jax():
    """An in-process main() called after jax loaded (tests, tools
    driving sched_bench.main) still lands on the same directory."""
    proc = _run(
        "import jax\n"
        "from k8s1m_tpu.envboot import place_compile_cache\n"
        "got = place_compile_cache()\n"
        "print(repr((got, jax.config.jax_compilation_cache_dir)))\n",
        env_drop=("JAX_COMPILATION_CACHE_DIR",),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = os.path.join(REPO, ".jax_cache")
    assert eval(proc.stdout.strip()) == (want, want)


def test_cleaned_cpu_env_leaves_pythonpath_alone():
    from k8s1m_tpu.envboot import cleaned_cpu_env

    base = {
        "PYTHONPATH": "/a:/some/site:/b",
        "XLA_FLAGS": "--foo --xla_force_host_platform_device_count=2",
    }
    env = cleaned_cpu_env(base, 8)
    assert env["PYTHONPATH"] == "/a:/some/site:/b"
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"] == "--foo --xla_force_host_platform_device_count=8"
    assert "PYTHONPATH" not in cleaned_cpu_env({}, 4)
    assert base["XLA_FLAGS"].endswith("=2")      # input not mutated


# ---- no CPU fallback -------------------------------------------------------


@pytest.mark.parametrize(
    "script", ["chip_smoke.py", "bench.py", "tools/kernel_time.py"])
def test_entry_points_refuse_a_cpu_and_print_no_metric(script):
    proc = _run(os.path.join(REPO, script), script=True)
    assert proc.returncode not in (0, None)
    assert "tpu" in proc.stderr.lower()
    for line in proc.stdout.splitlines():
        assert not line.lstrip().startswith("{"), line     # no result JSON
        assert "binds" not in line


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The driver also runs the script with nothing else of the repo
    beside it: it must fail, not report."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


# ---- the smoke's own logic, tiny, on the CPU -------------------------------


@pytest.fixture(scope="module")
def smoke():
    import chip_smoke       # conftest.py put the repo root on sys.path

    return chip_smoke


def test_smoke_phases_a_and_b_tiny(smoke):
    from k8s1m_tpu.store.native import MemStore

    compiles, timer = smoke.CompileCounter(), smoke.Timer()
    with MemStore() as store:
        smoke.load_nodes(store, 256)
        coord, res = smoke.phase_a(
            store, compiles, timer, nodes=256, batch=32, chunk=128,
            score_pct=5, waves=3, webhook_pods=8, oracle_sample=16,
        )
        coord.close()
        assert res["bound"] == 96 and len(res["binds"]) == 96
        assert res["compiles_after_warmup"] == 0
        assert all(len(v) == 1 for v in res["binds"].values())
        failures = smoke.phase_b(
            coord, timer, batch=32, chunk=128, score_pct=5, fit_nodes=12
        )
    assert failures == {}
    # Set-up seconds are labelled as such: one entry per executable.
    assert {"A.bootstrap", "B.base.pallas_step",
            "B.xla_scan.all_rows.xla_step", "B.assign.window_wave",
            "B.assign.fit_10k_brim", "B.prologue.9x5",
            "B.prologue.512x64",
            "B.affinity_wide_ids.pallas_candidates"} <= set(timer.seconds)


def test_smoke_phase_full_tiny(smoke):
    """Phase F on the benchmark cell cut to 1,000 nodes: the combined
    kernel (interpreted here) equals the XLA scan on live and long-lived
    counts, on a wave of the cell's own pods."""
    import copy

    def tiny(workload, config, pods):
        config = copy.deepcopy(config)
        config["nodes"].update(count=1000, cordon_every=16)
        config["table_spec"]["max_nodes"] = 1024
        config["pod_spec"]["batch"] = 128
        config["coordinator"].update(chunk=128, backend="xla")
        return dict(workload, wave=128), config, pods

    timer = smoke.Timer()
    got = smoke.phase_full(timer, waves=3, tiny=tiny)
    assert got["kernel"] == "fused_topk_affinity_constraints"
    assert got["fused_labels"] is True       # 1,000 hostnames fit the word
    assert got["counted"] > 0 and got["candidates"] > 0
    assert {"F.setup", "F.pallas_candidates", "F.xla_candidates"} <= set(
        timer.seconds)


def test_smoke_check_catches_a_double_bind(smoke):
    """The pass/fail logic itself: a pod seen bound twice on the watch
    (or never) fails the drive check."""
    from k8s1m_tpu.store.native import MemStore

    compiles, timer = smoke.CompileCounter(), smoke.Timer()
    with MemStore() as store:
        smoke.load_nodes(store, 256)
        before = smoke.fallback_counts()
        coord = smoke.make_coordinator(
            store, nodes=256, batch=32, chunk=128, score_pct=5
        )
        coord.bootstrap()
        res = smoke.drive(
            coord, store, compiles, namespace="t", waves=1, webhook_pods=2
        )
        coord.close()
        check = dict(namespace="t", oracle_sample=8, fallbacks_before=before)
        smoke.check_drive(coord, store, res, **check)
        key = next(iter(res["binds"]))
        twice = {**res, "binds": {**res["binds"], key: res["binds"][key] * 2}}
        with pytest.raises(RuntimeError, match="bound more than once"):
            smoke.check_drive(coord, store, twice, **check)
        lost = {**res, "binds": {k: v for k, v in res["binds"].items()
                                 if k != key}}
        with pytest.raises(RuntimeError, match="never bound"):
            smoke.check_drive(coord, store, lost, **check)


def test_smoke_mesh_phase_tiny(smoke):
    from k8s1m_tpu.store.native import MemStore

    compiles, timer = smoke.CompileCounter(), smoke.Timer()
    with MemStore() as store:
        smoke.load_nodes(store, 512)
        smoke.mesh_phase(
            store, compiles, timer, nodes=512, batch=32, chunk=128,
            score_pct=5, waves=2, webhook_pods=4, oracle_sample=8,
        )


# ---- store/build.py: content hash, not mtimes ------------------------------


@pytest.fixture
def fake_build(tmp_path, monkeypatch):
    """store/build.py pointed at a scratch library, with the compiler
    replaced by a recorder (the real build is ~10 s of g++)."""
    from k8s1m_tpu.store import build

    lib = tmp_path / "libmemstore.so"
    monkeypatch.setattr(build, "LIB_PATH", str(lib))
    monkeypatch.setattr(build, "STAMP_PATH", str(lib) + ".sha256")
    calls = []

    def fake_run(cmd, **kw):
        calls.append(cmd)
        with open(cmd[cmd.index("-o") + 1], "w") as f:
            f.write("built")
        return subprocess.CompletedProcess(cmd, 0, "", "")

    monkeypatch.setattr(build.subprocess, "run", fake_run)
    return build, lib, calls


def test_build_ignores_mtimes_of_unchanged_sources(fake_build):
    build, lib, calls = fake_build
    build.ensure_built()
    assert len(calls) == 1 and lib.read_text() == "built"
    for when in (0, 2**31 - 1):       # older and newer than every source
        os.utime(lib, (when, when))
        build.ensure_built()
    assert len(calls) == 1


def test_build_rebuilds_when_recorded_hash_differs(fake_build):
    build, lib, calls = fake_build
    build.ensure_built()
    with open(build.STAMP_PATH, "w") as f:
        f.write("0" * 64 + "\n")
    build.ensure_built()
    assert len(calls) == 2
    assert open(build.STAMP_PATH).read().strip() == build.source_hash()
    os.remove(build.STAMP_PATH)        # a library with no record of its
    build.ensure_built()               # sources is not trusted either
    assert len(calls) == 3


def test_build_without_a_compiler_fails_loudly(fake_build, monkeypatch):
    build, lib, _ = fake_build

    def no_gxx(cmd, **kw):
        raise FileNotFoundError(2, "No such file or directory", "g++")

    monkeypatch.setattr(build.subprocess, "run", no_gxx)
    with pytest.raises(RuntimeError, match=r"g\+\+ not found"):
        build.ensure_built()
    assert not lib.exists()
