"""Process-environment bootstrap shared by the entry points and the tests.

Two jobs, both of which must happen before jax picks a backend:

- :func:`place_compile_cache` — every entry point that can take the chip
  calls it first, so all of them share one persistent compilation cache
  whose location can be set from outside the program.
- :func:`cleaned_cpu_env` — the environment of a child interpreter that
  must host an ``n``-device virtual CPU mesh (the test suite and the
  driver's multi-chip dry run); the device count is an XLA flag read at
  backend start-up, so it cannot be changed in a process that already
  initialised one.

Must stay importable without jax (it runs before backend selection).
"""

from __future__ import annotations

import gc
import os
import sys

_COUNT_FLAG = "--xla_force_host_platform_device_count"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# One fixed path inside the checkout: a directory that moves between
# runs (a tempdir, a pid, a timestamp) is never found again by the next
# process.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def place_compile_cache() -> str:
    """Point jax's persistent compilation cache at ``$JAX_COMPILATION_
    CACHE_DIR`` when the caller's environment sets it — in which case
    nothing is set in code — and at ``<checkout>/.jax_cache`` otherwise.
    Returns the directory in effect.

    jax reads the variable when it is first imported, so entry points
    call this before importing it; for callers that could not (an
    in-process ``main()`` invoked after jax loaded) the already-imported
    config is updated to the same value.
    """
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    os.environ[CACHE_ENV] = DEFAULT_CACHE_DIR
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def tune_gc(gen0: int = 50_000, gen1: int = 50, gen2: int = 50) -> None:
    """Relax the cyclic-GC cadence for a serving hot loop.

    The reference deploys its Go scheduler fleet with GOGC≈700-1000 and a
    GOMEMLIMIT because collector pressure was a measured tail-latency and
    throughput cost at 14K pods/s (reference README.adoc:672-677,
    terraform/kubernetes/dist-scheduler.tf:220-228).  The CPython analogue:
    the coordinator's intake loop allocates hundreds of thousands of
    small, acyclic objects per second (event tuples, byte slices,
    PendingPods) while holding large long-lived dicts (_bound), so the
    default gen0 threshold of 700 fires the collector thousands of times
    a second and every gen2 pass rescans the bound-pod table — measured
    at ~35% of end-to-end schedule-to-bind throughput on one core.
    Refcounting reclaims the acyclic garbage either way; raising the
    thresholds keeps cycle collection for what actually needs it.

    Objects that survived startup never become garbage in steady state:
    freeze them out of the young generations entirely.
    """
    gc.collect()
    gc.freeze()
    gc.set_threshold(gen0, gen1, gen2)


def cleaned_cpu_env(environ, n_devices: int) -> dict:
    """A copy of ``environ`` prepared for an ``n_devices`` CPU-mesh child:
    JAX_PLATFORMS=cpu and the virtual device count forced (replacing any
    existing count flag).  Everything else — PYTHONPATH included — is
    the caller's."""
    env = dict(environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split() if not f.startswith(_COUNT_FLAG)
    ]
    flags.append(f"{_COUNT_FLAG}={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env
