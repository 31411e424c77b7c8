"""Sampling profiler (obs/profiler.py) — the Parca/pprof role.

Pins that the sampler attributes wall time to the function that burns
it, and that artifacts are well-formed collapsed stacks.
"""

import json
import threading
import time

from k8s1m_tpu.obs.profiler import SamplingProfiler


def _spin(deadline):
    x = 0
    while time.perf_counter() < deadline:
        for _ in range(1000):
            x += 1
    return x


def test_profiler_attributes_hot_function(tmp_path):
    import sys

    # The GIL bounds the effective rate on a 1-core host: the spinning
    # main thread holds it for whole switch intervals and under suite
    # load the sampler can starve entirely.  A 1ms switch interval for
    # the test's duration guarantees wakeups; the window is adaptive on
    # top of that.
    old = sys.getswitchinterval()
    sys.setswitchinterval(0.001)
    try:
        prof = SamplingProfiler(hz=250)
        deadline = time.perf_counter() + 20.0
        with prof:
            while prof.samples < 25 and time.perf_counter() < deadline:
                _spin(time.perf_counter() + 0.3)
    finally:
        sys.setswitchinterval(old)
    assert prof.samples > 5
    rep = prof.report(top=1000)
    # _spin accrued self-time.  (Rank-based asserts flake in full-suite
    # runs: leftover daemon threads from other test files also accrue a
    # full-count frame per tick and can outrank the spinner.)
    assert rep["top_self"], rep
    assert any("_spin" in row["frame"] for row in rep["top_self"]), (
        rep["top_self"][:5]
    )
    # Collapsed stacks are ;-joined frames ending at the leaf; at least
    # one sampled stack bottoms out in the spinner.
    assert any(
        "_spin" in stack.split(";")[-1] for stack in rep["collapsed"]
    )

    path = prof.dump(str(tmp_path / "p.json"))
    with open(path) as f:
        disk = json.load(f)
    assert disk["thread_samples"] == rep["thread_samples"]
    assert prof.format_top().startswith("profile:")


def test_profiler_samples_other_threads(tmp_path):
    stop = threading.Event()

    def worker():
        while not stop.is_set():
            _spin(time.perf_counter() + 0.01)

    def seen_in_collapsed(rep):
        # The collapsed stacks are untruncated; top_cumulative's top-N
        # can be crowded out by idle daemon threads (each idle thread's
        # wait frames accrue EVERY tick, a full-count entry per frame).
        return any("_spin" in s for s in rep["collapsed"])

    t = threading.Thread(target=worker, name="hot-worker", daemon=True)
    t.start()
    try:
        with SamplingProfiler(hz=250) as prof:
            # Adaptive window (suite load on the single core can starve
            # short fixed sleeps of samples).
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                time.sleep(0.25)
                if seen_in_collapsed(prof.report()):
                    break
    finally:
        stop.set()
        t.join()
    assert seen_in_collapsed(prof.report())


def test_profiler_excludes_late_started_profiler_thread():
    """Exclusions re-resolve per sample tick: a profiler(-named) thread
    started AFTER this one must not be sampled as workload (the
    start-time snapshot could never see it — its wait/fold frames then
    accrued a full-count entry per tick)."""
    stop = threading.Event()

    def _late_decoy_spin():
        while not stop.is_set():
            time.sleep(0.002)

    prof = SamplingProfiler(hz=250).start()
    late = threading.Thread(
        # Matches the _EXCLUDE_THREADS prefix, like a second profiler.
        target=_late_decoy_spin, name="sampling-profiler-late", daemon=True,
    )
    try:
        # Thread.start() returns only after the thread registered in
        # threading.enumerate(), so every later tick can resolve it.
        late.start()
        deadline = time.monotonic() + 10.0
        while prof.samples < 10 and time.monotonic() < deadline:
            _spin(time.perf_counter() + 0.05)
    finally:
        stop.set()
        prof.stop()
        late.join()
    assert prof.samples > 0
    assert not any("_late_decoy_spin" in s for s in prof.stacks), (
        [s for s in prof.stacks if "_late_decoy_spin" in s][:3]
    )
