"""The benchmark: one cell, once, on the chip, from the client's side.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Native ``MemStore`` -> pod-prefix watch -> ``Coordinator`` -> device ->
``bind_batch`` CAS -> the client's own store watch.  Everything a cell is
comes from data: ``BENCHMARK.json`` names it, ``benchmark/workloads/``,
``benchmark/configs/``, ``benchmark/pods/`` and ``benchmark/metrics/``
describe it (see benchmark/README.md).  The last line of standard output
is the result object; the numbers that decide ``correct`` are printed
beside their limits as the last lines of standard error as well.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up counts from here

import argparse
import contextlib
import importlib.util
import json
import os
import random
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (
    faults, generate, readers, reference, roofline, span_readers, trace_reduce,
)

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
REFERENCES_DIR = os.path.join(ROOT, "benchmark", "references")
STORE_SAMPLE = 512
POLL = 16384
WARM_UP_WAVES = 3


def log(msg: str) -> None:
    print(msg, flush=True)


def read_json(*rel: str) -> dict:
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def load_cell(manifest: dict, name: str) -> tuple[dict, dict, dict]:
    """The cell's manifest entry merged with its workload file, its
    configuration file and its pods file."""
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    workload = {**read_json("benchmark", "workloads", f"{name}.json"), **entry}
    cfg = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    config = read_json(cfg["file"])
    pods = read_json("benchmark", "pods", f"{workload['pods']}.json")
    return workload, config, pods


def load_reference(name: str):
    """The ``numbers`` function of ``benchmark/references/<name>.py``: a
    configuration's own guarantees (benchmark/README.md), loaded by path
    since the file imports nothing, not the program and not the harness."""
    path = os.path.join(REFERENCES_DIR, f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"configuration names reference {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark_reference_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.numbers


def trace_due(*, now: float, t0: float, deadline: float, trace_seconds: float,
              first: int, offered: int, most: int, wave: int) -> bool:
    """Whether the traced part of the window starts now, at the end of an
    iteration: when ``trace_seconds`` are left on the clock, or, in a cell
    that fills to the brim (``most`` pods in all), when the waves left
    before the brim are what the loop, at the pace it has kept since the
    window opened, offers in ``trace_seconds`` -- whichever comes first,
    so that the traced part is ``trace_seconds`` long, give or take a
    wave, at every rate the cell can reach."""
    if not trace_seconds:
        return False
    if now >= deadline - trace_seconds:
        return True
    if most >= 1 << 62 or offered <= first:
        return False
    pace = (now - t0) * wave / (offered - first)        # seconds a wave
    return (most - offered) // wave * pace <= trace_seconds


def metrics_of(manifest: dict, kind: str, cell: str) -> list[dict]:
    """The manifest's metrics of one kind that this cell reports."""
    e2e_cells = {
        m["name"]: m.get("workloads") for m in manifest["end_to_end"]
    }

    def reports(m: dict) -> bool:
        if "workloads" in m:
            return cell in m["workloads"]
        if kind == "end_to_end":
            return True
        moved = e2e_cells[m["moves"]]
        return moved is None or cell in moved

    return [m for m in manifest[kind] if reports(m)]


class CompileCounter:
    """Counts executables built (compiled or loaded from the persistent
    cache) and persistent-cache hits, from jax's own monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.built = 0
        self.cache_hits = 0
        self.cache_writes = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1


_COMPILES: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = CompileCounter()
    return _COMPILES


def fallback_counts() -> dict:
    """The counters that say a wave did not take the device path it was
    configured for (process-global, so callers compare snapshots)."""
    import k8s1m_tpu.control.coordinator  # noqa: F401  (registers both)
    from k8s1m_tpu.obs.metrics import REGISTRY
    from k8s1m_tpu.snapshot.packing import FALLBACK_REASONS

    cyc = REGISTRY.get("coordinator_cycle_seconds")
    fb = REGISTRY.get("device_packing_fallback_total")
    return {
        "cycle_seconds{stage=fallback}": cyc.sum(stage="fallback"),
        **{f"packing_fallback{{reason={r}}}": fb.value(reason=r)
           for r in FALLBACK_REASONS},
    }


def make_coordinator(store, config: dict, workload: dict, seed: int):
    """The deployment's coordinator: the configuration file's
    ``coordinator`` object (and the cell's, on top) as keyword arguments."""
    from k8s1m_tpu.config import PodSpec, TableSpec
    from k8s1m_tpu.control.coordinator import Coordinator
    from k8s1m_tpu.plugins.registry import Profile

    kw = {**config["coordinator"], **workload.get("coordinator", {})}
    return Coordinator(
        store, TableSpec(**config["table_spec"]), PodSpec(**config["pod_spec"]),
        Profile(**config["profile"]), seed=seed % (1 << 31), **kw,
    )


class GcClock:
    """Seconds the cyclic collector ran, and how often, by generation."""

    def __init__(self) -> None:
        import gc

        self.seconds = [0.0, 0.0, 0.0]
        self.runs = [0, 0, 0]
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            g = info["generation"]
            self.seconds[g] += time.perf_counter() - self._t
            self.runs[g] += 1

    def close(self) -> str:
        import gc

        gc.callbacks.remove(self._on)
        return " ".join(
            f"gen{g}={self.runs[g]}x/{self.seconds[g]:.3f}s" for g in range(3)
        )


class Cell:
    """One run of one cell: set-up, window, drain, check."""

    def __init__(self, store, config: dict, workload: dict, pods: dict,
                 seed: int) -> None:
        self.store, self.config, self.workload = store, config, workload
        self.seed = seed
        self.wave = int(workload["wave"])
        self.nodes = generate.Nodes(config["nodes"])
        self.pods = generate.Pods(pods, seed)
        self.ledger = reference.Ledger(len(self.pods.key_prefix))
        self.offered = 0
        self.most = self._most_pods()
        self.compiles = compile_counter()
        self.setup_split: dict[str, float] = {}
        self.span = lambda _name: contextlib.nullcontext()
        self.coord = self._watch = self._undo = None

    def _most_pods(self) -> int:
        """How many pods the run may offer in all: in a cell that names
        its ``brim_slack_pods``, whole waves up to the open nodes' pod
        slots less that slack; otherwise no limit but the window."""
        if "brim_slack_pods" not in self.workload:
            return 1 << 62
        n = self.nodes
        open_nodes = sum(not n.cordoned(i) for i in range(n.count))
        slots = open_nodes * n.pods - int(self.workload["brim_slack_pods"])
        return slots // self.wave * self.wave

    # ---- set-up ----------------------------------------------------------

    @contextlib.contextmanager
    def _phase(self, label: str):
        t = time.perf_counter()
        yield
        self.setup_split[label] = round(time.perf_counter() - t, 3)

    def setup(self, fault: str | None = None) -> None:
        from k8s1m_tpu.control.coordinator import PODS_PREFIX
        from k8s1m_tpu.store.native import prefix_end

        rng = random.Random(self.seed)
        with self._phase("store_load"):
            self.nodes.verify(rng)
            self.pods.verify(rng)
            generate.load_nodes(self.store, self.nodes)
        self.fallbacks_before = fallback_counts()
        with self._phase("bootstrap"):
            self.coord = make_coordinator(
                self.store, self.config, self.workload, self.seed
            )
            if fault:
                self._undo = faults.load(fault)(self.store, self.coord)
            self.coord.bootstrap()
        self._watch = self.store.watch(
            PODS_PREFIX, prefix_end(PODS_PREFIX), queue_cap=1 << 21
        )
        with self._phase("warm_up"):
            self.warm_up()

    def warm_up(self) -> None:
        """The one shape the window uses, and no other: full waves
        through the pipelined step."""
        for _ in range(WARM_UP_WAVES):
            self._offer(self.wave)
            self.coord.step()
            self.drain_watch()
        self.idle()

    # ---- the client ------------------------------------------------------

    def _offer(self, n: int) -> None:
        """Create pods ``offered .. offered+n`` in one batched put, as a
        bulk client's packed frame."""
        with self.span("bench.put"):
            rev = self.store.put_frame(self.pods.frame(self.offered, n), n)
        if rev < 0:
            raise RuntimeError(f"put_frame rc={rev}")
        self.offered += n

    def drain_watch(self) -> int:
        """Everything the client's watch holds, stamped with the time the
        client had it in hand (columnar batches, read after the window)."""
        seen = 0
        with self.span("bench.watch"):
            while True:
                events = self._watch.poll_pods(POLL)
                self.ledger.add(time.perf_counter(), events)
                seen += events.n
                if events.n < POLL:
                    return seen

    def idle(self) -> None:
        """Run the coordinator until nothing is pending and the watch is
        quiet."""
        while True:
            self.coord.run_until_idle()
            if not self.drain_watch():
                return

    # ---- the window ------------------------------------------------------

    def window(self, seconds: float, trace_seconds: float = 0.0) -> dict:
        """Backlog arrivals: offer a wave, step, drain the watch, until
        ``seconds`` are up (or, in a cell that fills to the brim, the
        last wave that fits has been offered).  The window closes at the
        end of the iteration that passes the deadline, and its length is
        measured, so no work falls between the count and the clock."""
        import jax

        from k8s1m_tpu.obs.metrics import REGISTRY

        if self.workload["arrival"] != "backlog":
            raise SystemExit(f"arrival {self.workload['arrival']!r}")
        cyc = REGISTRY.get("coordinator_cycle_seconds")
        w, step, most = self.wave, self.coord.step, self.most
        tracing = False
        built0 = self.compiles.built
        first = self.offered
        # what the program counted up to here is set-up's; the snapshots
        # are taken outside [t0, t1]
        setup_stage_s = span_readers.stage_sums()
        counters = {"open": span_readers.snapshot_counters()}
        cyc.reset()
        gc_clock = GcClock()
        t0 = time.perf_counter()
        deadline = t0 + seconds
        clock = time.perf_counter
        t_put = t_step = t_watch = 0.0
        now = t0
        while True:
            self._offer(w)
            a = clock()
            with self.span("bench.step"):
                step()
            b = clock()
            self.drain_watch()
            t_put += a - now
            now = clock()
            t_step += b - a
            t_watch += now - b
            if now >= deadline or self.offered + w > most:
                break
            if not tracing and trace_due(
                now=now, t0=t0, deadline=deadline, trace_seconds=trace_seconds,
                first=first, offered=self.offered, most=most, wave=w,
            ):
                tracing = True
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
                self.span = jax.profiler.TraceAnnotation
                t_trace = clock()
        t1 = now
        gc_line = gc_clock.close()
        stage_s = span_readers.stage_sums()
        counters["close"] = span_readers.snapshot_counters()
        built = self.compiles.built - built0
        if tracing:
            jax.block_until_ready(self.coord.table)
            jax.profiler.stop_trace()
            self.span = lambda _name: contextlib.nullcontext()
            log(f"trace of the window's last {t1 - t_trace:.3f}s (from "
                f"{t_trace - t0:.3f}s to its close at {t1 - t0:.3f}s; "
                f"trace_seconds {trace_seconds}) written in "
                f"{time.perf_counter() - t1:.1f}s")
        return {"t0": t0, "t1": t1, "first": first, "last": self.offered,
                "gc": gc_line,
                "loop_s": {"put": t_put, "step": t_step, "watch": t_watch},
                "stage_s": stage_s, "setup_stage_s": setup_stage_s,
                "counters": counters, "compiled_in_window": built,
                "traced": tracing,
                "traced_s": t1 - t_trace if tracing else 0.0}

    def brim(self) -> int:
        """After the window has closed, the same loop goes on, untimed,
        until the last wave that fits the deployment's pod slots has been
        offered: the waves in which pods contend for the last slots of
        nearly full nodes.  Returns the pods offered here."""
        at = self.offered
        while self.offered + self.wave <= self.most < 1 << 62:
            self._offer(self.wave)
            self.coord.step()
            self.drain_watch()
        return self.offered - at

    # ---- after the window ------------------------------------------------

    def check(self, win: dict) -> dict:
        """The comparison that decides ``correct``: the client's history
        replayed by the plain reference, and the store, the host mirror
        and the device's table held to what the replay arrives at."""
        import numpy as np

        from k8s1m_tpu.snapshot.packing import is_packed

        n = self.nodes
        node_index = {n.name(i): i for i in range(n.count)}
        seen = self.ledger.arrays(node_index)
        reps = -(-self.offered // len(self.pods.pattern))
        pod_cpu = np.tile(self.pods.requests("cpu_milli"), reps)[:self.offered]
        pod_mem = np.tile(self.pods.requests("mem_kib"), reps)[:self.offered]
        rep = reference.replay(
            seen, offered=self.offered, pod_cpu=pod_cpu, pod_mem=pod_mem,
            alloc_cpu=np.full(n.count, n.cpu_milli, np.int64),
            alloc_mem=np.full(n.count, n.mem_kib, np.int64),
            alloc_pods=np.full(n.count, n.pods, np.int64),
            cordoned=np.fromiter(
                (n.cordoned(i) for i in range(n.count)), bool, n.count),
        )
        numbers = dict(rep["numbers"])
        # the store, read back: a sample drawn from the seed, the newest
        # wave in it
        rng = random.Random(self.seed)
        sample = set(range(max(0, self.offered - self.wave), self.offered, 8))
        sample.update(rng.randrange(self.offered) for _ in range(STORE_SAMPLE))
        wrong = 0
        for i in sample:
            kv = self.store.get(self.pods.key(i))
            node = rep["node_of_pod"][i]
            if kv is None or node < 0 or reference.bind_node(kv.value) != n.name(node):
                wrong += 1
        numbers["store_disagrees"] = wrong

        host, table = self.coord.host, self.coord.table
        row_of_node = np.fromiter(
            (host.row_of(n.name(i).decode()) for i in range(n.count)),
            np.int64, n.count,
        )
        numbers["mirror_rows_wrong"] = reference.rows_wrong(
            rep, row_of_node, host.cpu_req, host.mem_req, host.pods_req
        )
        numbers["device_rows_wrong"] = reference.rows_wrong(
            rep, row_of_node, np.asarray(table.cpu_req),
            np.asarray(table.mem_req), np.asarray(table.pods_req),
        )
        numbers["compiled_in_window"] = win["compiled_in_window"]
        fell = sum(
            v != self.fallbacks_before[k] for k, v in fallback_counts().items()
        )
        numbers["fell_back"] = int(fell) + (
            self.config["coordinator"].get("packing") == "packed"
            and not is_packed(table)
        )
        numbers["watch_dropped"] = int(self._watch.dropped)
        # the configuration's own guarantees, where it names a reference
        # for them: more numbers, each with the limit 0 like the rest
        if "reference" in self.config:
            own = load_reference(self.config["reference"])(
                seen, rep, nodes=self.config["nodes"],
                pattern=self.pods.pattern, offered=self.offered,
            )
            taken = sorted(set(own) & set(numbers))
            if taken:
                raise RuntimeError(
                    f"reference {self.config['reference']!r} returns "
                    f"{taken}: the base comparison's own numbers")
            numbers.update(own)

        in_window, rate = reference.window_rate(seen["bind_t"], win["t0"], win["t1"])
        per_pod = np.bincount(
            seen["bind_pod"], minlength=self.offered
        )[win["first"]:win["last"]]
        return {
            "numbers": numbers,
            "binds": in_window,
            "binds_per_s": rate,
            "attempted": win["last"] - win["first"],
            "failed": int((per_pod != 1).sum()),
        }

    def shapes(self) -> dict:
        """What roofline.py needs, from the live arrays' shapes alone:
        the node table's columns and, where the deployment keeps them, the
        constraint planes that have the node axis (``[slots, N]``), each
        as bytes per node row."""
        t, c = self.coord.table, self.config["coordinator"]
        cols = {
            name: (leaf.dtype.itemsize,
                   int(leaf.size // leaf.shape[0]) if leaf.shape[0] else 0)
            for name, leaf in vars(t).items() if hasattr(leaf, "dtype")
        }
        cons = self.coord.constraints
        if cons is not None:
            cols.update({
                name: (leaf.dtype.itemsize, int(leaf.shape[0]))
                for name, leaf in vars(cons).items()
                if getattr(leaf, "ndim", 0) == 2 and leaf.shape[1] == t.num_rows
            })
        return {
            "scan_rows": roofline.window_rows(
                t.num_rows, int(c.get("score_pct", 100)), int(c["chunk"])
            ),
            "columns": cols,
            "batch": self.config["pod_spec"]["batch"],
            "k": self.coord.k,
            "pod_bytes": roofline.POD_BYTES,
        }

    def close(self) -> None:
        if self._undo is not None:
            self._undo()
        if self._watch is not None:
            self._watch.cancel()
        if self.coord is not None:
            self.coord.close()


def read_trace(cell: Cell) -> dict | None:
    """The traced seconds as events, the device's busy time in them, and
    the breakdown.  The traced window runs from the start of the
    benchmark's first span in the trace to the end of its last."""
    t = time.perf_counter()
    size = os.path.getsize(trace_reduce.trace_file(TRACE_DIR))
    loaded = trace_reduce.load_trace(TRACE_DIR)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    events, host_spans = loaded["events"], loaded["host_spans"]
    log(f"trace: {size} bytes, {len(events)} events read in "
        f"{time.perf_counter() - t:.1f}s")
    planes = trace_reduce.device_planes(events)
    if not planes:
        raise RuntimeError("trace holds no TPU device plane")
    spans = [(n, s, d) for _l, n, s, d in host_spans if n.startswith("bench.")]
    t0 = min(s for _n, s, _d in spans)
    t1 = max(s + d for _n, s, d in spans)
    device = trace_reduce.busy_window(events, t0, t1)
    plane = planes[0]
    op_names = loaded["op_names"].get(plane, {})
    breakdown = {
        "device_ops": [
            [trace_reduce.short_name(n), s] for n, s in trace_reduce.sums_by_name(
                events, plane, trace_reduce.OPS_LINE)
        ],
        "idle_gaps": [
            [n, s] for n, s in trace_reduce.idle_gaps(events, plane, spans, t0, t1)
        ],
        "idle_by_span": [
            [n, s] for n, s in span_readers.idle_by_span(
                events, plane, host_spans, t0, t1, top=10)
        ],
        "device_scopes": span_readers.device_scopes(
            events, plane, op_names, t0, t1),
    }
    return {"events": events, "plane": plane, "op_names": op_names,
            "host_spans": host_spans, "device": device, "breakdown": breakdown}


def per_layer_values(manifest: dict, name: str, ctx: dict) -> dict:
    """The cell's per-layer metrics, each by the reader its file names;
    one whose reader finds nothing to read has no value."""
    values = {}
    for m in metrics_of(manifest, "per_layer", name):
        spec = read_json("benchmark", "metrics", f"{m['name']}.json")
        values[m["name"]] = readers.resolve(spec["reader"])(spec.get("args", {}), ctx)
    return values


def log_counters(counters: dict) -> None:
    """Every counter that moved in the window or holds anything at its
    close, by label set: what it grew by, and what it holds."""
    for name, at_close in sorted(counters["close"].items()):
        at_open = counters["open"].get(name, {})
        rows = [
            (",".join(v for _k, v in key) or "-", n - at_open.get(key, 0.0), n)
            for key, n in sorted(at_close.items())
        ]
        if any(grown or n for _k, grown, n in rows):
            log(f"{name}: " + " ".join(
                f"{k}=+{grown:.0f}({n:.0f})" for k, grown, n in rows))


def run_cell(manifest: dict, name: str, cell_files: tuple[dict, dict, dict],
             *, seed: int, seconds: float, trace: bool, device: dict,
             peaks: dict, fault: str | None = None,
             dump_trace: str | None = None, keep: dict | None = None) -> dict:
    """Everything after the device check; returns the result object.
    ``keep``, where given, is filled with the readers' ``ctx``."""
    import jax
    import numpy as np

    from k8s1m_tpu.envboot import tune_gc
    from k8s1m_tpu.obs.metrics import REGISTRY
    from k8s1m_tpu.store.native import MemStore

    workload, config, pods = cell_files
    t_ready = time.perf_counter() - T_START
    with MemStore() as store:
        cell = Cell(store, config, workload, pods, seed)
        try:
            cell.setup(fault)
            tune_gc()
            setup_s = time.perf_counter() - T_START
            log(f"set-up {setup_s:.3f}s: start_to_device={t_ready:.3f} " + " ".join(
                f"{k}={v}" for k, v in cell.setup_split.items()
            ) + f" executables built={cell.compiles.built} "
                f"cache hits={cell.compiles.cache_hits} "
                f"cache writes={cell.compiles.cache_writes}")
            win = cell.window(
                seconds, float(workload.get("trace_seconds", 0.4)) if trace else 0.0
            )
            stats = jax.local_devices()[0].memory_stats() or {}
            device = {**device, "memory_peak_bytes": int(
                stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))
            )}
            t_brim = time.perf_counter()
            brim_pods = cell.brim()
            cell.idle()
            brim_s = time.perf_counter() - t_brim
            traced = read_trace(cell) if win["traced"] else None
            if traced and dump_trace:
                os.makedirs(os.path.dirname(dump_trace), exist_ok=True)
                with open(dump_trace, "w") as f:
                    f.write(trace_reduce.overview(traced["events"]))
            shapes = cell.shapes()
            t_check = time.perf_counter()
            out = cell.check(win)
            check_s = time.perf_counter() - t_check
        finally:
            cell.close()

    limits = {k: 0 for k in out["numbers"]}
    correct = all(out["numbers"][k] <= limits[k] for k in limits) and not out["failed"]
    log(f"window {win['t1'] - win['t0']:.3f}s: offered={out['attempted']} "
        f"binds seen={out['binds']} compiled in window="
        f"{win['compiled_in_window']}; after it: {brim_pods} pods to the brim, "
        f"drained in {brim_s:.1f}s, check {check_s:.1f}s")
    sched = REGISTRY.get("coordinator_pods_scheduled_total")
    log("pods sent back to the queue for another wave: "
        f"{sched.value(outcome='retry'):.0f}, parked for good: "
        f"{sched.value(outcome='unschedulable'):.0f}")
    log("stage seconds: " + " ".join(
        f"{k}={v:.3f}" for k, v in win["stage_s"].items()))
    log("set-up stage seconds: " + " ".join(
        f"{k}={v:.3f}" for k, v in win["setup_stage_s"].items()))
    log("counters, +grown in the window(held at its close):")
    log_counters(win["counters"])
    log("client loop seconds: " + " ".join(
        f"{k}={v:.3f}" for k, v in win["loop_s"].items())
        + f"; collector in window: {win['gc']}")

    values = {"binds_per_s": out["binds_per_s"], "setup_s": setup_s}
    ctx = {
        "stage_s": win["stage_s"], "setup_stage_s": win["setup_stage_s"],
        "counters": win["counters"], "binds": out["binds"], "trace": traced,
        "shapes": shapes, "peaks": peaks,
    }
    if keep is not None:
        keep.update(ctx)
    if trace:
        values.update(per_layer_values(manifest, name, ctx))
    metrics = {}
    for m in metrics_of(manifest, "per_layer" if trace else "end_to_end", name):
        v = values.get(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {
        "correct": bool(correct), "attempted": out["attempted"],
        "failed": out["failed"], "metrics": metrics, "device": device,
    }
    if traced:
        result["device"].update(
            busy_s=traced["device"]["busy_s"], window_s=traced["device"]["window_s"]
        )
        result["breakdown"] = traced["breakdown"]
    result["compared"] = {
        k: {"value": v, "limit": limits[k]} for k, v in out["numbers"].items()
    }
    for k, v in out["numbers"].items():
        print(f"compared {k}={v} limit={limits[k]}", file=sys.stderr)
    print(f"correct={correct} failed_pods={out['failed']}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=faults.names(), default=None,
                    help="break the timed path (controls; not a measurement)")
    ap.add_argument("--dump-trace", default=None,
                    help="write an overview of the trace to this file")
    args = ap.parse_args(argv)

    from k8s1m_tpu.envboot import place_compile_cache

    cache_dir = place_compile_cache()
    import jax

    manifest = read_json("BENCHMARK.json")
    cell_files = load_cell(manifest, args.workload)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']} compile cache: {cache_dir}")
    chips = int(cell_files[0]["chips"])
    if device["platform"] != "tpu" or device["count"] < chips:
        print(f"benchmark: needs {chips} TPU chip(s), found "
              f"{device['count']} x {device['platform']!r}; refusing to run",
              file=sys.stderr)
        return 2
    peaks = read_json("benchmark", "peaks.json")
    if device["kind"] not in peaks:
        print(f"benchmark: no peaks for device {device['kind']!r}", file=sys.stderr)
        return 2
    result = run_cell(
        manifest, args.workload, cell_files, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), device=device,
        peaks=peaks[device["kind"]], fault=args.fault, dump_trace=args.dump_trace,
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
