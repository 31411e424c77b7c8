"""One traced run of a benchmark cell, read by the program's own names.

    python3 tools/span_report.py --workload <cell> --seed <n> --seconds <s>

``benchmark/run.py`` runs the cell unedited (``--trace 1``); this wrapper
collects beside it what the readers of ``benchmark/span_readers.py`` need
and ``run.py`` does not hand over yet -- every stage label at window
close, the stage sums before the window's reset, counter snapshots at
window open and close, the device ops' op_names -- and adds to the result
line ``span_metrics`` (the eleven of ``benchmark/span_metrics.json``) and,
under ``breakdown``, ``idle_by_span`` and ``device_scopes``.  It is
the stand-in until a ``benchmark`` PR wires ``run.py`` (PERF.md section
7), and is no part of the yardstick.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run, span_readers, trace_reduce
from benchmark.readers import READERS

SPECS = run.read_json("benchmark", "span_metrics.json")["metrics"]
# What the metric specs read, and the intake's shape table, which no
# metric reads: its one decode falls in the warm-up, so it is logged at
# the window's close as well as over the window.
COUNTERS = sorted(
    {m["args"]["counter"] for m in SPECS if "counter" in m["args"]}
    | {"coordinator_pod_shapes_total"}
)


class SpanCell(run.Cell):
    """``run.Cell`` that keeps what the span readers read."""

    last: "SpanCell | None" = None
    keep_trace: str | None = None

    def __init__(self, *args, **kw) -> None:
        super().__init__(*args, **kw)
        self.ctx: dict = {}
        SpanCell.last = self

    def window(self, seconds: float, trace_seconds: float = 0.0) -> dict:
        # nothing runs between here and the window's reset and first put
        self.ctx["setup_stage_s"] = span_readers.stage_sums()
        counters = {"open": span_readers.snapshot_counters(COUNTERS)}
        win = super().window(seconds, trace_seconds)
        # nor between the loop's end and here but the trace's stop
        counters["close"] = span_readers.snapshot_counters(COUNTERS)
        win["stage_s"] = span_readers.stage_sums()
        self.ctx.update(counters=counters, stage_s=win["stage_s"])
        return win

    def check(self, win: dict) -> dict:
        out = super().check(win)
        self.ctx["binds"] = out["binds"]
        return out


_read_trace = run.read_trace


def read_trace(cell: SpanCell) -> dict:
    """``run.read_trace`` (which deletes the trace), and before it the
    names ``trace_reduce.load`` drops; after it the two breakdowns by the program's names."""
    names = span_readers.load_names(run.TRACE_DIR)
    if cell.keep_trace:
        os.makedirs(os.path.dirname(cell.keep_trace), exist_ok=True)
        shutil.copy(trace_reduce.trace_file(run.TRACE_DIR), cell.keep_trace)
    traced = _read_trace(cell)
    events, plane = traced["events"], traced["plane"]
    traced["op_names"] = names["op_names"].get(plane, {})
    traced["host_spans"] = names["host_spans"]
    bench = [(s, s + d) for _p, _l, n, s, d in events if n.startswith("bench.")]
    t0, t1 = min(s for s, _e in bench), max(e for _s, e in bench)
    traced["breakdown"]["idle_by_span"] = [
        [n, s] for n, s in span_readers.idle_by_span(
            events, plane, traced["host_spans"], t0, t1
        )
    ]
    traced["breakdown"]["device_scopes"] = span_readers.device_scopes(
        events, plane, traced["op_names"], t0, t1
    )
    cell.ctx["trace"] = traced
    return traced


def span_metrics(ctx: dict) -> dict:
    """The eleven, each by the reader its spec names; one that finds
    nothing to read is left out."""
    out = {}
    by_name = {**READERS, **span_readers.READERS}
    for m in SPECS:
        if m["reader"] in READERS and not all(
            s in ctx["stage_s"] for s in m["args"].get("stages", ())
        ):
            continue            # the accepted reader would read a nought
        v = by_name[m["reader"]](m["args"], ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def report(manifest: dict, name: str, cell_files, **kw) -> dict:
    """``run.run_cell`` with the two hooks in place, and the result line
    with ``span_metrics`` added."""
    cell, reader = run.Cell, run.read_trace
    run.Cell, run.read_trace = SpanCell, read_trace
    try:
        result = run.run_cell(manifest, name, cell_files, **kw)
    finally:
        run.Cell, run.read_trace = cell, reader
    ctx = SpanCell.last.ctx
    result["span_metrics"] = span_metrics(ctx)
    run.log("set-up stage seconds: " + " ".join(
        f"{k}={v:.3f}" for k, v in ctx["setup_stage_s"].items()))
    for name in COUNTERS:
        at_open = ctx["counters"]["open"].get(name, {})
        at_close = {
            ",".join(v for _k, v in key): (n, n - at_open.get(key, 0.0))
            for key, n in ctx["counters"]["close"].get(name, {}).items()
        }
        run.log(f"{name} in the window: " + " ".join(
            f"{k}={grown:.0f}" for k, (_n, grown) in sorted(at_close.items())
        ) + "; since the process began: " + " ".join(
            f"{k}={n:.0f}" for k, (n, _grown) in sorted(at_close.items())))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the .xplane.pb to this file")
    ap.add_argument("--dump-trace", default=None,
                    help="write an overview of the trace to this file")
    args = ap.parse_args(argv)

    from k8s1m_tpu.envboot import place_compile_cache

    place_compile_cache()
    import jax

    manifest = run.read_json("BENCHMARK.json")
    cell_files = run.load_cell(manifest, args.workload)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    peaks = run.read_json("benchmark", "peaks.json")
    if device["platform"] != "tpu" or device["kind"] not in peaks:
        print(f"span_report: needs a TPU with peaks, found {device}",
              file=sys.stderr)
        return 2
    SpanCell.keep_trace = args.keep_trace
    result = report(
        manifest, args.workload, cell_files, seed=args.seed,
        seconds=args.seconds, trace=True, device=device,
        peaks=peaks[device["kind"]], dump_trace=args.dump_trace,
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
