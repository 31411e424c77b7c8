"""Per-layer metric readers.  A metric is a file
``benchmark/metrics/<name>.json`` that names a reader and its arguments:
a bare name is one of ``READERS`` below, ``<module>.<function>`` is that
function of the module ``benchmark/<module>.py`` (``resolve``), so a
reader a later PR brings is a file of its own and needs no registration.
A reader is ``f(args, ctx)``; one that finds nothing to read returns None
and the metric is left out of the line.

The context ``ctx`` a reader gets:
  stage_s        {stage: seconds}: every label coordinator_cycle_seconds
                 holds at window close, summed over the window
  setup_stage_s  the same sums just before the window resets them (set-up)
  counters       {"open": snap, "close": snap} around the window, each
                 {counter: {labels: value}} of every counter in the
                 program's registry, labels as a sorted tuple of
                 (name, value) pairs
  binds          binds the client saw in the window
  trace          None, or the traced part of the window: {"events",
                 "plane", "op_names" ({event name: op_name} of the device
                 plane), "host_spans" ((line id, name, start_s, dur_s))}
  shapes         what roofline.py needs, read from the live table's shapes:
                 scan_rows, columns {name: (itemsize, elements per row)},
                 batch, k, pod_bytes
  peaks          this device's entry of peaks.json
"""

from __future__ import annotations

import importlib
import re

from benchmark import roofline, trace_reduce

_DOTTED = re.compile(r"^([A-Za-z_][A-Za-z0-9_]*)\.([A-Za-z_][A-Za-z0-9_]*)$")


def registry_stage_per_bind(args: dict, ctx: dict):
    """Microseconds of the named coordinator stages per bind; nothing
    when the program observed none of them in the window."""
    stage_s = ctx["stage_s"]
    if not ctx["binds"] or not any(s in stage_s for s in args["stages"]):
        return None
    return 1e6 * sum(stage_s.get(s, 0.0) for s in args["stages"]) / ctx["binds"]


def trace_ms_per_wave(args: dict, ctx: dict):
    """Device milliseconds of the events on ``args.line`` that match
    ``args.pattern``, per whole wave (``trace_reduce.whole_waves``): the
    whole ones of them over their own count (a module that runs once a
    wave), or those of them inside a whole ``args.wave_pattern`` event on
    ``args.wave_line`` over the count of these (a kernel inside the step).
    Sum and count are over the same steps: what the trace holds of a step
    it cut is in neither."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    waves = trace_reduce.whole_waves(
        tr["events"], tr["plane"], args.get("wave_line", args["line"]),
        args.get("wave_pattern", args["pattern"]),
    )
    if "wave_pattern" in args:
        durs = [d for _s, d in trace_reduce.inside(waves, [
            (s, d) for _p, _l, _n, s, d in trace_reduce.select(
                tr["events"], tr["plane"], args["line"], args["pattern"])
        ])]
    else:
        durs = [end - start for start, end in waves]
    if not durs or not waves:
        return None
    return 1e3 * sum(durs) / len(waves)


def trace_roofline_pct(args: dict, ctx: dict):
    """HBM roofline share of a candidates kernel: least seconds for the
    bytes one wave must move over the kernel's measured seconds a whole
    wave.  The table columns counted are ``args.columns`` (the plugins the
    kernel's deployment runs decide them; default
    ``roofline.BASE_COLUMNS``)."""
    ms = trace_ms_per_wave(args, ctx)
    if ms is None:
        return None
    s = ctx["shapes"]
    moved = roofline.wave_bytes(
        scan_rows=s["scan_rows"],
        bytes_per_row=roofline.row_bytes(
            s["columns"], args.get("columns", roofline.BASE_COLUMNS)),
        batch=s["batch"], k=s["k"], pod_bytes=s["pod_bytes"],
    )
    return roofline.hbm_share_pct(
        moved, ms * 1e-3, ctx["peaks"]["hbm_bytes_per_s"]
    )


READERS = {
    "registry_stage_per_bind": registry_stage_per_bind,
    "trace_module_ms_per_wave": trace_ms_per_wave,
    "trace_kernel_ms_per_wave": trace_ms_per_wave,
    "trace_roofline_pct": trace_roofline_pct,
}


def resolve(name: str):
    """The reader a metric file names."""
    if name in READERS:
        return READERS[name]
    dotted = _DOTTED.match(name)
    if not dotted:
        raise LookupError(f"reader {name!r}: not one of {sorted(READERS)} "
                          "and not <module>.<function>")
    module, function = dotted.groups()
    try:
        reader = getattr(importlib.import_module(f"benchmark.{module}"), function)
    except (ImportError, AttributeError) as e:
        raise LookupError(f"reader {name!r}: {e}") from None
    if not callable(reader):
        raise LookupError(f"reader {name!r} is not a function")
    return reader
