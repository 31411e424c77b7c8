"""Per-layer metric readers.  A metric is a file
``benchmark/metrics/<name>.json`` that names one of these and its
arguments; a reader that finds nothing to read returns None and the
metric is left out of the line.

The context ``ctx`` a reader gets:
  stage_s   {stage: seconds} summed from coordinator_cycle_seconds over the window
  binds     binds the client saw in the window
  trace     None, or {"events", "plane"} of the traced part of the window
  shapes    what roofline.py needs, read from the live table's shapes
  peaks     this device's entry of peaks.json
"""

from __future__ import annotations

from benchmark import roofline, trace_reduce


def registry_stage_per_bind(args: dict, ctx: dict):
    """Microseconds of the named coordinator stages per bind."""
    if not ctx["binds"]:
        return None
    total = sum(ctx["stage_s"].get(s, 0.0) for s in args["stages"])
    return 1e6 * total / ctx["binds"]


def trace_ms_per_wave(args: dict, ctx: dict):
    """Device milliseconds of the events on ``args.line`` that match
    ``args.pattern``, per wave: over their own count (a module that runs
    once a wave), or over the count of ``args.wave_pattern`` events on
    ``args.wave_line`` (a kernel inside the step)."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    total, count = trace_reduce.per_event(
        tr["events"], tr["plane"], args["line"], args["pattern"]
    )
    waves = count
    if "wave_pattern" in args:
        _t, waves = trace_reduce.per_event(
            tr["events"], tr["plane"], args["wave_line"], args["wave_pattern"]
        )
    if not count or not waves:
        return None
    return 1e3 * total / waves


def trace_roofline_pct(args: dict, ctx: dict):
    """HBM roofline share of the candidates kernel: least seconds for the
    bytes one wave must move over the kernel's measured seconds."""
    ms = trace_ms_per_wave(args, ctx)
    if ms is None:
        return None
    s = ctx["shapes"]
    moved = roofline.wave_bytes(
        scan_rows=s["scan_rows"], bytes_per_row=s["bytes_per_row"],
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
