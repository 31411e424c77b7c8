"""From a profiler trace to numbers: one reduction, patterns as data.

``load_trace`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
plain tuples, once; everything below it works on those tuples alone, so
the tests drive it with a handful of synthetic intervals.

An event is ``(plane, line, name, start_s, dur_s)``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("bench.", "coord.", "feed.")
# The stat of a device op's XEventMetadata that holds its op_name, the
# "jit(f)/scope/.../primitive:" path jax gives every HLO instruction.
OP_NAME_STAT = "tf_op"
# Timestamps are seconds since the epoch as floats, a quarter of a
# microsecond apart: two events end "together" within this.
EDGE_S = 1e-6


def _xspace_class():
    """The few fields of the profiler's XSpace that the reduction reads,
    as a protobuf message of its own (jax's ``ProfileData`` takes minutes
    over the millions of events a while loop leaves; this takes seconds):
    events, each line's name and id (two threads' lines can share a
    name), and the event metadata's stats with the stats' names (XLA
    keeps a device op's op_name there).  Field numbers are
    tsl/profiler/protobuf/xplane.proto's."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    T = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmark_xplane.proto", package="benchmark_xplane", syntax="proto3"
    )

    def msg(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, num, ftype, rep, tname in fields:
            m.field.add(
                name=fname, number=num, type=ftype, type_name=tname,
                label=T.LABEL_REPEATED if rep else T.LABEL_OPTIONAL,
            )

    P = ".benchmark_xplane."
    msg("XEvent", ("metadata_id", 1, T.TYPE_INT64, 0, None),
        ("offset_ps", 2, T.TYPE_INT64, 0, None),
        ("duration_ps", 3, T.TYPE_INT64, 0, None))
    msg("XLine", ("id", 1, T.TYPE_INT64, 0, None),
        ("name", 2, T.TYPE_STRING, 0, None),
        ("timestamp_ns", 3, T.TYPE_INT64, 0, None),
        ("events", 4, T.TYPE_MESSAGE, 1, P + "XEvent"))
    msg("XStat", ("metadata_id", 1, T.TYPE_INT64, 0, None),
        ("str_value", 5, T.TYPE_STRING, 0, None),
        ("ref_value", 7, T.TYPE_UINT64, 0, None))
    msg("XEventMetadata", ("id", 1, T.TYPE_INT64, 0, None),
        ("name", 2, T.TYPE_STRING, 0, None),
        ("stats", 5, T.TYPE_MESSAGE, 1, P + "XStat"))
    msg("EventEntry", ("key", 1, T.TYPE_INT64, 0, None),
        ("value", 2, T.TYPE_MESSAGE, 0, P + "XEventMetadata"))
    msg("XStatMetadata", ("id", 1, T.TYPE_INT64, 0, None),
        ("name", 2, T.TYPE_STRING, 0, None))
    msg("StatEntry", ("key", 1, T.TYPE_INT64, 0, None),
        ("value", 2, T.TYPE_MESSAGE, 0, P + "XStatMetadata"))
    msg("XPlane", ("name", 2, T.TYPE_STRING, 0, None),
        ("lines", 3, T.TYPE_MESSAGE, 1, P + "XLine"),
        ("event_metadata", 4, T.TYPE_MESSAGE, 1, P + "EventEntry"),
        ("stat_metadata", 5, T.TYPE_MESSAGE, 1, P + "StatEntry"))
    msg("XSpace", ("planes", 1, T.TYPE_MESSAGE, 1, P + "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchmark_xplane.XSpace")
    )


def trace_file(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_trace(trace_dir: str) -> dict:
    """The one loader.  ``events``: every event as ``(plane, line, name,
    start_s, dur_s)``.  ``op_names``: ``{device plane: {event name:
    op_name}}`` for the device events whose metadata carries one (XLA
    keeps the ``jit(f)/scope/.../primitive:`` path out of the event's
    name, which is the HLO instruction's text, and in the ``tf_op`` stat
    of its XEventMetadata).  ``host_spans``: ``(line id, name, start_s,
    dur_s)`` of the host's ``bench.`` / ``coord.`` / ``feed.``
    annotations."""
    with open(trace_file(trace_dir), "rb") as f:
        space = _xspace_class().FromString(f.read())
    events, host_spans = [], []
    op_names: dict[str, dict[str, str]] = {}
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.event_metadata}
        pname = plane.name
        if DEVICE_PLANE.match(pname):
            stat_name = {e.key: e.value.name for e in plane.stat_metadata}
            ops = op_names.setdefault(pname, {})
            for entry in plane.event_metadata:
                for st in entry.value.stats:
                    if stat_name.get(st.metadata_id) == OP_NAME_STAT:
                        ops[entry.value.name] = (
                            st.str_value or stat_name.get(st.ref_value, "")
                        )
        for line in plane.lines:
            lname, base = line.name, line.timestamp_ns * 1e-9
            for ev in line.events:
                name = names.get(ev.metadata_id, "")
                start, dur = base + ev.offset_ps * 1e-12, ev.duration_ps * 1e-12
                events.append((pname, lname, name, start, dur))
                if pname == HOST_PLANE and name.startswith(HOST_SPANS):
                    host_spans.append((line.id, name, start, dur))
    return {"events": events, "op_names": op_names, "host_spans": host_spans}


def load(trace_dir: str) -> list[tuple[str, str, str, float, float]]:
    return load_trace(trace_dir)["events"]


def short_name(name: str) -> str:
    """An HLO op's name without its text: ``%while.7``; a Mosaic kernel
    keeps its mark (``%_call.1 tpu_custom_call``)."""
    head = name.split(" = ", 1)[0]
    if 'custom_call_target="tpu_custom_call"' in name:
        head += " tpu_custom_call"
    return head[:120]


def device_planes(events) -> list[str]:
    return sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])})


def select(events, plane: str, line: str, pattern: str | None = None):
    rx = re.compile(pattern) if pattern else None
    return [
        e for e in events
        if e[0] == plane and e[1] == line and (rx is None or rx.search(e[2]))
    ]


def union_seconds(intervals) -> float:
    """Total length covered by ``(start, dur)`` intervals."""
    total, end = 0.0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def busy_window(events, t0: float, t1: float) -> dict:
    """Seconds in which an operation ran on the device inside
    ``[t0, t1]``, averaged over the device planes, and the window's
    length.  Idle share = 1 - busy_s / window_s."""
    planes = device_planes(events)
    busy = []
    for p in planes:
        clipped = []
        for _p, _l, _n, s, d in select(events, p, OPS_LINE):
            lo, hi = max(s, t0), min(s + d, t1)
            if hi > lo:
                clipped.append((lo, hi - lo))
        busy.append(union_seconds(clipped))
    if not busy:
        raise RuntimeError("trace holds no device plane")
    return {"busy_s": sum(busy) / len(busy), "window_s": t1 - t0,
            "planes": len(planes)}


def per_event(events, plane: str, line: str, pattern: str) -> tuple[float, int]:
    """Summed duration and count of the events on ``line`` that match,
    cut by the trace or not (the per-wave readers take ``whole_waves``)."""
    sel = select(events, plane, line, pattern)
    return sum(e[4] for e in sel), len(sel)


def whole_waves(events, plane: str, line: str, pattern: str
                ) -> list[tuple[float, float]]:
    """``(start, end)`` of the events on ``line`` that match ``pattern``
    and that the trace holds whole, in order.  The profiler starts and
    stops in the middle of whatever the device is doing, and an event it
    cut is there all the same, from the trace's first instant or up to its
    last, with its head or its tail missing; as a wave it would count one
    for a part of one.  Nothing in an event says that it was cut, so one
    is whole only where the line holds something before it and something
    after it: the event that starts at the line's first timestamp and the
    one that ends at its last are left out, cut or not (a whole wave left
    out moves no time per wave)."""
    on_line = select(events, plane, line)
    if not on_line:
        return []
    first = min(e[3] for e in on_line)
    last = max(e[3] + e[4] for e in on_line)
    rx = re.compile(pattern)
    return sorted(
        (s, s + d) for _p, _l, name, s, d in on_line
        if rx.search(name) and s > first + EDGE_S and s + d < last - EDGE_S
    )


def inside(waves, intervals) -> list:
    """Those of ``intervals``, tuples that begin ``(start, dur, ...)``,
    that lie inside one of ``waves`` (``whole_waves``: in order, none over
    another): what ran in a step the trace cut belongs to no wave."""
    starts = [w[0] for w in waves]
    kept = []
    for iv in intervals:
        i = bisect.bisect_right(starts, iv[0] + EDGE_S) - 1
        if i >= 0 and iv[0] + iv[1] <= waves[i][1] + EDGE_S:
            kept.append(iv)
    return kept


def sums_by_name(events, plane: str, line: str, top: int = 10):
    sums: dict[str, float] = {}
    for _p, _l, name, _s, d in select(events, plane, line):
        sums[name] = sums.get(name, 0.0) + d
    return sorted(sums.items(), key=lambda kv: -kv[1])[:top]


def idle_gaps(events, plane: str, host_spans, t0: float, t1: float,
              top: int = 10):
    """Summed idle time of the device by what the host was doing at the
    middle of each gap: ``host_spans`` are ``(name, start_s, dur_s)`` of
    the benchmark's own annotations; a gap that no span covers is
    ``unattributed``."""
    ops = sorted(
        (max(s, t0), min(s + d, t1))
        for _p, _l, _n, s, d in select(events, plane, OPS_LINE)
        if s + d > t0 and s < t1
    )
    gaps, end = [], t0
    for s, e in ops:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if t1 > end:
        gaps.append((end, t1))
    spans = sorted((s, s + d, n) for n, s, d in host_spans)
    out: dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) / 2
        name = "unattributed"
        for hs, he, hn in spans:
            if hs <= mid < he:
                name = hn
                break
        out[name] = out.get(name, 0.0) + (e - s)
    return sorted(out.items(), key=lambda kv: -kv[1])[:top]


def overview(events, top: int = 25) -> str:
    """What a trace holds — planes, lines, busiest names — for the first
    look by hand."""
    lines: dict[tuple[str, str], dict[str, list]] = {}
    for p, l, n, _s, d in events:
        rec = lines.setdefault((p, l), {}).setdefault(n, [0, 0.0])
        rec[0] += 1
        rec[1] += d
    out = []
    for (p, l), names in sorted(lines.items()):
        out.append(f"{p} | {l}: {sum(r[0] for r in names.values())} events, "
                   f"{len(names)} names")
        for n, (c, d) in sorted(names.items(), key=lambda kv: -kv[1][1])[:top]:
            out.append(f"    {d:10.6f}s {c:8d}x {n[:160]}")
    return "\n".join(out)
