"""Readers of what the program itself names: ``jax.named_scope`` phases of
the device step, ``coord.*`` host spans, lane counters, set-up stages.

A metric file names one of them as ``span_readers.<function>``
(``readers.resolve``); the harness hands them, in ``ctx`` (the table in
``readers.py``), ``trace["op_names"]``, ``counters`` and
``setup_stage_s``.  The reductions below the readers work on plain
tuples, so the tests drive them with a handful of synthetic intervals.
"""

from __future__ import annotations

import bisect

from benchmark import trace_reduce

SCOPES = ("candidates", "assign", "commit")


def load_names(trace_dir: str) -> dict:
    """``op_names`` and ``host_spans`` of ``trace_reduce.load_trace``.
    The harness calls the loader itself; tests/test_coord_spans.py, which
    a ``benchmark`` PR may not edit, still reads its spans through this."""
    loaded = trace_reduce.load_trace(trace_dir)
    return {"op_names": loaded["op_names"], "host_spans": loaded["host_spans"]}


# ---- reductions on plain tuples -------------------------------------------


def scope_of(op_name: str, scopes=SCOPES) -> str | None:
    """The first of ``scopes`` among the op_name path's components."""
    parts = op_name.rstrip(":").split("/")
    return next((sc for sc in scopes if sc in parts), None)


def scoped_ops(events, plane: str, op_names: dict, scopes=SCOPES):
    """``(start, dur, scope)`` of the device's ``XLA Ops`` events, scope
    None where there is none.  An event with an op_name takes its scope
    from it.  One without (XLA's TPU pipeline leaves a ``while`` none)
    that contains other events takes the scope they all share: a loop's
    own time belongs with its body's."""
    ops = sorted(
        ((s, d, scope_of(op_names[n], scopes) if n in op_names else None,
          n in op_names)
         for _p, _l, n, s, d in
         trace_reduce.select(events, plane, trace_reduce.OPS_LINE)),
        key=lambda o: (o[0], -o[1]),            # a container before its contents
    )
    starts = [o[0] for o in ops]
    out = []
    for i, (s, d, scope, named) in enumerate(ops):
        if not named:
            inside = ops[i + 1:bisect.bisect_left(starts, s + d, i + 1)]
            shared = {o[2] for o in inside if o[3]}
            if len(shared) == 1:
                scope = shared.pop()
        out.append((s, d, scope))
    return out


def device_scopes(events, plane: str, op_names: dict, t0: float, t1: float,
                  scopes=SCOPES) -> list[list]:
    """Seconds the device was busy inside ``[t0, t1]``, by named scope: the
    union of each scope's intervals (a loop's event contains its body's, so
    a sum would count them twice), largest first, and what no scope covers
    as ``unscoped``.  The rows add up to ``busy_window``'s ``busy_s``."""
    ops = scoped_ops(events, plane, op_names, scopes)

    def busy(keep) -> float:
        return trace_reduce.union_seconds(
            (max(s, t0), min(s + d, t1) - max(s, t0))
            for s, d, sc in ops if keep(sc) and min(s + d, t1) > max(s, t0)
        )

    rows = sorted(
        ([sc, busy(lambda x, sc=sc: x == sc)] for sc in scopes),
        key=lambda r: -r[1],
    )
    rows.append(["unscoped",
                 busy(lambda x: True) - busy(lambda x: x is not None)])
    return rows


def innermost_segments(spans) -> list[tuple[float, float, str]]:
    """Nested ``(start, end, name)`` spans of one thread, flattened to
    ``(start, end, name)`` segments that do not overlap: every instant
    goes to the innermost span that covers it."""
    segs, stack, at = [], [], 0.0

    def close_until(t):
        nonlocal at
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > at:
                segs.append((at, end, name))
                at = end

    for start, end, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        close_until(start)
        if stack and start > at:
            segs.append((at, start, stack[-1][1]))
        stack.append((end, name))
        at = start
    close_until(float("inf"))
    return segs


def idle_by_span(events, plane: str, host_spans, t0: float, t1: float,
                 root: str = "bench.step", top: int = 12):
    """The device's idle seconds inside ``[t0, t1]`` by what the host loop
    was in meanwhile: each gap between ``XLA Ops`` is split over the
    **innermost** spans that overlap it, second for second (the pipeline
    leaves one long gap a wave, and a rule that gives a whole gap to one
    span names only the longest stage).  ``host_spans`` are ``load_trace``'s
    ``(line id, name, start_s, dur_s)``; only those on the line that holds
    ``root`` count (the loop's own thread: another thread's span, the
    hotfeed worker's say, overlaps the wave and explains no wait, and the
    two lines may share a name); idle time no span covers is
    ``unattributed``."""
    lines = {lid for lid, n, _s, _d in host_spans if n == root}
    segs = innermost_segments(
        (s, s + d, n) for lid, n, s, d in host_spans if lid in lines
    )
    ends = [seg[1] for seg in segs]
    ops = sorted(
        (max(s, t0), min(s + d, t1))
        for _p, _l, _n, s, d in
        trace_reduce.select(events, plane, trace_reduce.OPS_LINE)
        if s + d > t0 and s < t1
    )
    out: dict[str, float] = {}
    at = t0
    for s, e in [*ops, (t1, t1)]:
        if s > at:
            covered = 0.0
            for a, b, name in segs[bisect.bisect_right(ends, at):]:
                if a >= s:
                    break
                part = min(b, s) - max(a, at)
                out[name] = out.get(name, 0.0) + part
                covered += part
            if s - at > covered:
                out["unattributed"] = out.get("unattributed", 0.0) \
                    + (s - at - covered)
        at = max(at, e)
    return sorted(out.items(), key=lambda kv: -kv[1])[:top]


def snapshot_counters(names=None) -> dict:
    """``{counter: {labels: value}}`` of the program's registry now, labels
    as a sorted tuple of ``(name, value)``: every counter the registry
    holds, or the ``names`` of them that it holds."""
    from k8s1m_tpu.obs.metrics import REGISTRY, Counter

    metrics = REGISTRY.metrics() if names is None else map(REGISTRY.get, names)
    counters = [m for m in metrics if isinstance(m, Counter)]
    return {
        c.name: {
            tuple(sorted(zip(c.labelnames, key))):
                c.value(**dict(zip(c.labelnames, key)))
            for key in c.label_keys()
        }
        for c in counters
    }


def stage_sums() -> dict[str, float]:
    """Every ``stage`` label ``coordinator_cycle_seconds`` holds now."""
    from k8s1m_tpu.obs.metrics import REGISTRY

    cyc = REGISTRY.get("coordinator_cycle_seconds")
    return {k[0]: cyc.sum(stage=k[0]) for k in cyc.label_keys()}


# ---- readers --------------------------------------------------------------


def trace_scope_ms_per_wave(args: dict, ctx: dict):
    """Device milliseconds under the named scope per whole wave: the union
    of the ``XLA Ops`` intervals whose op_name path holds ``args.scope``
    and that lie inside a whole ``args.wave_pattern`` event on
    ``args.wave_line`` (``trace_reduce.whole_waves``), over the count of
    those events."""
    tr = ctx.get("trace")
    if tr is None or not tr.get("op_names"):
        return None
    waves = trace_reduce.whole_waves(
        tr["events"], tr["plane"], args["wave_line"], args["wave_pattern"]
    )
    covered = trace_reduce.inside(waves, [
        (s, d) for s, d, scope in
        scoped_ops(tr["events"], tr["plane"], tr["op_names"])
        if scope == args["scope"]
    ])
    if not covered or not waves:
        return None
    return 1e3 * trace_reduce.union_seconds(covered) / len(waves)


def counter_share_pct(args: dict, ctx: dict):
    """The increase of ``args.counter``'s ``args.labels`` label sets over the
    increase of its ``args.of`` label sets (all of the counter's when
    ``of`` is missing), in %: over the window (``over: "window"``, open to
    close) or over set-up (``over: "setup"``, process start to open)."""
    snaps = ctx.get("counters")
    if not snaps or args["counter"] not in snaps["open"]:
        return None
    at_open = snaps["open"][args["counter"]]
    at_close = snaps["close"].get(args["counter"], {})

    def grown(label_sets) -> float:
        total = 0.0
        for key in label_sets:
            if args["over"] == "setup":
                total += at_open.get(key, 0.0)
            else:
                total += at_close.get(key, 0.0) - at_open.get(key, 0.0)
        return total

    as_key = lambda labels: tuple(sorted(labels.items()))
    of = [as_key(l) for l in args["of"]] if "of" in args \
        else set(at_open) | set(at_close)
    whole = grown(of)
    if not whole:
        return None
    return 100.0 * grown([as_key(l) for l in args["labels"]]) / whole


def setup_stage_s(args: dict, ctx: dict):
    """Seconds of the named coordinator stages observed before the window
    opened (set-up)."""
    before = ctx.get("setup_stage_s")
    if not before or not any(s in before for s in args["stages"]):
        return None
    return sum(before.get(s, 0.0) for s in args["stages"])
