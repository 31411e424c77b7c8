"""A reader for a ``jax.named_scope`` that is not one of the step's three
phases.  ``span_readers.trace_scope_ms_per_wave`` splits the device's ops
over ``span_readers.SCOPES`` (``candidates``, ``assign``, ``commit``), each
op to the first of them on its path, so a scope opened *inside* one of
them (``candidates/cons_prologue``) reads as its parent there.  This one
asks for the named scope alone, wherever on the path it stands; the
parent's reading still holds it.
"""

from __future__ import annotations

from benchmark import span_readers, trace_reduce


def named_scope_ms_per_wave(args: dict, ctx: dict):
    """Device milliseconds per whole wave of the ops whose op_name path
    has the component ``args.scope``: their union inside the whole
    ``args.wave_pattern`` events on ``args.wave_line``
    (``trace_reduce.whole_waves``), over the count of those events.
    Nothing where the trace has no op_names, no whole wave, or no op
    under that scope (a program that never opens it)."""
    tr = ctx.get("trace")
    if tr is None or not tr.get("op_names"):
        return None
    waves = trace_reduce.whole_waves(
        tr["events"], tr["plane"], args["wave_line"], args["wave_pattern"]
    )
    scope = args["scope"]
    covered = trace_reduce.inside(waves, [
        (s, d) for s, d, found in span_readers.scoped_ops(
            tr["events"], tr["plane"], tr["op_names"], (scope,))
        if found == scope
    ])
    if not covered or not waves:
        return None
    return 1e3 * trace_reduce.union_seconds(covered) / len(waves)
