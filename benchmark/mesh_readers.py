"""A reader for a candidates kernel's roofline share on one chip of a mesh.

``readers.trace_roofline_pct`` counts the bytes of the whole table's window
(``shapes.scan_rows``) against the kernel's time on the device plane the
trace reads.  On a mesh that plane is one chip's, and the chip scans its
own shard's window alone, so that count would read ``sp`` times high.  This
one takes the window of one shard, from the configuration the metric file
names: its rows over ``sp``, at its ``score_pct``, in whole chunks, as the
program samples a shard; and the chip's part of the wave's pods.
"""

from __future__ import annotations

import json
import os

from benchmark import readers, roofline

CONFIGS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs")


def shard_roofline_pct(args: dict, ctx: dict):
    """HBM roofline share of the kernel ``args.pattern`` names on one chip
    of the mesh of ``args.config``: least seconds for the bytes the chip's
    shard must move for one wave over the kernel's measured seconds a whole
    wave (``readers.trace_ms_per_wave``).  Nothing where the trace has no
    whole wave of it, or the configuration runs on one device."""
    ms = readers.trace_ms_per_wave(args, ctx)
    if ms is None:
        return None
    with open(os.path.join(CONFIGS, f"{args['config']}.json")) as f:
        config = json.load(f)
    coord = config["coordinator"]
    mesh = str(coord.get("mesh", "none")).lower()
    if "x" not in mesh:
        return None
    dp, sp = (int(n) for n in mesh.split("x"))
    s = ctx["shapes"]
    moved = roofline.wave_bytes(
        scan_rows=roofline.window_rows(
            config["table_spec"]["max_nodes"] // sp,
            int(coord.get("score_pct", 100)), int(coord["chunk"]),
        ),
        bytes_per_row=roofline.row_bytes(
            s["columns"], args.get("columns", roofline.BASE_COLUMNS)),
        batch=s["batch"] // dp, k=s["k"], pod_bytes=s["pod_bytes"],
    )
    return roofline.hbm_share_pct(
        moved, ms * 1e-3, ctx["peaks"]["hbm_bytes_per_s"]
    )
