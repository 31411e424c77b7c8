"""Component-cost probe for the fused pallas kernel.

Times fused_topk over a pct-window-sized table with individual score
plugins disabled — weights are static arguments, so a zeroed plugin is
dead-code-eliminated from the trace and its cost shows up as the delta
against the full profile.  The tool for answering "where do the
ms/batch go" on the real chip (the XLA scan path can be profiled the
same way through bench.py --backend xla).

    python -m k8s1m_tpu.tools.kernel_probe --nodes 53248 --batch 8192

Prints one JSON line per variant.  Run variants serially on the one
real chip; each recompiles (~15-30s).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from k8s1m_tpu.config import PodSpec, TableSpec
from k8s1m_tpu.cluster import populate_kwok_nodes, uniform_pods
from k8s1m_tpu.ops.pallas_topk import fused_topk
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot import NodeTableHost, PodBatchHost


def variants() -> dict[str, Profile]:
    base = dict(node_affinity=0, topology_spread=0, interpod_affinity=0)
    return {
        "full": Profile(**base),
        "no-least-allocated": Profile(least_allocated=0, **base),
        "no-balanced-allocation": Profile(balanced_allocation=0, **base),
        "no-taint-toleration": Profile(taint_toleration=0, **base),
        "filter-only": Profile(
            least_allocated=0, balanced_allocation=0, taint_toleration=0,
            **base,
        ),
    }


def profile_stages(
    table,
    enc,
    *,
    chunk: int,
    k: int = 4,
    steps: int = 3,
    repeats: int = 3,
    backend: str = "xla",
    only: set[str] | None = None,
) -> dict:
    """Per-stage ms/batch via the plugin-knockout DCE trick, reusable
    from sched_bench's ``--kernel-profile`` lane.

    Zeroed plugin weights are static arguments, so a disabled scorer is
    dead-code-eliminated from the trace; ``full - no-X`` is X's cost and
    ``filter-only`` is the irreducible filter+top-k floor.  ``table``
    may be either snapshot layout (packed tables decode in the chunk
    slice, so the probe measures the production decode cost too); ``enc``
    is a PodBatchHost-compatible encoder sharing the table's vocab.

    Each variant is timed as the MIN over ``repeats`` independent
    ``steps``-iteration blocks: the minimum is the right estimator for
    a deterministic program under one-sided scheduler noise, and a
    single-block mean let a noisy ``full`` sample push knockout deltas
    negative (the committed taint_toleration -3.524 ms/batch artifact).
    Deltas can still dip slightly negative at tiny shapes; they are
    reported raw, not clamped — but ``repeats`` is recorded in the
    return so the report says how hard the noise was squeezed.

    Returns {"backend", "repeats", "ms_per_batch": {variant: ms},
    "stages": {plugin: ms-delta}}.
    """
    import functools as _ft

    from k8s1m_tpu.engine.cycle import filter_score_topk
    from k8s1m_tpu.snapshot.pod_encoding import unpack_pod_batch

    pods = uniform_pods(enc.spec.batch)
    picked = variants()
    if only:
        picked = {n: p for n, p in picked.items() if n in only}
    ms: dict[str, float] = {}

    if backend == "pallas":
        batch = enc.encode(pods)

        def run(prof, i):
            idx, _ = fused_topk(
                table, batch, jnp.int32(i), prof,
                chunk=chunk, k=k, with_affinity=False,
            )
            return idx
    else:
        packed = enc.encode_packed(pods)
        keys = list(jax.random.split(jax.random.key(0), steps + 1))

        @_ft.lru_cache(maxsize=None)
        def _fn(prof):
            def fn(table, ints, bools, key):
                b = unpack_pod_batch(
                    ints, bools, packed.spec, packed.table_spec,
                    packed.groups,
                )
                return filter_score_topk(
                    table, b, key, prof, chunk=chunk, k=k
                ).idx

            return jax.jit(fn)

        def run(prof, i):
            return _fn(prof)(table, packed.ints, packed.bools, keys[i])

    if repeats < 1:
        raise ValueError(f"repeats must be >= 1, got {repeats}")
    for name, prof in picked.items():
        idx = run(prof, 0)
        jax.device_get(idx)      # compile + settle
        best = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            for i in range(steps):
                idx = run(prof, i + 1)
            jax.block_until_ready(idx)
            dt = (time.perf_counter() - t0) / steps * 1e3
            best = dt if best is None else min(best, dt)
        ms[name] = round(best, 3)

    stages: dict[str, float] = {}
    if "full" in ms:
        for knock, label in (
            ("no-least-allocated", "least_allocated"),
            ("no-balanced-allocation", "balanced_allocation"),
            ("no-taint-toleration", "taint_toleration"),
        ):
            if knock in ms:
                # full - knocked-out = the zeroed plugin's cost.
                stages[label] = round(ms["full"] - ms[knock], 3)
        if "filter-only" in ms:
            stages["filter_topk_floor"] = ms["filter-only"]
    return {
        "backend": backend, "repeats": repeats,
        "ms_per_batch": ms, "stages": stages,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description="pallas kernel component probe")
    ap.add_argument("--nodes", type=int, default=13 * 4096,
                    help="table rows (default: the 1M-table pct5 window)")
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--chunk", type=int, default=1 << 12)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument(
        "--repeats", type=int, default=3,
        help="timing blocks per variant; min-of-repeats is reported "
        "(one-sided noise estimator — keeps knockout deltas from going "
        "negative when a single block catches a scheduler hiccup)",
    )
    ap.add_argument("--only", default=None,
                    help="comma-separated variant names (default: all)")
    ap.add_argument(
        "--backend", choices=("pallas", "xla"), default="pallas",
        help="pallas times the fused kernel; xla times the scan path's "
        "filter_score_topk with the same plugin-knockout variants "
        "(engine/cycle.py) — the decomposition tool for whichever "
        "backend is under investigation",
    )
    ap.add_argument(
        "--packing", choices=("off", "packed"), default=None,
        help="device-snapshot layout (snapshot/packing.py): 'packed' "
        "probes the bit/byte-packed production layout, so the per-chunk "
        "decode cost shows up in every variant's ms.  Unset is 'off'",
    )
    args = ap.parse_args(argv)
    from k8s1m_tpu.snapshot.packing import resolve_packing

    args.packing = resolve_packing(args.packing)

    spec = TableSpec(max_nodes=args.nodes)
    host = NodeTableHost(spec)
    populate_kwok_nodes(host, args.nodes)
    from k8s1m_tpu.snapshot.packing import is_packed, pack_table_auto

    if args.packing == "packed":
        table = pack_table_auto(host, spec)
    else:
        table = host.to_device()
    enc = PodBatchHost(PodSpec(batch=args.batch), spec, host.vocab)

    only = (
        {n.strip() for n in args.only.split(",")} if args.only else None
    )
    picked = variants()
    for name in picked:
        if only and name not in only:
            continue
        # One variant per profile_stages call so each JSON line lands as
        # soon as its variant finishes (serial on-chip runs recompile
        # per variant, ~15-30s each).
        res = profile_stages(
            table, enc, chunk=args.chunk, k=args.k, steps=args.steps,
            repeats=args.repeats, backend=args.backend, only={name},
        )
        dt_ms = res["ms_per_batch"][name]
        print(json.dumps({
            "variant": name,
            "backend": args.backend,
            "repeats": args.repeats,
            # The mode actually in effect: pack_table_auto falls back
            # to unpacked when taint_slots outgrow the meta word.
            "packing": "packed" if is_packed(table) else "off",
            "ms_per_batch": dt_ms,
            "binds_per_sec_equiv": (
                round(args.batch / (dt_ms / 1e3), 1) if dt_ms else None
            ),
            "nodes": args.nodes,
            "batch": args.batch,
        }), flush=True)


if __name__ == "__main__":
    main()
