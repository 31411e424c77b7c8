"""End-to-end scheduling benchmark: store -> watch -> TPU -> CAS binds.

The reference's headline is end-to-end pods/s through the whole control
plane (~14K/s at 1M nodes on 256 shards, reference README.adoc:730,783-787).
bench.py measures the device cycle alone; this tool measures the full
loop the coordinator runs in production: pods enter the store, arrive by
watch, are encoded, scheduled on the TPU, and bound back via Txn CAS —
with the pipelined coordinator overlapping device work and store writes.

    python -m k8s1m_tpu.tools.sched_bench --nodes 100000 --pods 50000

Runs against an in-process store by default (the store and scheduler
colocated, like the reference's mem_etcd benchmarks); --target uses a
remote store server instead, adding the gRPC hop to every operation.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time

from k8s1m_tpu.config import PodSpec, TableSpec
from k8s1m_tpu.control.coordinator import Coordinator
from k8s1m_tpu.envboot import place_compile_cache, tune_gc
from k8s1m_tpu.control.objects import encode_node, encode_pod, node_key, pod_key
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot.pod_encoding import PodInfo
from k8s1m_tpu.store.native import MemStore
from k8s1m_tpu.tools.make_nodes import build_node

REFERENCE_E2E = 14_000.0


def _print_stage_stats(window_s: float) -> None:
    """Per-stage coordinator time totals over the measured window."""
    import sys

    from k8s1m_tpu.obs.metrics import REGISTRY

    cyc = REGISTRY.get("coordinator_cycle_seconds")
    for key in sorted(cyc.label_keys()):
        stage = dict(zip(cyc.labelnames, key)).get("stage", "?")
        print(
            f"# stage {stage:10s} {cyc.sum(stage=stage)*1e3:9.1f} ms "
            f"total ({cyc.sum(stage=stage)/window_s*100:5.1f}% of window)",
            file=sys.stderr,
        )


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="end-to-end scheduling bench")
    ap.add_argument("--nodes", type=int, default=100_000)
    ap.add_argument("--pods", type=int, default=50_000)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument(
        "--backend", choices=("auto", "xla", "pallas"), default="auto",
        help="filter+score+top-k backend.  'auto' (default) picks the "
        "fused pallas kernel only when the jax backend is a real TPU "
        "and the XLA scan path otherwise — on CPU envs the kernel runs "
        "INTERPRETED, orders of magnitude slower, so an unconditional "
        "pallas default silently produced misleading numbers",
    )
    ap.add_argument("--target", default=None,
                    help="remote store addr (default: in-process store)")
    ap.add_argument("--ca-pem", default=None,
                    help="TLS: trust this CA for --target (a secured tier)")
    ap.add_argument("--token", default=None,
                    help="bearer token for --target")
    ap.add_argument(
        "--rate", type=int, default=0,
        help="offered load in pods/s (paced producer + adaptive batch "
        "buckets; reports p50/p95/p99 schedule-to-bind latency).  0 = "
        "max-throughput fill",
    )
    ap.add_argument(
        "--score-pct", type=int, default=100,
        help="percentageOfNodesToScore (the reference's 1M-node production "
        "config uses 5, terraform tfvars percentageOfNodesToScore: 5)",
    )
    ap.add_argument("--no-pipeline", action="store_true")
    ap.add_argument(
        "--stats", action="store_true",
        help="after the run, print per-stage coordinator time totals "
        "(drain/encode/device/sync_out/bind) to stderr",
    )
    ap.add_argument(
        "--encode-profile", action="store_true",
        help="add host-feed evidence to the report detail: host-encode "
        "seconds by path (inline vs hidden in the feed worker), encode "
        "template-cache hit rate, staged-batch use and stale-discard "
        "counts (snapshot/hotfeed.py)",
    )
    ap.add_argument(
        "--deltacache", choices=("off", "on"), default=None,
        help="incremental scheduling (engine/deltacache.py): cache each "
        "pod shape's feasibility/score plane in HBM and recompute only "
        "dirty rows on a shape hit — byte-identical binds, O(batch x "
        "dirty) steady-state device work.  Unset is 'off'",
    )
    ap.add_argument(
        "--delta-profile", action="store_true",
        help="add delta-plane-cache evidence to the report detail: "
        "delta vs full wave split, shape hit rate, mean dirty "
        "fraction, planes resident, fills and LRU evictions, and (with "
        "--delta-index-k) the candidate-index wave/touched-rows/drop "
        "accounting (engine/deltacache.py)",
    )
    ap.add_argument(
        "--delta-index-k", type=int, default=0, metavar="K",
        help="per-resident-plane top-K candidate index (requires "
        "--deltacache on): all-hit waves derive candidates from the "
        "index + dirty set and skip the O(N) plane scan entirely — "
        "byte-identical binds, fail-closed on floor underflow.  0 "
        "disables the index",
    )
    ap.add_argument(
        "--stratum-bits", type=int, default=0, metavar="B",
        help="high jitter bits drawn from a wave-invariant per-(node, "
        "column) hash stratum instead of the seeded draw: splits tied "
        "score levels so the candidate-index floor can cut inside them "
        "(homogeneous clusters tie ~all rows at one score, which "
        "otherwise fails the index closed every wave).  Scale it with "
        "the cluster — per-pod spread only exists WITHIN a class, so "
        "target ~32 tied rows per class (log2(nodes) - 5, the "
        "megarow_drill.stratum_bits_for rule); 2^B >= nodes collapses "
        "every wave onto the same few rows.  0 keeps the historical "
        "jitter bit-for-bit",
    )
    ap.add_argument(
        "--shape-pool", type=int, default=0, metavar="N",
        help="pods draw structural shapes (nodeAffinity required + "
        "preferred terms, Deployment-template style) from a pool of N "
        "specs instead of the plain uniform pod — the paper's "
        "template-shaped firehose.  Enables the node-affinity plugin. "
        "0 = plain pods (default)",
    )
    ap.add_argument(
        "--shape-share", type=float, default=1.0, metavar="F",
        help="with --shape-pool: fraction of pods drawing from the hot "
        "pool; the rest draw from a bounded 4N-spec tail (the 90%%-hot "
        "regime of artifacts/hostpath_bench.json)",
    )
    ap.add_argument(
        "--shape-cold", action="store_true",
        help="every pod is its OWN shape (unique request scalars): the "
        "shape cache can never hit — the deltasched overhead lane",
    )
    ap.add_argument(
        "--depth", type=int, default=2,
        help="scheduling pipeline depth (in-flight waves; >2 helps when "
        "the device round trip dominates the wave)",
    )
    ap.add_argument(
        "--churn", action="store_true",
        help="BASELINE config 5 shape: delete the pods bound two waves "
        "ago while new waves arrive — sustained create+delete churn "
        "instead of a fill-up",
    )
    ap.add_argument(
        "--node-churn", type=float, default=0.0, metavar="RATE",
        help="steady capacity-only node-update traffic (updates/s) "
        "during the measured window — KWOK heartbeats / capacity "
        "updates at wall-clock rate.  The quiesce-free pipeline must "
        "hold its depth through this (pipeline_quiesce_total "
        "{reason=structural} stays 0; quiesce and sustained-depth "
        "evidence lands in the report detail)",
    )
    ap.add_argument(
        "--out", default=None, metavar="PATH",
        help="also write the report JSON to PATH (tier-1 smoke artifact)",
    )
    ap.add_argument(
        "--stress-watchers", type=int, default=0,
        help="run the apiserver-stress equivalent (tools/watch_stress) "
        "as a subprocess against the same --target for the whole "
        "measured window — config 5's full shape is churn UNDER watch "
        "stress.  Requires --target.",
    )
    ap.add_argument(
        "--stress-write-concurrency", type=int, default=1,
        help="stressor's concurrent writers (keep low on a single-core "
        "host or the stressor starves the scheduler it is stressing)",
    )
    ap.add_argument(
        "--mesh", default=None, metavar="DPxSP",
        help="drive the wave through the sharded step over a dp x sp "
        "device mesh (parallel/sharded_cycle.make_sharded_packed_step) — "
        "the reference's multi-replica fan-out as mesh devices.  "
        "Accepts DPxSP or DP,SP (dp*sp <= len(jax.devices())), or "
        "'auto' (largest workload-valid split).  Unset is single "
        "device; the sharded run is byte-identical to single-device "
        "at score-pct 100, so every churn/overload/encode-profile lane "
        "composes with it.  Mesh evidence (per-shard staged feed depth, "
        "sharded-scatter counts) lands in the report detail.",
    )
    ap.add_argument(
        "--trace", type=int, default=0, metavar="N",
        help="podtrace (obs/podtrace.py): trace 1-in-N pods through "
        "the whole lifecycle (head-sampled, deterministic by pod-key "
        "hash); the stage-attribution waterfall lands in the report's "
        "latency_attribution detail.  0 = off (the null tracer — free)",
    )
    ap.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="with --trace: write the Chrome/Perfetto trace-event JSON "
        "export to PATH (stages as tracks, pods as flow events; load "
        "in ui.perfetto.dev or chrome://tracing)",
    )
    ap.add_argument(
        "--profile", metavar="PATH", default=None,
        help="sample the measured window with obs/profiler.py, write "
        "the collapsed-stack artifact to PATH, and print the self-time "
        "top table to stderr (the pprof/Parca role)",
    )
    ap.add_argument(
        "--fault-plan", default=None,
        help="faultline plan: inline JSON or @path "
        "(k8s1m_tpu/faultline — deterministic drop/delay/disconnect/"
        "conflict injection across the store wire and the coordinator; "
        "injected-fault and retry counts land in the output detail)",
    )
    ap.add_argument(
        "--overload-at", type=float, default=0.0,
        help="seconds into the paced window to start an overload phase "
        "(requires --rate; the producer jumps to rate x "
        "--overload-factor for --overload-seconds, then drops back — "
        "the shed-and-recover shape of tools/overload_drill.py at "
        "wall-clock scale)",
    )
    ap.add_argument("--overload-seconds", type=float, default=300.0)
    ap.add_argument("--overload-factor", type=float, default=5.0)
    ap.add_argument(
        "--tenants", type=int, default=0,
        help="spread the pod population over N tenant namespaces with "
        "zipf-skewed tenant sizes (cluster/workload.py tenant_assignments"
        "; seed-deterministic).  0 = the historical single-namespace "
        "load",
    )
    ap.add_argument("--tenant-skew", type=float, default=1.0,
                    help="zipf skew of tenant sizes (0 = uniform)")
    ap.add_argument(
        "--tenant-schedule", default="steady",
        choices=("steady", "diurnal", "flash"),
        help="tenant-mix arrival shape along the emission sequence "
        "(diurnal: phase-shifted day curves; flash: tenant-0 crowds "
        "10x in the middle fifth — pair with --rate for wall-clock "
        "arrival schedules)",
    )
    ap.add_argument("--seed", type=int, default=0,
                    help="tenant-assignment seed")
    ap.add_argument(
        "--packing", choices=("off", "packed"), default=None,
        help="device-snapshot layout (snapshot/packing.py): 'packed' "
        "holds the cold node-table columns bit/byte-packed in HBM "
        "(byte-identical binds, >=2x less cold-column HBM).  Unset "
        "is 'off'.  Layout + donation evidence lands in "
        "the report's device_state detail",
    )
    ap.add_argument(
        "--kernel-profile", action="store_true",
        help="after the measured window, decompose the device step via "
        "the plugin-knockout DCE trick (tools/kernel_probe.py): per-"
        "stage ms/batch and bytes/node land in the report's "
        "kernel_profile detail (each variant compiles once — budget "
        "seconds on CPU, tens of seconds on TPU)",
    )
    args = ap.parse_args(argv)
    if args.overload_at and not args.rate:
        ap.error("--overload-at requires --rate (the paced producer)")
    if args.trace_out and not args.trace:
        ap.error("--trace-out requires --trace (the pod tracer)")
    return args


def offered_pods_at(args, t: float) -> float:
    """Cumulative offered pods at ``t`` seconds into the paced window —
    the integral of the (piecewise-constant) offered rate, so the
    overload phase is a rate *step*, not a one-off burst."""
    if not args.overload_at or args.overload_factor <= 1.0:
        return args.rate * t
    t1 = args.overload_at
    t2 = t1 + args.overload_seconds
    total = args.rate * min(t, t1)
    if t > t1:
        total += args.rate * args.overload_factor * (min(t, t2) - t1)
    if t > t2:
        total += args.rate * (t - t2)
    return total


def _encode_profile_detail(enabled: bool) -> dict:
    """Host-feed evidence for the report (empty unless --encode-profile)."""
    if not enabled:
        return {}
    from k8s1m_tpu.obs.metrics import REGISTRY

    enc = REGISTRY.get("hotfeed_encode_seconds_total")
    hits = REGISTRY.get("hotfeed_cache_hits_total").value()
    misses = REGISTRY.get("hotfeed_cache_misses_total").value()
    stale = REGISTRY.get("hotfeed_stale_batches_total")
    cyc = REGISTRY.get("coordinator_cycle_seconds")
    return {"encode_profile": {
        # Worker-path seconds ran OFF the cycle critical path; the
        # encode stage below is what the cycle actually waited on
        # (claim hits make it ~the staged-batch handoff cost).
        "host_encode_seconds": {
            "inline": round(enc.value(path="inline"), 4),
            "feed": round(enc.value(path="feed"), 4),
        },
        "encode_stage_seconds": round(cyc.sum(stage="encode"), 4),
        "cache_hit_rate": (
            round(hits / (hits + misses), 4) if hits + misses else None
        ),
        "staged_used": int(
            REGISTRY.get("hotfeed_staged_used_total").value()
        ),
        "staged_stale": {
            r: int(stale.value(reason=r))
            for r in ("vocab", "reordered", "error", "merge")
        },
        "staged_depth": int(
            REGISTRY.get("hotfeed_staged_depth").value()
        ),
    }}


def _delta_profile_detail(args, coord) -> dict:
    """Delta-plane-cache evidence for the report (ISSUE 12 deltasched;
    empty unless --delta-profile)."""
    if not args.delta_profile:
        return {}
    from k8s1m_tpu.obs.metrics import REGISTRY

    waves = REGISTRY.get("deltasched_waves_total")
    delta_waves = waves.value(path="delta")
    full_waves = waves.value(path="full")
    hits = REGISTRY.get("deltasched_shape_hits_total").value()
    misses = REGISTRY.get("deltasched_shape_misses_total").value()
    dirty = REGISTRY.get("deltasched_dirty_rows_total").value()
    rows = coord.table_spec.max_nodes
    detail = {"delta_profile": {
        "enabled": coord.delta_enabled,
        "delta_waves": int(delta_waves),
        "full_waves": int(full_waves),
        "shape_hit_rate": (
            round(hits / (hits + misses), 4) if hits + misses else None
        ),
        # Journaled dirty rows actually recomputed, as a fraction of the
        # full-recompute work the delta waves displaced.
        "mean_dirty_fraction": (
            round(dirty / (delta_waves * rows), 6) if delta_waves else None
        ),
        "planes_resident": int(
            REGISTRY.get("deltasched_planes_resident").value()
        ),
        "fills": int(REGISTRY.get("deltasched_fills_total").value()),
        "evictions": int(
            REGISTRY.get("deltasched_evictions_total").value()
        ),
    }}
    cache = getattr(coord, "_delta", None)
    index_k = getattr(cache, "index_k", 0) if cache is not None else 0
    if index_k:
        iw = REGISTRY.get("deltasched_index_waves_total")
        touched = REGISTRY.get("deltasched_index_touched_rows_total")
        drops = REGISTRY.get("deltasched_index_drops_total")
        idx_waves = iw.value(path="index")
        plane_waves = iw.value(path="plane")
        t_idx = touched.value(path="index")
        t_plane = touched.value(path="plane")
        detail["delta_profile"]["index"] = {
            "index_k": int(index_k),
            "stratum_bits": int(cache.stratum_bits),
            "index_waves": int(idx_waves),
            "plane_waves": int(plane_waves),
            # Mean rows visited per wave on each tail — the index path
            # touches dirty + k*batch candidate rows; the plane path is
            # the N-row chunk scan plus the dirty slice.  The fraction
            # is the index tail's visit cost against the N rows each
            # such wave would otherwise have scanned.
            "mean_touched_rows": {
                "index": (
                    round(t_idx / idx_waves, 1) if idx_waves else None
                ),
                "plane": (
                    round(t_plane / plane_waves, 1)
                    if plane_waves else None
                ),
            },
            "index_touched_fraction_of_n": (
                round(t_idx / (idx_waves * rows), 6)
                if idx_waves else None
            ),
            # Why index-eligible waves fell back to the plane scan —
            # floor underflows vs oversized dirty sets vs wholesale
            # invalidations (fill / generation / resync / packing).
            "drops": {
                r: int(drops.value(reason=r))
                for r in (
                    "underflow", "oversized-dirty", "fill",
                    "generation", "resync", "packing",
                    "fill-error", "dispatch-error",
                )
                if drops.value(reason=r)
            },
        }
    return detail


def _trace_detail(args, tracer) -> dict:
    """Stage-attribution waterfall for the report (empty without
    --trace): per-stage p50/p99 + share of the end-to-end total,
    coverage, and the optional Perfetto export."""
    from k8s1m_tpu.obs.podtrace import trace_report_detail

    return trace_report_detail(tracer, args.trace_out)


def _tenant_detail(args) -> dict:
    """Tenant-load shape for the report (empty without --tenants)."""
    if not args.tenants:
        return {}
    return {"tenant_load": {
        "tenants": args.tenants,
        "skew": args.tenant_skew,
        "schedule": args.tenant_schedule,
        "seed": args.seed,
    }}


def _device_state_detail(coord) -> dict:
    """Device-snapshot layout + donation evidence (ISSUE 10): table
    layout, HBM bytes/node (total and cold-column, with the reduction
    ratio vs the plain i32 layout), whether per-wave commit donation ran
    in place, and any fail-closed layout rebuilds."""
    if coord.table is None:
        return {}
    from k8s1m_tpu.obs.metrics import REGISTRY
    from k8s1m_tpu.snapshot.packing import FALLBACK_REASONS, bytes_report

    fb = REGISTRY.get("device_packing_fallback_total")
    return {"device_state": {
        **bytes_report(coord.table, coord.table_spec),
        "donation_inplace": coord.donation_inplace,
        "packing_fallbacks": {
            r: int(fb.value(reason=r))
            for r in FALLBACK_REASONS if fb.value(reason=r)
        },
    }}


def _shard_local_table(coord):
    """Single-device copy of ONE sp shard's slice of the live table
    (the first row block), keeping the live layout — packed tables stay
    packed, so the profile includes the production per-chunk decode.
    profile_stages runs the single-device step; this view makes it time
    exactly the program each shard executes per stage (same rows/chunk
    shape as one shard's scan), instead of an unintended
    resharded/gathered run over the whole sharded table.  Built from
    each leaf's first ADDRESSABLE shard, not a global np.asarray: on a
    multi-host mesh the global array spans non-addressable devices (the
    gather would raise and lose the whole report), and even single-host
    it would fetch the full table only to keep 1/sp of it."""
    import jax
    import numpy as np

    sp = int(coord.mesh.shape["sp"])
    local_rows = coord.table_spec.max_nodes // sp
    dev = jax.local_devices()[0]

    def local(a):
        shards = getattr(a, "addressable_shards", None)
        if shards:
            # The shard holding the FIRST row block (deterministic
            # across dp replicas: all dp copies of block 0 are equal).
            s = min(
                shards,
                key=lambda s: tuple(sl.start or 0 for sl in s.index),
            )
            return jax.device_put(np.asarray(s.data), dev)
        return jax.device_put(np.asarray(a)[:local_rows], dev)

    return jax.tree.map(local, coord.table)


def _kernel_profile_detail(args, coord) -> dict:
    """Per-stage device-step decomposition for the report (opt-in:
    --kernel-profile; each plugin-knockout variant is its own compile).
    Runs over the coordinator's LIVE table — layout, request columns and
    vocab exactly as the measured window left them.  Under --mesh the
    probe times the SHARD-LOCAL step (one sp shard's row slice, live
    layout) and records dp/sp + rows_per_shard so the ms/batch numbers
    read as per-shard stage costs."""
    if not args.kernel_profile or coord.table is None:
        return {}
    from k8s1m_tpu.snapshot.packing import bytes_report
    from k8s1m_tpu.tools.kernel_probe import profile_stages

    if coord.mesh is not None:
        table = _shard_local_table(coord)
    else:
        table = coord.table
    prof = profile_stages(
        table, coord.encoder, chunk=args.chunk, k=coord.k,
        steps=3, backend=args.backend,
    )
    if coord.mesh is not None:
        prof["mesh"] = {
            "dp": int(coord.mesh.shape["dp"]),
            "sp": int(coord.mesh.shape["sp"]),
            "rows_per_shard": int(table.num_rows),
        }
    prof["bytes_per_node"] = bytes_report(table, coord.table_spec)
    prof["batch"] = coord.pod_spec.batch
    return {"kernel_profile": prof}


def _resilience_detail() -> dict:
    """Injected-fault + retry evidence for the output JSON (empty when
    no fault plan is active)."""
    from k8s1m_tpu import faultline

    fired = faultline.active_injector().fire_counts()
    if not fired:
        return {}
    return {
        "faults_injected": fired,
        "retry_attempts": faultline.retry_counts(),
        "give_ups": faultline.give_up_counts(),
        "recovery": faultline.recovery_stats(),
    }


class _NodeChurn:
    """Paced capacity-only node updates (same name, same labels, wiggled
    allocatable) — the steady heartbeat/capacity traffic the 1M full-
    churn config never stops emitting.  Capacity-only by construction:
    every update targets a node the table already holds, so the
    pipelined coordinator scatters it mid-flight without a quiesce."""

    def __init__(self, store, nodes: int, rate: float):
        self._store = store
        self._nodes = nodes
        self._rate = rate
        self.emitted = 0

    def advance(self, elapsed_s: float) -> None:
        due = int(self._rate * elapsed_s)
        # Bound one burst so a long device wave can't turn catch-up into
        # a giant synchronous write (which would itself stall the cycle).
        due = min(due, self.emitted + 4096)
        if due <= self.emitted:
            return
        items = []
        for j in range(self.emitted, due):
            i = j % self._nodes
            items.append((
                node_key(f"kwok-node-{i}"),
                encode_node(build_node(
                    i, cpu_milli=32000 + (j // self._nodes) % 16
                )),
            ))
        write_wave(self._store, items)
        self.emitted = due


_QUIESCE_REASONS = ("structural", "resync", "breaker", "adaptive")


def _quiesce_counts() -> dict:
    from k8s1m_tpu.obs.metrics import REGISTRY

    q = REGISTRY.get("pipeline_quiesce_total")
    return {r: q.value(reason=r) for r in _QUIESCE_REASONS}


def _overlap_totals() -> tuple[float, float]:
    """(hidden, exposed) host-stage seconds so far."""
    from k8s1m_tpu.control.coordinator import _OVERLAP_STAGES
    from k8s1m_tpu.obs.metrics import REGISTRY

    ov = REGISTRY.get("pipeline_stage_overlap_seconds_total")
    return (
        sum(ov.value(stage=s, inflight="yes") for s in _OVERLAP_STAGES),
        sum(ov.value(stage=s, inflight="no") for s in _OVERLAP_STAGES),
    )


def _pipeline_detail(
    coord, quiesce_base, overlap_base, depth_samples, churn
) -> dict:
    """Quiesce / in-flight-depth / overlap evidence for the report."""
    import numpy as np

    hid, exposed = _overlap_totals()
    hid -= overlap_base[0]
    exposed -= overlap_base[1]
    samples = np.asarray(depth_samples or [0])
    return {
        "node_churn_rate": churn._rate if churn else 0.0,
        "node_churn_events": churn.emitted if churn else 0,
        "pipeline_quiesce": {
            r: int(_quiesce_counts()[r] - quiesce_base[r])
            for r in _QUIESCE_REASONS
        },
        # Depth sampled after every step while the producer was live:
        # the pipeline holds --depth iff the median sits there.
        "sustained_inflight_depth": int(np.median(samples)),
        "max_inflight_depth": int(samples.max()),
        "depth_seconds": {
            str(k): round(v, 4) for k, v in coord.depth_timer.seconds().items()
        },
        # Share of instrumented host-stage time that ran while device
        # waves were in flight (i.e. cost hidden behind device work).
        "stage_overlap_ratio": round(
            hid / (hid + exposed), 4
        ) if hid + exposed else None,
    }


def _mesh_detail(coord, feed_depth_samples) -> dict:
    """dp x sp execution evidence for the report (empty when the run is
    single-device): axis sizes, sharded dirty-row scatter counts, and
    per-dp-shard staged feed depth sampled while the producer was live."""
    if coord.mesh is None:
        return {}
    import numpy as np

    from k8s1m_tpu.obs.metrics import REGISTRY

    sc = REGISTRY.get("mesh_sharded_scatter_total")
    detail = {
        "dp": int(coord.mesh.shape["dp"]),
        "sp": int(coord.mesh.shape["sp"]),
        "sharded_scatters": {
            c: int(sc.value(cols=c)) for c in ("full", "cap")
        },
    }
    if feed_depth_samples:
        per_shard = np.asarray(feed_depth_samples)   # [samples, dp]
        detail["feed_staged_depth_per_shard"] = {
            "max": per_shard.max(axis=0).tolist(),
            "mean": [round(v, 3) for v in per_shard.mean(axis=0)],
        }
    return {"mesh_exec": detail}


def _sample_mesh_feed(coord, feed_depth_samples) -> None:
    from k8s1m_tpu.snapshot.hotfeed import ShardedHostFeed

    feed = getattr(coord, "_feed", None)
    if isinstance(feed, ShardedHostFeed):
        feed_depth_samples.append(feed.depths())


def _pipeline_window_start(coord, store, args):
    """Baselines + trackers captured immediately before a measured
    window (must run AFTER warmup — warm waves count adaptive quiesces).
    Returns (quiesce_base, overlap_base, depth_samples, node_churn)."""
    coord.depth_timer.reset()
    return (
        _quiesce_counts(),
        _overlap_totals(),
        [],
        _NodeChurn(store, args.nodes, args.node_churn)
        if args.node_churn else None,
    )


def _emit_report(report: dict, out_path: str | None) -> dict:
    print(json.dumps(report), flush=True)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
    return report


def write_wave(store, items) -> None:
    """Apply (key, value|None-for-delete) pairs via the store's batched
    path when it has one, else per key."""
    put_batch = getattr(store, "put_batch", None)
    if put_batch is not None:
        put_batch(items)
        return
    for k, v in items:
        if v is None:
            store.delete(k)
        else:
            store.put(k, v)


import contextlib


@contextlib.contextmanager
def _bench_window(args, coord, store):
    """Measured-window lifecycle: optional watch stressor and sampling
    profiler for the whole window, and guaranteed teardown (stressor,
    coordinator watches, store channel) even when the window raises
    mid-run."""
    stress = (
        _start_watch_stress(
            args.target, args.stress_watchers, args.stress_write_concurrency
        )
        if args.stress_watchers else None
    )
    prof = None
    if args.profile:
        from k8s1m_tpu.obs.profiler import SamplingProfiler

        prof = SamplingProfiler().start()
        coord.profiler = prof
    try:
        yield
    finally:
        if prof is not None:
            prof.stop()
            prof.dump(args.profile)
            print(prof.format_top(), file=sys.stderr)
        if stress is not None:
            stress.terminate()
            try:
                stress.wait(timeout=10)
            except subprocess.TimeoutExpired:
                stress.kill()
        coord.close()
        if hasattr(store, "close"):
            store.close()


class _ChurnFrontier:
    """Tracks which emitted pods are safe to delete.

    Churn must only delete BOUND pods (bind order diverges from key
    order whenever pods retry), but a pod that binds *after* the delete
    frontier sweeps past must still be deleted later — otherwise any
    bind lag (retries, a backed-up run, a slow device) silently turns
    the sustained create+delete shape back into a fill-up.  Skipped
    indices stay pending and are retried on every advance.
    """

    def __init__(self, coord, key_strs, start: int = 1):
        self._coord = coord
        self._key_strs = key_strs
        self._at = start
        self._pending: list[int] = []

    def advance(self, frontier: int) -> list[int]:
        """Bound indices in [previous, frontier) plus previously-skipped
        ones that have bound since; the rest stay pending."""
        if frontier > self._at:
            self._pending.extend(range(self._at, frontier))
            self._at = frontier
        bound = self._coord._bound
        ks = self._key_strs
        dels = [i for i in self._pending if ks[i] in bound]
        if dels:
            hit = set(dels)
            self._pending = [i for i in self._pending if i not in hit]
        return dels


def _start_watch_stress(target: str, watchers: int, write_concurrency: int):
    """Spawn the apiserver-stress equivalent against ``target`` for the
    duration of the bench window (terminated by the caller)."""
    import atexit
    import sys

    proc = subprocess.Popen(
        [
            sys.executable, "-m", "k8s1m_tpu.tools.watch_stress",
            "--target", target, "--watchers", str(watchers),
            "--write-concurrency", str(write_concurrency),
            "--writes", str(1 << 30), "--quiet",
        ],
        stdout=subprocess.DEVNULL,
    )
    atexit.register(lambda: proc.poll() is None and proc.kill())
    return proc


def main(argv=None):
    place_compile_cache()
    from k8s1m_tpu.obs.profiler import install_signal_dump

    # Always-on on-demand stack dump (SIGUSR2 -> /tmp/stacks-<pid>.txt),
    # the py-spy-dump role: a long run that stops progressing can be
    # interrogated without being killed.
    install_signal_dump()
    args = parse_args(argv)
    if args.backend == "auto":
        # The fused kernel is only a win compiled on real TPU silicon;
        # everywhere else it runs interpreted (orders of magnitude
        # slower), so auto picks the XLA scan path off-TPU.
        import jax

        args.backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if args.chunk is None:
        args.chunk = (1 << 12) if args.backend == "pallas" else (1 << 14)
    if args.stress_watchers and not args.target:
        raise SystemExit("--stress-watchers requires --target (wire store)")
    from k8s1m_tpu import faultline

    if args.fault_plan:
        faultline.install_plan(faultline.FaultPlan.from_arg(args.fault_plan))

    if args.target:
        from k8s1m_tpu.store.remote import RemoteStore

        store = RemoteStore(
            args.target,
            ca_pem=getattr(args, 'ca_pem', None),
            token=getattr(args, 'token', None),
        )
    else:
        store = MemStore()

    t0 = time.perf_counter()
    items = []
    for i in range(args.nodes):
        items.append((node_key(f"kwok-node-{i}"), encode_node(build_node(i))))
        if len(items) == 8192:
            write_wave(store, items)
            items.clear()
    if items:
        write_wave(store, items)
    nodes_s = time.perf_counter() - t0

    cap = 1 << max(10, (args.nodes - 1).bit_length())
    # The chunked scan needs chunk <= table rows (both powers of two
    # here); the per-backend default assumes a big table.
    args.chunk = min(args.chunk, cap)
    from k8s1m_tpu.parallel import resolve_mesh

    # Resolved here, not in the Coordinator, so the chunk clamp below
    # sees the mesh "auto" picked.
    mesh = resolve_mesh(
        args.mesh, batch=args.batch, max_nodes=cap, chunk=args.chunk
    )
    if mesh is not None:
        # The chunked scan runs per shard; clamp to the shard's rows.
        args.chunk = min(args.chunk, cap // mesh.shape["sp"])
    # Template-shaped pods (--shape-pool) do real per-(pod, node)
    # selector work, so the affinity plugin is live for them — the
    # regime the delta-plane cache collapses.  Plain pods keep the
    # committed-baseline profile (affinity would contribute zeros).
    profile = (
        Profile(topology_spread=0, interpod_affinity=0)
        if args.shape_pool
        else Profile(node_affinity=0, topology_spread=0, interpod_affinity=0)
    )
    tracer = None
    if args.trace:
        from k8s1m_tpu.obs.podtrace import PodTracer

        tracer = PodTracer(sample_n=args.trace)
    coord = Coordinator(
        store, TableSpec(max_nodes=cap), PodSpec(batch=args.batch),
        profile, chunk=args.chunk, with_constraints=False,
        backend=args.backend, pipeline=not args.no_pipeline, depth=args.depth,
        score_pct=args.score_pct, adaptive_batch=bool(args.rate),
        mesh=mesh,
        packing=args.packing,
        deltacache=args.deltacache,
        delta_index_k=args.delta_index_k,
        stratum_bits=args.stratum_bits,
        tracer=tracer,
    )
    t0 = time.perf_counter()
    coord.bootstrap()
    bootstrap_s = time.perf_counter() - t0

    # Pre-encode pod values (the writer's cost, not the scheduler's).
    # With --tenants the population spreads over tenant namespaces
    # (zipf sizes, scheduled mix) — emission is in index order, so the
    # paced producer below turns the index axis into arrival time.
    if args.tenants > 0:
        from k8s1m_tpu.cluster.workload import tenant_assignments

        tenant_ids = tenant_assignments(
            args.pods, args.tenants, skew=args.tenant_skew,
            seed=args.seed, schedule=args.tenant_schedule,
        )
        namespaces = [f"tenant-{t}" for t in tenant_ids]
    else:
        namespaces = ["default"] * args.pods
    shape_templates = []
    if args.shape_pool:
        # Deployment-template shapes doing real per-(pod, node) selector
        # work against the KWOK zone/region labels: a required In over
        # two zones + a region NotIn, plus a preferred zone — the
        # node_affinity_pods structure (sized to build_node's 8 zones /
        # 4 regions), made key-distinct beyond the 8 structural combos
        # by the request scalar the shape key also covers.  Pods draw
        # from a HOT pool of N specs or, for the (1 - share) slice, a
        # bounded 4N-spec tail — real pools' tails repeat too
        # (hotfeed's hit rate is 1.0 at 90%-hot pools,
        # artifacts/hostpath_bench.json).
        from k8s1m_tpu.cluster.workload import node_affinity_pods

        pool = node_affinity_pods(5 * args.shape_pool, zones=8, regions=4)
        for j, t in enumerate(pool):
            t.cpu_milli = 10 + j
            shape_templates.append(t)

    def bench_pod(i: int) -> PodInfo:
        p = PodInfo(
            f"bench-{i}", namespace=namespaces[i],
            cpu_milli=10, mem_kib=1024,
        )
        if args.shape_cold:
            # Every pod its own shape (the key includes the request
            # scalars): identical device work, zero possible cache hits
            # — isolates the deltasched host overhead.
            p.cpu_milli = 10 + i
            return p
        if shape_templates:
            import random as _random

            rng = _random.Random((args.seed << 20) | i)
            hot = rng.random() < args.shape_share
            j = (
                rng.randrange(args.shape_pool) if hot
                else args.shape_pool + rng.randrange(4 * args.shape_pool)
            )
            t = shape_templates[j]
            p.cpu_milli = t.cpu_milli
            p.required_terms = t.required_terms
            p.preferred_terms = t.preferred_terms
        return p

    values = [encode_pod(bench_pod(i)) for i in range(args.pods)]
    keys = [
        pod_key(namespaces[i], f"bench-{i}") for i in range(args.pods)
    ]
    key_strs = [f"{namespaces[i]}/bench-{i}" for i in range(args.pods)]

    # Warm the compile cache outside the measured window.
    store.put(keys[0], values[0])
    while coord.run_until_idle() == 0:
        pass
    if args.churn:
        # Churn also exercises the dirty-row scatter (delete -> row
        # re-upload) at full wave-sized buckets; compile those now too.
        wk = [pod_key("warm", f"w-{i}") for i in range(4096)]
        write_wave(store, [
            (k, encode_pod(PodInfo(f"w-{i}", cpu_milli=1, mem_kib=1)))
            for i, k in enumerate(wk)
        ])
        coord.run_until_idle()
        write_wave(store, [(k, None) for k in wk])
        coord.run_until_idle()

    # Producer interleaved with scheduling, like make_pods running against
    # a live scheduler; wave pacing keeps the 10K-deep watch buffer from
    # overflowing (the reference's webhook intake exists for the same
    # burst-arrival reason, README.adoc:684-695).  Interleaved, not
    # threaded: on a single-core host a producer thread only adds GIL
    # contention and queue backlog.
    from k8s1m_tpu.obs.metrics import REGISTRY, quantile_report_ms

    if args.rate:
        # Warm the adaptive buckets the paced run will actually use
        # (each bucket is its own compiled executable).
        # Every bucket must be compiled up front: a mid-run compile stall
        # (tens of seconds) while the queue is growing destroys the tail.
        b = coord.min_batch
        warm = {coord.pod_spec.batch}   # overload bucket (may be non-pow2)
        while b <= coord.pod_spec.batch:
            warm.add(b)
            b <<= 1
        woff = 0
        for b in sorted(warm):
            ks = [pod_key("warm2", f"r-{woff+i}") for i in range(b)]
            vs = [encode_pod(PodInfo(f"r-{woff+i}", cpu_milli=1, mem_kib=1))
                  for i in range(b)]
            woff += b
            write_wave(store, list(zip(ks, vs)))
            coord.run_until_idle()
        # Over a remote target the warm pods' watch events may still be
        # in flight when run_until_idle sees an empty queue — any warm
        # pod binding INSIDE the measured window inflates binds/s.
        # Drain until the whole warm population is accounted for.
        warm_deadline = time.perf_counter() + 30.0
        while (
            sum(1 for k in coord._bound if k.startswith("warm2/")) < woff
            and time.perf_counter() < warm_deadline
        ):
            coord.run_until_idle()
            time.sleep(0.05)
        REGISTRY.get("coordinator_schedule_to_bind_seconds").reset()
        if args.stats:
            REGISTRY.get("coordinator_cycle_seconds").reset()
        tune_gc()

        # Paced producer: emit pods on the offered-load schedule, step
        # the coordinator continuously, measure intake-to-bind latency.
        # --churn deletes BOUND pods a lag behind the emission point
        # (config 5's sustained create+delete shape at a rate); the lag
        # is capped at a quarter of the run so short runs still delete.
        lag = min(3 * coord.pod_spec.batch, max(args.pods // 4, 64))
        quiesce_base, overlap_base, depth_samples, node_churn = (
            _pipeline_window_start(coord, store, args)
        )
        feed_depth_samples: list = []
        t0 = time.perf_counter()
        bound = 0
        emitted = 1
        churn = _ChurnFrontier(coord, key_strs)
        deleted = 0
        with _bench_window(args, coord, store):
            while (
                emitted < args.pods or coord.queue or coord._inflights
                or coord._backoff
            ):
                due = min(
                    args.pods,
                    1 + int(offered_pods_at(args, time.perf_counter() - t0)),
                )
                if due > emitted:
                    write_wave(
                        store, list(zip(keys[emitted:due], values[emitted:due]))
                    )
                    emitted = due
                if node_churn is not None:
                    node_churn.advance(time.perf_counter() - t0)
                if args.churn:
                    # Advance on EVERY cycle, not only on emission: when
                    # binds lag the producer (CPU), most land after
                    # emission finished, and a frontier advanced only on
                    # emission would leave them pending forever —
                    # config 5 is a sustained create+DELETE shape, so
                    # deletions must keep executing through the drain.
                    dels = churn.advance(emitted - lag)
                    if dels:
                        write_wave(store, [(keys[i], None) for i in dels])
                        deleted += len(dels)
                bound += coord.step()
                if emitted < args.pods:
                    # Depth evidence only while the producer is live —
                    # the tail drain legitimately winds the pipeline down.
                    depth_samples.append(len(coord._inflights))
                    _sample_mesh_feed(coord, feed_depth_samples)
                if (
                    emitted >= args.pods
                    and not coord.queue
                    and not coord._inflights
                    and not coord._backoff
                ):
                    bound += coord.run_until_idle()
                    if args.churn:
                        dels = churn.advance(emitted - lag)
                        if dels:
                            write_wave(
                                store, [(keys[i], None) for i in dels]
                            )
                            deleted += len(dels)
                    break
            sched_s = time.perf_counter() - t0
            lat = REGISTRY.get("coordinator_schedule_to_bind_seconds")
        e2e = bound / sched_s if sched_s else 0.0
        if args.stats:
            _print_stage_stats(sched_s)
        q = quantile_report_ms(lat)
        return _emit_report({
            "metric": f"e2e_p50_bind_ms_{args.nodes}_nodes_at_{args.rate}",
            "value": q["p50_ms"],
            "unit": "ms",
            "vs_baseline": None,
            "detail": {
                "rate": args.rate,
                "mesh": args.mesh,
                "backend": args.backend,
                "score_pct": args.score_pct,
                "overload": (
                    {"at_s": args.overload_at,
                     "seconds": args.overload_seconds,
                     "factor": args.overload_factor}
                    if args.overload_at else None
                ),
                "binds_per_sec": round(e2e, 1),
                "bound": bound,
                "unbound": args.pods - 1 - bound,
                "deleted": deleted,
                "stress_watchers": args.stress_watchers,
                **q,
                **_pipeline_detail(
                    coord, quiesce_base, overlap_base, depth_samples,
                    node_churn,
                ),
                **_mesh_detail(coord, feed_depth_samples),
                **_tenant_detail(args),
                **_trace_detail(args, tracer),
                **_encode_profile_detail(args.encode_profile),
                **_delta_profile_detail(args, coord),
                **_device_state_detail(coord),
                **_kernel_profile_detail(args, coord),
                **_resilience_detail(),
            },
        }, args.out)

    wave = args.batch
    if args.stats:
        REGISTRY.get("coordinator_cycle_seconds").reset()
    tune_gc()
    quiesce_base, overlap_base, depth_samples, node_churn = (
        _pipeline_window_start(coord, store, args)
    )
    feed_depth_samples: list = []
    t0 = time.perf_counter()
    bound = 0
    off = 1
    deleted = 0
    churn = _ChurnFrontier(coord, key_strs)
    with _bench_window(args, coord, store):
        while off < args.pods:
            write_wave(
                store, list(zip(keys[off:off + wave], values[off:off + wave]))
            )
            if node_churn is not None:
                node_churn.advance(time.perf_counter() - t0)
            if args.churn:
                # Delete BOUND pods behind the emission lag — the
                # scheduler keeps binding into capacity that deletions
                # keep freeing; pods not yet bound stay pending in the
                # frontier and are deleted once they bind.
                dels = churn.advance(off - 2 * wave)
                write_wave(store, [(keys[i], None) for i in dels])
                deleted += len(dels)
            off += wave
            bound += coord.step()
            if off < args.pods:
                depth_samples.append(len(coord._inflights))
                _sample_mesh_feed(coord, feed_depth_samples)
        if args.churn:
            # Drain with the frontier still advancing (same lag): on CPU
            # most binds land here, after the producer finished, and the
            # sustained-delete shape must hold through the drain.
            # Cycle-bounded like run_until_idle: unschedulable pods
            # retry forever and would otherwise spin this loop forever.
            idle = 0
            for _ in range(10_000):
                n = coord.step()
                bound += n
                dels = churn.advance(args.pods - 2 * wave)
                if dels:
                    write_wave(store, [(keys[i], None) for i in dels])
                    deleted += len(dels)
                if not coord.queue and not coord._inflights and not coord._backoff:
                    idle += 1
                    if idle > 1 and coord.drain_watches() == 0:
                        break
                else:
                    idle = 0
            bound += coord.flush()
        else:
            bound += coord.run_until_idle()
        sched_s = time.perf_counter() - t0
    create_s = sched_s  # creation is inside the measured window
    e2e = bound / sched_s if sched_s else 0.0

    lat = REGISTRY.get("coordinator_schedule_to_bind_seconds")
    p50_ms = quantile_report_ms(lat, (0.5,))["p50_ms"] if lat else None

    if args.stats:
        _print_stage_stats(sched_s)

    suffix = f"_pct{args.score_pct}" if args.score_pct != 100 else ""
    return _emit_report({
        "metric": f"e2e_binds_per_sec_{args.nodes}_nodes{suffix}",
        "value": round(e2e, 1),
        "unit": "binds/s",
        "vs_baseline": round(e2e / REFERENCE_E2E, 3),
        "detail": {
            "score_pct": args.score_pct,
            "mesh": args.mesh,
            "backend": args.backend,
            "pods": args.pods,
            "bound": bound,
            "deleted": deleted,
            "node_create_s": round(nodes_s, 2),
            "bootstrap_s": round(bootstrap_s, 2),
            "pod_create_per_sec": round(args.pods / create_s, 1),
            "schedule_s": round(sched_s, 2),
            "stress_watchers": args.stress_watchers,
            "p50_bind_ms": p50_ms,
            **_pipeline_detail(
                coord, quiesce_base, overlap_base, depth_samples, node_churn,
            ),
            **_mesh_detail(coord, feed_depth_samples),
            **_tenant_detail(args),
            **_trace_detail(args, tracer),
            **_encode_profile_detail(args.encode_profile),
            **_delta_profile_detail(args, coord),
            **_device_state_detail(coord),
            **_kernel_profile_detail(args, coord),
            **_resilience_detail(),
        },
    }, args.out)


if __name__ == "__main__":
    main()
