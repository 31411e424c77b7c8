"""Headline benchmark: pod binds/sec against a 1M-node KWOK-style table.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "binds/s", "vs_baseline": N}

Baseline (BASELINE.md): the reference's 1M-node run schedules ~14K pods/s
on 289 scheduler replicas / 8,670 AMD Turin cores (reference
README.adoc:730,783-787).  This measures the TPU scheduling cycle on the
single real chip: filter+score+top-k, conflict resolution, capacity
commit — i.e. the work the Go fleet spreads over 256 shards, minus the
apiserver bind write (which the reference also excludes from its
scheduling-rate metric).

``--score-pct`` defaults to 5 — the SAME percentageOfNodesToScore the
reference's production 1M-node configuration runs (reference
terraform/kubernetes/dist-scheduler.tf:562, README.adoc:525-531), so the
headline number is apples-to-apples with the 14K/s baseline: each batch
filters+scores one rotating chunk-aligned ~5% window of the table and
commits binds into the full table.  ``--score-pct 100`` scores every
node for every pod (20x the per-pod work of the baseline config).

Runs only on the chip: it exits non-zero, printing no metric, when jax
reports any platform but ``tpu`` — a CPU number is never written under
a device metric's name.

``--mesh DPxSP`` routes the step through the dp x sp sharded cycle
(parallel/sharded_cycle.make_sharded_packed_step) — the production
execution path; byte-identical binds to single-device at the same seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

BASELINE_BINDS_PER_SEC = 14_000.0


def main():
    from k8s1m_tpu.envboot import place_compile_cache

    place_compile_cache()       # before jax loads: it reads the env at import
    import jax
    import numpy as np

    from k8s1m_tpu.config import PodSpec, TableSpec
    from k8s1m_tpu.cluster import populate_kwok_nodes, uniform_pods
    from k8s1m_tpu.engine.cycle import (
        sample_offset_for,
        sample_rows_for,
        schedule_batch_packed,
    )
    from k8s1m_tpu.plugins.registry import Profile
    from k8s1m_tpu.snapshot import NodeTableHost, PodBatchHost

    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=1 << 20)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument(
        "--chunk", type=int, default=None,
        help="node-chunk size (default: per-backend sweet spot)",
    )
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument(
        "--mesh", default=None, metavar="DPxSP",
        help="route the step through the dp x sp sharded cycle "
        "(parallel/sharded_cycle) — the production execution path; "
        "byte-identical binds to single-device for the same seed.  "
        "Also accepts 'auto'.",
    )
    ap.add_argument(
        "--score-pct", type=int, default=None,
        help="percentageOfNodesToScore (default 5, the reference's "
        "production 1M config — constraint plugins included: domain "
        "statistics stay global, only candidate scan follows the window)",
    )
    ap.add_argument(
        "--backend", choices=("xla", "pallas"), default=None,
        help="filter+score+top-k backend; pallas is the fused kernel "
        "(ops/pallas_topk.py), xla the scan path (engine/cycle.py). "
        "Default: pallas, or xla when --constraints is set (pass "
        "--backend pallas with --constraints for the fused constraint "
        "stage).",
    )
    ap.add_argument(
        "--packing", choices=("off", "packed"), default=None,
        help="device-snapshot layout (snapshot/packing.py): 'packed' "
        "holds the cold node-table columns bit/byte-packed in HBM and "
        "decodes per chunk on device — byte-identical binds, >=2x less "
        "cold-column HBM (the report's cold_bytes_reduction).  Unset "
        "is 'off'.  Composes with --mesh: the packed "
        "planes shard over sp and decode in the shard-local chunk "
        "slice (the production path since meshpack).",
    )
    ap.add_argument(
        "--constraints", action="store_true",
        help="BASELINE configs 3-4: pods carry topologySpread + inter-pod "
        "(anti)affinity constraints, scheduled under the full default "
        "profile with live ConstraintState",
    )
    ap.add_argument(
        "--deltacache", action="store_true",
        help="ISSUE 12 deltasched lane: pre-fill the per-shape "
        "feasibility/score planes (engine/deltacache.py) and run the "
        "delta step — full kernel over --delta-dirty rows per step, "
        "scatter-merge, hashed top-k over the merged planes.  The "
        "steady-state low-churn regime; byte-identical binds to the "
        "full pass.  Implies --score-pct 100 (planes cover the whole "
        "table); incompatible with --constraints (constraint-coupled "
        "pods are not cacheable).",
    )
    ap.add_argument(
        "--delta-dirty", type=int, default=128,
        help="journaled dirty rows recomputed per delta step (the "
        "churn knob of the --deltacache lane; default 128 ~ the "
        "<=100 dirty rows/s low-churn regime at wave rate)",
    )
    ap.add_argument(
        "--affinity", action="store_true",
        help="BASELINE config 2: pods carry NodeAffinity required terms "
        "(zone In + region NotIn) and preferred zone terms, scheduled "
        "under the default profile minus constraints — runs fused on the "
        "pallas backend",
    )
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()
    if args.constraints and args.affinity:
        ap.error("--constraints and --affinity are separate configs")
    if args.deltacache:
        if args.constraints:
            ap.error("--deltacache: constraint-coupled pods are not "
                     "cacheable (engine/deltacache.py)")
        if args.score_pct is None:
            args.score_pct = 100     # planes cover the whole table
    from k8s1m_tpu.snapshot.packing import resolve_packing

    args.packing = resolve_packing(args.packing)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"bench: needs a TPU, jax reports platform {dev.platform!r}",
            file=sys.stderr,
        )
        raise SystemExit(2)
    if args.backend is None:
        args.backend = "xla" if args.constraints else "pallas"
    if args.chunk is None:
        # Sweet spots: VMEM-sized tiles for the fused kernel, bigger scan
        # chunks for the XLA path.
        args.chunk = (1 << 12) if args.backend == "pallas" else (1 << 14)
    # The chunked scan needs chunk <= table rows.
    args.chunk = min(args.chunk, args.nodes)
    if args.score_pct is None:
        args.score_pct = 5
    if not 1 <= args.score_pct <= 100:
        ap.error("--score-pct must be in [1, 100]")
    mesh = None
    if args.mesh:
        from k8s1m_tpu.parallel import resolve_mesh

        mesh = resolve_mesh(
            args.mesh, batch=args.batch, max_nodes=args.nodes,
            chunk=args.chunk,
        )
        if mesh is not None:
            # The chunked scan runs per shard; clamp to the shard's rows.
            args.chunk = min(args.chunk, args.nodes // mesh.shape["sp"])
    # Rotating sample window, the coordinator's exact rule (engine
    # helpers) — SHARD-LOCAL under a mesh, like the coordinator's.
    window_nodes = (
        args.nodes // mesh.shape["sp"] if mesh is not None else args.nodes
    )
    sample_rows = sample_rows_for(window_nodes, args.score_pct, args.chunk)
    if args.deltacache and sample_rows is not None:
        ap.error("--deltacache needs the full scan (--score-pct 100): "
                 "a sampled window computes different planes than the "
                 "cache holds")

    # Constraint runs size the domain dims to the workload (64 zones /
    # 8 regions from populate_kwok_nodes): the fused constraint stage
    # materializes [max_zones, chunk] one-hot planes in VMEM.
    spec = (
        TableSpec(max_nodes=args.nodes, max_zones=128, max_regions=16)
        if args.constraints else TableSpec(max_nodes=args.nodes)
    )
    host = NodeTableHost(spec)
    t0 = time.perf_counter()
    populate_kwok_nodes(host, args.nodes)
    build_s = time.perf_counter() - t0

    pod_spec = PodSpec(batch=args.batch)
    constraints = None
    if args.constraints:
        from k8s1m_tpu.cluster.workload import (
            affinity_deployment,
            spread_deployment,
        )
        from k8s1m_tpu.snapshot.constraints import (
            ConstraintTracker,
            empty_constraints,
        )

        profile = Profile()      # full default profile
        tracker = ConstraintTracker(spec)
        half = args.batch // 2
        pods = (
            spread_deployment(tracker, "bench-spread", half, topo=1)
            + affinity_deployment(
                tracker, "bench-anti", args.batch - half, anti=True
            )
        )
        constraints = empty_constraints(spec)
        # Slot/ref dims fitted to the workload (one spread ref or one
        # anti-affinity term per pod): the fused constraint stage
        # unrolls per ref slot, same sizing rule as the affinity kernel.
        pod_spec = PodSpec(
            batch=args.batch, spread_refs=1, affinity_refs=1,
            spread_incs=1, ipa_incs=1,
        )
    elif args.affinity:
        from k8s1m_tpu.cluster.workload import node_affinity_pods

        # Default profile minus the constraint plugins: NodeAffinity
        # filters AND scores with live selector data, fused in the pallas
        # kernel (ops/pallas_topk.py affinity stage).  The PodSpec is
        # fitted to the workload's selector shape: the fused kernel's
        # program size (and Mosaic compile time) scales with the slot
        # count, so production encoders should size aff_terms/aff_exprs/
        # aff_values to the batch, not to the worst case (static shapes
        # sized to the workload — the same rule as every other TPU dim).
        profile = Profile(topology_spread=0, interpod_affinity=0)
        pods = node_affinity_pods(args.batch)
        pod_spec = PodSpec(
            batch=args.batch, aff_terms=1, aff_exprs=2, aff_values=2,
            pref_terms=1,
        )
    else:
        # Uniform KWOK pods carry no affinity/spread terms, so the base
        # profile is exact for this workload (affinity plugins would
        # contribute identically-zero scores); it is also what the pallas
        # backend covers.
        profile = Profile(
            node_affinity=0, topology_spread=0, interpod_affinity=0
        )
        pods = uniform_pods(args.batch)

    enc = PodBatchHost(pod_spec, spec, host.vocab)
    table_sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        table_sharding = NamedSharding(mesh, P("sp"))
        if constraints is not None:
            from k8s1m_tpu.parallel.mesh import constraint_specs

            constraints = jax.device_put(
                constraints,
                jax.tree.map(
                    lambda s: NamedSharding(mesh, s),
                    constraint_specs(constraints),
                ),
            )
    if args.packing == "packed":
        # Composes with the mesh (meshpack): the packed planes land
        # sharded over sp exactly like the plain columns.
        from k8s1m_tpu.snapshot.packing import pack_table_auto

        table = pack_table_auto(host, spec, table_sharding)
    else:
        table = host.to_device(table_sharding)
    from k8s1m_tpu.snapshot.packing import bytes_report

    layout_report = bytes_report(table, spec)
    packed = enc.encode_packed(pods)
    # The production coordinator path: packed pod buffers in, one i32[B]
    # bind-row array out (engine schedule_batch_packed — also the path
    # that supports the rotating percentageOfNodesToScore window).
    # schedule_batch_packed jits internally; keys are pre-split and bind
    # counts stay on-device so the loop is pure async dispatch.
    # Keys pre-split into a host list so the timed loop dispatches ONLY
    # the scheduling step (a device-array index or a separate count
    # program would each add a dispatch per step).
    keys = list(jax.random.split(jax.random.key(0), args.warmup + args.steps))

    def window(i: int) -> int:
        if sample_rows is None:
            return 0
        return sample_offset_for(i, window_nodes, sample_rows)

    # The production shape on BOTH paths: the step donates the table
    # (and constraint) buffers so the per-wave commit is in-place in
    # HBM — the mesh executables pin out_specs AND donate, aliasing
    # shard-by-shard.  Safe here because the loop reassigns ``table``
    # from every return.
    donate = True

    delta_detail = {}
    if args.deltacache:
        # The deltasched lane: pre-fill one plane slot per pod shape
        # (engine/deltacache.py fill executable, in fill-batch groups),
        # then run the delta step — the steady-state shape-hit wave.
        # ``planes`` rides the loop like ``table``: both donate.
        import dataclasses as _dc

        from jax import numpy as jnp

        from k8s1m_tpu.engine.cycle import (
            fill_shape_planes,
            schedule_batch_delta,
        )
        from k8s1m_tpu.snapshot.hotfeed import shape_key

        pods_of = {}
        for p in pods:
            pods_of.setdefault(shape_key(p), []).append(p)
        if None in pods_of:
            raise SystemExit("--deltacache: workload has uncacheable pods")
        shapes = list(pods_of)
        slot_of = {s: i for i, s in enumerate(shapes)}
        slot_ids = jnp.asarray(np.array(
            [slot_of[shape_key(p)] for p in pods], np.int32
        ))
        nslots = len(shapes)
        pmask = jnp.zeros((nslots, args.nodes), jnp.bool_)
        pscore = jnp.zeros((nslots, args.nodes), jnp.int32)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            plane_sharding = NamedSharding(mesh, P(None, "sp"))
            pmask = jax.device_put(pmask, plane_sharding)
            pscore = jax.device_put(pscore, plane_sharding)
        fb = 16
        fill_enc = PodBatchHost(
            _dc.replace(pod_spec, batch=fb), spec, host.vocab
        )
        planes = (pmask, pscore)
        for off in range(0, nslots, fb):
            reps = [pods_of[s][0] for s in shapes[off:off + fb]]
            fs = np.full(fb, nslots, np.int32)
            fs[: len(reps)] = range(off, off + len(reps))
            planes = fill_shape_planes(
                table, fill_enc.encode_packed(reps), jnp.asarray(fs),
                planes, profile=profile, chunk=args.chunk, mesh=mesh,
            )
        rng = np.random.default_rng(0)
        dirtys = [
            jnp.asarray(np.sort(rng.choice(
                args.nodes, args.delta_dirty, replace=False,
            )).astype(np.int32))
            for _ in range(args.warmup + args.steps)
        ]
        delta_detail = {"delta": {
            "dirty_rows_per_step": args.delta_dirty,
            "dirty_fraction": round(args.delta_dirty / args.nodes, 6),
            "shapes": nslots,
            "plane_mb": round(nslots * args.nodes * 5 / 2**20, 1),
        }}

        def step(table, planes, i):
            table, _asg, rows, planes = schedule_batch_delta(
                table, packed, keys[i], profile=profile,
                slot_ids=slot_ids, planes=planes, dirty=dirtys[i],
                chunk=args.chunk, k=args.k, mesh=mesh, donate=donate,
            )
            return table, planes, rows

        constraints = planes     # rides the loop variable below
    else:
        def step(table, constraints, i):
            table, constraints, _asg, rows = schedule_batch_packed(
                table, packed, keys[i], profile=profile,
                constraints=constraints,
                chunk=args.chunk, k=args.k, backend=args.backend,
                sample_rows=sample_rows, sample_offset=window(i),
                mesh=mesh, donate=donate,
            )
            return table, constraints, rows

    from k8s1m_tpu.snapshot import packing

    t0 = time.perf_counter()
    probe_ptr = None
    for i in range(args.warmup):
        if donate and i == args.warmup - 1:
            # Donation evidence: did the runtime alias the hot planes in
            # place across the last warmup step?  The pointer reads sync
            # — they land in the warmup (compile-dominated) window, kept
            # out of the measured steps window below.
            probe_ptr = packing.donation_probe(table)
        table, constraints, rows = step(table, constraints, i)
    if args.warmup:
        jax.device_get(rows)
    donation_inplace = (
        packing.donation_inplace(table, probe_ptr)
        if probe_ptr is not None else None
    )
    warm_s = time.perf_counter() - t0
    if donate and probe_ptr is None:
        # --warmup 0: probe across the measured window instead — the
        # syncing pointer reads land before t0 and after the window's
        # closing sync, so the evidence never costs timed time
        # (and never silently reads as "not probed").
        probe_ptr = packing.donation_probe(table)

    all_rows = []
    t0 = time.perf_counter()
    for i in range(args.steps):
        table, constraints, rows = step(table, constraints, args.warmup + i)
        all_rows.append(rows)
    # Sync on the LAST wave only, INSIDE the timed window: it depends on
    # the whole table chain, so waiting for it waits for every step —
    # dispatch is asynchronous, and a window closed before this measures
    # the enqueue rate.  Counting happens on host, after.
    jax.block_until_ready(all_rows[-1])
    elapsed = time.perf_counter() - t0
    if donate and donation_inplace is None:
        donation_inplace = packing.donation_inplace(table, probe_ptr)
    total_bound = int(sum(
        (np.asarray(jax.device_get(r)) >= 0).sum() for r in all_rows
    ))

    binds_per_sec = total_bound / elapsed
    if args.verbose:
        print(
            f"# build={build_s:.1f}s warmup(compile)={warm_s:.1f}s "
            f"steps={args.steps} batch={args.batch} bound={total_bound} "
            f"elapsed={elapsed*1e3:.1f}ms "
            f"({elapsed/args.steps*1e3:.2f}ms/batch)",
        )
    suffix = (
        "_constrained" if args.constraints
        else "_affinity" if args.affinity
        else ""
    )
    if args.deltacache:
        suffix += "_delta"
    if sample_rows is not None:
        # Only when a window is actually in effect: chunk rounding can
        # promote a small table's pct window to a full scan.
        suffix += f"_pct{args.score_pct}"
    if mesh is not None:
        suffix += f"_mesh{mesh.shape['dp']}x{mesh.shape['sp']}"
    metric = f"pod_binds_per_sec_{args.nodes}_nodes{suffix}"
    report = {
        "metric": metric,
        "value": round(binds_per_sec, 1),
        "unit": "binds/s",
        "vs_baseline": round(binds_per_sec / BASELINE_BINDS_PER_SEC, 3),
        # Device-memory evidence (ISSUE 10): snapshot layout, bytes/node
        # (cold_bytes_reduction is the >=2x packing acceptance ratio vs
        # the plain i32 layout), and whether buffer donation ran the
        # per-wave commit in place.  The metric NAME is layout-invariant
        # so packed runs compare against the same committed baseline.
        # "layout" is the mode actually in effect (pack_table_auto can
        # fall back to unpacked when taint_slots outgrow the meta word)
        # — the requested mode is never reported as evidence.
        **layout_report,
        "donation_inplace": donation_inplace,
        **delta_detail,
    }
    print(json.dumps(report))


if __name__ == "__main__":
    main()
