"""Kernel-only timing of the fused candidates kernel at a cell's widths.

    python3 tools/kernel_time.py [config ...]

For each benchmark configuration named (default ``full-1m-pct5``) it
builds, from a fixed seed, the inputs one wave of that cell hands the
fused kernel — a packed table of the cell's scan window (53,248 rows at
1M nodes and pct 5), the cell's label layout, count tables at its slot
widths, a pod batch at its ``PodSpec`` widths — and times one jitted
``fused_topk`` call on them: compiled once, then ``REPS`` calls each
ended by ``block_until_ready``, whose median is printed in ms.  The call
is the kernel and the few small XLA ops ``fused_topk`` puts around it
(the label and taint planes' transposes, the per-pod statistics).  The
kernel's cost does not depend on the values, so random ones stand for
the cell's.  One JSON line per configuration, naming the device; it
refuses any platform but ``tpu``.

The script imports the ``k8s1m_tpu`` beside it, so a copy placed in
another checkout's ``tools/`` times that checkout's kernel.
"""

from __future__ import annotations

import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

REPS = 15
SEED = 47


def inputs(name: str):
    """(call, args, meta): the jitted fused_topk of configuration ``name``
    and one wave's arguments at its widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s1m_tpu.config import PodSpec, TableSpec
    from k8s1m_tpu.engine.cycle import prologue_stats, sample_rows_for
    from k8s1m_tpu.ops.pallas_topk import fused_topk, kernel_name
    from k8s1m_tpu.plugins.registry import Profile
    from k8s1m_tpu.snapshot.constraints import ConstraintState
    from k8s1m_tpu.snapshot.node_table import NodeTableHost
    from k8s1m_tpu.snapshot.packing import (
        DEFAULT_VAL_BITS,
        build_packing_spec,
        pack_table_host,
    )
    from k8s1m_tpu.snapshot.pod_encoding import PodBatch, batch_field_specs

    cfg = json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())
    coord = cfg["coordinator"]
    full = TableSpec(**cfg["table_spec"])
    chunk = coord["chunk"]
    rows = sample_rows_for(full.max_nodes, coord["score_pct"], chunk) or full.max_nodes
    spec = TableSpec(**{**cfg["table_spec"], "max_nodes": rows})
    pspec = PodSpec(**cfg["pod_spec"])
    profile = Profile(**cfg["profile"])
    with_cons = bool(coord.get("with_constraints"))
    per_zone = bool(coord.get("in_wave_skew"))
    k = spec.max_zones if per_zone else 4     # the coordinator's default k
    # A cell whose pods carry selectors runs the affinity stage (its
    # profile weighs node affinity); one hostname a node past the fused
    # word's value bits splits the label planes.
    with_aff = profile.node_affinity > 0
    fuse = full.max_nodes < (1 << DEFAULT_VAL_BITS)

    rng = np.random.default_rng(SEED)
    host = NodeTableHost(spec)
    host.valid[:] = rng.random(rows) >= 1 / 128
    host.cpu_alloc[:] = 32_000
    host.mem_alloc[:] = 64 << 20
    host.pods_alloc[:] = 110
    host.cpu_req[:] = rng.integers(0, 16_000, rows)
    host.zone[:] = rng.integers(1, spec.max_zones, rows)
    host.region[:] = rng.integers(1, spec.max_regions, rows)
    host.label_key[:] = rng.integers(1, 8, host.label_key.shape)
    host.label_val[:] = rng.integers(1, 1 << 18, host.label_val.shape)
    host.taint_id[:, 0] = 1
    host.taint_effect[:, 0] = 1
    table = pack_table_host(host, build_packing_spec(spec, fuse_labels=fuse))

    def counts(*shape):
        return jnp.asarray(rng.integers(0, 1 << 12, shape), jnp.int32)

    c, a, z, r = spec.spread_slots, spec.affinity_slots, spec.max_zones, spec.max_regions
    cons = ConstraintState(
        spread_node=counts(c, rows), spread_zone=counts(c, z),
        spread_region=counts(c, r), tgt_node=counts(a, rows),
        tgt_zone=counts(a, z), tgt_region=counts(a, r),
        own_node=counts(a, rows), own_zone=counts(a, z),
        own_region=counts(a, r),
    ) if with_cons else None
    fields = {}
    for field, is_bool, shape in batch_field_specs(pspec, spec):
        if is_bool:
            fields[field] = jnp.asarray(rng.random(shape) < 0.5)
        else:
            fields[field] = jnp.asarray(rng.integers(0, 4, shape), jnp.int32)
    fields["cpu"] = jnp.full((pspec.batch,), 100, jnp.int32)
    fields["mem"] = jnp.full((pspec.batch,), 200 << 10, jnp.int32)
    fields["node_name_id"] = jnp.zeros((pspec.batch,), jnp.int32)
    batch = PodBatch(**fields)
    stats = prologue_stats(table, cons) if with_cons else None

    call = jax.jit(lambda t, b, cs, st, seed: fused_topk(
        t, b, seed, profile, chunk=chunk, k=k, with_affinity=with_aff,
        constraints=cs, stats=st, per_zone=per_zone,
    ))
    meta = {"config": name, "kernel": kernel_name(with_aff, with_cons),
            "rows": rows, "batch": pspec.batch, "chunk": chunk, "k": k,
            "fused_labels": fuse if with_aff else None}
    return call, (table, batch, cons, stats, jnp.int32(SEED)), meta


def main(argv: list[str]) -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"kernel_time: no TPU (platform is {dev.platform!r})", file=sys.stderr)
        return 2
    for name in argv or ["full-1m-pct5"]:
        call, args, meta = inputs(name)
        t0 = time.perf_counter()
        jax.block_until_ready(call(*args))
        compile_s = time.perf_counter() - t0
        laps = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(call(*args))
            laps.append(time.perf_counter() - t0)
        print(json.dumps({
            **meta, "median_ms": 1e3 * statistics.median(laps),
            "min_ms": 1e3 * min(laps), "max_ms": 1e3 * max(laps),
            "reps": REPS, "first_call_s": compile_s,
            "device": {"platform": dev.platform, "kind": dev.device_kind},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
