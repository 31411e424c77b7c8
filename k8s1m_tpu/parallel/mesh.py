"""Device mesh and sharding specs for the scheduling framework.

Two mesh axes replace the reference's two distribution mechanisms
(reference SURVEY.md §2.5):

- ``sp`` (shard parallel) — the node table's row axis is sharded over sp.
  This is the TPU equivalent of the `dist-scheduler.dev/scheduler` node
  label that partitions 1M nodes across 256 Go replicas (reference
  cmd/dist-scheduler/leader_activities.go:227-343) — except rebalancing is
  free: rows are assigned to devices by position, not by a leader
  rewriting labels through the apiserver.
- ``dp`` (data parallel) — the pending-pod batch axis.  The reference
  broadcasts every pod to every shard through a fan-out-10 relay tree
  (reference pkg/schedulerset/schedulerset.go:161-193) because NIC
  bandwidth bounded the scatter; on a mesh the scatter is an ICI
  all-gather at the end of the cycle instead.

Node tables shard over ``sp`` and replicate over ``dp``; pod batches shard
over ``dp`` and replicate over ``sp``; scalar/leaf metadata (qkey, PRNG
key) is replicated everywhere.  The specs are layout-agnostic: the packed
production snapshot (snapshot/packing.PackedNodeTable) shards its planes
— meta word, fused label words, int16/int8 scalars — over ``sp`` exactly
like the plain i32 columns, which is what lets packed × sharded run as
one production path (meshpack).
"""

from __future__ import annotations

import logging

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from k8s1m_tpu.snapshot.node_table import NodeTable
from k8s1m_tpu.snapshot.pod_encoding import PodBatch

log = logging.getLogger("k8s1m.mesh")

def make_mesh(dp: int, sp: int, devices=None) -> jax.sharding.Mesh:
    if devices is None:
        devices = jax.devices()
    if dp * sp > len(devices):
        raise ValueError(f"mesh {dp}x{sp} needs {dp*sp} devices, have {len(devices)}")
    arr = np.asarray(devices[: dp * sp]).reshape(dp, sp)
    return jax.sharding.Mesh(arr, ("dp", "sp"))


def parse_mesh(s: str | None):
    """"DPxSP"/"DP,SP" -> (dp, sp); "auto" -> "auto"; "none"/""/None -> None."""
    if s is None:
        return None
    s = s.strip().lower()
    if s in ("", "none", "0", "off"):
        return None
    if s == "auto":
        return "auto"
    for sep in ("x", ","):
        if sep in s:
            dp_s, sp_s = s.split(sep, 1)
            dp, sp = int(dp_s), int(sp_s)
            if dp < 1 or sp < 1:
                raise ValueError(f"mesh axes must be >= 1, got {s!r}")
            return dp, sp
    raise ValueError(f"mesh spec {s!r} is not DPxSP, DP,SP, auto, or none")


def auto_mesh_shape(
    n_devices: int, *, batch: int, max_nodes: int, chunk: int
) -> tuple[int, int] | None:
    """Largest valid (dp, sp) split of ``n_devices`` for this workload.

    Validity is the coordinator's own divisibility contract: rows shard
    evenly over sp in chunk-aligned blocks (max_nodes % sp == 0 and
    rows-per-shard % chunk == 0) and the pod batch shards evenly over dp.
    Preference order: use every device, and give ``sp`` the larger axis —
    the node table is the only large resident, and sp is the axis whose
    all-gather must stay cheap (parallel/multihost.py's placement note).
    Returns None when no split beats single-device.
    """
    for total in range(n_devices, 1, -1):
        for sp in range(total, 0, -1):
            if total % sp:
                continue
            dp = total // sp
            if max_nodes % sp or (max_nodes // sp) % chunk or batch % dp:
                continue
            return dp, sp
    return None


def resolve_mesh(mesh, *, batch: int, max_nodes: int, chunk: int):
    """The coordinator's mesh-selection funnel.

    ``mesh`` may be an already-built jax Mesh (returned as-is), a spec
    string — "DPxSP" (also "DP,SP"), "auto", "none" — or None (single
    device).  "auto" picks the largest workload-valid dp x sp over the
    visible devices and falls back to single-device (with a log line
    saying why) when none fits — the single-device fallback story
    documented in README "Sharded execution"."""
    if mesh is None or isinstance(mesh, str):
        shape = parse_mesh(mesh)
        if shape is None:
            return None
        if shape == "auto":
            n = len(jax.devices())
            shape = auto_mesh_shape(
                n, batch=batch, max_nodes=max_nodes, chunk=chunk
            )
            if shape is None:
                log.info(
                    "mesh auto: no dp x sp split of %d devices fits "
                    "batch=%d max_nodes=%d chunk=%d; running single-device",
                    n, batch, max_nodes, chunk,
                )
                return None
        mesh = make_mesh(*shape)
    return mesh


def table_specs(table):
    """PartitionSpec pytree: every node-table leaf shards its row axis
    over sp.  Accepts either layout — a plain ``NodeTable`` or a packed
    ``PackedNodeTable`` (whose static ``spec`` rides the pytree aux data,
    so the tree.map covers exactly the array planes)."""
    return jax.tree.map(lambda _: P("sp"), table)


def constraint_specs(cons) -> object:
    """PartitionSpecs for ConstraintState: hostname-domain tables shard
    their node axis (axis 1) over sp; zone/region tables replicate."""
    from k8s1m_tpu.snapshot.constraints import ConstraintState

    return ConstraintState(
        spread_node=P(None, "sp"), spread_zone=P(), spread_region=P(),
        tgt_node=P(None, "sp"), tgt_zone=P(), tgt_region=P(),
        own_node=P(None, "sp"), own_zone=P(), own_region=P(),
    )


def batch_specs(batch: PodBatch) -> PodBatch:
    """PartitionSpec pytree: pod-leading arrays shard over dp; qkey replicates."""

    b = batch.batch

    def spec(x):
        return P("dp") if (x.ndim >= 1 and x.shape[0] == b) else P()

    specs = jax.tree.map(spec, batch)
    # qkey is [Q] and Q could coincidentally equal B; force it replicated.
    return specs.replace(qkey=P())
