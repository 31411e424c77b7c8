"""Child process for the real 2-process jax.distributed test
(tests/test_multihost.py).  Runs ONE sharded scheduling step over the
global dp=2 x sp=4 mesh and prints a digest of the (replicated)
assignment for cross-process / cross-topology parity checks.

Launched with a cleaned CPU env (envboot.cleaned_cpu_env) and 4 virtual
devices per process — two of these form the same 8-device world the single-process
reference run uses.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--process-id", type=int, required=True)
    args = ap.parse_args()

    from k8s1m_tpu.parallel import multihost

    multihost.initialize(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )

    import jax
    import numpy as np

    from k8s1m_tpu.config import PodSpec, TableSpec
    from k8s1m_tpu.cluster import populate_kwok_nodes, uniform_pods
    from k8s1m_tpu.parallel import make_sharded_step
    from k8s1m_tpu.plugins.registry import Profile
    from k8s1m_tpu.snapshot import NodeTableHost, PodBatchHost

    assert jax.process_count() == args.num_processes, jax.process_count()
    n_dev = len(jax.devices())
    mesh = multihost.make_global_mesh()   # dp = processes, sp = local devs

    # Identical world in every process (deterministic builders).
    chunk = 8
    sp = n_dev // args.num_processes
    num_nodes = sp * 2 * chunk
    batch = 4 * args.num_processes
    spec = TableSpec(max_nodes=num_nodes, max_zones=16, max_regions=8)
    host = NodeTableHost(spec)
    populate_kwok_nodes(host, num_nodes, zones=8, regions=4)
    table = multihost.shard_table_to_mesh(host, mesh)
    enc = PodBatchHost(PodSpec(batch=batch), spec, host.vocab)
    pods = enc.encode(uniform_pods(batch))

    profile = Profile(topology_spread=0, interpod_affinity=0)
    step = make_sharded_step(mesh, profile, chunk=chunk, k=2)
    new_table, _, asg = step(table, pods, jax.random.key(0))
    jax.block_until_ready(new_table)

    bound = np.asarray(asg.bound)
    rows = np.asarray(asg.node_row)
    digest = hashlib.sha256(
        bound.tobytes() + rows.tobytes()
    ).hexdigest()
    print(json.dumps({
        "process": args.process_id,
        "devices": n_dev,
        "bound": int(bound.sum()),
        "digest": digest,
    }), flush=True)


if __name__ == "__main__":
    sys.exit(main())
