"""REAL multi-process jax.distributed test: two local processes, four
virtual CPU devices each, one global dp=2 x sp=4 mesh, one full sharded
scheduling step — and bind parity against the same step on a
single-process 8-device mesh.

This is the DCN story the in-process tests cannot cover: cross-process
device enumeration, global-mesh construction, cross-process collectives
(the sp candidate all-gather and dp commit all-gather), and
multi-process jax.device_put of the sharded node table.  The reference's
equivalent surface is its whole §2.5-2.6 scale-out story (relay tree +
CollectScore over gRPC); here the mesh IS the membership.
"""

from __future__ import annotations

import hashlib
import json
import os
import socket
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHILD = os.path.join(REPO, "tests", "multihost_child.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _reference_digest():
    """Single-process reference: same world, same mesh SHAPE (dp=2 x
    sp=4) over this test process's 8 virtual devices; the sharded step's
    jitter folds in mesh coordinates only, so results must be
    bit-identical across process topologies."""
    import jax

    from k8s1m_tpu.config import PodSpec, TableSpec
    from k8s1m_tpu.cluster import populate_kwok_nodes, uniform_pods
    from k8s1m_tpu.parallel import make_mesh, make_sharded_step
    from k8s1m_tpu.plugins.registry import Profile
    from k8s1m_tpu.snapshot import NodeTableHost, PodBatchHost

    chunk = 8
    num_nodes = 4 * 2 * chunk
    batch = 8
    spec = TableSpec(max_nodes=num_nodes, max_zones=16, max_regions=8)
    host = NodeTableHost(spec)
    populate_kwok_nodes(host, num_nodes, zones=8, regions=4)
    mesh = make_mesh(dp=2, sp=4)
    from jax.sharding import NamedSharding, PartitionSpec as P

    table = host.to_device(NamedSharding(mesh, P("sp")))
    enc = PodBatchHost(PodSpec(batch=batch), spec, host.vocab)
    pods = enc.encode(uniform_pods(batch))
    step = make_sharded_step(
        mesh, Profile(topology_spread=0, interpod_affinity=0),
        chunk=chunk, k=2,
    )
    new_table, _, asg = step(table, pods, jax.random.key(0))
    jax.block_until_ready(new_table)
    bound = np.asarray(asg.bound)
    rows = np.asarray(asg.node_row)
    return (
        hashlib.sha256(bound.tobytes() + rows.tobytes()).hexdigest(),
        int(bound.sum()),
    )


def test_two_process_distributed_step_matches_single_process():
    from k8s1m_tpu.envboot import cleaned_cpu_env

    ref_digest, ref_bound = _reference_digest()
    assert ref_bound == 8

    coord = f"127.0.0.1:{_free_port()}"
    env = cleaned_cpu_env(os.environ, 4)   # 4 local devices per process
    # The child is a script under tests/: the package root is not on
    # its sys.path unless PYTHONPATH carries it.
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, *filter(None, [env.get("PYTHONPATH")])]
    )
    procs = [
        subprocess.Popen(
            [
                sys.executable, CHILD,
                "--coordinator", coord,
                "--num-processes", "2",
                "--process-id", str(i),
            ],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=REPO,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=570)
        assert p.returncode == 0, f"child failed:\n{err[-4000:]}"
        outs.append(json.loads(out.strip().splitlines()[-1]))

    for doc in outs:
        # Both processes observed the full 8-device world...
        assert doc["devices"] == 8, doc
        assert doc["bound"] == ref_bound, doc
        # ...and computed the exact single-process result.
        assert doc["digest"] == ref_digest, (doc, ref_digest)
