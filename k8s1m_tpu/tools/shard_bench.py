"""Multi-shard end-to-end benchmark: N coordinator PROCESSES against one
store at scale — the process topology of the reference's 256-replica
fleet (reference README.adoc:697-730: near-linear scaling to 256
replicas, 14K binds/s at 1M nodes on 8,670 cores).

Each worker process runs a full ShardMember (control/shardset.py): FNV
pod-hash intake split, node space owned via group masks, CAS binds —
the same machinery the in-process harness tests pin, here across real
process + wire boundaries.  The parent populates nodes, spawns workers,
paces the pod load, and aggregates binds/s + latency from worker status
heartbeats written through the store (the same channel the shard set's
own heartbeats use).

    python -m k8s1m_tpu.tools.shard_bench --nodes 1048576 --pods 200000 \
        --shards 4 --score-pct 5

Device note: a chip belongs to one process at a time, so on a one-chip
host at most one worker may take the TPU (--tpu-worker 0); the rest run
the identical XLA program on the CPU backend.  The parent never
initialises a jax backend — the worker that needs the chip would
otherwise find it held.  On a pod slice each worker would own its
chips; the process/wire machinery measured here is what that deployment
adds on top of bench.py's device numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from k8s1m_tpu.control.objects import encode_node, encode_pod, node_key, pod_key
from k8s1m_tpu.snapshot.pod_encoding import PodInfo
from k8s1m_tpu.tools.make_nodes import build_node

STATUS_PREFIX = b"/bench/shard-status/"
START_KEY = b"/bench/start"
END_KEY = b"/bench/end"

REFERENCE_E2E = 14_000.0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="multi-shard e2e bench")
    ap.add_argument("--nodes", type=int, default=262_144)
    ap.add_argument("--pods", type=int, default=100_000)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--backend", choices=("xla", "pallas"), default="xla")
    ap.add_argument("--score-pct", type=int, default=5)
    ap.add_argument("--rate", type=int, default=0,
                    help="offered pods/s (0 = max-throughput fill)")
    ap.add_argument("--target", default=None,
                    help="existing store addr (default: spawn one)")
    ap.add_argument(
        "--tpu-worker", type=int, default=-1,
        help="worker index allowed on the real TPU (-1: all workers CPU; "
        "one process holds a chip at a time, so at most one)",
    )
    ap.add_argument("--worker", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--json", action="store_true",
                    help="print one machine-readable result line")
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# Worker process
# ---------------------------------------------------------------------------


def run_worker(args) -> None:
    from k8s1m_tpu.envboot import place_compile_cache

    place_compile_cache()
    from k8s1m_tpu.config import PodSpec, TableSpec
    from k8s1m_tpu.control.coordinator import Coordinator
    from k8s1m_tpu.control.shardset import ShardMember, pod_shard
    from k8s1m_tpu.envboot import tune_gc
    from k8s1m_tpu.obs.metrics import REGISTRY, quantile_report_ms
    from k8s1m_tpu.plugins.registry import Profile
    from k8s1m_tpu.store.remote import RemoteStore

    store = RemoteStore(args.target)
    cap = 1 << max(10, (args.nodes - 1).bit_length())
    coord = Coordinator(
        store, TableSpec(max_nodes=cap), PodSpec(batch=args.batch),
        Profile(node_affinity=0, topology_spread=0, interpod_affinity=0),
        chunk=min(args.chunk, cap), with_constraints=False,
        backend=args.backend, score_pct=args.score_pct,
    )
    member = ShardMember(store, coord, args.worker, args.shards)
    member.start(now=time.monotonic())

    # Warm the compile cache before reporting ready (a mid-window compile
    # stall would look like a straggler shard).  The warm pod's name must
    # HASH to this shard or the intake filter drops it.
    n = 0
    while pod_shard(f"warm/w{args.worker}-{n}", args.shards) != args.worker:
        n += 1
    warm_name = f"w{args.worker}-{n}"
    store.put(
        pod_key("warm", warm_name),
        encode_pod(PodInfo(warm_name, namespace="warm",
                           cpu_milli=1, mem_kib=1)),
    )
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        member.tick(time.monotonic())
        if f"warm/{warm_name}" in coord._bound:
            break
    tune_gc()
    hist = REGISTRY.get("coordinator_schedule_to_bind_seconds")
    hist.reset()
    # Own binds only: coord._bound also tracks binds OBSERVED from other
    # shards via the pod watch (cluster-wide churn accounting), so the
    # shard's throughput stat must come from its bind counter.
    sched = REGISTRY.get("coordinator_pods_scheduled_total")
    warm_bound = int(sched.value(outcome="bound"))

    def post_status(done: bool) -> None:
        doc = {
            "worker": args.worker,
            "bound": int(sched.value(outcome="bound")) - warm_bound,
            "conflicts": int(sched.value(outcome="conflict")),
            **quantile_report_ms(hist, (0.5, 0.99)),
            "done": done,
        }
        store.put(STATUS_PREFIX + str(args.worker).encode(),
                  json.dumps(doc).encode())

    print(json.dumps({"ready": args.worker}), flush=True)
    while store.get(START_KEY) is None:
        time.sleep(0.05)

    last_beat = 0.0
    idle_ticks = 0
    ended = False
    while idle_ticks < 40:
        n = member.tick(time.monotonic())
        if (
            n == 0 and not coord.queue and not coord._inflights
            and not coord._backoff
        ):
            # Only start counting down once the producer declared done —
            # a rate-paced load has idle gaps longer than the countdown.
            if ended or (ended := store.get(END_KEY) is not None):
                idle_ticks += 1
            time.sleep(0.005)
        else:
            idle_ticks = 0
        now = time.monotonic()
        if now - last_beat > 0.25:
            post_status(False)
            last_beat = now
    post_status(True)
    member.close()
    store.close()


# ---------------------------------------------------------------------------
# Parent: populate, spawn, pace, aggregate
# ---------------------------------------------------------------------------


def _spawn_store(args):
    from k8s1m_tpu.cluster.harness import _free_port, wait_for_port

    port = _free_port()
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "k8s1m_tpu.store.server_main",
            "--host", "127.0.0.1", "--port", str(port),
            "--metrics-port", "0",
        ],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    wait_for_port(port, proc=proc)
    return proc, f"127.0.0.1:{port}"


def _spawn_worker(args, idx: int):
    env = {**os.environ}
    if idx != args.tpu_worker:
        env["JAX_PLATFORMS"] = "cpu"
    cmd = [
        sys.executable, "-m", "k8s1m_tpu.tools.shard_bench",
        "--worker", str(idx), "--shards", str(args.shards),
        "--target", args.target, "--nodes", str(args.nodes),
        "--pods", str(args.pods), "--batch", str(args.batch),
        "--backend", args.backend, "--score-pct", str(args.score_pct),
    ]
    if args.chunk:
        cmd += ["--chunk", str(args.chunk)]
    return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE, text=True)


def main(argv=None):
    args = parse_args(argv)
    if args.chunk is None:
        args.chunk = (1 << 12) if args.backend == "pallas" else (1 << 14)
    if args.worker is not None:
        run_worker(args)
        return

    from k8s1m_tpu.control.shardset import init_assignment, pod_shard
    from k8s1m_tpu.store.remote import RemoteStore

    store_proc = None
    if not args.target:
        store_proc, args.target = _spawn_store(args)
    store = RemoteStore(args.target)

    t0 = time.perf_counter()
    wave = []
    for i in range(args.nodes):
        wave.append((node_key(f"kwok-node-{i}"), encode_node(build_node(i))))
        if len(wave) == 8192:
            store.put_batch(wave)
            wave.clear()
    if wave:
        store.put_batch(wave)
    init_assignment(store, args.shards)
    populate_s = time.perf_counter() - t0
    print(f"# {args.nodes} nodes in {populate_s:.1f}s", file=sys.stderr)

    workers = [_spawn_worker(args, i) for i in range(args.shards)]
    try:
        for w in workers:
            line = w.stdout.readline()
            if not line or "ready" not in line:
                raise RuntimeError(f"worker failed to start: {line!r}")
        print("# workers ready", file=sys.stderr)

        # Pre-encode pods; split stats for the report.
        values = [
            encode_pod(PodInfo(f"bench-{i}", cpu_milli=10, mem_kib=1024))
            for i in range(args.pods)
        ]
        keys = [pod_key("default", f"bench-{i}") for i in range(args.pods)]
        share = [0] * args.shards
        for i in range(args.pods):
            share[pod_shard(f"default/bench-{i}", args.shards)] += 1

        store.put(START_KEY, b"go")
        t0 = time.perf_counter()
        emitted = 0
        while emitted < args.pods:
            if args.rate:
                due = min(args.pods,
                          1 + int(args.rate * (time.perf_counter() - t0)))
            else:
                due = min(args.pods, emitted + 8192)
            if due > emitted:
                store.put_batch(list(zip(keys[emitted:due],
                                         values[emitted:due])))
                emitted = due
            else:
                time.sleep(0.002)
        store.put(END_KEY, b"done")

        # Aggregate from status heartbeats until every pod is bound.
        from k8s1m_tpu.store.native import prefix_end

        stats = {}
        while True:
            res = store.range(STATUS_PREFIX, prefix_end(STATUS_PREFIX))
            total = 0
            for kv in res.kvs:
                doc = json.loads(kv.value)
                stats[doc["worker"]] = doc
                total += doc["bound"]
            if total >= args.pods:
                break
            # A worker that drained its share posts done:true and EXITS
            # (rc=0) while slower shards are still binding — on a
            # one-core host the tails spread by tens of seconds.  Only a
            # non-zero exit is a death.
            if any(w.poll() not in (None, 0) for w in workers):
                rcs = [w.poll() for w in workers]
                raise RuntimeError(f"a shard worker died mid-run: rcs={rcs}")
            if all(w.poll() is not None for w in workers):
                # Everyone exited cleanly; one final refresh already ran
                # this iteration — if the total still comes up short,
                # pods were lost, which IS an error.
                if total < args.pods:
                    raise RuntimeError(
                        f"workers exited with {total}/{args.pods} bound"
                    )
                break
            time.sleep(0.1)
        window = time.perf_counter() - t0
        # The window closed at the last bind; workers post their final
        # done:true status only after their idle countdown, so give them
        # a moment — otherwise per_worker reports a stale done:false.
        deadline = time.monotonic() + 10.0
        while (not all(s.get("done") for s in stats.values())
               and time.monotonic() < deadline):
            time.sleep(0.05)
            res = store.range(STATUS_PREFIX, prefix_end(STATUS_PREFIX))
            for kv in res.kvs:
                doc = json.loads(kv.value)
                stats[doc["worker"]] = doc
            if all(w.poll() is not None for w in workers):
                # Every worker exited and the refresh above ran after
                # that: a normal exit's done:true is in; a crashed
                # worker's done:false surfaces in the report instead of
                # spinning out the deadline.
                break
    finally:
        for w in workers:
            if w.poll() is None:
                w.terminate()
        for w in workers:
            try:
                w.wait(timeout=15)
            except subprocess.TimeoutExpired:
                w.kill()
        store.close()
        if store_proc is not None:
            store_proc.terminate()
            store_proc.wait(timeout=10)

    binds_s = args.pods / window
    result = {
        "metric": "shard_e2e_binds_per_sec",
        "value": round(binds_s, 1),
        "unit": "binds/s",
        "vs_baseline": round(binds_s / REFERENCE_E2E, 3),
        "shards": args.shards,
        "nodes": args.nodes,
        "pods": args.pods,
        "window_s": round(window, 2),
        "pod_share": share,
        "per_worker": [stats.get(i) for i in range(args.shards)],
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
