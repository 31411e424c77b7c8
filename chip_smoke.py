"""Chip smoke: the served path at 1M nodes, once, on the TPU.

    python3 chip_smoke.py

One process, no flags.  Proves that the program still starts on the chip
through the entry points a deployment uses — it is NOT a benchmark: it
prints set-up seconds and counts, never a rate.

- **Device check**: prints platform / device_kind / count and exits 2
  unless the platform is ``tpu`` (nothing below may run interpreted or
  on a CPU backend).
- **Phase A — the main path at real size**: 1,048,576 KWOK nodes
  (tools/make_nodes.build_node) into a native MemStore; a ``Coordinator``
  bootstrapped from it with the reference's 1M configuration
  (percentageOfNodesToScore 5, batch 4096, packed layout, pipelined
  waves, the fused kernel — the arguments tools/sched_bench passes);
  make_pods-shaped pods put into the store, a slice of them POSTed
  through a real ``WebhookServer`` into ``submit_external``; a store
  watch on the pod prefix must see every bind.  No ``breaker=``: a
  dispatch failure raises.
- **Phase B — kernels that compile**: against the same resident table,
  one wave through the normal step functions for each kernel variant the
  production path can select (base, affinity, constraint — all over the
  packed int16/int8 planes — and the pallas delta tail behind the
  candidate index), each pallas ``(idx, prio)`` compared bit for bit
  with the XLA scan's on the same inputs, plus the XLA scan itself at
  the window shape and over all 1M rows; and ``greedy_assign`` (a wave's
  conflicts in parallel rounds) against the sequential scan it replaced,
  the four outputs bit for bit, over a wave of this cluster and over the
  last waves that fill a 10,000-node cluster to its brim.
- **Mesh phase** (>= 4 chips): phase A once more over an explicit 1x4
  mesh, and a one-chip / 1x4 pair at percentageOfNodesToScore 100 whose
  binds must be byte-identical (sampled windows rotate shard-locally by
  design — parallel/sharded_cycle.py — so identity is only promised for
  the full scan).

Every phase runs; the last stdout line is the result JSON only if all
of them passed.
"""

from __future__ import annotations

import contextlib
import json
import sys
import threading
import time
import traceback
import urllib.request

NODES = 1 << 20
BATCH = 4096
CHUNK = 1 << 12         # the fused kernel's VMEM-sized tile
SCORE_PCT = 5
WAVES = 6               # full waves in the checked window (>= 20,480 pods)
WEBHOOK_PODS = 256
ORACLE_SAMPLE = 256
MESH_WAVES = 2
FIT_NODES = 10_000      # fit-10k's cluster, filled to its brim in phase B
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def attempt(failures: dict, name: str, fn):
    """Run one phase or variant; a failure is recorded and reported, and
    the rest still run, so one chip run shows every refusal at once."""
    try:
        return fn()
    except Exception as e:  # reported by main(), which then exits non-zero
        traceback.print_exc()
        failures[name] = f"{type(e).__name__}: {e}"[:2000]
        log(f"phase {name}: FAILED {failures[name][:300]}")
        return None


class CompileCounter:
    """Counts executables built (compiled or loaded from the persistent
    cache) and persistent-cache hits, from jax's own monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.built = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Timer:
    """Set-up seconds by label (store load, bootstrap, each compile)."""

    def __init__(self) -> None:
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def measure(self, label: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[label] = round(time.perf_counter() - t0, 2)
            log(f"  setup {label}: {self.seconds[label]:.2f}s")


# ---- phase A: store -> watch -> Coordinator -> device -> CAS bind -> watch


def load_nodes(store, nodes: int) -> None:
    from k8s1m_tpu.control.objects import encode_node, node_key
    from k8s1m_tpu.tools.make_nodes import build_node

    for lo in range(0, nodes, 8192):
        store.put_batch([
            (node_key(f"kwok-node-{i}"), encode_node(build_node(i)))
            for i in range(lo, min(lo + 8192, nodes))
        ])


def make_coordinator(store, *, nodes, batch, chunk, score_pct, mesh=None):
    """The reference's 1M configuration, as tools/sched_bench builds it."""
    from k8s1m_tpu.config import PodSpec, TableSpec
    from k8s1m_tpu.control.coordinator import Coordinator
    from k8s1m_tpu.plugins.registry import Profile

    return Coordinator(
        store, TableSpec(max_nodes=nodes), PodSpec(batch=batch),
        Profile(node_affinity=0, topology_spread=0, interpod_affinity=0),
        chunk=chunk, with_constraints=False, backend="pallas",
        pipeline=True, depth=2, score_pct=score_pct,
        mesh=mesh,
        packing="packed", seed=SEED,
    )


def post_review(port: int, pod_obj: dict) -> None:
    review = {
        "apiVersion": "admission.k8s.io/v1",
        "kind": "AdmissionReview",
        "request": {"uid": pod_obj["metadata"]["name"], "object": pod_obj},
    }
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/validate",
        data=json.dumps(review).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        if not json.loads(resp.read())["response"]["allowed"]:
            raise RuntimeError("webhook refused a pod")


class BindWatch:
    """The client's view: a store watch on the pod prefix, recording
    every event that carries spec.nodeName."""

    def __init__(self, store) -> None:
        from k8s1m_tpu.control.coordinator import PODS_PREFIX
        from k8s1m_tpu.store.native import prefix_end

        self._prefix = len(PODS_PREFIX)
        self._w = store.watch(
            PODS_PREFIX, prefix_end(PODS_PREFIX), queue_cap=1 << 20
        )
        self.binds: dict[str, list[str]] = {}

    def drain(self) -> None:
        while True:
            events = self._w.poll_light(10000)
            for etype, key, val, _rev in events:
                if etype == 0 and b'"nodeName"' in val:
                    node = json.loads(val)["spec"].get("nodeName")
                    if node:
                        self.binds.setdefault(
                            key[self._prefix:].decode(), []
                        ).append(node)
            if len(events) < 10000:
                break
        if self._w.dropped:
            raise RuntimeError(f"bind watch dropped {self._w.dropped} events")

    def close(self) -> None:
        self._w.cancel()


def drive(coord, store, compiles: CompileCounter, *, namespace: str,
          waves: int, webhook_pods: int) -> dict:
    """Warm up one wave of each intake kind, then run ``waves`` full
    waves (``webhook_pods`` of them entering through the webhook first)
    and collect what a client watching the pod prefix saw."""
    from k8s1m_tpu.control.objects import encode_pod, pod_key
    from k8s1m_tpu.control.webhook import WebhookServer
    from k8s1m_tpu.snapshot.pod_encoding import PodInfo

    batch = coord.pod_spec.batch

    def submit(names, via_webhook: int = 0) -> None:
        values = [
            encode_pod(PodInfo(
                n, namespace=namespace, cpu_milli=10, mem_kib=1024
            ))
            for n in names
        ]
        # Admission fires before the write is persisted: webhook pods
        # are POSTed first, then stored like every other pod.
        for v in values[:via_webhook]:
            post_review(hook.port, json.loads(v))
        for _ in range(via_webhook):
            if not staged.acquire(timeout=30):
                raise RuntimeError("webhook never handed a pod to its sink")
        store.put_batch(
            [(pod_key(namespace, n), v) for n, v in zip(names, values)]
        )

    # The webhook answers before it hands the pod to its sink; wait for
    # the hand-off so which intake lane a pod takes never depends on a
    # thread race (the mesh phase compares placements run against run).
    staged = threading.Semaphore(0)

    def sink(obj: dict) -> None:
        coord.submit_external(obj)
        staged.release()

    watch = BindWatch(store)
    hook = WebhookServer(sink).start()
    try:
        # Warm-up: one full wave from the store plus a webhook pod, so
        # both intake lanes' executables exist before the checked window.
        warm = [f"warm-{i}" for i in range(batch + 1)]
        submit(warm, via_webhook=1)
        bound = coord.run_until_idle()
        watch.drain()
        if bound != len(warm):
            raise RuntimeError(f"warm-up bound {bound}/{len(warm)}")
        built_warm = compiles.built

        names = [f"smoke-{i}" for i in range(waves * batch)]
        bound = 0
        for w in range(waves):
            submit(
                names[w * batch:(w + 1) * batch],
                via_webhook=webhook_pods if w == 0 else 0,
            )
            bound += coord.step()
            watch.drain()
        bound += coord.run_until_idle()
        watch.drain()
    finally:
        hook.stop()
        watch.close()
    return {
        "warm": warm,
        "names": names,
        "bound": bound,
        "binds": {k: v for k, v in watch.binds.items()
                  if not k.startswith(f"{namespace}/warm-")},
        "compiles_after_warmup": compiles.built - built_warm,
    }


def fallback_counts() -> dict:
    """The two counters that say a wave did not take the device path it
    was configured for (process-global, so callers compare snapshots)."""
    import k8s1m_tpu.control.coordinator  # noqa: F401  (registers both)
    from k8s1m_tpu.obs.metrics import REGISTRY
    from k8s1m_tpu.snapshot.packing import FALLBACK_REASONS

    cyc = REGISTRY.get("coordinator_cycle_seconds")
    fb = REGISTRY.get("device_packing_fallback_total")
    return {
        "coordinator_cycle_seconds{stage=fallback}": cyc.sum(stage="fallback"),
        **{f"device_packing_fallback_total{{reason={r}}}": fb.value(reason=r)
           for r in FALLBACK_REASONS},
    }


def check_drive(coord, store, result: dict, *, namespace: str,
                oracle_sample: int, fallbacks_before: dict) -> None:
    """Every pod bound exactly once and seen on the watch; the store
    agrees; a seeded sample is oracle-feasible against the host mirror;
    nothing fell back."""
    import random

    from k8s1m_tpu.control.objects import decode_node, decode_pod, node_key, pod_key
    from k8s1m_tpu.oracle import oracle_feasible
    from k8s1m_tpu.snapshot.packing import is_packed

    names, binds = result["names"], result["binds"]
    if result["bound"] != len(names):
        raise RuntimeError(f"bound {result['bound']} of {len(names)} pods")
    if result["compiles_after_warmup"]:
        raise RuntimeError(
            f"{result['compiles_after_warmup']} compilations after warm-up"
        )
    keys = [f"{namespace}/{n}" for n in names]
    missing = [k for k in keys if k not in binds]
    multi = [k for k in keys if len(binds.get(k, ())) > 1]
    if missing or multi or len(binds) != len(keys):
        raise RuntimeError(
            f"watch saw {len(binds)} bound pods of {len(keys)}: "
            f"{len(missing)} never bound, {len(multi)} bound more than once"
        )
    if not is_packed(coord.table):
        raise RuntimeError("live table is not in the packed layout")
    fell = {
        k: v - fallbacks_before[k]
        for k, v in fallback_counts().items() if v != fallbacks_before[k]
    }
    if fell:
        raise RuntimeError(f"fallback counters moved: {fell}")

    # The plain reference, outside any timed region: the chosen node
    # must pass every filter with the pod's own request taken back out
    # of the host mirror's final requested columns (binds only add, so a
    # node that is not overcommitted at the end never was).
    host = coord.host
    rng = random.Random(SEED)
    for n in rng.sample(names, min(oracle_sample, len(names))):
        kv = store.get(pod_key(namespace, n))
        pod = decode_pod(kv.value)
        node_name = pod.node_name
        if [node_name] != binds[f"{namespace}/{n}"]:
            raise RuntimeError(f"store and watch disagree on {n}")
        node = decode_node(store.get(node_key(node_name)).value)
        row = host.row_of(node_name)
        pod.node_name = None
        req = (
            int(host.cpu_req[row]) - pod.cpu_milli,
            int(host.mem_req[row]) - pod.mem_kib,
            int(host.pods_req[row]) - 1,
        )
        if min(req) < 0 or not oracle_feasible(node, pod, req):
            raise RuntimeError(
                f"oracle: {n} on {node_name} infeasible (requested {req})"
            )


def phase_a(store, compiles, timer, *, nodes, batch, chunk, score_pct,
            waves, webhook_pods, oracle_sample, mesh=None, label="A"):
    """Bootstrap a coordinator from the store and drive it.  Returns the
    live coordinator (its table is phase B's input) and the drive result."""
    fallbacks_before = fallback_counts()
    coord = make_coordinator(
        store, nodes=nodes, batch=batch, chunk=chunk, score_pct=score_pct,
        mesh=mesh,
    )
    with timer.measure(f"{label}.bootstrap"):
        coord.bootstrap()
    namespace = f"smoke-{label.lower()}"
    with timer.measure(f"{label}.drive_incl_compile"):
        result = drive(
            coord, store, compiles, namespace=namespace, waves=waves,
            webhook_pods=webhook_pods,
        )
    check_drive(
        coord, store, result, namespace=namespace,
        oracle_sample=oracle_sample, fallbacks_before=fallbacks_before,
    )
    log(
        f"phase {label}: rows={coord.table.num_rows} layout=packed "
        f"pods_bound={result['bound']} waves={waves} "
        f"webhook_pods={webhook_pods} "
        f"compiles_after_warmup={result['compiles_after_warmup']} "
        f"donation_inplace={coord.donation_inplace}"
    )
    return coord, result


# ---- phase B: every kernel variant through Mosaic, bit for bit vs XLA -----


def _equal(name: str, a, b) -> None:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or not np.array_equal(a, b):
        diff = int((a != b).sum()) if a.shape == b.shape else -1
        raise RuntimeError(
            f"{name}: pallas and XLA differ in {diff} of {a.size} entries"
        )


def _window_candidates(packed, profile, *, rows, chunk, k, offset, backend):
    """Jitted ``(table, ints, bools, key, constraints) -> (idx, prio)``:
    the step's own candidates stage (engine/cycle.candidates) over one
    scan window, on the backend asked for."""
    import jax

    from k8s1m_tpu.engine.cycle import candidates, has_selectors
    from k8s1m_tpu.snapshot.pod_encoding import unpack_pod_batch

    aff = has_selectors(packed.groups)

    def impl(table, ints, bools, key, constraints):
        batch = unpack_pod_batch(
            ints, bools, packed.spec, packed.table_spec, packed.groups
        )
        cand = candidates(
            table, batch, key, constraints, profile, chunk=chunk, k=k,
            backend=backend, with_affinity=aff, window=(offset, rows),
        )
        return cand.idx, cand.prio

    return jax.jit(impl)


def _variant(name, table, packed, profile, timer, *, chunk, k, sample_rows,
             offset, constraints=None, also=None):
    """One wave through the normal step function on the fused kernel,
    then its candidate stage against the XLA scan's on the same inputs.
    With constraints the comparison runs on the state the step left
    behind — live counts, not zeros — and again on ``also(state)``."""
    import jax

    from k8s1m_tpu.engine.cycle import schedule_batch_packed

    key = jax.random.key(SEED)
    with timer.measure(f"B.{name}.pallas_step"):
        _t, cons, asg, rows = schedule_batch_packed(
            table, packed, key, profile=profile, constraints=constraints,
            chunk=chunk, k=k, backend="pallas", sample_rows=sample_rows,
            sample_offset=offset,
        )
        bound = int((jax.device_get(rows) >= 0).sum())
    if bound != packed.batch:
        raise RuntimeError(f"{name}: pallas step bound {bound}/{packed.batch}")
    states = [cons if constraints is not None else None]
    if also is not None:
        states.append(also(states[0]))
    out = {}
    for backend in ("pallas", "xla"):
        fn = _window_candidates(
            packed, profile, chunk=chunk, k=k, offset=offset, backend=backend,
            rows=table.num_rows if sample_rows is None else sample_rows,
        )
        with timer.measure(f"B.{name}.{backend}_candidates"):
            out[backend] = [
                jax.device_get(fn(table, packed.ints, packed.bools, key, st))
                for st in states
            ]
    for i, (got, want) in enumerate(zip(out["pallas"], out["xla"])):
        _equal(f"{name} idx (state {i})", got[0], want[0])
        _equal(f"{name} prio (state {i})", got[1], want[1])
        if not (got[1] >= 0).any():
            raise RuntimeError(f"{name}: no feasible candidate (state {i})")
    log(f"phase B {name}: compiled, {bound} bound, (idx, prio) == XLA")
    return rows


def sequential_assign(cand_idx, cand_prio, cand_cpu, cand_mem, cand_pods,
                       pod_cpu, pod_mem, pod_valid):
    """The reference: a wave's conflicts as one sequential scan, pod i
    taking the first of its candidates with room after pods j < i — what
    ``greedy_assign`` ran as until its rounds, kept to hold the order: here
    on the chip, and as ``parents_greedy_assign`` in tests/test_spread_waves.py
    and tests/test_assign_rounds.py on the CPU."""
    import jax.numpy as jnp
    from jax import lax

    from k8s1m_tpu.ops.priority import unpack_score

    b, _k = cand_idx.shape
    arange_b = jnp.arange(b)

    def step(carry, _):
        node_of, bound, i = carry
        taken = (cand_idx[i][:, None] == node_of[None, :]) & (
            (arange_b < i) & bound)[None, :]
        ok = (
            (cand_prio[i] >= 0) & (cand_idx[i] >= 0)
            & (pod_cpu[i] <= cand_cpu[i] - (taken * pod_cpu[None, :]).sum(-1))
            & (pod_mem[i] <= cand_mem[i] - (taken * pod_mem[None, :]).sum(-1))
            & (cand_pods[i] - taken.sum(-1) >= 1)
        )
        any_ok = ok.any() & pod_valid[i]
        kstar = jnp.argmax(ok)
        node = jnp.where(any_ok, cand_idx[i, kstar], -1)
        score = jnp.where(any_ok, unpack_score(cand_prio[i, kstar]), -1)
        carry = (node_of.at[i].set(node), bound.at[i].set(any_ok), i + 1)
        return carry, (node, any_ok, score, kstar.astype(jnp.int32))

    init = (jnp.full((b,), -1, jnp.int32), jnp.zeros((b,), bool), jnp.int32(0))
    return lax.scan(step, init, None, length=b)[1]


def _assign_inputs(packed, profile, *, chunk, k, window):
    """Jitted ``(table, ints, bools, key) -> greedy_assign's eight
    arguments``: the wave's candidates from the fused kernel with what
    each holds free, and the pods' requests."""
    import jax

    from k8s1m_tpu.engine.cycle import candidates, commit_fields_of
    from k8s1m_tpu.snapshot.pod_encoding import unpack_pod_batch

    def impl(table, ints, bools, key):
        batch = unpack_pod_batch(
            ints, bools, packed.spec, packed.table_spec, packed.groups
        )
        cand = candidates(
            table, batch, key, None, profile, chunk=chunk, k=k,
            backend="pallas", with_affinity=False, window=window,
        )
        fields = commit_fields_of(batch)
        return (cand.idx, cand.prio, cand.cpu, cand.mem, cand.pods,
                fields.cpu, fields.mem, fields.valid)

    return jax.jit(impl)


def _assign_variant(table, base, base_profile, timer, *, chunk, k,
                    sample_rows, offset, fit_nodes):
    """``greedy_assign`` against ``sequential_assign`` on the chip, bit
    for bit: one wave of this cluster (nobody bumped: one round), then
    ``fit-10k``'s deployment filled by the normal step function, every
    wave over its last sixteen waves' worth of slots compared before it
    is committed (pod slots run out: several rounds a wave, unbound pods)."""
    import jax
    import numpy as np

    from k8s1m_tpu.cluster.workload import uniform_pods
    from k8s1m_tpu.config import PodSpec, TableSpec
    from k8s1m_tpu.engine.assign import greedy_assign
    from k8s1m_tpu.engine.cycle import schedule_batch_packed
    from k8s1m_tpu.plugins.registry import Profile
    from k8s1m_tpu.snapshot import NodeInfo, NodeTableHost, PodBatchHost
    from k8s1m_tpu.snapshot.packing import pack_table_auto

    def without_legal(*args):
        *four, _legal, settled = greedy_assign(*args)
        return *four, settled

    rounds, scan = jax.jit(without_legal), jax.jit(sequential_assign)

    def compare(label, args) -> list:
        *got, settled = jax.device_get(rounds(*args))
        for name, a, b in zip(("node_row", "bound", "score", "chosen_k"),
                              got, jax.device_get(scan(*args))):
            _equal(f"assign {label} {name}", a, b)
        return settled.tolist()

    key = jax.random.key(SEED)
    with timer.measure("B.assign.window_wave"):
        inputs = _assign_inputs(
            base, base_profile, chunk=chunk, k=k,
            window=None if sample_rows is None else (offset, sample_rows),
        )
        settled = compare("1m window", inputs(table, base.ints, base.bools, key))
    log(f"phase B assign: a wave of the window == the sequential scan; "
        f"settled (rounds, scan, evaluations) {settled}")

    nodes, batch = fit_nodes, base.batch
    spec = TableSpec(max_nodes=max(chunk, 1 << (nodes - 1).bit_length()))
    host = NodeTableHost(spec)
    for i in range(nodes):
        host.upsert(NodeInfo(
            f"kwok-node-{i}", cpu_milli=32_000, mem_kib=64 << 20, pods=110,
            unschedulable=i % 128 == 127,
        ))
    small = pack_table_auto(host, spec)
    fit = Profile(
        least_allocated=1, balanced_allocation=0, taint_toleration=0,
        node_affinity=0, topology_spread=0, interpod_affinity=0,
    )
    pods = PodBatchHost(PodSpec(batch=batch), spec, host.vocab).encode_packed(
        uniform_pods(batch)
    )
    inputs = _assign_inputs(pods, fit, chunk=chunk, k=k, window=None)
    open_slots = (nodes - nodes // 128) * 110
    bound = waves = 0
    seen = []
    with timer.measure("B.assign.fit_10k_brim"):
        while bound < open_slots:
            key, sub = jax.random.split(key)
            if open_slots - bound <= 16 * batch:
                seen.append(compare(
                    f"brim wave {waves}",
                    inputs(small, pods.ints, pods.bools, sub),
                ))
            small, _c, _a, rows = schedule_batch_packed(
                small, pods, sub, profile=fit, chunk=chunk, k=k,
                backend="pallas",
            )
            bound += int((np.asarray(rows) >= 0).sum())
            waves += 1
            if waves > 4 * open_slots // batch:
                raise RuntimeError(f"fit-10k fill: {bound}/{open_slots} bound "
                                   f"after {waves} waves")
    if not any(e > 1 for *_s, e in seen):
        raise RuntimeError(f"fit-10k brim: no wave took a second round {seen}")
    log(f"phase B assign: {len(seen)} waves at fit-10k's brim ({waves} to fill "
        f"{bound} slots) == the sequential scan; settled {seen}")


def _window_node_names(host, offset: int, rows: int, *, want: int) -> list:
    """Names of ``want`` nodes whose table row lies in the scan window
    (rows follow the store's key order, not the node index)."""
    import random

    rng = random.Random(SEED)
    names: list[str] = []
    for _ in range(1000 * want):
        name = f"kwok-node-{rng.randrange(host.num_nodes)}"
        if name not in names and offset <= host.row_of(name) < offset + rows:
            names.append(name)
            if len(names) == want:
                return names
    raise RuntimeError(f"only {len(names)} of {want} nodes in the window")


def _delta_variant(table, vocab, table_spec, timer, *, batch, chunk, k):
    """A pct100 template wave through the delta step with the candidate
    index on: the pallas plane tail (delta_plane_topk) compiles inside
    the normal step function, and its (idx, prio) must equal the XLA
    tail's over the same merged planes."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s1m_tpu.cluster.workload import node_affinity_pods
    from k8s1m_tpu.config import PodSpec
    from k8s1m_tpu.engine.cycle import fill_shape_planes, schedule_batch_delta
    from k8s1m_tpu.engine.deltacache import DeltaPlaneCache, plane_topk
    from k8s1m_tpu.ops.pallas_topk import delta_plane_topk
    from k8s1m_tpu.ops.priority import seed_of
    from k8s1m_tpu.plugins.registry import Profile
    from k8s1m_tpu.snapshot.hotfeed import shape_key
    from k8s1m_tpu.snapshot.pod_encoding import PodBatchHost
    from k8s1m_tpu.tools.megarow_drill import stratum_bits_for

    n = table.num_rows
    profile = Profile(topology_spread=0, interpod_affinity=0)
    pod_spec = PodSpec(
        batch=batch, aff_terms=1, aff_exprs=2, aff_values=2, pref_terms=1
    )
    # Deployment-template shapes (tools/sched_bench --shape-pool): a few
    # structural shapes, every pod of the wave drawn from them.
    templates = node_affinity_pods(8, zones=8, regions=4)
    pods = []
    for i in range(batch):
        t = templates[i % len(templates)]
        pods.append(dc.replace(t, name=f"tmpl-{i}", cpu_milli=10 + i % 8))
    sbits = stratum_bits_for(n)
    cache = DeltaPlaneCache(n, index_k=64, stratum_bits=sbits)
    gen = vocab.generation()
    cache.check_generation(gen)
    keys = [shape_key(p) for p in pods]
    cache.plan(keys, batch)             # first sighting: shapes noted
    plan = cache.plan(keys, batch)      # second: promoted, fills planned
    if plan.slot_ids is None or not plan.fill_idx:
        raise RuntimeError("delta: template wave did not plan a delta step")
    packed = PodBatchHost(pod_spec, table_spec, vocab).encode_packed(pods)
    fill_enc = PodBatchHost(
        dc.replace(pod_spec, batch=cache.fill_batch), table_spec, vocab
    )
    fs = np.full(cache.fill_batch, cache.slots, np.int32)
    fs[: len(plan.fill_slots)] = plan.fill_slots
    with timer.measure("B.delta.fill"):
        planes = fill_shape_planes(
            table, fill_enc.encode_packed([pods[i] for i in plan.fill_idx]),
            jnp.asarray(fs), cache.planes(gen), profile=profile, chunk=chunk,
        )
        jax.block_until_ready(planes)
    cache.commit(*planes)
    cache.note_fill(plan)

    key = jax.random.key(SEED)
    flags = []
    rows_by = {}
    for backend in ("pallas", "xla"):
        planes, index = cache.planes(gen), cache.index_state(gen)
        with timer.measure(f"B.delta.{backend}_step"):
            # Two waves of the one executable: the first finds the index
            # unbuilt and takes the plane tail (rebuilding it), the
            # second may take the index tail.
            for _ in range(2):
                _t, _asg, rows, planes, index, flag = schedule_batch_delta(
                    table, packed, key, profile=profile,
                    slot_ids=jnp.asarray(plan.slot_ids), planes=planes,
                    dirty=jnp.asarray(plan.dirty), chunk=chunk, k=k,
                    backend=backend, stratum_bits=sbits, index=index,
                    rep_idx=jnp.asarray(plan.rep_idx),
                    rebuild_slots=jnp.asarray(plan.rebuild_slots),
                    index_dirty_cap=cache.index_dirty_cap,
                )
                flags.append(int(jax.device_get(flag)))
            rows_by[backend] = jax.device_get(rows)
    _equal("delta step rows", rows_by["pallas"], rows_by["xla"])
    if flags[0] != 0:
        raise RuntimeError("delta: unbuilt index did not take the plane tail")

    pmask, pscore = planes
    slot_ids = jnp.asarray(plan.slot_ids)
    out = {}
    for backend, fn in (("pallas", delta_plane_topk), ("xla", plane_topk)):
        with timer.measure(f"B.delta.{backend}_tail"):
            cand = jax.jit(
                lambda m, s, sl, sd, fn=fn: fn(
                    m, s, sl, sd, chunk=chunk, k=k, stratum_bits=sbits
                )
            )(pmask, pscore, slot_ids, seed_of(key))
            out[backend] = jax.device_get((cand.idx, cand.prio))
    _equal("delta idx", out["pallas"][0], out["xla"][0])
    _equal("delta prio", out["pallas"][1], out["xla"][1])
    # Not every pod need bind: a few hundred pods of one shape share
    # one plane's top classes and can exhaust their four candidates'
    # pod slots (they retry next wave) — identically on both backends.
    bound = int((rows_by["pallas"] >= 0).sum())
    if not bound:
        raise RuntimeError("delta: no pod bound")
    log(
        f"phase B delta: delta_plane_topk compiled, {bound}/{batch} bound, "
        f"(idx, prio) == XLA plane_topk, index path flags={flags}"
    )


def phase_b(coord, timer, *, batch, chunk, score_pct,
            fit_nodes=FIT_NODES) -> dict:
    """Runs every variant even after one fails, so a chip run reports
    all refusals at once; returns {variant: error string} (empty = all
    compiled and matched)."""
    import jax
    import jax.numpy as jnp

    from k8s1m_tpu.cluster.workload import (
        affinity_deployment,
        node_affinity_pods,
        spread_deployment,
        uniform_pods,
    )
    from k8s1m_tpu.config import SEL_OP_IN, PodSpec, TableSpec
    from k8s1m_tpu.engine.cycle import (
        sample_offset_for,
        sample_rows_for,
        schedule_batch_packed,
    )
    from k8s1m_tpu.plugins.registry import Profile
    from k8s1m_tpu.snapshot.constraints import (
        ConstraintTracker,
        empty_constraints,
    )
    from k8s1m_tpu.snapshot.node_table import HOSTNAME_LABEL
    from k8s1m_tpu.snapshot.pod_encoding import (
        NodeSelectorTerm,
        PodBatchHost,
        SelectorRequirement,
    )

    interpreted = jax.default_backend() != "tpu"
    log(
        "phase B: pallas kernels "
        + ("INTERPRETED (not a chip run)" if interpreted
           else "compiled by Mosaic, nothing interpreted")
    )
    table, vocab = coord.table, coord.host.vocab
    n = table.num_rows
    spec = coord.table_spec
    k = coord.k
    sample_rows = sample_rows_for(n, score_pct, chunk)
    # A window in the middle of the rotation, not the trivially-aligned
    # first one.
    offset = sample_offset_for(1, n, sample_rows) if sample_rows else 0
    win = dict(chunk=chunk, k=k, sample_rows=sample_rows, offset=offset)
    failures: dict[str, str] = {}

    # (i) the base fused kernel, with_affinity=False.
    base_profile = Profile(
        node_affinity=0, topology_spread=0, interpod_affinity=0
    )
    base = PodBatchHost(PodSpec(batch=batch), spec, vocab).encode_packed(
        uniform_pods(batch, cpu_milli=10, mem_kib=1024)
    )
    base_rows = attempt(failures, "base", lambda: _variant(
        "base", table, base, base_profile, timer, **win
    ))

    # (ii) the affinity stage, PodSpec slots sized to the workload.
    # Zone and region ids are small; every fourth pod instead requires
    # one of two hostnames inside the window, whose value ids run to the
    # node count — the kernel carries ids through its dots as 16-bit
    # halves, and only wide ids show whether the dot kept them exact.
    def affinity():
        pods = node_affinity_pods(batch, zones=8, regions=4)
        names = _window_node_names(
            coord.host, offset, sample_rows or n, want=64
        )
        for j, p in enumerate(pods[::4]):
            p.required_terms = [NodeSelectorTerm([SelectorRequirement(
                HOSTNAME_LABEL, SEL_OP_IN,
                [names[2 * j % len(names)], names[(2 * j + 1) % len(names)]],
            )])]
        packed = PodBatchHost(
            PodSpec(batch=batch, aff_terms=1, aff_exprs=2, aff_values=2,
                    pref_terms=1),
            spec, vocab,
        ).encode_packed(pods)
        _variant(
            "affinity", table, packed,
            Profile(topology_spread=0, interpod_affinity=0), timer, **win
        )

    attempt(failures, "affinity", affinity)

    # (iii) the constraint stage under the full default profile with live
    # ConstraintState; domain dims sized to the workload (the stage
    # materializes [max_zones, chunk] one-hot planes in VMEM).
    def constraint():
        cspec = TableSpec(max_nodes=n, max_zones=128, max_regions=16)
        tracker = ConstraintTracker(cspec)
        half = batch // 2
        pods = (
            spread_deployment(tracker, "smoke-spread", half, topo=1)
            + affinity_deployment(
                tracker, "smoke-anti", batch - half, anti=True
            )
        )
        packed = PodBatchHost(
            PodSpec(batch=batch, spread_refs=1, affinity_refs=1,
                    spread_incs=1, ipa_incs=1),
            cspec, vocab,
        ).encode_packed(pods)
        # One wave leaves per-domain counts that are small and even, and
        # bf16 carries those exactly.  The second state holds what a
        # long-lived cluster does — thousands per domain, uneven — with
        # every count of the form m*512 + 255: rounded to 8 significant
        # bits each comes back one too high, which puts the least-loaded
        # zone over maxSkew and turns every spread pod infeasible.  Only
        # a count dot that keeps full precision agrees with XLA here.
        def long_lived(cons):
            def counts(t):
                return jnp.broadcast_to(
                    4351 + 512 * jnp.arange(t.shape[1], dtype=t.dtype),
                    t.shape,
                )

            return cons.replace(
                spread_zone=counts(cons.spread_zone),
                spread_region=counts(cons.spread_region),
                tgt_zone=counts(cons.tgt_zone),
                tgt_region=counts(cons.tgt_region),
                own_zone=counts(cons.own_zone),
                own_region=counts(cons.own_region),
            )

        _variant(
            "constraint", table, packed, Profile(), timer,
            constraints=empty_constraints(cspec), also=long_lived, **win
        )

    attempt(failures, "constraint", constraint)

    # (iv) the pallas delta tail behind the candidate index.
    attempt(failures, "delta", lambda: _delta_variant(
        table, vocab, spec, timer, batch=batch, chunk=chunk, k=k
    ))

    # (v) the XLA scan through the normal step function: at the window
    # shape, then over every row of the table (the shape that did not
    # finish warm-up on the chip before chunk_topk took lax.top_k).
    def xla_scan():
        key = jax.random.key(SEED)
        shapes = [("window", sample_rows, offset)]
        if sample_rows is not None:
            shapes.append(("all_rows", None, 0))
        for label, rows, off in shapes:
            got = {}
            for backend in ("xla", "pallas"):
                if label == "window" and backend == "pallas":
                    got[backend] = base_rows
                    continue
                with timer.measure(f"B.xla_scan.{label}.{backend}_step"):
                    _t, _c, _a, r = schedule_batch_packed(
                        table, base, key, profile=base_profile,
                        chunk=chunk, k=k, backend=backend,
                        sample_rows=rows, sample_offset=off,
                    )
                    got[backend] = jax.device_get(r)
            if got["pallas"] is None:
                raise RuntimeError("no pallas base rows to compare with")
            _equal(f"xla_scan {label} rows", got["pallas"], got["xla"])
            log(f"phase B xla_scan {label}: XLA step binds == pallas step binds")

    attempt(failures, "xla_scan", xla_scan)

    # (vi) the conflict rounds against the sequential scan.
    attempt(failures, "assign", lambda: _assign_variant(
        table, base, base_profile, timer, fit_nodes=fit_nodes, **win
    ))
    return failures


# ---- mesh phase ------------------------------------------------------------


def check_sharded(table, sp: int) -> None:
    import jax

    for path, leaf in jax.tree_util.tree_leaves_with_path(table):
        name = jax.tree_util.keystr(path)
        if not leaf.size:
            continue    # fused labels leave an empty [N, 0] value plane
        if leaf.sharding.is_fully_replicated:
            raise RuntimeError(f"mesh: table leaf {name} is fully replicated")
        devs = {s.device for s in leaf.addressable_shards}
        rows = {s.data.shape[0] for s in leaf.addressable_shards}
        if len(devs) != sp or rows != {leaf.shape[0] // sp}:
            raise RuntimeError(
                f"mesh: table leaf {name} on {len(devs)} devices, "
                f"shard rows {rows} (want {sp} x {leaf.shape[0] // sp})"
            )


def mesh_phase(store, compiles, timer, *, nodes, batch, chunk, score_pct,
               waves, webhook_pods, oracle_sample, sp=4) -> None:
    from k8s1m_tpu.control.objects import pod_key
    from k8s1m_tpu.parallel import make_mesh

    mesh = make_mesh(1, sp)
    # The production configuration over the mesh.
    coord, res = phase_a(
        store, compiles, timer, nodes=nodes, batch=batch, chunk=chunk,
        score_pct=score_pct, waves=waves, webhook_pods=webhook_pods,
        oracle_sample=oracle_sample, mesh=mesh, label="M",
    )
    check_sharded(coord.table, sp)
    coord.close()

    # Byte-identity, one chip vs 1xsp, at the full scan.  Each run starts
    # from the same store state: the previous run's pods are deleted and
    # a fresh coordinator bootstraps.
    placements = []
    for label, m in (("M1", None), (f"M{sp}", mesh)):
        c, r = phase_a(
            store, compiles, timer, nodes=nodes, batch=batch, chunk=chunk,
            score_pct=100, waves=waves, webhook_pods=0,
            oracle_sample=oracle_sample, mesh=m, label=label,
        )
        if m is not None:
            check_sharded(c.table, sp)
        c.close()
        ns = f"smoke-{label.lower()}"
        placements.append({
            k[len(ns) + 1:]: v for k, v in r["binds"].items()
        })
        store.put_batch(
            [(pod_key(ns, n), None) for n in r["warm"] + r["names"]]
        )
    if placements[0] != placements[1]:
        diff = sum(
            1 for k in placements[0] if placements[0][k] != placements[1].get(k)
        )
        raise RuntimeError(
            f"mesh: {diff} of {len(placements[0])} binds differ between "
            f"one chip and 1x{sp}"
        )
    log(
        f"mesh phase: 1x{sp} table sharded over sp, "
        f"{len(placements[0])} binds byte-identical to one chip"
    )


# ---- entry -----------------------------------------------------------------


def main() -> int:
    from k8s1m_tpu.envboot import place_compile_cache

    cache_dir = place_compile_cache()
    import jax

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    log(f"device: platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU (platform is "
              f"{dev.platform!r}); refusing to run", file=sys.stderr)
        return 2
    log(f"compile cache: {cache_dir}")

    from k8s1m_tpu.store.native import MemStore

    compiles = CompileCounter()
    timer = Timer()
    failures: dict[str, str] = {}
    t_start = time.perf_counter()
    sizes = dict(nodes=NODES, batch=BATCH, chunk=CHUNK, score_pct=SCORE_PCT)
    with MemStore() as store:
        with timer.measure("store_load"):
            load_nodes(store, NODES)
        done = attempt(failures, "A", lambda: phase_a(
            store, compiles, timer, waves=WAVES, webhook_pods=WEBHOOK_PODS,
            oracle_sample=ORACLE_SAMPLE, **sizes,
        ))
        if done is not None:
            coord = done[0]
            coord.close()
            failures.update(phase_b(
                coord, timer, batch=BATCH, chunk=CHUNK, score_pct=SCORE_PCT
            ))
            del coord, done
        if device["count"] >= 4:
            attempt(failures, "mesh", lambda: mesh_phase(
                store, compiles, timer, waves=MESH_WAVES,
                webhook_pods=WEBHOOK_PODS, oracle_sample=ORACLE_SAMPLE,
                **sizes,
            ))
        else:
            log(f"mesh phase: did not run ({device['count']} chip visible, "
                "needs 4)")

    log(
        f"set-up seconds: total={time.perf_counter() - t_start:.1f} "
        + " ".join(f"{k}={v}" for k, v in timer.seconds.items())
    )
    log(f"executables built={compiles.built} "
        f"persistent-cache hits={compiles.cache_hits}")
    if failures:
        for name, err in failures.items():
            print(f"chip_smoke: phase {name} FAILED: {err}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
