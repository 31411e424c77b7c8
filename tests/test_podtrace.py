"""podtrace: per-pod lifecycle tracing with stage attribution (ISSUE 13).

Layers:

1. The tracer itself — deterministic head sampling, the contiguous
   span-chain contract, bounds (live cap + completed ring), and the
   free null tracer.
2. The Perfetto exporter + structural validator — valid trace-event
   JSON, monotone per-track timestamps, every flow event resolves; the
   validator also actually rejects malformed documents.
3. The composed tier-1 acceptance gate: at 4096 nodes under capacity
   churn + tenants + depth-3 pipelining, stage attribution covers
   >= 95% of every traced pod's schedule-to-bind time (sum of stage
   spans vs end-to-end) and the waterfall's shares sum to ~1.
"""

from __future__ import annotations

import json
import os
import time

from k8s1m_tpu.config import PodSpec, TableSpec
from k8s1m_tpu.control.coordinator import Coordinator
from k8s1m_tpu.control.objects import encode_node, encode_pod, node_key, pod_key
from k8s1m_tpu.obs.podtrace import (
    NULL_TRACER,
    PodTracer,
    STAGES,
    validate_trace,
)
from k8s1m_tpu.plugins.registry import Profile
from k8s1m_tpu.snapshot.node_table import NodeInfo
from k8s1m_tpu.snapshot.pod_encoding import PodInfo
from k8s1m_tpu.store.native import MemStore
from k8s1m_tpu.tenancy import TenancyController
from k8s1m_tpu.tenancy.policy import TenancyPolicy

PROFILE = Profile(topology_spread=0, interpod_affinity=0)


# ---- 1. the tracer -----------------------------------------------------


def test_sampling_is_deterministic_and_head_based():
    t1 = PodTracer(sample_n=4)
    t2 = PodTracer(sample_n=4)
    keys = [f"ns/pod-{i}" for i in range(400)]
    picked = [k for k in keys if t1.sampled(k)]
    # Same decision on a fresh tracer (pure pod-key hash, no RNG).
    assert picked == [k for k in keys if t2.sampled(k)]
    # Roughly 1-in-4 (hash spread, not an exact stride).
    assert 50 <= len(picked) <= 150
    # sample_n=1 traces everything.
    assert all(PodTracer(sample_n=1).sampled(k) for k in keys)


def test_span_chain_is_contiguous_and_telescopes():
    tr = PodTracer(sample_n=1)
    assert tr.begin("ns/p", 10.0, source="test")
    assert not tr.begin("ns/p", 11.0)      # already live: no re-anchor
    tr.emit("ns/p", "queue_wait", t=10.5)
    tr.emit("ns/p", "encode", t=10.6)
    # A non-monotone stamp clamps to the chain head, never rewinds.
    tr.emit("ns/p", "device", t=10.4)
    done = tr.finish("ns/p", "bind", t=11.0, outcome="bound")
    assert done is not None
    spans = done.spans
    assert [s[0] for s in spans] == ["queue_wait", "encode", "device", "bind"]
    for (_, _, t1, _), (_, t0, _, _) in zip(spans, spans[1:]):
        assert t0 == t1                    # contiguous by construction
    assert sum(t1 - t0 for _, t0, t1, _ in spans) == 11.0 - 10.0
    assert tr.live_count() == 0
    # Emits against a finished (or never-begun) key no-op.
    assert not tr.emit("ns/p", "late")
    assert not tr.emit("ns/other", "late")


def test_tracer_bounds_live_and_ring():
    tr = PodTracer(sample_n=1, max_live=8, ring=4)
    opened = sum(tr.begin(f"ns/p{i}", float(i)) for i in range(20))
    assert opened == 8                     # live cap: the rest dropped
    for i in range(8):
        tr.finish(f"ns/p{i}", "bind", t=100.0)
    assert len(tr.completed()) == 4        # ring keeps the newest 4


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    assert not NULL_TRACER.begin("k", 0.0)
    assert not NULL_TRACER.emit("k", "bind")
    assert NULL_TRACER.finish("k", "bind") is None
    assert NULL_TRACER.spans_of("k") == []
    assert NULL_TRACER.attribution() == {}


# ---- 2. exporter + validator ------------------------------------------


def _traced_run(tmp_path, *, sample_n=1, pods=6):
    store = MemStore()
    for i in range(32):
        store.put(node_key(f"n-{i}"), encode_node(NodeInfo(
            name=f"n-{i}", cpu_milli=64000, mem_kib=1 << 24, pods=110,
        )))
    tracer = PodTracer(sample_n=sample_n)
    coord = Coordinator(
        store, TableSpec(max_nodes=64), PodSpec(batch=8), PROFILE,
        chunk=64, with_constraints=False, tracer=tracer,
    )
    try:
        coord.bootstrap()
        for i in range(pods):
            store.put(
                pod_key("default", f"p{i}"),
                encode_pod(PodInfo(f"p{i}", cpu_milli=10, mem_kib=1024)),
            )
        assert coord.run_until_idle() == pods
    finally:
        coord.close()
        store.close()
    return tracer


def test_export_validates_and_flows_resolve(tmp_path):
    tracer = _traced_run(tmp_path)
    path = str(tmp_path / "trace.json")
    tracer.export(path)
    with open(path) as f:
        doc = json.load(f)                 # valid JSON by parse
    assert validate_trace(doc) == []
    evs = doc["traceEvents"]
    phs = {e["ph"] for e in evs}
    assert {"M", "X", "s", "f"} <= phs
    # Stage tracks are named via thread_name metadata.
    names = {
        e["args"]["name"] for e in evs
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert {"queue_wait", "encode", "device", "bind"} <= names
    assert names <= set(STAGES)
    # Device spans carry the wave attributes.
    dev = [e for e in evs if e["ph"] == "X" and e["name"] == "device"]
    assert dev and all(
        "wave_epoch" in e["args"] and "path" not in e["args"]
        and e["args"]["depth"] >= 1
        for e in dev
    )


def test_validator_rejects_malformed_documents():
    assert validate_trace({}) != []
    assert validate_trace({"traceEvents": "nope"}) != []
    # Non-monotone per-track X timestamps.
    bad_order = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 1, "name": "a", "ts": 10, "dur": 1},
        {"ph": "X", "pid": 1, "tid": 1, "name": "b", "ts": 5, "dur": 1},
    ]}
    assert any("monotone" in e for e in validate_trace(bad_order))
    # A flow finish with no start, and a start that never finishes.
    dangling = {"traceEvents": [
        {"ph": "f", "pid": 1, "tid": 1, "ts": 1, "id": 7},
        {"ph": "s", "pid": 1, "tid": 1, "ts": 2, "id": 8},
    ]}
    errs = validate_trace(dangling)
    assert any("before its 's'" in e for e in errs)
    assert any("never finished" in e for e in errs)


def test_submit_external_admit_span_even_when_webhook_began_trace():
    """The admit span (with tenant + bucket attrs) lands whether the
    trace was opened by the webhook at receipt (shared tracer) or by
    submit_external itself — begin() deduplicates, emit() must not be
    gated on it."""
    tracer = PodTracer(sample_n=1)
    with MemStore() as store:
        store.put(node_key("n-0"), encode_node(NodeInfo(
            name="n-0", cpu_milli=64000, mem_kib=1 << 24, pods=110,
        )))
        tn = TenancyController(TenancyPolicy())
        coord = Coordinator(
            store, TableSpec(max_nodes=16), PodSpec(batch=8), PROFILE,
            chunk=16, with_constraints=False, tenancy=tn, tracer=tracer,
        )
        try:
            coord.bootstrap()
            pod = PodInfo("w0", cpu_milli=10, mem_kib=1024)
            obj = json.loads(encode_pod(pod))
            # The webhook opened the trace first (shared tracer).
            tracer.begin(
                "default/w0", time.perf_counter(), source="webhook"
            )
            coord.submit_external(obj)
            store.put(pod_key("default", "w0"), encode_pod(pod))
            assert coord.run_until_idle() == 1
        finally:
            coord.close()
    done = [t for t in tracer.completed() if t.key == "default/w0"]
    assert done
    admit = [s for s in done[0].spans if s[0] == "admit"]
    assert admit, [s[0] for s in done[0].spans]
    attrs = admit[0][3]
    assert attrs["tenant"] == "default" and "bucket" in attrs
    assert done[0].attrs["source"] == "webhook"   # receipt anchor won


def test_committed_perfetto_artifact_validates():
    """The committed sample export stays structurally valid (valid
    trace-event JSON, monotone per-track timestamps, flows resolve) —
    regenerate via `steady_drill --smoke --trace 4 --trace-out
    artifacts/podtrace_steady_smoke.trace.json` when it drifts."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(
        repo, "artifacts", "podtrace_steady_smoke.trace.json"
    )
    with open(path) as f:
        doc = json.load(f)
    assert validate_trace(doc) == []
    assert len(doc["traceEvents"]) > 100


# ---- 3. the composed acceptance gate ----------------------------------


def test_podtrace_composed_4096_coverage_gate():
    """ISSUE 13 acceptance: at 4096 nodes under capacity churn +
    tenants + depth-3 pipelining, the stage spans of every traced pod
    sum to >= 95% of its schedule-to-bind time, and the attribution
    waterfall is internally consistent (shares sum to ~1)."""
    tracer = PodTracer(sample_n=4)
    with MemStore() as store:
        for i in range(4096):
            store.put(node_key(f"n{i:05d}"), encode_node(NodeInfo(
                name=f"n{i:05d}", cpu_milli=1 << 22, mem_kib=1 << 30,
                pods=(1 << 15) - 1,
            )))
        tn = TenancyController(TenancyPolicy())
        coord = Coordinator(
            store, TableSpec(max_nodes=4096, max_zones=16, max_regions=8),
            PodSpec(batch=64), PROFILE, chunk=512, k=4,
            with_constraints=False, seed=13, pipeline=True, depth=3,
            tenancy=tn, tracer=tracer,
        )
        try:
            coord.bootstrap()
            seq = 0
            for wave in range(6):
                for i in range(48):
                    seq += 1
                    ns = f"tenant-{i % 3}"
                    store.put(
                        pod_key(ns, f"p{seq:05d}"),
                        encode_pod(PodInfo(
                            f"p{seq:05d}", namespace=ns,
                            cpu_milli=10, mem_kib=1 << 10,
                        )),
                    )
                for j in range(8):         # capacity-only churn
                    i = (17 * wave + j) % 4096
                    store.put(node_key(f"n{i:05d}"), encode_node(NodeInfo(
                        name=f"n{i:05d}", cpu_milli=(1 << 22) + wave,
                        mem_kib=1 << 30, pods=(1 << 15) - 1,
                    )))
                coord.step()
            coord.run_until_idle()
        finally:
            coord.close()
    traces = tracer.completed()
    assert len(traces) >= 40               # ~288/4 head-sampled
    for t in traces:
        total = t.last_t - t.t0
        covered = sum(t1 - t0 for _, t0, t1, _ in t.spans)
        assert covered >= 0.95 * total, (t.key, covered, total)
    att = tracer.attribution()
    assert att["coverage"] >= 0.95
    assert abs(sum(s["share"] for s in att["stages"].values()) - 1.0) < 0.05
    # The lifecycle stages the composed pipeline must attribute.
    assert {"queue_wait", "encode", "dispatch_wait", "device", "bind"} <= (
        set(att["stages"])
    )
    assert att["end_to_end"]["p50_ms"] > 0
    # Depth-3 pipelining visibly attributed: some device span saw the
    # pipeline at depth > 1.
    depths = {
        a.get("depth") for t in traces
        for s, _, _, a in t.spans if s == "device"
    }
    assert max(d for d in depths if d is not None) > 1
