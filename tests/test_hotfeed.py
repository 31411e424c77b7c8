"""hotfeed: differential byte-identity + double-buffered feed suite.

Layers:

1. **Differential** — the cached ``HotPodBatchHost`` must be
   byte-identical to the uncached ``PodBatchHost`` on every output
   (``encode_packed`` ints/bools/groups/fields, ``encode`` PodBatch
   arrays), across shape reuse, the TEMPLATE_MIN small-group fork,
   arena recycling, vocab growth, and the adjust-path commit fields.
2. **Feed unit** — HostFeed's claim protocol fails closed on every
   staleness axis: vocab generation moved, queue prefix reordered,
   worker encode raised.
3. **Feed integration** — a pipelined coordinator under vocab-growing
   node churn never hands a wave a batch encoded against a stale vocab
   (every launch's ``vocab_gen`` matches the live generation), and the
   staged path actually engages.
4. **Committed-evidence gate** — ``hostpath_bench --smoke`` passes its
   speedup gate and its built-in byte-identity check.
"""

from __future__ import annotations

import collections
import time

import numpy as np
import pytest

from k8s1m_tpu.config import (
    SEL_OP_GT,
    SEL_OP_IN,
    SEL_OP_NOT_IN,
    TOPO_HOSTNAME,
    TOPO_ZONE,
    PodSpec,
    TableSpec,
)
from k8s1m_tpu.engine.cycle import commit_fields_np
from k8s1m_tpu.obs.metrics import REGISTRY
from k8s1m_tpu.snapshot.hotfeed import (
    PLAIN,
    TEMPLATE_MIN,
    EncodeCache,
    HostFeed,
    HotPodBatchHost,
    fingerprint,
)
from k8s1m_tpu.snapshot.node_table import NodeInfo, NodeTableHost, Taint
from k8s1m_tpu.snapshot.pod_encoding import (
    AffinityTermRef,
    NodeSelectorTerm,
    PodBatchHost,
    PodInfo,
    PreferredSchedulingTerm,
    SelectorRequirement,
    SpreadConstraintRef,
    Toleration,
)


def make_host(n: int = 32) -> NodeTableHost:
    host = NodeTableHost(TableSpec(max_nodes=64))
    for i in range(n):
        host.upsert(NodeInfo(
            name=f"n-{i}",
            labels={"zone": f"z{i % 4}", "disk": ("ssd", "hdd")[i % 2],
                    "gen": str(i % 5)},
            taints=(
                [Taint("dedicated", f"team{i % 3}", 1)] if i % 5 == 0 else []
            ),
        ))
    return host


def shaped_pod(i: int, shape: int, tag: str = "p") -> PodInfo:
    """Deterministic pod; ``shape`` selects the structural template."""
    p = PodInfo(f"{tag}-{i}", cpu_milli=10 + i, mem_kib=512 + i)
    if shape == 0:
        return p                                    # plain
    if shape == 1:
        p.node_selector = {"disk": "ssd"}
        p.tolerations = [Toleration(key="dedicated", value="team1")]
        p.required_terms = [NodeSelectorTerm([
            SelectorRequirement("gen", SEL_OP_GT, ["2"]),
            SelectorRequirement("zone", SEL_OP_IN, ["z0", "z1"]),
        ])]
    elif shape == 2:
        p.preferred_terms = [PreferredSchedulingTerm(
            7, NodeSelectorTerm([
                SelectorRequirement("zone", SEL_OP_NOT_IN, ["z3"]),
            ]),
        )]
        p.node_name = "n-1"
    elif shape == 3:
        p.spread_refs = [SpreadConstraintRef(1, TOPO_ZONE)]
        p.affinity_refs = [AffinityTermRef(
            2, TOPO_HOSTNAME, required=True, anti=True,
        )]
        p.spread_incs = [(1, TOPO_ZONE)]
        p.ipa_incs = [(2, TOPO_HOSTNAME)]
    else:
        p.node_selector = {f"k{shape}": f"v{shape}", "zone": "z2"}
        p.tolerations = [Toleration()]              # tolerate-everything
    return p


def assert_packed_equal(a, b, ctx: str = "") -> None:
    assert a.groups == b.groups, (ctx, a.groups, b.groups)
    np.testing.assert_array_equal(a.ints, b.ints, ctx)
    np.testing.assert_array_equal(a.bools, b.bools, ctx)
    assert set(a.fields) == set(b.fields), ctx
    for name in a.fields:
        np.testing.assert_array_equal(
            a.fields[name], b.fields[name], f"{ctx}:{name}"
        )


def encoders(host, batch=16, **kw):
    spec = PodSpec(batch=batch)
    ref = PodBatchHost(spec, host.spec, host.vocab)
    hot = HotPodBatchHost(spec, host.spec, host.vocab, **kw)
    return ref, hot


# ---- differential ----------------------------------------------------


def test_encode_packed_byte_identical_across_batches_and_arena_reuse():
    host = make_host()
    ref, hot = encoders(host)
    # Varied batches: rich, plain-only (arena bleed check), mixed order,
    # singleton shapes (direct fork) and repeated shapes (template fork).
    batches = [
        [shaped_pod(i, i % 5) for i in range(14)],
        [shaped_pod(i, 0, "plain") for i in range(9)],
        [shaped_pod(i, 1, "t") for i in range(TEMPLATE_MIN + 3)],
        [shaped_pod(i, (i * 3) % 5, "m") for i in range(16)],
        [shaped_pod(0, 4, "one")],
    ]
    for bi, pods in enumerate(batches):
        assert_packed_equal(
            ref.encode_packed(pods), hot.encode_packed(pods), f"batch{bi}"
        )
    # Shape reuse across calls must be served from the template cache.
    assert len(hot.cache) > 0


def test_encode_unpacked_byte_identical():
    host = make_host()
    ref, hot = encoders(host)
    pods = [shaped_pod(i, i % 5) for i in range(12)]
    a, b = ref.encode(pods), hot.encode(pods)
    for name in type(a).__dataclass_fields__:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)), name
        )


def test_vocab_growth_invalidates_and_stays_identical():
    host = make_host(8)
    ref, hot = encoders(host)
    pods = [shaped_pod(i, 1, "g") for i in range(8)]
    assert_packed_equal(ref.encode_packed(pods), hot.encode_packed(pods))
    gen0 = host.vocab.feed_generation()
    # Grow every encode-relevant namespace: new taint triple (changes
    # `tolerated`), new label value for "disk" (a selector value that
    # previously encoded NONE_ID would now resolve).
    host.upsert(NodeInfo(
        name="new-node", labels={"disk": "nvme", "newkey": "newval"},
        taints=[Taint("dedicated", "team9", 1)],
    ))
    assert host.vocab.feed_generation() > gen0
    assert_packed_equal(
        ref.encode_packed(pods), hot.encode_packed(pods), "post-growth"
    )


def test_tolerations_against_no_matching_taint_keep_group_parity():
    """A pod whose tolerations match no live triple produces an all-zero
    tolerated row — the uncached path then EXCLUDES the tol group, and
    the cached group derivation must agree (it scans, not assumes)."""
    host = NodeTableHost(TableSpec(max_nodes=8))
    host.upsert(NodeInfo(name="n0", taints=[Taint("k", "v", 1)]))
    ref, hot = encoders(host, batch=8)
    p = PodInfo("never", cpu_milli=5, mem_kib=64)
    p.tolerations = [Toleration(key="other", value="x")]
    pods = [p] * (TEMPLATE_MIN + 1)
    a, b = ref.encode_packed(pods), hot.encode_packed(pods)
    assert "tol" not in a.groups
    assert_packed_equal(a, b)


def test_adjust_path_commit_fields_identical():
    """The coordinator's _process_adjusts consumes commit fields from
    the cached packed encode; they must match the uncached encode for
    constraint-carrying pods (the CAS-rollback / delete storm shape)."""
    host = make_host()
    ref, hot = encoders(host)
    pods = [shaped_pod(i, 3, "adj") for i in range(TEMPLATE_MIN + 2)]
    fa = commit_fields_np(ref.encode_packed(pods).fields)
    fb = commit_fields_np(hot.encode_packed(pods).fields)
    for name in type(fa).__dataclass_fields__:
        np.testing.assert_array_equal(
            np.asarray(getattr(fa, name)), np.asarray(getattr(fb, name)),
            name,
        )


def test_fields_survive_arena_recycling():
    """A wave's packed fields are read at retire time, after later
    encodes recycled the arena — they must be views of the wave's own
    buffers, not the arena."""
    host = make_host()
    _, hot = encoders(host)
    pods = [shaped_pod(i, 1, "w") for i in range(TEMPLATE_MIN)]
    first = hot.encode_packed(pods)
    keep = {k: v.copy() for k, v in first.fields.items()}
    for r in range(3):
        hot.encode_packed([shaped_pod(i, (i + r) % 5, f"x{r}") for i in range(10)])
    for name, arr in keep.items():
        np.testing.assert_array_equal(arr, first.fields[name], name)


def test_plain_fingerprint_is_shared_sentinel():
    assert fingerprint(PodInfo("a")) is PLAIN
    p = PodInfo("b")
    p.node_selector = {"k": "v"}
    assert fingerprint(p) is not PLAIN


# ---- feed unit -------------------------------------------------------


def _pending(pods):
    """Wrap PodInfos the way the coordinator queues them."""
    from k8s1m_tpu.control.coordinator import PendingPod

    return [
        PendingPod(
            p, 1, 0.0, cpu_milli=p.cpu_milli, mem_kib=p.mem_kib,
            key_str=p.key,
        )
        for p in pods
    ]


def _mkfeed(host, batch=8):
    enc = HotPodBatchHost(
        PodSpec(batch=batch), host.spec, host.vocab, path="feed"
    )
    return HostFeed(enc)


def test_feed_claim_happy_path_and_stale_vocab():
    host = make_host()
    feed = _mkfeed(host)
    try:
        queue = collections.deque(
            _pending([shaped_pod(i, 1, "f") for i in range(8)])
        )
        assert feed.stage(queue, 8)
        taken = [queue.popleft() for _ in range(8)]
        packed = feed.claim(taken, host.vocab.feed_generation())
        assert packed is not None

        # Stale vocab: stage again, grow the vocab, claim must refuse.
        queue = collections.deque(
            _pending([shaped_pod(i, 1, "f2") for i in range(8)])
        )
        base = REGISTRY.get("hotfeed_stale_batches_total").value(
            reason="vocab"
        )
        assert feed.stage(queue, 8)
        # Wait for the worker to finish BEFORE growing the vocab, so
        # the staged batch is deterministically stale (growth during
        # the encode would also be caught — but by the same check).
        deadline = time.monotonic() + 10.0
        while not feed.ready():
            assert time.monotonic() < deadline, "feed worker stuck"
            time.sleep(0.005)
        host.upsert(NodeInfo(
            name="grow", labels={"fresh": "value"},
            taints=[Taint("fresh", "t", 1)],
        ))
        taken = [queue.popleft() for _ in range(8)]
        assert feed.claim(taken, host.vocab.feed_generation()) is None
        assert REGISTRY.get("hotfeed_stale_batches_total").value(
            reason="vocab"
        ) == base + 1
    finally:
        feed.close()


def test_feed_claim_refuses_reordered_prefix_and_short_batch():
    host = make_host()
    feed = _mkfeed(host)
    try:
        queue = collections.deque(
            _pending([shaped_pod(i, 0, "r") for i in range(10)])
        )
        assert feed.stage(queue, 8)
        # A requeue_front-style mutation changes the prefix.
        queue.appendleft(_pending([shaped_pod(99, 0, "intruder")])[0])
        taken = [queue.popleft() for _ in range(8)]
        assert feed.claim(taken, host.vocab.feed_generation()) is None
        # Nothing staged now: an immediate claim is a clean miss.
        assert feed.claim(taken, host.vocab.feed_generation()) is None
    finally:
        feed.close()


def test_feed_worker_error_stages_none_and_inline_path_raises():
    host = make_host()
    feed = _mkfeed(host, batch=8)
    try:
        bad = shaped_pod(0, 1, "bad")
        # More distinct selector keys than PodSpec.query_keys can hold:
        # the worker encode raises, the claim falls back to None, and
        # the inline encode reproduces the error for the caller.
        bad.node_selector = {f"k{i}": "v" for i in range(64)}
        queue = collections.deque(
            _pending([bad] + [shaped_pod(i, 0, "ok") for i in range(7)])
        )
        assert feed.stage(queue, 8)
        taken = [queue.popleft() for _ in range(8)]
        assert feed.claim(taken, host.vocab.feed_generation()) is None
        with pytest.raises(ValueError):
            feed.encoder.encode_packed(
                [p.ensure_pod() for p in taken]
            )
    finally:
        feed.close()


def test_feed_plain_lane_is_generation_independent():
    host = make_host()
    feed = _mkfeed(host)
    try:
        from k8s1m_tpu.control.coordinator import PendingPod

        queue = collections.deque([
            PendingPod(None, 1, 0.0, cpu_milli=5 + i, mem_kib=64,
                       key_str=f"default/pl-{i}")
            for i in range(8)
        ])
        assert feed.stage(queue, 8)
        # Vocab growth does NOT invalidate a plain-lane batch.
        host.upsert(NodeInfo(name="g2", labels={"zz": "yy"}))
        taken = [queue.popleft() for _ in range(8)]
        packed = feed.claim(taken, host.vocab.feed_generation())
        assert packed is not None and packed.vocab_gen is None
    finally:
        feed.close()


def test_feed_lock_discipline_under_audit():
    """The @guarded_by annotations on HostFeed/EncodeCache hold under
    the PR-4 runtime audit: a full stage -> encode -> claim round trip
    (cycle thread + worker thread) records zero violations."""
    from k8s1m_tpu.lint import guards

    host = make_host()
    with guards.audit():
        feed = _mkfeed(host)
        try:
            queue = collections.deque(
                _pending([shaped_pod(i, 1, "aud") for i in range(8)])
            )
            assert feed.stage(queue, 8)
            taken = [queue.popleft() for _ in range(8)]
            assert feed.claim(taken, host.vocab.feed_generation()) is not None
            assert feed.depth() == 0 and not feed.ready()
        finally:
            feed.close()
    assert guards.violations() == []


# ---- feed integration: churn never hands a wave a stale batch --------


def test_coordinator_feed_never_launches_stale_vocab_batch():
    from k8s1m_tpu.control.coordinator import Coordinator
    from k8s1m_tpu.control.objects import (
        encode_node,
        encode_pod,
        node_key,
        pod_key,
    )
    from k8s1m_tpu.plugins.registry import Profile
    from k8s1m_tpu.store.native import MemStore

    store = MemStore()
    for i in range(64):
        store.put(node_key(f"kn-{i}"), encode_node(NodeInfo(
            name=f"kn-{i}", cpu_milli=64000, mem_kib=64 << 20,
            labels={"zone": f"z{i % 4}"},
        )))
    profile = Profile(topology_spread=0, interpod_affinity=0)
    coord = Coordinator(
        store, TableSpec(max_nodes=64), PodSpec(batch=16),
        profile, chunk=64, with_constraints=False,
        pipeline=True, depth=2, hotfeed=True,
    )
    coord.bootstrap()

    launches: list[tuple] = []
    orig_launch = coord._launch

    def checked_launch(batch_pods, batch):
        gen = coord.host.vocab.feed_generation()
        launches.append((batch.vocab_gen, gen))
        assert batch.vocab_gen is None or batch.vocab_gen == gen, (
            "wave launched with a batch encoded against a stale vocab"
        )
        return orig_launch(batch_pods, batch)

    coord._launch = checked_launch
    used0 = REGISTRY.get("hotfeed_staged_used_total").value()

    # Selector-carrying pods (non-plain: the staged batches are vocab-
    # stamped) interleaved with node updates that grow the vocab (a new
    # label value per round — capacity-only row updates, no quiesce).
    total = 0
    bound = 0
    for round_i in range(6):
        for i in range(32):
            p = PodInfo(f"c{round_i}-{i}", cpu_milli=5, mem_kib=64)
            p.node_selector = {"zone": f"z{i % 4}"}
            store.put(pod_key("default", p.name), encode_pod(p))
            total += 1
        bound += coord.step()
        # Mid-stream vocab growth: an existing node gains a fresh label
        # value while a staged batch may be waiting.
        store.put(node_key("kn-3"), encode_node(NodeInfo(
            name="kn-3", cpu_milli=64000, mem_kib=64 << 20,
            labels={"zone": "z3", "round": f"r{round_i}"},
        )))
        bound += coord.step()
    bound += coord.run_until_idle()
    # Quiet tail (no node churn): staged batches here cannot go vocab-
    # stale, so the feed engages deterministically — during the churn
    # rounds above, discarding most staged batches is the CORRECT
    # outcome, so engagement there is timing-dependent.
    for i in range(64):
        p = PodInfo(f"tail-{i}", cpu_milli=5, mem_kib=64)
        p.node_selector = {"zone": f"z{i % 4}"}
        store.put(pod_key("default", p.name), encode_pod(p))
        total += 1
    for _ in range(6):
        bound += coord.step()
    bound += coord.run_until_idle()
    coord.close()
    assert bound == total, (bound, total)
    assert launches, "no waves launched"
    # The feed engaged at least once across the run.
    assert REGISTRY.get("hotfeed_staged_used_total").value() > used0


# ---- committed-evidence gate -----------------------------------------


def test_hostpath_bench_smoke_passes(tmp_path):
    """Satellite: the CPU-JAX host-path microbenchmark's --smoke shape
    runs to its report with byte-identity asserted per batch and every
    timed encode served from the template cache.  What a CPU run can
    prove, and no more: the tool's own gate on the speed ratio (exit 1
    under ``gate``) is a stopwatch, and under ``-n 6`` on a loaded CPU it
    read under 2x with nothing wrong; the report is written before it."""
    import json

    from k8s1m_tpu.tools.hostpath_bench import main

    out = tmp_path / "hostpath.json"
    try:
        main(["--smoke", "--no-cycle", "--out", str(out)])
    except SystemExit as e:       # the tool's speed gate alone exits
        assert e.code == 1
    report = json.loads(out.read_text())
    assert report["metric"] == "hostpath_encode_speedup_smoke"
    assert report["detail"]["byte_identical"] is True
    encode = report["detail"]["encode"]
    assert encode["cache_hit_rate"] == 1.0
    assert 0 < encode["distinct_templates"] <= report["detail"]["shapes"]
    assert encode["pods"] > 0 and report["value"] > 0


def test_committed_artifact_meets_acceptance():
    """The committed artifacts/hostpath_bench.json shows the >=3x
    encode-path win on the 90%-shape-shared load."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(__file__), "..", "artifacts", "hostpath_bench.json"
    )
    with open(path) as f:
        report = json.load(f)
    d = report["detail"]
    assert d["byte_identical"] is True
    assert d["share"] == 0.9
    assert report["value"] >= 3.0
    assert d["encode"]["cache_hit_rate"] >= 0.9
