"""A ``WaveSkew`` wave settled a block at a time: ``greedy_assign`` against
the sequential skew scan it replaced, bind for bind.

The reference is the scan itself (``chip_smoke.sequential_skew_assign``:
pod i takes the first of its candidates that has room after pods j < i,
keeps every hard zone and region constraint within maxSkew on the counts
as pods j < i left them and, under the gate, lies in no domain an earlier
pod closed to it).  ``greedy_assign`` settles such a wave in blocks of W
pods by rounds of ``F`` and hands what the rounds leave of a block to the
scan's own step, so on every input its outputs have to be the scan's,
the count table its blocks fold has to be the scan's final one, and
``settled`` has to say which of the two did the work.
"""

from __future__ import annotations

from unittest import mock

import jax
import numpy as np
import pytest

from k8s1m_tpu.config import TOPO_HOSTNAME, TOPO_REGION, TOPO_ZONE
from k8s1m_tpu.engine import assign
from k8s1m_tpu.engine.assign import WaveSkew, greedy_assign
from chip_smoke import sequential_skew_assign    # conftest.py: the root is on the path
from test_interpod_waves import interpod_of, random_wave

W = assign._block_pods(4096)


def skew_of(rng, dep, *, zones, regions, topos=(TOPO_ZONE, TOPO_REGION),
            max_skew=(1, 2), own=0.9, start=3):
    """The count state of pods ``dep`` [B] (the pod's Deployment):
    Deployment d's hard constraint in slot 2d, on a topology of ``topos``
    with a maxSkew of ``max_skew``, its soft hostname one in slot 2d+1;
    a share ``own`` of the pods carries its Deployment's label (counts
    itself, increments both slots); wave-start counts below ``start``.
    Zone ids 1..zones-1 and region ids 1..regions-1 hold nodes."""
    b, deps = len(dep), int(dep.max()) + 1
    topo_d = rng.choice(topos, deps)
    skew_d = rng.choice(max_skew, deps)
    pair = lambda a, c: np.stack([a, c], axis=1).astype(np.int32)
    cid = pair(2 * dep, 2 * dep + 1)
    topo = pair(topo_d[dep], np.full(b, TOPO_HOSTNAME))
    mine = rng.random(b) < own
    present = np.zeros(zones + regions, bool)
    present[1:zones] = True
    present[zones + 1:] = True
    return WaveSkew(
        counts=rng.integers(0, start, (2 * deps, zones + regions)).astype(np.int32),
        present=present, zones=zones, cid=cid, topo=topo,
        max_skew=pair(skew_d[dep], np.ones(b)), self_inc=pair(mine, mine),
        hard=pair(np.ones(b), np.zeros(b)).astype(bool),
        inc_valid=pair(mine, mine).astype(bool), inc_cid=cid, inc_topo=topo,
    )


def per_zone(rng, b, *, zones=8, rows=64, slots=110, same_order=False,
             valid=1.0):
    """``b`` pods with the best row of each zone 1..``zones`` as their
    candidates (``rows`` nodes a zone, ``slots`` pods a node), in priority
    order, and the slot of zone id 0 empty — ``candidates(in_wave_skew=
    True)``'s form; ``same_order``: every pod ranks the zones alike, so
    each bind moves the next pod of its Deployment to another zone."""
    k = zones + 1
    zone = np.tile(np.arange(k, dtype=np.int32), (b, 1))
    idx = np.where(zone > 0, zone * rows + rng.integers(0, rows, (b, k)), -1)
    prio = (rng.integers(0, 1 << 20, (b, k)) if not same_order
            else np.tile((k - np.arange(k)) << 10, (b, 1)))
    prio = np.where(zone > 0, prio, -1)
    order = np.argsort(-prio, axis=1, kind="stable")
    idx, prio, zone = (np.take_along_axis(x, order, axis=1)
                       for x in (idx, prio, zone))
    full = lambda v: np.full((b, k), v, np.int32)
    return (idx.astype(np.int32), prio.astype(np.int32), full(32000),
            full(64 << 20), np.where(idx >= 0, slots, 0).astype(np.int32),
            np.full(b, 100, np.int32), np.full(b, 200 << 10, np.int32),
            rng.random(b) < valid, zone, np.where(zone > 0, (zone - 1) % 4 + 1, 0)
            .astype(np.int32))


_reference = jax.jit(lambda raw, skew, ip: sequential_skew_assign(
    *raw[:8], skew, raw[8], raw[9], ip))


def blocks(raw, skew, ip):
    """``greedy_assign``'s outputs, and the count table its blocks leave
    (read off ``_skew_blocks`` inside the same trace)."""
    seen = []
    real = assign._skew_blocks

    def spy(*args):
        out = real(*args)
        seen.append(out[5])
        return out

    def fn(raw, skew, ip):
        return greedy_assign(*raw[:8], skew, raw[8], raw[9], ip), seen[-1]

    with mock.patch.object(assign, "_skew_blocks", spy):
        return jax.jit(fn)(raw, skew, ip)


def check(raw, skew, ip=None):
    """The blocks against the scan; returns (bound, settled as a list)."""
    raw = tuple(map(np.asarray, raw))
    out, counts = jax.device_get(blocks(raw, skew, ip))
    want = jax.device_get(_reference(raw, skew, ip))
    node_row, bound, score, chosen_k, legal, settled, *blocked = out
    valid = raw[7]
    for name, got, exp in (("node_row", node_row, want[0]),
                           ("bound", bound, want[1]), ("score", score, want[2]),
                           ("legal", legal, want[4]), ("counts", counts, want[6]),
                           # an invalid pod's slot under the bootstrap rule
                           # is the scan's alone (it binds nowhere)
                           ("chosen_k", chosen_k[valid], want[3][valid])):
        assert (got == exp).all(), (name, np.flatnonzero(got != exp)[:8])
    if ip is not None:
        assert (blocked[0] == want[5]).all(), np.flatnonzero(blocked[0] != want[5])[:8]
    by_rounds, by_scan, evaluations = settled.tolist()
    assert by_rounds + by_scan == int(valid.sum()) and evaluations >= 0
    return bound, (by_rounds, by_scan, evaluations)


# ---- random waves: contended, with and without the gate ----------------------


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("b,k,nodes", [(256, 4, 24), (192, 9, 40)])
@pytest.mark.parametrize("gated", [False, True], ids=["skew", "skew+gate"])
def test_a_contended_wave_is_the_scans_bind_for_bind(gated, b, k, nodes, seed):
    """Tight capacity (one to three pods a node), four Deployments in a
    random order, zone and region constraints; under the gate random
    required and preferred terms, bootstraps among them."""
    rng = np.random.default_rng([seed, b, k, nodes, gated])
    raw, fields = random_wave(rng, b, k, nodes, slots=(1, 4))
    skew = skew_of(rng, rng.integers(0, 4, b), zones=6, regions=4)
    bound, (by_rounds, _by_scan, evaluations) = check(
        raw, skew, interpod_of(fields, 6) if gated else None)
    assert bound.any() and not bound.all()
    if not gated:
        assert by_rounds > 0 and evaluations > 0


# ---- the shapes the cells send ----------------------------------------------------


@pytest.mark.parametrize("seed", range(3))
def test_spreads_one_in_sixteen_interleave_settles_in_a_few_rounds_a_block(seed):
    """``spread-1m-pct5.fill``: sixteen Deployments, pod i of
    ``pattern[i % 16]``, a candidate in each of eight zones, maxSkew 1 on
    the zone: a block holds W/16 pods of each, so its rounds settle it in
    about W/16 + 1 evaluations and the step takes next to nothing."""
    rng = np.random.default_rng(seed)
    b = 8 * W
    pattern = rng.permutation(16)
    raw = per_zone(rng, b)
    skew = skew_of(rng, pattern[np.arange(b) % 16], zones=9, regions=5,
                   topos=(TOPO_ZONE,), max_skew=(1,), own=1.0, start=2)
    bound, (by_rounds, by_scan, evaluations) = check(raw, skew)
    assert bound.all()
    assert by_scan <= 2 * (b // W) and by_rounds >= b - 2 * (b // W)
    assert evaluations <= (b // W) * (W // 16 + 3)


@pytest.mark.parametrize("seed", range(2))
def test_a_wave_of_one_deployment_leaves_most_of_each_block_to_the_step(seed):
    """A chain longer than a block: one Deployment, every pod ranking the
    zones alike, so each pod's zone is the next one the counts allow and a
    round settles about one pod — the rounds stop paying after the grace
    and the scan's step settles the rest of the block."""
    rng = np.random.default_rng(seed)
    b = 4 * W
    raw = per_zone(rng, b, same_order=True)
    skew = skew_of(rng, np.zeros(b, int), zones=9, regions=5,
                   topos=(TOPO_ZONE,), max_skew=(1,), own=1.0, start=1)
    bound, (by_rounds, by_scan, evaluations) = check(raw, skew)
    assert bound.all()
    assert by_scan >= b // 2 and by_rounds > 0
    # no more rounds a block than the stop rule allows
    steps = assign._round_steps(W, raw[0].shape[1])
    assert evaluations <= (b // W) * (assign._GRACE_ROUNDS * steps // (steps - 1) + 2)


def full_mix(seed: int, batch: int = 256):
    """``full-1m-pct5``'s 64-unit mix at test_full_waves' size, encoded as
    intake encodes it: ``greedy_assign``'s inputs for the second wave
    (the first one bound, so the count tables hold pods)."""
    from k8s1m_tpu.config import PodSpec
    from k8s1m_tpu.control.objects import decode_pod, encode_pod
    from k8s1m_tpu.engine import schedule_batch
    from k8s1m_tpu.engine.cycle import (
        candidates, commit_fields_of, prologue_stats, wave_interpod, wave_skew,
    )
    from k8s1m_tpu.snapshot import NodeTableHost, PodBatchHost
    from k8s1m_tpu.snapshot.constraints import ConstraintTracker, empty_constraints
    from k8s1m_tpu.tools.make_pods import build_pod
    from test_full_waves import CHUNK, CONFIG, K, NODE, PROFILE, SPEC, pattern_of

    host = NodeTableHost(SPEC)
    host.bulk_upsert(NODE)
    tracker = ConstraintTracker(SPEC)
    enc = PodBatchHost(PodSpec(**{**CONFIG["pod_spec"], "batch": batch}),
                       SPEC, host.vocab)
    table, cons = host.to_device(), empty_constraints(SPEC)
    pattern = pattern_of(seed)
    step = dict(chunk=CHUNK, k=K, backend="xla", with_affinity=True,
                in_wave_skew=True)
    for w in range(2):
        wave = enc.encode([
            decode_pod(encode_pod(build_pod(i, **pattern[i % len(pattern)])), tracker)
            for i in range(w * batch, (w + 1) * batch)])
        key = jax.random.key(seed * 10 + w)
        if w == 0:
            table, cons, _asg = schedule_batch(
                table, wave, key, profile=PROFILE, constraints=cons, **step)
    stats = prologue_stats(table, cons)
    cand = candidates(table, wave, key, cons, PROFILE, stats=stats, **step)
    fields = commit_fields_of(wave)
    raw = (cand.idx, cand.prio, cand.cpu, cand.mem, cand.pods, fields.cpu,
           fields.mem, fields.valid, cand.zone, cand.region)
    return (jax.device_get(raw), wave_skew(wave, cons, stats),
            wave_interpod(wave, cons, stats))


@pytest.mark.parametrize("seed", range(2))
def test_fulls_sixty_four_unit_mix_under_the_gate(seed):
    """``full-1m-pct5.fill``: web and ha Deployments spread over the zones,
    ha and cache replicas one a node, eight nodes of full size so that
    replicas meet at the gate; a block holds two web pods and one ha pod
    of each Deployment."""
    raw, skew, ip = full_mix(seed)
    bound, (by_rounds, by_scan, evaluations) = check(raw, skew, ip)
    assert bound.sum() > len(bound) // 2 and by_rounds > by_scan
    assert evaluations > 0


# ---- where the blocks meet the rest of the wave ------------------------------------


@pytest.mark.parametrize("first_boot", [W + 37, 2 * W])
def test_blocks_that_straddle_the_first_pod_that_may_bootstrap(first_boot):
    """From the first pod whose required affinity term may bootstrap, the
    scan's step settles every pod: the block that holds it by rounds up to
    it, every block after by the step alone."""
    rng = np.random.default_rng(first_boot)
    b, terms = 4 * W, 6
    raw, fields = random_wave(rng, b, 4, 48, slots=(2, 5), valid=1.0)
    fields["tgt_total"][:] = 1                  # nothing bootstraps ...
    boot = terms                                # ... but a term of its own:
    fields["tgt_total"] = np.append(fields["tgt_total"], 0).astype(np.int32)
    for i in range(first_boot, b, 7):           # self-selecting, required
        fields["ipa_valid"][i, 0] = fields["ipa_required"][i, 0] = True
        fields["ipa_anti"][i, 0] = False
        fields["ipa_tid"][i, 0] = fields["inc_tid"][i, 0] = boot
        fields["ipa_topo"][i, 0] = TOPO_ZONE
        fields["inc_valid"][i, 0] = fields["ipa_self"][i, 0] = True
    skew = skew_of(rng, rng.integers(0, 4, b), zones=6, regions=4)
    last = int(np.flatnonzero((raw[1] >= 0).any(axis=1)).max()) + 1
    bound, (by_rounds, by_scan, _e) = check(raw, skew, interpod_of(fields, terms + 1))
    assert by_scan >= last - first_boot and by_rounds > 0


@pytest.mark.parametrize("seed", range(3))
def test_a_wave_shorter_than_a_block_is_one_block(seed):
    rng = np.random.default_rng(seed)
    b = W // 2
    assert assign._block_pods(b) == b
    raw, fields = random_wave(rng, b, 4, 8, slots=(1, 3))
    skew = skew_of(rng, rng.integers(0, 3, b), zones=6, regions=4)
    check(raw, skew)
    check(raw, skew, interpod_of(fields, 6))


def test_behind_its_last_pod_with_a_candidate_a_wave_has_no_block():
    """A wave of retried pods and their padding: the blocks end at the
    last pod that has a candidate; the padding is nobody's."""
    rng = np.random.default_rng(5)
    b, front = 4 * W, W // 2 + 3
    raw = list(per_zone(rng, b, valid=1.0))
    raw[1][front:] = -1
    raw[7] = np.arange(b) < front
    skew = skew_of(rng, rng.integers(0, 4, b), zones=9, regions=5,
                   topos=(TOPO_ZONE,))
    bound, (by_rounds, by_scan, evaluations) = check(tuple(raw), skew)
    assert bound.sum() == front and by_rounds + by_scan == front
    assert evaluations <= W // 4
    # nothing any pod can take: no block at all
    raw[1][:] = -1
    assert check(tuple(raw), skew)[1] == (front, 0, 0)


def test_the_chip_smokes_skew_phase_rehearses_on_the_cpu():
    """chip_smoke.py phase B (vi) at a sixteenth of its wave: both of its
    waves equal the scan, and each is settled by rounds."""
    from chip_smoke import Timer, _skew_variant

    seen = _skew_variant(Timer(), batch=4 * W)
    for kind in ("spread", "full"):
        by_rounds, by_scan, evaluations = seen[kind]
        assert by_rounds > by_scan and evaluations > 0, kind


def test_the_block_is_a_power_of_two_that_divides_the_wave():
    assert W == assign._BLOCK_PODS and 4096 % W == 0
    assert assign._block_pods(48) == 16 and assign._block_pods(100) == 4
    assert assign._block_pods(7) == 1 and assign._block_pods(W * 3) == W
