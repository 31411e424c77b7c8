"""A wave whose field groups no earlier wave had runs the step of a wider
set that one did (``Coordinator._built_step_for``): the groups it lacks go
in as the zeros an absent group unpacks to (``hotfeed.widen_packed``), so
only which executable runs changes, and the binds do not.

The wave this is for is full-1m-pct5's leftover: a few pods the gate or the
skew sent back, whose shapes are a part of the mix.  Under its own group
set its step would build the combined kernel again (minutes on the chip);
under the window's it runs the step the window ran.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

import test_full_waves as F
from k8s1m_tpu.control.coordinator import Coordinator
from k8s1m_tpu.control.objects import decode_pod, encode_node, encode_pod, node_key, pod_key
from k8s1m_tpu.engine import cycle
from k8s1m_tpu.engine.cycle import schedule_batch_packed
from k8s1m_tpu.faultline import RetryPolicy
from k8s1m_tpu.snapshot import NodeTableHost, PodBatchHost
from k8s1m_tpu.snapshot.constraints import ConstraintTracker, empty_constraints
from k8s1m_tpu.snapshot.hotfeed import widen_packed
from k8s1m_tpu.snapshot.pod_encoding import unpack_pod_batch
from k8s1m_tpu.store.native import MemStore, prefix_end
from k8s1m_tpu.tools.make_pods import build_pod

HA = next(s for s in F.SHAPES if s.get("app") == "ha-0")
NO_DELAY = RetryPolicy(base_delay_s=0.0, jitter=0.0)


def _mix(w: int):
    pattern = F.pattern_of(46)
    return [pattern[i % len(pattern)] for i in range(w * F.WAVE, (w + 1) * F.WAVE)]


def _waves():
    """A wave of the mix, then a leftover of two ``ha-0`` pods, encoded
    against one vocabulary; and the table the first leaves behind."""
    host = NodeTableHost(F.SPEC)
    host.bulk_upsert(F.NODE)
    tracker = ConstraintTracker(F.SPEC)
    enc = PodBatchHost(F.POD_SPEC, F.SPEC, host.vocab)
    pods = lambda shapes, at: [
        decode_pod(encode_pod(build_pod(at + j, **s)), tracker)
        for j, s in enumerate(shapes)]
    full = enc.encode_packed(pods(_mix(0), 0))
    leftover = enc.encode_packed(pods([HA, HA], F.WAVE))
    return host, full, leftover


def test_the_leftover_wave_packs_under_the_windows_groups_to_the_same_values():
    _host, full, leftover = _waves()
    assert leftover.groups < full.groups
    wide = widen_packed(leftover, full.groups)
    assert wide.groups == full.groups and wide.ints.size > leftover.ints.size
    own = unpack_pod_batch(leftover.ints, leftover.bools, leftover.spec,
                           leftover.table_spec, leftover.groups)
    under = unpack_pod_batch(wide.ints, wide.bools, wide.spec, wide.table_spec,
                             wide.groups)
    for name in vars(own):
        np.testing.assert_array_equal(
            np.asarray(getattr(own, name)), np.asarray(getattr(under, name)), name)
    for name, value in leftover.fields.items():
        np.testing.assert_array_equal(value, wide.fields[name], name)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_two_ha_pods_bind_the_same_rows_under_their_own_groups_and_the_windows(
        backend):
    """After a wave of the mix, whose counts and gate the two replicas
    meet: the same rows, the same reasons, the same count tables."""
    host, full, leftover = _waves()
    step = dict(profile=F.PROFILE, chunk=F.CHUNK, k=F.K, backend=backend,
                in_wave_skew=True)
    table, cons, _asg, _rows = schedule_batch_packed(
        host.to_device(), full, jax.random.key(7),
        constraints=empty_constraints(F.SPEC), **step)
    out = []
    for batch in (leftover, widen_packed(leftover, full.groups)):
        _t, c, asg, rows = schedule_batch_packed(
            table, batch, jax.random.key(8), constraints=cons, **step)
        out.append((np.asarray(rows), np.asarray(asg.unbound),
                    jax.tree.map(np.asarray, c)))
    (rows1, unbound1, cons1), (rows2, unbound2, cons2) = out
    assert (rows1[:2] >= 0).all()
    np.testing.assert_array_equal(rows1, rows2)
    np.testing.assert_array_equal(unbound1, unbound2)
    for name in vars(cons1):
        np.testing.assert_array_equal(getattr(cons1, name), getattr(cons2, name))


def _drive(widen: bool):
    """A coordinator on the mix's nodes: a wave of the mix, what it sends
    back, then two ``ha-0`` pods.  Returns where every pod went, the group
    sets of the steps the waves ran after the first, and the one-device
    steps built meanwhile."""
    with MemStore() as store:
        for n in F.NODE:
            store.put(node_key(n.name), encode_node(n))
        c = Coordinator(
            store, F.SPEC, F.POD_SPEC, F.PROFILE, chunk=F.CHUNK, k=F.K,
            with_constraints=True, in_wave_skew=True, seed=5, max_attempts=8,
            retry_policy=NO_DELAY, packing="packed",
        )
        ran = []
        built_for = c._built_step_for

        def spy(batch, profile, sample_rows):
            out = built_for(batch, profile, sample_rows) if widen else batch
            ran.append(out.groups)
            return out

        c._built_step_for = spy
        c.bootstrap()
        put = lambda shapes, at: store.put_batch([
            (pod_key("default", p.name), encode_pod(p))
            for p in (build_pod(at + j, **s) for j, s in enumerate(shapes))])
        put(_mix(0), 0)
        c.step()
        misses = cycle._jitted_schedule_packed.cache_info().misses
        c.run_until_idle()
        put([HA, HA], F.WAVE)
        c.run_until_idle()
        built = cycle._jitted_schedule_packed.cache_info().misses - misses
        res = store.range(b"/registry/pods/", prefix_end(b"/registry/pods/"))
        placed = {bytes(kv.key): bytes(kv.value) for kv in res.kvs}
        c.close()
    return placed, ran, built


def test_a_coordinator_runs_the_leftovers_on_the_step_it_built():
    """Widened, every wave after the first runs the first's group set and
    no step is built after it; the pods land where they land when every
    wave runs a step of its own groups."""
    placed, ran, built = _drive(widen=True)
    assert len(ran) >= 2 and set(ran[1:]) == {ran[0]} and built == 0
    own_placed, own_ran, _built = _drive(widen=False)
    assert any(g < own_ran[0] for g in own_ran[1:])   # leftovers of fewer groups
    assert placed == own_placed
