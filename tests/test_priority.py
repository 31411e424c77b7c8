"""ops/priority.pod_priority_of edge cases + adaptive-floor interaction.

Until now the pod-priority parse was covered only indirectly through
the loadshed drills; these are the direct unit gates: garbage never
raises (a malformed PriorityClass must not take down admission), the
numeric conventions match Kubernetes (ints, stringly ints, floats
truncate), and the HealthController's adaptive floor behaves with the
values the parser can actually emit (negative, huge, skipped levels) —
plus the ``floor=False`` bypass the tenancy layer rides.
"""

import pytest

from k8s1m_tpu.loadshed import (
    HEALTHY,
    SHEDDING,
    HealthController,
    LoadshedConfig,
    Signals,
)
from k8s1m_tpu.ops.priority import pod_priority_of


def _pod(priority):
    return {"spec": {"priority": priority}}


class TestPodPriorityOf:
    def test_missing_everything(self):
        assert pod_priority_of({}) == 0
        assert pod_priority_of({"spec": {}}) == 0
        assert pod_priority_of({"spec": None}) == 0

    def test_plain_and_negative_and_huge(self):
        assert pod_priority_of(_pod(7)) == 7
        # Negative priorities are legal in Kubernetes (system classes
        # reserve the top; users may go below zero).
        assert pod_priority_of(_pod(-5)) == -5
        # int64-scale values must survive untruncated: the floor
        # comparison is plain int math, not a packed field.
        assert pod_priority_of(_pod(2_000_000_000)) == 2_000_000_000
        assert pod_priority_of(_pod(1 << 40)) == 1 << 40

    def test_non_int_forms(self):
        assert pod_priority_of(_pod("12")) == 12      # stringly int
        assert pod_priority_of(_pod(3.9)) == 3        # floats truncate
        assert pod_priority_of(_pod("high")) == 0     # garbage -> 0
        assert pod_priority_of(_pod(None)) == 0
        assert pod_priority_of(_pod([5])) == 0
        assert pod_priority_of(_pod({"v": 5})) == 0

    def test_not_a_dict_spec_values(self):
        # obj.get("spec") returning a non-dict must not raise.
        assert pod_priority_of({"spec": "Pending"}) == 0
        assert pod_priority_of({"spec": 3}) == 0

    def test_falsy_zero_vs_unset(self):
        assert pod_priority_of(_pod(0)) == 0
        # "or 0" coalescing: explicit False/""/0.0 all read as 0.
        assert pod_priority_of(_pod(False)) == 0
        assert pod_priority_of(_pod("")) == 0


CFG = LoadshedConfig(
    queue_degraded=10, queue_shed=20, queue_cap=1000, queue_recover=4,
    recover_cycles=2,
)


def _shedding(name: str) -> HealthController:
    ctrl = HealthController(CFG, name=name)
    ctrl.tick(Signals(queue_depth=25))   # >= queue_shed -> SHEDDING
    assert ctrl.current_state() == SHEDDING
    return ctrl


class TestAdaptiveFloorEdges:
    def test_floor_climbs_through_negative_priorities(self):
        ctrl = _shedding("prio-neg")
        # Offer only negative priorities; the floor tracks the offered
        # band, so it must climb high enough to bite within it.
        for _ in range(6):
            for p in (-3, -2, -1):
                ctrl.try_admit(p)
            ctrl.tick(Signals(queue_depth=25))
        assert not ctrl.admit(-3)
        assert ctrl.admit(-1)

    def test_floor_never_exceeds_offered_max(self):
        ctrl = _shedding("prio-cap")
        for _ in range(50):
            ctrl.try_admit(2)
            ctrl.tick(Signals(queue_depth=25))
        # 50 overloaded ticks, but the floor stops at the highest
        # priority anyone actually offered: 2 stays admitted.
        assert ctrl.admit(2)

    def test_huge_priority_always_admitted_under_floor(self):
        ctrl = _shedding("prio-huge")
        for _ in range(4):
            ctrl.try_admit(0)
            ctrl.try_admit(1 << 40)
            ctrl.tick(Signals(queue_depth=25))
        assert ctrl.admit(1 << 40)
        assert not ctrl.admit(0)

    def test_floor_resets_on_recovery(self):
        ctrl = _shedding("prio-reset")
        for _ in range(4):
            ctrl.try_admit(0)
            ctrl.try_admit(3)
            ctrl.tick(Signals(queue_depth=25))
        assert not ctrl.admit(0)
        # Calm ticks walk the state down; leaving SHEDDING must re-admit
        # every priority (the floor falls back to the observed minimum).
        for _ in range(20):
            ctrl.tick(Signals(queue_depth=0))
            if ctrl.current_state() == HEALTHY:
                break
        assert ctrl.current_state() == HEALTHY
        assert ctrl.admit(0)

    def test_floor_false_bypasses_priority_but_not_cap(self):
        ctrl = _shedding("prio-bypass")
        for _ in range(4):
            ctrl.try_admit(0)
            ctrl.try_admit(3)
            ctrl.tick(Signals(queue_depth=25))
        # The tenancy layer's form: the global floor must not run...
        assert ctrl.try_admit(0, floor=False) is None
        assert ctrl.try_admit(0) == "priority"
        # ...but the hard cap still binds regardless of the flag.
        small = HealthController(
            LoadshedConfig(
                queue_degraded=2, queue_shed=3, queue_cap=4,
                queue_recover=1,
            ),
            name="prio-bypass-cap",
        )
        small.tick(Signals(queue_depth=4))
        assert small.try_admit(99, floor=False) == "cap"


def test_decode_paths_parse_priority():
    """spec.priority round-trips through the JSON codec, and the
    canonical fast parser stays priority-less by design."""
    from k8s1m_tpu.control.objects import decode_pod, decode_pod_fast, encode_pod
    from k8s1m_tpu.snapshot.pod_encoding import PodInfo

    enc = encode_pod(PodInfo("p", priority=9))
    assert decode_pod_fast(enc) is None      # non-canonical on purpose
    assert decode_pod(enc, None).priority == 9
    plain = encode_pod(PodInfo("q"))
    fast = decode_pod_fast(plain)
    assert fast is not None and fast.priority == 0


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
