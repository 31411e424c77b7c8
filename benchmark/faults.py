"""Ways to break the timed path underneath a run, from the benchmark's
side (nothing in the program is edited; a fault that patches a module
returns the call that undoes it).  Each is planted before the
coordinator's bootstrap.  ``--fault <name>`` is one of ``FAULTS`` below
or, failing that, ``benchmark/controls/<name>.py`` (``load``): a
configuration's own guarantee needs a control of its own, and that one
arrives as a file.

Controls of "How correct is decided", each breaking one guarantee the
configuration states: ``lazy_bind`` (an acknowledged bind is read back
from the store and seen on the watch — the later, rarer flush that would
tempt a host optimisation); ``filter_off`` and ``capacity_off`` (a pod is
bound only to a node that passes every filter, and no node is
overcommitted — the device decides on a table in which no node is
cordoned, or every node holds twice its pods, as a kernel with that
check left out would).  The others are the faults a cell can have;
tests/benchmark_cells drives a run with each and sees ``correct`` come
out false.
"""

from __future__ import annotations

import importlib.util
import os

CONTROLS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "controls")


def _wrap_bind_batch(store, fn):
    real = store.bind_batch

    def bind_batch(entries, *rest):
        return fn(real, list(entries), rest)

    store.bind_batch = bind_batch


def _rewrite_nodes(coord, old: bytes, new: bytes) -> None:
    """The program lists the deployment's nodes with ``old`` read as
    ``new``; the store, and so the reference, keep the truth."""
    real = coord._relist_nodes

    def relist():
        values, rev = real()
        return [v.replace(old, new) for v in values], rev

    coord._relist_nodes = relist


def lazy_bind(store, coord, every: int = 64) -> None:
    """One bind in ``every`` is acknowledged to the coordinator but never
    written to the store."""

    def fn(real, entries, rest):
        kept = [e for i, e in enumerate(entries) if i % every]
        revs = iter(real(kept, *rest))
        return [next(revs) if i % every else 1 for i in range(len(entries))]

    _wrap_bind_batch(store, fn)


def filter_off(store, coord) -> None:
    """The device's filter never sees a cordon: a cordoned node is as
    good as any other."""
    _rewrite_nodes(coord, b'"spec":{"unschedulable":true}', b'"spec":{}')


def capacity_off(store, coord) -> None:
    """The device's capacity check passes twice the pods a node holds."""
    import re

    sample = coord._relist_nodes()[0][0]
    pods = re.search(rb'"pods":"(\d+)"', sample).group(1)
    _rewrite_nodes(coord, b'"pods":"%s"' % pods, b'"pods":"%d"' % (2 * int(pods)))


def half_batch(store, coord) -> None:
    """The second half of every wave's binds is left out."""
    lazy_bind(store, coord, every=2)


def answer_altered(store, coord) -> None:
    """The first pod of each wave is bound to the node chosen for the
    second, not to its own."""

    def fn(real, entries, rest):
        if len(entries) > 1 and entries[0][2] != entries[1][2]:
            key, mod, _node = entries[0]
            entries[0] = (key, mod, entries[1][2])
        return real(entries, *rest)

    _wrap_bind_batch(store, fn)


def state_unchanged(store, coord) -> None:
    """The engine step returns the table it was given: binds are decided
    and written, the device never learns of them."""
    import k8s1m_tpu.control.coordinator as mod

    real = mod.schedule_batch_packed

    def step(table, *args, **kw):
        kw["donate"] = False
        _new, *rest = real(table, *args, **kw)
        return (table, *rest)

    mod.schedule_batch_packed = step

    def undo():
        mod.schedule_batch_packed = real

    return undo


FAULTS = {f.__name__: f for f in (
    lazy_bind, filter_off, capacity_off, half_batch, answer_altered,
    state_unchanged,
)}


def names() -> list[str]:
    """What ``--fault`` takes: the built-in ones and every control file."""
    files = os.listdir(CONTROLS_DIR) if os.path.isdir(CONTROLS_DIR) else []
    return sorted(set(FAULTS) | {f[:-3] for f in files if f.endswith(".py")})


def load(name: str):
    """``FAULTS[name]`` or, failing that, the ``plant`` function of
    ``benchmark/controls/<name>.py``, loaded by path as a reference is:
    ``plant(store, coord)`` is called where the built-in ones are and
    returns the call that undoes it, or None."""
    if name in FAULTS:
        return FAULTS[name]
    path = os.path.join(CONTROLS_DIR, f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"--fault {name!r}: not one of {sorted(FAULTS)} and no {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark_control_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.plant
