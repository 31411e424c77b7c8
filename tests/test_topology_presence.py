"""Which zones and regions hold a node: the prologue's compare-and-any
against the scatter-max it replaced, bit for bit.

``topology._domain_presence`` reads ``present[d] = any(valid & (ids == d))``
over the whole column, ``present[0]`` forced to 0 (id 0 is "label
missing"), and under ``shard_map`` a ``pmax`` over the node-shard axis.
The reference is the scatter form as it stood (``scatter_presence``, a
copy kept here): a 0/1 ``int32[size]``, the same flags on every table.
Every statistic of ``TopoStats`` that reads the flags (``spread_min``,
``spread_max``, ``tgt_max``) and the one that does not (``tgt_total``)
has to come out equal too, on the plain and the packed layout.
"""

from __future__ import annotations

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from k8s1m_tpu.config import TableSpec
from k8s1m_tpu.engine.cycle import prologue_stats
from k8s1m_tpu.parallel import make_mesh
from k8s1m_tpu.parallel.mesh import constraint_specs, table_specs
from k8s1m_tpu.plugins import topology
from k8s1m_tpu.snapshot import NodeTableHost
from k8s1m_tpu.snapshot.constraints import ConstraintState
from k8s1m_tpu.snapshot.packing import build_packing_spec, pack_table_host

ROWS = 256
SIZES = {"cells": (9, 5), "defaults": (512, 64)}   # (max_zones, max_regions)


def scatter_presence(table, size, ids, axis_name=None):
    """The prologue's presence flags as a scatter-max of ``valid`` into the
    domain ids (the form the compare replaced)."""
    present = jnp.zeros((size,), jnp.int32).at[ids].max(table.valid.astype(jnp.int32))
    if axis_name is not None:
        present = lax.pmax(present, axis_name)
    return present.at[0].set(0)


def _mixed(rng, n, z, r):
    valid = rng.random(n) < 0.9
    return valid, rng.integers(0, z, n), rng.integers(0, r, n)


def _tombstoned_only_node(rng, n, z, r):
    """Zone 3 and region 2 are held by one tombstoned row alone; the top
    ids by one live row."""
    valid, zone, region = _mixed(rng, n, z, r)
    zone[zone == 3] = 4
    region[region == 2] = 1
    zone[7], region[7], valid[7] = 3, 2, False
    zone[11], region[11], valid[11] = z - 1, r - 1, True
    return valid, zone, region


def _id0_present(rng, n, z, r):
    valid, zone, region = _mixed(rng, n, z, r)
    zone[:n // 4], region[:n // 4], valid[:n // 4] = 0, 0, True
    return valid, zone, region


def _id0_absent(rng, n, z, r):
    valid, _, _ = _mixed(rng, n, z, r)
    return valid, rng.integers(1, z, n), rng.integers(1, r, n)


def _last_id(rng, n, z, r):
    valid = rng.random(n) < 0.5
    return valid, np.full(n, z - 1), np.full(n, r - 1)


def _all_invalid(rng, n, z, r):
    _, zone, region = _mixed(rng, n, z, r)
    return np.zeros(n, bool), zone, region


TABLES = {f.__name__.lstrip("_"): f for f in (
    _mixed, _tombstoned_only_node, _id0_present, _id0_absent, _last_id,
    _all_invalid,
)}


def _spec(n, z, r):
    return TableSpec(max_nodes=n, max_zones=z, max_regions=r,
                     spread_slots=4, affinity_slots=3)


def _table(spec, valid, zone, region, layout):
    host = NodeTableHost(spec)
    host.valid[:] = valid
    host.zone[:] = zone
    host.region[:] = region
    if layout == "packed":
        return pack_table_host(host, build_packing_spec(spec))
    return host.to_device()


def _constraints(rng, spec):
    """Count tables of every shape, uneven, so that a wrong flag moves a
    minimum or a maximum."""
    c, a = spec.spread_slots, spec.affinity_slots
    n, z, r = spec.max_nodes, spec.max_zones, spec.max_regions
    counts = lambda *shape: jnp.asarray(rng.integers(0, 1000, shape), jnp.int32)
    return ConstraintState(
        spread_node=counts(c, n), spread_zone=counts(c, z),
        spread_region=counts(c, r), tgt_node=counts(a, n),
        tgt_zone=counts(a, z), tgt_region=counts(a, r), own_node=counts(a, n),
        own_zone=counts(a, z), own_region=counts(a, r),
    )


def _expected_flags(valid, ids, size):
    flags = np.zeros(size, np.int32)
    flags[np.unique(ids[valid])] = 1
    flags[0] = 0
    return flags


def _whole(table, cons):
    """The one-device prologue, traced afresh (a patched presence is seen)."""
    return jax.jit(lambda t, c: prologue_stats(t, c))(table, cons)


def _assert_stats_equal(got, want):
    for field in ("zone_present", "region_present", "spread_min",
                  "spread_max", "tgt_max", "tgt_total"):
        a, b = np.asarray(getattr(got, field)), np.asarray(getattr(want, field))
        assert a.dtype == b.dtype and a.shape == b.shape, field
        np.testing.assert_array_equal(a, b, err_msg=field)


@pytest.mark.parametrize("layout", ["plain", "packed"])
@pytest.mark.parametrize("sizes", list(SIZES), ids=list(SIZES))
@pytest.mark.parametrize("kind", list(TABLES))
def test_presence_is_the_scatters_bit_for_bit(kind, sizes, layout):
    z, r = SIZES[sizes]
    rng = np.random.default_rng(sorted(TABLES).index(kind) * 7 + z)
    valid, zone, region = TABLES[kind](rng, ROWS, z, r)
    spec = _spec(ROWS, z, r)
    table = _table(spec, valid, zone, region, layout)
    cons = _constraints(rng, spec)

    got = _whole(table, cons)
    with mock.patch.object(topology, "_domain_presence", scatter_presence):
        want = _whole(table, cons)

    _assert_stats_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(got.zone_present), _expected_flags(valid, zone, z))
    np.testing.assert_array_equal(
        np.asarray(got.region_present), _expected_flags(valid, region, r))
    if kind == "tombstoned_only_node":
        assert int(got.zone_present[3]) == 0 and int(got.region_present[2]) == 0
        assert int(got.zone_present[z - 1]) == 1
        assert int(got.region_present[r - 1]) == 1


@pytest.mark.parametrize("layout", ["plain", "packed"])
@pytest.mark.parametrize("sizes", list(SIZES), ids=list(SIZES))
def test_a_domain_on_one_shard_reads_present_on_every_shard(sizes, layout):
    """The sharded prologue (``axis_name="sp"``, each shard its own rows):
    a zone and a region held by one live row of the last shard, and one
    held by a tombstoned row of the first, read as the whole table's
    scatter reads them."""
    z, r = SIZES[sizes]
    sp = 4
    rng = np.random.default_rng(z)
    valid, zone, region = _mixed(rng, ROWS, z, r)
    zone[zone >= z - 2] = 1
    region[region >= r - 2] = 1
    last = ROWS - 3                                  # inside the last shard
    zone[last], region[last], valid[last] = z - 1, r - 1, True
    zone[2], region[2], valid[2] = z - 2, r - 2, False
    spec = _spec(ROWS, z, r)
    table = _table(spec, valid, zone, region, layout)
    cons = _constraints(rng, spec)

    mesh = make_mesh(dp=1, sp=sp)

    def sharded():
        return jax.jit(jax.shard_map(
            lambda t, c: prologue_stats(t, c, axis_name="sp"), mesh=mesh,
            in_specs=(table_specs(table), constraint_specs(cons)),
            out_specs=P(), check_vma=False,
        ))(table, cons)

    got = sharded()
    with mock.patch.object(topology, "_domain_presence", scatter_presence):
        want_sharded = sharded()
        want_whole = _whole(table, cons)

    _assert_stats_equal(got, want_sharded)
    _assert_stats_equal(got, want_whole)
    flags = np.asarray(got.zone_present), np.asarray(got.region_present)
    assert flags[0][z - 1] == 1 and flags[1][r - 1] == 1
    assert flags[0][z - 2] == 0 and flags[1][r - 2] == 0
