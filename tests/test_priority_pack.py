"""The packed priority: ``score << JITTER_BITS | jitter``, the key every
candidates kernel ranks rows by (ops/priority.py).

- a higher integer score wins over any jitter, and the jitter stays
  under ``2**JITTER_BITS``, so ``prio >> JITTER_BITS`` is the score;
- the jitter is a pure function of (seed, pod row, node column): the
  same triple draws the same bits wherever it sits in a block;
- a block hashed at a pod and a row offset draws what the whole grid
  draws at those global coordinates — the contract a mesh shard's
  candidates rest on (engine.cycle.filter_score_topk ``row_offset`` /
  ``pod_offset``).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from k8s1m_tpu.ops.priority import (
    INFEASIBLE,
    JITTER_BITS,
    MAX_SCORE,
    hash_jitter,
    pack_hashed,
    unpack_score,
)

SEEDS = [0, -7, (1 << 31) - 1]
B, N = 16, 96


def grid(seed, rows=B, cols=N, pod_offset=0, row_offset=0):
    r = jnp.arange(rows, dtype=jnp.int32)[:, None] + pod_offset
    c = jnp.arange(cols, dtype=jnp.int32)[None, :] + row_offset
    return np.asarray(hash_jitter(jnp.int32(seed), r, c))


@pytest.mark.parametrize("seed", SEEDS)
def test_the_score_outranks_any_jitter_and_unpacks_exactly(seed):
    rng = np.random.default_rng(seed & 0xFFFF)
    score = rng.integers(-5, MAX_SCORE + 50, (B, N)).astype(np.int32)
    mask = rng.random((B, N)) < 0.8
    r = jnp.arange(B, dtype=jnp.int32)[:, None]
    c = jnp.arange(N, dtype=jnp.int32)[None, :]
    prio = np.asarray(pack_hashed(
        jnp.asarray(score), jnp.int32(seed), jnp.asarray(mask), r, c
    ))
    jitter = grid(seed)
    assert jitter.min() >= 0 and jitter.max() < (1 << JITTER_BITS)
    clipped = np.clip(score, 0, MAX_SCORE)
    np.testing.assert_array_equal(
        prio, np.where(mask, (clipped << JITTER_BITS) | jitter, INFEASIBLE)
    )
    np.testing.assert_array_equal(
        np.asarray(unpack_score(jnp.asarray(prio))),
        np.where(mask, clipped, -1),
    )
    # Across any two feasible pairs of one pod, a higher score ranks
    # higher whatever either drew.
    for b in range(B):
        ok = mask[b]
        s, p = clipped[b][ok], prio[b][ok]
        hi = s[:, None] > s[None, :]
        assert (p[:, None] > p[None, :])[hi].all()


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_the_draw_is_a_pure_function_of_seed_row_and_column(seed):
    full = grid(seed)
    # The same triples in another order and shape draw the same bits.
    rows = jnp.asarray([5, 0, 15, 5], jnp.int32)[:, None]
    cols = jnp.asarray([95, 3, 3, 40, 0], jnp.int32)[None, :]
    picked = np.asarray(hash_jitter(jnp.int32(seed), rows, cols))
    np.testing.assert_array_equal(
        picked, full[np.ix_([5, 0, 15, 5], [95, 3, 3, 40, 0])]
    )
    np.testing.assert_array_equal(grid(seed), full)
    # Another seed draws other bits.
    assert (grid(seed + 1) != full).mean() > 0.9


@pytest.mark.parametrize("pod_offset,row_offset", [
    (0, 32), (8, 0), (8, 64),
])
def test_a_block_at_its_offsets_draws_the_global_grid(pod_offset, row_offset):
    """What one of a 2 x 3 mesh's shards hashes with its global bases is
    the single device's draw for the same (pod, node) pairs."""
    full = grid(1234)
    block = grid(1234, rows=B // 2, cols=N // 3,
                 pod_offset=pod_offset, row_offset=row_offset)
    np.testing.assert_array_equal(
        block,
        full[pod_offset:pod_offset + B // 2, row_offset:row_offset + N // 3],
    )
