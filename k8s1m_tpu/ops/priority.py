"""Priority packing: integer score + random tie-break bits in one int32.

Upstream picks uniformly at random among max-score nodes (reference
dist-scheduler/pkg/scoreevaluator/scoreevaluator.go:99-120 mirrors upstream
selectHost).  On TPU, argmax over ``score * 2^JITTER_BITS + uniform jitter``
is exactly that: ties in the integer score are broken by independent
uniform bits, and any real score difference dominates the jitter.  Scores
are integers for the same reason upstream's are (framework scores are
int64).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# 20 jitter bits: with ~500k equal-score nodes (cold uniform cluster) the
# expected number of nodes colliding at the max jitter draw stays well
# under 1, so top_k's prefer-earlier-index tie rule contributes no
# measurable chunk-order bias.  11 score bits bound the weighted plugin
# sum (default profile max is 1100).
JITTER_BITS = 20
MAX_SCORE = (1 << 11) - 1  # 2047; 2047 * 2^20 + (2^20 - 1) == int32 max
INFEASIBLE = -1


def pack(score_int: jax.Array, key: jax.Array, mask: jax.Array) -> jax.Array:
    """score_int i32[...], mask bool[...] -> priority i32[...] (-1 infeasible).

    Threefry-jittered variant — kept for callers without stable element
    coordinates.  The scheduling hot path uses ``pack_hashed`` (the
    counter-mode PRNG costs ~1.8s per [4096,16384] wave on XLA CPU where
    the separable hash costs ~0.1s, and the hash is what makes the two
    backends bit-identical)."""
    s = jnp.clip(score_int, 0, MAX_SCORE)
    jitter = jax.random.randint(
        key, score_int.shape, 0, 1 << JITTER_BITS, dtype=jnp.int32
    )
    prio = (s << JITTER_BITS) | jitter
    return jnp.where(mask, prio, INFEASIBLE)


def mix32(h):
    """murmur3 finalizer in uint32 (wraps identically everywhere)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return h


def hash_jitter(seed, row_ids, col_ids):
    """Stateless uniform bits in [0, 2^JITTER_BITS) per (pod, node).

    Separable construction shared by BOTH backends (the fused pallas
    kernel and the XLA scan path) and the numpy oracle: each axis is
    murmur3-finalized on its own narrow shape ([B, 1] rows, [1, C]
    cols) and the full-width work is ONE xor + one mask.  Integer ops
    reproduce bit-for-bit everywhere, which is what the cross-backend
    tie-break parity rests on.  See ops/pallas_topk.py for the
    correlated-tie trade-off note."""
    rh = mix32(
        seed.astype(jnp.uint32)
        ^ (row_ids.astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
    )
    ch = mix32(
        seed.astype(jnp.uint32)
        ^ (col_ids.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
    )
    return ((rh ^ ch) & jnp.uint32((1 << JITTER_BITS) - 1)).astype(jnp.int32)


def seed_of(key: jax.Array) -> jax.Array:
    """Derive an i32 hash seed from a jax PRNG key (ONE scalar threefry
    draw per wave; the per-element stream comes from hash_jitter)."""
    return jax.random.randint(key, (), -(1 << 31), (1 << 31) - 1, jnp.int32)


def pack_hashed(
    score_int: jax.Array, seed: jax.Array, mask: jax.Array,
    row_ids: jax.Array, col_ids: jax.Array,
) -> jax.Array:
    """``pack`` with the separable hash jitter: priorities are a pure
    function of (seed, pod row, node column), so the XLA scan path and
    the pallas kernel produce IDENTICAL tie-breaks for the same wave."""
    s = jnp.clip(score_int, 0, MAX_SCORE)
    prio = (s << JITTER_BITS) | hash_jitter(seed, row_ids, col_ids)
    return jnp.where(mask, prio, INFEASIBLE)


def unpack_score(prio: jax.Array) -> jax.Array:
    return jnp.where(prio >= 0, prio >> JITTER_BITS, -1)


def pod_priority_of(obj: dict) -> int:
    """``spec.priority`` of a pod API object dict (0 when unset/garbage).

    The *pod* priority (PriorityClass semantics, not the packed node
    priority above): the admission-shedding key — under overload the
    loadshed controller rejects lowest-priority pods first, the same
    ordering kube-apiserver priority-and-fairness applies to request
    flows.  Priority never reaches the device; it is consumed entirely
    at the admission points (control/webhook.py,
    Coordinator.submit_external)."""
    try:
        return int((obj.get("spec") or {}).get("priority") or 0)
    except (TypeError, ValueError, AttributeError):
        return 0
