"""Shared batched-commit primitives: priority ranking and the sort-free
segment prefix gate used by every sequential-equivalent commit kernel
(node capacity, quota levels, reservations).

Split out of core.py so plugin kernels (reservation pre-pass, device
allocator) can reuse them without a circular import.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from koordinator_tpu.snapshot.schema import PodBatch

EPS = 0.5  # comparison tolerance in canonical units (millicores / MiB)
MAX_NODE_SCORE = 100.0  # framework.MaxNodeScore — single source of truth;
                        # the reservation-slot preference (3*MAX_NODE_SCORE+1
                        # in core.py) relies on every plugin score topping
                        # out at this value and at most THREE plugin scores
                        # (loadaware + numa + device) summing per node —
                        # raise the slot multiplier when adding a fourth


def stable_rank(key: jnp.ndarray) -> jnp.ndarray:
    """i32[P]: each element's position in the stable ascending sort of
    `key` (ties keep index order). One sort + one scatter; shared by the
    priority ranking and the straggler-tail compaction (whose budgeted
    selection admits the first K candidates of a ranking without
    materializing the sorted array)."""
    p = key.shape[0]
    order = jnp.argsort(key, stable=True)
    return jnp.zeros((p,), jnp.int32).at[order].set(
        jnp.arange(p, dtype=jnp.int32))


def rank_by_priority(pods: PodBatch) -> jnp.ndarray:
    """i32[P]: position in scheduling order — priority desc, index asc.

    The batched analogue of the scheduler queue order (Coscheduling Less +
    default PrioritySort); gang-group batching is handled by the caller.
    """
    return stable_rank(-pods.priority)


def segment_prefix_ok(seg: jnp.ndarray, earlier: jnp.ndarray,
                      req: jnp.ndarray, base_used: jnp.ndarray,
                      limit: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    """Does each pod fit its segment's limit when charged after all
    earlier-ranked pods of the same segment?

    bool[P]: base_used[seg] + Σ req of same-segment earlier pods + own req
    <= limit[seg]. Computed sort-free as a masked [P,P] x [P,R] matmul —
    TPU sorts cost ~1.5ms for even tiny arrays while the MXU does this
    contraction in microseconds. `earlier[p, p'] = rank[p'] < rank[p]` is
    shared across all segment levels of a commit step. Out-of-range
    segments (>= num_segments, the "no candidate" encoding) are vacuously
    OK; their req rows are zeroed by the caller.
    """
    same = seg[:, None] == seg[None, :]                         # [P, P]
    mask = (same & earlier).astype(req.dtype)
    # HIGHEST: at default precision the MXU rounds the f32 requests
    # (milli-CPU, MiB) to bf16, and the commit then over-admits
    cum_excl = jnp.matmul(mask, req,
                          precision=jax.lax.Precision.HIGHEST)  # [P, R]
    seg_c = jnp.clip(seg, 0, num_segments - 1)
    ok = jnp.all(base_used[seg_c] + cum_excl + req <= limit[seg_c] + EPS,
                 axis=-1)
    return ok | (seg >= num_segments)
