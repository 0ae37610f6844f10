"""Topology-manager hint merge: the four NUMA policies as batched mask
reductions.

Behavior parity with pkg/scheduler/frameworkext/topologymanager/ (SURVEY.md
2.1): per-node policy (none / best-effort / restricted / single-numa-node,
apis/extension/numa_aware.go:138-145), per-plugin hint providers (CPU+memory
from NodeNUMAResource, instance zones from DeviceShare), hints merged into
one NUMA affinity per pod, admission per policy (policy_best_effort.go,
policy_restricted.go, policy_single_numa_node.go, policy_none.go).

TPU design — no recursion, no bitmask objects: every affinity candidate is
one row of a fixed [M, Z] mask table (M = 2^Z, Z <= MAX_ZONES small). A
provider's hint list becomes two boolean [P, M] tensors:

  fit[p, m]  — the request fits in the combined free of mask m's zones
  pref[p, m] — m is MINIMAL for this provider (kubelet "preferred" =
               narrowest possible; policy.go mergePermutation keeps
               preferred only when every provider hint is preferred)

The reference's recursive permutation walk (policy.go
iterateAllProviderTopologyHints) reduces to per-mask ANDs because provider
hint sets here are monotone in the zone set (more zones never lose
capacity): a merged candidate c is achievable iff every provider fits c
directly, and it is preferred iff every provider is minimal at c. One
documented deviation: permutations of *differing* multi-zone preferred
hints whose bitwise AND is a strict subset of each (kubelet would emit the
intersection as "preferred" even though no provider can actually allocate
inside it) are not generated — that kubelet corner admits pods the zones
cannot hold, which a capacity-exact scheduler must not do.

Best-hint selection (policy.go mergeFilteredHints ordering): preferred
first, then narrowest (popcount), then hint Score — here the allocation-
strategy key (most/least-allocated over the mask's free CPU), which is
exactly how the reference wires NUMAAllocateStrategy into hint scores —
then lowest mask id for determinism.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu.api.extension import (
    NUMA_POLICY_BEST_EFFORT as POLICY_BEST_EFFORT,
    NUMA_POLICY_NONE as POLICY_NONE,
    NUMA_POLICY_RESTRICTED as POLICY_RESTRICTED,
    NUMA_POLICY_SINGLE_NUMA_NODE as POLICY_SINGLE_NUMA_NODE,
    numa_policy_code as policy_code,
)
from koordinator_tpu.scheduler.batching import EPS


@functools.lru_cache(maxsize=None)
def mask_table(n_zones: int) -> Tuple[np.ndarray, np.ndarray]:
    """(masks bool[M, Z], popcount i32[M]) for M = 2^Z candidate
    affinities; row id == bitmask value, row 0 is the empty mask."""
    m = 1 << n_zones
    ids = np.arange(m, dtype=np.uint32)
    masks = (ids[:, None] >> np.arange(n_zones, dtype=np.uint32)) & 1
    masks = masks.astype(bool)
    return masks, masks.sum(axis=1).astype(np.int32)


def capacity_hints(free_z: jnp.ndarray, req: jnp.ndarray,
                   valid: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The CPU+memory provider (NodeNUMAResource GetPodTopologyHints):
    free_z f32[P, Z, D], req f32[P, D], valid bool[P, Z] ->
    (fit, pref) bool[P, M].

    A mask fits when it uses only valid zones and its combined free covers
    every dimension; pods with zero request have no preference (all masks
    fit and are preferred — the nil-hint row of policy.go
    filterProvidersHints).
    """
    z = free_z.shape[1]
    masks_np, popcnt_np = mask_table(z)
    masks = jnp.asarray(masks_np)                            # [M, Z]
    popcnt = jnp.asarray(popcnt_np)                          # [M]
    avail = jnp.einsum("pzd,mz->pmd", free_z * valid[:, :, None],
                       masks.astype(free_z.dtype),
                       precision=jax.lax.Precision.HIGHEST)  # [P, M, D]
    fit = jnp.all(avail + EPS >= req[:, None, :], axis=-1)   # [P, M]
    # mask must lie within the node's valid zones
    inside = ~jnp.any(masks[None] & ~valid[:, None, :], axis=-1)
    fit &= inside & (popcnt > 0)[None]
    min_cnt = jnp.min(jnp.where(fit, popcnt[None], z + 1), axis=-1)
    pref = fit & (popcnt[None] == min_cnt[:, None])
    no_request = jnp.all(req <= EPS, axis=-1)
    dontcare = jnp.ones_like(fit)
    fit = jnp.where(no_request[:, None], dontcare, fit)
    pref = jnp.where(no_request[:, None], dontcare, pref)
    return fit, pref


def count_hints(zone_counts: jnp.ndarray, need: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The DeviceShare provider (deviceshare topology hints): zone_counts
    i32[P, Z] fitting instances per zone of the chosen node, need i32[P]
    instances -> (fit, pref) bool[P, M]. need == 0 pods have no
    preference."""
    z = zone_counts.shape[1]
    masks_np, popcnt_np = mask_table(z)
    masks = jnp.asarray(masks_np)
    popcnt = jnp.asarray(popcnt_np)
    have = jnp.einsum("pz,mz->pm", zone_counts.astype(jnp.int32),
                      masks.astype(jnp.int32))               # [P, M]
    fit = (have >= need[:, None]) & (popcnt > 0)[None]
    min_cnt = jnp.min(jnp.where(fit, popcnt[None], z + 1), axis=-1)
    pref = fit & (popcnt[None] == min_cnt[:, None])
    none = need <= 0
    dontcare = jnp.ones_like(fit)
    fit = jnp.where(none[:, None], dontcare, fit)
    pref = jnp.where(none[:, None], dontcare, pref)
    return fit, pref


def merge_hints(hints: List[Tuple[jnp.ndarray, jnp.ndarray]]
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """AND across providers (policy.go mergePermutation: affinity is the
    bitwise AND, preferred only when all are preferred)."""
    fit, pref = hints[0]
    for f, p in hints[1:]:
        fit = fit & f
        pref = pref & p
    return fit, pref & fit


def resolve(fit: jnp.ndarray, pref: jnp.ndarray, policy: jnp.ndarray,
            free_cpu_z: jnp.ndarray, valid: jnp.ndarray,
            strategy: str = "most"
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-pod policy outcome.

    Args: (fit, pref) bool[P, M] merged hints, policy i32[P] effective
    policy code, free_cpu_z f32[P, Z] live free CPU per zone (the hint-
    Score strategy key), valid bool[P, Z].
    Returns (affinity bool[P, Z], admit bool[P], engaged bool[P]):
    - engaged: the topology manager constrains this pod (policy != none)
    - admit: policy admission (canAdmitPodResult per policy); none/best-
      effort always admit, restricted needs a preferred merged hint,
      single-numa-node a preferred single-zone hint. Capacity ("no mask
      fits at all") is NOT folded in here — the caller's greedy take +
      prefix gates enforce it exactly.
    - affinity: the best hint's zones; all valid zones for none-policy or
      when nothing fits (so capacity gates, not the mask, reject).
    """
    p, m = fit.shape
    z = free_cpu_z.shape[1]
    masks_np, popcnt_np = mask_table(z)
    masks = jnp.asarray(masks_np)
    popcnt = jnp.asarray(popcnt_np)

    single = (popcnt == 1)[None]                             # [1, M]
    cand = {
        POLICY_BEST_EFFORT: fit,
        POLICY_RESTRICTED: fit & pref,
        POLICY_SINGLE_NUMA_NODE: fit & pref & single,
    }
    # strategy key per mask: total free CPU over the mask's zones,
    # normalised to [0, 1); most-allocated prefers the least-free mask
    mask_free = jnp.einsum("pz,mz->pm", free_cpu_z,
                           masks.astype(free_cpu_z.dtype),
                           precision=jax.lax.Precision.HIGHEST)
    denom = jnp.maximum(jnp.max(mask_free, axis=-1, keepdims=True), 1.0)
    strat = mask_free / (denom * (1.0 + EPS))
    if strategy != "most":
        strat = 1.0 - strat
    # minimise: non-preferred, then popcount, then strategy, then mask id
    base_key = (~pref) * (4.0 * m * (z + 2)) + popcnt[None] * (4.0 * m) \
        + strat * (2.0 * m) + jnp.arange(m)[None] * (1.0 / m)

    engaged = policy > POLICY_NONE
    admit = jnp.ones((p,), bool)
    best_mask = jnp.tile(valid, (1, 1))                      # default: all
    for code, c in cand.items():
        key = jnp.where(c, base_key, jnp.inf)
        idx = jnp.argmin(key, axis=-1)
        any_c = jnp.any(c, axis=-1)
        chosen = jnp.where(any_c[:, None], masks[idx], valid)
        is_pol = policy == code
        best_mask = jnp.where(is_pol[:, None], chosen, best_mask)
        if code == POLICY_RESTRICTED:
            admit &= ~is_pol | jnp.any(fit & pref, axis=-1) \
                | ~jnp.any(fit, axis=-1)
        elif code == POLICY_SINGLE_NUMA_NODE:
            admit &= ~is_pol | jnp.any(fit & pref & single, axis=-1) \
                | ~jnp.any(fit, axis=-1)
    # restricted/single-numa with SOME fitting mask but none admissible is
    # a policy rejection; with NO fitting mask the capacity gates reject,
    # keeping "policy admit" and "capacity" failures distinct like the
    # reference's Unschedulable statuses
    affinity = jnp.where(engaged[:, None], best_mask, valid)
    return affinity, admit, engaged


def greedy_take(free_z: jnp.ndarray, req: jnp.ndarray,
                affinity: jnp.ndarray, strategy: str = "most"
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Split req across the affinity's zones greedily in strategy order.

    free_z f32[P, Z, D] live free at the chosen node, req f32[P, D],
    affinity bool[P, Z] -> (take f32[P, Z, D], filled bool[P]).

    Zones are filled in allocation-strategy order (most-allocated packs
    the fullest zone first), each dimension independently — the batched
    equivalent of the reference allocating cpusets/memory per NUMA node
    inside the merged affinity (resource_manager.go Allocate). `filled`
    is False when the affinity's combined free cannot cover the request.
    """
    avail = jnp.where(affinity[:, :, None], free_z, 0.0)     # [P, Z, D]
    key = free_z[..., 0]                                     # free cpu
    key = jnp.where(affinity, key, jnp.inf if strategy == "most"
                    else -jnp.inf)
    order = jnp.argsort(key, axis=-1)                        # [P, Z]
    if strategy != "most":
        order = order[:, ::-1]
    sorted_avail = jnp.take_along_axis(avail, order[:, :, None], axis=1)
    cum = jnp.cumsum(sorted_avail, axis=1)
    before = cum - sorted_avail
    want = jnp.maximum(req[:, None, :] - before, 0.0)
    sorted_take = jnp.minimum(want, sorted_avail)
    take = jnp.zeros_like(sorted_take).at[
        jnp.arange(order.shape[0])[:, None], order].set(sorted_take)
    filled = jnp.all(jnp.sum(take, axis=1) + EPS >= req, axis=-1)
    return take, filled
