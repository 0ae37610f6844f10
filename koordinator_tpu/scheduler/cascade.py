"""Stage 1 of the Filter->Score gate cascade.

The reference scheduler never scores what it hasn't filtered: its Filter
stage prunes the node set before Score ever runs (koordinator's
Filter/Score cycle; cf. Tesserae's two-level prune-then-place, arxiv
2508.04953). The batched kernel historically had no Filter stage at all —
every gate, cheap or heavy, ran over the full [P, N] pair space. This
module is that missing Filter stage, split in two layers:

- `static_gates`: the cheap per-batch node gates (schedulable +
  nodeSelector + LoadAware usage + taint forbids) shared by BOTH cascade
  modes. Moved here out of `core.schedule_batch` so the cascade and the
  legacy full-width path run one implementation and cannot drift.
- `stage1_mask`: the cascade-only candidate mask — the static gates AND
  batch-start resource fit AND batch-start quota-ceiling admission
  (ops/feasibility kernels).

Soundness contract (why `cascade=True` is placement-preserving): within
one `schedule_batch` call, node `requested` and quota `used` are MONOTONE
— scatter-commits only add non-negative accepted requests — so a
(pod, node) pair that fails the batch-start fit or quota ceiling fails
the corresponding exact gate in every commit round. Folding the stage-1
mask into the static gates therefore removes only pairs the rounds would
have rejected anyway: masked scores, top-k order, and every downstream
prefix gate see identical inputs, and placements are bit-for-bit the
same with the cascade on or off. `cascade=False` is the conformance
oracle (tests/test_cascade.py pins equality on the full-gate workload).

What stage 2 buys: with the cheap mask folded in early, `core` narrows
the HEAVY per-pair machinery — the [P, N, I] device prefilter/score, the
[P, N, Z] zone prefilter/score, and the policy combined-fit — to the
class-prefix rows that can possibly engage them (the numa_prefix /
gpu_prefix packing contracts), padding pass-through rows back in. On the
constraint-sparse flagship workload those tensors shrink ~10x.

The [P, N] mask follows the snapshot's node-column sharding on a mesh
(parallel/mesh.candidate_mask_sharding): pods replicate, node columns
shard, so stage 1 is embarrassingly parallel over chips — the compiled
stage-1 HLO over sharded inputs contains zero collectives
(tests/test_mesh_flagship.py pins that structurally), and
parallel.shardops.stage1_mask_sharded is the explicit shard_map form
for callers composing the mask outside one jitted program. Pad rows
appended by parallel.pad_nodes_to_mesh are killed here: schedulable is
False and allocatable zero, so their columns are all-False in every
stage-1 mask.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from koordinator_tpu import obs
from koordinator_tpu.obs import phases as obs_phases
from koordinator_tpu.ops import feasibility
from koordinator_tpu.scheduler.batching import MAX_NODE_SCORE
from koordinator_tpu.scheduler.plugins import loadaware
from koordinator_tpu.snapshot.schema import (
    ClusterSnapshot,
    MAX_QUOTA_DEPTH,
    NodeState,
    PodBatch,
    shape_contract,
)


@shape_contract(
    nodes="NodeState", pods="PodBatch", cfg="LoadAwareConfig",
    _returns=("bool[P~pad:invalid,N~pad:false]",
              "?f32[P~pad:any,N~pad:any]"),
    _pad="unschedulable (padded) node columns are False everywhere; "
         "taint_penalty is None when the batch models no tolerations "
         "(has_taints False — the gate compiles out)")
def static_gates(nodes: NodeState, pods: PodBatch,
                 cfg: loadaware.LoadAwareConfig
                 ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """(static_ok bool[P, N], taint_penalty f32[P, N] or None): the
    cheap round-invariant node gates of the batch.

    - nodeSelector: sel_match[sel_id, label_group[n]]; -1 matches all.
    - LoadAware filter: round-invariant (it reads only NodeMetric-derived
      columns and thresholds, never assume state — load_aware.go:123-254
      touches no NodeInfo.requested), so it is computed once per batch.
    - TaintToleration (vanilla-framework plugin the reference's extender
      wraps): forbid on untolerated NoSchedule/NoExecute, penalize
      untolerated PreferNoSchedule. Matrices ride (toleration-set x
      taint-group) exactly like the selector gate; `has_taints` False
      means the batch carries no toleration modeling (synthetic fast
      path) and the gates compile out (taint_penalty None).
    """
    with obs.phase(obs_phases.PHASE_STAGE1_STATIC):
        sel = jnp.maximum(pods.selector_id, 0)
        sel_ok = (pods.selector_id[:, None] < 0) | \
            pods.selector_match[sel][:, nodes.label_group]       # [P, N]
        la_ok = loadaware.filter_mask(nodes, pods, cfg)
        static_ok = la_ok & sel_ok & nodes.schedulable[None, :]  # [P, N]
        if pods.has_taints:
            tol_row = pods.tol_forbid[jnp.maximum(pods.toleration_id, 0)]
            static_ok &= ~tol_row[:, nodes.taint_group]          # [P, N]
            prefer_cnt = pods.tol_prefer[
                jnp.maximum(pods.toleration_id, 0)][:, nodes.taint_group]
            taint_penalty = prefer_cnt / jnp.maximum(
                jnp.max(pods.tol_prefer), 1.0) * MAX_NODE_SCORE
        else:
            taint_penalty = None
        return static_ok, taint_penalty


@shape_contract(
    snap="ClusterSnapshot", pods="PodBatch",
    static_ok="bool[P~pad:invalid,N~pad:false]",
    _returns="bool[P~pad:invalid,N~pad:false]",
    _pad="a SUPERSET of every commit round's node-column feasibility; "
         "never applied to reservation slot columns (consumers draw "
         "from the slot's own hold)")
def stage1_mask(snap: ClusterSnapshot, pods: PodBatch,
                static_ok: jnp.ndarray,
                fit_dims: Optional[tuple] = None,
                quota_depth: int = MAX_QUOTA_DEPTH) -> jnp.ndarray:
    """bool[P, N]: the stage-1 candidate mask — `static_ok` pruned by
    batch-start resource fit and quota-ceiling admission.

    MASK CONTRACT: the mask is a SUPERSET of every commit round's exact
    feasibility on node columns (monotone batch-start state; see module
    docstring), so ANDing it into the static gates is placement-
    preserving. It must NOT be applied to reservation slot columns: a
    consumer draws from the slot's own hold, not the node's open pool,
    so a full node legitimately admits its slot's consumers
    (core keeps `static_base` for the slot columns).
    """
    with obs.phase(obs_phases.PHASE_STAGE1_MASK):
        mask = static_ok & feasibility.resource_fit(
            snap.nodes.allocatable, snap.nodes.requested, pods.requests,
            fit_dims)
        mask &= feasibility.quota_ceiling_ok(
            snap.quotas, pods, quota_depth, fit_dims)[:, None]
        return mask


@shape_contract(mask="bool[P~pad:invalid,N~pad:false]",
                _returns="i32[P~pad:any]",
                _pad="pad pod rows count their surviving pad-invariant "
                     "columns — observability only, masked by valid")
def candidate_counts(mask: jnp.ndarray) -> jnp.ndarray:
    """i32[P]: surviving candidate nodes per pod — the cascade's
    observability hook (a zero row is a pod stage 1 already proved
    unschedulable; tools/cascade_smoke.py asserts on it)."""
    return jnp.sum(mask.astype(jnp.int32), axis=1)
