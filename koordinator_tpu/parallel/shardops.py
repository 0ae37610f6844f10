"""Explicit shard_map kernels for the node-sharded scheduling program.

Inside one jitted `schedule_batch` GSPMD propagation is enough: the
snapshot's node sharding flows through every [.., N] intermediate, the
cascade's stage-1 mask is computed shard-locally with zero collectives,
and `lax.top_k` over the sharded axis lowers to a per-shard top-k plus
an ICI merge (tests/test_mesh_flagship.py and
tests/test_chip_compile.py pin both structurally on the compiled HLO).
Where GSPMD has nothing to propagate through — stages composed OUTSIDE
one jitted program, such as tests building the stage-1 mask
standalone, or custom pipelines that want the candidate merge before a
host-side commit — these shard_map kernels are the explicit,
conformance-pinned equivalents:

- `stage1_mask_sharded`: the cascade's stage-1 candidate mask computed
  per node shard (each chip sees only its node columns; the quota
  ceiling, a [P]-only term, is recomputed replicated per shard — cheap
  and collective-free).
- `shard_local_topk`: per-shard `lax.top_k` + all-gather of the
  (value, global index) candidates over ICI + `topk_merge`, the
  lexicographic (value desc, index asc) merge whose tie order is
  exactly `lax.top_k`'s — bit-identical to the global reduction
  (tests/test_mesh_flagship.py pins it, ties included).

Both run under `jax.jit` at the call site; nothing here is a
module-level jit entry.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from koordinator_tpu import obs
from koordinator_tpu.obs import phases as obs_phases
from koordinator_tpu.parallel.mesh import (
    NODE_AXIS,
    node_shards,
    snapshot_sharding,
)
from koordinator_tpu.scheduler.cascade import stage1_mask
from koordinator_tpu.snapshot.schema import (
    ClusterSnapshot,
    MAX_QUOTA_DEPTH,
    PodBatch,
    shape_contract,
)


def stage1_mask_sharded(mesh: Mesh, snap: ClusterSnapshot, pods: PodBatch,
                        static_ok: jnp.ndarray,
                        fit_dims: Optional[tuple] = None,
                        quota_depth: int = MAX_QUOTA_DEPTH) -> jnp.ndarray:
    """bool[P, N]: `cascade.stage1_mask` computed shard-locally — each
    chip evaluates batch-start resource fit over its own node columns
    only. Zero collectives by construction (the resource fit is
    elementwise over node columns; the quota-ceiling term reads no node
    state and is recomputed identically on every shard), and
    bit-identical to the global mask.

    `check_vma=False` because shard_map cannot prove the replicated
    quota term is shard-invariant; the conformance test does."""
    snap_spec = jax.tree_util.tree_map(lambda s: s.spec,
                                       snapshot_sharding(mesh))
    pods_spec = jax.tree_util.tree_map(lambda _: P(), pods)
    mask_spec = P(None, NODE_AXIS)

    fn = jax.shard_map(
        lambda sn, pd, so: stage1_mask(sn, pd, so, fit_dims=fit_dims,
                                       quota_depth=quota_depth),
        mesh=mesh, in_specs=(snap_spec, pods_spec, mask_spec),
        out_specs=mask_spec, check_vma=False)
    return fn(snap, pods, static_ok)


@shape_contract(
    vals="f32[P~pad:any,KC]", idxs="i32[P~pad:any,KC]",
    _returns=("f32[P~pad:any,KC]", "i32[P~pad:any,KC]"),
    _pad="KC = gathered per-shard candidates (k x node shards); rows "
         "sort by (value desc, global index asc) — exactly lax.top_k's "
         "tie order, so [:, :k] of the output equals the global top-k")
def topk_merge(vals: jnp.ndarray, idxs: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Lexicographic merge of gathered per-shard top-k candidate rows:
    sort each row by (value descending, global index ascending). Every
    global top-k element survives its own shard's local top-k, so
    slicing the merged row to k is bit-identical to `lax.top_k` over
    the full row — including ties, which lax.top_k breaks toward the
    lowest index."""
    with obs.phase(obs_phases.PHASE_ICI_MERGE):
        order = jnp.lexsort((idxs, -vals), axis=-1)
        return (jnp.take_along_axis(vals, order, axis=-1),
                jnp.take_along_axis(idxs, order, axis=-1))


def shard_local_topk(mesh: Mesh, scores: jnp.ndarray, k: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(f32[P, k], i32[P, k]): the explicit form of the ICI top-k
    reduce — per-shard `lax.top_k` over the local node columns,
    all-gather of the (value, global index) candidates over the node
    axis, then `topk_merge`. Bit-identical to
    `jax.lax.top_k(scores, k)` on the unsharded operand.

    Requires the sharded axis divisible by the shard count (run node
    columns through `pad_nodes_to_mesh` first) and k <= the local
    width — the global top-k may live entirely in one shard, so a
    shard must be able to nominate k candidates.
    """
    n = scores.shape[-1]
    shards = node_shards(mesh)
    if n % shards:
        raise ValueError(f"column count {n} not divisible by the "
                         f"{shards}-way node axis (pad_nodes_to_mesh)")
    local = n // shards
    if k > local:
        raise ValueError(f"k={k} exceeds the local shard width {local}; "
                         "a single shard could hold the whole top-k")

    def per_shard(x):
        with obs.phase(obs_phases.PHASE_TOPK):
            v, i = jax.lax.top_k(x, k)
            off = jax.lax.axis_index(NODE_AXIS) * local
            i = (i + off).astype(jnp.int32)
        with obs.phase(obs_phases.PHASE_ICI_MERGE):
            v = jax.lax.all_gather(v, NODE_AXIS, axis=v.ndim - 1,
                                   tiled=True)
            i = jax.lax.all_gather(i, NODE_AXIS, axis=i.ndim - 1,
                                   tiled=True)
            mv, mi = topk_merge(v, i)
            return mv[..., :k], mi[..., :k]

    fn = jax.shard_map(per_shard, mesh=mesh,
                       in_specs=P(None, NODE_AXIS),
                       out_specs=(P(), P()), check_vma=False)
    return fn(scores)
