"""Device mesh + sharding specs for the cluster snapshot.

Design (scaling-book recipe): pick a mesh, annotate shardings, let XLA
insert collectives.

- 1D mesh over axis "nodes" (the default): every per-node column
  ([N, ...]) is sharded on dim 0; pod batches, quota/gang state, and
  config are replicated. The [P, N] score matrix is then computed
  shard-locally ([P, N/dev] per chip); jax.lax.top_k over the sharded
  axis makes XLA emit an all-gather of the per-shard top-k candidates
  over ICI (the global "selectHost" reduce); scatter-commits to node
  columns land shard-locally.
- 2D mesh over ("pods", "nodes") (`make_mesh(devices, pods_axis=m)`):
  the pod queue's [P, ...] columns additionally shard over the pods
  axis, so the [P, N] intermediates tile over BOTH axes — the option
  for meshes big enough that node-axis sharding alone leaves chips
  idle. `batch_sharding`/`shard_batch` place a PodBatch accordingly.
- The equivalent of sequence/context parallelism for this workload is
  exactly this node-axis sharding (SURVEY.md 5 "long-context"): the scaling
  axis is cluster size, and the collective pattern (shard-local reduce +
  cross-chip top-k merge) mirrors ring-attention's shard-local softmax +
  global combine.

Inside `scheduler.core.schedule_batch` (pure jit) annotating the
operand placements is enough — GSPMD propagates the node sharding
through every [.., N] intermediate, computes the cascade's stage-1 mask
shard-locally (zero collectives; tests/test_mesh_flagship.py pins that
structurally on the compiled HLO) and emits the ICI top-k merge for
lax.top_k. For stages composed OUTSIDE one jitted program — where GSPMD
propagation has nothing to propagate through — the explicit shard_map
kernels live in `parallel.shardops` (shard-local stage-1, per-shard
top-k + ICI merge with exact tie semantics).

Sharding specs are DERIVED from the koordshape `register_struct`
field-spec tables (snapshot/schema.py): a leaf whose declared spec
carries the node symbol `N` shards that axis over "nodes", a [P]-leading
pod column shards over "pods" when the mesh has that axis, everything
else replicates. Adding a snapshot field therefore cannot silently get
the wrong placement — the same table that feeds the shape checkers
feeds the mesh layout.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from koordinator_tpu.snapshot.schema import (
    ClusterSnapshot,
    PAD_FILL_VALUES,
    PodBatch,
    STRUCT_CLASSES,
    STRUCT_SPECS,
)

NODE_AXIS = "nodes"
POD_AXIS = "pods"


def make_mesh(devices: Optional[list] = None, pods_axis: int = 1) -> Mesh:
    """Mesh over all (or the given) devices: 1D on the node axis by
    default; `pods_axis > 1` folds the devices into a 2D
    (pods, nodes) grid (pods_axis must divide the device count)."""
    devices = jax.devices() if devices is None else devices
    if pods_axis <= 1:
        return Mesh(np.asarray(devices), (NODE_AXIS,))
    if len(devices) % pods_axis:
        raise ValueError(f"pods_axis={pods_axis} must divide the device "
                         f"count {len(devices)}")
    grid = np.asarray(devices).reshape(pods_axis,
                                       len(devices) // pods_axis)
    return Mesh(grid, (POD_AXIS, NODE_AXIS))


def mesh_axis_sizes(mesh: Mesh) -> dict:
    """{axis name: size} — the self-describing mesh stamp (a 4-device
    mesh must say whether it is 1x4 or 2x2)."""
    return {name: int(size)
            for name, size in zip(mesh.axis_names, mesh.devices.shape)}


def node_shards(mesh: Mesh) -> int:
    return int(mesh.shape[NODE_AXIS])


# --- spec-derived sharding trees ----------------------------------------

def _leaf_dims(spec) -> Optional[tuple]:
    """Dim-symbol tuple of a leaf spec string, `~pad:` predicates
    stripped ("f32[N~pad:zero,R]" -> ("N", "R")); None for struct
    references and bare-symbol properties."""
    if not isinstance(spec, str) or "[" not in spec:
        return None
    body = spec[spec.index("[") + 1:spec.rindex("]")].strip()
    if not body:
        return ()
    return tuple(t.split("~")[0].strip() for t in body.split(","))


def _node_fill(spec: str):
    """The concrete pad fill for a leaf's node axis, read off the `N`
    dim's declared ~pad: predicate (PAD_FILL_VALUES); predicates with
    no canonical fill (invalid/any) and undeclared dims fill 0."""
    body = spec[spec.index("[") + 1:spec.rindex("]")]
    for tok in body.split(","):
        dim, _, anno = tok.strip().partition("~")
        if dim.strip() == "N" and anno.strip().startswith("pad:"):
            fill = PAD_FILL_VALUES.get(anno.strip()[len("pad:"):])
            return 0 if fill is None else fill
    return 0


def _leaf_partition(dims: tuple, mesh: Mesh, shard_pods: bool) -> P:
    """PartitionSpec for one leaf: any `N` axis shards over the node
    axis; a LEADING `P` shards over the pods axis when asked for and
    the mesh has one; everything else replicates."""
    axes = []
    for i, d in enumerate(dims):
        if d == "N":
            axes.append(NODE_AXIS)
        elif (d == "P" and i == 0 and shard_pods
              and POD_AXIS in mesh.axis_names):
            axes.append(POD_AXIS)
        else:
            axes.append(None)
    while axes and axes[-1] is None:  # P(None) is not P()
        axes.pop()
    return P(*axes)


def struct_sharding(name: str, mesh: Mesh, shard_pods: bool = False):
    """Build a struct-shaped pytree of NamedShardings from the
    registered field-spec table (bare-symbol properties are skipped;
    nested registered structs recurse). Works for ANY registered
    struct whose defining module is imported — e.g.
    struct_sharding("ScheduleResult", mesh) derives the out_shardings
    of a sharded schedule step."""
    fields = {}
    for fname, spec in STRUCT_SPECS[name].items():
        if isinstance(spec, str) and spec in STRUCT_SPECS:
            fields[fname] = struct_sharding(spec, mesh, shard_pods)
            continue
        dims = _leaf_dims(spec)
        if dims is None:
            continue  # symbolic-int property (num_nodes), not a field
        fields[fname] = NamedSharding(
            mesh, _leaf_partition(dims, mesh, shard_pods))
    return STRUCT_CLASSES[name](**fields)


def snapshot_sharding(mesh: Mesh) -> ClusterSnapshot:
    """A ClusterSnapshot-shaped pytree of NamedShardings, derived from
    the koordshape field-spec tables: node columns ([N, ...] leaves in
    nodes.*/devices.*) shard dim 0, everything else replicates."""
    return struct_sharding("ClusterSnapshot", mesh)


def batch_sharding(pods: PodBatch, mesh: Mesh) -> PodBatch:
    """A PodBatch-shaped pytree of NamedShardings for the 2D mesh path:
    per-pod [P, ...] columns shard over the pods axis (when the mesh
    has one), the batch-global [*, N] domain matrices shard their node
    axis, count surfaces and selector/toleration tables replicate.
    Built by `replace` on `pods` so the static gate switches
    (has_taints & co, pytree aux data) match the batch being placed."""
    upd = {}
    for fname, spec in STRUCT_SPECS["PodBatch"].items():
        dims = _leaf_dims(spec)
        if dims is None:
            continue
        part = _leaf_partition(dims, mesh, shard_pods=True)
        # degenerate compile-out extents (the [1, 1] domain matrices of
        # slim workloads) and any axis the mesh doesn't divide replicate
        shape = getattr(pods, fname).shape
        part = P(*(ax if ax is not None
                   and shape[i] % mesh.shape[ax] == 0 and shape[i] > 1
                   else None
                   for i, ax in enumerate(part)))
        upd[fname] = NamedSharding(mesh, part)
    return pods.replace(**upd)


def candidate_mask_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for the cascade's [P, N] stage-1 candidate mask
    (scheduler/cascade.stage1_mask): pods replicate, node columns shard
    — the mask follows the node-column layout of every other [.., N]
    operand, so stage 1 is shard-local with zero collectives. Inside
    `schedule_batch` GSPMD derives exactly this placement from the
    snapshot's sharding; the export exists for callers that build or
    inspect the mask OUTSIDE the jitted program (smoke tools, tests)."""
    return NamedSharding(mesh, P(None, NODE_AXIS))


def shard_snapshot(snap: ClusterSnapshot, mesh: Mesh) -> ClusterSnapshot:
    """Place a host snapshot onto the mesh (node axis sharded over ICI).

    The node count must be divisible by the mesh's node-axis size —
    run the snapshot through `pad_nodes_to_mesh` first when it isn't
    (SnapshotBuilder's max_nodes is the padded size on the typed path).

    Zero-size leaves (the [N, A, 0] aux-device columns of a cluster
    with no aux devices) replicate: that is the layout a jitted commit
    returns them in, and publishing any other would make the second
    cycle recompile the whole sharded program.
    """
    replicated = NamedSharding(mesh, P())
    shardings = snapshot_sharding(mesh)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s if x.size else replicated),
        snap, shardings)


def shard_batch(pods: PodBatch, mesh: Mesh) -> PodBatch:
    """Place a pod batch onto the mesh per `batch_sharding` (the 2D
    mesh path; on a 1D node mesh it replicates per-pod columns and
    shards only the [*, N] domain matrices)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), pods, batch_sharding(pods, mesh))


# --- node-axis padding ---------------------------------------------------
#
# Pad fills are DERIVED from the ~pad: predicates the field-spec tables
# declare (_node_fill above): cpu_amplification pads 1.0 (pad:one — a
# ratio column stays semantically well-formed), instance/domain topology
# pads -1 (pad:-1 — "unknown" / "node lacks the topology key"), and
# everything else pads 0. tools/padcheck.py asserts the fills; the
# pad-soundness lint pass asserts consumers respect them.


def padded_node_count(num_nodes: int, mesh: Mesh) -> int:
    """The node-axis size after padding to a multiple of the mesh's
    node-axis extent."""
    size = node_shards(mesh)
    return -(-num_nodes // size) * size


def _pad_leaf(x, dims: tuple, n_old: int, n_new: int, fill):
    """Pad every axis whose declared symbol is N (and whose runtime
    extent actually is the node count — degenerate [1, 1] compile-out
    matrices stay put) from n_old to n_new with `fill`."""
    for axis, d in enumerate(dims):
        if d != "N" or x.shape[axis] != n_old:
            continue
        lib = np if isinstance(x, np.ndarray) else jax.numpy
        shape = x.shape[:axis] + (n_new - n_old,) + x.shape[axis + 1:]
        x = lib.concatenate(
            [x, lib.full(shape, fill, dtype=x.dtype)], axis=axis)
    return x


def _pad_struct(obj, name: str, n_old: int, n_new: int):
    upd = {}
    for fname, spec in STRUCT_SPECS[name].items():
        if isinstance(spec, str) and spec in STRUCT_SPECS:
            upd[fname] = _pad_struct(getattr(obj, fname), spec,
                                     n_old, n_new)
            continue
        dims = _leaf_dims(spec)
        if dims is None or "N" not in dims:
            continue
        upd[fname] = _pad_leaf(getattr(obj, fname), dims, n_old, n_new,
                               _node_fill(spec))
    return obj.replace(**upd)


def pad_nodes_to_mesh(snap: ClusterSnapshot, mesh: Mesh) -> ClusterSnapshot:
    """Pad the snapshot's node axis to a multiple of the mesh's
    node-axis size with zero-capacity rows, so callers never hand-pad
    before `shard_snapshot`. Derived from the same field-spec tables as
    the shardings (every leaf with an `N` axis pads; numpy inputs stay
    on host).

    PAD-ROW CONTRACT: pad rows are PROVABLY unschedulable — schedulable
    is False (the static gates zero their columns, so the cascade's
    stage-1 mask kills them before any score is computed) and
    allocatable is zero (the resource-fit gate rejects them
    independently). They therefore can never be charged: `requested`
    stays zero and the overcommit invariant is checked on the real rows
    only (`core.overcommit_ok(snap, num_real_nodes)` — pad rows are
    excluded by construction, not by tolerance).
    """
    n_old = snap.num_nodes
    n_new = padded_node_count(n_old, mesh)
    if n_new == n_old:
        return snap
    return _pad_struct(snap, "ClusterSnapshot", n_old, n_new)


def unpad_nodes(snap: ClusterSnapshot, num_real: int) -> ClusterSnapshot:
    """Slice a `pad_nodes_to_mesh`-padded snapshot back to its real
    node count — the inverse walk over the same field-spec tables.

    The mesh-shrink ladder rung (frameworkext.DegradationLadder) pads
    and re-shards per cycle over whatever devices survive; committing
    the PADDED post-cycle snapshot to the store would make the stored
    shapes a function of the surviving-device count (a recompile per
    shrink event, and a shape mismatch the moment the full mesh
    returns). Unpadding is sound because pad rows are provably inert:
    schedulable=False + zero allocatable means they are never chosen
    and never charged (`core.overcommit_ok` pins that), so slicing
    them off loses nothing."""
    n_now = snap.num_nodes
    if n_now == num_real:
        return snap
    if n_now < num_real:
        raise ValueError(f"cannot unpad {n_now} nodes to {num_real}")

    def slice_leaf(x, dims):
        for axis, d in enumerate(dims):
            if d == "N" and x.shape[axis] == n_now:
                index = [slice(None)] * x.ndim
                index[axis] = slice(0, num_real)
                x = x[tuple(index)]
        return x

    def walk(obj, name):
        upd = {}
        for fname, spec in STRUCT_SPECS[name].items():
            if isinstance(spec, str) and spec in STRUCT_SPECS:
                upd[fname] = walk(getattr(obj, fname), spec)
                continue
            dims = _leaf_dims(spec)
            if dims is None or "N" not in dims:
                continue
            upd[fname] = slice_leaf(getattr(obj, fname), dims)
        return obj.replace(**upd)

    return walk(snap, "ClusterSnapshot")


def pad_batch_nodes(pods: PodBatch, num_nodes: int) -> PodBatch:
    """Pad the batch's node-indexed matrices (the [*, N] topology
    domain maps) to a padded snapshot's node count, filling -1 ("node
    lacks the key") so pad columns can never open or charge a domain.
    A no-op when nothing carries the real node count (the [1, 1]
    compile-out matrices of slim workloads)."""
    extents = set()
    for fname, spec in STRUCT_SPECS["PodBatch"].items():
        dims = _leaf_dims(spec)
        if dims is None or "N" not in dims:
            continue
        extents.add(getattr(pods, fname).shape[dims.index("N")])
    extents -= {1, num_nodes}
    if not extents:
        return pods
    if len(extents) > 1 or max(extents) > num_nodes:
        raise ValueError(f"inconsistent batch node extents {sorted(extents)} "
                         f"vs padded node count {num_nodes}")
    return _pad_struct(pods, "PodBatch", extents.pop(), num_nodes)
