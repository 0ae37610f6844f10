"""The registry-walking AOT enumerator: warm the scheduler before the
cluster needs it.

Every program the scheduler can dispatch is already named by the
koordshape registry — STRUCT_SPECS declares the field layout of each
pytree struct, the contract table declares each kernel's arg specs —
so for a configured working set (P pods, N nodes, I instances, Z
zones, G gangs, ... and a device count) the whole program set is
enumerable ahead of time:

  - the flagship cycle program (core.schedule_batch, or the guarded
    fusion when the service runs guards) per cascade form, on one
    device with the pod batch as the one buffer the service uploads
    (snapshot/hostbatch.py);
  - the same under every plausible SHRUNK mesh (devices, devices-1,
    ..., 1) with the node axis padded to each mesh exactly as the
    service's mesh-shrink rung pads it — so device loss fails over
    onto an already-compiled program;
  - the canonical tail-compaction form (`tail_program` below: the
    device-resident adaptive tail with buffer donation threaded
    through, the same donate-(snap, counts) signature the bench jits).

`warm()` lowers + AOT-compiles each through a CompileCache; the JAX
persistent cache then serves the XLA binary to any later jit dispatch
of the same computation, so a warmed process (or a fresh process over
the same cache dir, SAME HOST) traces but never re-compiles.

`ensure_cycle_program` is the service-side entry: derive the abstract
signature from the CONCRETE cycle inputs (shapes, dtypes, committed
shardings) and ensure that one point — a dict lookup once warm.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu.compilecache import keys
from koordinator_tpu.compilecache.cache import CompileCache
from koordinator_tpu.scheduler import core
from koordinator_tpu.snapshot import hostbatch
from koordinator_tpu.snapshot.schema import shape_contract

# --- the canonical AOT tail form ------------------------------------------
# The bench builds its tail closure inline (sweep fused in); the
# service has no tail yet. This module-level form IS the enumerable
# tail program: schedule_batch at tail strength threaded through the
# device-resident compaction loop, with the same (snap, counts) buffer
# donation the bench's tail jits carry — donated operands alias into
# the outputs on device backends instead of doubling the snapshot's
# footprint per pass.


@shape_contract(
    snap="ClusterSnapshot",
    counts=("f32[SG,DM~pad:zero]", "f32[AG,DM~pad:zero]",
            "f32[AG,DM~pad:zero]", "f32[FG,DM~pad:zero]"),
    assign="i32[P~pad:-1]", pods="PodBatch", cfg="LoadAwareConfig",
    _returns=("ClusterSnapshot",
              ("f32[SG,DM~pad:zero]", "f32[AG,DM~pad:zero]",
               "f32[AG,DM~pad:zero]", "f32[FG,DM~pad:zero]"),
              "i32[P~pad:-1]", "i32[4]"),
    _static={"tail_chunk": "TC", "min_passes": 1, "max_passes": 2,
             "tail_rounds": 2, "tail_k": 2, "cascade": False},
    _pad="delegates to core.tail_compaction_loop (same stats contract: "
         "[after_sweep, final, never_retried, passes]); counts ride "
         "COUNT_FIELDS order and pass through unchanged "
         "(charge_counts=False — the topology-free tail form)")
@functools.partial(jax.jit,
                   static_argnames=("tail_chunk", "min_passes",
                                    "max_passes", "tail_rounds",
                                    "tail_k", "cascade"),
                   donate_argnums=(0, 1))
def tail_program(snap, counts, assign, pods, cfg, *, tail_chunk: int,
                 min_passes: int, max_passes: int, tail_rounds: int = 4,
                 tail_k: int = 32, cascade: bool = False):
    """The precompilable tail: one jitted program the enumerator can
    lower for any working-set point (the bench's fused sweep+tail
    closure is shape-identical in its tail half)."""
    step = functools.partial(core.schedule_batch, num_rounds=tail_rounds,
                             k_choices=tail_k, score_dims=(0, 1),
                             tie_break=True, quota_depth=2,
                             fit_dims=(0, 1, 2, 3), cascade=cascade)
    return core.tail_compaction_loop(
        step, snap, counts, assign, pods, cfg, tail_chunk=tail_chunk,
        min_passes=min_passes, max_passes=max_passes,
        charge_counts=False)


# --- abstract-input construction from the registry ------------------------

_DTYPE_NAMES = {"f32": "float32", "i32": "int32", "i8": "int8",
                "u32": "uint32", "bool": "bool"}

# the configured working set's default dim sizes (every non-fixed
# symbol a struct field can carry); callers override the ones they
# care about (P, N, I, Z, G, devices) via WorkSet(sizes={...})
DEFAULT_SIZES = {
    "P": 256, "N": 128, "I": 2, "Z": 2, "G": 8, "Q": 8, "V": 4,
    "S": 4, "L": 4, "T": 4, "TG": 4, "SG": 1, "AG": 1, "FG": 1,
    "DM": 1, "J": 2, "K": 8, "KC": 8, "TC": 64, "RD": 4, "NS": 4,
}


def full_sizes(sizes: Dict[str, int]) -> Dict[str, int]:
    """Overlay the caller's sizes on the defaults and pin the fixed
    axes (R = NUM_RESOURCES, AGG/DEV/AX/QD module constants) — the
    same closure tools/shapecheck.py runs the contracts under."""
    from koordinator_tpu.api.extension import NUM_RESOURCES
    from koordinator_tpu.snapshot.schema import FIXED_DIMS

    out = dict(DEFAULT_SIZES)
    out.update(sizes)
    out["R"] = NUM_RESOURCES
    out.update(FIXED_DIMS)
    return out


def _parse_leaf(raw: str):
    """Minimal field-spec read (the parallel/mesh.py `_leaf_dims`
    precedent: package code re-reads the literal grammar rather than
    importing the tools/ lint tier). Returns (dtype, dims, optional)
    for a leaf spec, or None for a bare-symbol DimProp / struct ref."""
    s = raw.strip()
    optional = s.startswith("?")
    if optional:
        s = s[1:]
    if "[" not in s or not s.endswith("]"):
        return None
    dtype, rest = s.split("[", 1)
    if dtype not in _DTYPE_NAMES:
        return None
    dims: List[Any] = []
    body = rest[:-1].strip()
    if body:
        for tok in body.split(","):
            tok = tok.split("~")[0].strip()  # strip the ~pad: predicate
            dims.append(int(tok) if tok.lstrip("-").isdigit() else tok)
    return _DTYPE_NAMES[dtype], tuple(dims), optional


def _leaf_sharding(dims: tuple, mesh) -> Optional[Any]:
    """The service's mesh-shrink placement rule: node-leading axes
    shard over the mesh's node axis, everything else replicates
    (parallel/mesh.py struct_sharding, shard_pods=False)."""
    if mesh is None:
        return None
    from koordinator_tpu.parallel import mesh as meshlib

    axes = tuple(meshlib.NODE_AXIS if d == "N" else None for d in dims)
    return jax.sharding.NamedSharding(mesh,
                                      jax.sharding.PartitionSpec(*axes))


def abstract_value(raw, sizes: Dict[str, int], mesh=None,
                   materialize_optional: bool = True):
    """One field spec -> an abstract value: ShapeDtypeStruct leaves
    (sharding-annotated under a mesh), recursed structs, tuples.
    Returns the `_SKIP` sentinel for bare-symbol DimProps."""
    from koordinator_tpu.snapshot.schema import STRUCT_SPECS

    if isinstance(raw, tuple):
        return tuple(abstract_value(r, sizes, mesh, materialize_optional)
                     for r in raw)
    leaf = _parse_leaf(raw)
    if leaf is not None:
        dtype, dims, optional = leaf
        if optional and not materialize_optional:
            return None
        shape = tuple(d if isinstance(d, int) else sizes[d] for d in dims)
        sharding = _leaf_sharding(dims, mesh)
        if sharding is not None:
            return jax.ShapeDtypeStruct(shape, np.dtype(dtype),
                                        sharding=sharding)
        return jax.ShapeDtypeStruct(shape, np.dtype(dtype))
    name = raw.strip().lstrip("?")
    if name in STRUCT_SPECS:
        return abstract_struct(name, sizes, mesh, materialize_optional)
    return _SKIP


_SKIP = object()


def abstract_struct(name: str, sizes: Dict[str, int], mesh=None,
                    materialize_optional: bool = True):
    """Registry walk: STRUCT_SPECS[name] -> an abstract struct instance
    whose leaves are ShapeDtypeStructs sized by the working set (bare
    dim symbols are symbolic-int properties, never constructor
    fields — the shapecheck Tier-B rule)."""
    from koordinator_tpu.snapshot.schema import STRUCT_CLASSES, STRUCT_SPECS

    cls = STRUCT_CLASSES[name]
    kwargs = {}
    for fname, raw in STRUCT_SPECS[name].items():
        v = abstract_value(raw, sizes, mesh, materialize_optional)
        if v is _SKIP:
            continue
        kwargs[fname] = v
    return cls(**kwargs)


def abstract_from_example(tree):
    """Concrete cycle inputs -> the same pytree of ShapeDtypeStructs,
    preserving committed shardings (64-bit host leaves canonicalize to
    the 32-bit layout jit would give them)."""
    from jax import dtypes as jax_dtypes

    def to_sds(x):
        dt = getattr(x, "dtype", None)
        if dt is None:
            dt = np.asarray(x).dtype
        dt = jax_dtypes.canonicalize_dtype(np.dtype(dt))
        sharding = getattr(x, "sharding", None)
        if isinstance(sharding, jax.sharding.NamedSharding):
            return jax.ShapeDtypeStruct(np.shape(x), dt,
                                        sharding=sharding)
        return jax.ShapeDtypeStruct(np.shape(x), dt)

    return jax.tree_util.tree_map(to_sds, tree)


def mesh_axes_of(tree) -> Optional[Dict[str, int]]:
    """The mesh axis sizes any sharded leaf of `tree` was committed
    under, or None (single-device / host inputs)."""
    from koordinator_tpu.parallel import mesh as meshlib

    for leaf in jax.tree_util.tree_leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        if isinstance(sharding, jax.sharding.NamedSharding):
            return meshlib.mesh_axis_sizes(sharding.mesh)
    return None


# --- the working set + enumeration ----------------------------------------

# the service's cycle-program static defaults (SchedulerService passes
# its schedule_kwargs verbatim; these mirror the smoke/test settings)
DEFAULT_STATICS = {"num_rounds": 2, "k_choices": 4}
DEFAULT_TAIL = {"tail_chunk": 64, "min_passes": 2, "max_passes": 6,
                "tail_rounds": 4, "tail_k": 32, "cascade": False}


@dataclasses.dataclass
class WorkSet:
    """One configured (P, N, I, Z, G, ..., devices) working set to
    pre-lower. `devices` enumerates the shrunk-mesh ladder d, d-1,
    ..., 1; `cascade_forms` enumerates the cascade on/off program
    pair; `tail` configures the canonical tail form (None skips it);
    `guards` lowers the guarded fusion instead of the bare kernel."""

    sizes: Dict[str, int] = dataclasses.field(default_factory=dict)
    statics: Dict[str, Any] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_STATICS))
    devices: int = 1
    cascade_forms: Tuple[bool, ...] = (False, True)
    tail: Optional[Dict[str, Any]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_TAIL))
    guards: bool = False
    materialize_optional: bool = True


@dataclasses.dataclass
class ProgramSpec:
    """One enumerated (program, working-set point): a label, the
    manifest cache key, the AOT build thunk, and manifest metadata."""

    label: str
    key: str
    build: Callable[[], Any]
    meta: Dict[str, Any]


def _cycle_callable(guarded: bool):
    if guarded:
        from koordinator_tpu.scheduler import guards
        return guards.guarded_schedule_batch, "guarded_schedule_batch"
    return core.schedule_batch, "schedule_batch"


def enumerate_programs(ws: WorkSet,
                       fingerprint: Optional[str] = None
                       ) -> List[ProgramSpec]:
    """Walk the registry for every (mesh rung x program form) of the
    working set. Meshes are built over the first d visible devices —
    the same `jax.devices()[:d]` prefix the service's mesh-shrink rung
    rebuilds over."""
    from koordinator_tpu.parallel import mesh as meshlib

    if fingerprint is None:
        fingerprint = keys.contract_fingerprint()
    fn, fn_label = _cycle_callable(ws.guards)
    visible = jax.devices()
    max_d = max(min(ws.devices, len(visible)), 1)
    specs: List[ProgramSpec] = []
    for d in range(max_d, 0, -1):
        mesh = meshlib.make_mesh(visible[:d]) if d > 1 else None
        sizes = full_sizes(ws.sizes)
        if mesh is not None:
            # the mesh-shrink rung pads the node axis to the shrunk
            # mesh before resharding — enumerate the PADDED shape
            sizes["N"] = meshlib.padded_node_count(sizes["N"], mesh)
        mesh_axes = meshlib.mesh_axis_sizes(mesh) if mesh else None
        snap_sds = abstract_struct("ClusterSnapshot", sizes, mesh,
                                   ws.materialize_optional)
        pods_sds = abstract_struct("PodBatch", sizes, None,
                                   ws.materialize_optional)
        # on one device the service uploads a host batch as one buffer
        # (snapshot/hostbatch.py); the mesh rungs take it per leaf
        batch_sds = hostbatch.abstract(pods_sds) if mesh is None \
            else pods_sds
        cfg_sds = abstract_struct("LoadAwareConfig", sizes, None,
                                  ws.materialize_optional)
        for cascade in ws.cascade_forms:
            statics = dict(ws.statics, cascade=cascade)
            label = (f"{fn_label}/devices={d}/cascade="
                     f"{'on' if cascade else 'off'}")
            specs.append(ProgramSpec(
                label=label,
                key=keys.cache_key(
                    label, keys.abstract_digest(
                        (snap_sds, batch_sds, cfg_sds)),
                    statics, mesh_axes, fingerprint=fingerprint),
                build=functools.partial(
                    _build_cycle, fn, snap_sds, batch_sds, cfg_sds,
                    statics),
                meta={"form": "cycle", "devices": d,
                      "cascade": cascade, "sizes_P": sizes["P"],
                      "sizes_N": sizes["N"]}))
        if ws.tail is not None:
            tail_statics = dict(DEFAULT_TAIL, **ws.tail)
            tail_statics["tail_chunk"] = min(tail_statics["tail_chunk"],
                                             sizes["P"])
            counts_sds = tuple(getattr(pods_sds, f)
                               for f in core.COUNT_FIELDS)
            assign_sds = jax.ShapeDtypeStruct((sizes["P"],),
                                              np.dtype("int32"))
            label = f"tail_program/devices={d}"
            specs.append(ProgramSpec(
                label=label,
                key=keys.cache_key(
                    label, keys.abstract_digest(
                        (snap_sds, counts_sds, assign_sds, pods_sds,
                         cfg_sds)),
                    tail_statics, mesh_axes, fingerprint=fingerprint),
                build=functools.partial(
                    _build_tail, snap_sds, counts_sds, assign_sds,
                    pods_sds, cfg_sds, tail_statics),
                meta={"form": "tail", "devices": d,
                      "sizes_P": sizes["P"], "sizes_N": sizes["N"]}))
    return specs


def _build_cycle(fn, snap_sds, pods_sds, cfg_sds, statics):
    return fn.lower(snap_sds, pods_sds, cfg_sds, **statics).compile()


def _build_tail(snap_sds, counts_sds, assign_sds, pods_sds, cfg_sds,
                statics):
    return tail_program.lower(snap_sds, counts_sds, assign_sds,
                              pods_sds, cfg_sds, **statics).compile()


def warm(cache: CompileCache, ws: WorkSet, metrics=None,
         log_fn: Optional[Callable[[str], None]] = None) -> dict:
    """Pre-lower + AOT-compile the working set through the cache.
    Activates the cache (opt-in happened when the caller built one).
    Observes per-program wall time on `metrics.precompile_seconds`
    when a SchedulerMetrics catalog is passed."""
    cache.activate()
    report = {"programs": 0, "hit": 0, "warm": 0, "miss": 0,
              "seconds": 0.0}
    for spec in enumerate_programs(ws, fingerprint=cache.fingerprint):
        t0 = time.perf_counter()
        status = cache.ensure(spec.label, spec.build, key=spec.key,
                              meta=spec.meta)
        dt = time.perf_counter() - t0
        if metrics is not None:
            metrics.precompile_seconds.observe(dt)
        report["programs"] += 1
        report[status] += 1
        report["seconds"] += dt
        if log_fn is not None:
            log_fn(f"precompile: {status:<4s} {spec.label} "
                   f"({dt:.2f}s)")
    report["seconds"] = round(report["seconds"], 3)
    return report


# --- the service-side ensure ----------------------------------------------

def ensure_cycle_program(cache: CompileCache, snap, pods, cfg,
                         statics: Dict[str, Any], *, guarded: bool,
                         metrics=None) -> str:
    """Warm exactly the program the service is about to dispatch:
    abstract signature from the CONCRETE inputs (padded/sharded forms
    included), keyed like the enumerator. A dict lookup once warm —
    the ensure path costs one lower+compile per NEW working-set point
    and nothing after."""
    fn, fn_label = _cycle_callable(guarded)
    sds = abstract_from_example((snap, pods, cfg))
    key = keys.cache_key(fn_label, keys.abstract_digest(sds), statics,
                         mesh_axes_of(sds),
                         fingerprint=cache.fingerprint)
    status = cache.ensure(
        fn_label, functools.partial(_build_cycle, fn, *sds, statics),
        key=key, meta={"form": "cycle", "source": "service"})
    if metrics is not None:
        if status == "miss":
            metrics.compile_cache_misses.inc()
        else:
            metrics.compile_cache_hits.inc()
    return status
