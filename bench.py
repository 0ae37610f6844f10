"""North-star benchmark: score + bind 100k pending pods against a 10k-node
snapshot (BASELINE.md: < 2 s on a TPU v5e-4; this runs on however many chips
are visible — on >1 device the node axis is sharded over the mesh).

Prints one JSON line per measured config; the CANONICAL north-star line is
LAST:
  {"metric": ..., "value": <seconds>, "unit": "s", "vs_baseline": <2.0/value>}
Preceding lines (driver-captured per round, BENCH_EXTRAS=0 to skip): the
BASELINE config 2/3/4/5 paths (bench_configs.py) and the FULL-GATE flagship
run — the same 100k x 10k scale with every plugin gate compiled in (NUMA
binding, GPU pods, taints, spread, anti/affinity), the faithful analogue of
the reference hot loop running every registered plugin for every pod
(framework_extender.go:204-259).

Method: the pod queue lives on device as [num_chunks, CHUNK, ...] stacked
columns; ONE jitted program lax.scans the full scheduling pipeline over the
chunks — LoadAware filter+score over each [CHUNK, N] matrix, quota
admission, top-k commit with priority-ordered conflict resolution — carrying
the snapshot AND the topology (group x domain) counts between chunks, so
spread/anti/affinity placements in one chunk constrain the next (the
cross-batch count rule in core.domain_machinery). The full-gate paths
additionally run the Filter->Score gate cascade (scheduler/cascade.py,
BENCH_CASCADE overrides): a cheap stage-1 candidate mask prunes the pair
space before the heavy per-pair gates run, bit-identically. Stragglers are
retried device-side: tail passes pack the still-unplaced pod indices
(argsort), re-schedule them with more rounds and fall-through choices, and
scatter the results back into the assignment vector. The tail ADAPTS: at
least MIN_TAIL_PASSES always run, then passes repeat while the straggler
count improves or never-retried windows remain, bounded by
BENCH_MAX_TAIL_PASSES — no fixed retry-capacity cliff. The adaptive loop
itself is DEVICE-RESIDENT by default (core.tail_compaction_loop, a
lax.while_loop over the compacted retry batches): sweep + tail are one
program, and the only device->host transfers are the final assignment
readback (the bind log) and ONE packed stats vector after the tail —
regardless of straggler count. BENCH_TAIL_MODE=host keeps the previous
host-driven orchestration (one straggler-count readback per adaptive
decision) as the conformance oracle for A/B runs; every emitted line
records `cascade` and `tail_mode` so runs are self-describing.

Multichip flagship (promoted from the __graft_entry__ dryrun): with >1
visible device the node axis of the snapshot is sharded over the mesh
and the SAME chunked sweep + device tail runs under GSPMD — stage-1
masks stay shard-local, the top-k select merges per-shard candidates
over ICI, and the tail keeps its single packed stats readback.
BENCH_DEVICES=n pins the device count (the virtual CPU mesh in CI, a
slice on hardware); BENCH_MESH_PODS=m folds the devices into a 2D
pods x nodes mesh (parallel/mesh.py). Node counts indivisible by the
mesh are padded with provably-unschedulable zero-capacity rows
(parallel.pad_nodes_to_mesh), and multi-device lines additionally stamp
the mesh axis sizes. Placements are bit-identical to the single-device
program (exact top-k path) — tools/mesh_flagship_smoke.py and the slow
mesh conformance test pin it, placement-for-placement.

Warm-start + packing (round 11): every line stamps `compile_s` (summed
XLA compile-or-retrieve wall time of the warmup), `warm_start_s` (full
warmup wall), and `cache=cold|miss|hit` — cold means no persistent
cache is configured, miss means real compiles happened, hit means the
persistent cache served everything. On a TPU `main` keeps the
persistent cache where JAX_COMPILATION_CACHE_DIR says, else in
`<repo>/.jax_cache` (koordinator_tpu/compilecache); BENCH_PRECOMPILE=1 first warms the
enumerated working set through the contract-keyed manifest there
(koordinator_tpu/compilecache/precompile.py) so the measured run
starts warm; BENCH_PACK_SNAPSHOT=1 routes snapshot + batch through
the bf16 score-column round-trip (snapshot/packing.py) and stamps
`pack=bf16` + `pack_saved_bytes` — placements stay bit-identical (the
packing tests pin it), so A/B lines differ only in bandwidth.
"""

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# overridable for mesh smoke tests on small/virtual device counts; the
# driver-run configuration is the defaults
NUM_NODES = int(os.environ.get("BENCH_NODES", 10_000))
NUM_PODS = int(os.environ.get("BENCH_PODS", 100_000))
CHUNK = int(os.environ.get("BENCH_CHUNK", 2_000))
FULL_CHUNK = int(os.environ.get("BENCH_FULL_CHUNK", CHUNK))
MIN_TAIL_PASSES = 2   # always run (keeps the tail program warm)
DEFAULT_MAX_TAIL_PASSES = 6
# the narrower full-gate tail needs more adaptive passes to cover the
# same straggler pool (3160 at the 100k capture > 6 x 512)
FULL_GATE_MAX_TAIL_PASSES = 10


def max_tail_passes(full_gate: bool) -> int:
    """THE single parse of BENCH_MAX_TAIL_PASSES. It used to be read
    TWICE with different semantics — once at import into a module
    constant (so a value set after import was ignored by one reader)
    and once as a raw truthiness check at run_northstar (so an empty
    string crashed the import-time int() but flipped the run-time
    branch). One call-time parse: an explicit value wins verbatim on
    BOTH the slim and full-gate paths; unset or empty falls to the
    per-path default. Pinned by tests/test_bench_tail.py."""
    raw = (os.environ.get("BENCH_MAX_TAIL_PASSES") or "").strip()
    if raw:
        return max(int(raw), 0)
    return FULL_GATE_MAX_TAIL_PASSES if full_gate else DEFAULT_MAX_TAIL_PASSES


# Protocol note (round 4 -> 5): since round 4 the timed region includes the
# ADAPTIVE tail's host readbacks (round 3 ran a fixed TAIL_PASSES count with
# no mid-region sync), so cross-round comparisons against BENCH_r03 and
# earlier are not strictly apples-to-apples; `tail_passes` is recorded in
# every line so a reader can normalize.  Round 5 kept the adaptive
# semantics but batched the sweep + MIN-pass counts into ONE device->host
# transfer (each blocking scalar readback costs a full device round
# trip; round 4 paid five of them).  Round 6 moves the whole adaptive
# loop on device (core.tail_compaction_loop): the timed region now holds
# exactly ONE straggler-stats readback however many passes run, and
# `tail_mode` in every line says which protocol produced it.  The 2 s
# target itself is unchanged (BASELINE.json).
BASELINE_SECONDS = 2.0

def host_fields() -> dict:
    """Host fingerprint recorded in every bench line: these CI hosts
    live-migrate and resize mid-session (observed nproc 8 -> 1), and
    without cores/host in the artifact a degraded-host number is
    indistinguishable from a kernel regression (VERDICT r4 weak #3)."""
    from koordinator_tpu.utils.hostinfo import host_fields as hf
    return hf()


_COMPILE_CACHE = None


def compile_cache():
    """The contract-keyed manifest over the persistent compilation
    cache (koordinator_tpu/compilecache), activated once per process
    when BENCH_PRECOMPILE=1 asks for the warmer; None otherwise. Its
    directory is the one `enable_persistent_cache` chose."""
    global _COMPILE_CACHE
    if os.environ.get("BENCH_PRECOMPILE", "0") in ("0", "false", ""):
        return None
    if _COMPILE_CACHE is None:
        from koordinator_tpu.compilecache import CompileCache
        _COMPILE_CACHE = CompileCache().activate()
    return _COMPILE_CACHE


def ensure_platform() -> None:
    """Honor JAX_PLATFORMS; anything but an explicit `cpu` must find a
    TPU, or the run exits non-zero. There is no fallback: a number is
    only ever printed under the platform it was measured on."""
    plat = os.environ.get("JAX_PLATFORMS")
    if plat:
        jax.config.update("jax_platforms", plat)
    if plat == "cpu":
        return
    found = jax.devices()[0].platform
    if found != "tpu":
        raise SystemExit(f"bench: no TPU (jax.devices()[0] is {found!r}); "
                         "set JAX_PLATFORMS=cpu for a CPU run")


def run_northstar(full_gate: bool = False, num_pods: int = None,
                  num_nodes: int = None, chunk: int = None,
                  metric: str = None, degraded: str = None,
                  num_devices: int = None, recovered: str = None) -> dict:
    from koordinator_tpu.parallel import mesh as meshlib
    from koordinator_tpu.scheduler import core
    from koordinator_tpu.scheduler.plugins.loadaware import LoadAwareConfig
    from koordinator_tpu.utils import synthetic

    num_pods = NUM_PODS if num_pods is None else num_pods
    num_nodes = NUM_NODES if num_nodes is None else num_nodes
    if chunk is None:
        chunk = FULL_CHUNK if full_gate else CHUNK
    if num_pods % chunk:
        raise SystemExit(f"BENCH_PODS={num_pods} must be a multiple of "
                         f"the chunk size {chunk}")
    if full_gate:
        pods = synthetic.full_gate_pods(num_pods, num_nodes, seed=1,
                                        num_quotas=32)
        # gate-class prefix packing: ~17% of the workload carries a
        # spread/anti/aff term, ~11% is CPU-bind, ~10% requests
        # devices; packing each class into a (nested) static chunk
        # prefix shrinks the per-inner-step [P, P] machinery of the
        # topology, topology-manager and GPU gates quadratically
        # (core.schedule_batch topo/numa/gpu prefix contracts)
        pods, prefixes, masks = synthetic.pack_gate_prefixes(pods, chunk)
        topo_prefix, topo_mask = prefixes["topo"], masks["topo"]
        make_snap = functools.partial(synthetic.full_gate_cluster,
                                      num_nodes, num_quotas=32)
        metric = metric or "score_bind_100k_pods_10k_nodes_full_gate"
        step_kw = dict(enable_numa=True, enable_devices=True,
                       topo_prefix=topo_prefix,
                       dom_classes=synthetic.dom_classes(pods),
                       numa_prefix=prefixes["numa"],
                       gpu_prefix=prefixes["gpu"])
        # the numa_prefix contract needs a policy-free snapshot; checked
        # against the real cluster below (see after make_snap)
        tail_kw_override = dict(numa_prefix=None, gpu_prefix=None)
    else:
        topo_prefix, topo_mask = None, None
        tail_kw_override = {}
        pods = synthetic.synthetic_pods(num_pods, seed=1, num_quotas=32)
        make_snap = functools.partial(synthetic.synthetic_cluster,
                                      num_nodes, num_quotas=32)
        metric = metric or "score_bind_100k_pods_10k_nodes"
        # no pod in the slim workload requests CPU binding or devices —
        # the batched analogue of the reference's state.skip fast paths
        step_kw = dict(enable_numa=False)
    cfg = LoadAwareConfig.make()

    # --- device / mesh selection (the multichip flagship path) -----------
    # BENCH_DEVICES=n runs on the first n visible devices (the virtual
    # CPU mesh in CI, a real slice on hardware); unset = all visible.
    # BENCH_MESH_PODS=m folds the devices into a 2D (pods x nodes) mesh.
    devices = jax.devices()
    ndev_env = (os.environ.get("BENCH_DEVICES") or "").strip()
    if num_devices is not None:
        # an explicit count wins over the env: run_with_ladder's
        # device-lost rung retries on a SHRUNK device set
        ndev = int(num_devices)
        if not 1 <= ndev <= len(devices):
            raise SystemExit(f"num_devices={ndev} but "
                             f"{len(devices)} devices are visible")
        devices = devices[:ndev]
    elif ndev_env:
        ndev = int(ndev_env)
        if not 1 <= ndev <= len(devices):
            raise SystemExit(f"BENCH_DEVICES={ndev} but "
                             f"{len(devices)} devices are visible")
        devices = devices[:ndev]
    mesh_pods = int((os.environ.get("BENCH_MESH_PODS") or "1").strip())
    mesh = None
    if len(devices) > 1:
        # multi-chip: node columns sharded over the mesh (ICI); the pod
        # queue and quota/gang state replicate on the 1D node mesh and
        # shard over the pods axis on the 2D one. GSPMD turns the top-k
        # select into a shard-local reduce + cross-chip merge, and the
        # cascade's stage-1 mask stays shard-local (zero collectives —
        # tools/mesh_flagship_smoke.py pins that on the compiled HLO).
        mesh = meshlib.make_mesh(devices, pods_axis=mesh_pods)
        if mesh_pods > 1 and (num_pods % mesh_pods or chunk % mesh_pods):
            raise SystemExit(f"BENCH_MESH_PODS={mesh_pods} must divide "
                             f"both BENCH_PODS={num_pods} and the chunk "
                             f"{chunk}")
        # node counts indivisible by the mesh get zero-capacity pad rows
        # (provably unschedulable; excluded from the overcommit checks)
        n_pad = meshlib.padded_node_count(num_nodes, mesh)
        repl = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        snap_shardings = meshlib.snapshot_sharding(mesh)

        def put_snap(s):
            return meshlib.shard_snapshot(
                meshlib.pad_nodes_to_mesh(s, mesh), mesh)

        put_repl = functools.partial(jax.device_put, device=repl)
        if mesh_pods > 1:
            put_batch = functools.partial(meshlib.shard_batch, mesh=mesh)
            put_stacked = functools.partial(
                jax.device_put,
                device=jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(
                        None, meshlib.POD_AXIS)))
        else:
            put_batch = put_repl
            put_stacked = put_repl
        # the batch's node-indexed domain matrices follow the padded
        # snapshot (pad columns are -1 = "node lacks the key")
        pods = meshlib.pad_batch_nodes(pods, n_pad)
    else:
        put_snap = jax.device_put
        put_repl = jax.device_put
        put_batch = jax.device_put
        put_stacked = jax.device_put

    # bf16 columnar packing (snapshot/packing.py): quantize the
    # score/metric columns through the packed representation, so the
    # run measures exactly the values a packed snapshot feeds the
    # kernels; placements stay bit-identical to the f32 oracle
    # (tests/test_packing.py) and the line stamps `pack` + the bytes
    # the packed layout saves
    pack_on = os.environ.get("BENCH_PACK_SNAPSHOT", "0") \
        not in ("0", "false", "")
    if pack_on:
        from koordinator_tpu.snapshot import packing
        pods = packing.roundtrip_pods(pods)

    # the queue as [C, CHUNK, ...] per-pod columns (scan operand)
    stacked = synthetic.stack_pod_chunks(pods, chunk)

    def checked_snap(seed):
        """Build a snapshot and enforce the numa_prefix contract on THE
        snapshot being scheduled (every seed, not just warmup): a
        policy node would engage pods beyond the prefix whose gates
        were sliced away."""
        snap_host = make_snap(seed=seed)
        if full_gate and step_kw.get("numa_prefix") is not None \
                and np.asarray(snap_host.nodes.numa_policy).any():
            raise ValueError("numa_prefix needs a policy-free snapshot "
                             "(core.schedule_batch contract)")
        if pack_on:
            from koordinator_tpu.snapshot import packing
            snap_host = packing.roundtrip_snapshot(snap_host)
        return snap_host

    snap0 = put_snap(checked_snap(0))
    stacked = put_stacked(stacked)
    pods_dev = put_batch(pods)
    cfg = put_repl(cfg)
    counts0 = put_repl(tuple(getattr(pods, f) for f in core.COUNT_FIELDS))

    # Candidate selection defaults to EXACT lax.top_k since round 5:
    # the hardware capture measured exact FASTER than approx_max_k at
    # the canonical shape (0.980 s vs 1.082 s, same session, fuller
    # placements at no recall loss), so the partial reduction buys
    # nothing here — k=8..32 over 10k columns is far below the regime
    # approx_max_k targets. BENCH_APPROX=1 re-enables it for
    # comparison runs (tests/test_approx_topk.py pins the quality
    # bound either way; on CPU both lower to the exact reduction), and
    # every emitted line records which mode ran.
    approx = os.environ.get("BENCH_APPROX", "0") not in ("0", "false", "")
    # sweep/tail shape knobs, hardware-sweepable without code edits
    # (defaults = the recorded protocol): rounds scale the per-chunk
    # [P, N] matrix cost, k the inner fall-through steps, and CHUNK the
    # quadratic [P, P] prefix machinery
    rounds = int(os.environ.get("BENCH_ROUNDS", "2"))
    kch = int(os.environ.get("BENCH_K", "8"))
    tail_rounds = int(os.environ.get("BENCH_TAIL_ROUNDS", "4"))
    tail_k = int(os.environ.get("BENCH_TAIL_K", "32"))
    # the Filter->Score gate cascade: ON by default for the full-gate
    # paths (where the heavy per-pair gates it narrows exist), off on
    # the slim path so the canonical protocol stays byte-stable;
    # BENCH_CASCADE overrides either way. cascade=False is the
    # conformance oracle — placements are bit-identical (test_cascade).
    cascade_env = os.environ.get("BENCH_CASCADE")
    cascade_on = (full_gate if cascade_env is None
                  else cascade_env not in ("0", "false", ""))
    # tail orchestration: "device" = the lax.while_loop compaction loop
    # (one straggler-stats readback total); "host" = the previous
    # per-pass host-driven loop, kept as the conformance oracle
    tail_mode = (os.environ.get("BENCH_TAIL_MODE") or "device").strip()
    if tail_mode not in ("device", "host"):
        raise SystemExit(f"BENCH_TAIL_MODE={tail_mode!r}: "
                         "must be 'device' or 'host'")
    step = functools.partial(core.schedule_batch, num_rounds=rounds,
                             k_choices=kch,
                             score_dims=(0, 1), approx_topk=approx,
                             tie_break=True, quota_depth=2,
                             fit_dims=(0, 1, 2, 3), cascade=cascade_on,
                             **step_kw)
    # the tail's retry batches are gathered device-side, so only the
    # topo contract (budgeted selection below) can be re-established
    # there — the numa/gpu prefixes apply to the host-packed sweep only
    tail_step = functools.partial(core.schedule_batch,
                                  num_rounds=tail_rounds,
                                  k_choices=tail_k, score_dims=(0, 1),
                                  approx_topk=approx, tie_break=True,
                                  quota_depth=2, fit_dims=(0, 1, 2, 3),
                                  cascade=cascade_on,
                                  **dict(step_kw, **tail_kw_override))
    # tail retry width, decoupled from the sweep chunk: stragglers
    # don't need a sweep-wide retry program (the [P, P] prefix
    # machinery scales quadratically with this width); smaller widths
    # trade more adaptive passes (one readback each) for much cheaper
    # passes. Both paths default to 512: the full-gate's heavy gate
    # set makes a 2000-wide pass ~16x a 512-wide one (20k x 2k CPU:
    # 9.1 s -> 5.8 s), and the canonical's ~510 stragglers fit inside
    # the two MANDATORY passes either way (captured 501-516 at 100k),
    # so the slim path pays no extra readbacks for a ~15% CPU-measured
    # saving (3.5 s -> 2.2 s at 20k x 2k). A non-default width is
    # stamped into the emitted line as a knob.
    default_tail = min(chunk, 512)
    tail_chunk = max(min(int(os.environ.get("BENCH_TAIL_CHUNK",
                                            default_tail)),
                         num_pods), 1)
    max_tail = max_tail_passes(full_gate)
    if topo_mask is not None:
        topo_mask = put_repl(jnp.asarray(topo_mask))

    def charge_all(counts, batch, assignment):
        """Thread placed topology charges into the carried counts (the
        cross-batch count rule, core.charge_all_counts; no-op
        compile-out on the slim path)."""
        if not full_gate:
            return counts
        return core.charge_all_counts(counts, batch, assignment)

    def with_counts(batch, counts):
        return batch.replace(**dict(zip(core.COUNT_FIELDS, counts)))

    def run_sweep(snap, counts, stacked, pods_dev, cfg):
        def body(carry, cols):
            snap, counts = carry
            # selector_match and the (group x domain) matrices are
            # batch-global; every per-pod column comes from the chunk
            batch = with_counts(pods_dev.replace(**cols), counts)
            res = step(snap, batch, cfg)
            counts = charge_all(counts, batch, res.assignment)
            return (res.snapshot, counts), res.assignment
        (snap, counts), assign = jax.lax.scan(body, (snap, counts),
                                              stacked)
        return snap, counts, assign.reshape(-1)

    # on a mesh the jitted programs pin their output placements (the
    # carried snapshot stays node-sharded across chunks/passes instead
    # of wherever GSPMD's cost model lands it; donation then aliases
    # shard-for-shard): (snap, counts, assign[, stats/tried]) outputs
    if mesh is not None:
        counts_sh = tuple(repl for _ in core.COUNT_FIELDS)
        sweep_jit = functools.partial(
            jax.jit, donate_argnums=(0, 1),
            out_shardings=(snap_shardings, counts_sh, repl))
        tail4_out = (snap_shardings, counts_sh, repl, repl)
        sweep_tail_jit = functools.partial(
            jax.jit, donate_argnums=(0, 1), out_shardings=tail4_out)
        tail_pass_jit = functools.partial(
            jax.jit, donate_argnums=(0, 1, 2, 3), out_shardings=tail4_out)
    else:
        sweep_jit = functools.partial(jax.jit, donate_argnums=(0, 1))
        sweep_tail_jit = sweep_jit
        tail_pass_jit = functools.partial(jax.jit,
                                          donate_argnums=(0, 1, 2, 3))

    @sweep_jit
    def sweep(snap, counts, stacked, pods_dev, cfg):
        return run_sweep(snap, counts, stacked, pods_dev, cfg)

    @sweep_tail_jit
    def sweep_and_tail(snap, counts, stacked, pods_dev, cfg):
        """tail_mode=device: sweep + the adaptive tail compaction loop
        (core.tail_compaction_loop, a lax.while_loop over compacted
        retry batches) are ONE program — stragglers are gathered,
        retried, and scattered back entirely on device, and the host
        reads back a single packed stats vector after the loop."""
        snap, counts, assign = run_sweep(snap, counts, stacked,
                                         pods_dev, cfg)
        return core.tail_compaction_loop(
            tail_step, snap, counts, assign, pods_dev, cfg,
            tail_chunk=tail_chunk, min_passes=MIN_TAIL_PASSES,
            max_passes=max_tail, charge_counts=full_gate,
            topo_prefix=topo_prefix, topo_mask=topo_mask)

    @tail_pass_jit
    def tail_pass(snap, counts, assign, tried, pods_dev, cfg):
        """tail_mode=host: one retry pass (core.tail_pass — the same
        gather/compact/retry/scatter program the device loop runs, so
        host mode is the conformance oracle for it). Selection and
        budgeted-constrained semantics live in core.tail_select."""
        return core.tail_pass(
            tail_step, snap, counts, assign, tried, pods_dev, cfg,
            tail_chunk=tail_chunk, charge_counts=full_gate,
            topo_prefix=topo_prefix, topo_mask=topo_mask)

    @jax.jit
    def pass_stats(assign, tried, pods_dev):
        """[left, never_retried] as ONE device array: one transfer per
        adaptive decision instead of two device round trips. The
        post-sweep count reuses it with an all-false `tried` so a
        single program serves every readback site."""
        bad = pods_dev.valid & (assign < 0)
        return jnp.stack([bad.sum(), (bad & ~tried).sum()])

    def full_pass(snap, counts):
        if tail_mode == "device":
            snap, counts, assign, stats = sweep_and_tail(
                snap, counts, stacked, pods_dev, cfg)
            # the run's ONE straggler-count readback, after the whole
            # adaptive loop ([after_sweep, final, never_retried,
            # passes] packed); the assignment transfer is the bind log
            stats = np.asarray(stats)
            return (snap, counts, np.asarray(assign), int(stats[0]),
                    int(stats[1]), int(stats[2]), int(stats[3]))
        # tail_mode=host — the previous protocol, kept as the
        # conformance oracle. The sweep and the MIN mandatory tail
        # passes are issued back-to-back with NO host readback between
        # them: each blocking scalar transfer pays a full device
        # round trip, and five of them inside the timed region more
        # than doubled the round-4 canonical time. All the counts the adaptive decision needs
        # are stacked device-side and read in ONE transfer after the
        # mandatory passes.
        snap, counts, assign = sweep(snap, counts, stacked, pods_dev, cfg)
        tried = jnp.zeros((num_pods,), bool)
        pair_hist = [pass_stats(assign, tried, pods_dev)]
        passes = 0
        # the mandatory passes honor the MAX cap too (BENCH_MAX_TAIL_PASSES
        # below MIN is a legitimate quick-run knob)
        for _ in range(min(MIN_TAIL_PASSES, max_tail)):
            snap, counts, assign, tried = tail_pass(
                snap, counts, assign, tried, pods_dev, cfg)
            passes += 1
            # pass_stats is the SAME program the adaptive loop reads, so
            # the mandatory passes keep it warm — no cold compile can
            # land inside the adaptive region
            pair_hist.append(pass_stats(assign, tried, pods_dev))
        stats = np.asarray(jnp.concatenate(pair_hist))
        left_after_sweep = int(stats[0])
        hist = [int(x) for x in stats[2::2]]
        left = hist[-1] if hist else left_after_sweep
        prev = hist[-2] if passes >= 2 else left_after_sweep
        improved = left < prev
        never_retried = int(stats[2 * passes + 1])
        # passes continue while the straggler count improves OR fresh
        # (never-retried) windows remain — a pass that placed nothing
        # must not strand disjoint windows that were never tried. Only
        # the MAX cap can leave never_retried > 0.
        while (passes < max_tail and left > 0
               and (improved or never_retried > 0)):
            snap, counts, assign, tried = tail_pass(
                snap, counts, assign, tried, pods_dev, cfg)
            passes += 1
            # the oracle's per-pass blocking readback IS the cost the
            # device loop deletes (koordlint HS006 guards the bug
            # class; this one marked instance is the measured baseline)
            pair = np.asarray(  # koordlint: disable=HS006
                pass_stats(assign, tried, pods_dev))
            new_left, never_retried = int(pair[0]), int(pair[1])
            improved = new_left < left
            left = new_left
        # final device->host transfer: the bind log
        return (snap, counts, np.asarray(assign), left_after_sweep,
                left, never_retried, passes)

    # BENCH_COST=1: static cost stamps for the flagship program this
    # line actually runs (obs/costmodel.py over the SAME jitted
    # callable) — flops, bytes accessed, static HBM peak, flops/pod.
    # Opt-in because it pays one extra AOT lower+compile of the
    # flagship (the persistent cache absorbs it when configured);
    # lowering happens BEFORE the warmup so the donated buffers are
    # still live to trace against.
    cost_stamp = {}
    if os.environ.get("BENCH_COST", "0") not in ("0", "false", ""):
        from koordinator_tpu.obs import costmodel
        cost_target = sweep_and_tail if tail_mode == "device" else sweep
        cost_compiled = cost_target.lower(snap0, counts0, stacked,
                                          pods_dev, cfg).compile()
        stamp = costmodel.flagship_stamp(cost_compiled, num_pods)
        cost_stamp = {
            "flops": stamp["flops"],
            "bytes_accessed": stamp["bytes_accessed"],
            "hbm_peak_bytes": stamp["hbm_peak_bytes"],
            "flops_per_pod": round(stamp["flops_per_pod"], 1),
        }
        del cost_compiled

    # warmup/compile (sweep + tail always run at least MIN passes — no
    # cold path in the timed region regardless of the warm data). The
    # compile watcher around it feeds the warm-start stamps: what
    # compilation (or persistent-cache retrieval) cost this line, and
    # whether the opt-in compile cache served it
    pack_stats = None
    if pack_on:
        from koordinator_tpu.snapshot import packing
        pack_stats = packing.packed_savings(snap0, pods)
    from koordinator_tpu.compilecache import counters as compile_counters
    warm_t0 = time.perf_counter()
    with compile_counters.watch() as warm_watch:
        out = full_pass(snap0, counts0)
    warm_start_s = time.perf_counter() - warm_t0
    del out
    if not jax.config.jax_compilation_cache_dir:
        cache_status = "cold"     # no persistent cache configured
    elif warm_watch.cache_misses == 0:
        cache_status = "hit"      # every program retrieved, zero compiles
    else:
        cache_status = "miss"     # at least one real XLA compile

    # timed steady-state pass on a fresh snapshot
    snap1 = put_snap(checked_snap(7))
    counts1 = put_repl(tuple(getattr(pods, f) for f in core.COUNT_FIELDS))
    t0 = time.perf_counter()
    (snap, counts, assign, left_after_sweep, left_final, never_retried,
     passes) = full_pass(snap1, counts1)
    elapsed = time.perf_counter() - t0

    placed = int((assign >= 0).sum())
    if never_retried > 0:
        # every straggler should get at least one retry before the
        # adaptive loop gives up — surface any that never did
        print(f"bench: WARNING: {never_retried} stragglers were never "
              f"retried after {passes} adaptive tail passes "
              f"(tail_chunk={tail_chunk}); raise BENCH_MAX_TAIL_PASSES",
              file=sys.stderr)
    # non-default shape knobs are stamped into the line: a sweep run
    # must never be mistaken for the canonical protocol (the module
    # protocol note relies on every variable being readable off the
    # line)
    knob_tags = {}
    for name, val, default in (("rounds", rounds, 2), ("k", kch, 8),
                               ("tail_rounds", tail_rounds, 4),
                               ("tail_k", tail_k, 32),
                               ("tail_chunk", tail_chunk, default_tail),
                               # 2000 is the PROTOCOL chunk (BASELINE);
                               # smoke/sweep shapes stamp their width
                               ("chunk", chunk, 2000)):
        if val != default:
            knob_tags[name] = val
    result = {
        "metric": metric,
        "value": round(elapsed, 4),
        "unit": "s",
        **({"knobs": knob_tags} if knob_tags else {}),
        "vs_baseline": round(BASELINE_SECONDS / elapsed, 2),
        "pods_per_sec": round(num_pods / elapsed),
        "placed": placed,
        "stragglers_after_sweep": left_after_sweep,
        "stragglers_final": left_final,
        "never_retried": never_retried,
        "tail_passes": passes,
        "approx_topk": approx,
        # A/B protocol knobs, stamped on EVERY line (not only when
        # non-default): a cascade-off or host-tail run must be
        # self-describing without consulting the code's defaults
        "cascade": cascade_on,
        "tail_mode": tail_mode,
        # warm-start stamps (every line): wall time of the warmup pass
        # (trace + compile-or-retrieve + one untimed execution), the
        # XLA compile-or-retrieve seconds inside it, and whether the
        # persistent compilation cache served it — "cold" = no cache
        # configured, "hit" = zero compiles
        "compile_s": round(warm_watch.compile_seconds, 4),
        "warm_start_s": round(warm_start_s, 4),
        "cache": cache_status,
        # present ONLY on a BENCH_COST=1 run: static cost/memory of the
        # flagship program this line ran (obs/costmodel.py) — joins the
        # measured trajectory to the AOT cost model
        **cost_stamp,
        # present ONLY on a bf16-packed run (BENCH_PACK_SNAPSHOT): the
        # kernels consumed packed score/metric columns and the line
        # says what the packed layout saves on the wire
        **({"pack": "bf16",
            "pack_saved_bytes": pack_stats["bytes_saved"]}
           if pack_stats is not None else {}),
        # present ONLY on a run the bench ladder re-ran degraded
        # (run_with_ladder): the classified failure class + the retried
        # chunk, so a degraded number can never pass as the protocol
        **({"degraded": degraded} if degraded else {}),
        # present ONLY after the ladder recovered a DEVICE_LOST run on
        # a shrunk device set (the bench mirror of the service's
        # mesh-shrink rung); `devices`/`mesh` below then carry the
        # SHRUNK size, so the line is self-describing
        **({"recovered": recovered} if recovered else {}),
        "devices": len(devices),
        # the mesh stamp makes a 4-device line self-describing (1x4 vs
        # 2x2); absent on single-device lines so trajectories stay
        # byte-comparable with earlier rounds
        **({"mesh": meshlib.mesh_axis_sizes(mesh)}
           if mesh is not None else {}),
        "platform": devices[0].platform,
        **host_fields(),
    }
    print(json.dumps(result))
    # non-serialized conformance surfaces (tests + the CI mesh smoke
    # compare sharded placements against the single-device oracle and
    # check the overcommit invariant on the real rows): attached AFTER
    # the line is emitted so the artifact stays line-parseable
    result["arrays"] = {
        "assignment": assign,
        "requested": np.asarray(snap.nodes.requested),
        "allocatable": np.asarray(snap.nodes.allocatable),
        "num_nodes": num_nodes,
    }
    return result


def run_with_ladder(max_halvings: int = 2, _run=None, **kw) -> dict:
    """The bench's rung of the degradation ladder: a run whose failure
    classifies as RESOURCE_EXHAUSTED retries with the chunk halved (up
    to `max_halvings` times) and the retried line carries a `degraded`
    stamp (failure class + the chunk that survived); one that
    classifies as DEVICE_LOST retries on a device set shrunk by one —
    the bench mirror of the service's mesh-shrink rung — and the
    retried line carries a `recovered` stamp plus the shrunk
    `devices`/`mesh` size. Either way a non-protocol number is
    self-describing and can never pass as the canonical protocol. Any
    other failure class propagates — the caller's evidence guards own
    those. `_run` is the injectable run function (tests)."""
    from koordinator_tpu.scheduler.errorhandler import (
        FailureClass,
        classify_failure,
    )

    run = _run if _run is not None else run_northstar
    chunk = kw.pop("chunk", None)
    num_devices = kw.pop("num_devices", None)
    degraded = None
    recovered = None
    for retries in range(max_halvings + 1):
        try:
            return run(chunk=chunk, degraded=degraded,
                       num_devices=num_devices, recovered=recovered,
                       **kw)
        except Exception as exc:
            fc = classify_failure(exc)
            cur = chunk if chunk is not None \
                else (FULL_CHUNK if kw.get("full_gate", False) else CHUNK)
            cur_dev = num_devices if num_devices is not None \
                else int((os.environ.get("BENCH_DEVICES") or "").strip()
                         or len(jax.devices()))
            if retries == max_halvings:
                raise
            if fc is FailureClass.RESOURCE_EXHAUSTED and cur >= 2:
                chunk = cur // 2
                degraded = f"{fc.value}:chunk={chunk}"
                print(f"bench: {fc.value}; retrying with chunk {cur} "
                      f"-> {chunk}", file=sys.stderr)
            elif fc is FailureClass.DEVICE_LOST and cur_dev >= 2:
                num_devices = cur_dev - 1
                recovered = f"{fc.value}:devices={num_devices}"
                print(f"bench: {fc.value}; retrying on {cur_dev} -> "
                      f"{num_devices} device(s)", file=sys.stderr)
            else:
                # out of rungs (or an unabsorbable class): the REAL
                # exception propagates, never a synthetic stand-in
                raise


def main():
    if jax.devices()[0].platform == "tpu":
        # the persistent cache only on the chip: XLA:CPU artifacts can
        # segfault when read back on another host (compilecache)
        from koordinator_tpu.compilecache import enable_persistent_cache

        enable_persistent_cache()
    cache = compile_cache()
    if cache is not None:
        # BENCH_PRECOMPILE=1: run the AOT warmer BEFORE any measured
        # line, so the registry-enumerated flagship programs (service
        # cycle + tail forms) are persisted and a service starting
        # against the same cache warm-starts
        from koordinator_tpu.compilecache import precompile
        report = precompile.warm(
            cache, precompile.WorkSet(devices=len(jax.devices())))
        print(f"bench: precompile warmed {report['programs']} "
              f"program(s) in {report['seconds']:.1f}s "
              f"(hit={report['hit']} warm={report['warm']} "
              f"miss={report['miss']})", file=sys.stderr)
    if os.environ.get("BENCH_EXTRAS", "1") not in ("0", "false", ""):
        # BASELINE configs 1-5 + the full-gate flagship, driver-captured
        # per round (VERDICT r3: self-reported tables don't count)
        import bench_configs
        bench_configs.config_1_spark()
        bench_configs.config_2_numa()
        bench_configs.config_3_gangs()
        bench_configs.config_4_quota()
        bench_configs.config_5_descheduler()
        run_with_ladder(full_gate=True)
    # the canonical north-star line, LAST (ladder-wrapped: an OOM on a
    # smaller-memory host retries with the chunk halved and the line
    # stamps `degraded` instead of recording nothing for the round)
    run_with_ladder(full_gate=False)


if __name__ == "__main__":
    ensure_platform()
    main()
