"""The Filter->Score gate cascade + device-resident tail equivalence
suite (scheduler/cascade.py, ops/feasibility.py, core.tail_*).

Two conformance oracles, both pinned BIT-identical:
- `cascade=False` is the oracle for `cascade=True`: stage 1 folds only
  pairs the exact round gates would reject anyway (monotone batch-start
  state), and stage 2's prefix-narrowed heavy gates are pass-through
  beyond the packing prefixes — so placements, scores, and the whole
  post-commit snapshot must match exactly.
- the host-driven tail orchestration (`_host_tail` below) is the
  oracle for `core.tail_compaction_loop`: the device lax.while_loop
  runs the SAME `core.tail_pass` under the same retry-budget semantics,
  so final placements, pass counts, and straggler stats must match —
  with the host paying one readback per adaptive decision and the
  device loop exactly one at the end.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu.ops import feasibility
from koordinator_tpu.scheduler import cascade, core
from koordinator_tpu.scheduler.plugins.loadaware import LoadAwareConfig
from koordinator_tpu.utils import synthetic

P, N, CHUNK = 512, 96, 256

KW = dict(num_rounds=2, k_choices=8, score_dims=(0, 1), tie_break=True,
          quota_depth=2, fit_dims=(0, 1, 2, 3), enable_numa=True,
          enable_devices=True)


def _sparse_workload(seed=1):
    """Full-gate pods whose constrained classes stay WELL below the
    chunk width, so the packed prefixes are proper (< CHUNK) and the
    cascade's narrowed heavy gates actually slice (a workload whose
    prefixes equal the chunk would vacuously pass the equivalence)."""
    pods = synthetic.full_gate_pods(P, N, seed=seed, num_quotas=8,
                                    num_gangs=8, n_anti_groups=4,
                                    anti_members=8, n_aff_groups=2,
                                    aff_members=6, spread_frac=0.08,
                                    numa_bind_frac=0.12,
                                    gpu_pod_frac=0.08)
    packed, prefixes, masks = synthetic.pack_gate_prefixes(pods, CHUNK)
    assert prefixes["numa"] < CHUNK and prefixes["gpu"] < CHUNK
    return packed, prefixes, masks


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_results_equal(a, b):
    for f in core.PER_POD_RESULT_FIELDS + ("gang_failed",):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)
    _assert_trees_equal(a.snapshot, b.snapshot)


def test_cascade_on_off_bit_identical_full_gate():
    """The acceptance pin: cascade on vs off on the full-gate fixture
    cluster, with every packing contract engaged so both cascade layers
    (stage-1 mask AND narrowed heavy gates) are exercised."""
    pods, prefixes, _ = _sparse_workload()
    snap = synthetic.full_gate_cluster(N, seed=0, num_quotas=8,
                                       num_gangs=8)
    cfg = LoadAwareConfig.make()
    kw = dict(KW, topo_prefix=prefixes["topo"],
              dom_classes=synthetic.dom_classes(pods),
              numa_prefix=prefixes["numa"], gpu_prefix=prefixes["gpu"])
    batch = synthetic.slice_batch(pods, 0, CHUNK)
    off = core.schedule_batch(snap, batch, cfg, cascade=False, **kw)
    on = core.schedule_batch(snap, batch, cfg, cascade=True, **kw)
    _assert_results_equal(off, on)
    assert int((on.assignment >= 0).sum()) > 0


def test_cascade_across_carried_chunks():
    """Chunked scheduling with carried topology counts (the bench sweep
    contract): both modes must agree chunk by chunk AND leave identical
    carried counts."""
    pods, prefixes, _ = _sparse_workload(seed=5)
    snap_a = synthetic.full_gate_cluster(N, seed=4, num_quotas=8,
                                         num_gangs=8)
    snap_b = snap_a
    cfg = LoadAwareConfig.make()
    kw = dict(KW, topo_prefix=prefixes["topo"],
              dom_classes=synthetic.dom_classes(pods),
              numa_prefix=prefixes["numa"], gpu_prefix=prefixes["gpu"])
    counts_a = tuple(jnp.asarray(getattr(pods, f))
                     for f in core.COUNT_FIELDS)
    counts_b = counts_a
    for s in range(0, P, CHUNK):
        batch = synthetic.slice_batch(pods, s, CHUNK)
        batch_a = batch.replace(**dict(zip(core.COUNT_FIELDS, counts_a)))
        batch_b = batch.replace(**dict(zip(core.COUNT_FIELDS, counts_b)))
        res_a = core.schedule_batch(snap_a, batch_a, cfg, cascade=False,
                                    **kw)
        res_b = core.schedule_batch(snap_b, batch_b, cfg, cascade=True,
                                    **kw)
        np.testing.assert_array_equal(np.asarray(res_a.assignment),
                                      np.asarray(res_b.assignment))
        counts_a = core.charge_all_counts(counts_a, batch_a,
                                          res_a.assignment)
        counts_b = core.charge_all_counts(counts_b, batch_b,
                                          res_b.assignment)
        snap_a, snap_b = res_a.snapshot, res_b.snapshot
    for a, b in zip(counts_a, counts_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stage1_mask_is_sound():
    """Every placement the full machinery produces survives the
    stage-1 mask — the prune removes only provably-dead pairs — and a
    quota already at its ceiling kills its pods' rows. (Reuses the
    packed program the equivalence tests compiled: the mask contract is
    the same either way, and a fresh full-width compile would buy no
    coverage.)"""
    pods, prefixes, _ = _sparse_workload(seed=7)
    snap = synthetic.full_gate_cluster(N, seed=6, num_quotas=8,
                                       num_gangs=8)
    cfg = LoadAwareConfig.make()
    kw = dict(KW, topo_prefix=prefixes["topo"],
              dom_classes=synthetic.dom_classes(pods),
              numa_prefix=prefixes["numa"], gpu_prefix=prefixes["gpu"])
    batch = synthetic.slice_batch(pods, 0, CHUNK)
    static_ok, _ = cascade.static_gates(snap.nodes, batch, cfg)
    mask = np.asarray(cascade.stage1_mask(snap, batch, static_ok,
                                          fit_dims=(0, 1, 2, 3),
                                          quota_depth=2))
    res = core.schedule_batch(snap, batch, cfg, cascade=False, **kw)
    assign = np.asarray(res.assignment)
    slot = np.asarray(res.res_slot)
    # reservation-slot placements are exempt by contract (consumers
    # draw from the slot's hold, not the node's open pool)
    node_placed = (assign >= 0) & (slot < 0)
    rows = np.flatnonzero(node_placed)
    assert rows.size > 0
    assert mask[rows, assign[rows]].all()

    # exhausted quota: used == runtime at the pod's own level -> the
    # whole row dies in the ceiling gate
    q = snap.quotas
    used = np.asarray(q.used).copy()
    qid = int(np.asarray(batch.quota_id)[0])
    assert qid >= 0
    used[qid] = np.asarray(q.runtime)[qid]
    ok = np.asarray(feasibility.quota_ceiling_ok(
        q.replace(used=used), batch, quota_depth=2,
        fit_dims=(0, 1, 2, 3)))
    hit = np.asarray(batch.quota_id) == qid
    req = np.asarray(batch.requests)[:, :4]
    # only dims with a FINITE runtime can hit the ceiling (batch-tier
    # dims carry runtime inf in this tree and legitimately pass)
    finite = np.isfinite(np.asarray(q.runtime)[qid][:4])
    blocked = hit & (req[:, finite] > 0.5).any(axis=1)
    assert blocked.any()
    assert not ok[blocked].any()
    assert ok[~hit].all()


def _overcommitted_tail_setup(seed=2, n_nodes=16):
    """A tight cluster with EVERYTHING still unplaced: the tail loop
    doesn't care how the straggler pool arose, so starting from
    assign = -1 skips a sweep compile the fixture would otherwise pay.
    512 pods against 16 nodes overcommits hard enough that the pool
    stops improving before it drains — the adaptive stop path."""
    snap = synthetic.full_gate_cluster(n_nodes, seed=0, num_quotas=8,
                                       num_gangs=8)
    pods = synthetic.full_gate_pods(P, n_nodes, seed=seed, num_quotas=8,
                                    num_gangs=8)
    packed, prefixes, masks = synthetic.pack_gate_prefixes(pods, CHUNK)
    cfg = LoadAwareConfig.make()
    counts = tuple(jnp.asarray(getattr(packed, f))
                   for f in core.COUNT_FIELDS)
    assign = jnp.full((P,), -1, jnp.int32)
    left0 = int(np.asarray(packed.valid).sum())
    assert left0 > 0
    return snap, counts, assign, packed, masks, cfg, left0


def _blocking_stats(valid, assign, tried):
    """The oracle's per-pass host readback (the deliberate cost the
    device loop deletes; koordlint HS006 flags it in package code)."""
    bad = valid & (np.asarray(assign) < 0)
    return int(bad.sum()), int((bad & ~np.asarray(tried)).sum())


def _host_tail(tail_step, snap, counts, assign, pods, cfg, *,
               tail_chunk, min_passes, max_passes, topo_prefix=None,
               topo_mask=None):
    """The host-driven tail orchestration:
    mandatory passes, then adaptive passes while the count improves or
    never-retried windows remain — one readback per decision."""
    valid = np.asarray(pods.valid)
    left0 = int((valid & (np.asarray(assign) < 0)).sum())
    tried = jnp.zeros((pods.valid.shape[0],), bool)
    passes, hist = 0, []
    for _ in range(min(min_passes, max_passes)):
        snap, counts, assign, tried = core.tail_pass(
            tail_step, snap, counts, assign, tried, pods, cfg,
            tail_chunk=tail_chunk, topo_prefix=topo_prefix,
            topo_mask=topo_mask)
        passes += 1
        hist.append(_blocking_stats(valid, assign, tried))
    left = hist[-1][0] if hist else left0
    prev = hist[-2][0] if passes >= 2 else left0
    improved = left < prev
    nr = hist[-1][1] if hist else left0
    while passes < max_passes and left > 0 and (improved or nr > 0):
        snap, counts, assign, tried = core.tail_pass(
            tail_step, snap, counts, assign, tried, pods, cfg,
            tail_chunk=tail_chunk, topo_prefix=topo_prefix,
            topo_mask=topo_mask)
        passes += 1
        new_left, nr = _blocking_stats(valid, assign, tried)
        improved = new_left < left
        left = new_left
    return snap, counts, assign, (left0, left, nr, passes)


def test_device_tail_matches_host_tail():
    """core.tail_compaction_loop (lax.while_loop, one stats readback)
    vs the host-driven orchestration: identical final placements,
    snapshots, and [after_sweep, final, never_retried, passes] stats.
    Runs WITH the budgeted constrained (topo_prefix) selection — the
    superset of the plain path; one loop compile instead of two keeps
    the suite tier-1 fast. This test alone pins the budget cap: the
    never-retried count is one of the compared stats."""
    snap, counts, assign, packed, masks, cfg, left0 = \
        _overcommitted_tail_setup()
    tail_step = functools.partial(core.schedule_batch, num_rounds=4,
                                  k_choices=8, score_dims=(0, 1),
                                  tie_break=True, quota_depth=2,
                                  fit_dims=(0, 1, 2, 3),
                                  enable_numa=True, enable_devices=True)
    topo_kw = dict(topo_prefix=48, topo_mask=jnp.asarray(masks["topo"]))
    # max_passes=3 walks every control edge (mandatory, adaptive
    # continue, budget stop) while keeping the host oracle's eager
    # passes cheap
    hs, hc, ha, hstats = _host_tail(
        tail_step, snap, counts, assign, packed, cfg, tail_chunk=64,
        min_passes=2, max_passes=3, **topo_kw)
    loop = jax.jit(functools.partial(
        core.tail_compaction_loop, tail_step, tail_chunk=64,
        min_passes=2, max_passes=3, **topo_kw))
    ds, dc, da, dstats = loop(snap, counts, assign, packed, cfg)
    dstats = tuple(int(x) for x in np.asarray(dstats))
    assert dstats == hstats
    assert dstats[0] == left0
    np.testing.assert_array_equal(np.asarray(ha), np.asarray(da))
    _assert_trees_equal(hs, ds)
    _assert_trees_equal(hc, dc)


def test_cascade_no_prefix_identical():
    """Cascade on/off equivalence WITHOUT packing contracts (the
    service-caller shape): the heavy gates stay full width —
    `dev_pg == numa_pn == p` — and only the stage-1 fit/quota fold is
    in play. Runs at the tail fixture's shapes so the cascade=False
    side is the program the host-tail oracle already compiled."""
    snap, counts, assign, packed, masks, cfg, left0 = \
        _overcommitted_tail_setup()
    step = functools.partial(core.schedule_batch, num_rounds=4,
                             k_choices=8, score_dims=(0, 1),
                             tie_break=True, quota_depth=2,
                             fit_dims=(0, 1, 2, 3), enable_numa=True,
                             enable_devices=True)
    batch = synthetic.slice_batch(packed, 0, 64).replace(
        **dict(zip(core.COUNT_FIELDS, counts)))
    # cascade omitted (not `cascade=False`): an explicitly-passed
    # static kwarg keys a separate jit-cache entry, and the default
    # form is the one the host-tail oracle above already compiled
    off = step(snap, batch, cfg)
    on = step(snap, batch, cfg, cascade=True)
    _assert_results_equal(off, on)


def test_candidate_mask_sharding_spec():
    """The [P, N] cascade mask follows node columns on the mesh (pods
    replicated, nodes sharded) — the sharding every [.., N] snapshot
    column uses."""
    from koordinator_tpu.parallel import candidate_mask_sharding, make_mesh
    mesh = make_mesh(jax.devices())
    s = candidate_mask_sharding(mesh)
    spec = s.spec
    assert tuple(spec) == (None, "nodes")
    mask = jax.device_put(jnp.ones((16, 800), bool), s)
    assert mask.sharding.is_equivalent_to(s, 2)


def test_tail_loop_zero_pending_batch():
    """Boundary: a ZERO-pending batch through tail_compaction_loop.
    The forced min_passes still run (the warm-path contract), but each
    pass gathers an all-invalid retry batch and must be a no-op: stats
    [0, 0, 0, min_passes], untouched assignment/counts, and a snapshot
    identical up to the version counter."""
    n_nodes, p = 4, 16
    snap = synthetic.synthetic_cluster(n_nodes, seed=3)
    pods = synthetic.synthetic_pods(p, seed=4)
    pods = pods.replace(valid=jnp.zeros((p,), bool))   # nothing pending
    cfg = LoadAwareConfig.make()
    counts = tuple(jnp.asarray(getattr(pods, f))
                   for f in core.COUNT_FIELDS)
    assign = jnp.full((p,), -1, jnp.int32)
    # the slimmest full program that still walks the loop's control
    # edges (the boundary under test is the loop, not the gates)
    step = functools.partial(core.schedule_batch, num_rounds=1,
                             k_choices=1, quota_depth=1,
                             enable_numa=False, enable_devices=False)
    loop = jax.jit(functools.partial(
        core.tail_compaction_loop, step, tail_chunk=8, min_passes=2,
        max_passes=4, charge_counts=False))
    snap2, counts2, assign2, stats = loop(snap, counts, assign, pods,
                                          cfg)
    assert [int(x) for x in np.asarray(stats)] == [0, 0, 0, 2]
    np.testing.assert_array_equal(np.asarray(assign2), np.asarray(assign))
    _assert_trees_equal(counts2, counts)
    # the no-op passes must not move any capacity; only the version
    # counter advances (one bump per schedule_batch call)
    _assert_trees_equal(
        snap2.replace(version=jnp.zeros_like(snap2.version)),
        snap.replace(version=jnp.zeros_like(snap.version)))


def test_prefix_larger_than_batch_identical():
    """Boundary: a batch SMALLER than the declared packing prefixes.
    stage1_mask and every stage-2 slice clamp the prefix to the batch
    width (pc = pn = pg = P), so oversized prefixes must be bit-
    identical to the unprefixed program — cascade off AND on."""
    n_nodes, p = 8, 32
    snap = synthetic.synthetic_cluster(n_nodes, seed=5)
    pods = synthetic.synthetic_pods(p, seed=6)
    cfg = LoadAwareConfig.make()
    kw = dict(num_rounds=1, k_choices=2, quota_depth=1)
    big = dict(topo_prefix=4 * p, numa_prefix=4 * p, gpu_prefix=4 * p)
    base = core.schedule_batch(snap, pods, cfg, **kw)
    clamped = core.schedule_batch(snap, pods, cfg, **kw, **big)
    _assert_results_equal(base, clamped)
    cas = core.schedule_batch(snap, pods, cfg, cascade=True, **kw)
    cas_clamped = core.schedule_batch(snap, pods, cfg, cascade=True,
                                      **kw, **big)
    _assert_results_equal(cas, cas_clamped)
    # the cascade conformance holds at this boundary too
    _assert_results_equal(base, cas)
