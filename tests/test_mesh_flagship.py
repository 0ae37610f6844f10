"""The promoted sharded flagship path (ISSUE 11): spec-derived
shardings, node-axis padding, the 2D pods x nodes mesh option and the
explicit shard_map kernels, on tiny slim-gate programs (cheap
compiles). The served path's sharded conformance, full-gate included,
is tests/test_service_sharded_*.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from koordinator_tpu.parallel import (
    NODE_AXIS,
    POD_AXIS,
    batch_sharding,
    make_mesh,
    mesh_axis_sizes,
    pad_batch_nodes,
    pad_nodes_to_mesh,
    padded_node_count,
    shard_batch,
    shard_snapshot,
    shardops,
    snapshot_sharding,
    struct_sharding,
)
from koordinator_tpu.scheduler import core
from koordinator_tpu.scheduler.cascade import stage1_mask, static_gates
from koordinator_tpu.scheduler.plugins.loadaware import LoadAwareConfig
from koordinator_tpu.snapshot.schema import STRUCT_SPECS
from koordinator_tpu.utils import synthetic


def test_make_mesh_shapes():
    mesh1 = make_mesh(jax.devices())
    assert mesh_axis_sizes(mesh1) == {"nodes": 8}
    mesh2 = make_mesh(jax.devices(), pods_axis=2)
    assert mesh_axis_sizes(mesh2) == {"pods": 2, "nodes": 4}
    assert mesh2.axis_names == (POD_AXIS, NODE_AXIS)
    with pytest.raises(ValueError):
        make_mesh(jax.devices(), pods_axis=3)  # 3 does not divide 8


def test_snapshot_sharding_derived_from_specs():
    """Every snapshot leaf whose registered spec leads with N is
    node-sharded; every other leaf replicates — the layout is a pure
    function of the koordshape field tables, so a new field cannot
    silently get the wrong placement."""
    mesh = make_mesh(jax.devices())
    sh = snapshot_sharding(mesh)
    for group, struct in (("nodes", "NodeState"), ("devices", "DeviceState"),
                          ("quotas", "QuotaState"), ("gangs", "GangState"),
                          ("reservations", "ReservationState")):
        sub = getattr(sh, group)
        for fname, spec in STRUCT_SPECS[struct].items():
            if "[" not in spec:
                continue  # symbolic-int property
            dims = spec[spec.index("[") + 1:spec.rindex("]")].split(",")
            lead = dims[0].split("~")[0].strip() if dims else ""
            want = NODE_AXIS if lead == "N" else None
            got = getattr(sub, fname).spec
            assert (got[0] if len(got) else None) == want, \
                (group, fname, got)
    assert sh.version.spec == jax.sharding.PartitionSpec()


def test_service_commit_keeps_the_published_layout():
    """A commit returns every snapshot leaf in the layout shard_snapshot
    published it in (zero-size aux columns included), so the second
    cycle reuses the first cycle's program instead of recompiling it."""
    from koordinator_tpu.compilecache import counters
    from koordinator_tpu.scheduler.frameworkext import SchedulerService

    mesh = make_mesh(jax.devices()[:4])
    snap = synthetic.synthetic_cluster(32, seed=4, num_quotas=4)
    pods = synthetic.synthetic_pods(64, seed=5, num_quotas=4)
    svc = SchedulerService(num_rounds=2, k_choices=4)
    svc.publish(shard_snapshot(snap, mesh))
    published = jax.tree_util.tree_map(lambda x: x.sharding,
                                       svc.store.current())
    svc.schedule(pods)
    committed = jax.tree_util.tree_map(lambda x: x.sharding,
                                       svc.store.current())
    assert committed == published
    with counters.watch() as w:
        svc.schedule(pods)
    assert w.backend_compiles == 0


def test_result_sharding_derived():
    mesh = make_mesh(jax.devices())
    rs = struct_sharding("ScheduleResult", mesh)
    assert rs.assignment.spec == jax.sharding.PartitionSpec()
    assert rs.snapshot.nodes.requested.spec[0] == NODE_AXIS


def _anti_pods(num, n_nodes, n_zones, seed=3):
    """Slim pods + one hand-built hostname-free anti group over zone
    domains — real [*, N] domain matrices without the full gate set's
    compile cost."""
    zone_of_node = (np.arange(n_nodes) % n_zones).astype(np.int32)
    pods = synthetic.synthetic_pods(num, seed=seed, prod_frac=1.0)
    return pods.replace(
        anti_id=np.zeros((num,), np.int32),
        anti_member=np.ones((num, 1), bool),
        anti_carrier=np.ones((num, 1), bool),
        anti_domain=zone_of_node[None, :].copy(),
        anti_count0=np.zeros((1, n_zones), np.float32),
        anti_carrier_count0=np.zeros((1, n_zones), np.float32),
        has_anti=True)


def test_pad_boundary_indivisible_nodes():
    """The fast boundary pin: a mesh-size-indivisible node count goes
    through pad_nodes_to_mesh/pad_batch_nodes, and the sharded program
    places bit-identically to the unpadded single-device oracle; pad
    rows are provably unschedulable and never charged."""
    mesh = make_mesh(jax.devices())  # 8-way node axis
    n_real = 13
    n_pad = padded_node_count(n_real, mesh)
    assert n_pad == 16
    snap_h = synthetic.synthetic_cluster(n_real, seed=0)
    pods = _anti_pods(6, n_real, n_zones=4)
    cfg = LoadAwareConfig.make()

    res1 = core.schedule_batch(snap_h, pods, cfg, num_rounds=2,
                               k_choices=4, enable_numa=False,
                               enable_devices=False)
    a1 = np.asarray(res1.assignment)
    assert (a1 >= 0).any()

    padded = pad_nodes_to_mesh(snap_h, mesh)
    assert padded.num_nodes == n_pad
    pods_p = pad_batch_nodes(pods, n_pad)
    assert pods_p.anti_domain.shape == (1, n_pad)
    assert (np.asarray(pods_p.anti_domain)[:, n_real:] == -1).all()
    snap_d = shard_snapshot(padded, mesh)
    with mesh:
        res8 = core.schedule_batch(snap_d, pods_p, cfg, num_rounds=2,
                                   k_choices=4, enable_numa=False,
                                   enable_devices=False)
    a8 = np.asarray(res8.assignment)
    assert np.array_equal(a8, a1)
    assert a8.max() < n_real  # pad rows unassigned
    assert core.overcommit_ok(res8.snapshot, n_real)
    assert not np.asarray(res8.snapshot.nodes.requested)[n_real:].any()

    # the stage-1 mask kills pad columns (the pad-row contract)
    static_ok, _ = static_gates(snap_d.nodes, pods_p, cfg)
    mask = np.asarray(stage1_mask(snap_d, pods_p, static_ok))
    assert not mask[:, n_real:].any()


def test_pad_noop_and_consistency_checks():
    mesh = make_mesh(jax.devices())
    snap = synthetic.synthetic_cluster(16, seed=0)
    assert pad_nodes_to_mesh(snap, mesh) is snap  # divisible: no-op
    pods = synthetic.synthetic_pods(4, seed=1)
    # slim [1, 1] compile-out domain matrices: nothing to pad
    assert pad_batch_nodes(pods, 16) is pods
    bad = pods.replace(anti_domain=np.zeros((1, 24), np.int32))
    with pytest.raises(ValueError):
        pad_batch_nodes(bad, 16)  # extent beyond the padded count


def test_overcommit_ok_detects_charged_pad_row():
    snap = synthetic.synthetic_cluster(8, seed=0)
    assert core.overcommit_ok(snap, 6)
    req = np.asarray(snap.nodes.requested).copy()
    req[7, 0] = 1.0  # a pad row got charged: must fail loudly
    assert not core.overcommit_ok(
        snap.replace(nodes=snap.nodes.replace(requested=req)), 6)


def test_shard_local_topk_matches_lax_top_k_with_ties():
    """The ICI merge kernel is bit-identical to lax.top_k, ties
    included (lexicographic value-desc / index-asc order)."""
    mesh = make_mesh(jax.devices())
    rng = np.random.default_rng(0)
    x = rng.integers(0, 4, size=(16, 64)).astype(np.float32)  # heavy ties
    x[3] = -1.0  # an all-infeasible row
    for k in (1, 5, 8):
        v0, i0 = jax.lax.top_k(jnp.asarray(x), k)
        v1, i1 = jax.jit(
            lambda a, k=k: shardops.shard_local_topk(mesh, a, k))(
                jnp.asarray(x))
        assert np.array_equal(np.asarray(v0), np.asarray(v1)), k
        assert np.array_equal(np.asarray(i0), np.asarray(i1)), k
    with pytest.raises(ValueError):
        shardops.shard_local_topk(mesh, jnp.asarray(x), 9)  # k > local
    with pytest.raises(ValueError):
        shardops.shard_local_topk(mesh, jnp.asarray(x[:, :60]), 4)


def test_stage1_mask_sharded_conformance():
    mesh = make_mesh(jax.devices())
    snap = synthetic.synthetic_cluster(16, seed=0, num_quotas=4)
    pods = synthetic.synthetic_pods(12, seed=1, num_quotas=4)
    cfg = LoadAwareConfig.make()
    snap_d = shard_snapshot(snap, mesh)
    static_ok, _ = static_gates(snap_d.nodes, pods, cfg)
    g = np.asarray(stage1_mask(snap_d, pods, static_ok))
    s = np.asarray(jax.jit(
        lambda sn, pd, so: shardops.stage1_mask_sharded(mesh, sn, pd, so)
    )(snap_d, pods, static_ok))
    assert np.array_equal(g, s)

    # full gate over a padded node axis: the same mask, pad columns
    # dead, and stage 1 (elementwise over node columns) compiles with no
    # cross-device collective over sharded inputs
    n = 35
    snap_p = pad_nodes_to_mesh(
        synthetic.full_gate_cluster(n, num_quotas=4, num_gangs=2,
                                    gpus_per_node=4), mesh)
    snap_d = shard_snapshot(snap_p, mesh)
    pods = pad_batch_nodes(
        synthetic.full_gate_pods(256, n, num_quotas=4, num_gangs=2,
                                 n_anti_groups=4, anti_members=4,
                                 n_aff_groups=2, aff_members=4),
        int(snap_p.num_nodes))
    static_ok, _ = static_gates(snap_d.nodes, pods, cfg)
    g = np.asarray(stage1_mask(snap_d, pods, static_ok))
    s = np.asarray(jax.jit(
        lambda sn, pd, so: shardops.stage1_mask_sharded(mesh, sn, pd, so)
    )(snap_d, pods, static_ok))
    assert np.array_equal(g, s)
    assert g[:, :n].any() and not g[:, n:].any()
    hlo = jax.jit(stage1_mask).lower(snap_d, pods, static_ok) \
        .compile().as_text()
    collectives = ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute", "reduce-scatter")
    assert [c for c in collectives if c in hlo] == []


def test_2d_pods_nodes_mesh_conformance():
    """The 2D mesh option is layout, not semantics: a 2x2 pods x nodes
    mesh with the batch sharded over the pods axis places bit-
    identically to the single-device program."""
    mesh = make_mesh(jax.devices()[:4], pods_axis=2)
    snap_h = synthetic.synthetic_cluster(16, seed=0)
    pods = _anti_pods(8, 16, n_zones=4)
    cfg = LoadAwareConfig.make()
    res1 = core.schedule_batch(snap_h, pods, cfg, num_rounds=2,
                               k_choices=4, enable_numa=False,
                               enable_devices=False)
    sh = batch_sharding(pods, mesh)
    assert sh.requests.spec[0] == POD_AXIS
    assert sh.anti_domain.spec == jax.sharding.PartitionSpec(None,
                                                             NODE_AXIS)
    assert sh.anti_count0.spec == jax.sharding.PartitionSpec()
    with mesh:
        res2 = core.schedule_batch(shard_snapshot(snap_h, mesh),
                                   shard_batch(pods, mesh), cfg,
                                   num_rounds=2, k_choices=4,
                                   enable_numa=False,
                                   enable_devices=False)
    assert np.array_equal(np.asarray(res2.assignment),
                          np.asarray(res1.assignment))
