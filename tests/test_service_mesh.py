"""The served path on a snapshot node-sharded over the virtual 8-CPU
mesh (conftest forces 8 devices): closed-loop drains through
`SchedulerService.schedule()` with each benchmark configuration's
statics, plus the sharded store's topology deltas and the cross-chunk
count rule.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import service_mesh
from koordinator_tpu.scheduler import core

NODES, BATCH, CALLS = 800, 1000, 4


@pytest.mark.parametrize("config", ["colocation", "full-gate"])
def test_service_drains_on_8_device_mesh(config):
    """4,000 pods in 1,000-pod calls on 800 nodes sharded 100 to a
    device, counts carried between calls: every committed snapshot
    keeps the published sharding, no node is overcommitted, and the
    placements are one device's. Colocation binds every pod but the
    stragglers its 4-round x 8-choice budget hands back to the queue
    on one device too (2 strict gangs and 4 batch-tier pods of the
    first call); the full-gate bulk binds."""
    assert len(jax.devices()) == 8
    block, snap, batches = service_mesh.make_inputs(config, NODES, BATCH,
                                                    CALLS)
    alone = service_mesh.make_service(block)
    alone.publish(snap)
    want = service_mesh.drain(alone, batches)

    sharded, batches = service_mesh.shard_over(snap, batches,
                                               jax.devices())
    svc = service_mesh.make_service(block)
    svc.publish(sharded)
    published = svc.store.current()

    def on_commit(i, assignment):
        committed = svc.store.current()
        assert service_mesh.moved_leaves(committed, published) == [], i
        assert svc.metrics.mesh_size.value() == 8
        assert core.overcommit_arrays_ok(committed.nodes.requested,
                                         committed.nodes.allocatable,
                                         NODES), i
        np.testing.assert_array_equal(assignment, want[i],
                                      err_msg=f"call {i}")

    assignments = service_mesh.drain(svc, batches, on_commit)
    placed = sum(int((a >= 0).sum()) for a in assignments)
    if config == "colocation":
        assert placed >= 0.99 * BATCH * CALLS
    else:
        # tight topology constraints leave stragglers; the bulk binds
        assert placed > 3000


def test_topology_delta_ingests_into_a_sharded_store():
    """Node churn must stay O(K) on a MESH deployment too: the jitted
    topology scatter runs against node columns sharded over the
    8-device mesh (GSPMD handles the scatter placement), and the
    patched row is visible to a subsequent sharded schedule step."""
    from koordinator_tpu.api import types as api
    from koordinator_tpu.api.extension import ResourceKind as RK
    from koordinator_tpu.parallel import make_mesh, snapshot_sharding
    from koordinator_tpu.snapshot import SnapshotStore
    from koordinator_tpu.snapshot.builder import SnapshotBuilder

    mesh = make_mesh(jax.devices())
    store = SnapshotStore(sharding=snapshot_sharding(mesh))
    b = SnapshotBuilder(max_nodes=16)
    for i in range(16):
        b.add_node(api.Node(meta=api.ObjectMeta(name=f"n{i}"),
                            allocatable={RK.CPU: 16000.0,
                                         RK.MEMORY: 32768.0}))
    snap, ctx = b.build(now=1e9)
    store.publish(snap)

    b.add_node(api.Node(meta=api.ObjectMeta(name="n3"),
                        allocatable={RK.CPU: 96000.0,
                                     RK.MEMORY: 262144.0}))
    with mesh:
        store.ingest(b.topology_delta(["n3"], now=1e9, pad_to=4))
    got = store.current()
    assert float(np.asarray(got.nodes.allocatable)[3, int(RK.CPU)]) \
        == 96000.0

    # a sharded schedule step sees the patched capacity
    from koordinator_tpu.scheduler import core
    from koordinator_tpu.scheduler.plugins.loadaware import LoadAwareConfig

    pods = [api.Pod(meta=api.ObjectMeta(name=f"p{j}"), priority=9000,
                    requests={RK.CPU: 20000.0, RK.MEMORY: 4096.0})
            for j in range(2)]
    batch = b.build_pod_batch(pods, ctx)
    with mesh:
        res = core.schedule_batch(got, batch, LoadAwareConfig.make(),
                                  num_rounds=2, k_choices=2)
    a = np.asarray(res.assignment)
    assert (a == 3).all()  # only the upgraded node fits 20-core pods


def test_anti_affinity_holds_across_chunks():
    """Regression for the cross-chunk count rule: carriers of one anti
    group scheduled in DIFFERENT chunks still land in distinct domains,
    because the bench threads core.charge_domain_counts output into the
    next chunk's count0 (core.domain_machinery's cross-batch contract).
    """
    from koordinator_tpu.scheduler import core
    from koordinator_tpu.scheduler.plugins.loadaware import LoadAwareConfig
    from koordinator_tpu.utils import synthetic

    n_nodes, n_zones = 16, 4
    snap = synthetic.synthetic_cluster(n_nodes, seed=0)
    zone_of_node = (np.arange(n_nodes) % n_zones).astype(np.int32)

    def carriers(num):
        pods = synthetic.synthetic_pods(num, seed=3, prod_frac=1.0)
        return pods.replace(
            anti_id=np.zeros((num,), np.int32),
            anti_member=np.ones((num, 1), bool),
            anti_carrier=np.ones((num, 1), bool),
            anti_domain=zone_of_node[None, :].copy(),
            anti_count0=np.zeros((1, n_zones), np.float32),
            anti_carrier_count0=np.zeros((1, n_zones), np.float32),
            has_anti=True)

    counts = (jnp.zeros((1, n_zones), jnp.float32),
              jnp.zeros((1, n_zones), jnp.float32))
    zones = []
    for _ in range(2):  # two chunks of 2 carriers each
        batch = carriers(2).replace(anti_count0=counts[0],
                                    anti_carrier_count0=counts[1])
        res = core.schedule_batch(snap, batch, LoadAwareConfig.make(),
                                  num_rounds=2, k_choices=4,
                                  enable_numa=False)
        a = np.asarray(res.assignment)
        assert (a >= 0).all()
        zones.extend(zone_of_node[a].tolist())
        snap = res.snapshot
        counts = (
            core.charge_domain_counts(counts[0], batch.anti_domain,
                                      batch.anti_member, res.assignment),
            core.charge_domain_counts(counts[1], batch.anti_domain,
                                      batch.anti_carrier, res.assignment),
        )
    # 4 carriers over 4 zones: all distinct IFF the second chunk saw the
    # first chunk's charges
    assert len(set(zones)) == 4, zones
    assert np.asarray(counts[0]).sum() == 4.0
