"""Adversarial scale semantics: strict gangs spanning batch/chunk
boundaries (the Permit wait carried in gangs.assumed across scan steps,
coscheduling core.go:311-341)."""

import numpy as np
import pytest

from koordinator_tpu.api.types import Node, NodeMetric, ObjectMeta, Pod, PodGroup
from koordinator_tpu.api.extension import ResourceKind as RK
from koordinator_tpu.scheduler import core
from koordinator_tpu.scheduler.plugins import loadaware
from koordinator_tpu.snapshot import SnapshotBuilder
from koordinator_tpu.snapshot.delta import forget_pods

NOW = 1e9


def _cluster(b, n_nodes=2, cpu=32000):
    for i in range(n_nodes):
        b.add_node(Node(meta=ObjectMeta(name=f"n{i}"),
                        allocatable={RK.CPU: cpu, RK.MEMORY: 65536}))
        b.set_node_metric(NodeMetric(node_name=f"n{i}", update_time=NOW,
                                     node_usage={}))


def _members(ctx_builder, gang, count, start=0, cpu=1000.0):
    return [Pod(meta=ObjectMeta(name=f"{gang}-{start + j}"), priority=9000,
                requests={RK.CPU: cpu, RK.MEMORY: 256.0}, gang_name=gang)
            for j in range(count)]


def test_strict_gang_spanning_chunks_completes():
    """A 6-member strict gang split 3+3 over two successive batches (the
    bench CHUNK boundary): the first batch's members stay ASSUMED below
    quorum because members are still outstanding, and the second batch
    completes the gang."""
    b = SnapshotBuilder(max_nodes=2, max_gangs=1)
    _cluster(b)
    b.add_gang(PodGroup(meta=ObjectMeta(name="g"), min_member=6,
                        total_member=6))
    snap, ctx = b.build(now=NOW)
    cfg = loadaware.LoadAwareConfig.make()

    chunk1 = b.build_pod_batch(_members(b, "g", 3), ctx)
    res1 = core.schedule_batch(snap, chunk1, cfg, num_rounds=4)
    a1 = np.asarray(res1.assignment)
    assert np.all(a1 >= 0), "partial members must HOLD (Permit wait), " \
        f"not roll back, got {a1}"
    assert np.asarray(res1.snapshot.gangs.assumed)[0] == 3
    # their capacity is charged while they wait at the barrier
    assert np.asarray(res1.snapshot.nodes.requested)[:, 0].sum() == \
        pytest.approx(3000.0)

    assert not np.asarray(res1.gang_failed)[0], \
        "a gang with outstanding members is not yet failed"

    chunk2 = b.build_pod_batch(_members(b, "g", 3, start=3), ctx)
    res2 = core.schedule_batch(res1.snapshot, chunk2, cfg, num_rounds=4)
    a2 = np.asarray(res2.assignment)
    assert np.all(a2 >= 0)
    assert np.asarray(res2.snapshot.gangs.assumed)[0] == 6


def test_strict_gang_single_batch_still_all_or_nothing():
    """When the WHOLE gang is attempted in one batch (no members
    outstanding) and cannot fit, rollback stays immediate — the
    chunk-spanning hold must not weaken the single-batch barrier."""
    b = SnapshotBuilder(max_nodes=2, max_gangs=1)
    _cluster(b, cpu=8000)
    b.add_gang(PodGroup(meta=ObjectMeta(name="g"), min_member=5,
                        total_member=5))
    snap, ctx = b.build(now=NOW)
    pods = _members(b, "g", 5, cpu=6000.0)
    res = core.schedule_batch(snap, b.build_pod_batch(pods, ctx),
                              loadaware.LoadAwareConfig.make(), num_rounds=4)
    assert np.all(np.asarray(res.assignment) == -1)
    assert np.asarray(res.snapshot.gangs.assumed)[0] == 0
    # the proven failure is signalled to the host
    assert np.asarray(res.gang_failed)[0]


def test_strict_gang_hold_reclaimed_by_unassume():
    """If the rest of a spanning gang never fits, the held members'
    charges flow back through the forget/un-assume path (the Permit
    wait-expiry rollback: GangDirectory.expire_waits -> store.forget)."""
    b = SnapshotBuilder(max_nodes=2, max_gangs=1)
    _cluster(b, cpu=4000)
    b.add_gang(PodGroup(meta=ObjectMeta(name="g"), min_member=6,
                        total_member=6))
    snap, ctx = b.build(now=NOW)
    cfg = loadaware.LoadAwareConfig.make()

    chunk1 = b.build_pod_batch(_members(b, "g", 3, cpu=2000.0), ctx)
    res1 = core.schedule_batch(snap, chunk1, cfg, num_rounds=4)
    assert np.all(np.asarray(res1.assignment) >= 0)
    # chunk 2 members can never fit (8000 CPU total, 6000 held)
    chunk2 = b.build_pod_batch(
        _members(b, "g", 3, start=3, cpu=3000.0), ctx)
    res2 = core.schedule_batch(res1.snapshot, chunk2, cfg, num_rounds=4)
    assert np.all(np.asarray(res2.assignment) == -1)
    # the 3 held members still charge the nodes while waiting
    assert np.asarray(res2.snapshot.nodes.requested)[:, 0].sum() == \
        pytest.approx(6000.0)
    # every member has now been attempted and the gang is short: the
    # result PROVES the failure so the host need not wait for the timeout
    assert np.asarray(res2.gang_failed)[0]

    # the proven failure (or, for gangs whose members never reappear, the
    # Permit wait expiry) triggers the un-assume of the held members
    import jax.numpy as jnp
    mask = jnp.asarray(np.ones(chunk1.valid.shape, bool))
    after = forget_pods(res2.snapshot, chunk1, res1, mask)
    assert np.asarray(after.nodes.requested)[:, 0].sum() == pytest.approx(0.0)
    assert np.asarray(after.gangs.assumed)[0] == 0


def test_service_gang_failed_hook_reclaims_held_members():
    """Production loop: the SchedulerService surfaces gang_failed to its
    hook, and the hook un-assumes the earlier batch's held members
    through the store — capacity returns without the Permit timeout."""
    from koordinator_tpu.scheduler.frameworkext import SchedulerService

    b = SnapshotBuilder(max_nodes=2, max_gangs=1)
    _cluster(b, cpu=4000)
    b.add_gang(PodGroup(meta=ObjectMeta(name="g"), min_member=6,
                        total_member=6))
    snap, ctx = b.build(now=NOW)
    svc = SchedulerService(num_rounds=4)
    svc.publish(snap)

    retained = []  # the gang controller retains (batch, result) per gang

    def on_gang_failed(gids, _result):
        assert list(gids) == [0]
        import jax.numpy as jnp
        for batch, res in retained:
            mask = jnp.asarray(batch.gang_id == 0) & batch.valid & \
                (res.assignment >= 0)
            svc.store.update(lambda s: forget_pods(s, batch, res, mask))

    svc.on_gang_failed = on_gang_failed

    chunk1 = b.build_pod_batch(_members(b, "g", 3, cpu=2000.0), ctx)
    res1 = svc.schedule(chunk1)
    retained.append((chunk1, res1))
    assert np.all(np.asarray(res1.assignment) >= 0)
    assert svc.last_gang_failed is not None and not svc.last_gang_failed[0]

    chunk2 = b.build_pod_batch(_members(b, "g", 3, start=3, cpu=3000.0), ctx)
    res2 = svc.schedule(chunk2)
    assert np.all(np.asarray(res2.assignment) == -1)
    assert svc.last_gang_failed[0]
    # the hook ran: held capacity flowed back into the live snapshot
    cur = svc.store.current()
    assert np.asarray(cur.nodes.requested)[:, 0].sum() == pytest.approx(0.0)
    assert np.asarray(cur.gangs.assumed)[0] == 0
