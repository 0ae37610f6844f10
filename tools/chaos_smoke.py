"""Chaos smoke: the fault-injection matrix for the resilience layer.

For EVERY fault class in koordinator_tpu.testing.faults.ALL_FAULTS this
stage asserts, on a small full-gate workload:

  1. DETECTED   — the guard word carries the expected defect bit, the
                  failure classifies to the expected FailureClass, or
                  the delta guard surfaces the typed reject reason;
  2. QUARANTINED — corrupted node rows end the cycle schedulable=False,
                  corrupted pod rows end unplaced and drain through the
                  error chain as infrastructure errors
                  (unschedulable=False);
  3. SERVICE UP — schedule() returns (degrading if it must) and the
                  NEXT clean cycle also completes;
  4. CONFORMANT — placements on clean rows are BIT-IDENTICAL to a
                  no-fault oracle run (for column faults the oracle is
                  the same batch with the corrupted rows masked
                  manually; for runtime faults it is the same clean
                  inputs at the ladder state the service ended in).

Runs on CPU in CI (tools/ci.sh); correctness-only, never wall-clock.
Usage: JAX_PLATFORMS=cpu python tools/chaos_smoke.py [fault ...]
       --overhead additionally measures guarded-vs-unguarded warm time.
"""

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# a small virtual device fleet (CPU), set before the backend
# initializes: the device_lost cases must exercise the mesh-shrink
# rung (>= 2 survivors), not only the single-device abandon path.
# Programs still run on device 0 unless a rung shards them, so every
# other fault class is unaffected.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

import jax

if os.environ.get("JAX_PLATFORMS"):
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

import numpy as np

from koordinator_tpu.api.extension import ResourceKind as RK
from koordinator_tpu.api.types import Node, NodeMetric, ObjectMeta, Pod
from koordinator_tpu.metrics import Registry
from koordinator_tpu.scheduler import guards
from koordinator_tpu.scheduler.errorhandler import FailureClass
from koordinator_tpu.scheduler.frameworkext import (
    DegradationLadder,
    LadderState,
    SchedulerService,
)
from koordinator_tpu.scheduler.metrics_defs import SchedulerMetrics
from koordinator_tpu.snapshot import SnapshotBuilder
from koordinator_tpu.testing import faults
from koordinator_tpu.utils import synthetic

N_NODES, N_PODS = 64, 192
SEED = int(os.environ.get("CHAOS_SEED", "0"))


def make_inputs(seed):
    snap = synthetic.full_gate_cluster(N_NODES, seed=seed, num_quotas=8,
                                       num_gangs=8)
    pods = synthetic.full_gate_pods(N_PODS, N_NODES, seed=seed + 100,
                                    num_quotas=8, num_gangs=8)
    return snap, pods


def make_service(**kw):
    svc = SchedulerService(metrics=SchedulerMetrics(Registry()),
                           num_rounds=2, k_choices=4, **kw)
    svc._sleep = lambda _s: None  # chaos runs don't wait out real backoff
    return svc


def typed_pods_for(p):
    return [Pod(meta=ObjectMeta(name=f"pod-{i}", namespace="chaos"))
            for i in range(p)]


def infra_error_collector(svc):
    """Default error handler recording (row order lost, names kept)."""
    drained = {"infra": [], "unschedulable": []}

    def handler(pod_info, err):
        key = "unschedulable" if err.unschedulable else "infra"
        drained[key].append(pod_info.pod.meta.name)

    svc.error_dispatcher.set_default_handler(handler)
    return drained


def oracle_assignment(snap, pods, bad_nodes=None, bad_pods=None,
                      ladder_state=None):
    """The no-fault oracle: clean columns with the corrupted rows
    masked the way quarantine semantically masks them (node
    schedulable=False / pod valid=False), run at `ladder_state`."""
    import jax.numpy as jnp

    if bad_nodes is not None and len(bad_nodes):
        sched = np.asarray(snap.nodes.schedulable).copy()
        sched[np.asarray(bad_nodes)] = False
        snap = snap.replace(nodes=snap.nodes.replace(
            schedulable=jnp.asarray(sched)))
    if bad_pods is not None and len(bad_pods):
        valid = np.asarray(pods.valid).copy()
        valid[np.asarray(bad_pods)] = False
        pods = pods.replace(valid=jnp.asarray(valid))
    svc = make_service()
    if ladder_state is not None:
        svc.ladder.level = ladder_state.level
        svc.ladder.chunk_splits = ladder_state.chunk_splits
    svc.publish(snap)
    return np.asarray(svc.schedule(pods).assignment)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def run_snapshot_fault(kind):
    inj = faults.FaultInjector(SEED)
    snap, pods = make_inputs(3)
    bad_snap, rows = inj.corrupt_snapshot(snap, kind, n_rows=2)
    svc = make_service()
    svc.publish(bad_snap)
    res = svc.schedule(pods)
    word = svc.last_health_word
    # 1. detected
    check(word & faults.EXPECTED_BIT[kind],
          f"{kind}: expected bit not in word 0x{word:x}")
    # 2. quarantined: the committed snapshot pins the nodes out
    sched = np.asarray(svc.store.current().nodes.schedulable)
    check(not sched[rows].any(), f"{kind}: rows {rows} still schedulable")
    assign = np.asarray(res.assignment)
    check(not np.isin(assign, rows).any(),
          f"{kind}: a pod landed on a quarantined node")
    # 4. clean-row conformance, bit-identical
    oracle = oracle_assignment(snap, pods, bad_nodes=rows)
    check(np.array_equal(assign, oracle),
          f"{kind}: placements drifted from the masked-row oracle")
    # 3. service stays up on the next cycle
    svc.schedule(pods)
    return {"fault": kind, "quarantined_nodes": len(rows),
            "word": hex(word)}


def run_batch_fault(kind):
    inj = faults.FaultInjector(SEED)
    snap, pods = make_inputs(5)
    bad_pods_batch, rows = inj.corrupt_batch(pods, kind, n_rows=3)
    svc = make_service()
    drained = infra_error_collector(svc)
    svc.publish(snap)
    res = svc.schedule(bad_pods_batch,
                       typed_pods=typed_pods_for(N_PODS))
    word = svc.last_health_word
    check(word & faults.EXPECTED_BIT[kind],
          f"{kind}: expected bit not in word 0x{word:x}")
    assign = np.asarray(res.assignment)
    check((assign[rows] == -1).all(), f"{kind}: a corrupt row was placed")
    # quarantined rows drained as INFRASTRUCTURE errors, not no-fit
    names = {f"pod-{i}" for i in rows}
    check(names <= set(drained["infra"]),
          f"{kind}: quarantined rows missing from the infra drain "
          f"({sorted(names - set(drained['infra']))[:5]})")
    check(not (names & set(drained["unschedulable"])),
          f"{kind}: a quarantined row drained as unschedulable")
    oracle = oracle_assignment(snap, pods, bad_pods=rows)
    check(np.array_equal(assign, oracle),
          f"{kind}: placements drifted from the masked-row oracle")
    svc.schedule(pods)
    return {"fault": kind, "quarantined_pods": len(rows),
            "word": hex(word)}


def run_runtime_fault(kind):
    inj = faults.FaultInjector(SEED)
    snap, pods = make_inputs(7)
    svc = make_service()
    svc.publish(snap)
    expected = {
        "xla_oom": FailureClass.RESOURCE_EXHAUSTED,
        "xla_transient": FailureClass.XLA_INTERNAL,
        "device_lost": FailureClass.DEVICE_LOST,
        "watchdog_stall": FailureClass.WATCHDOG_STALL,
    }[kind]
    if kind == "xla_oom":
        svc.fault_injection = inj.oom_above(N_PODS // 2)
    elif kind == "xla_transient":
        svc.fault_injection = inj.xla_transient(fail_attempts={1, 2})
    elif kind == "device_lost":
        # one lost-device hiccup is absorbed by the transient retry at
        # the SAME rung; only an exhausted retry budget (RetryPolicy
        # max_attempts=3) moves the ladder — to the mesh-shrink rung
        # when >= 2 devices survive, to single-device otherwise
        svc.fault_injection = inj.device_lost(fail_attempts={1, 2, 3, 4})
    else:
        inj.stall_watchdog(svc)
    res = svc.schedule(pods)
    assign = np.asarray(res.assignment)
    # 1. detected: the typed class was counted
    counted = svc.metrics.failures_classified.labels(expected.value).get()
    check(counted >= 1, f"{kind}: class {expected.value} never counted")
    # 3. service completed THIS cycle and the next clean one
    svc.fault_injection = None
    svc.monitor.timeout = 30.0
    svc.schedule(pods)
    # 4. conformance at the ladder state the service ended the faulted
    # cycle in (chunked placements differ from one-shot BY DESIGN; the
    # oracle runs the same clean inputs at the same rung)
    oracle = oracle_assignment(snap, pods,
                               ladder_state=svc.last_ladder_state
                               if kind != "watchdog_stall" else None)
    check(np.array_equal(assign, oracle),
          f"{kind}: placements drifted from the same-rung oracle")
    if kind == "xla_oom":
        check(svc.ladder.level == DegradationLadder.L_CHUNKED,
              f"{kind}: expected the chunked rung, "
              f"got {svc.ladder.state().label()}")
    if kind == "device_lost":
        # with >= 2 survivors the mesh SHRINKS instead of being
        # abandoned (ISSUE 14); single-device only on a 1-device host
        expected_level = (DegradationLadder.L_MESH_SHRINK
                          if jax.device_count() >= 2
                          else DegradationLadder.L_SINGLE_DEVICE)
        check(svc.ladder.level == expected_level,
              f"{kind}: expected "
              f"{DegradationLadder.LEVELS[expected_level]}, "
              f"got {svc.ladder.state().label()}")
    if kind == "watchdog_stall":
        check(svc.monitor.timeouts >= 1, "stall never tripped the monitor")
        check(svc.ladder.level > 0, "stall did not degrade the next cycle")
    return {"fault": kind, "class": expected.value,
            "ladder": svc.ladder.state().label(),
            "transitions": svc.ladder.transitions}


def run_device_lost_mid_chunk(kind):
    """ISSUE 14 satellite: a device dies MID-chunked-batch (chunks 0-1
    already committed to the journal) and stays dead. The service must
    resume on the SHRUNK mesh from the last committed chunk — zero
    duplicated and zero lost placements, bit-identical to the no-fault
    chunked oracle — instead of restarting (or abandoning) the batch;
    probe-up then returns to the normal rung."""
    import shutil
    import tempfile

    from koordinator_tpu.scheduler.journal import CommitJournal

    if jax.device_count() < 3:
        # needs >= 2 survivors after losing one device; the module
        # header forces 4 virtual devices, so this only trips when a
        # caller overrode XLA_FLAGS
        return {"fault": kind, "skipped": f"{jax.device_count()} devices"}
    inj = faults.FaultInjector(SEED)
    snap, pods = make_inputs(11)
    workdir = tempfile.mkdtemp(prefix="chaos_mid_chunk_")
    try:
        svc = make_service(
            journal=CommitJournal(os.path.join(workdir, "journal.bin")))
        svc.ladder.level = DegradationLadder.L_CHUNKED
        svc.ladder.chunk_splits = 2  # 4 journaled chunks
        svc.ladder.probe_after = 2
        # the device dies after 2 chunk programs and STAYS dead until
        # the mesh stops including it (faults.lost_device_until_shrunk)
        svc.fault_injection = inj.lost_device_until_shrunk(after_calls=2)
        survivors = jax.devices()[:-1]
        svc.device_health = lambda: survivors
        svc.publish(snap)
        res = svc.schedule(pods)
        # 1. detected + degraded to the NEW rung, not single_device
        check(svc.ladder.level == DegradationLadder.L_MESH_SHRINK,
              f"{kind}: expected mesh_shrink, "
              f"got {svc.ladder.state().label()}")
        check(svc.metrics.mesh_shrink_events.value() == 1,
              f"{kind}: mesh-shrink event not counted")
        check(svc.metrics.mesh_size.value() == len(survivors),
              f"{kind}: mesh-size gauge {svc.metrics.mesh_size.value()} "
              f"!= {len(survivors)} survivors")
        # 2. resumed, not restarted: the pre-crash chunks were REPLAYED
        # from the journal (asserted bit-identical inside it, never
        # re-appended) and every chunk appears exactly once
        check(svc.metrics.recovery_replayed.value() == 2,
              f"{kind}: expected 2 replayed chunks, got "
              f"{svc.metrics.recovery_replayed.value()}")
        records = svc.journal.records_for(1)
        check(sorted(records) == [0, 1, 2, 3],
              f"{kind}: journal chunk set {sorted(records)} is not "
              f"exactly one record per chunk")
        # 4. no duplicate, no lost placements: bit-identical to the
        # no-fault chunked oracle on the full mesh
        oracle = oracle_assignment(
            snap, pods, ladder_state=LadderState(
                DegradationLadder.L_CHUNKED, 2))
        check(np.array_equal(np.asarray(res.assignment), oracle),
              f"{kind}: resumed placements drifted from the chunked "
              f"no-fault oracle")
        # 3. service up, and probe-up returns to the normal rung
        svc.fault_injection = None
        svc.device_health = None
        for _ in range(8):
            spans = len(svc.store.current().nodes.allocatable.sharding
                        .device_set)
            svc.schedule(pods)
            if svc.ladder.level < DegradationLadder.L_MESH_SHRINK:
                break
        check(svc.ladder.level < DegradationLadder.L_MESH_SHRINK,
              f"{kind}: probe-up never left mesh_shrink "
              f"({svc.ladder.transitions})")
        # off the shrink rung the cycle runs where the committed
        # snapshot lives, and the gauge reports the devices it spans
        check(svc.metrics.mesh_size.value() == spans,
              f"{kind}: mesh-size gauge {svc.metrics.mesh_size.value()} "
              f"after probe-up, snapshot spans {spans}")
        return {"fault": kind, "ladder": svc.ladder.state().label(),
                "replayed": 2, "survivors": len(survivors),
                "transitions": svc.ladder.transitions}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def run_delta_fault(kind):
    from koordinator_tpu.snapshot.delta import DeltaRejectReason

    inj = faults.FaultInjector(SEED)
    b = SnapshotBuilder(max_nodes=8)
    for i in range(8):
        b.add_node(Node(meta=ObjectMeta(name=f"n{i}"),
                        allocatable={RK.CPU: 8_000.0,
                                     RK.MEMORY: 16_384.0}))
        b.set_node_metric(NodeMetric(node_name=f"n{i}", update_time=100.0,
                                     node_usage={RK.CPU: 500.0}))
    snap, _ = b.build(now=105.0)
    svc = make_service()
    svc.publish(snap)
    fresh = b.metric_delta(["n1"], now=106.0, pad_to=2)
    svc.ingest(fresh)
    before = np.asarray(svc.store.current().nodes.usage).copy()
    v_before = svc.store.version
    stale = inj.stale_delta(
        b.metric_delta(["n2"], now=107.0, pad_to=2),
        applied_version=svc.store.applied_delta_version)
    svc.ingest(stale)
    # 1. detected with the typed reason on the metric
    rejected = sum(
        svc.metrics.delta_rejected.labels(r.value).get()
        for r in DeltaRejectReason)
    check(rejected == 1, "stale delta not surfaced to metrics")
    # 2. quarantined == not applied: columns and version untouched
    check(svc.store.version == v_before, "stale delta bumped the version")
    check(np.array_equal(
        np.asarray(svc.store.current().nodes.usage), before),
        "stale delta scattered rows")
    # 3./4. the service still schedules, identically to the oracle
    pods = synthetic.full_gate_pods(32, 8, seed=9, num_quotas=2,
                                    num_gangs=2)
    snap_now = svc.store.current()  # BEFORE the commit mutates the store
    assign = np.asarray(svc.schedule(pods).assignment)
    o = make_service()
    o.publish(snap_now)
    check(np.array_equal(assign, np.asarray(o.schedule(pods).assignment)),
          "post-rejection placements drifted")
    return {"fault": kind, "rejections": int(rejected)}


def measure_overhead():
    """Warm guarded-vs-unguarded wall clock at the 20k x 2k full-gate
    CPU proxy, run the way the service (and the bench sweep) actually
    runs it: chunks of 2000 pods scheduled sequentially against the
    evolving snapshot. The acceptance bound is <= 2% added warm
    wall-clock; checked on the proxy host, not in CI wall-clock."""
    from koordinator_tpu.scheduler import core
    from koordinator_tpu.scheduler.plugins import loadaware

    n = int(os.environ.get("CHAOS_OVERHEAD_NODES", "2000"))
    p = int(os.environ.get("CHAOS_OVERHEAD_PODS", "20000"))
    chunk = int(os.environ.get("CHAOS_OVERHEAD_CHUNK", "2000"))
    snap0 = synthetic.full_gate_cluster(n, seed=1, num_quotas=32)
    pods = synthetic.full_gate_pods(p, n, seed=2, num_quotas=32)
    import jax

    pods = jax.device_put(pods)
    cfg = loadaware.LoadAwareConfig.make()
    kw = dict(num_rounds=2, k_choices=8, score_dims=(0, 1),
              tie_break=True, quota_depth=2, fit_dims=(0, 1, 2, 3),
              cascade=True)

    def sweep(fn, snap):
        counts = tuple(getattr(pods, f) for f in core.COUNT_FIELDS)
        assigns = []
        for start in range(0, p, chunk):
            batch = synthetic.slice_batch(pods, start, chunk)
            batch = batch.replace(**dict(zip(core.COUNT_FIELDS, counts)))
            out = fn(snap, batch, cfg, **kw)
            res = out[0] if isinstance(out, tuple) else out
            counts = core.charge_all_counts(counts, batch,
                                            res.assignment)
            snap = res.snapshot
            assigns.append(res.assignment)
        return np.asarray(jnp_concat(assigns))

    def jnp_concat(parts):
        import jax.numpy as jnp
        return jnp.concatenate(parts)

    def timed(fn):
        sweep(fn, jax.device_put(snap0))  # compile + warm
        t0 = time.perf_counter()
        sweep(fn, jax.device_put(snap0))
        return time.perf_counter() - t0

    base = timed(core.schedule_batch)
    guarded = timed(guards.guarded_schedule_batch)
    print(f"overhead ({p}x{n} full-gate, chunk {chunk}): "
          f"base={base:.3f}s guarded={guarded:.3f}s "
          f"({(guarded / base - 1) * 100:+.2f}%)", flush=True)


def main(argv):
    overhead = "--overhead" in argv
    selected = [a for a in argv if not a.startswith("-")]
    matrix = selected or list(faults.ALL_FAULTS)
    failures = []
    for fault in matrix:
        if fault == "device_lost_mid_chunk":
            runner = run_device_lost_mid_chunk
        elif fault in faults.SNAPSHOT_FAULTS:
            runner = run_snapshot_fault
        elif fault in faults.BATCH_FAULTS:
            runner = run_batch_fault
        elif fault in faults.RUNTIME_FAULTS:
            runner = run_runtime_fault
        elif fault in faults.DELTA_FAULTS:
            runner = run_delta_fault
        else:
            raise SystemExit(f"unknown fault class {fault!r} "
                             f"(known: {faults.ALL_FAULTS})")
        try:
            verdict = runner(fault)
            print(f"CHAOS OK   {fault}: {verdict}", flush=True)
        except AssertionError as exc:
            failures.append((fault, str(exc)))
            print(f"CHAOS FAIL {fault}: {exc}", flush=True)
    if overhead:
        measure_overhead()
    print(f"CHAOS SMOKE: {len(matrix) - len(failures)}/{len(matrix)} "
          f"fault classes green (seed {SEED})", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
