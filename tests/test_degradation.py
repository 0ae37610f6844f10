"""Typed failure classification, monotonic backoff, and the degradation
ladder (errorhandler.py + frameworkext.DegradationLadder +
SchedulerService integration).

The full chaos matrix is the tools/chaos_smoke.py CI stage; a
slow-marked test here runs the same matrix so `pytest -m slow` covers
it without double-paying in the fast battery.
"""

import threading
import time

import jax
import numpy as np
import pytest

from koordinator_tpu.api.types import ObjectMeta, Pod
from koordinator_tpu.compilecache import counters
from koordinator_tpu.metrics import Registry
from koordinator_tpu.scheduler.errorhandler import (
    Backoff,
    ErrorHandlerDispatcher,
    FailureClass,
    GuardTripError,
    RetryPolicy,
    TRANSIENT_CLASSES,
    WatchdogStall,
    classify_failure,
    dispatch_batch_errors,
)
from koordinator_tpu.scheduler.frameworkext import (
    DegradationLadder,
    SchedulerService,
)
from koordinator_tpu.scheduler.metrics_defs import SchedulerMetrics
from koordinator_tpu.testing import faults
from koordinator_tpu.utils import synthetic

N, P = 32, 64


# --- classify_failure ------------------------------------------------------

@pytest.mark.parametrize("message,expected", [
    ("RESOURCE_EXHAUSTED: Out of memory allocating 1GB",
     FailureClass.RESOURCE_EXHAUSTED),
    ("Internal: out of memory on device", FailureClass.RESOURCE_EXHAUSTED),
    ("UNAVAILABLE: device lost; socket closed", FailureClass.DEVICE_LOST),
    ("INTERNAL: Mosaic lowering failed", FailureClass.XLA_INTERNAL),
    ("DATA_LOSS: checkpoint corrupt", FailureClass.XLA_INTERNAL),
])
def test_classifier_message_vocabulary(message, expected):
    assert classify_failure(RuntimeError(message)) is expected
    # the real XLA exception type carries the same vocabulary
    assert classify_failure(faults.make_xla_error(message)) is expected


def test_classifier_unrecognized_text():
    # a plain exception with no vocabulary is UNKNOWN...
    assert classify_failure(RuntimeError("something else entirely")) \
        is FailureClass.UNKNOWN
    # ...but the same text on an XlaRuntimeError is still an XLA
    # runtime failure (the mro-name fallback)
    assert classify_failure(
        faults.make_xla_error("something else entirely")) \
        is FailureClass.XLA_INTERNAL


def test_classifier_typed_exceptions_win():
    assert classify_failure(GuardTripError(0x8)) is FailureClass.GUARD_TRIP
    assert classify_failure(WatchdogStall("cycle over budget")) \
        is FailureClass.WATCHDOG_STALL
    assert classify_failure(TimeoutError()) is FailureClass.WATCHDOG_STALL
    # an XlaRuntimeError with unrecognized text is still an XLA failure
    assert classify_failure(faults.make_xla_error("weird new status")) \
        is FailureClass.XLA_INTERNAL


def test_oom_is_not_transient():
    """Retrying the identical program after an OOM OOMs identically —
    only degrading (chunk halving) helps, so the ladder must see it
    immediately."""
    assert FailureClass.RESOURCE_EXHAUSTED not in TRANSIENT_CLASSES
    assert FailureClass.XLA_INTERNAL in TRANSIENT_CLASSES


# --- Backoff: monotonic bookkeeping (ISSUE 13 satellite) -------------------

def test_backoff_delays_grow_and_stay_bounded():
    b = Backoff(RetryPolicy(max_attempts=5, base_seconds=0.1,
                            multiplier=2.0, max_seconds=0.5,
                            jitter_frac=0.25), clock=lambda: 0.0, seed=1)
    delays = [b.next_delay() for _ in range(5)]
    assert b.exhausted()
    for i, d in enumerate(delays):
        nominal = min(0.1 * 2.0 ** i, 0.5)
        assert 0.0 <= d <= nominal * 1.25 + 1e-9
        assert d >= nominal * 0.75 - 1e-9
    # the jittered sequence trends upward overall
    assert delays[-1] > delays[0]


def test_backoff_never_negative_under_clock_steps():
    """The pin behind the time.monotonic switch: a clock that jumps
    BACKWARD mid-retry (the wall-clock NTP/DST failure mode) must not
    produce a negative window — delays derive from the attempt count,
    and remaining() clamps at zero."""
    now = {"t": 1000.0}
    b = Backoff(RetryPolicy(base_seconds=0.2), clock=lambda: now["t"],
                seed=2)
    d = b.next_delay()
    assert d >= 0.0
    assert b.remaining() > 0.0
    now["t"] -= 3600.0  # the clock steps an hour backwards
    assert b.remaining() >= 0.0  # never negative
    assert b.next_delay() >= 0.0
    now["t"] += 7200.0  # and far forwards: window simply expired
    assert b.remaining() == 0.0


def test_backoff_reset_restores_the_budget():
    b = Backoff(RetryPolicy(max_attempts=2), clock=lambda: 0.0)
    b.next_delay()
    b.next_delay()
    assert b.exhausted()
    b.reset()
    assert not b.exhausted() and b.remaining() == 0.0


# --- DegradationLadder unit transitions ------------------------------------

def test_ladder_oom_jumps_to_chunking_and_halves():
    lad = DegradationLadder(max_chunk_splits=3)
    assert lad.on_failure(FailureClass.RESOURCE_EXHAUSTED, probing=False)
    assert (lad.level, lad.chunk_splits) == (DegradationLadder.L_CHUNKED, 1)
    assert lad.on_failure(FailureClass.RESOURCE_EXHAUSTED, probing=False)
    assert lad.chunk_splits == 2
    lad.on_failure(FailureClass.RESOURCE_EXHAUSTED, probing=False)
    assert lad.chunk_splits == 3
    # the ladder is finite: past max splits there is no lower rung
    assert not lad.on_failure(FailureClass.RESOURCE_EXHAUSTED,
                              probing=False)


def test_ladder_device_lost_jumps_to_single_device():
    """Without survivor visibility (or with < 2 survivors) the mesh is
    abandoned — the conservative pre-ISSUE-14 behavior stays."""
    lad = DegradationLadder()
    assert lad.on_failure(FailureClass.DEVICE_LOST, probing=False)
    assert lad.level == DegradationLadder.L_SINGLE_DEVICE
    assert not lad.on_failure(FailureClass.DEVICE_LOST, probing=False)
    lad2 = DegradationLadder()
    assert lad2.on_failure(FailureClass.DEVICE_LOST, probing=False,
                           survivors=1)
    assert lad2.level == DegradationLadder.L_SINGLE_DEVICE


def test_ladder_device_lost_with_survivors_shrinks_the_mesh():
    """>= 2 survivors earn the mesh-shrink rung; a SECOND device loss
    there falls to single_device (monotone)."""
    lad = DegradationLadder()
    assert lad.on_failure(FailureClass.DEVICE_LOST, probing=False,
                          survivors=7)
    assert lad.level == DegradationLadder.L_MESH_SHRINK
    assert lad.state().mesh_shrink and not lad.state().single_device
    assert lad.on_failure(FailureClass.DEVICE_LOST, probing=False,
                          survivors=6)
    assert lad.level == DegradationLadder.L_SINGLE_DEVICE
    # chunking in force is KEPT across the shrink
    lad2 = DegradationLadder()
    lad2.on_failure(FailureClass.RESOURCE_EXHAUSTED, probing=False)
    lad2.on_failure(FailureClass.DEVICE_LOST, probing=False, survivors=4)
    assert lad2.state().label() == "mesh_shrink/2^1"


def test_ladder_generic_failures_skip_the_mesh_shrink_rung():
    """mesh_shrink is the DEVICE_LOST rung: a generic failure past
    chunking goes straight to single_device (shrinking a mesh with no
    lost device is meaningless)."""
    lad = DegradationLadder()
    lad.level = DegradationLadder.L_CHUNKED
    lad.chunk_splits = 1
    assert lad.on_failure(FailureClass.XLA_INTERNAL, probing=False)
    assert lad.level == DegradationLadder.L_SINGLE_DEVICE


def test_ladder_probe_from_mesh_shrink_restores_the_full_mesh():
    lad = DegradationLadder(probe_after=1)
    lad.on_failure(FailureClass.DEVICE_LOST, probing=False, survivors=3)
    lad.on_success(False, lad.state())
    state, probing = lad.begin_cycle()
    # chunk-free mesh_shrink probes past the chunked rung entirely
    assert probing and state.level == DegradationLadder.L_NO_CASCADE
    lad2 = DegradationLadder(probe_after=1)
    lad2.on_failure(FailureClass.RESOURCE_EXHAUSTED, probing=False)
    lad2.on_failure(FailureClass.DEVICE_LOST, probing=False, survivors=3)
    lad2.on_success(False, lad2.state())
    state2, probing2 = lad2.begin_cycle()
    assert probing2 and state2.label() == "chunked/2^1"
    # single_device probes to mesh_shrink first (gentler re-entry)
    lad3 = DegradationLadder(probe_after=1)
    lad3.on_failure(FailureClass.DEVICE_LOST, probing=False)
    lad3.on_success(False, lad3.state())
    state3, probing3 = lad3.begin_cycle()
    assert probing3 and state3.level == DegradationLadder.L_MESH_SHRINK


def test_ladder_generic_failures_step_one_rung():
    lad = DegradationLadder()
    path = []
    while lad.on_failure(FailureClass.XLA_INTERNAL, probing=False):
        path.append(lad.state().label())
    assert path == ["no_cascade", "chunked/2^1", "single_device/2^1"]


def test_ladder_probes_up_after_clean_streak():
    lad = DegradationLadder(probe_after=3)
    lad.on_failure(FailureClass.RESOURCE_EXHAUSTED, probing=False)
    lad.on_failure(FailureClass.RESOURCE_EXHAUSTED, probing=False)
    assert lad.state().label() == "chunked/2^2"
    labels = []
    for _ in range(30):
        state, probing = lad.begin_cycle()
        if probing:
            labels.append(state.label())
        lad.on_success(probing, state)
        if lad.level == DegradationLadder.L_NORMAL:
            break
    # one rung at a time, each earned by a fresh clean streak
    assert labels == ["chunked/2^1", "no_cascade", "normal"]
    assert lad.level == DegradationLadder.L_NORMAL


def test_ladder_failed_probe_falls_back_without_degrading():
    lad = DegradationLadder(probe_after=1)
    lad.on_failure(FailureClass.XLA_INTERNAL, probing=False)
    lad.on_success(False, lad.state())
    state, probing = lad.begin_cycle()
    assert probing and state.level == DegradationLadder.L_NORMAL
    lad.on_failure(FailureClass.XLA_INTERNAL, probing=True)
    # still at the pre-probe rung, streak restarted
    assert lad.level == DegradationLadder.L_NO_CASCADE
    assert lad.clean_streak == 0
    assert lad.begin_cycle()[1] is False


# --- error-chain drain -----------------------------------------------------

def test_dispatch_infra_mask_routes_as_infrastructure_error():
    seen = []
    d = ErrorHandlerDispatcher()
    d.set_default_handler(
        lambda info, err: seen.append((info.pod.meta.name,
                                       err.unschedulable)))
    pods = [Pod(meta=ObjectMeta(name=f"p{i}")) for i in range(3)]
    assignment = np.asarray([-1, -1, 2])
    valid = np.asarray([True, True, True])
    infra = np.asarray([True, False, True])
    n = dispatch_batch_errors(d, assignment, valid, pods,
                              infra_mask=infra)
    assert n == 2
    # p0 quarantined -> infrastructure (retry hard, never preempt);
    # p1 plain no-fit -> unschedulable; p2 placed -> not dispatched
    assert seen == [("p0", False), ("p1", True)]


# --- service integration ---------------------------------------------------

def make_service(**kw):
    svc = SchedulerService(metrics=SchedulerMetrics(Registry()),
                           num_rounds=2, k_choices=4, **kw)
    svc._sleep = lambda _s: None
    return svc


def slim_inputs(seed=0):
    snap = synthetic.synthetic_cluster(N, seed=seed, num_quotas=4,
                                       num_gangs=4)
    pods = synthetic.synthetic_pods(P, seed=seed + 3, num_quotas=4,
                                    num_gangs=4)
    return snap, pods


def test_service_oom_degrades_to_chunked_and_conforms():
    snap, pods = slim_inputs(1)
    inj = faults.FaultInjector(5)
    svc = make_service()
    svc.publish(snap)
    svc.fault_injection = inj.oom_above(P // 2)
    res = svc.schedule(pods)
    assert svc.ladder.level == DegradationLadder.L_CHUNKED
    assert svc.metrics.failures_classified.labels(
        "resource_exhausted").get() >= 1
    assert svc.metrics.degraded_cycles.labels(
        svc.last_ladder_state.label()).get() == 1
    # chunked placements == a clean service FORCED to the same rung
    oracle = make_service()
    oracle.ladder.level = svc.ladder.level
    oracle.ladder.chunk_splits = svc.ladder.chunk_splits
    oracle.publish(snap)
    np.testing.assert_array_equal(
        np.asarray(res.assignment),
        np.asarray(oracle.schedule(pods).assignment))


def test_service_transient_retries_in_place():
    snap, pods = slim_inputs(2)
    inj = faults.FaultInjector(7)
    svc = make_service()
    svc.publish(snap)
    svc.fault_injection = inj.xla_transient(fail_attempts={1, 2})
    res = svc.schedule(pods)
    assert svc.ladder.level == DegradationLadder.L_NORMAL
    assert svc.metrics.failures_classified.labels(
        "xla_internal").get() == 2
    # after the retries the cycle is the plain program, bit-identical
    oracle = make_service()
    oracle.publish(snap)
    np.testing.assert_array_equal(
        np.asarray(res.assignment),
        np.asarray(oracle.schedule(pods).assignment))


def test_service_device_lost_resumes_on_the_shrunk_mesh():
    """ISSUE 14: a device that dies and STAYS dead (until excluded)
    must land the service on the mesh-shrink rung — scheduling over
    the survivors, bit-identical to the healthy program — and probe-up
    must return to the normal rung."""
    import jax

    if jax.device_count() < 3:
        pytest.skip("needs >= 3 devices (conftest forces 8 on CPU)")
    snap, pods = slim_inputs(11)
    inj = faults.FaultInjector(3)
    svc = make_service()
    svc.ladder.probe_after = 1
    svc.fault_injection = inj.lost_device_until_shrunk(after_calls=0)
    survivors = jax.devices()[:-1]
    svc.device_health = lambda: survivors
    svc.publish(snap)
    res = svc.schedule(pods)
    assert svc.ladder.level == DegradationLadder.L_MESH_SHRINK
    assert svc.metrics.mesh_shrink_events.value() == 1
    assert svc.metrics.mesh_size.value() == len(survivors)
    assert svc.summary()["meshSize"] == len(survivors)
    # placements on the shrunk mesh == the no-fault oracle at the same
    # rung == (by the PR 4 mesh conformance) the plain program
    oracle = make_service()
    oracle.ladder.level = DegradationLadder.L_MESH_SHRINK
    oracle.publish(snap)
    np.testing.assert_array_equal(
        np.asarray(res.assignment),
        np.asarray(oracle.schedule(pods).assignment))
    # the committed snapshot keeps REAL shapes (unpadded): the store
    # must not grow pad rows from the shrunk-mesh cycle
    assert int(np.asarray(
        svc.store.current().nodes.schedulable).shape[0]) == N
    # device heals -> probe-up returns to the normal rung
    svc.fault_injection = None
    svc.device_health = None
    for _ in range(6):
        svc.schedule(pods)
        if svc.ladder.level < DegradationLadder.L_MESH_SHRINK:
            break
    assert svc.ladder.level < DegradationLadder.L_MESH_SHRINK
    # the normal rung runs where the committed snapshot lives — still
    # the survivors' mesh until the edge republishes — and the gauge
    # reports those devices, not every visible one
    assert svc.metrics.mesh_size.value() == len(survivors)


def test_service_watchdog_stall_degrades_next_cycle():
    snap, pods = slim_inputs(3)
    svc = make_service()
    svc.publish(snap)
    faults.FaultInjector.stall_watchdog(svc)
    svc.schedule(pods)
    assert svc.monitor.timeouts >= 1
    assert svc.ladder.level == DegradationLadder.L_NO_CASCADE
    svc.monitor.timeout = 30.0
    svc.schedule(pods)  # next cycle runs degraded and completes
    assert svc.metrics.degraded_cycles.labels("no_cascade").get() == 1


def _warm_service(seed):
    """A service whose cycle program is already compiled, with a 1 s
    watchdog: the next cycle of `pods` compiles nothing of its own."""
    snap, pods = slim_inputs(seed)
    svc = make_service()
    svc.publish(snap)
    svc.schedule(pods)
    svc.monitor.timeout = 1.0
    return svc, pods


def _report_compile(seconds):
    jax.monitoring.record_event_duration_secs(
        counters.DURATION_BACKEND_COMPILE, seconds)


def test_watchdog_ignores_a_long_compile_on_the_cycle_thread():
    svc, pods = _warm_service(11)

    def compiling(_state, _batch):
        # 1.5 s spent compiling on the cycle's own thread: a cold start
        time.sleep(1.5)
        _report_compile(1.5)

    svc.fault_injection = compiling
    svc.schedule(pods)
    assert svc.last_elapsed > svc.monitor.timeout
    assert svc.monitor.timeouts == 0
    assert svc.ladder.level == DegradationLadder.L_NORMAL \
        and not svc.ladder.transitions


def test_watchdog_trips_on_a_stall_while_another_thread_compiles():
    svc, pods = _warm_service(12)

    def stalled(_state, _batch):
        # another thread compiles for a minute while this cycle stalls:
        # that compile is not this cycle's and excuses nothing
        other = threading.Thread(target=_report_compile, args=(60.0,))
        other.start()
        other.join()
        time.sleep(1.5)

    svc.fault_injection = stalled
    svc.schedule(pods)
    assert svc.monitor.timeouts == 1
    assert svc.ladder.level == DegradationLadder.L_NO_CASCADE


def test_service_exhausted_ladder_raises_the_classified_failure():
    snap, pods = slim_inputs(4)
    svc = make_service(max_cycle_attempts=20)
    svc.publish(snap)
    svc.fault_injection = faults.FaultInjector(9).oom_above(0)  # every width
    with pytest.raises(Exception) as exc_info:
        svc.schedule(pods)
    assert classify_failure(exc_info.value) \
        is FailureClass.RESOURCE_EXHAUSTED
    # the ladder bottomed out trying: chunking reached its max
    assert svc.ladder.chunk_splits == svc.ladder.max_chunk_splits


def test_summary_exposes_resilience_state():
    snap, pods = slim_inputs(5)
    svc = make_service()
    svc.publish(snap)
    svc.schedule(pods)
    s = svc.summary()
    assert s["degradationLevel"] == "normal"
    assert s["ladderTransitions"] == 0
    assert s["lastHealthWord"] == 0


def test_service_never_retries_past_the_commit():
    """A failure AFTER the snapshot commit (the on_assumed user hook)
    must propagate, never re-enter the retry loop: re-running the cycle
    would schedule the same batch against its own post-commit snapshot
    and double-charge every placement."""
    from koordinator_tpu.api.types import ObjectMeta as OM, Pod as P_

    snap, pods = slim_inputs(6)
    svc = make_service()
    svc.publish(snap)
    calls = {"n": 0}

    def exploding_hook(_assignment, _typed, _result):
        calls["n"] += 1
        raise RuntimeError("assume cache wiring broke")  # class UNKNOWN

    svc.on_assumed = exploding_hook
    typed = [P_(meta=OM(name=f"p{i}")) for i in range(P)]
    requested_before = np.asarray(svc.store.current().nodes.requested)
    with pytest.raises(RuntimeError, match="assume cache wiring broke"):
        svc.schedule(pods, typed_pods=typed)
    # exactly ONE program ran (no transient retry), and exactly one
    # commit landed — not a double-charge
    assert calls["n"] == 1
    requested_after = np.asarray(svc.store.current().nodes.requested)
    assert (requested_after >= requested_before - 1e-3).all()
    svc.on_assumed = None
    oracle = make_service()
    oracle.publish(snap)
    oracle.schedule(pods)
    np.testing.assert_allclose(
        requested_after,
        np.asarray(oracle.store.current().nodes.requested))


def test_quarantine_converges_for_capacity_defects():
    """An overcommitted row is clamped by the scrub, so the COMMITTED
    snapshot no longer trips the guard: one fault = one trip, not a
    per-cycle alarm storm in a long-lived service."""
    snap, pods = slim_inputs(7)
    inj = faults.FaultInjector(31)
    bad_snap, rows = inj.corrupt_snapshot(snap, "overcommit_row")
    svc = make_service()
    svc.publish(bad_snap)
    svc.schedule(pods)
    assert svc.last_health_word != 0
    trips = svc.metrics.guard_trips.labels("node_overcommit").get()
    svc.schedule(pods)
    assert svc.last_health_word == 0, "guard re-tripped on the " \
        "already-quarantined snapshot"
    assert svc.metrics.guard_trips.labels("node_overcommit").get() == trips
    # the node STAYS quarantined until a fresh publish
    assert not np.asarray(svc.store.current().nodes.schedulable)[rows].any()


# --- the full chaos matrix, slow-marked ------------------------------------

@pytest.mark.slow
def test_full_chaos_matrix():
    """The same matrix tools/chaos_smoke.py runs as a CI stage (per
    fault class: detected, quarantined, service up, clean rows
    bit-identical to the oracle)."""
    import tools.chaos_smoke as chaos

    assert chaos.main([]) == 0
