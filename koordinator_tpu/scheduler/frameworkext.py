"""frameworkext: the extender seam around the batched scheduling core —
cycle watchdog, live score introspection, plugin service endpoints, and the
sidecar-facing scheduler service.

Capability parity with pkg/scheduler/frameworkext (SURVEY.md 2.1):
- SchedulerMonitor (scheduler_monitor.go:40-52): records each batch's
  start; a completion past the timeout logs a warning and increments a
  counter; overdue in-flight cycles are queryable (the watchdog thread).
- Debug score tables (debug.go:42-59): when enabled, every scheduled batch
  dumps a pretty-printed top-N nodes-by-score table per pod — the direct
  fixture for eyeballing the TPU score matrix.
- Services (services/): every registered provider serves its summary at
  /apis/v1/plugins/<name> on a plain HTTP endpoint; /debug/flags/s toggles
  the score dump at runtime like the reference's DebugScoresSetter.
- SchedulerService: the seam the control-plane edge calls (the gRPC
  sidecar boundary per BASELINE.json): holds the SnapshotStore, schedules
  pod batches chunk-by-chunk against the current snapshot, publishes the
  post-commit snapshot, and reports through the monitor/debug hooks.
- Resilience layer (docs/DESIGN.md "Failure model & degradation
  ladder"): device health guards fused into every batch program
  (scheduler/guards.py), typed failure classification with bounded
  monotonic backoff (errorhandler.classify_failure/Backoff), and the
  DegradationLadder below — the explicit rungs between "all healthy"
  and "crash", with automatic probing back up after clean cycles.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu.utils.httpserver import (
    BackgroundHTTPServer,
    QuietJsonHandler,
)

from koordinator_tpu.compilecache import counters as compile_counters
from koordinator_tpu.metrics import kernel_timer
from koordinator_tpu.obs import phases as obs_phases
from koordinator_tpu.obs.memwatch import MemWatch
from koordinator_tpu.obs.slo import SloTracker
from koordinator_tpu.obs.trace import NOOP_SPAN, Tracer
from koordinator_tpu.scheduler import core, guards
from koordinator_tpu.scheduler.errorhandler import (
    Backoff,
    FailureClass,
    RetryPolicy,
    TRANSIENT_CLASSES,
    classify_failure,
)
from koordinator_tpu.scheduler.journal import JournalConflict
from koordinator_tpu.scheduler.metrics_defs import SchedulerMetrics
from koordinator_tpu.scheduler.plugins.loadaware import LoadAwareConfig
from koordinator_tpu.snapshot import hostbatch
from koordinator_tpu.snapshot.schema import ClusterSnapshot, PodBatch
from koordinator_tpu.snapshot.store import SnapshotStore
from koordinator_tpu.utils.sync import guarded_by

log = logging.getLogger(__name__)


def _spanned_devices(snap: ClusterSnapshot) -> int:
    """How many devices the snapshot's node columns span (1 for host
    arrays, which run on the default device)."""
    sharding = getattr(snap.nodes.allocatable, "sharding", None)
    return len(sharding.device_set) if sharding is not None else 1


def _home_device(snap: ClusterSnapshot):
    """Where a single-device snapshot's program runs: the device its
    columns are committed to, else None (host or uncommitted arrays run
    on the default device). An upload placed there keeps the program's
    signature the snapshot's own, cycle after cycle."""
    col = snap.nodes.allocatable
    if isinstance(col, jax.Array) and col.committed:
        return next(iter(col.sharding.device_set))
    return None


@guarded_by(
    _inflight="_lock",
    _compiled_before="_lock",
    _seq="_lock",
    timeouts="_lock",
    timeout="publish-once",
    metrics="publish-once",
)
class SchedulerMonitor:
    """Per-batch cycle watchdog. A cycle trips it when its elapsed time,
    less the XLA compile seconds spent on the cycle's own thread,
    exceeds `timeout`: a cold compile (39 s for the node-sharded
    full-gate program on a v5e-4) is not a device stall, and a stall
    that overlaps another thread's compile still trips. Start and close
    a cycle on the same thread."""

    def __init__(self, timeout_seconds: float = 30.0,
                 metrics: Optional[SchedulerMetrics] = None):
        self.timeout = timeout_seconds
        self.timeouts = 0
        self.metrics = metrics
        self._lock = threading.Lock()
        self._inflight: Dict[int, float] = {}
        self._compiled_before: Dict[int, float] = {}
        self._seq = 0
        compile_counters.install()

    def start_cycle(self, now: Optional[float] = None) -> int:
        now = time.monotonic() if now is None else now
        compiled = compile_counters.thread_compile_seconds()
        with self._lock:
            self._seq += 1
            self._inflight[self._seq] = now
            self._compiled_before[self._seq] = compiled
            return self._seq

    def complete_cycle(self, token: int, now: Optional[float] = None
                       ) -> Tuple[float, bool]:
        """Close the cycle: (elapsed seconds, whether it stalled)."""
        now = time.monotonic() if now is None else now
        compiled = compile_counters.thread_compile_seconds()
        with self._lock:
            started = self._inflight.pop(token, now)
            compiled -= self._compiled_before.pop(token, compiled)
            elapsed = now - started
            stalled = elapsed - compiled > self.timeout
            if stalled:
                # inside the lock: concurrent sidecar cycles would
                # otherwise lose timeout increments
                self.timeouts += 1
        if stalled:
            if self.metrics is not None:
                self.metrics.scheduling_timeout.labels("default").inc()
            log.warning("scheduling cycle exceeded %.0fs: %.2fs "
                        "(%.2fs of it compiling)", self.timeout, elapsed,
                        compiled)
        return elapsed, stalled

    def overdue(self, now: Optional[float] = None) -> List[int]:
        now = time.monotonic() if now is None else now
        with self._lock:
            return [t for t, s in self._inflight.items()
                    if now - s > self.timeout]


class _CommittedCycleError(Exception):
    """A failure AFTER a cycle's snapshot commit (post-commit hooks):
    terminal by construction — retrying would schedule the same batch
    against its own post-commit snapshot and double-charge every
    placement. schedule() unwraps and re-raises the cause."""

    def __init__(self, cause: BaseException):
        super().__init__(str(cause))
        self.cause = cause


@dataclasses.dataclass(frozen=True)
class LadderState:
    """The configuration one scheduling cycle runs at."""

    level: int = 0         # index into DegradationLadder.LEVELS
    chunk_splits: int = 0  # batch scheduled as 2**splits sequential chunks

    @property
    def cascade_off(self) -> bool:
        return self.level >= DegradationLadder.L_NO_CASCADE

    @property
    def chunked(self) -> bool:
        return self.chunk_splits > 0

    @property
    def mesh_shrink(self) -> bool:
        return self.level == DegradationLadder.L_MESH_SHRINK

    @property
    def single_device(self) -> bool:
        return self.level >= DegradationLadder.L_SINGLE_DEVICE

    @property
    def degraded(self) -> bool:
        return self.level > 0 or self.chunk_splits > 0

    def label(self) -> str:
        name = DegradationLadder.LEVELS[self.level]
        if self.chunk_splits > 0:
            name += f"/2^{self.chunk_splits}"
        return name


@guarded_by(
    # see the class docstring: the ladder is cycle machinery — the
    # service mutates it only between program attempts of one cycle
    level="confined",
    chunk_splits="confined",
    clean_streak="confined",
    degraded_cycles="confined",
    transitions="confined",
    probe_after="publish-once",
    max_chunk_splits="publish-once",
)
class DegradationLadder:
    """The explicit ladder between "all healthy" and "crash".

    Rungs, in degradation order (each rung keeps the degradations of the
    rungs above it):
      normal        -> the caller's full configuration
      no_cascade    -> cascade=False: the conformance-oracle program —
                       structurally simpler (no stage-2 narrowing), the
                       first thing to try when the full program misbehaves
      chunked       -> the batch runs as 2**chunk_splits sequential
                       sub-batches (counts and the snapshot carried
                       chunk-to-chunk); each further OOM halves again
      mesh_shrink   -> the cycle runs on a mesh rebuilt over the
                       SURVIVING devices (parallel/mesh.py pad helpers
                       re-shard the snapshot per cycle); placements
                       stay bit-identical to the full-mesh program —
                       losing 1 of 8 chips costs capacity, not a whole
                       mesh. Reached only by DEVICE_LOST with >= 2
                       survivors; a probe-up restores the full mesh.
      single_device -> inputs pinned to device 0 (the mesh is
                       abandoned until the fleet heals)

    Transitions are keyed on FailureClass: RESOURCE_EXHAUSTED jumps
    straight to chunking (retrying an identical OOM is useless),
    DEVICE_LOST goes to mesh-shrink when >= 2 devices survive (else
    single-device), everything else steps one rung — skipping
    mesh_shrink, which is meaningless without a lost device. After
    `probe_after` consecutive clean cycles below normal, ONE cycle
    probes the rung above; success commits the promotion, failure falls
    straight back (and the streak restarts). Every transition is
    recorded so the chaos matrix can assert the exact path taken.

    Not thread-safe by itself: the service mutates it only while holding
    its cycle machinery (transitions happen between program attempts).
    """

    LEVELS = ("normal", "no_cascade", "chunked", "mesh_shrink",
              "single_device")
    (L_NORMAL, L_NO_CASCADE, L_CHUNKED, L_MESH_SHRINK,
     L_SINGLE_DEVICE) = range(5)

    def __init__(self, probe_after: int = 8, max_chunk_splits: int = 4):
        self.probe_after = probe_after
        self.max_chunk_splits = max_chunk_splits
        self.level = self.L_NORMAL
        self.chunk_splits = 0
        self.clean_streak = 0
        self.degraded_cycles = 0
        self.transitions: List[Tuple[str, str]] = []  # (cause, new label)

    def state(self) -> LadderState:
        return LadderState(self.level, self.chunk_splits)

    def _probe_target(self) -> LadderState:
        if self.level == self.L_CHUNKED and self.chunk_splits > 1:
            return LadderState(self.level, self.chunk_splits - 1)
        if self.level == self.L_SINGLE_DEVICE:
            return LadderState(self.L_MESH_SHRINK, self.chunk_splits)
        if self.level == self.L_MESH_SHRINK:
            # the probe that restores the FULL mesh: back to the
            # chunked rung when chunking was in force, else straight
            # past it (a chunk-free mesh_shrink never chunked)
            if self.chunk_splits > 0:
                return LadderState(self.L_CHUNKED, self.chunk_splits)
            return LadderState(self.L_NO_CASCADE, 0)
        if self.level == self.L_CHUNKED:
            return LadderState(self.L_NO_CASCADE, 0)
        return LadderState(max(self.level - 1, 0), 0)

    def begin_cycle(self) -> Tuple[LadderState, bool]:
        """-> (state to run at, whether this cycle is an up-probe)."""
        if self.level > self.L_NORMAL \
                and self.clean_streak >= self.probe_after:
            return self._probe_target(), True
        return self.state(), False

    def on_success(self, probing: bool, state: LadderState) -> None:
        if probing:
            # commit the promotion; earn the next probe from scratch
            self._transition("probe_up", state)
            self.clean_streak = 0
        else:
            self.clean_streak += 1

    def on_failure(self, fc: FailureClass, probing: bool,
                   survivors: Optional[int] = None) -> bool:
        """Degrade for the failure class; returns False when there is no
        lower rung left (the caller re-raises). A failed PROBE is not a
        degradation — the pre-probe state simply stays. `survivors` is
        the surviving-device count the service observed for a
        DEVICE_LOST failure: >= 2 earns the mesh-shrink rung instead of
        abandoning the mesh outright (None — a caller without device
        visibility — degrades conservatively to single-device)."""
        self.clean_streak = 0
        if probing:
            return True
        if fc is FailureClass.RESOURCE_EXHAUSTED:
            if self.level < self.L_CHUNKED:
                nxt = LadderState(self.L_CHUNKED, 1)
            elif self.chunk_splits < self.max_chunk_splits:
                nxt = LadderState(self.level, self.chunk_splits + 1)
            else:
                return False
        elif fc is FailureClass.DEVICE_LOST:
            if survivors is not None and survivors >= 2 \
                    and self.level < self.L_MESH_SHRINK:
                nxt = LadderState(self.L_MESH_SHRINK, self.chunk_splits)
            elif self.level >= self.L_SINGLE_DEVICE:
                return False
            else:
                nxt = LadderState(self.L_SINGLE_DEVICE, self.chunk_splits)
        else:
            if self.level >= self.L_SINGLE_DEVICE:
                return False
            new_level = self.level + 1
            if new_level == self.L_MESH_SHRINK:
                # mesh_shrink is the DEVICE_LOST rung; a generic
                # failure that already exhausted chunking goes past it
                new_level = self.L_SINGLE_DEVICE
            nxt = LadderState(
                new_level,
                max(self.chunk_splits, 1)
                if new_level >= self.L_CHUNKED else self.chunk_splits)
        self._transition(fc.value, nxt)
        return True

    def _transition(self, cause: str, nxt: LadderState) -> None:
        self.level = nxt.level
        self.chunk_splits = nxt.chunk_splits
        self.transitions.append((cause, nxt.label()))


def debug_score_table(snap: ClusterSnapshot, pods: PodBatch,
                      cfg: LoadAwareConfig, top_n: int = 5,
                      pod_names: Optional[List[str]] = None) -> str:
    """Top-N nodes by summed plugin score per pod (debug.go:61
    debugScores) recomputed from the snapshot with the same kernels the
    commit loop uses."""
    from koordinator_tpu.scheduler.plugins import (
        deviceshare,
        loadaware,
        numaaware,
    )

    scores = np.asarray(loadaware.score_matrix(snap.nodes, pods, cfg))
    scores = scores + np.asarray(numaaware.numa_score_matrix(
        snap.nodes, pods))
    if snap.devices.gpu_free.shape[1] > 0:
        scores = scores + np.asarray(
            deviceshare.score_matrix(snap.devices, pods))
    feasible = (np.asarray(loadaware.filter_mask(snap.nodes, pods, cfg))
                & np.asarray(snap.nodes.schedulable)[None, :])
    forbid, penalty = _taint_matrices(snap, pods)
    if forbid is not None:
        feasible &= ~forbid
        scores = np.maximum(scores - penalty, 0.0)
    scores = np.where(feasible, scores, -1.0)
    lines = []
    p = pods.num_pods
    for i in range(p):
        name = pod_names[i] if pod_names else f"pod[{i}]"
        order = np.argsort(-scores[i])[:top_n]
        cells = " | ".join(f"node{int(n)}:{scores[i, n]:.1f}"
                           for n in order if scores[i, n] >= 0)
        lines.append(f"{name:<24} | {cells}")
    header = f"{'pod':<24} | top-{top_n} nodes by score"
    return "\n".join([header, "-" * len(header)] + lines)


def _taint_matrices(snap: ClusterSnapshot, pods: PodBatch):
    """(forbid [P, N], penalty [P, N]) from the TaintToleration matrices,
    or (None, None) for a batch without taint modeling — the same math
    the batch kernel applies (core.py use_taints block)."""
    if not pods.has_taints:
        return None, None
    tid = np.maximum(np.asarray(pods.toleration_id), 0)
    tg = np.asarray(snap.nodes.taint_group)
    forbid = np.asarray(pods.tol_forbid)[tid][:, tg]
    prefer = np.asarray(pods.tol_prefer)[tid][:, tg]
    max_cnt = max(float(np.asarray(pods.tol_prefer).max()), 1.0)
    from koordinator_tpu.scheduler.batching import MAX_NODE_SCORE
    return forbid, prefer / max_cnt * MAX_NODE_SCORE


def debug_filter_table(snap: ClusterSnapshot, pods: PodBatch,
                       cfg: LoadAwareConfig,
                       pod_names: Optional[List[str]] = None) -> str:
    """Per-pod filter diagnosis (debug.go DebugFiltersSetter
    /debug/flags/f): how many nodes each gate rejects, recomputed from
    the snapshot with the same prefilter kernels the batch uses — the
    per-plugin failure breakdown the reference prints per pod."""
    from koordinator_tpu.scheduler.plugins import (
        deviceshare,
        loadaware,
        numaaware,
    )

    nodes = snap.nodes
    n = int(nodes.num_nodes)
    gates: List[tuple] = []
    gates.append(("Unschedulable",
                  np.broadcast_to(np.asarray(nodes.schedulable)[None, :],
                                  (pods.num_pods, n))))
    alloc = np.asarray(nodes.allocatable)
    req = np.asarray(pods.requests)
    fit = np.all(req[:, None, :] + np.asarray(nodes.requested)[None]
                 <= alloc[None] + 1e-3, axis=-1)
    gates.append(("NodeResourcesFit", fit))
    gates.append(("LoadAwareScheduling",
                  np.asarray(loadaware.filter_mask(nodes, pods, cfg))))
    forbid, _ = _taint_matrices(snap, pods)
    if forbid is not None:
        gates.append(("TaintToleration", ~forbid))
    if pods.has_spread:
        # carrier-matrix gating (multi-constraint pods) — mirrors core
        dom_all = np.asarray(pods.spread_domain)           # [Sg, N]
        counts = np.asarray(pods.spread_count0)
        dvalid = np.asarray(pods.spread_dvalid)
        skew = np.asarray(pods.spread_max_skew)
        soft = ~np.isfinite(skew)
        min_c = np.min(np.where(dvalid, counts, np.inf), axis=1)
        min_c = np.where(np.isfinite(min_c), min_c, 0.0)
        cnt_at = np.where(dom_all >= 0,
                          np.take_along_axis(counts,
                                             np.maximum(dom_all, 0),
                                             axis=1), 0.0)
        ok_map = soft[:, None] | ((dom_all >= 0)
                                  & (cnt_at + 1.0 - min_c[:, None]
                                     <= skew[:, None] + 1e-3))
        blocked = (np.asarray(pods.spread_carrier).astype(float)
                   @ (~ok_map).astype(float)) > 0.5
        gates.append(("PodTopologySpread", ~blocked))
    if pods.has_anti:
        # (a) per-group occupancy gated by the CARRIER matrix (a pod
        # carrying several terms is gated by each — mirrors core.py)
        dom_all = np.asarray(pods.anti_domain)
        occ_a = np.where(dom_all >= 0,
                         np.take_along_axis(
                             np.asarray(pods.anti_count0),
                             np.maximum(dom_all, 0), axis=1), 0.0) > 0.5
        blocked_a = (np.asarray(pods.anti_carrier).astype(float)
                     @ occ_a.astype(float)) > 0.5
        # direction (b): matching pods avoid carrier domains
        carr = np.asarray(pods.anti_carrier_count0)
        occ = np.where(dom_all >= 0,
                       np.take_along_axis(carr, np.maximum(dom_all, 0),
                                          axis=1), 0.0) > 0.5
        blocked = (np.asarray(pods.anti_member).astype(float)
                   @ occ.astype(float)) > 0.5
        gates.append(("InterPodAntiAffinity", ~blocked_a & ~blocked))
    if pods.has_aff:
        # carrier-matrix gating with per-(pod, group) bootstrap
        dom_all = np.asarray(pods.aff_domain)              # [Fg, N]
        counts = np.asarray(pods.aff_count0)
        carrier = np.asarray(pods.aff_carrier)
        member = np.asarray(pods.aff_member)
        total = counts.sum(axis=1)
        cc_map = np.where(dom_all >= 0,
                          np.take_along_axis(counts,
                                             np.maximum(dom_all, 0),
                                             axis=1), 0.0)
        boot_pg = carrier & member & (total < 0.5)[None, :]
        bad_nonboot = ((dom_all < 0) | (cc_map <= 0.5)).astype(float)
        bad_boot = (dom_all < 0).astype(float)
        blocked = ((carrier & ~boot_pg).astype(float) @ bad_nonboot
                   + boot_pg.astype(float) @ bad_boot) > 0.5
        gates.append(("InterPodAffinity", ~blocked))
    if np.asarray(nodes.numa_valid).any():
        gates.append(("NodeNUMAResource",
                      np.asarray(numaaware.zone_prefilter(nodes, pods))))
    if snap.devices.gpu_free.shape[1] > 0:
        gates.append(("DeviceShare",
                      np.asarray(deviceshare.prefilter(snap.devices,
                                                       pods))))
    lines = []
    for i in range(pods.num_pods):
        name = pod_names[i] if pod_names else f"pod[{i}]"
        feasible = np.ones((n,), bool)
        cells = []
        for gate_name, mask in gates:
            rejected = int((~mask[i] & feasible).sum())
            feasible &= mask[i]
            if rejected:
                cells.append(f"{gate_name}:-{rejected}")
        cells.append(f"fit:{int(feasible.sum())}/{n}")
        lines.append(f"{name:<24} | {' '.join(cells)}")
    header = f"{'pod':<24} | nodes rejected per gate"
    return "\n".join([header, "-" * len(header)] + lines)


class ServiceRegistry:
    """APIServiceProvider registry: name -> summary() (services.go:44-51)."""

    def __init__(self):
        self._providers: Dict[str, Callable[[], dict]] = {}

    def register(self, name: str, summary: Callable[[], dict]) -> None:
        self._providers[name] = summary

    def names(self) -> List[str]:
        return sorted(self._providers)

    def summary(self, name: str) -> Optional[dict]:
        fn = self._providers.get(name)
        return fn() if fn is not None else None


class DebugFlags:
    """Runtime debug toggles (debug.go DebugScoresSetter /debug/flags/s)."""

    def __init__(self):
        self.score_top_n = 0     # 0 = disabled
        self.filter_dump = False  # /debug/flags/f (DebugFiltersSetter)


class ServicesServer:
    """HTTP endpoint: /apis/v1/plugins/<name> summaries, /debug/flags/s,
    and Prometheus-format /metrics exposition."""

    def __init__(self, registry: ServiceRegistry, flags: DebugFlags,
                 host: str = "127.0.0.1", port: int = 0,
                 metrics_registry=None):
        if metrics_registry is None:
            from koordinator_tpu.metrics import global_registry
            metrics_registry = global_registry()
        registry_ref, flags_ref = registry, flags
        metrics_ref = metrics_registry

        class Handler(QuietJsonHandler):
            def do_GET(self):
                if self.path == "/metrics":
                    self.reply_raw(200, "text/plain; version=0.0.4",
                                   metrics_ref.expose().encode())
                    return
                if self.path == "/apis/v1/plugins":
                    self.reply_json(200, {"plugins": registry_ref.names()})
                    return
                prefix = "/apis/v1/plugins/"
                if self.path.startswith(prefix):
                    summary = registry_ref.summary(self.path[len(prefix):])
                    if summary is None:
                        self.reply_json(404, {"error": "unknown plugin"})
                    else:
                        self.reply_json(200, summary)
                    return
                self.reply_json(404, {"error": "not found"})

            def do_PUT(self):
                if self.path.startswith("/debug/flags/s"):
                    length = int(self.headers.get("Content-Length", 0))
                    raw = self.rfile.read(length).decode().strip()
                    try:
                        flags_ref.score_top_n = int(raw or "0")
                    except ValueError:
                        self.reply_json(400, {"error": f"bad value {raw!r}"})
                        return
                    self.reply_json(200,
                                    {"scoreTopN": flags_ref.score_top_n})
                    return
                if self.path.startswith("/debug/flags/f"):
                    length = int(self.headers.get("Content-Length", 0))
                    raw = self.rfile.read(length).decode().strip().lower()
                    flags_ref.filter_dump = raw in ("1", "true", "on")
                    self.reply_json(200,
                                    {"filterDump": flags_ref.filter_dump})
                    return
                self.reply_json(404, {"error": "not found"})

        self._server = BackgroundHTTPServer(Handler, host, port)
        self.port = self._server.port

    def close(self) -> None:
        self._server.close()


@guarded_by(
    # batch commits: snapshot read -> device program -> publish, plus
    # all journal/epoch bookkeeping, serialize under the commit lock
    epoch="_commit_lock",
    _own_epochs="_commit_lock",
    _forced_chunks="_commit_lock",
    _cycle_digest="_commit_lock",
    _cycle_base_version="_commit_lock",
    _cycle_replayed="_commit_lock",
    _cycle_state="_commit_lock",
    _last_mesh_size="_commit_lock",
    last_committed_version="_commit_lock",
    schedule_kwargs="_commit_lock",
    # post-commit throughput counters get their own cheap lock so
    # readers never queue behind a device program
    batches="_counter_lock",
    pods_placed="_counter_lock",
    # per-thread (version, elapsed) handoff — see last_schedule_info
    _tls="confined",
    # shared last_* observability attrs: torn reads tolerated by
    # design (last_schedule_info is the race-free alternative)
    last_elapsed="racy-monitor",
    last_health_word="racy-monitor",
    last_quarantined_pods="racy-monitor",
    last_ladder_state="racy-monitor",
    last_gang_failed="racy-monitor",
    last_recovery="racy-monitor",
    # wiring, fixed before concurrent traffic starts
    store="publish-once",
    cfg="publish-once",
    metrics="publish-once",
    monitor="publish-once",
    flags="publish-once",
    registry="publish-once",
    auto_pack="publish-once",
    guards_enabled="publish-once",
    max_cycle_attempts="publish-once",
    ladder="publish-once",
    retry_policy="publish-once",
    _sleep="publish-once",
    fault_injection="publish-once",
    journal="publish-once",
    compile_cache="publish-once",
    tracer="publish-once",
    memwatch="publish-once",
    slo="publish-once",
    _cycle_ids="publish-once",
    device_health="publish-once",
    _explicit_amp="publish-once",
    error_dispatcher="publish-once",
    on_gang_failed="publish-once",
    on_assumed="publish-once",
)
class SchedulerService:
    """The sidecar seam: snapshot in, assignments out.

    The control-plane edge publishes snapshots (or functional deltas) into
    the store and feeds pending-pod batches; each batch runs the full
    device program, the post-commit snapshot becomes the next version, and
    the per-cycle watchdog + optional score dump observe every batch.
    """

    def __init__(self, store: Optional[SnapshotStore] = None,
                 cfg: Optional[LoadAwareConfig] = None,
                 monitor: Optional[SchedulerMonitor] = None,
                 flags: Optional[DebugFlags] = None,
                 registry: Optional[ServiceRegistry] = None,
                 metrics: Optional[SchedulerMetrics] = None,
                 ladder: Optional[DegradationLadder] = None,
                 retry_policy: Optional[RetryPolicy] = None,
                 journal=None,
                 compile_cache=None,
                 trace: Optional[Tracer] = None,
                 **schedule_kwargs):
        self.store = store or SnapshotStore()
        self.cfg = cfg if cfg is not None else LoadAwareConfig.make()
        self.metrics = metrics if metrics is not None else SchedulerMetrics()
        self.monitor = monitor or SchedulerMonitor(metrics=self.metrics)
        if self.monitor.metrics is None:
            self.monitor.metrics = self.metrics
        self.flags = flags or DebugFlags()
        self.registry = registry or ServiceRegistry()
        # auto_pack: derive the batching-layer specializations the bench
        # uses — domain classes for same-topologyKey groups, and the
        # topo/numa/gpu prefix packing contracts — per batch, invisibly
        # to callers (results come back in the caller's pod order).
        # Prefix widths are bucketed to powers of two so steady-state
        # traffic compiles a handful of program variants, not one per
        # constrained-count.
        self.auto_pack = bool(schedule_kwargs.pop("auto_pack", True))
        # resilience layer (docs/DESIGN.md "Failure model & degradation
        # ladder"): health guards fused into the batch program, typed
        # failure classification with bounded backoff, and the explicit
        # degradation ladder between "all healthy" and "crash"
        self.guards_enabled = bool(schedule_kwargs.pop("guards", True))
        self.max_cycle_attempts = int(
            schedule_kwargs.pop("max_cycle_attempts", 8))
        self.ladder = ladder or DegradationLadder()
        self.retry_policy = retry_policy or RetryPolicy()
        self._sleep: Callable[[float], None] = time.sleep
        # chaos seam (koordinator_tpu.testing.faults): called with
        # (LadderState, PodBatch) before every program attempt; a raised
        # exception injects a device-program failure deterministically
        self.fault_injection: Optional[Callable] = None
        # crash-recoverable scheduling (docs/DESIGN.md "Crash recovery
        # & mesh elasticity"): an optional CommitJournal makes every
        # chunk commit durable with append-before-publish ordering —
        # committed chunks of an interrupted batch replay bit-identical
        # on resume (in-process retry OR restart via recover()), and
        # uncommitted chunks are simply scheduled. Epochs are assigned
        # per batch under the commit lock, resuming where the journal
        # left off.
        self.journal = journal
        # warm-start layer (docs/DESIGN.md "Compile cache & columnar
        # packing"): an optional, STRICTLY OPT-IN compilecache handle.
        # With one attached, every cycle's device program is ensured
        # through the cache before dispatch — recover() replays and
        # mesh-shrink/chunked rung transitions then reuse AOT-compiled
        # executables (a dict lookup once warm) instead of cold-jitting
        # at the worst possible moment. None (the default) changes
        # nothing: no process-global cache config is ever touched.
        self.compile_cache = compile_cache
        if compile_cache is not None:
            compile_cache.activate()
        # koordtrace (docs/OBSERVABILITY.md): an optional span tracer.
        # None (the default) keeps the dispatch path allocation-free —
        # every span site routes through _span(), which returns the
        # shared NOOP_SPAN singleton when tracing is off. With a tracer
        # attached, closed spans feed scheduler_cycle_phase_seconds and
        # ring overflow feeds scheduler_trace_spans_dropped unless the
        # caller wired its own hooks.
        self.tracer = Tracer() if trace is True else trace
        if self.tracer is not None:
            if self.tracer.observer is None:
                self.tracer.observer = (
                    lambda name, dur:
                    self.metrics.cycle_phase_seconds
                        .labels(name).observe(dur))
            if self.tracer.on_drop is None:
                self.tracer.on_drop = self.metrics.trace_spans_dropped.inc
        # koordcost runtime plane (docs/OBSERVABILITY.md): both knobs
        # STRICTLY OPT-IN, exactly like the tracer — None (the default)
        # adds zero work to the cycle path. memwatch samples device
        # memory at the dispatch/device_wait span boundaries and runs
        # the leak sentinel per committed cycle; slo turns the cycle
        # and placement series into error-budget burn. Both surface
        # through health().
        memwatch = schedule_kwargs.pop("memwatch", None)
        if memwatch is True:
            memwatch = MemWatch(metrics=self.metrics)
        self.memwatch: Optional[MemWatch] = memwatch or None
        slo = schedule_kwargs.pop("slo", None)
        if slo is True:
            slo = SloTracker(self.metrics)
        self.slo: Optional[SloTracker] = slo or None
        # trace cycle ids: a process-monotonic sequence assigned per
        # schedule() call (itertools.count: one atomic bump per cycle)
        self._cycle_ids = itertools.count()
        self.epoch = journal.next_epoch() if journal is not None else 0
        # epochs whose records THIS process appended: a base-version
        # mismatch on one of these is a raced ingest between retry
        # attempts (safe to abandon — nothing published), never a
        # restart mis-rehydration
        self._own_epochs: set = set()
        self._forced_chunks: Optional[int] = None
        self._cycle_digest = 0
        self._cycle_base_version = 0
        self._cycle_replayed = 0
        self.last_recovery: Optional[dict] = None
        # device-loss visibility seam: a health prober returning the
        # SURVIVING jax devices; None = trust the runtime's view. The
        # mesh-shrink rung rebuilds its mesh over exactly this list.
        self.device_health: Optional[Callable[[], list]] = None
        self._last_mesh_size = 1
        self._cycle_state = LadderState()
        self.last_health_word = 0
        self.last_quarantined_pods: Optional[np.ndarray] = None
        self.last_ladder_state = LadderState()
        self.schedule_kwargs = schedule_kwargs
        self._explicit_amp = "enable_amplification" in schedule_kwargs
        self.batches = 0
        self.pods_placed = 0
        self.last_elapsed = 0.0
        # snapshot ingest and batch commits are serialized: a publish
        # landing mid-batch would otherwise be silently replaced by the
        # post-commit snapshot derived from the PREVIOUS version
        self._commit_lock = threading.Lock()
        # pre->default->post error chain; plugins (reservation writeback)
        # register filters (errorhandler_dispatcher.go)
        from koordinator_tpu.scheduler.errorhandler import (
            ErrorHandlerDispatcher,
        )
        self.error_dispatcher = ErrorHandlerDispatcher()
        # version of the last commit THIS service made (read under the
        # commit lock; `store.version` alone can already reflect another
        # thread's later commit)
        self.last_committed_version = 0
        # per-thread (version, elapsed) of the calling thread's last
        # schedule() — see last_schedule_info
        self._tls = threading.local()
        self._counter_lock = threading.Lock()
        # called with (failed_gang_indices, result) when a batch PROVES
        # strict gangs short of quorum; the gang controller un-assumes
        # their held members through store.forget with the batches it
        # retained (the immediate tier of the Permit rollback — the
        # wait-expiry timeout stays the backstop for gangs whose members
        # simply never reappear)
        self.on_gang_failed: Optional[Callable] = None
        self.last_gang_failed: Optional[np.ndarray] = None
        # called with (assignment, typed_pods, result) after each commit
        # when typed_pods was provided: the host assume-cache hook
        # (SnapshotSyncer.attach_scheduler) records placed pods so
        # rebuilds/topology deltas keep the in-flight charges
        self.on_assumed: Optional[Callable] = None
        self.registry.register("scheduler", self.summary)

    def _span(self, name: str, cycle: Optional[int] = None):
        """Open a koordtrace span, or the shared NOOP_SPAN when tracing
        is off. Deliberately takes NO attrs argument: hot-path callers
        attach attributes via the yielded dict (`as a: ... if a is not
        None`), so the disabled path allocates nothing — not even an
        empty dict."""
        t = self.tracer
        if t is None:
            return NOOP_SPAN
        return t.span(name, None, cycle)

    def _event(self, name: str, attrs: Optional[dict] = None,
               cycle: Optional[int] = None) -> None:
        if self.tracer is not None:
            self.tracer.event(name, attrs, cycle)

    def dump_trace(self, out_dir: str, prefix: str = "koordtrace",
                   formats=("chrome", "jsonl", "prom")) -> List[str]:
        """Write the span buffer (+ this service's metric registry, for
        the prom format) into `out_dir`; returns the written paths.
        Raises without a tracer attached — a silent empty dump would
        read as 'the service did nothing'."""
        if self.tracer is None:
            raise RuntimeError(
                "dump_trace: this service was built with trace=None")
        from koordinator_tpu.obs import export as obs_export

        return obs_export.dump(self.tracer, self.metrics.registry,
                               out_dir, prefix=prefix, formats=formats)

    def commit_guard(self):
        """The batch-commit lock, exposed so host-side snapshot writers
        (SnapshotSyncer) can serialize rebuild/ingest publishes with
        in-flight schedule commits: an unserialized rebuild landing
        between a batch's snapshot read and its post-commit publish
        would be silently overwritten (lost update), and the assume
        hook would resolve result rows against a swapped builder.
        Lock order is commit -> view, everywhere."""
        return self._commit_lock

    def surviving_devices(self) -> list:
        """The devices the service believes are healthy right now: the
        `device_health` prober's answer when one is attached, else
        whatever the runtime reports. The mesh-shrink rung builds its
        mesh over exactly this list, and DEVICE_LOST ladder decisions
        key on its length."""
        if self.device_health is not None:
            return list(self.device_health())
        return list(jax.devices())

    def publish(self, snapshot: ClusterSnapshot) -> int:
        """Returns the published version, read under the commit lock so a
        concurrent mutator cannot be misattributed."""
        with self._commit_lock:
            self.store.publish(snapshot)
            self.last_committed_version = self.store.version
            version = self.last_committed_version
        # checkpoint OUTSIDE the lock: a fsync must never stall a
        # concurrent schedule/ingest waiting on the commit lock
        self.store.maybe_checkpoint()
        return version

    def ingest(self, delta) -> int:
        """Apply an O(K) metric delta SERIALIZED with batch commits — a
        delta landing between a batch's snapshot read and its post-commit
        publish would be silently overwritten (the same hazard the commit
        lock exists for; see the lock comment above). An out-of-order /
        duplicate delta no-ops in the store's version guard; the typed
        reason lands on the scheduler_delta_rejected metric here."""
        with self._commit_lock:
            self.store.ingest(delta)
            reason = self.store.take_delta_rejection()
            if reason is not None:
                self.metrics.delta_rejected.labels(reason.value).inc()
                log.warning("delta rejected (%s): store at delta "
                            "version %d", reason.value,
                            self.store.applied_delta_version)
            self.last_committed_version = self.store.version
            version = self.last_committed_version
        self.store.maybe_checkpoint()
        return version

    # batches at or below this size schedule as-is: the quadratic
    # [P, P] savings cannot pay for the pack/unpack permutations there
    AUTO_PACK_MIN_BATCH = 512

    def _prepare_batch(self, snap: ClusterSnapshot, pods: PodBatch,
                       allow_prefix_pack: bool = True):
        """Derive the batching-layer specializations for this batch:
        `(maybe-packed pods, extra static kwargs, inverse permutation
        or None)`. Every contract the kwargs claim is established or
        verified here, host-side (the scheduler silently trusts them):
        domain classes come from actual row equality, prefixes from an
        actual pack, and numa_prefix only on a policy-free snapshot.
        `allow_prefix_pack=False` (the ladder's chunked rung) keeps the
        dom_classes derivation but skips the prefix contracts — slicing
        a prefix-packed batch into chunks would break the row-range
        claims the prefixes make."""
        from koordinator_tpu.utils import synthetic as batching

        from koordinator_tpu.scheduler.plugins import deviceshare

        kwargs = {}
        if not self.auto_pack:
            return pods, kwargs, None
        if pods.has_spread or pods.has_anti or pods.has_aff:
            classes = batching.dom_classes(pods)
            if any(len(c) > 1 for fam in classes for c in fam):
                # all-singleton partitions ARE the default program —
                # omitting them avoids a needless static-arg variant.
                # NOTE: a CHANGING partition across batches is a
                # recompile trigger (dom_classes is a static jit arg);
                # group structure is stable in steady-state traffic,
                # and auto_pack=False opts out entirely.
                kwargs["dom_classes"] = classes
        p = int(np.asarray(pods.valid).shape[0])
        if p <= self.AUTO_PACK_MIN_BATCH or not allow_prefix_pack:
            return pods, kwargs, None

        # cheap masks FIRST; the full batch copy + contract validation
        # in pack_gate_prefixes runs only when a prefix survives
        topo = batching.topo_constrained_mask(pods)
        numa = np.asarray(pods.numa_single, bool)
        gpu = np.asarray(deviceshare.has_device_request(pods), bool)

        def bucket(count):
            # power-of-two widths (>= the packer's tight 128-aligned
            # prefix by construction) bound the compile variants; a
            # class covering most of the batch is not worth a prefix
            if count == 0 or count >= p // 2:
                return None
            width = 128
            while width < count:
                width *= 2
            return min(width, p)

        want = {}
        if topo.any():
            want["topo_prefix"] = bucket(int(topo.sum()))
        if self.schedule_kwargs.get("enable_numa", True) and numa.any() \
                and not np.asarray(snap.nodes.numa_policy).any():
            want["numa_prefix"] = bucket(int((topo | numa).sum()))
        if self.schedule_kwargs.get("enable_devices", True) \
                and gpu.any():
            want["gpu_prefix"] = bucket(int((topo | numa | gpu).sum()))
        want = {k: v for k, v in want.items() if v is not None}
        if not want:
            return pods, kwargs, None  # classes alone need no reorder
        packed, _, masks = batching.pack_gate_prefixes(pods, p)
        kwargs.update(want)
        perm = masks["perm"]
        inv = np.empty_like(perm)
        inv[perm] = np.arange(perm.size)
        return packed, kwargs, inv

    def _begin_journal_cycle(self, pods: PodBatch) -> None:
        """Journal bookkeeping for one cycle attempt, under the commit
        lock: capture the base version/digest the records will carry,
        and detect a RESUME — committed records already journaled for
        this epoch pin the chunk layout and must match the resubmitted
        batch (digest) and the rehydrated snapshot (base version);
        either mismatch is a terminal JournalConflict, because replay
        against different inputs would silently diverge from the
        journaled placements."""
        from koordinator_tpu.scheduler import journal as journal_mod

        self._cycle_base_version = self.store.version
        self._cycle_replayed = 0
        self._cycle_digest = journal_mod.batch_digest(pods)
        self._forced_chunks = None
        committed = self.journal.records_for(self.epoch)
        if not committed:
            return
        rec = next(iter(committed.values()))
        if rec.batch_digest != self._cycle_digest:
            raise journal_mod.JournalConflict(
                f"epoch {self.epoch} resume: the resubmitted batch's "
                f"digest {self._cycle_digest:#x} differs from the "
                f"journaled {rec.batch_digest:#x} — refusing to "
                f"complete another batch's committed chunks (if the "
                f"interrupted batch is gone for good, call "
                f"abandon_interrupted_epoch() to close its epoch)")
        if rec.base_version != self.store.version:
            if self.epoch in self._own_epochs:
                # a delta/publish landed between THIS process's retry
                # attempts (the backoff sleeps outside the commit lock
                # by design): the journaled chunks pinned placements
                # against a snapshot that no longer exists, but nothing
                # of this epoch was ever published (publish seals an
                # epoch) — so abandon them durably and re-run the whole
                # batch against the fresher snapshot, exactly what the
                # pre-journal retry did
                log.warning(
                    "epoch %d: store moved %d -> %d under an in-flight "
                    "retry; abandoning %d journaled chunk(s) and "
                    "re-running the batch fresh", self.epoch,
                    rec.base_version, self.store.version, len(committed))
                self.journal.abandon(self.epoch)
                self.epoch = self.journal.next_epoch()
                return
            raise journal_mod.JournalConflict(
                f"epoch {self.epoch} resume: store at version "
                f"{self.store.version} but the journaled chunks ran "
                f"against version {rec.base_version} — rehydrate the "
                f"store (checkpoint restore + delta/epoch replay) "
                f"before resuming")
        self._forced_chunks = rec.n_chunks

    def _ensure_cached(self, snap: ClusterSnapshot, pods: PodBatch,
                       kwargs: dict) -> None:
        """Request the cycle program from the compile cache before
        dispatch (no-op without a cache handle). The abstract signature
        is derived from the CONCRETE inputs — padded/sharded mesh-
        shrink forms and chunked sub-batch widths key distinct entries,
        exactly the transitions that used to cold-jit. Best-effort: a
        cache failure must never fail a scheduling cycle."""
        if self.compile_cache is None:
            return
        from koordinator_tpu.compilecache import precompile

        with self._span(obs_phases.SPAN_ENSURE_CACHED):
            try:
                precompile.ensure_cycle_program(
                    self.compile_cache, snap, pods, self.cfg, kwargs,
                    guarded=self.guards_enabled, metrics=self.metrics)
            except Exception:  # noqa: BLE001 — warmth is advisory
                log.warning("compile-cache ensure failed; cycle will "
                            "cold-jit", exc_info=True)

    def _inject_fault(self, pods: PodBatch) -> None:
        """The chaos seam, called at each PROGRAM invocation so chunked
        cycles inject per sub-batch — a width-dependent OOM stops firing
        once halving narrows below its threshold, exactly like a real
        allocator."""
        if self.fault_injection is not None:
            self.fault_injection(self._cycle_state, pods)

    def _run_program(self, snap: ClusterSnapshot, pods, kwargs: dict):
        """One guarded/unguarded device-program invocation ->
        `(result, health u32[3] device array or None, node_bad,
        pod_bad)`. With guards on, detection + quarantine + scheduling
        are ONE fused program (scheduler/guards.py). `pods` is a
        PodBatch or its one-buffer upload (`_upload`), which both
        programs take apart on the device."""
        if self.guards_enabled:
            return guards.guarded_schedule_batch(snap, pods, self.cfg,
                                                 **kwargs)
        result = core.schedule_batch(snap, pods, self.cfg, **kwargs)
        return result, None, None, None

    def _upload(self, pods: PodBatch, device):
        """Move a single-program cycle's batch to `device` (None: the
        default device, for a snapshot held on the host). A batch of
        host numpy columns crosses as ONE packed buffer
        (snapshot/hostbatch.py) that the served program unpacks on the
        chip; a batch with any column already a jax.Array crosses per
        leaf."""
        with self._span(obs_phases.SPAN_UPLOAD) as upl:
            if hostbatch.host_columns(pods):
                moved = jax.device_put(hostbatch.pack(pods), device)
                if upl is not None:
                    upl.update(path="packed", transfers=1,
                               bytes=int(moved.words.nbytes))
                return moved
            if upl is not None:
                host = [x for x in jax.tree_util.tree_leaves(pods)
                        if not isinstance(x, jax.Array)]
                upl.update(path="leaves", transfers=len(host),
                           bytes=int(sum(np.asarray(x).nbytes
                                         for x in host)))
            return jax.device_put(pods, device)

    def _journal_commit(self, chunk: int, n_chunks: int,
                        assignment: np.ndarray) -> None:
        """Durably commit one chunk's assignment (append-before-publish
        — the store has NOT published when this runs). An identical
        already-journaled record is the replay path: counted, asserted
        bit-identical inside the journal, and never re-appended — a
        committed pod is never re-placed. A divergent record raises
        JournalConflict (terminal)."""
        from koordinator_tpu.scheduler import journal as journal_mod

        rec = journal_mod.JournalRecord(
            epoch=self.epoch, chunk=chunk, n_chunks=n_chunks,
            base_version=self._cycle_base_version,
            delta_watermark=self.store.applied_delta_version,
            batch_digest=self._cycle_digest,
            assignment=np.asarray(assignment, np.int32))
        with self._span(obs_phases.SPAN_JOURNAL_APPEND) as jrn:
            wrote = self.journal.append(rec)
            if jrn is not None:
                # the trace <-> commit-log join: a journal record is
                # findable from its span and vice versa
                jrn["epoch"] = self.epoch
                jrn["chunk"] = chunk
                jrn["n_chunks"] = n_chunks
                jrn["bytes"] = int(wrote)
                jrn["replayed"] = not wrote
        if wrote:
            self._own_epochs.add(self.epoch)
            self.metrics.journal_appends.inc()
            self.metrics.journal_bytes.inc(wrote)
        else:
            self._cycle_replayed += 1

    def _run_chunked(self, snap: ClusterSnapshot, pods: PodBatch,
                     kwargs: dict, n_chunks: int):
        """The ladder's chunked rung: `n_chunks` sequential sub-batches
        against the evolving snapshot, topology counts carried
        chunk-to-chunk exactly like the bench sweep (the cross-batch
        count rule). `gang_failed` is SUPPRESSED here — per-chunk
        quorum proofs don't compose across chunks, and a false
        un-assume corrupts held capacity; the Permit wait-expiry
        timeout stays the rollback backstop for degraded cycles. All
        merging stays device-side with no per-chunk host sync — except
        under a commit journal, which by design trades one assignment
        readback per chunk for chunk-granular crash durability."""
        p = int(np.asarray(pods.valid).shape[0])
        n_chunks = max(min(n_chunks, p), 1)
        from koordinator_tpu.utils import synthetic
        sizes = [len(c) for c in np.array_split(np.arange(p), n_chunks)]
        # the whole batch on device first (one upload, like the bench
        # sweep): the count-charge helpers compose eagerly with .at
        # scatters and clipped gathers — numpy operands would raise on
        # the degenerate [1, 1] domain matrices instead of dropping
        pods = jax.device_put(pods)
        counts = tuple(getattr(pods, f) for f in core.COUNT_FIELDS)
        parts, pod_bads, node_bad, health = [], [], None, None
        start = 0
        chunk_idx = -1
        ensured_widths = set()
        for size in sizes:
            if size == 0:
                continue
            chunk_idx += 1
            batch = synthetic.slice_batch(pods, start, size)
            batch = batch.replace(**dict(zip(core.COUNT_FIELDS, counts)))
            if size not in ensured_widths:
                # one ensure per DISTINCT sub-batch width: array_split
                # yields at most two widths per layout, and every later
                # chunk of the same width is the same program
                ensured_widths.add(size)
                self._ensure_cached(snap, batch, kwargs)
            self._inject_fault(batch)
            res_i, h_i, nb_i, pb_i = self._run_program(snap, batch, kwargs)
            if self.journal is not None:
                # the journaled readback is the chunk's COMMIT point
                self._journal_commit(chunk_idx, n_chunks,
                                     np.asarray(res_i.assignment))
            counts = core.charge_all_counts(counts, batch,
                                            res_i.assignment)
            snap = res_i.snapshot
            parts.append(res_i)
            if h_i is not None:
                pod_bads.append(pb_i)
                node_bad = nb_i if node_bad is None else node_bad | nb_i
                # the WORD merges bitwise; counts do not (a node bad in
                # several chunks is one bad node) — the node count is
                # recomputed from the merged mask below, pod rows are
                # disjoint so their counts sum
                health = h_i if health is None else jnp.stack(
                    [health[0] | h_i[0], health[1], health[2] + h_i[2]])
            start += size
        if health is not None:
            health = jnp.stack([health[0],
                                node_bad.sum().astype(jnp.uint32),
                                health[2]])
        merged = parts[0].replace(
            snapshot=snap,
            gang_failed=jnp.zeros_like(parts[0].gang_failed),
            **{f: jnp.concatenate([getattr(r, f) for r in parts])
               for f in core.PER_POD_RESULT_FIELDS})
        pod_bad = jnp.concatenate(pod_bads) if pod_bads else None
        return merged, health, node_bad, pod_bad

    def _device_cycle(self, snap: ClusterSnapshot, pods: PodBatch,
                      kwargs: dict, state: LadderState):
        """Run one cycle's device program at the ladder state's
        configuration. A journaled resume (`_forced_chunks`) pins the
        chunk layout to the journaled epoch's regardless of the current
        ladder state — replay must slice the batch exactly as the
        interrupted run did."""
        self._cycle_state = state
        n_real = None
        # a single-program cycle on one device uploads its batch there
        # (`_upload`); the mesh rungs and the chunked rung place the
        # batch per leaf
        upload, device = False, None
        if state.single_device:
            device = jax.devices()[0]
            snap = jax.device_put(snap, device)
            upload = True
            self._last_mesh_size = 1
        elif state.mesh_shrink:
            # rebuild the mesh over the survivors: pad the node axis to
            # the shrunk mesh, re-shard, run — then unpad the committed
            # snapshot so stored shapes never depend on the surviving-
            # device count. Placements are bit-identical through the
            # padding/sharding path (the PR 4 mesh conformance pins).
            from koordinator_tpu.parallel import mesh as meshlib

            devs = self.surviving_devices()
            mesh = meshlib.make_mesh(devs)
            n_real = int(snap.num_nodes)
            snap = meshlib.shard_snapshot(
                meshlib.pad_nodes_to_mesh(snap, mesh), mesh)
            pods = meshlib.pad_batch_nodes(
                pods, meshlib.padded_node_count(n_real, mesh))
            self._last_mesh_size = len(devs)
        else:
            # the normal rung runs where the published snapshot lives
            self._last_mesh_size = _spanned_devices(snap)
            if self._last_mesh_size == 1:
                upload, device = True, _home_device(snap)
        if state.cascade_off:
            kwargs = dict(kwargs, cascade=False)
        # the journaled layout wins over the ladder in BOTH directions:
        # a 1-chunk epoch replays as the single program even on a
        # chunked-rung service (running it chunked would journal
        # conflicting n_chunks records)
        n_chunks = (self._forced_chunks if self._forced_chunks is not None
                    else 2 ** state.chunk_splits)
        if n_chunks > 1:
            if state.single_device:
                pods = jax.device_put(pods, device)
            out = self._run_chunked(snap, pods, kwargs, n_chunks)
        else:
            # the single-program paths ensure here: on the shrink rung
            # `snap`/`pods` already carry the padded, resharded
            # survivor-mesh forms, and an uploaded batch carries its
            # layout, so the cache key is exactly the program about to
            # dispatch
            batch = self._upload(pods, device) if upload else pods
            self._ensure_cached(snap, batch, kwargs)
            self._inject_fault(pods)
            out = self._run_program(snap, batch, kwargs)
        if n_real is not None:
            from koordinator_tpu.parallel import mesh as meshlib

            result, health, node_bad, pod_bad = out
            result = result.replace(
                snapshot=meshlib.unpad_nodes(result.snapshot, n_real))
            if node_bad is not None:
                node_bad = node_bad[:n_real]
            out = (result, health, node_bad, pod_bad)
        return out

    def _locked_cycle(self, pods: PodBatch, typed_pods,
                      state: LadderState):
        """The serialized snapshot-read -> program -> commit section of
        one cycle attempt."""
        with self._commit_lock:
            with self._span(obs_phases.SPAN_ADMIT) as adm:
                snap = self.store.current()
                if self.journal is not None:
                    self._begin_journal_cycle(pods)
                # amplified-CPU auto-detection happens on the snapshot
                # the batch actually runs against (an explicit
                # enable_amplification kwarg from the constructor
                # wins). Deriving here rather than at publish time
                # keeps the flag correct for writers that bypass
                # service.publish() and put snapshots straight into the
                # shared SnapshotStore (SnapshotSyncer._rebuild,
                # embedded compositions).
                if not self._explicit_amp:
                    with self._span(obs_phases.SPAN_AMP_CHECK):
                        self.schedule_kwargs["enable_amplification"] = \
                            bool(np.asarray(
                                snap.nodes.cpu_amplification > 1.0).any())
                # a journaled resume (forced chunk layout) also forbids
                # prefix packing: slicing a packed batch breaks the
                # row-range contracts, exactly like the chunked rung
                with self._span(obs_phases.SPAN_PREPARE_BATCH):
                    sched_pods, pack_kwargs, inv = self._prepare_batch(
                        snap, pods,
                        allow_prefix_pack=not state.chunked
                        and (self._forced_chunks is None
                             or self._forced_chunks <= 1))
                if adm is not None:
                    # the trace <-> journal join at cycle granularity
                    adm["base_version"] = self.store.version
                    if self.journal is not None:
                        adm["epoch"] = self.epoch
            if self.memwatch is not None:
                # boundary sample 1: residency as the dispatch opens
                self.memwatch.sample()
            with kernel_timer(self.metrics.kernel_seconds,
                              obs_phases.PHASE_SCHEDULE_BATCH):
                with self._span(obs_phases.SPAN_DISPATCH) as dsp:
                    result, health_dev, _node_bad, pod_bad = \
                        self._device_cycle(
                            snap, sched_pods,
                            {**self.schedule_kwargs, **pack_kwargs},
                            state)
                    if dsp is not None:
                        dsp["ladder"] = state.label()
                        dsp["mesh_size"] = self._last_mesh_size
                if inv is not None:
                    # back to the CALLER's pod order before anything
                    # (hooks, error chain, debug tables) sees the result
                    with self._span(obs_phases.SPAN_UNPACK):
                        result = result.replace(
                            **{f: getattr(result, f)[inv]
                               for f in core.PER_POD_RESULT_FIELDS})
                        if pod_bad is not None:
                            pod_bad = pod_bad[inv]
                # single D2H transfer doubles as the completion barrier
                # (and makes the kernel timer measure device time)
                with self._span(obs_phases.SPAN_DEVICE_WAIT):
                    assignment = np.asarray(result.assignment)
                if self.memwatch is not None:
                    # boundary sample 2: residency after the program
                    # completed — the sample the leak sentinel advances
                    # on at commit
                    self.memwatch.sample()
            # the guards' ONE packed readback ([word, bad nodes, bad
            # pods]); the full masks stay on device unless the word is
            # non-zero (cold path)
            with self._span(obs_phases.SPAN_GUARD_SCAN) as gsc:
                health = (np.asarray(health_dev)
                          if health_dev is not None else None)
                if gsc is not None:
                    gsc["guards"] = self.guards_enabled
                    if health is not None:
                        gsc["word"] = int(health[0])
            # what _device_cycle ACTUALLY ran: the journaled layout
            # overrides the ladder in both directions
            chunked_run = (self._forced_chunks > 1
                           if self._forced_chunks is not None
                           else state.chunked)
            if self.journal is not None and not chunked_run:
                # append-before-publish: the single-program cycle's one
                # record lands BEFORE the store publish below, so a
                # crash between them replays rather than loses the batch
                self._journal_commit(0, 1, assignment)
            with self._span(obs_phases.SPAN_PUBLISH) as pub:
                self.store.update(lambda _old: result.snapshot)
                if self.journal is not None:
                    # the batch committed: the epoch is sealed (its
                    # chunk set is complete in the journal) and the
                    # next schedule opens a new one; the own-epoch
                    # marker only matters for the CURRENT epoch's
                    # retries, so drop the sealed one (a resident
                    # service must not accrete the set)
                    self._own_epochs.discard(self.epoch)
                    self.epoch += 1
                    self._forced_chunks = None
                if pub is not None:
                    pub["version"] = self.store.version
            # THE COMMIT POINT: everything below ran against a snapshot
            # version that is now published. A failure past here must
            # NOT re-enter the retry loop — re-running the cycle would
            # schedule the same batch against its own post-commit
            # snapshot and double-charge every placement — so it is
            # wrapped as terminal (_CommittedCycleError).
            try:
                # THIS call's commit version, captured under the lock —
                # the shared last_committed_version attribute can
                # already reflect a racing ingest by the time a caller
                # reads it
                version = self.store.version
                self.last_committed_version = version
                if self.on_assumed is not None and typed_pods is not None:
                    # under the commit lock: an attached syncer's
                    # rebuild (which serializes on the same lock)
                    # cannot swap the builder between this batch's
                    # snapshot and the hook's row-name resolution
                    self.on_assumed(assignment, typed_pods, result)
            except Exception as exc:
                raise _CommittedCycleError(exc) from exc
            # cycle-local copies captured under the lock: by the time
            # schedule() publishes metrics, a concurrent cycle may have
            # overwritten the shared attributes
            mesh_size = self._last_mesh_size
            replayed = self._cycle_replayed
        return (snap, result, assignment, health, pod_bad, version,
                mesh_size, replayed)

    def _trace_transitions(self, n_before: int, cycle_id: int) -> None:
        """Emit one koordtrace instant event per ladder transition the
        last ladder call appended (detected by list-length delta — the
        ladder itself stays trace-free)."""
        if self.tracer is None:
            return
        for cause, label in self.ladder.transitions[n_before:]:
            self._event(obs_phases.EVENT_LADDER_TRANSITION,
                        {"cause": cause, "to": label}, cycle=cycle_id)

    def schedule(self, pods: PodBatch,
                 pod_names: Optional[List[str]] = None,
                 typed_pods: Optional[List] = None) -> core.ScheduleResult:
        """`typed_pods` (batch-ordered api.Pod list) opts unplaced rows
        into the error-handler chain — the reservation filter needs the
        typed pod to recognize reserve pods.

        Runtime failures are classified (errorhandler.classify_failure),
        transients retried with bounded monotonic backoff, and
        persistent failures walked down the degradation ladder; the
        backoff sleeps happen OUTSIDE the commit lock so publishes and
        ingests proceed while a retry waits."""
        token = self.monitor.start_cycle()
        cycle_id = next(self._cycle_ids)
        # the cycle id is unique per call (no two concurrent cycles
        # share a jitter stream) and needs no lock, unlike the batch
        # counter it used to seed from
        backoff = Backoff(self.retry_policy, seed=cycle_id)
        attempts = 0
        while True:
            n_trans = len(self.ladder.transitions)
            state, probing = self.ladder.begin_cycle()
            self._trace_transitions(n_trans, cycle_id)
            try:
                with self._span(obs_phases.SPAN_CYCLE,
                                cycle=cycle_id) as cyc:
                    if cyc is not None:
                        cyc["attempt"] = attempts
                        cyc["ladder"] = state.label()
                    (snap, result, assignment, health, pod_bad,
                     version, mesh_size,
                     replayed) = self._locked_cycle(pods, typed_pods,
                                                    state)
                n_trans = len(self.ladder.transitions)
                self.ladder.on_success(probing, state)
                self._trace_transitions(n_trans, cycle_id)
                break
            except _CommittedCycleError as exc:
                # the snapshot already committed: never retry (see
                # _CommittedCycleError), surface the hook's failure
                self.monitor.complete_cycle(token)
                raise exc.cause
            except JournalConflict:
                # the journal disagrees with this cycle's inputs:
                # terminal by construction — a retry re-derives the
                # same divergence, and degrading cannot fix a wrong
                # batch or a stale snapshot
                self.monitor.complete_cycle(token)
                raise
            except Exception as exc:
                # every device-program failure routes through the
                # FailureClass classifier (koordlint RB001)
                fc = classify_failure(exc)
                self.metrics.failures_classified.labels(fc.value).inc()
                attempts += 1
                if self.tracer is not None:
                    self._event(obs_phases.EVENT_RETRY,
                                {"failure_class": fc.value,
                                 "attempt": attempts,
                                 "ladder": state.label()},
                                cycle=cycle_id)
                log.warning(
                    "scheduling cycle failed (class=%s, attempt %d, "
                    "ladder=%s): %r", fc.value, attempts, state.label(),
                    exc)
                if attempts >= self.max_cycle_attempts:
                    self.monitor.complete_cycle(token)
                    raise
                if probing:
                    # a failed up-probe falls straight back; the
                    # pre-probe state was never left
                    n_trans = len(self.ladder.transitions)
                    self.ladder.on_failure(fc, probing=True)
                    self._trace_transitions(n_trans, cycle_id)
                    continue
                if fc in TRANSIENT_CLASSES and not backoff.exhausted():
                    delay = backoff.next_delay()
                    with self._span(obs_phases.SPAN_BACKOFF,
                                    cycle=cycle_id) as bko:
                        if bko is not None:
                            bko["failure_class"] = fc.value
                            bko["attempt"] = attempts
                            bko["delay_s"] = delay
                        self._sleep(delay)
                    continue
                survivors = None
                if fc is FailureClass.DEVICE_LOST:
                    # the ladder's DEVICE_LOST decision keys on how
                    # many devices actually survive: >= 2 earns the
                    # mesh-shrink rung, fewer abandons the mesh
                    survivors = len(self.surviving_devices())
                pre_level = self.ladder.level
                n_trans = len(self.ladder.transitions)
                if not self.ladder.on_failure(fc, probing=False,
                                              survivors=survivors):
                    # no lower rung left: the failure is terminal
                    self.monitor.complete_cycle(token)
                    raise
                self._trace_transitions(n_trans, cycle_id)
                if self.ladder.level == DegradationLadder.L_MESH_SHRINK \
                        and pre_level != DegradationLadder.L_MESH_SHRINK:
                    self.metrics.mesh_shrink_events.inc()
                backoff.reset()
        # the post-commit block: the cycle committed; what follows
        # reads its results on the host and feeds the metrics
        with self._span(obs_phases.SPAN_FINALIZE, cycle=cycle_id):
            self.last_ladder_state = state
            if state.degraded or probing:
                self.metrics.degraded_cycles.labels(state.label()).inc()
            self.metrics.degradation_level.set(float(self.ladder.level))
            self.metrics.mesh_size.set(float(mesh_size))
            if self.journal is not None and replayed:
                self.metrics.recovery_replayed.inc(replayed)
            word = int(health[0]) if health is not None else 0
            self.last_health_word = word
            pod_bad_np: Optional[np.ndarray] = None
            if word:
                defects = guards.decode_health_word(word)
                for name in defects:
                    self.metrics.guard_trips.labels(name).inc()
                n_bad_nodes, n_bad_pods = int(health[1]), int(health[2])
                if n_bad_nodes:
                    self.metrics.quarantined_inputs.labels("node").inc(
                        n_bad_nodes)
                if n_bad_pods:
                    self.metrics.quarantined_inputs.labels("pod").inc(
                        n_bad_pods)
                if pod_bad is not None:
                    pod_bad_np = np.asarray(pod_bad)
                if self.tracer is not None:
                    self._event(obs_phases.EVENT_QUARANTINE,
                                {"word": word, "defects": defects,
                                 "bad_nodes": n_bad_nodes,
                                 "bad_pods": n_bad_pods}, cycle=cycle_id)
                log.warning(
                    "health guards tripped: word=0x%x (%s); %d node(s) / "
                    "%d pod(s) quarantined", word, ",".join(defects),
                    n_bad_nodes, n_bad_pods)
            self.last_quarantined_pods = pod_bad_np
            elapsed, stalled = self.monitor.complete_cycle(token)
            self.last_elapsed = elapsed
            if stalled:
                # the stall completed, but the NEXT cycle runs degraded:
                # a watchdog trip is a classified failure like any other
                self.metrics.failures_classified.labels(
                    FailureClass.WATCHDOG_STALL.value).inc()
                n_trans = len(self.ladder.transitions)
                self.ladder.on_failure(FailureClass.WATCHDOG_STALL,
                                       probing=False)
                self._trace_transitions(n_trans, cycle_id)
            # per-CALL (version, elapsed) for the calling thread: the
            # threaded sidecar reads them after scheduling, and the shared
            # attributes race with concurrent ingests/schedules
            self._tls.version = version
            self._tls.elapsed = elapsed
            self.metrics.cycle_seconds.observe(elapsed)
            valid = np.asarray(pods.valid)
            placed_n = int(((assignment >= 0) & valid).sum())
            with self._counter_lock:
                # += on the shared counters is not atomic across threads;
                # the threaded sidecar schedules concurrently
                self.batches += 1
                self.pods_placed += placed_n
            self.metrics.pods_scheduled.labels("placed").inc(placed_n)
            unsched = (assignment < 0) & valid
            if pod_bad_np is not None:
                # quarantined rows are infrastructure errors, already
                # counted per kind above — not "unschedulable" (cluster
                # full) rows
                unsched &= ~pod_bad_np
            self.metrics.pods_scheduled.labels("unschedulable").inc(
                int(unsched.sum()))
            self.metrics.snapshot_version.set(float(self.store.version))
            # koordcost: the cycle committed and its counters/histograms
            # are final — advance the leak sentinel and the SLO rings
            if self.memwatch is not None:
                self.memwatch.observe_cycle()
            if self.slo is not None:
                self.slo.observe_cycle()
            gang_failed = np.asarray(result.gang_failed)
            self.last_gang_failed = gang_failed
            if gang_failed.any() and self.on_gang_failed is not None:
                self.on_gang_failed(np.where(gang_failed)[0], result)
            if typed_pods is not None:
                from koordinator_tpu.scheduler.errorhandler import (
                    dispatch_batch_errors,
                )
                dispatch_batch_errors(self.error_dispatcher, assignment, valid,
                                      typed_pods, infra_mask=pod_bad_np)
        if self.flags.score_top_n > 0:
            log.info("score table:\n%s", debug_score_table(
                snap, pods, self.cfg, self.flags.score_top_n, pod_names))
        if self.flags.filter_dump:
            log.info("filter table:\n%s", debug_filter_table(
                snap, pods, self.cfg, pod_names))
        # the post-commit checkpoint, outside the commit lock: a fsync
        # must never stall the next cycle's snapshot read
        with self._span(obs_phases.SPAN_CHECKPOINT,
                        cycle=cycle_id) as ckp:
            wrote_ckpt = self.store.maybe_checkpoint()
            if ckp is not None:
                ckp["wrote"] = bool(wrote_ckpt)
            if wrote_ckpt and self.journal is not None:
                # epochs below the fresh checkpoint can never replay:
                # prune them so a resident service's journal stays
                # bounded (serialized with appends via the commit lock)
                with self._commit_lock:
                    self.journal.prune(
                        self.store.last_checkpoint_version)
        return result

    def abandon_interrupted_epoch(self) -> bool:
        """Durably close the current epoch's journaled chunks with a
        tombstone and move to a fresh epoch — the unwedge path when an
        interrupted batch will NEVER be resubmitted (without this,
        every future schedule() of a different batch would refuse with
        a digest JournalConflict). Safe because an incomplete epoch
        has published nothing: dropping its chunks loses no
        externally-visible placement. Returns False when there is
        nothing to abandon."""
        if self.journal is None:
            return False
        with self._commit_lock:
            if not self.journal.records_for(self.epoch):
                return False
            self.journal.abandon(self.epoch)
            self.epoch = self.journal.next_epoch()
            return True

    def recover(self, batches,
                typed_pods_by_epoch: Optional[Dict[int, List]] = None
                ) -> dict:
        """Restart recovery: rehydrate the store, then bring the world
        back to exactly where the crash interrupted it — never
        re-placing a committed pod, never dropping an uncommitted one.

        1. If the store has no snapshot yet, restore the last
           checkpoint (version + delta high-water mark come with it).
           A caller whose producer logs deltas re-ingests them next:
           already-applied ones no-op in the store's version guard.
        2. Every journaled epoch whose base version is AT OR PAST the
           rehydrated store version re-runs through the normal
           schedule() path: committed chunks replay (the journal
           asserts them bit-identical and they are never re-appended),
           missing chunks of an interrupted tail epoch schedule fresh,
           and each epoch's publish re-derives the store state the
           crash destroyed.

        `batches` maps epoch -> the resubmitted PodBatch (or is a
        callable epoch -> PodBatch); the journal's batch digest pins
        that the resubmission is the same batch. Returns a report dict
        (also kept on `last_recovery`) with the per-epoch results."""
        if self.journal is None:
            raise RuntimeError("recover() needs a commit journal")

        t0 = time.monotonic()
        t0_ns = time.monotonic_ns()
        restored = False
        # the whole recovery runs under a compile watcher so the
        # recorded time splits into what replay actually spent vs what
        # XLA compilation cost on top — the component a warmed compile
        # cache deletes (PR 5/6 recoveries were compile-dominated)
        with compile_counters.watch() as compile_watch:
            try:
                self.store.current()
            except RuntimeError:
                restored = self.store.restore()
                if not restored:
                    raise RuntimeError(
                        "recover(): no snapshot and no readable checkpoint "
                        "— publish the initial snapshot, then call "
                        "recover() again to replay the journal")
            epochs = [e for e in self.journal.epochs()
                      if self.journal.base_version_of(e)
                      >= self.store.version]
            results = {}
            replayed = 0
            for e in epochs:
                pods = batches(e) if callable(batches) else batches[e]
                typed = (typed_pods_by_epoch or {}).get(e)
                # epoch/bookkeeping writes take the commit lock even on
                # this (normally single-threaded) startup path: a
                # producer already re-ingesting deltas concurrently
                # must never see a half-switched epoch
                with self._commit_lock:
                    self.epoch = e
                results[e] = self.schedule(pods, typed_pods=typed)
                with self._commit_lock:
                    replayed += self._cycle_replayed
            with self._commit_lock:
                self.epoch = self.journal.next_epoch()
        seconds = time.monotonic() - t0
        compile_seconds = min(compile_watch.compile_seconds, seconds)
        replay_seconds = seconds - compile_seconds
        self.metrics.recovery_seconds.observe(seconds)
        self.metrics.recovery_compile_seconds.observe(compile_seconds)
        self.metrics.recovery_replay_seconds.observe(replay_seconds)
        if self.tracer is not None:
            # the recover span plus its replay-vs-compile split as two
            # child spans. The split is derived from the compile
            # watcher, not separately clocked, so the children are laid
            # out proportionally inside the parent (replay first) —
            # their DURATIONS are the measured truth, their ordering an
            # approximation.
            end_ns = t0_ns + int(seconds * 1e9)
            split_ns = t0_ns + int(replay_seconds * 1e9)
            self.tracer.record_span(
                obs_phases.SPAN_RECOVER, t0_ns, end_ns,
                attrs={"epochs": list(epochs),
                       "records_replayed": replayed,
                       "restored_checkpoint": restored})
            self.tracer.record_span(
                obs_phases.SPAN_RECOVER_REPLAY, t0_ns, split_ns,
                parent=obs_phases.SPAN_RECOVER)
            self.tracer.record_span(
                obs_phases.SPAN_RECOVER_COMPILE, split_ns, end_ns,
                parent=obs_phases.SPAN_RECOVER)
        self.last_recovery = {
            "restored_checkpoint": restored,
            "epochs_replayed": epochs,
            "records_replayed": replayed,
            "journal_tail": self.journal.tail_reason.value,
            "seconds": seconds,
            "compile_seconds": compile_seconds,
            "replay_seconds": replay_seconds,
            # real XLA compilations during recovery: with a persistent
            # cache active the cache-miss count is exact (retrievals
            # don't fire it); without one only the compile-or-retrieve
            # invocation count exists, and every one is a compile
            "compiled_programs": (
                compile_watch.cache_misses
                if self.compile_cache is not None
                and self.compile_cache.active
                else compile_watch.backend_compiles),
            "results": results,
        }
        log.info("recovery complete: %d epoch(s), %d journaled "
                 "chunk(s) replayed, %.3fs (tail: %s)", len(epochs),
                 replayed, seconds, self.journal.tail_reason.value)
        return self.last_recovery

    def last_schedule_info(self) -> tuple:
        """(commit version, elapsed seconds) of THE CALLING THREAD's
        most recent schedule() — race-free under the threaded sidecar,
        where the shared last_* attributes can reflect another
        connection's commit. Raises for a thread that never scheduled:
        a silent fallback to the shared attributes would reintroduce
        the exact misattribution this API exists to prevent."""
        version = getattr(self._tls, "version", None)
        if version is None:
            raise RuntimeError(
                "last_schedule_info: this thread has not called "
                "schedule(); read last_committed_version/last_elapsed "
                "for the shared (racy) values instead")
        return version, self._tls.elapsed

    def summary(self) -> dict:
        with self._counter_lock:
            batches, placed = self.batches, self.pods_placed
        return {
            "batches": batches,
            "podsPlaced": placed,
            "lastCycleSeconds": round(self.last_elapsed, 4),
            "cycleTimeouts": self.monitor.timeouts,
            "snapshotVersion": self.store.version,
            "degradationLevel": DegradationLadder.LEVELS[self.ladder.level],
            "ladderTransitions": len(self.ladder.transitions),
            "lastHealthWord": self.last_health_word,
            # deliberately lockless: a monitoring read must never queue
            # behind an in-flight device program on the commit lock;
            # torn values here cost a stale dashboard sample, nothing
            # more
            "meshSize": self._last_mesh_size,  # koordlint: disable=GB001
            "epoch": self.epoch,  # koordlint: disable=GB001
            "journaled": self.journal is not None,
        }

    def health(self) -> dict:
        """The koordcost health snapshot: the degradation rung, SLO
        status (burn rates, remaining budget) when an SloTracker is
        attached, device-memory telemetry + HBM headroom when a
        MemWatch is attached, and the journal's replay lag. `ok` is
        the one-bit verdict: every SLO objective inside budget AND the
        leak sentinel silent — a service built without either plane is
        vacuously ok (this method stays cheap and lock-free either
        way, like summary())."""
        slo_status = self.slo.status() if self.slo is not None else None
        mem = self.memwatch.snapshot() \
            if self.memwatch is not None else None
        ok = True
        budget_remaining = None
        if slo_status is not None:
            ok = slo_status["ok"]
            budget_remaining = slo_status["budget_remaining"]
        leak_events = 0 if mem is None else mem["leak_events"]
        return {
            "ok": bool(ok and leak_events == 0),
            "rung": DegradationLadder.LEVELS[self.ladder.level],
            "slo": slo_status,
            "budgetRemaining": budget_remaining,
            "memory": mem,
            "hbmHeadroomBytes":
                None if mem is None else mem["headroom_bytes"],
            "leakEvents": leak_events,
            # epochs still resident in the journal = how much a crash
            # right now would have to replay (pruned at checkpoints)
            "journalLagEpochs":
                len(self.journal.epochs())
                if self.journal is not None else 0,
            "lastCycleSeconds": round(self.last_elapsed, 4),
            "snapshotVersion": self.store.version,
        }
