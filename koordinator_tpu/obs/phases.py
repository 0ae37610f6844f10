"""koordtrace phase-name table — the single source of truth for every
span/annotation name in the system.

Three consumers join on these strings and MUST agree:
  * device-side `jax.named_scope`/`TraceAnnotation` labels (via
    `obs.phase(...)` — koordlint OB001 rejects bare literals),
  * host-side `SchedulerService` cycle spans (obs/trace.py records),
  * the trace parsers (`tools/trace_fullgate.py`,
    `tools/profile_fullgate.py`, `tools/trace_smoke.py`).

Names, not enums, because they end up verbatim in Chrome trace-event
JSON and in the `scheduler_cycle_phase_seconds{phase=...}` label set.
Kernel phases carry the `koord/` prefix (they appear inside XLA
profiler streams next to XLA-internal names and need a grep-able
namespace); host cycle spans are bare. Host spans land in koordtrace's
ring and, while a span is open, as a `TraceAnnotation` of the same
name on the profiler's host plane (obs/trace.py), beside the device
ops of a profiler capture.
"""

# --- device/kernel phases (named_scope / TraceAnnotation labels) ---

# the whole fused schedule_batch dispatch (kernel_timer annotation —
# predates koordtrace, kept verbatim so old traces still join)
PHASE_SCHEDULE_BATCH = "koord/schedule_batch"

# cascade stage 1 (cheap whole-batch prefilters)
PHASE_STAGE1_STATIC = "koord/stage1_static_gates"
PHASE_STAGE1_MASK = "koord/stage1_mask"

# stage-2 gate families (per-family score/prefilter kernels)
PHASE_STAGE2_DEVICESHARE = "koord/stage2_deviceshare"
PHASE_STAGE2_NUMA = "koord/stage2_numa"
PHASE_STAGE2_POLICY = "koord/stage2_policy"

# per-round selection + the cross-shard merge
PHASE_TOPK = "koord/topk_select"
PHASE_ICI_MERGE = "koord/ici_merge"

# adaptive tail
PHASE_TAIL_SELECT = "koord/tail_select"
PHASE_TAIL_PASS = "koord/tail_pass"
PHASE_TAIL_LOOP = "koord/tail_loop"

# --- host-side cycle spans (SchedulerService / bench) ---

SPAN_CYCLE = "cycle"
SPAN_ADMIT = "admit"
# inside admit: the cpu_amplification > 1 check (a device op and its
# readback) and _prepare_batch's auto-pack
SPAN_AMP_CHECK = "amp_check"
SPAN_PREPARE_BATCH = "prepare_batch"
SPAN_GUARD_SCAN = "guard_scan"
SPAN_ENSURE_CACHED = "ensure_cached"
SPAN_DISPATCH = "dispatch"
# inside dispatch: the pod batch's host->device upload (one packed
# buffer, or per leaf)
SPAN_UPLOAD = "upload"
SPAN_DEVICE_WAIT = "device_wait"
# auto-pack's inverse-permutation gather back to the caller's pod order
SPAN_UNPACK = "unpack"
SPAN_JOURNAL_APPEND = "journal_append"
SPAN_PUBLISH = "publish"
SPAN_CHECKPOINT = "checkpoint"
# schedule()'s post-commit block after the cycle span: health word,
# metrics, the gang_failed readback, error dispatch
SPAN_FINALIZE = "finalize"
SPAN_BACKOFF = "backoff"
SPAN_RECOVER = "recover"
SPAN_RECOVER_REPLAY = "recover_replay"
SPAN_RECOVER_COMPILE = "recover_compile"

# instant events (zero-duration marks)
EVENT_QUARANTINE = "quarantine"
EVENT_LADDER_TRANSITION = "ladder_transition"
EVENT_RETRY = "retry"

KERNEL_PHASES = frozenset({
    PHASE_SCHEDULE_BATCH,
    PHASE_STAGE1_STATIC,
    PHASE_STAGE1_MASK,
    PHASE_STAGE2_DEVICESHARE,
    PHASE_STAGE2_NUMA,
    PHASE_STAGE2_POLICY,
    PHASE_TOPK,
    PHASE_ICI_MERGE,
    PHASE_TAIL_SELECT,
    PHASE_TAIL_PASS,
    PHASE_TAIL_LOOP,
})

HOST_SPANS = frozenset({
    SPAN_CYCLE,
    SPAN_ADMIT,
    SPAN_AMP_CHECK,
    SPAN_PREPARE_BATCH,
    SPAN_GUARD_SCAN,
    SPAN_ENSURE_CACHED,
    SPAN_DISPATCH,
    SPAN_UNPACK,
    SPAN_DEVICE_WAIT,
    SPAN_JOURNAL_APPEND,
    SPAN_PUBLISH,
    SPAN_CHECKPOINT,
    SPAN_FINALIZE,
    SPAN_BACKOFF,
    SPAN_RECOVER,
    SPAN_RECOVER_REPLAY,
    SPAN_RECOVER_COMPILE,
    EVENT_QUARANTINE,
    EVENT_LADDER_TRANSITION,
    EVENT_RETRY,
})

# host spans the benchmark's clock join does not place yet: its list
# (benchmark/hostclock.py SERVICE_SPANS) is pinned equal to HOST_SPANS
# less the recovery spans and the events, and counts their time under
# their parent span
HOST_SUBSPANS = frozenset({
    SPAN_UPLOAD,
})

ALL_PHASES = KERNEL_PHASES | HOST_SPANS | HOST_SUBSPANS

# the span skeleton every committed service cycle must carry, in order
# (tools/trace_smoke.py asserts it cycle-by-cycle)
CYCLE_SKELETON = (
    SPAN_ADMIT,
    SPAN_DISPATCH,
    SPAN_DEVICE_WAIT,
    SPAN_GUARD_SCAN,
    SPAN_JOURNAL_APPEND,
    SPAN_PUBLISH,
    SPAN_FINALIZE,
)


def check_phase(name: str) -> str:
    """Validate `name` against the table (raises ValueError on drift).

    The runtime complement of koordlint OB001: OB001 catches bare
    literals statically; this catches a constant that was renamed
    without updating the table.
    """
    if name not in ALL_PHASES:
        raise ValueError(
            f"unknown koordtrace phase {name!r}; add it to "
            "koordinator_tpu/obs/phases.py or use an existing constant")
    return name
