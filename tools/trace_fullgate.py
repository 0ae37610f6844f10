"""Sampled kernel-phase attribution of the flagship full-gate batch.

Captures a `jax.profiler.trace` of one warmed `core.schedule_batch`
dispatch on the full-gate workload, parses the trace-event stream the
profiler writes (Perfetto's `*.trace.json.gz`), and attributes device
time to the shared koordtrace phase table
(koordinator_tpu/obs/phases.py) — every kernel region is wrapped in a
`jax.named_scope` phase label (cascade stage 1, the stage-2 gate
families, top-k + ICI merge, the adaptive tail), so each XLA
instruction's `op_name` metadata carries the `koord/...` scope.

The join is two-step because backends differ in what the trace stream
preserves: TPU-style captures embed the scope path in the event args
(substring match suffices), but the CPU profiler emits only the bare
HLO instruction names (`add.635`, `fusion.19`) — so the tool also
compiles the SAME program, parses `op_name="...koord/..."` metadata
out of the HLO text, and joins trace events to phases through the
instruction-name map. Same program, same names, exact join.

It emits koordtrace JSONL keyed by the same phase names as the
service's spans.

Usage: JAX_PLATFORMS=cpu python tools/trace_fullgate.py [pods] [nodes]
  TRACE_FULLGATE_OUT=<path>  also write the per-phase koordtrace JSONL
  TRACE_FULLGATE_DIR=<dir>   keep the raw profiler capture (default: a
                             temp dir, deleted after parsing)

If a backend yields no attributable events the tool says so and exits
0 — an empty capture is a backend property, not a phase-table failure.
"""

import functools
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax

if os.environ.get("JAX_PLATFORMS"):
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

from koordinator_tpu.obs import hloattrib
from koordinator_tpu.obs.trace import jsonl_record
from koordinator_tpu.scheduler import core
from koordinator_tpu.scheduler.plugins.loadaware import LoadAwareConfig
from koordinator_tpu.utils import synthetic

P = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000
N = int(sys.argv[2]) if len(sys.argv) > 2 else 1_000

# attribution-coverage floor over the compiled program's instructions.
# Deliberately modest: the full parse counts EVERY instruction line —
# parameter/constant plumbing and XLA-introduced copies carry no
# op_name at all — and the measured flagship sits near 8% instructions
# / 26% output bytes. The floor exists to catch the scope labels
# silently vanishing (a named_scope refactor dropping the koord/
# prefix), not to demand XLA annotate its own plumbing.
MIN_INSTRUCTION_COVERAGE = 0.02


def build_step():
    step = jax.jit(functools.partial(
        core.schedule_batch, num_rounds=2, k_choices=8,
        score_dims=(0, 1), tie_break=True, quota_depth=2,
        fit_dims=(0, 1, 2, 3), cascade=True,
        enable_numa=True, enable_devices=True))
    snap = jax.device_put(synthetic.full_gate_cluster(
        N, seed=0, num_quotas=8, num_gangs=8))
    pods = jax.device_put(synthetic.full_gate_pods(
        P, N, seed=1, num_quotas=8, num_gangs=8))
    cfg = jax.device_put(LoadAwareConfig.make())
    return step, snap, pods, cfg


def instruction_phases(step, snap, pods, cfg):
    """{hlo instruction name: phase} plus attribution coverage, both
    from the SHARED parser (obs.hloattrib) — the named_scope labels end
    up as op_name path components, and the profiler's X events reuse
    the instruction names verbatim. Using hloattrib here means this
    sampled view and the static-cost view (obs.costmodel) join on
    literally the same regexes and the same innermost-scope rule."""
    txt = step.lower(snap, pods, cfg).compile().as_text()
    mapping = hloattrib.instruction_phases(txt)
    cov = hloattrib.coverage(hloattrib.attribute_bytes(txt))
    return mapping, cov


def capture(step, snap, pods, cfg, trace_dir):
    """One compiled dispatch under jax.profiler.trace (warmed first —
    the capture must hold the steady-state dispatch, not the
    compile)."""
    jax.block_until_ready(step(snap, pods, cfg).assignment)
    with jax.profiler.trace(trace_dir):
        out = step(snap, pods, cfg)
        jax.block_until_ready(out.assignment)
    return int((jax.numpy.asarray(out.assignment) >= 0).sum())


def load_trace_events(trace_dir):
    """All traceEvents from every Perfetto JSON the profiler wrote
    (plugins/profile/*/.../*.trace.json.gz)."""
    events = []
    pats = (os.path.join(trace_dir, "**", "*.trace.json.gz"),
            os.path.join(trace_dir, "**", "*.trace.json"))
    for pat in pats:
        for path in sorted(glob.glob(pat, recursive=True)):
            opener = gzip.open if path.endswith(".gz") else open
            try:
                with opener(path, "rt") as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                continue
            events.extend(doc.get("traceEvents", []))
    return events


def phase_of(event, instr2phase):
    """Map one profiler X event to a koordtrace phase, or None — the
    shared two-step join (exact instruction-name first, scope-substring
    over name + string args second) lives in obs.hloattrib now."""
    name = str(event.get("name", ""))
    args = event.get("args")
    extra = ([str(v) for v in args.values()]
             if isinstance(args, dict) else [])
    return hloattrib.phase_of_event(name, extra, instr2phase)


def attribute(events, instr2phase):
    """({phase: (total_duration_s, event_count)}, device-time coverage)
    over complete ('X') events; container/metadata events carry no
    duration and are skipped. Coverage counts how many duration-
    carrying events (and how much of their device time) mapped to a
    phase — the unmapped remainder is reported, never dropped
    silently."""
    totals = {}
    mapped_ev = unmapped_ev = 0
    mapped_s = unmapped_s = 0.0
    for ev in events:
        if ev.get("ph") != "X":
            continue
        dur_s = float(ev.get("dur", 0)) / 1e6   # trace-event us
        phase = phase_of(ev, instr2phase)
        if phase is None:
            unmapped_ev += 1
            unmapped_s += dur_s
            continue
        mapped_ev += 1
        mapped_s += dur_s
        tot, cnt = totals.get(phase, (0.0, 0))
        totals[phase] = (tot + dur_s, cnt + 1)
    total_ev = mapped_ev + unmapped_ev
    total_s = mapped_s + unmapped_s
    cov = {
        "events_total": total_ev, "events_mapped": mapped_ev,
        "event_coverage": mapped_ev / total_ev if total_ev else 0.0,
        "device_time_total_s": total_s, "device_time_mapped_s": mapped_s,
        "device_time_coverage": mapped_s / total_s if total_s else 0.0,
    }
    return totals, cov


def main():
    keep_dir = (os.environ.get("TRACE_FULLGATE_DIR") or "").strip()
    trace_dir = keep_dir or tempfile.mkdtemp(prefix="trace_fullgate_")
    print(f"platform={jax.devices()[0].platform} P={P} N={N} "
          f"capture={trace_dir}", flush=True)
    try:
        step, snap, pods, cfg = build_step()
        instr2phase, static_cov = instruction_phases(step, snap, pods,
                                                     cfg)
        print(f"hlo_instructions_mapped={len(instr2phase)} "
              f"instruction_coverage="
              f"{static_cov['instruction_coverage']:.3f} "
              f"output_byte_coverage="
              f"{static_cov['output_byte_coverage']:.3f}", flush=True)
        if static_cov["instruction_coverage"] < MIN_INSTRUCTION_COVERAGE:
            print(f"trace_fullgate: ATTRIBUTION COVERAGE below floor "
                  f"({static_cov['instruction_coverage']:.3f} < "
                  f"{MIN_INSTRUCTION_COVERAGE}) — the koord/ scope "
                  f"labels are not reaching op_name metadata",
                  flush=True)
            return 1
        placed = capture(step, snap, pods, cfg, trace_dir)
        events = load_trace_events(trace_dir)
        totals, ev_cov = attribute(events, instr2phase)
        print(f"placed={placed} profiler_events={len(events)} "
              f"attributed_phases={len(totals)} "
              f"events_mapped={ev_cov['events_mapped']}"
              f"/{ev_cov['events_total']} "
              f"device_time_mapped="
              f"{ev_cov['device_time_mapped_s'] * 1e3:.3f}ms"
              f"/{ev_cov['device_time_total_s'] * 1e3:.3f}ms",
              flush=True)
        if not totals:
            print("trace_fullgate: no phase-attributed events in this "
                  "backend's capture (empty capture is a backend "
                  "property, not a phase-table failure)", flush=True)
            return 0
        width = max(len(p) for p in totals)
        lines = []
        for phase, (dur_s, cnt) in sorted(totals.items(),
                                          key=lambda kv: -kv[1][0]):
            print(f"{phase:{width}s} total={dur_s * 1e3:9.3f}ms "
                  f"events={cnt}", flush=True)
            lines.append(jsonl_record(
                phase, dur_s,
                attrs={"source": "trace_fullgate", "events": cnt,
                       "pods": P, "nodes": N}))
        out = (os.environ.get("TRACE_FULLGATE_OUT") or "").strip()
        if out:
            with open(out, "w") as f:
                f.write("\n".join(lines) + "\n")
            print(f"koordtrace JSONL -> {out}", flush=True)
        return 0
    finally:
        if not keep_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
