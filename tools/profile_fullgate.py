"""Bisect the full-gate per-chunk cost on live hardware.

Runs the SWEEP only (no tail) at a reduced pod count so each compile is
cheap, toggling one gate family off at a time; the delta against the
all-on baseline localizes where the 100k x 10k full-gate time goes.
Usage: python tools/profile_fullgate.py [pods] [nodes]

Besides the human table, the bisection emits its per-gate deltas as
koordtrace JSONL (obs.trace.jsonl_record) keyed by the SHARED phase
table (koordinator_tpu/obs/phases.py) — the same names
tools/trace_fullgate.py attributes from the XLA profiler stream and the
`scheduler_cycle_phase_seconds{phase=...}` series carries, so the
subtractive and the sampled attributions land in one namespace and can
be compared line-for-line. PROFILE_TRACE_OUT=<path> writes the records
there; unset, they print after the table.
"""

import functools
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax

# honor JAX_PLATFORMS explicitly, before the first device query
if os.environ.get("JAX_PLATFORMS"):
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

from koordinator_tpu.obs import phases as obs_phases
from koordinator_tpu.obs.trace import jsonl_record
from koordinator_tpu.scheduler import core
from koordinator_tpu.scheduler.plugins.loadaware import LoadAwareConfig
from koordinator_tpu.utils import synthetic

P = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000
N = int(sys.argv[2]) if len(sys.argv) > 2 else 10_000
CHUNK = 2_000

# which shared phase each subtractive gate-off row attributes to; gate
# families without a kernel phase of their own (the topo score terms,
# taints) charge the whole-batch phase with the family in the attrs
GATE_PHASES = {
    "numa": obs_phases.PHASE_STAGE2_NUMA,
    "devices": obs_phases.PHASE_STAGE2_DEVICESHARE,
}


def time_sweep(tag, pods, step_kw, slim=False, pack=False):
    cfg = LoadAwareConfig.make()
    if pack:
        # mirror the bench full-gate configuration: all three nested
        # prefixes + domain classes
        pods, prefixes, _ = synthetic.pack_gate_prefixes(pods, CHUNK)
        step_kw = dict(step_kw, topo_prefix=prefixes["topo"],
                       dom_classes=synthetic.dom_classes(pods))
        if step_kw.get("enable_numa", True):
            step_kw["numa_prefix"] = prefixes["numa"]
        if step_kw.get("enable_devices", True):
            step_kw["gpu_prefix"] = prefixes["gpu"]
    stacked = synthetic.stack_pod_chunks(pods, CHUNK)
    snap = jax.device_put(synthetic.full_gate_cluster(N, num_quotas=32,
                                                      seed=0))
    stacked = jax.device_put(stacked)
    pods_d = jax.device_put(pods)
    counts = jax.device_put(tuple(getattr(pods, f)
                                  for f in core.COUNT_FIELDS))
    step = functools.partial(core.schedule_batch, num_rounds=2,
                             k_choices=8, score_dims=(0, 1),
                             approx_topk=True, tie_break=True,
                             quota_depth=2, fit_dims=(0, 1, 2, 3),
                             **step_kw)

    def charge(counts, batch, assignment):
        # mirror bench.py: the full-gate bench pays charge_all_counts
        # regardless of which gate families are compiled in, so gate-off
        # rows must keep paying it too or the bisection mislocalizes
        if slim:
            return counts
        return core.charge_all_counts(counts, batch, assignment)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def sweep(snap, counts, stacked, pods_d, cfg):
        def body(carry, cols):
            snap, counts = carry
            batch = pods_d.replace(**cols).replace(
                **dict(zip(core.COUNT_FIELDS, counts)))
            res = step(snap, batch, cfg)
            counts = charge(counts, batch, res.assignment)
            return (res.snapshot, counts), res.assignment
        (snap, counts), assign = jax.lax.scan(body, (snap, counts),
                                              stacked)
        return snap, counts, assign.reshape(-1)

    jax.block_until_ready((stacked, pods_d, cfg, snap, counts))
    t0 = time.perf_counter()
    out = sweep(snap, counts, stacked, pods_d, cfg)
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0

    runs = []
    placed = -1
    for rep in range(3):
        snap = jax.device_put(synthetic.full_gate_cluster(
            N, num_quotas=32, seed=7 + rep))
        counts = jax.device_put(tuple(getattr(pods, f)
                                      for f in core.COUNT_FIELDS))
        jax.block_until_ready((snap, counts))
        t0 = time.perf_counter()
        out = sweep(snap, counts, stacked, pods_d, cfg)
        jax.block_until_ready(out)
        runs.append(time.perf_counter() - t0)
        placed = int((out[2] >= 0).sum())
    run_s = min(runs)
    per_chunk = run_s / (P / CHUNK)
    print(f"{tag:28s} min={run_s:7.3f}s per_chunk={per_chunk * 1e3:8.1f}ms"
          f" all={['%.3f' % r for r in runs]}"
          f" placed={placed} compile={compile_s:6.1f}s", flush=True)
    return run_s


def emit_gate_trace(baseline_s, gate_rows):
    """Render the subtractive attribution as koordtrace JSONL: one
    record per gate family, `duration_s` = the delta the family costs
    over the all-on packed baseline (clamped at zero — timing noise on
    a cheap gate must not emit a negative span). Synthetic spans anchor
    at t=0 (obs.trace.jsonl_record), so any JSONL consumer — including
    obs.export's chrome conversion — renders them side by side."""
    lines = [jsonl_record(
        obs_phases.PHASE_SCHEDULE_BATCH, baseline_s,
        attrs={"source": "profile_fullgate", "row": "ALL-ON packed",
               "pods": P, "nodes": N, "chunk": CHUNK})]
    for gate, off_s in gate_rows:
        phase = GATE_PHASES.get(gate, obs_phases.PHASE_SCHEDULE_BATCH)
        lines.append(jsonl_record(
            phase, max(baseline_s - off_s, 0.0),
            attrs={"source": "profile_fullgate", "gate": gate,
                   "baseline_s": round(baseline_s, 4),
                   "gate_off_s": round(off_s, 4)}))
    out = (os.environ.get("PROFILE_TRACE_OUT") or "").strip()
    if out:
        with open(out, "w") as f:
            f.write("\n".join(lines) + "\n")
        print(f"koordtrace JSONL -> {out}", flush=True)
    else:
        for line in lines:
            print(line, flush=True)


def main():
    print(f"platform={jax.devices()[0].platform} P={P} N={N} chunk={CHUNK}",
          flush=True)
    pods = synthetic.full_gate_pods(P, N, seed=1, num_quotas=32)
    full_kw = dict(enable_numa=True, enable_devices=True)
    time_sweep("ALL-ON unpacked (ref)", pods, full_kw)
    baseline_s = time_sweep("ALL-ON packed", pods, full_kw, pack=True)
    gate_rows = [
        ("numa", time_sweep("packed, numa off", pods,
                            dict(enable_numa=False, enable_devices=True),
                            pack=True)),
        ("devices", time_sweep("packed, devices off", pods,
                               dict(enable_numa=True,
                                    enable_devices=False), pack=True)),
        ("spread", time_sweep("packed, spread off",
                              pods.replace(has_spread=False), full_kw,
                              pack=True)),
        ("anti", time_sweep("packed, anti off",
                            pods.replace(has_anti=False), full_kw,
                            pack=True)),
        ("aff", time_sweep("packed, aff off",
                           pods.replace(has_aff=False), full_kw,
                           pack=True)),
        ("taints", time_sweep("packed, taints off",
                              pods.replace(has_taints=False), full_kw,
                              pack=True)),
        ("topo_all", time_sweep("packed, topo all off", pods.replace(
            has_spread=False, has_anti=False, has_aff=False), full_kw,
            pack=True)),
    ]
    slim_pods = synthetic.synthetic_pods(P, seed=1, num_quotas=32)
    time_sweep("slim workload (ref)", slim_pods, dict(enable_numa=False),
               slim=True)
    emit_gate_trace(baseline_s, gate_rows)


if __name__ == "__main__":
    main()
