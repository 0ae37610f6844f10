"""koordtrace smoke: the end-to-end observability contract in CI.

On a small full-gate workload with a journaled, traced
SchedulerService this stage asserts:

  1. SKELETON   — every committed cycle records the full host span
                  skeleton (admit -> dispatch -> device_wait ->
                  guard_scan -> journal_append -> publish -> finalize)
                  under one shared cycle id, plus the checkpoint
                  epilogue;
  2. LOADABLE   — the Chrome dump is valid trace-event JSON (complete
                  X events with us timestamps, instant events marked
                  ph='i'), i.e. Perfetto-loadable;
  3. FAULTS     — a corrupted-snapshot cycle carries the quarantine
                  event (guard word + defect list in its attrs) and a
                  runtime-fault cycle carries the retry + backoff +
                  ladder_transition records;
  4. NAMES      — every recorded span name resolves against the shared
                  phase table (obs/phases.py), so the trace, the
                  `scheduler_cycle_phase_seconds{phase=...}` series,
                  and the kernel named_scope labels stay one namespace;
  5. JOIN      — journal_append span attrs carry (epoch, chunk) that
                  match the commit journal's own records — the
                  trace <-> commit-log join key.

Runs on CPU in CI (tools/ci.sh); correctness-only, never wall-clock.
Usage: JAX_PLATFORMS=cpu python tools/trace_smoke.py
"""

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

import numpy as np

from koordinator_tpu.metrics import Registry
from koordinator_tpu.obs import phases
from koordinator_tpu.scheduler.frameworkext import SchedulerService
from koordinator_tpu.scheduler.journal import CommitJournal
from koordinator_tpu.scheduler.metrics_defs import SchedulerMetrics
from koordinator_tpu.testing import faults
from koordinator_tpu.utils import synthetic

N_NODES, N_PODS = 64, 128
SEED = int(os.environ.get("TRACE_SEED", "0"))


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def make_service(workdir, **kw):
    svc = SchedulerService(
        metrics=SchedulerMetrics(Registry()), num_rounds=2, k_choices=4,
        journal=CommitJournal(os.path.join(workdir, "journal.bin")),
        trace=True, **kw)
    svc._sleep = lambda _s: None  # smoke runs don't wait out backoff
    return svc


def spans_by_cycle(tracer):
    by_cycle = {}
    for r in tracer.records():
        by_cycle.setdefault(r.cycle, []).append(r)
    return by_cycle


def check_clean_cycles(workdir):
    """Two committed cycles; each carries the full skeleton under its
    own cycle id, the journal join key matches, and the Chrome dump is
    loadable."""
    svc = make_service(workdir)
    snap = synthetic.full_gate_cluster(N_NODES, seed=SEED, num_quotas=8,
                                       num_gangs=8)
    svc.publish(snap)
    for i in range(2):
        pods = synthetic.full_gate_pods(N_PODS, N_NODES, seed=SEED + i,
                                        num_quotas=8, num_gangs=8)
        res = svc.schedule(pods)
        check(int((np.asarray(res.assignment) >= 0).sum()) > 0,
              f"cycle {i} placed nothing")

    by_cycle = spans_by_cycle(svc.tracer)
    for cyc in (0, 1):
        names = {r.name for r in by_cycle.get(cyc, [])}
        missing = set(phases.CYCLE_SKELETON) - names
        check(not missing,
              f"cycle {cyc} skeleton incomplete: missing {sorted(missing)} "
              f"(got {sorted(names)})")
        check(phases.SPAN_CYCLE in names, f"cycle {cyc} has no cycle span")
        check(phases.SPAN_CHECKPOINT in names,
              f"cycle {cyc} missing the checkpoint epilogue")
    # 4. every name resolves against the table
    for r in svc.tracer.records():
        check(r.name in phases.ALL_PHASES,
              f"span {r.name!r} not in the shared phase table")
    # 5. the trace <-> commit-log join: journal_append attrs vs journal
    appends = [r for r in svc.tracer.records()
               if r.name == phases.SPAN_JOURNAL_APPEND]
    check(len(appends) == 2, f"expected 2 journal_append spans, "
                             f"got {len(appends)}")
    for r in appends:
        epoch, chunk = r.attrs.get("epoch"), r.attrs.get("chunk")
        check(epoch is not None and chunk is not None,
              f"journal_append span missing the epoch/chunk join key: "
              f"{r.attrs}")
        check(chunk in svc.journal.records_for(epoch),
              f"journal has no record for traced (epoch={epoch}, "
              f"chunk={chunk})")
    # phase metric observed from the same spans
    p50 = svc.metrics.cycle_phase_seconds.percentile(
        0.5, phases.SPAN_DISPATCH)
    check(p50 is not None and p50 >= 0,
          "cycle_phase_seconds{phase=dispatch} never observed")

    # 2. dump + validate the Chrome JSON
    out = svc.dump_trace(workdir, prefix="smoke")
    chrome_path = [p for p in out if p.endswith(".trace.json")][0]
    with open(chrome_path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    check(len(evs) >= len(svc.tracer.records()),
          "chrome dump lost records")
    for e in evs:
        check(e["ph"] in ("X", "i"), f"unexpected phase type {e['ph']!r}")
        check(isinstance(e["ts"], (int, float)), "non-numeric ts")
        if e["ph"] == "X":
            check(e["dur"] >= 0, "negative duration")
        else:
            check(e.get("s") == "t", "instant event missing scope")
    check(doc["otherData"]["dropped"] == 0, "clean run dropped spans")
    prom_path = [p for p in out if p.endswith(".prom")][0]
    with open(prom_path) as f:
        prom = f.read()
    check("scheduler_cycle_phase_seconds" in prom,
          "prom dump missing the phase histogram")
    return {"cycles": 2, "spans": len(svc.tracer.records()),
            "chrome_events": len(evs)}


def check_quarantine_cycle(workdir):
    """3. a corrupted-snapshot cycle carries the quarantine event with
    the guard word + defect attribution."""
    inj = faults.FaultInjector(SEED)
    svc = make_service(workdir)
    snap = synthetic.full_gate_cluster(N_NODES, seed=SEED + 3,
                                       num_quotas=8, num_gangs=8)
    bad_snap, rows = inj.corrupt_snapshot(snap, "nan_metric_column",
                                          n_rows=2)
    svc.publish(bad_snap)
    pods = synthetic.full_gate_pods(N_PODS, N_NODES, seed=SEED + 4,
                                    num_quotas=8, num_gangs=8)
    svc.schedule(pods)
    quars = [r for r in svc.tracer.records()
             if r.name == phases.EVENT_QUARANTINE]
    check(len(quars) == 1, f"expected 1 quarantine event, got {len(quars)}")
    q = quars[0]
    check(q.t_start_ns == q.t_end_ns, "quarantine must be an instant event")
    check(q.attrs.get("word", 0) != 0, f"quarantine attrs carry no guard "
                                       f"word: {q.attrs}")
    check(q.attrs.get("defects"), "quarantine attrs carry no defect list")
    check(q.attrs.get("bad_nodes") == len(rows),
          f"quarantine bad_nodes {q.attrs.get('bad_nodes')} != "
          f"{len(rows)} corrupted rows")
    check(q.cycle == 0, "quarantine event not attributed to its cycle")
    return {"word": hex(q.attrs["word"]), "bad_nodes": len(rows)}


def check_degraded_cycle(workdir):
    """3. a runtime-fault cycle records retry + backoff + the
    ladder_transition the failure caused, all under the cycle's id."""
    inj = faults.FaultInjector(SEED)
    svc = make_service(workdir)
    snap = synthetic.full_gate_cluster(N_NODES, seed=SEED + 7,
                                       num_quotas=8, num_gangs=8)
    svc.publish(snap)
    pods = synthetic.full_gate_pods(N_PODS, N_NODES, seed=SEED + 8,
                                    num_quotas=8, num_gangs=8)
    # cycle 0: a transient XLA failure — retried in place with backoff
    svc.fault_injection = inj.xla_transient(fail_attempts={1, 2})
    svc.schedule(pods)
    # cycle 1: persistent OOM — walks the degradation ladder
    svc.fault_injection = inj.oom_above(N_PODS // 2)
    svc.schedule(pods)
    recs = svc.tracer.records()
    retries = [r for r in recs if r.name == phases.EVENT_RETRY]
    check(len(retries) >= 2, "faulted cycles recorded no retry events")
    check(all(r.attrs.get("failure_class") for r in retries),
          "retry events carry no failure_class")
    backoffs = [r for r in recs if r.name == phases.SPAN_BACKOFF
                and r.cycle == 0]
    check(backoffs, "the transient cycle recorded no backoff span")
    check(all(r.attrs.get("delay_s") is not None for r in backoffs),
          "backoff spans carry no delay")
    trans = [r for r in recs
             if r.name == phases.EVENT_LADDER_TRANSITION
             and r.cycle == 1]
    check(trans, "degradation recorded no ladder_transition event")
    check(any(r.attrs.get("to") for r in trans),
          f"ladder_transition events carry no target rung: "
          f"{[r.attrs for r in trans]}")
    # the final (successful) attempt's cycle span says which rung ran
    cycles = [r for r in recs if r.name == phases.SPAN_CYCLE
              and r.cycle == 1]
    check(len(cycles) >= 2, "the degraded schedule() should record one "
                            "cycle span per attempt")
    check(cycles[-1].attrs.get("ladder") not in (None, "normal"),
          f"the committed attempt's cycle span does not carry the "
          f"degraded rung: {cycles[-1].attrs}")
    # every fault-path name still resolves
    for r in recs:
        check(r.name in phases.ALL_PHASES,
              f"span {r.name!r} not in the shared phase table")
    return {"retries": len(retries),
            "transitions": [r.attrs.get("to") for r in trans],
            "committed_ladder": cycles[-1].attrs.get("ladder")}


def main():
    stages = (("clean-cycles", check_clean_cycles),
              ("quarantine", check_quarantine_cycle),
              ("degraded", check_degraded_cycle))
    failures = []
    for name, fn in stages:
        workdir = tempfile.mkdtemp(prefix=f"trace_smoke_{name}_")
        try:
            verdict = fn(workdir)
            print(f"TRACE OK   {name}: {verdict}", flush=True)
        except AssertionError as exc:
            failures.append((name, str(exc)))
            print(f"TRACE FAIL {name}: {exc}", flush=True)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
    print(f"TRACE SMOKE: {len(stages) - len(failures)}/{len(stages)} "
          f"stages green (seed {SEED})", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
