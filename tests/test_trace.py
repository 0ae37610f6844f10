"""koordtrace battery: the span tracer's structural contracts (ring
overflow, nesting, thread safety, monotonic timestamps), the Chrome
trace-event export schema Perfetto loads, `Histogram.percentile`
against numpy.quantile, the zero-overhead-when-disabled pin on the
service dispatch path, and the spans' mirror on the profiler's host
plane."""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from koordinator_tpu.metrics import Registry
from koordinator_tpu.obs import phases
from koordinator_tpu.obs.export import dump, jsonl_to_chrome
from koordinator_tpu.obs.trace import (
    NOOP_SPAN,
    SpanRecord,
    Tracer,
    jsonl_record,
)


# --- span lifecycle ---------------------------------------------------------


def test_span_records_duration_and_attrs():
    tr = Tracer()
    with tr.span("cycle", cycle=3) as a:
        a["attempt"] = 1
        time.sleep(0.002)
    (rec,) = tr.records()
    assert rec.name == "cycle" and rec.cycle == 3
    assert rec.attrs == {"attempt": 1}
    assert rec.t_end_ns > rec.t_start_ns
    assert rec.duration_s >= 0.002


def test_nested_spans_record_parent_and_inherit_cycle():
    tr = Tracer()
    with tr.span("cycle", cycle=7):
        with tr.span("dispatch"):
            with tr.span("device_wait"):
                pass
    by_name = {r.name: r for r in tr.records()}
    assert by_name["device_wait"].parent == "dispatch"
    assert by_name["dispatch"].parent == "cycle"
    assert by_name["cycle"].parent is None
    # cycle id flows down to every nested span
    assert {r.cycle for r in tr.records()} == {7}


def test_event_is_instant_and_inherits_enclosing_span():
    tr = Tracer()
    with tr.span("cycle", cycle=2):
        tr.event("quarantine", attrs={"word": 5})
    ev = [r for r in tr.records() if r.name == "quarantine"][0]
    assert ev.t_start_ns == ev.t_end_ns
    assert ev.parent == "cycle" and ev.cycle == 2


def test_exception_marks_span_and_unwinds_stack():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("cycle", cycle=0):
            with tr.span("dispatch"):
                raise RuntimeError("boom")
    by_name = {r.name: r for r in tr.records()}
    assert by_name["dispatch"].attrs["error"] == "RuntimeError"
    assert by_name["cycle"].attrs["error"] == "RuntimeError"
    # the thread-local stack fully unwound: a fresh span is a root
    with tr.span("next", cycle=1):
        pass
    assert {r.name: r for r in tr.records()}["next"].parent is None


def test_observer_fires_per_close_with_duration():
    seen = []
    tr = Tracer(observer=lambda name, dur: seen.append((name, dur)))
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    assert [n for n, _ in seen] == ["inner", "outer"]
    assert all(d >= 0 for _, d in seen)


# --- ring overflow ----------------------------------------------------------


def test_ring_overflow_drops_oldest_and_counts():
    drops = []
    tr = Tracer(capacity=4, on_drop=lambda: drops.append(1))
    for i in range(10):
        tr.record_span(f"s{i}", 0, 1)
    recs = tr.records()
    assert len(recs) == 4
    # the NEWEST four survive, oldest first
    assert [r.name for r in recs] == ["s6", "s7", "s8", "s9"]
    assert tr.dropped == 6 and len(drops) == 6


def test_capacity_validated():
    with pytest.raises(ValueError):
        Tracer(capacity=0)


# --- monotonic timestamps / thread safety -----------------------------------


def test_timestamps_monotonic_within_thread():
    tr = Tracer()
    for _ in range(50):
        with tr.span("tick"):
            pass
    recs = tr.records()
    assert all(r.t_end_ns >= r.t_start_ns for r in recs)
    starts = [r.t_start_ns for r in recs]
    assert starts == sorted(starts)
    # the anchor pair lets post-hoc analysis map monotonic -> epoch
    assert tr.anchor_monotonic_ns <= recs[0].t_start_ns
    assert tr.anchor_unix_ns > 0


def test_threaded_spans_nest_independently():
    tr = Tracer()
    n_threads, n_spans = 8, 200

    def worker(tid):
        for i in range(n_spans):
            with tr.span(f"outer_t{tid}", cycle=tid):
                with tr.span(f"inner_t{tid}"):
                    pass

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = tr.records()
    assert len(recs) == n_threads * n_spans * 2 and tr.dropped == 0
    # parent attribution never crosses threads: every inner span's
    # parent is its OWN thread's outer span, and cycle ids match
    for r in recs:
        if r.name.startswith("inner_t"):
            tid = int(r.name[len("inner_t"):])
            assert r.parent == f"outer_t{tid}"
            assert r.cycle == tid


# --- Chrome export schema ---------------------------------------------------


def test_chrome_export_schema():
    tr = Tracer()
    with tr.span("cycle", cycle=1, attrs={"attempt": 0}):
        with tr.span("dispatch"):
            pass
        tr.event("retry", attrs={"failure_class": "XLA_TRANSIENT"})
    doc = json.loads(json.dumps(tr.to_chrome()))   # JSON-serializable
    evs = doc["traceEvents"]
    assert {e["name"] for e in evs} == {"cycle", "dispatch", "retry"}
    for e in evs:
        assert e["cat"] == "koordtrace"
        assert e["pid"] == tr.pid and isinstance(e["tid"], int)
        assert e["args"]["cycle"] == 1
        if e["name"] == "retry":
            assert e["ph"] == "i" and e["s"] == "t" and "dur" not in e
            assert e["args"]["failure_class"] == "XLA_TRANSIENT"
        else:
            assert e["ph"] == "X" and e["dur"] >= 0
            assert isinstance(e["ts"], float)
    other = doc["otherData"]
    assert other["tracer"] == "koordtrace" and other["dropped"] == 0
    assert other["anchor_unix_ns"] > 0


def test_jsonl_roundtrips_to_chrome():
    tr = Tracer()
    with tr.span("cycle", cycle=4):
        tr.event("quarantine")
    lines = tr.to_jsonl().splitlines()
    assert all(json.loads(l) for l in lines)
    doc = jsonl_to_chrome(lines)
    assert {e["name"] for e in doc["traceEvents"]} == \
        {"cycle", "quarantine"}
    inst = [e for e in doc["traceEvents"] if e["name"] == "quarantine"][0]
    assert inst["ph"] == "i" and inst["args"]["parent"] == "cycle"


def test_jsonl_record_synthetic_span():
    line = jsonl_record(phases.PHASE_STAGE2_NUMA, 0.25,
                        attrs={"gate": "numa"})
    r = json.loads(line)
    assert r["span"] == phases.PHASE_STAGE2_NUMA
    assert r["t_start_ns"] == 0 and r["t_end_ns"] == 250_000_000
    # negative deltas (timing noise) clamp to an instant, not a crash
    r2 = json.loads(jsonl_record("x", -0.1))
    assert r2["t_end_ns"] == 0


def test_dump_writes_requested_formats(tmp_path):
    tr = Tracer()
    with tr.span("cycle", cycle=0):
        pass
    reg = Registry()
    reg.counter("c_total").inc()
    paths = dump(tr, registry=reg, out_dir=str(tmp_path), prefix="t",
                 formats=("chrome", "jsonl", "prom"))
    assert [p.rsplit("/", 1)[-1] for p in paths] == \
        ["t.trace.json", "t.jsonl", "t.prom"]
    chrome = json.loads((tmp_path / "t.trace.json").read_text())
    assert chrome["traceEvents"]
    assert "c_total 1" in (tmp_path / "t.prom").read_text()
    # absent sources skip silently: no tracer -> prom only
    only = dump(None, registry=reg, out_dir=str(tmp_path), prefix="p",
                formats=("chrome", "jsonl", "prom"))
    assert [p.rsplit("/", 1)[-1] for p in only] == ["p.prom"]


# --- phase table ------------------------------------------------------------


def test_phase_table_check():
    assert phases.check_phase(phases.PHASE_TOPK) == phases.PHASE_TOPK
    with pytest.raises(ValueError):
        phases.check_phase("koord/not_a_phase")
    assert set(phases.CYCLE_SKELETON) <= phases.HOST_SPANS
    assert phases.ALL_PHASES == phases.KERNEL_PHASES | phases.HOST_SPANS


# --- Histogram.percentile vs numpy ------------------------------------------


def test_histogram_percentile_tracks_numpy_quantile():
    from koordinator_tpu.scheduler.metrics_defs import PHASE_BUCKETS

    r = Registry()
    h = r.histogram("lat_seconds", labels=("phase",),
                    buckets=PHASE_BUCKETS)
    rng = np.random.default_rng(42)
    draws = rng.uniform(0.0005, 0.4, size=2000)
    for d in draws:
        h.labels("dispatch").observe(float(d))
    for q in (0.5, 0.9, 0.99):
        est = h.percentile(q, "dispatch")
        exact = float(np.quantile(draws, q))
        # bucketed estimate is exact only to the enclosing bucket's
        # width: the estimate and the true quantile share a bucket
        bounds = [0.0] + [b for b in PHASE_BUCKETS]
        idx_est = np.searchsorted(bounds, est, side="left")
        idx_exact = np.searchsorted(bounds, exact, side="left")
        assert abs(idx_est - idx_exact) <= 1, (q, est, exact)
        lo = bounds[max(min(idx_exact, len(bounds) - 1) - 1, 0)]
        hi = bounds[min(idx_exact + 1, len(bounds) - 1)]
        assert lo <= est <= hi, (q, est, exact)


def test_histogram_percentile_edge_cases():
    r = Registry()
    h = r.histogram("x_seconds", buckets=(0.1, 1.0))
    assert h.percentile(0.5) is None          # empty child
    h.observe(0.05)
    assert 0.0 <= h.percentile(0.5) <= 0.1    # first-bucket lower bound 0
    h2 = r.histogram("y_seconds", buckets=(0.1,))
    h2.observe(5.0)                           # lands in +Inf
    assert h2.percentile(0.99) == 0.1         # clamps to last finite bound
    with pytest.raises(ValueError):
        h.percentile(1.5)


# --- zero overhead when disabled --------------------------------------------


def test_noop_span_is_shared_and_stateless():
    assert NOOP_SPAN.__enter__() is None
    with NOOP_SPAN as a:
        assert a is None


def test_disabled_service_span_path_allocates_nothing():
    """trace=None must keep the dispatch path allocation-free in
    obs/trace.py: `_span` returns the shared NOOP_SPAN singleton and a
    full schedule() makes no allocation attributable to the tracer
    module (tracemalloc filtered to obs/trace.py)."""
    import tracemalloc

    from koordinator_tpu.obs import trace as trace_mod
    from koordinator_tpu.scheduler.frameworkext import SchedulerService
    from koordinator_tpu.utils import synthetic

    svc = SchedulerService(num_rounds=1, k_choices=4)
    assert svc.tracer is None
    assert svc._span("cycle") is NOOP_SPAN
    assert svc._span("dispatch", cycle=3) is NOOP_SPAN
    svc.publish(synthetic.synthetic_cluster(16, num_quotas=4))
    svc.schedule(synthetic.synthetic_pods(16, num_quotas=4))  # warm

    filt = tracemalloc.Filter(True, trace_mod.__file__)
    tracemalloc.start()
    try:
        svc.schedule(synthetic.synthetic_pods(16, seed=5, num_quotas=4))
        snap = tracemalloc.take_snapshot().filter_traces([filt])
    finally:
        tracemalloc.stop()
    stats = snap.statistics("lineno")
    assert stats == [], [str(s) for s in stats]


def test_enabled_service_cycle_carries_skeleton():
    """The flip side of the zero-overhead pin: trace=True records the
    full committed-cycle span skeleton with one shared cycle id."""
    from koordinator_tpu.scheduler.frameworkext import SchedulerService
    from koordinator_tpu.utils import synthetic

    svc = SchedulerService(num_rounds=1, k_choices=4, trace=True)
    svc.publish(synthetic.synthetic_cluster(16, num_quotas=4))
    svc.schedule(synthetic.synthetic_pods(16, num_quotas=4))
    recs = svc.tracer.records()
    names = {r.name for r in recs}
    # journal_append only appears on journaled services
    assert set(phases.CYCLE_SKELETON) - {phases.SPAN_JOURNAL_APPEND} \
        <= names
    cycles = {r.cycle for r in recs if r.name == phases.SPAN_CYCLE}
    assert cycles == {0}
    for r in recs:
        assert r.name in phases.ALL_PHASES


# --- the mirror on the profiler's host plane --------------------------------

# the spans every committed cycle of a small service opens (no journal,
# no auto-pack reorder at 16 pods: no journal_append, no unpack)
MIRRORED = (phases.SPAN_CYCLE, phases.SPAN_ADMIT, phases.SPAN_AMP_CHECK,
            phases.SPAN_PREPARE_BATCH, phases.SPAN_DISPATCH,
            phases.SPAN_DEVICE_WAIT, phases.SPAN_GUARD_SCAN,
            phases.SPAN_PUBLISH, phases.SPAN_FINALIZE)


def host_plane_events(trace_dir, names):
    """{name: [(start, end)] in start order} of the host-plane events
    named in `names`, from every .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData

    out = {}
    for path in glob.glob(os.path.join(str(trace_dir), "**",
                                       "*.xplane.pb"), recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        out.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return {k: sorted(v) for k, v in out.items()}


def captured_cycles(trace_dir, trace, n):
    """A small service, warmed outside the capture, then `n` schedule()
    calls inside a CPU profiler capture."""
    import jax

    from koordinator_tpu.scheduler.frameworkext import SchedulerService
    from koordinator_tpu.utils import synthetic

    svc = SchedulerService(num_rounds=1, k_choices=4, trace=trace)
    svc.publish(synthetic.synthetic_cluster(16, num_quotas=4))
    svc.schedule(synthetic.synthetic_pods(16, num_quotas=4))
    jax.profiler.start_trace(str(trace_dir))
    try:
        for i in range(n):
            svc.schedule(synthetic.synthetic_pods(16, seed=i + 1,
                                                  num_quotas=4))
    finally:
        jax.profiler.stop_trace()
    return svc


def test_spans_are_mirrored_on_the_profiler_host_plane(tmp_path):
    """A traced schedule() puts each span on the profiler's host plane
    under its own name, once per cycle, nested as the ring nests it."""
    n = 2
    svc = captured_cycles(tmp_path, True, n)
    plane = host_plane_events(tmp_path, phases.HOST_SPANS)
    ring = [r for r in svc.tracer.records() if r.cycle >= 1]
    for name in MIRRORED:
        assert len(plane.get(name, [])) == n, (name, plane.get(name))
        assert sum(r.name == name for r in ring) == n, name
    parents = {(r.name, r.parent) for r in ring
               if r.name in MIRRORED and r.parent is not None}
    assert (phases.SPAN_AMP_CHECK, phases.SPAN_ADMIT) in parents
    assert (phases.SPAN_ADMIT, phases.SPAN_CYCLE) in parents
    for child, parent in parents:
        for (cs, ce), (ps, pe) in zip(plane[child], plane[parent]):
            assert ps <= cs and ce <= pe, (child, parent)
    # finalize follows its cycle's span, inside the same call
    for (_, cycle_end), (fin_start, _) in zip(plane[phases.SPAN_CYCLE],
                                              plane[phases.SPAN_FINALIZE]):
        assert cycle_end <= fin_start


def test_disabled_service_opens_no_annotation(tmp_path):
    """trace=None mirrors nothing: the capture holds the kernel timer's
    own annotation and no koordtrace span."""
    captured_cycles(tmp_path, None, 1)
    plane = host_plane_events(
        tmp_path, phases.HOST_SPANS | {phases.PHASE_SCHEDULE_BATCH})
    assert set(plane) == {phases.PHASE_SCHEDULE_BATCH}
