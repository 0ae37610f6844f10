"""The trace reduction the per-layer readers rest on: busy union, idle
gaps and share, per-phase and collective time, on hand-built events, on
a profiler trace recorded here on the CPU, and on a few cycles of a
trace recorded on the chip (benchmark/fixtures/)."""

import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import BENCH

import run
import tracereduce as tr

FIXTURES = os.path.join(BENCH, "fixtures")


def test_union_merges_overlaps_and_clips_to_the_window():
    starts, ends = [0, 5, 6, 20], [10, 8, 12, 25]
    assert tr.union_length(starts, ends) == 17.0
    assert tr.union_length(starts, ends, lo=9, hi=22) == 5.0
    assert tr.union_length([], []) == 0.0
    assert tr.idle_gaps(starts, ends, -5, 30) == [(-5, 0), (12, 20),
                                                   (25, 30)]


def intervals(events):
    names = sorted({e[2] for e in events})
    return tr.Intervals([e[0] for e in events], [e[1] for e in events],
                        [names.index(e[2]) for e in events], names)


def view_of(devices, host, cycles=2, chips=1, modules=()):
    ops = {k: {"ops": intervals(v), "programs": intervals(list(modules))}
           for k, v in devices.items()}
    return tr.TraceView([], ops, host, cycles, 1.0, chips)


def metric(name, view):
    return run.read_metric(run.ROOT, name, view)


def test_readers_on_hand_built_events():
    ms = 1_000_000
    host = [("bench/window", 0, 100 * ms), ("bench/schedule", 0, 40 * ms),
            ("bench/schedule", 50 * ms, 90 * ms)]
    dev = [(0, 10 * ms, "fusion.1"), (5 * ms, 20 * ms, "while.2"),
           (60 * ms, 70 * ms, "all-gather.3")]
    progs = [(0, 20 * ms, "jit_guarded_schedule_batch"),
             (60 * ms, 70 * ms, "jit_add")]
    one = view_of({0: dev}, host, modules=progs)
    # the served program's runs only: jit_add is not a kernel of it
    assert metric("kernel_ms", one) == pytest.approx(10.0)
    assert metric("device_idle_pct", one) == pytest.approx(70.0)
    assert metric("collective_ms", one) is None  # one chip
    two = view_of({0: dev, 1: dev[:2]}, host, chips=2, modules=progs)
    assert metric("collective_ms", two) == pytest.approx(2.5)
    br = one.breakdown()
    gaps = dict(br["idle_gaps"])
    assert gaps["bench/schedule"] == pytest.approx(0.050)
    assert gaps["outside bench spans"] == pytest.approx(0.020)
    assert br["device_ops"][0] == ["program jit_guarded_schedule_batch",
                                   pytest.approx(0.020)]
    assert ["op while.2", pytest.approx(0.015)] in br["device_ops"]
    assert tr.short_name("%fusion.12 = f32[4]{0} fusion(f32[4] %all-gather.1)"
                         ) == "fusion.12"


def test_readers_find_nothing_where_nothing_ran():
    empty = view_of({}, [])
    for name in ("kernel_ms", "device_idle_pct",
                 "collective_ms", "admit_ms", "commit_ms", "dispatch_ms"):
        assert metric(name, empty) is None


def test_host_spans_per_cycle():
    rec = lambda n, a, b: SimpleNamespace(name=n, t_start_ns=a,  # noqa
                                          t_end_ns=b)
    spans = [rec("admit", 0, 2e6), rec("admit", 10e6, 14e6),
             rec("dispatch", 2e6, 3e6), rec("publish", 5e6, 6e6),
             rec("guard_scan", 4e6, 5e6)]
    view = tr.TraceView(spans, {}, [], 2, 1.0, 1)
    assert metric("admit_ms", view) == pytest.approx(3.0)
    assert metric("commit_ms", view) == pytest.approx(1.0)
    assert metric("dispatch_ms", view) == pytest.approx(1.0)


def test_reads_a_profiler_trace_recorded_here(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(run.SPAN_WINDOW):
        for _ in range(2):
            with jax.profiler.TraceAnnotation(run.SPAN_SCHEDULE):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    view = tr.TraceView.from_run(str(tmp_path), [], 0, 1, 2, 1.0, 1)
    names = [n for n, _, _ in view.host]
    assert names.count("bench/schedule") == 2
    assert 0 < view.window_s < 5
    assert view.devices == {}  # the CPU has no TPU plane


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(FIXTURES) if f.startswith("trace_")))
def test_readers_on_cycles_recorded_on_the_chip(name):
    """A few cycles of a chip trace (device ops and programs of each
    chip, harness spans) and the numbers the readers give on them
    (`kernel_ms` recomputed in PR 22 when it came to read the served
    program's runs alone)."""
    with open(os.path.join(FIXTURES, name)) as f:
        fx = json.load(f)
    devices = {int(k): {line: tr.Intervals(*iv) for line, iv in v.items()}
               for k, v in fx["devices"].items()}
    host = [tuple(h) for h in fx["host"]]
    view = tr.TraceView([], devices, host, fx["cycles"], 0.0, len(devices))
    for key, want in fx["expect"].items():
        got = view.breakdown() if key == "breakdown" else metric(key, view)
        assert got == pytest.approx(want, rel=1e-9) if key != "breakdown" \
            else got == want, key
    assert 0 < metric("kernel_ms", view) <= view.window_s * 1e3 / fx["cycles"]
    assert 0 <= metric("device_idle_pct", view) < 100
