"""The `upload_ms` reader: the mean koordtrace `upload` span per cycle,
on hand-built spans, None where the program opens no such span, and
read from a traced run of a tiny cell on the CPU, where every cycle's
batch crosses as one packed buffer."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench_tiny import ROOT

import run
import tracereduce as tr

MS = 1_000_000


def rec(name, start_ms, end_ms, cycle, **attrs):
    return SimpleNamespace(name=name, t_start_ns=int(start_ms * MS),
                           t_end_ns=int(end_ms * MS), cycle=cycle,
                           attrs=attrs)


def view_of(spans, cycles):
    return tr.TraceView(spans, {}, [], cycles, 1.0, 1)


def metric(name, view):
    return run.read_metric(run.ROOT, name, view)


def test_mean_upload_span_per_cycle():
    spans = [rec("dispatch", 0, 5, 0), rec("upload", 0.5, 1.0, 0),
             rec("dispatch", 10, 16, 1), rec("upload", 10.5, 12.0, 1),
             rec("device_wait", 16, 20, 1)]
    assert metric("upload_ms", view_of(spans, 2)) == pytest.approx(1.0)


def test_no_upload_span_reads_nothing():
    # the parent program: a dispatch span, no upload inside it
    spans = [rec("dispatch", 0, 5, 0), rec("device_wait", 5, 9, 0)]
    assert metric("upload_ms", view_of(spans, 1)) is None
    assert metric("upload_ms", view_of([], 0)) is None


def test_the_uploaded_program_is_the_served_one_for_the_readers():
    """A batch uploaded as one buffer runs the served program under its
    own module name, so `kernel_ms` reads its runs as before."""
    name = "jit_guarded_schedule_batch"
    progs = tr.Intervals([0, 30 * MS], [20 * MS, 35 * MS], [0, 1],
                         [name, "jit_greater"])
    ops = tr.Intervals([0], [20 * MS], [0], ["fusion.1"])
    view = tr.TraceView([], {0: {"ops": ops, "programs": progs}},
                        [("bench/window", 0, 100 * MS)], 2, 0.1, 1)
    assert metric("kernel_ms", view) == pytest.approx(10.0)


def test_a_traced_tiny_cell_reports_upload_ms(checkout):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "tiny-colocation.lsbe", "--seed", "3000000023", "--seconds", "1",
         "--trace", "1"], cwd=checkout, env=env, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["checks"]
    upload = result["metrics"]["upload_ms"]["value"]
    assert 0 < upload < result["metrics"]["dispatch_ms"]["value"]
