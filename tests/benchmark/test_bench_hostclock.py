"""The service's spans on the profiler's clock (`benchmark/hostclock.py`):
the clock join, the idle split by the innermost span, the four readers
that rest on them, on hand-built events and on the chip fixture, and
one traced run of a tiny cell on the CPU whose mirrored spans are read
back from the profiler's host plane."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench_tiny import BENCH, ROOT

import hostclock as hc
import run
import tracereduce as tr

FIXTURES = os.path.join(BENCH, "fixtures")
# two calls recorded on the chip with the service's spans and the ring
SPANS_FIXTURE = "trace_colocation-10k.lsbe-backlog.spans.json"
MS, US = 1_000_000, 1_000
# ring time = profiler time + OFFSET
OFFSET = 5_000_000_000


def rec(name, start, end, cycle):
    """A ring record at profiler times start/end (ns)."""
    return SimpleNamespace(name=name, t_start_ns=start + OFFSET,
                           t_end_ns=end + OFFSET, cycle=cycle)


def intervals(events):
    names = sorted({e[2] for e in events})
    return tr.Intervals([e[0] for e in events], [e[1] for e in events],
                        [names.index(e[2]) for e in events], names)


# the chip's events show this much early against the host's (the
# profiler's own misalignment, 0.1-1.1 ms on a v5e host)
SKEW = MS


def two_cycles(service=True):
    """Two 30 ms schedule() calls 40 ms apart. In each, at ms from the
    call's start: admit 0.01-2 (amp_check 0.5-1.5, whose program runs
    0.51-0.52 on the chip), dispatch 2-8, device_wait 9-20, the served
    program 5-18 on the chip, finalize 25-29.99; the ring's first span
    opens and its last closes 10 us inside the call. The chip's events
    show SKEW early. Without `service` only the spans the parent
    program records."""
    host = [("bench/window", 0, 80 * MS)]
    spans, progs = [], []
    for k in range(2):
        t = k * 40 * MS
        host.append(("bench/schedule", t, t + 30 * MS))
        spans += [rec("cycle", t + 10 * US, t + 25 * MS, k),
                  rec("admit", t + 10 * US, t + 2 * MS, k),
                  rec("dispatch", t + 2 * MS, t + 8 * MS, k),
                  rec("device_wait", t + 9 * MS, t + 20 * MS, k),
                  rec("checkpoint", t + 29990 * US, t + 29990 * US, k)]
        if service:
            spans += [rec("amp_check", t + MS // 2, t + 3 * MS // 2, k),
                      rec("finalize", t + 25 * MS, t + 29990 * US, k)]
        progs += [(t + 510 * US - SKEW, t + 520 * US - SKEW, "jit_greater"),
                  (t + 5 * MS - SKEW, t + 18 * MS - SKEW,
                   "jit_guarded_schedule_batch")]
    ops = [(s, e, "fusion.1") for s, e, _ in progs]
    devices = {0: {"ops": intervals(ops), "programs": intervals(progs)}}
    return tr.TraceView(spans, devices, host, 2, 1.0, 1)


def metric(name, view):
    return run.read_metric(run.ROOT, name, view)


def test_clock_offset_is_bounded_by_the_harness_calls():
    view = two_cycles()
    offset, slack = hc.clock_offset(view)
    assert offset == pytest.approx(OFFSET) and slack == pytest.approx(
        10 * US)
    placed = hc.placed_spans(view, ("dispatch",))
    assert [(n, s, e, c) for n, s, e, c in placed] == [
        ("dispatch", 2 * MS, 8 * MS, 0), ("dispatch", 42 * MS, 48 * MS, 1)]
    # a cycle in the ring with no harness call to pair it with
    view.host = view.host[:-1]
    assert hc.clock_offset(view) is None
    assert hc.placed_spans(view, ("dispatch",)) is None
    assert metric("launch_ms", view) is None


def test_the_chip_clock_is_put_right_by_the_sync_span():
    # the least offset that puts the sync program inside its span: the
    # skew, less the 10 us the program started after the span opened
    assert hc.device_offset(two_cycles()) == pytest.approx(SKEW - 10 * US)
    # no amp_check span (the parent program), no offset
    assert hc.device_offset(two_cycles(service=False)) is None


@pytest.mark.parametrize("service", [True, False])
def test_readers_on_hand_built_events(service):
    view = two_cycles(service)
    if service:
        # on the corrected clock the program starts 2.99 ms after
        # dispatch opens and ends 2.01 ms before device_wait closes
        assert metric("launch_ms", view) == pytest.approx(2.99)
        assert metric("readback_ms", view) == pytest.approx(2.01)
        assert metric("amp_check_ms", view) == pytest.approx(1.0)
        assert metric("finalize_ms", view) == pytest.approx(4.99)
    else:
        # the parent program records none of the spans they rest on
        for name in ("launch_ms", "readback_ms", "amp_check_ms",
                     "finalize_ms"):
            assert metric(name, view) is None


def test_readers_find_nothing_where_nothing_ran():
    empty = tr.TraceView([], {}, [], 0, 1.0, 1)
    no_chip = two_cycles()
    no_chip.devices = {}
    for name in ("launch_ms", "readback_ms", "amp_check_ms", "finalize_ms"):
        assert metric(name, empty) is None
    for name in ("launch_ms", "readback_ms"):
        assert metric(name, no_chip) is None


def test_idle_goes_to_the_innermost_open_span():
    host = [("bench/window", 0, 100), ("bench/schedule", 10, 60),
            ("bench/count_carry", 60, 70)]
    service = [("cycle", 12, 50), ("admit", 12, 20), ("amp_check", 14, 18),
               ("dispatch", 22, 30), ("finalize", 52, 58)]
    gaps = [(0, 11), (15, 25), (30, 35), (50, 65), (90, 100)]
    split = hc.idle_split(gaps, host + service)
    want = {"outside bench spans": 20e-9, "bench/schedule": 5e-9,
            "amp_check": 3e-9, "admit": 2e-9, "cycle": 7e-9,
            "dispatch": 3e-9, "finalize": 6e-9, "bench/count_carry": 5e-9}
    assert split == pytest.approx(want)
    assert sum(split.values()) == pytest.approx(
        sum(b - a for a, b in gaps) / 1e9)
    # without the service's spans, what the harness spans alone give
    assert hc.idle_split(gaps, host) == pytest.approx(
        {"outside bench spans": 20e-9, "bench/schedule": 26e-9,
         "bench/count_carry": 5e-9})


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(FIXTURES) if f.startswith("trace_")))
def test_harness_spans_alone_split_as_the_breakdown(name):
    """On a chip trace of the parent program (harness spans only) the
    innermost split is the breakdown's idle split."""
    with open(os.path.join(FIXTURES, name)) as f:
        fx = json.load(f)
    devices = {int(k): {line: tr.Intervals(*iv) for line, iv in v.items()}
               for k, v in fx["devices"].items()}
    view = tr.TraceView([], devices, [tuple(h) for h in fx["host"]],
                        fx["cycles"], 0.0, len(devices))
    ops = devices[min(devices)]["ops"]
    split = hc.idle_split(tr.idle_gaps(ops.starts, ops.ends, view.lo,
                                       view.hi), view.host)
    assert split == pytest.approx(dict(view.breakdown()["idle_gaps"]),
                                  rel=1e-9)


def test_service_spans_recorded_on_the_chip():
    """Two calls of a colocation run on the chip with the service's
    spans: their mirrored copies on the profiler's host plane, the
    ring's records of the same calls, the chip's events. The ring lands
    on its mirrored copies; once `device_offset` corrects the chip's
    clock, each `amp_check` program runs inside its span; the readers
    give the numbers recorded with the fixture."""
    with open(os.path.join(FIXTURES, SPANS_FIXTURE)) as f:
        fx = json.load(f)
    devices = {int(k): {line: tr.Intervals(*iv) for line, iv in v.items()}
               for k, v in fx["devices"].items()}
    ring = [SimpleNamespace(name=n, t_start_ns=s, t_end_ns=e, cycle=c)
            for n, s, e, c in fx["ring"]]
    view = tr.TraceView(ring, devices, [tuple(h) for h in fx["host"]],
                        fx["cycles"], 0.0, len(devices))
    mirrored = [tuple(x) for x in fx["service"]]
    _, slack = hc.clock_offset(view)
    placed = hc.placed_spans(view, hc.SERVICE_SPANS)
    for name in {n for n, _, _ in mirrored}:
        a = sorted(s for n, s, _ in mirrored if n == name)
        b = sorted(s for n, s, _, _ in placed if n == name)
        assert len(a) == len(b) == fx["cycles"], name
        assert max(abs(x - y) for x, y in zip(a, b)) <= slack + 20 * US
    offset = hc.device_offset(view)
    starts, ends = hc.program_runs(view, served=False)
    for a0, a1 in [(s, e) for n, s, e in mirrored if n == "amp_check"]:
        assert any(a0 - slack <= s + offset and e + offset <= a1 + slack
                   for s, e in zip(starts, ends))
    for key, want in fx["expect_spans"].items():
        assert metric(key, view) == pytest.approx(want, rel=1e-9), key
    split = hc.idle_split(hc.idle_intervals(view, offset),
                          list(view.host) + mirrored)
    assert split.get("bench/schedule", 0.0) <= 0.05 * sum(split.values())


def test_slowest_call_from_the_ring():
    spans = [rec("cycle", 0, 10, 0), rec("finalize", 10, 12, 0),
             rec("cycle", 20, 900 * MS, 1), rec("dispatch", 30, 800 * MS, 1),
             rec("finalize", 900 * MS, 901 * MS, 1),
             rec("cycle", -50, -10, 7)]  # before the window
    s = hc.slowest(spans, t0_ns=OFFSET)
    assert s["cycle"] == 1 and s["calls"] == 2 and s["over_500ms"] == 1
    assert s["ring_ms"] == pytest.approx(901 - 20e-6)
    assert s["spans_ms"]["finalize"] == pytest.approx(1.0)
    assert hc.slowest([]) == {}


def test_the_benchmark_names_every_span_the_program_opens():
    from koordinator_tpu.obs import phases

    recovery = {phases.SPAN_RECOVER, phases.SPAN_RECOVER_REPLAY,
                phases.SPAN_RECOVER_COMPILE}
    events = {phases.EVENT_QUARANTINE, phases.EVENT_LADDER_TRANSITION,
              phases.EVENT_RETRY}
    assert set(hc.SERVICE_SPANS) == phases.HOST_SPANS - recovery - events


def test_a_traced_tiny_run_puts_the_spans_on_the_host_plane(checkout):
    """The script on the CPU: every cycle's spans are on the profiler's
    host plane inside its `bench/schedule`, the ring placed by the
    clock join lands where the mirrored copies are, and the span
    readers report (the device ones find no TPU plane)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run(
        [sys.executable, "benchmark/hostclock.py", "--workload",
         "tiny-colocation.lsbe", "--seed", "3000000023", "--seconds", "1"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    by = {x.get("bench"): x for x in lines}
    plane, result = by["plane"], lines[-1]
    calls = plane["calls"]
    assert calls > 0 and plane["outside_schedule"] == 0
    for name in ("cycle", "admit", "amp_check", "prepare_batch", "dispatch",
                 "device_wait", "guard_scan", "publish", "finalize",
                 "checkpoint"):
        assert plane["spans"][name] == calls, name
    # an annotation opens a microsecond or so after its ring record; a
    # busy host can stretch that now and then, hence the median
    assert plane["placement_error_us"]["median"] \
        <= abs(plane["offset_slack_us"]) + 20
    assert by["slowest"]["calls"] >= calls and by["slowest"]["cycle"] >= 0
    assert result["correct"] is True
    assert {"amp_check_ms", "finalize_ms"} <= set(result["metrics"])
    assert not {"launch_ms", "readback_ms"} & set(result["metrics"])
