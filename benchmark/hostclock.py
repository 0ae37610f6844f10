"""The service's koordtrace spans on the profiler's clock, and the
device's idle time put down to the innermost span the host was in.

The program mirrors every open koordtrace span as a
`jax.profiler.TraceAnnotation` of the same name on the profiler's host
plane, beside the device ops. `tracereduce.read_xspace` keeps only the
harness spans of that plane, so the per-layer readers get the service's
spans from the ring (`TraceView.spans`, on `time.monotonic_ns`).
`clock_offset` places the ring on the profiler's clock: each
`bench/schedule` span opens before a schedule() call's first span and
closes after its last, which bounds the offset between the two clocks
from both sides, cycle by cycle.

The profiler's own placement of the chip's events against the host's
was off by 0.1 to 1.1 ms from run to run on a v5e host: the program
that an `amp_check` span dispatches and waits for showed on the chip
up to 1.1 ms before the span opened. `device_offset` measures that
error from the `amp_check` spans, and the readers that join host spans
to device events (`launch_ms`, `readback_ms`) correct for it, so they
read nothing where the program opens no `amp_check` span.

Run as a script, it makes one traced run of a cell through `run.py`
and prints three lines more before the result line:

    python benchmark/hostclock.py --workload <cell> --seed <n> \\
        --seconds <s>

- `plane`: the mirrored spans found on the profiler's host plane, how
  many lie outside every `bench/schedule`, and how far the ring's
  spans placed by `clock_offset` fall from their mirrored copies;
- `idle_split`: the first chip's idle time in the traced window by the
  innermost span open over it, from the mirrored copies (`plane`) and
  from the placed ring (`placed`), the chip's events corrected by
  `device_offset` (given in the line; none where it finds none);
- `slowest`: the slowest schedule() call of the whole window, from the
  ring: its cycle id, its extent and each of its spans.
"""

import glob
import os
import sys

import numpy as np

import tracereduce

SCHEDULE = "bench/schedule"
WINDOW = "bench/window"
OUTSIDE = "outside bench spans"
# the served program's runs on the "XLA Modules" line (kernel_ms's)
SERVED = "schedule_batch"
# a span around one round trip to the chip: the host dispatches one
# small program and waits for its answer, so the program runs inside it
SYNC_SPAN = "amp_check"
# how far from a sync span its program's run is looked for
SYNC_REACH_NS = 10e6
# koordtrace's host span names (koordinator_tpu/obs/phases.py); the
# benchmark keeps its own list, as it imports nothing of the program
SERVICE_SPANS = ("cycle", "admit", "amp_check", "prepare_batch",
                 "ensure_cached", "dispatch", "unpack", "device_wait",
                 "guard_scan", "journal_append", "publish", "finalize",
                 "checkpoint", "backoff")


def clock_offset(view):
    """(offset, slack) in ns such that profiler time = ring time -
    offset, good to +- slack; None where the window's cycles in the
    ring and its `bench/schedule` spans do not pair one to one. A
    negative slack says the bounds cross: the two clocks drifted apart
    by twice as much over the window."""
    calls = sorted((s, e) for n, s, e in view.host if n == SCHEDULE)
    extent = {}
    for r in view.spans:
        if r.cycle < 0:
            continue
        lo, hi = extent.get(r.cycle, (r.t_start_ns, r.t_end_ns))
        extent[r.cycle] = (min(lo, r.t_start_ns), max(hi, r.t_end_ns))
    ring = sorted(extent.values())
    if not calls or len(calls) != len(ring):
        return None
    a, b = np.asarray(calls, np.float64).T
    c, d = np.asarray(ring, np.float64).T
    # a call opens before its first span (c - offset >= a) and closes
    # after its last (d - offset <= b)
    hi, lo = float(np.min(c - a)), float(np.max(d - b))
    return (lo + hi) / 2, (hi - lo) / 2


def placed_spans(view, names):
    """[(name, start, end, cycle)] in ns on the profiler's clock, in
    start order, of the ring's spans named in `names`; None where
    `clock_offset` cannot pair the cycles."""
    off = clock_offset(view)
    if off is None:
        return None
    d = off[0]
    return sorted(((r.name, r.t_start_ns - d, r.t_end_ns - d, r.cycle)
                   for r in view.spans if r.name in names),
                  key=lambda x: x[1])


def program_runs(view, served=True):
    """(starts, ends) in ns, on the chip's clock, of the served
    program's runs on every chip, or with `served` False of every other
    program's, in start order."""
    starts, ends = [], []
    for dev in view.devices.values():
        iv = dev.get("programs")
        if iv is None or not iv.ids.size:
            continue
        keep = iv.where(lambda name: (SERVED in name) == served)
        starts.append(iv.starts[keep])
        ends.append(iv.ends[keep])
    if not starts:
        return np.zeros(0), np.zeros(0)
    s, e = np.concatenate(starts), np.concatenate(ends)
    order = np.argsort(s, kind="stable")
    return s[order], e[order]


def device_offset(view):
    """ns to add to the chip's event times to put them on the host's
    clock, or None. Each `amp_check` span holds the run of one other
    program: the offsets that put a given run inside a given span form
    an interval, and the offset taken is the least that puts a run
    inside as many spans as any offset does (the chip starts that
    program a few microseconds after the span opens). None without
    such spans or runs, or where fewer than nine spans in ten agree."""
    spans = placed_spans(view, (SYNC_SPAN,))
    starts, ends = program_runs(view, served=False)
    if not spans or not starts.size:
        return None
    bounds = []
    for k, (_, a0, a1, _) in enumerate(spans):
        for i in range(np.searchsorted(starts, a0 - SYNC_REACH_NS),
                       np.searchsorted(starts, a1 + SYNC_REACH_NS)):
            lo, hi = a0 - starts[i], a1 - ends[i]
            if lo <= hi:
                bounds += [(lo, 0, k), (hi, 1, k)]
    bounds.sort()
    inside, covered, best, at = {}, 0, 0, None
    for x, closing, k in bounds:
        inside[k] = inside.get(k, 0) + (-1 if closing else 1)
        if closing and inside[k] == 0:
            covered -= 1
        elif not closing and inside[k] == 1:
            covered += 1
            if covered > best:
                best, at = covered, float(x)
    return at if best >= 0.9 * len(spans) else None


def cycle_windows(view):
    """{cycle: (dispatch start, device_wait start, device_wait end)} on
    the profiler's clock, of the last attempt of each cycle that has
    both spans; None where the spans cannot be placed."""
    spans = placed_spans(view, ("dispatch", "device_wait"))
    if spans is None:
        return None
    opened, out = {}, {}
    for name, s, e, cycle in spans:
        if name == "dispatch":
            opened[cycle] = s
        elif cycle in opened:
            out[cycle] = (opened[cycle], s, e)
    return out


def idle_intervals(view, offset=0.0):
    """[(start, end)] in ns on the host's clock of the first chip's idle
    intervals in the traced window, its events moved by `offset`."""
    ops = view.devices[min(view.devices)]["ops"]
    lo = view.lo if np.isfinite(view.lo) else float(ops.starts.min())
    hi = view.hi if np.isfinite(view.hi) else float(ops.ends.max())
    return [(a + offset, b + offset) for a, b in tracereduce.idle_gaps(
        ops.starts, ops.ends, lo - offset, hi - offset)]


def innermost_segments(spans):
    """[(start, end, name)]: the time some of `spans` [(name, start,
    end)] cover, cut where the innermost open span changes. A
    service span is inner to a harness span (`bench/...`); among spans
    of one kind, the later-opened is inner, and of two opened at once
    the one that closes first."""
    events = []
    for i, (_, s, e) in enumerate(spans):
        if e > s:
            events.append((s, 1, i))
            events.append((e, 0, i))
    events.sort()
    open_, segs, prev = set(), [], None
    for t, opening, i in events:
        if open_ and t > prev:
            top = max(open_, key=lambda j: (
                not spans[j][0].startswith("bench/"), spans[j][1],
                -spans[j][2]))
            segs.append((prev, t, spans[top][0]))
        if opening:
            open_.add(i)
        else:
            open_.discard(i)
        prev = t
    return segs


def idle_split(gaps, spans) -> dict:
    """{name: seconds} of the idle intervals `gaps` [(start, end)] in
    ns, each part put down to the innermost of `spans` [(name, start,
    end)] open over it (`bench/window` aside), and to `outside bench
    spans` where none is open."""
    segs = innermost_segments([x for x in spans if x[0] != WINDOW])
    out, j = {}, 0
    for a, b in sorted(gaps):
        left = b - a
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            part = min(b, segs[k][1]) - max(a, segs[k][0])
            if part > 0:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + part / 1e9
                left -= part
            k += 1
        if left > 0:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + left / 1e9
    return out


def slowest(records, t0_ns=None) -> dict:
    """The schedule() call whose spans in the ring (those opened at or
    after `t0_ns`) reach furthest from first open to last close: its
    cycle id, that extent and the time in each of its span names, with
    the median extent and the calls over half a second beside it."""
    by_cycle = {}
    for r in records:
        if r.cycle < 0 or (t0_ns is not None and r.t_start_ns < t0_ns):
            continue
        by_cycle.setdefault(r.cycle, []).append(r)
    if not by_cycle:
        return {}
    extent = {c: (max(r.t_end_ns for r in rs)
                  - min(r.t_start_ns for r in rs)) / 1e6
              for c, rs in by_cycle.items()}
    worst = max(extent, key=extent.get)
    spans = {}
    for r in by_cycle[worst]:
        spans[r.name] = spans.get(r.name, 0.0) \
            + (r.t_end_ns - r.t_start_ns) / 1e6
    ext = np.asarray(list(extent.values()))
    return {"cycle": int(worst), "ring_ms": extent[worst],
            "spans_ms": spans, "calls": len(extent),
            "median_ms": float(np.median(ext)),
            "over_500ms": int((ext > 500.0).sum())}


def read_plane(trace_dir, names):
    """[(name, start, end)] of the host-plane events named in `names`,
    from every .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData

    out = []
    for path in sorted(glob.glob(os.path.join(trace_dir, "**",
                                              "*.xplane.pb"),
                                 recursive=True)):
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        out.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
    return out


def plane_report(view, mirrored) -> dict:
    """The mirrored spans `mirrored` [(name, start, end)] against the
    harness's calls and against the ring placed by `clock_offset`."""
    calls = [(s, e) for n, s, e in view.host if n == SCHEDULE]
    counts, outside = {}, 0
    for name, s, e in mirrored:
        counts[name] = counts.get(name, 0) + 1
        outside += not any(a <= s and e <= b for a, b in calls)
    off = clock_offset(view)
    out = {"calls": len(calls), "spans": counts,
           "outside_schedule": outside,
           "offset_slack_us": off[1] / 1e3 if off else None}
    placed = placed_spans(view, SERVICE_SPANS) or []
    err = []
    for name in counts:
        a = sorted(s for n, s, _ in mirrored if n == name)
        b = sorted(s for n, s, _, _ in placed if n == name)
        if len(a) == len(b):
            err.extend(abs(x - y) for x, y in zip(a, b))
    out["placement_error_us"] = {
        "median": float(np.median(err)) / 1e3,
        "max": max(err) / 1e3} if err else None
    return out


def report(view, trace_dir, records, mono0, out) -> None:
    """Print the `plane`, `idle_split` and `slowest` lines of a traced
    run through `out` (run.Out)."""
    mirrored = read_plane(trace_dir, SERVICE_SPANS)
    out.emit(bench="plane", **plane_report(view, mirrored))
    split = {}
    if view.devices:
        offset = device_offset(view)
        gaps = idle_intervals(view, offset or 0.0)
        placed = [x[:3] for x in placed_spans(view, SERVICE_SPANS) or []]
        for key, spans in (("plane", mirrored), ("placed", placed)):
            by = idle_split(gaps, list(view.host) + spans)
            split[key] = sorted(([k, v] for k, v in by.items()),
                                key=lambda kv: -kv[1])
        split["idle_s"] = sum((b - a) for a, b in gaps) / 1e9
        split["device_offset_us"] = None if offset is None else offset / 1e3
    out.emit(bench="idle_split", **split)
    out.emit(bench="slowest", **slowest(records, mono0))


def main(argv=None) -> int:
    """run.py's main with the trace on, `report` called on the traced
    window before the per-layer readers."""
    import jax

    import run

    from_run = tracereduce.TraceView.from_run

    def from_run_and_report(cls, trace_dir, spans, mono0, mono1, cycles,
                            window_s, chips):
        view = from_run.__func__(cls, trace_dir, spans, mono0, mono1,
                                 cycles, window_s, chips)
        report(view, trace_dir, spans, mono0,
               run.Out(jax.devices()[:chips]))
        return view

    tracereduce.TraceView.from_run = classmethod(from_run_and_report)
    return run.main(list(sys.argv[1:] if argv is None else argv)
                    + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
