"""From a profiler trace of the window and the service's koordtrace
spans to the numbers the per-layer readers take.

The trace is JAX's `.xplane.pb`, read with `jax.profiler.ProfileData`.
On a TPU each `/device:TPU:<n>` plane has an "XLA Ops" line (one event
per HLO instruction run, named by the instruction's HLO text) and an
"XLA Modules" line (one event per program run); asynchronous ops
(copies, collectives started and awaited) are on "Async XLA Ops". Busy
time is the union of the "XLA Ops" events. The harness's own spans
(`bench/window`, `bench/schedule`, `bench/count_carry`,
`bench/republish`) are events on the host plane, on the same clock.
The ops carry no `op_name` there, so no `koord/...` phase can be read
from the trace alone.
"""

import glob
import os
import re
import shutil

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|all-to-all|"
                        r"collective-permute|reduce-scatter)")
BENCH_SPANS = ("bench/window", "bench/schedule", "bench/count_carry",
               "bench/republish")


def union_length(starts, ends, lo=-np.inf, hi=np.inf) -> float:
    """Length of the union of [start, end) intervals clipped to
    [lo, hi), in the intervals' unit."""
    s = np.clip(np.asarray(starts, np.float64), lo, hi)
    e = np.clip(np.asarray(ends, np.float64), lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return 0.0
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    # a new run starts where an interval begins after all before it end
    new = np.concatenate([[True], s[1:] > e[:-1]])
    run_start = s[new]
    run_end = np.concatenate([e[:-1][new[1:]], [e[-1]]])
    return float((run_end - run_start).sum())


def idle_gaps(starts, ends, lo, hi):
    """The [gap start, gap end) intervals in [lo, hi) where no interval
    runs."""
    s = np.clip(np.asarray(starts, np.float64), lo, hi)
    e = np.clip(np.asarray(ends, np.float64), lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return [(lo, hi)] if hi > lo else []
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    gaps = []
    if s[0] > lo:
        gaps.append((lo, s[0]))
    for a, b in zip(e[:-1], s[1:]):
        if b > a:
            gaps.append((a, b))
    if hi > e[-1]:
        gaps.append((e[-1], hi))
    return gaps


def short_name(hlo_text: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


class Intervals:
    """Events of one kind on one device: start/end in ns and a name id
    per event into `names`."""

    def __init__(self, starts, ends, ids, names):
        self.starts = np.asarray(starts, np.float64)
        self.ends = np.asarray(ends, np.float64)
        self.ids = np.asarray(ids, np.int64)
        self.names = list(names)

    def where(self, pred) -> np.ndarray:
        """bool per event: pred(name) of its name."""
        hit = np.array([bool(pred(n)) for n in self.names], bool)
        return hit[self.ids] if self.ids.size else np.zeros(0, bool)


def _line(events, name_of):
    starts, ends, ids, index = [], [], [], {}
    for ev in events:
        key = name_of(ev.name)
        ids.append(index.setdefault(key, len(index)))
        starts.append(ev.start_ns)
        ends.append(ev.start_ns + ev.duration_ns)
    return Intervals(starts, ends, ids, list(index))


def read_xspace(path: str):
    """({device id: {"ops", "async", "programs": Intervals}}, bench host
    spans [(name, start, end)]) from one .xplane.pb."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            empty = Intervals([], [], [], [])
            dev = {"ops": empty, "async": empty, "programs": empty}
            if OPS_LINE in lines:
                dev["ops"] = _line(lines[OPS_LINE].events, short_name)
            if ASYNC_LINE in lines:
                dev["async"] = _line(lines[ASYNC_LINE].events, short_name)
            if MODULES_LINE in lines:
                dev["programs"] = _line(lines[MODULES_LINE].events,
                                        lambda n: n.split("(", 1)[0])
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in BENCH_SPANS:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return devices, host


class TraceView:
    """What the per-layer readers read: the koordtrace spans of the
    traced window, each device's ops and programs, the harness spans,
    and the cycles the traced window holds."""

    def __init__(self, spans, devices, host, cycles, window_s, chips):
        self.spans = spans
        self.devices = devices
        self.host = host
        self.cycles = cycles
        self.chips = chips
        win = [(s, e) for n, s, e in host if n == "bench/window"]
        if win:
            self.lo, self.hi = win[0]
            self.window_s = (self.hi - self.lo) / 1e9
        else:
            self.lo, self.hi = -np.inf, np.inf
            self.window_s = window_s

    @classmethod
    def from_run(cls, trace_dir, spans, mono0, mono1, cycles, window_s,
                 chips):
        files = sorted(glob.glob(os.path.join(trace_dir, "**",
                                              "*.xplane.pb"),
                                 recursive=True))
        devices, host = {}, []
        for f in files:
            d, h = read_xspace(f)
            devices.update(d)
            host.extend(h)
        inside = [r for r in spans if mono0 <= r.t_start_ns <= mono1]
        return cls(inside, devices, host, cycles, window_s, chips)

    # --- host spans ---
    def span_seconds(self, *names) -> float:
        return sum((r.t_end_ns - r.t_start_ns) / 1e9 for r in self.spans
                   if r.name in names)

    def span_count(self, name) -> int:
        return sum(1 for r in self.spans if r.name == name)

    # --- device time, averaged over the cell's chips ---
    def device_seconds(self, pred=None, lines=("ops",)) -> float:
        """Mean over devices of the union of the (matching) events'
        intervals on the given lines in the window, in seconds; 0
        without device events."""
        if not self.devices:
            return 0.0
        total = 0.0
        for dev in self.devices.values():
            starts, ends = [], []
            for line in lines:
                iv = dev.get(line)
                if iv is None:
                    continue
                keep = iv.where(pred) if pred is not None else slice(None)
                starts.append(iv.starts[keep])
                ends.append(iv.ends[keep])
            if starts:
                total += union_length(np.concatenate(starts),
                                      np.concatenate(ends), self.lo, self.hi)
        return total / len(self.devices) / 1e9

    def busy_s(self) -> float:
        return self.device_seconds()

    def breakdown(self, top: int = 10) -> dict:
        """On the first device: the programs and then the ops that took
        most time in the window, and the device's idle time split by
        the harness span the host was in."""
        if not self.devices:
            return {"device_ops": [], "idle_gaps": []}
        dev = self.devices[min(self.devices)]
        ops, modules = dev["ops"], dev["programs"]
        lo = self.lo if np.isfinite(self.lo) else float(ops.starts.min())
        hi = self.hi if np.isfinite(self.hi) else float(ops.ends.max())

        def totals(iv, label):
            dur = np.clip(iv.ends, lo, hi) - np.clip(iv.starts, lo, hi)
            sums = np.bincount(iv.ids, weights=np.maximum(dur, 0),
                               minlength=len(iv.names))
            return [[f"{label} {iv.names[i]}", float(sums[i]) / 1e9]
                    for i in np.argsort(-sums) if sums[i] > 0]

        device_ops = totals(modules, "program")[:3]
        device_ops += totals(ops, "op")[:top - len(device_ops)]
        by_gap = {}
        spans = [(n, s, e) for n, s, e in self.host if n != "bench/window"]
        for a, b in idle_gaps(ops.starts, ops.ends, lo, hi):
            left = b - a
            for n, s, e in spans:
                part = min(b, e) - max(a, s)
                if part > 0:
                    by_gap[n] = by_gap.get(n, 0.0) + part / 1e9
                    left -= part
            if left > 0:
                by_gap["outside bench spans"] = \
                    by_gap.get("outside bench spans", 0.0) + left / 1e9
        gaps = sorted(by_gap.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": device_ops,
                "idle_gaps": [[k, v] for k, v in gaps]}


def remove(trace_dir) -> None:
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
