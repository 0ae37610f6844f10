"""Guards + program dispatch, host to chip: mean over cycles of the
time from a `dispatch` span's opening to the start of the first served
program run (`*schedule_batch` on "XLA Modules", on any chip) that
follows it before the cycle's `device_wait` closes: what it costs the
host to get the program onto the chip. Host spans and chip runs are
put on one clock by `hostclock` (the ring placed by the harness's
spans, the chip's runs corrected by `device_offset`)."""

import numpy as np

import hostclock


def read(view):
    windows = hostclock.cycle_windows(view)
    offset = hostclock.device_offset(view)
    starts, _ = hostclock.program_runs(view)
    if not windows or offset is None or not starts.size:
        return None
    starts = starts + offset
    gaps = []
    for opened, _, closed in windows.values():
        i = np.searchsorted(starts, opened)
        if i < starts.size and starts[i] < closed:
            gaps.append(starts[i] - opened)
    return float(np.mean(gaps)) / 1e6 if gaps else None
