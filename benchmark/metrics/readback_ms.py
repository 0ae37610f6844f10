"""Guards + program dispatch, chip to host: mean over cycles of the
time from the later of a `device_wait` span's opening and the end of
the served program run it waits on (the last `*schedule_batch` run on
"XLA Modules", on any chip, to end inside the cycle's dispatch-to-wait
interval) to the span's close: how long the assignment takes to reach
the host once the chip is done. One clock as in `launch_ms`."""

import numpy as np

import hostclock


def read(view):
    windows = hostclock.cycle_windows(view)
    offset = hostclock.device_offset(view)
    starts, ends = hostclock.program_runs(view)
    if not windows or offset is None or not starts.size:
        return None
    starts, ends = starts + offset, ends + offset
    gaps = []
    for opened, wait_start, closed in windows.values():
        mine = (starts >= opened) & (starts < closed)
        if mine.any():
            gaps.append(closed - max(wait_start, ends[mine].max()))
    return float(np.mean(gaps)) / 1e6 if gaps else None
