"""Kernels: device time per cycle of the served scheduling program
itself (`core.schedule_batch`, guarded or not): the union of its runs
on the "XLA Modules" line in the traced window over the cycles in it,
averaged over the cell's chips. Other programs the cycle dispatches
(the `admit` amplification check, auto-pack's inverse gather) are not
kernels of the scheduler and stay out; `device_idle_pct` counts them."""

SERVED = "schedule_batch"


def read(view):
    t = view.device_seconds(lambda name: SERVED in name, lines=("programs",))
    if t <= 0 or not view.cycles:
        return None
    return t / view.cycles * 1e3
