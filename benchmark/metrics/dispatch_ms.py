"""Guards + program dispatch: mean koordtrace `dispatch` span per cycle
(`_device_cycle` through `guards.guarded_schedule_batch`)."""


def read(view):
    n = view.span_count("dispatch")
    return view.span_seconds("dispatch") / n * 1e3 if n else None
