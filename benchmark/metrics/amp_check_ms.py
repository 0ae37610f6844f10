"""Service cycle, host: mean koordtrace `amp_check` span per cycle (the
`cpu_amplification > 1` device op inside `admit` and its readback)."""


def read(view):
    n = view.span_count("amp_check")
    return view.span_seconds("amp_check") / n * 1e3 if n else None
