"""Service cycle, host: mean koordtrace `finalize` span per cycle
(`schedule()` after the commit: the health word, metrics, the
`gang_failed` readback, error dispatch)."""


def read(view):
    n = view.span_count("finalize")
    return view.span_seconds("finalize") / n * 1e3 if n else None
