"""Service cycle, host: mean koordtrace `admit` span per cycle (snapshot
read, amplification check, `_prepare_batch` auto-pack)."""


def read(view):
    n = view.span_count("admit")
    return view.span_seconds("admit") / n * 1e3 if n else None
