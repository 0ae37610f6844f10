"""Service cycle, host: mean of the `guard_scan`, `journal_append` and
`publish` koordtrace spans per cycle (the commit after the program)."""


def read(view):
    n = view.span_count("admit")
    if not n:
        return None
    return view.span_seconds("guard_scan", "journal_append",
                             "publish") / n * 1e3
