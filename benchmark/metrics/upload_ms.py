"""Guards + program dispatch, host: mean koordtrace `upload` span per
cycle (inside `dispatch`: the pod batch packed into one buffer and put
on the chip, or put per leaf). None where the program opens no such
span."""


def read(view):
    n = view.span_count("upload")
    return view.span_seconds("upload") / n * 1e3 if n else None
