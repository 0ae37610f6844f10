"""koordtrace export surface: render a span buffer plus the metrics
registry into one observability dump.

Three formats, one call:
  * chrome — Chrome trace-event JSON (load the file in Perfetto /
    chrome://tracing),
  * jsonl — one span record per line (the format trace_fullgate's
    per-phase table also emits, so both join on the phase names in
    obs/phases.py),
  * prom — the metrics `Registry.expose()` text payload.

`dump(...)` writes the chosen formats side by side into a directory;
the CLI converts a saved JSONL dump to Chrome JSON after the fact
(`python -m koordinator_tpu.obs.export --in trace.jsonl --format
chrome`), so a service dump taken in one process can be inspected in
Perfetto from another.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Iterable, List, Optional, Sequence

from koordinator_tpu.obs.trace import Tracer


def render_chrome(tracer: Tracer) -> str:
    return json.dumps(tracer.to_chrome(), sort_keys=True)


def render_jsonl(tracer: Tracer) -> str:
    return tracer.to_jsonl()


def render_prom(registry) -> str:
    return registry.expose()


def jsonl_to_chrome(lines: Iterable[str], pid: int = 0) -> dict:
    """Rebuild a Chrome trace-event object from koordtrace JSONL lines
    (the inverse of `Tracer.to_jsonl`, minus the wall-clock anchor)."""
    events = []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        r = json.loads(line)
        ev = {
            "name": r["span"],
            "cat": "koordtrace",
            "ph": "X",
            "ts": r["t_start_ns"] / 1e3,
            "dur": (r["t_end_ns"] - r["t_start_ns"]) / 1e3,
            "pid": pid,
            "tid": r.get("thread", 0),
            "args": {"cycle": r.get("cycle", -1),
                     "parent": r.get("parent"),
                     **r.get("attrs", {})},
        }
        if r["t_end_ns"] == r["t_start_ns"]:
            ev["ph"] = "i"
            ev["s"] = "t"
            del ev["dur"]
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


FORMATS = ("chrome", "jsonl", "prom")


def dump(tracer: Optional[Tracer], registry=None, out_dir: str = ".",
         prefix: str = "koordtrace",
         formats: Sequence[str] = ("chrome", "jsonl")) -> List[str]:
    """Write the requested formats into `out_dir`; returns the written
    paths. `prom` requires `registry`; chrome/jsonl require `tracer`
    (each silently skipped when its source is absent, so one call
    serves every knob combination)."""
    os.makedirs(out_dir, exist_ok=True)
    paths: List[str] = []
    for fmt in formats:
        if fmt not in FORMATS:
            raise ValueError(f"unknown format {fmt!r}; want one of {FORMATS}")
        if fmt == "prom":
            if registry is None:
                continue
            path = os.path.join(out_dir, f"{prefix}.prom")
            payload = render_prom(registry)
        elif tracer is None:
            continue
        elif fmt == "chrome":
            path = os.path.join(out_dir, f"{prefix}.trace.json")
            payload = render_chrome(tracer)
        else:
            path = os.path.join(out_dir, f"{prefix}.jsonl")
            payload = render_jsonl(tracer)
        with open(path, "w") as f:
            f.write(payload)
        paths.append(path)
    return paths


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="convert a koordtrace JSONL dump to Chrome trace JSON")
    ap.add_argument("--in", dest="inp", required=True,
                    help="koordtrace JSONL file")
    ap.add_argument("--format", choices=("chrome", "jsonl"),
                    default="chrome")
    ap.add_argument("--out", default="-",
                    help="output path ('-' = stdout)")
    args = ap.parse_args(argv)
    with open(args.inp) as f:
        lines = f.readlines()
    if args.format == "chrome":
        payload = json.dumps(jsonl_to_chrome(lines), sort_keys=True)
    else:
        payload = "".join(lines)
    if args.out == "-":
        sys.stdout.write(payload + "\n")
    else:
        with open(args.out, "w") as f:
            f.write(payload)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
