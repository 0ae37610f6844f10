"""koordcost bench-trajectory comparator: noise-aware improve /
regress / neutral verdicts between two bench streams.

The bench emits self-describing JSON lines (bench.py) and the round
driver wraps them in BENCH_*.json artifacts; until now the trajectory
had no reader — a slower flagship only surfaced if a human diffed the
numbers. This tool joins two streams on the protocol identity

    (metric, devices, platform, cascade, tail_mode, cache)

so a cascade-off or host-tail or cold-cache line can never be compared
against its other-protocol sibling, takes the MEDIAN per joined key
(several lines per key = several runs; the median absorbs one bad
sample), and applies per-field tolerances with a direction each:

  * wall-clock fields (`value`, `compile_s`, `warm_start_s`) carry a
    LOOSE tolerance — these CI hosts live-migrate and resize
    mid-session (observed nproc 8 -> 1), so only order-of-magnitude
    movement is signal;
  * deterministic fields (`placed`, stragglers, `tail_passes`, and the
    BENCH_COST stamps `flops`/`bytes_accessed`/`hbm_peak_bytes`) are
    EXACT or near-exact — the program is deterministic per platform,
    so any movement is a real change, however cheap the host.

Degraded / recovered / stamped-capture lines are excluded: they are
evidence, not protocol.

Regressions carry the ``BENCH REGRESSION`` marker and fail the run.

Usage:
  python tools/benchdiff.py BASELINE CANDIDATE [--tol field=rel ...]
  python tools/benchdiff.py --self-test          # seeded noise vs regression
  JAX_PLATFORMS=cpu python tools/benchdiff.py --proxy-run OUT.jsonl
  JAX_PLATFORMS=cpu python tools/benchdiff.py --stamp-proxy
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from dataclasses import dataclass
from statistics import median
from typing import Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

MARKER = "BENCH REGRESSION"
BASELINE_PATH = os.path.join("perf", "BENCH_BASELINE.json")

KEY_FIELDS = ("metric", "devices", "platform", "cascade", "tail_mode",
              "cache")

# the CI proxy shape: small enough to compile + run in a CI stage,
# large enough that sweep chunking, the adaptive tail, and the cascade
# all engage. One definition — the stamper and the gate both call it.
PROXY_SHAPE = dict(num_pods=2_000, num_nodes=200, chunk=500,
                   metric="proxy_score_bind_2k_pods_200_nodes")


@dataclass(frozen=True)
class Field:
    """One compared field: which direction is good, and the relative
    tolerance inside which movement is noise."""

    direction: str  # "lower" | "higher"
    tolerance: float


# wall-clock loose, deterministic counts/cost stamps (near-)exact
DEFAULT_FIELDS: Dict[str, Field] = {
    "value": Field("lower", 3.0),
    "compile_s": Field("lower", 3.0),
    "warm_start_s": Field("lower", 3.0),
    "placed": Field("higher", 0.0),
    "stragglers_after_sweep": Field("lower", 0.0),
    "stragglers_final": Field("lower", 0.0),
    "tail_passes": Field("lower", 0.0),
    "flops": Field("lower", 0.01),
    "bytes_accessed": Field("lower", 0.01),
    "hbm_peak_bytes": Field("lower", 0.01),
}


def parse_stream(path: str) -> List[dict]:
    """Bench lines from either format: a JSONL file (one dict per
    line) or a driver BENCH_*.json artifact (object whose "tail"
    string embeds the emitted lines). Non-protocol lines (degraded,
    recovered, stamped re-emissions, non-dicts) are dropped."""
    with open(path, encoding="utf-8") as f:
        text = f.read()
    lines: List[dict] = []
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
    if isinstance(doc, dict) and "tail" in doc:
        raw = str(doc["tail"]).splitlines()
    elif isinstance(doc, list):
        lines = [l for l in doc if isinstance(l, dict)]
        raw = []
    elif isinstance(doc, dict):
        lines = [doc]
        raw = []
    else:
        raw = text.splitlines()
    for line in raw:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict):
            lines.append(obj)
    return [l for l in lines
            if "metric" in l and "value" in l
            and not l.get("degraded") and not l.get("recovered")
            and not l.get("stamped_capture")]


def join_key(line: dict) -> Tuple:
    return tuple(line.get(k) for k in KEY_FIELDS)


def _group(lines: List[dict]) -> Dict[Tuple, List[dict]]:
    groups: Dict[Tuple, List[dict]] = {}
    for line in lines:
        groups.setdefault(join_key(line), []).append(line)
    return groups


def _median_fields(lines: List[dict], fields: Dict[str, Field]
                   ) -> Dict[str, float]:
    out = {}
    for name in fields:
        vals = [float(l[name]) for l in lines
                if isinstance(l.get(name), (int, float))
                and not isinstance(l.get(name), bool)]
        if vals:
            out[name] = median(vals)
    return out


def diff(baseline: List[dict], candidate: List[dict],
         fields: Optional[Dict[str, Field]] = None) -> List[dict]:
    """Per (key, field) verdicts over every joined protocol identity:
    {key, field, old, new, rel, verdict} with verdict improve /
    regress / neutral, plus one unmatched record per key present on
    only one side (informational, never failing — protocols come and
    go by design)."""
    fields = DEFAULT_FIELDS if fields is None else fields
    old_g, new_g = _group(baseline), _group(candidate)
    verdicts: List[dict] = []
    for key in sorted(set(old_g) | set(new_g), key=repr):
        label = "/".join(f"{k}={v}" for k, v in zip(KEY_FIELDS, key)
                         if v is not None)
        if key not in new_g or key not in old_g:
            verdicts.append({
                "key": label, "field": None, "old": None, "new": None,
                "rel": None,
                "verdict": "baseline-only" if key in old_g
                else "candidate-only"})
            continue
        old_m = _median_fields(old_g[key], fields)
        new_m = _median_fields(new_g[key], fields)
        for name in fields:
            if name not in old_m or name not in new_m:
                continue
            ov, nv = old_m[name], new_m[name]
            rel = (nv - ov) / max(abs(ov), 1e-12)
            spec = fields[name]
            good_delta = -rel if spec.direction == "lower" else rel
            if good_delta < -spec.tolerance:
                verdict = "regress"
            elif good_delta > spec.tolerance:
                verdict = "improve"
            else:
                verdict = "neutral"
            verdicts.append({"key": label, "field": name, "old": ov,
                             "new": nv, "rel": rel, "verdict": verdict})
    return verdicts


def report(verdicts: List[dict]) -> int:
    """Print the verdict table; return 1 iff anything regressed."""
    counts = {"improve": 0, "regress": 0, "neutral": 0}
    for v in verdicts:
        if v["field"] is None:
            print(f"benchdiff: {v['verdict']}: {v['key']}")
            continue
        counts[v["verdict"]] += 1
        if v["verdict"] == "neutral":
            continue
        tag = MARKER if v["verdict"] == "regress" else "improve"
        print(f"{tag}: {v['key']} {v['field']} "
              f"{v['old']:.4g} -> {v['new']:.4g} ({v['rel']:+.1%})")
    print(f"benchdiff: {counts['improve']} improved, "
          f"{counts['regress']} regressed, "
          f"{counts['neutral']} neutral")
    return 1 if counts["regress"] else 0


def _tol_overrides(pairs: List[str]) -> Dict[str, Field]:
    fields = dict(DEFAULT_FIELDS)
    for pair in pairs:
        name, _, tol = pair.partition("=")
        if name not in fields:
            raise SystemExit(f"benchdiff: unknown field {name!r} "
                             f"(known: {', '.join(sorted(fields))})")
        fields[name] = Field(fields[name].direction, float(tol))
    return fields


def proxy_lines() -> List[dict]:
    """Run the CI proxy shape (one slim flagship line, BENCH_COST
    stamps on) and return its emitted line."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["BENCH_COST"] = "1"
    # pin the protocol: ambient BENCH_* knobs would change the join key
    # (cache stamp, cascade, tail mode, ...) or the timed program, and
    # the baseline was stamped with none of them set
    for knob in ("JAX_COMPILATION_CACHE_DIR", "BENCH_CASCADE",
                 "BENCH_TAIL_MODE", "BENCH_DEVICES", "BENCH_MESH_PODS",
                 "BENCH_PACK_SNAPSHOT", "BENCH_APPROX",
                 "BENCH_K", "BENCH_TAIL_K", "BENCH_ROUNDS",
                 "BENCH_TAIL_ROUNDS", "BENCH_TAIL_CHUNK",
                 "BENCH_MAX_TAIL_PASSES"):
        os.environ.pop(knob, None)
    import bench

    # no persistent cache, so the line's `cache` stamp reads "cold" as
    # the baseline's does (jax read the env var at import)
    bench.jax.config.update("jax_compilation_cache_dir", None)
    bench.ensure_platform()
    line = bench.run_northstar(full_gate=False, **PROXY_SHAPE)
    line.pop("arrays", None)
    return [line]


def _strip_host(line: dict) -> dict:
    """Host-fingerprint fields stay out of the checked-in baseline —
    the gate compares medians by field name, and a baseline pinned to
    one CI host's nproc would be misleading provenance."""
    return {k: v for k, v in line.items()
            if k not in ("cores", "host")}


def self_test() -> int:
    """Prove the comparator's discrimination on seeded synthetic
    streams: +-10% run-to-run noise must land neutral at a 30%
    tolerance, a planted 2x slowdown and a planted straggler jump must
    regress, and a planted 2x speedup must improve."""
    import random

    rng = random.Random(20)

    def lines(scale: float, stragglers: int, n: int = 9) -> List[dict]:
        return [{
            "metric": "synthetic_flagship", "devices": 1,
            "platform": "cpu", "cascade": True, "tail_mode": "device",
            "cache": "hit",
            "value": scale * rng.uniform(0.9, 1.1),
            "placed": 2000,
            "stragglers_after_sweep": stragglers,
            "tail_passes": 2,
        } for _ in range(n)]

    fields = _tol_overrides(["value=0.3"])
    base = lines(1.0, 40)

    noisy = diff(base, lines(1.0, 40), fields)
    planted = diff(base, lines(2.0, 40), fields)
    jumped = diff(base, lines(1.0, 55), fields)
    faster = diff(base, lines(0.5, 40), fields)

    def field_verdict(verdicts, name):
        return next(v["verdict"] for v in verdicts
                    if v["field"] == name)

    checks = [
        ("10% noise is neutral", field_verdict(noisy, "value"),
         "neutral"),
        ("2x slowdown regresses", field_verdict(planted, "value"),
         "regress"),
        ("straggler jump regresses",
         field_verdict(jumped, "stragglers_after_sweep"), "regress"),
        ("2x speedup improves", field_verdict(faster, "value"),
         "improve"),
        ("counts stay neutral under noise",
         field_verdict(noisy, "placed"), "neutral"),
    ]
    failed = 0
    for label, got, want in checks:
        ok = got == want
        failed += not ok
        print(f"benchdiff self-test: {label}: {got} "
              f"({'ok' if ok else f'want {want}'})")
    return 1 if failed else 0


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("baseline", nargs="?",
                        help="baseline stream (JSONL or BENCH_*.json)")
    parser.add_argument("candidate", nargs="?",
                        help="candidate stream to compare")
    parser.add_argument("--tol", action="append", default=[],
                        metavar="FIELD=REL",
                        help="override a field's relative tolerance")
    parser.add_argument("--self-test", action="store_true",
                        help="seeded noise-vs-regression discrimination")
    parser.add_argument("--proxy-run", metavar="OUT",
                        help="run the CI proxy shape, write its line "
                             "as JSONL to OUT")
    parser.add_argument("--stamp-proxy", action="store_true",
                        help=f"run the proxy shape and rewrite "
                             f"{BASELINE_PATH}")
    args = parser.parse_args(argv)
    if args.self_test:
        return self_test()
    if args.proxy_run or args.stamp_proxy:
        lines = [_strip_host(l) for l in proxy_lines()]
        out = args.proxy_run if args.proxy_run else \
            os.path.join(REPO_ROOT, BASELINE_PATH)
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w", encoding="utf-8") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
        print(f"benchdiff: wrote {len(lines)} proxy line(s) -> {out}")
        return 0
    if not args.baseline or not args.candidate:
        parser.error("need BASELINE and CANDIDATE (or --self-test / "
                     "--proxy-run / --stamp-proxy)")
    verdicts = diff(parse_stream(args.baseline),
                    parse_stream(args.candidate),
                    _tol_overrides(args.tol))
    return report(verdicts)


if __name__ == "__main__":
    sys.exit(main())
