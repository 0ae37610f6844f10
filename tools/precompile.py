"""CI/deploy cache warmer: enumerate the configured working set and
AOT-compile every program into the persistent compile cache
(JAX_COMPILATION_CACHE_DIR when set, else <repo>/.jax_cache), so the
first REAL scheduling cycle of the next process over that dir (same
host — see tests/conftest.py on artifact portability) traces but never
compiles.

The enumeration is the koordshape-registry walk in
koordinator_tpu/compilecache/precompile.py: the flagship cycle per
cascade form, every shrunk-mesh rung (devices, devices-1, ..., 1)
padded exactly as the service's mesh-shrink failover pads it, and the
canonical donated tail-compaction form.

Usage:
  python tools/precompile.py [--devices N] [--size P=256 --size N=128 ...] \\
      [--guards] [--no-tail] [--cascade on|off|both] [--json]

Exit code 0 on success; the report (per-program hit/warm/miss lines +
totals) goes to stdout.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax

if os.environ.get("JAX_PLATFORMS"):
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])


def parse_sizes(pairs):
    sizes = {}
    for pair in pairs or ():
        key, _, val = pair.partition("=")
        if not val or not val.lstrip("-").isdigit():
            raise SystemExit(f"--size wants KEY=INT, got {pair!r}")
        sizes[key] = int(val)
    return sizes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=None,
                    help="top of the shrunk-mesh ladder "
                         "(default: all visible devices)")
    ap.add_argument("--size", action="append", metavar="KEY=INT",
                    help="working-set dim override (P, N, I, Z, G, ...); "
                         "repeatable")
    ap.add_argument("--guards", action="store_true",
                    help="warm the guarded fusion instead of the bare "
                         "kernel")
    ap.add_argument("--no-tail", action="store_true",
                    help="skip the canonical tail-compaction form")
    ap.add_argument("--cascade", choices=("on", "off", "both"),
                    default="both", help="cascade forms to warm")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as one JSON line")
    args = ap.parse_args(argv)

    from koordinator_tpu.compilecache import precompile
    from koordinator_tpu.compilecache.cache import CompileCache

    cascade_forms = {"on": (True,), "off": (False,),
                     "both": (False, True)}[args.cascade]
    ws = precompile.WorkSet(
        sizes=parse_sizes(args.size),
        devices=(args.devices if args.devices is not None
                 else len(jax.devices())),
        cascade_forms=cascade_forms,
        tail=None if args.no_tail else dict(precompile.DEFAULT_TAIL),
        guards=args.guards)
    cache = CompileCache()
    report = precompile.warm(
        cache, ws,
        log_fn=None if args.json else lambda s: print(s, flush=True))
    report["cache_dir"] = cache.path
    report["fingerprint"] = cache.fingerprint[:16]
    if args.json:
        print(json.dumps(report))
    else:
        print(f"precompile: {report['programs']} program(s) "
              f"({report['hit']} hit / {report['warm']} warm / "
              f"{report['miss']} miss) in {report['seconds']}s "
              f"-> {cache.path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
