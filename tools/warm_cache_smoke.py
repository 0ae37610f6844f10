"""Warm-cache smoke: the cross-process warm-start gate for the compile
cache (ISSUE 17 tentpole).

Three REAL child processes run against ONE cache dir — process
boundaries, not clear_caches(), so the pin covers exactly the restart
path the cache exists for (same host; XLA:CPU artifacts are not
portable across machines, see tests/conftest.py):

  1. COLD    — a journaled flagship cycle over an empty cache dir:
               must record at least one persistent-cache miss (it is
               doing the compiling) and publish its placements;
  2. WARM    — the same cycle, fresh process, same dir: must compile
               ZERO programs (persistent cache_misses == 0 with hits)
               and place every pod bit-identically to the cold run;
  3. RECOVER — a fresh process over the same journal + cache dir runs
               restart recovery: `recover()` must report
               compiled_programs == 0 and replay the cold run's
               placements bit-identically.

Correctness + absence-of-compilation only, never wall-clock.
Usage: JAX_PLATFORMS=cpu python tools/warm_cache_smoke.py
Child mode (internal): ... --child <cold|warm|recover> <workdir> <seed>
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax

if os.environ.get("JAX_PLATFORMS"):
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

import numpy as np

from koordinator_tpu.compilecache import counters
from koordinator_tpu.compilecache.cache import CompileCache
from koordinator_tpu.metrics import Registry
from koordinator_tpu.scheduler.frameworkext import SchedulerService
from koordinator_tpu.scheduler.journal import CommitJournal
from koordinator_tpu.scheduler.metrics_defs import SchedulerMetrics
from koordinator_tpu.utils import synthetic

N_NODES, N_PODS = 32, 64
MARK = "WARM_CACHE_SMOKE_REPORT "


def make_inputs(seed: int):
    snap = synthetic.synthetic_cluster(N_NODES, seed=seed, num_quotas=4,
                                       num_gangs=4)
    pods = synthetic.synthetic_pods(N_PODS, seed=seed + 7, num_quotas=4,
                                    num_gangs=4)
    return snap, pods


def make_service(workdir: str, journal_name: str) -> SchedulerService:
    cache = CompileCache()  # JAX_COMPILATION_CACHE_DIR, from run_child
    journal = CommitJournal(os.path.join(workdir, journal_name))
    svc = SchedulerService(metrics=SchedulerMetrics(Registry()),
                           num_rounds=2, k_choices=4, guards=False,
                           journal=journal, compile_cache=cache)
    svc._sleep = lambda _s: None
    return svc


def child(mode: str, workdir: str, seed: int) -> int:
    """One process life: cold/warm schedule or restart recovery. The
    verdict rides one JSON line on stdout for the parent."""
    snap, pods = make_inputs(seed)
    # the warm probe gets its OWN journal: it re-runs the batch as a
    # fresh epoch, and a second completed epoch in the shared journal
    # would complicate the recover child's replay set. The cache dir —
    # the thing under test — is shared by all three.
    svc = make_service(workdir, "journal_warm.bin" if mode == "warm"
                       else "journal.bin")
    with counters.watch() as w:
        if mode == "recover":
            svc.publish(snap)
            report = svc.recover({1: pods})
            assignment = np.asarray(report["results"][1].assignment)
            compiled = report["compiled_programs"]
        else:
            svc.publish(snap)
            assignment = np.asarray(svc.schedule(pods).assignment)
            compiled = w.cache_misses
    print(MARK + json.dumps({
        "mode": mode,
        "assignment": assignment.tolist(),
        "compiled_programs": int(compiled),
        "persistent_hits": int(w.cache_hits),
        "persistent_misses": int(w.cache_misses),
        "manifest_hits": svc.compile_cache.hits,
        "manifest_misses": svc.compile_cache.misses,
        "manifest_entries": svc.compile_cache.stats()["entries"],
    }), flush=True)
    return 0


def run_child(mode: str, workdir: str, seed: int) -> dict:
    # the cache under test is placed from outside, as on a deployment
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(workdir, "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", mode,
         workdir, str(seed)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise AssertionError(
            f"{mode} child exited {proc.returncode};\nstderr tail: "
            f"{proc.stderr[-2000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith(MARK):
            return json.loads(line[len(MARK):])
    raise AssertionError(f"{mode} child printed no report;\nstdout "
                         f"tail: {proc.stdout[-2000:]}")


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def main(argv) -> int:
    if argv[:1] == ["--child"]:
        return child(argv[1], argv[2],
                     int(argv[3]) if len(argv) > 3 else 0)
    seed = int(argv[0]) if argv else 0
    workdir = tempfile.mkdtemp(prefix="warm_cache_smoke_")
    try:
        cold = run_child("cold", workdir, seed)
        check(cold["persistent_misses"] >= 1,
              f"cold run compiled nothing ({cold}) — the cache dir "
              f"cannot have been active")
        check(cold["manifest_entries"] >= 1,
              f"cold run recorded no manifest entries ({cold})")
        print(f"WARM OK    cold: {cold['persistent_misses']} compile(s), "
              f"{cold['manifest_entries']} manifest entr(ies)", flush=True)

        warm = run_child("warm", workdir, seed)
        check(warm["persistent_misses"] == 0,
              f"warm run still compiled {warm['persistent_misses']} "
              f"program(s) — the warm-start contract is broken")
        check(warm["persistent_hits"] >= 1,
              f"warm run hit nothing ({warm}) — it cannot have read "
              f"the cache")
        check(warm["manifest_misses"] == 0,
              f"warm run took {warm['manifest_misses']} manifest "
              f"miss(es): the cycle program's cache key drifted "
              f"between identical processes")
        check(warm["assignment"] == cold["assignment"],
              "warm placements diverged from the cold run")
        print(f"WARM OK    warm: 0 compiles, "
              f"{warm['persistent_hits']} persistent hit(s)", flush=True)

        rec = run_child("recover", workdir, seed)
        check(rec["compiled_programs"] == 0,
              f"restart recovery compiled {rec['compiled_programs']} "
              f"program(s) against a warmed cache")
        check(rec["assignment"] == cold["assignment"],
              "recovered placements diverged from the cold run")
        print("WARM OK    recover: 0 compiles, replay bit-identical",
              flush=True)
        print("WARM CACHE SMOKE: cold->warm->recover converge with "
              "zero warm-path compilations", flush=True)
        return 0
    except AssertionError as exc:
        print(f"WARM FAIL  {exc}", flush=True)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
