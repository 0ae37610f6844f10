"""The control and the planted faults: runs that must come out not
correct. The benchmark's own runs never run this file.

    python benchmark/control.py --workload <cell> --seeds 11,12,13 \
        --seconds 8 [--sound 0|1] [--fault <name> ...]

Each seed runs the cell in this one process (the chip is held once)
and prints one line with the compared numbers. `sound` is the program
as the configuration states it (the lower readings); its line also
carries the control's placement readings: the placement reference
computed in bfloat16 put in the program's place, one step below the
float32 the program scores in (`placement.py`), read on the same
sampled batches. Each `--fault` of FAULTS below is planted under the
timed path.
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402


def bf16_requests(backlog: dict) -> dict:
    """The backlog with every request rounded to bfloat16 (round to
    nearest even), the estimator's output left as sent."""
    req = np.asarray(backlog["requests"], np.float32)
    bits = req.view(np.uint32).astype(np.uint64)
    rounded = ((bits + 0x7FFF + ((bits >> 16) & 1)) >> 16) << 16
    out = dict(backlog)
    out["requests"] = rounded.astype(np.uint32).view(np.float32)
    return out


def plant_bf16_requests(service, chips):
    """Every request rounded to bfloat16 before the program sees it, as
    an MXU contraction at default precision rounds its operands (PR 21's
    fault): what is charged is no longer what the pods request."""
    orig = service.schedule

    def schedule(pods, *a, **k):
        sent = bf16_requests({"requests": np.asarray(pods.requests)})
        return orig(pods.replace(requests=sent["requests"]), *a, **k)

    service.schedule = schedule


def plant_score_dropped(service, chips):
    """LoadAware's score dropped from the program: every node scores 0,
    so each pod takes the first node that admits it. The compiled
    programs are dropped so that the change is traced; `undo` restores
    both."""
    import jax
    from koordinator_tpu.scheduler.plugins import loadaware

    orig = loadaware.score_matrix

    def zero(nodes, pods, cfg, score_dims=None):
        return orig(nodes, pods, cfg, score_dims) * 0.0

    loadaware.score_matrix = zero
    jax.clear_caches()

    def undo():
        loadaware.score_matrix = orig
        jax.clear_caches()

    return undo


def plant_state_unchanged(service, chips):
    """A cycle that commits nothing: the store keeps its snapshot."""
    service.store.update = lambda fn: service.store.current()


def plant_half_batch(service, chips):
    """Half of every batch left out: the second half is marked invalid
    before the program sees it."""
    orig = service.schedule

    def schedule(pods, *a, **k):
        valid = np.asarray(pods.valid).copy()
        valid[valid.size // 2:] = False
        return orig(pods.replace(valid=valid), *a, **k)

    service.schedule = schedule


def plant_answer_altered(service, chips):
    """One binding per cycle altered where it is produced: the first
    placed pod is returned on the next node."""
    import jax.numpy as jnp

    orig = service.schedule

    def schedule(pods, *a, **k):
        result = orig(pods, *a, **k)
        assign = np.asarray(result.assignment).copy()
        placed = np.flatnonzero(assign >= 0)
        if placed.size:
            n = int(service.store.current().nodes.allocatable.shape[0])
            assign[placed[0]] = (assign[placed[0]] + 1) % n
        return result.replace(assignment=jnp.asarray(assign))

    service.schedule = schedule


def plant_no_exchange(service, chips):
    """The exchange between chips left out: each chip commits the
    batch's placements it would have made alone, so every placed pod is
    charged once more on the same row of the next chip's shard."""
    import jax.numpy as jnp

    orig = service.schedule

    def schedule(pods, *a, **k):
        result = orig(pods, *a, **k)
        assign = np.asarray(result.assignment)
        snap = service.store.current()
        n = int(snap.nodes.allocatable.shape[0])
        placed = assign >= 0
        other = (assign[placed] + n // max(chips, 2)) % n
        extra = np.zeros(snap.nodes.requested.shape, np.float32)
        np.add.at(extra, other, np.asarray(pods.requests)[placed])
        service.store.update(lambda s: s.replace(nodes=s.nodes.replace(
            requested=s.nodes.requested + jnp.asarray(extra))))
        return result

    service.schedule = schedule


FAULTS = {"bf16_requests": plant_bf16_requests,
          "score_dropped": plant_score_dropped,
          "state_unchanged": plant_state_unchanged,
          "half_batch": plant_half_batch,
          "answer_altered": plant_answer_altered,
          "no_exchange": plant_no_exchange}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sound", type=int, choices=(0, 1), default=1)
    ap.add_argument("--fault", action="append", default=[],
                    choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT))
    spec = run.load_spec(run.ROOT, args.workload)
    devices = run.require_devices(int(spec["cell"]["chips"]))
    out = run.Out(devices)
    out.emit(bench="start", workload=args.workload,
             compile_cache=run.enable_cache(run.ROOT))
    kinds = (["sound"] if args.sound else []) + args.fault
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind in kinds:
            result = run.run_cell(
                spec, seed, args.seconds, False, devices, out,
                plant=FAULTS.get(kind), control=kind == "sound")
            out.emit(bench="reading", kind=kind, seed=seed,
                     correct=result["correct"], checks=result["checks"],
                     placement=result.get("placement"),
                     metrics=result["metrics"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
