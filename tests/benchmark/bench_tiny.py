"""Helpers of the benchmark's CPU tests: a tiny checkout of the
benchmark (a copy of benchmark/ with BENCHMARK.json, plus tiny cells
that are nothing but added files and entries: configs, traffic,
workloads), so every test that runs one also shows that a cell needs no
code edit; and one in-process run of such a cell."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

# tiny sizes: a few pods per node, so the clusters are not overfull
TINY_NODES, TINY_BACKLOG, TINY_BATCH = 128, 1024, 256
TINY_CELLS = {
    # cell: (configuration, traffic, chips)
    "tiny-fullgate.gated": ("fullgate-10k", "gated-backlog", 1),
    "tiny-colocation.lsbe": ("colocation-10k", "lsbe-backlog", 1),
    "tiny-fullgate.gated.4chip": ("fullgate-10k", "gated-backlog", 4),
}


def make_checkout(dest) -> str:
    dest = str(dest)
    shutil.copytree(BENCH, os.path.join(dest, "benchmark"),
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    added = set()
    for cell, (config, traffic, chips) in TINY_CELLS.items():
        if config not in added:
            with open(os.path.join(ROOT, files[config])) as f:
                cfg = json.load(f)
            cfg["cluster"]["params"]["num_nodes"] = TINY_NODES
            # a cluster of 128 nodes leaves more pods unplaced than one
            # of 10,000; the other limits are the cell's own
            cfg["guarantees"]["limits"]["unplaced_share"] = 0.25
            name = f"tiny-{config}"
            with open(os.path.join(dest, "benchmark", "configs",
                                   name + ".json"), "w") as f:
                json.dump(cfg, f)
            bench["configs"].append({
                "name": name, "source": "tests", "reduced": ["num_nodes"],
                "file": f"benchmark/configs/{name}.json", "why": "tests"})
            added.add(config)
        tname = f"tiny-{traffic}"
        with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
            tr = json.load(f)
        tr["backlog"], tr["batch"] = TINY_BACKLOG, TINY_BATCH
        with open(os.path.join(dest, "benchmark", "traffic",
                               tname + ".json"), "w") as f:
            json.dump(tr, f)
        bench["workloads"].append({
            "name": cell, "config": f"tiny-{config}", "traffic": tname,
            "chips": chips, "why": "tests"})
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return dest


def reading(checkout, cell, kind, seed=3_000_000_021):
    """One run of a tiny cell in this process on CPU devices (the
    harness's look for a chip skipped): `sound` (with the control's
    placement readings beside it), or a fault of control.FAULTS planted
    under the timed path."""
    import jax

    import control
    import run

    spec = run.load_spec(run.Path(checkout), cell)
    devices = jax.devices()[:int(spec["cell"]["chips"])]
    return run.run_cell(spec, seed, 0.5, False, devices, run.Out(devices),
                        plant=control.FAULTS.get(kind),
                        control=kind == "sound")
