"""The benchmark: one cell, one run, one process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (`BENCHMARK.json` `workloads`) names a configuration
(`benchmark/configs/<name>.json`: the cluster generator, the service's
plugin flags, the guarantees) and a traffic mix
(`benchmark/traffic/<name>.json`: the backlog generator, its size and
the batch size). Per-layer readers are `benchmark/metrics/<name>.py`.
Nothing here names a cell: a new one is files and entries only.

Set-up builds the `koord-scheduler` process the way a user starts it
(`cmd.scheduler.build`), generates the cluster and the backlog from
`--seed`, publishes the cluster (node-sharded on four chips) and drains
the backlog once to compile every program the window runs. The window
is a closed-loop backlog drain: `SchedulerService.schedule()` of one
batch after another, topology counts carried from batch to batch, and
the seeded cluster published again when the backlog is drained. After
the window, `reference.py` rebuilds every drain's committed state from
the returned bindings and holds them to the configuration's guarantees,
and `placement.py` scores the pods of batches drawn from the seed
against every node and reads how many the program bound below a node
it left untouched.

Every line names the device. Without a TPU the run exits non-zero
unless JAX_PLATFORMS=cpu asks for a rehearsal. The last line of stdout
is the result object; the last lines of stderr are the compared numbers
beside their limits.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import generators  # noqa: E402
import placement  # noqa: E402
import reference  # noqa: E402
import tracereduce  # noqa: E402

# harness spans on the profiler's host timeline (breakdown's idle gaps)
SPAN_WINDOW = "bench/window"
SPAN_SCHEDULE = "bench/schedule"
SPAN_CARRY = "bench/count_carry"
SPAN_REPUBLISH = "bench/republish"
# a traced run profiles the first seconds of its window: at least one
# whole drain of either mix, and a trace the host can read in seconds
TRACE_SECONDS = 8.0


class BenchError(Exception):
    pass


def load_spec(root: Path, workload: str) -> dict:
    """The cell's entry with its configuration, traffic and metrics,
    all found by the names in BENCHMARK.json."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "benchmark" / "traffic" / f"{cell['traffic']}.json")
        .read_text())

    def mine(m):
        return workload in m.get("workloads", [workload])

    return dict(root=root, cell=cell, config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def require_devices(chips: int) -> list:
    """The cell's chips, or SystemExit: a TPU, or the CPU when
    JAX_PLATFORMS=cpu asks for a rehearsal."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    if platform != "tpu" and not (rehearsal and platform == "cpu"):
        raise SystemExit(f"benchmark: no TPU (jax.devices()[0] is "
                         f"{platform!r}); set JAX_PLATFORMS=cpu to rehearse")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} "
                         f"{platform} device(s), found {len(devices)}")
    return devices[:chips]


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


class Out:
    """Printing with the device on every line."""

    def __init__(self, devices):
        self.device = device_info(devices)

    def emit(self, **fields) -> None:
        print(json.dumps({**fields, "device": self.device}), flush=True)

    def err(self, text: str) -> None:
        d = self.device
        print(f"[{d['platform']} {d['kind']} x{d['count']}] {text}",
              file=sys.stderr, flush=True)


def check_program_constants() -> None:
    """The generators' copied enum values must be the program's."""
    from koordinator_tpu.api.extension import (
        NUM_RESOURCES, PriorityClass, QoSClass, ResourceKind)
    from koordinator_tpu.snapshot import schema

    pairs = [(generators.R, NUM_RESOURCES),
             (generators.CPU, ResourceKind.CPU),
             (generators.MEM, ResourceKind.MEMORY),
             (generators.BCPU, ResourceKind.BATCH_CPU),
             (generators.BMEM, ResourceKind.BATCH_MEMORY),
             (generators.GPU_CORE, ResourceKind.GPU_CORE),
             (generators.GPU_MEMORY, ResourceKind.GPU_MEMORY),
             (generators.RDMA, ResourceKind.RDMA),
             (generators.FPGA, ResourceKind.FPGA),
             (generators.PRIO_PROD, PriorityClass.PROD),
             (generators.PRIO_BATCH, PriorityClass.BATCH),
             (generators.PRIO_MID, PriorityClass.MID),
             (generators.QOS_LS, QoSClass.LS), (generators.QOS_BE, QoSClass.BE),
             (generators.NUM_AGG, schema.NUM_AGG),
             (generators.MAX_QUOTA_DEPTH, schema.MAX_QUOTA_DEPTH),
             (generators.NUM_DEV_DIMS, schema.NUM_DEV_DIMS),
             (generators.NUM_AUX_TYPES, schema.NUM_AUX_TYPES),
             (reference.DEV_MEM, schema.DEV_MEM),
             (placement.DEV_MEM, schema.DEV_MEM),
             (placement.GPU_CORE, ResourceKind.GPU_CORE),
             (placement.GPU_MEMORY, ResourceKind.GPU_MEMORY),
             (placement.RDMA, ResourceKind.RDMA),
             (placement.FPGA, ResourceKind.FPGA)]
    for ours, theirs in pairs:
        if int(ours) != int(theirs):
            raise BenchError(f"the program's constant {theirs!r} is "
                             f"{int(theirs)}, the benchmark's {ours}")


def to_snapshot(cluster: dict):
    from koordinator_tpu.snapshot import schema as S

    return S.ClusterSnapshot(
        nodes=S.NodeState(**cluster["nodes"]),
        quotas=S.QuotaState(**cluster["quotas"]),
        gangs=S.GangState(**cluster["gangs"]),
        reservations=S.ReservationState(**cluster["reservations"]),
        devices=S.DeviceState(**cluster["devices"]),
        version=cluster["version"])


def to_batches(backlog: dict, batch: int) -> list:
    from koordinator_tpu.snapshot.schema import PodBatch

    p = backlog["valid"].shape[0]
    if p % batch:
        raise BenchError(f"backlog {p} is not a multiple of batch {batch}")
    out = []
    for s in range(0, p, batch):
        cols = {k: (v[s:s + batch] if k in generators.PER_POD_FIELDS else v)
                for k, v in backlog.items()}
        out.append(PodBatch(**cols))
    return out


def build_service(config: dict, tracer=None):
    """A SchedulerService inside the koord-scheduler process, built from
    the command line a user gives it (metrics endpoint off)."""
    from koordinator_tpu.cmd import scheduler as scheduler_cmd
    from koordinator_tpu.scheduler.frameworkext import SchedulerService
    from koordinator_tpu.snapshot import SnapshotStore

    svc = config["service"]
    service = SchedulerService(store=SnapshotStore(), trace=tracer,
                               guards=svc["guards"],
                               auto_pack=svc["auto_pack"],
                               **svc["schedule_kwargs"])
    return scheduler_cmd.build(["--metrics-port", "-1"],
                               service=service).service


def place_cluster(snap, devices):
    """The seeded cluster on the cell's chips: node-sharded over a mesh
    on several, whole on one."""
    import jax

    if len(devices) == 1:
        return jax.device_put(snap, devices[0])
    from koordinator_tpu import parallel

    mesh = parallel.make_mesh(list(devices))
    n = int(snap.nodes.allocatable.shape[0])
    if parallel.padded_node_count(n, mesh) != n:
        raise BenchError(f"{n} nodes do not split evenly over "
                         f"{len(devices)} chips")
    return parallel.shard_snapshot(parallel.pad_nodes_to_mesh(snap, mesh),
                                   mesh)


def committed_columns(snap, n: int) -> dict:
    """The committed snapshot's columns the reference compares, on the
    host."""
    g = lambda x: np.asarray(x)  # noqa: E731
    return dict(
        requested=g(snap.nodes.requested)[:n],
        assigned_estimated=g(snap.nodes.assigned_estimated)[:n],
        prod_assigned_estimated=g(snap.nodes.prod_assigned_estimated)[:n],
        numa_free=g(snap.nodes.numa_free)[:n],
        quota_used=g(snap.quotas.used),
        gang_assumed=g(snap.gangs.assumed),
        gpu_free=g(snap.devices.gpu_free)[:n],
        reservation_free=g(snap.reservations.free),
        reservation_valid=g(snap.reservations.valid))


def peak_memory(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def quantile(values, q: float) -> float:
    """The nearest-rank quantile: the sample's value at or above q."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[min(int(np.ceil(q * v.size)) - 1, v.size - 1)])


# the cross-batch count rule: (count column, domain matrix, member column)
COUNT_RULE = (("spread_count0", "spread_domain", "spread_member"),
              ("anti_count0", "anti_domain", "anti_member"),
              ("anti_carrier_count0", "anti_domain", "anti_carrier"),
              ("aff_count0", "aff_domain", "aff_member"))


def carry_counts(counts: dict, batch, assignment) -> dict:
    """The edge builder's count update after a batch, on the host: each
    placed member of group g adds one to g's count in its node's
    domain (what the builder recomputes from running + assumed pods)."""
    out = {}
    placed = assignment >= 0
    for count_f, dom_f, member_f in COUNT_RULE:
        c = counts[count_f].copy()
        dom = np.asarray(getattr(batch, dom_f))
        member = np.asarray(getattr(batch, member_f))
        p_idx, g_idx = np.nonzero(member & placed[:, None])
        d = dom[g_idx, assignment[p_idx]]
        ok = (d >= 0) & (d < c.shape[1])
        np.add.at(c, (g_idx[ok], d[ok]), 1.0)
        out[count_f] = c
    return out


class Drainer:
    """The closed-loop drain of one backlog against one service."""

    def __init__(self, service, batches, cluster_dev, annotate):
        self.service = service
        self.batches = batches
        self.cluster_dev = cluster_dev
        self.annotate = annotate
        self.counts0 = {f: np.asarray(getattr(batches[0], f))
                        for f, _, _ in COUNT_RULE}
        self.counts = self.counts0
        self.index = 0

    def cycle(self):
        """One schedule() call and the count carry after it: returns
        (wall seconds of the call, batch index, result, assignment)."""
        i = self.index
        batch = self.batches[i].replace(**self.counts)
        with self.annotate(SPAN_SCHEDULE):
            t0 = time.perf_counter()
            result = self.service.schedule(batch)
            assignment = np.asarray(result.assignment)
            wall = time.perf_counter() - t0
        with self.annotate(SPAN_CARRY):
            self.counts = carry_counts(self.counts, batch, assignment)
        self.index += 1
        return wall, i, result, assignment

    @property
    def drained(self) -> bool:
        return self.index == len(self.batches)

    def republish(self) -> None:
        with self.annotate(SPAN_REPUBLISH):
            self.service.publish(self.cluster_dev)
        self.counts = self.counts0
        self.index = 0


@contextlib.contextmanager
def no_annotation(_name):
    yield


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, devices,
             out: Out, plant=None, control=False) -> dict:
    """One run of the cell on `devices`; returns the result object.
    `plant` (control.py and the fault tests only) breaks the built
    service and may return an undo; `control` (control.py only) reads
    the control's placement gaps beside the program's."""
    import jax

    from koordinator_tpu.compilecache import counters
    from koordinator_tpu.obs.trace import Tracer

    check_program_constants()
    config, traffic = spec["config"], spec["traffic"]
    batch = int(traffic["batch"])
    cluster = generators.make_cluster(config, seed)
    backlog = generators.make_backlog(traffic, config["cluster"]["params"],
                                      seed + 1)
    n = cluster["nodes"]["allocatable"].shape[0]
    tracer = Tracer(capacity=1 << 20) if trace else None
    annotate = jax.profiler.TraceAnnotation if trace else no_annotation
    service = build_service(config, tracer)
    undo = plant(service, len(devices)) if plant is not None else None
    cluster_dev = place_cluster(to_snapshot(cluster), devices)
    batches = to_batches(backlog, batch)
    drainer = Drainer(service, batches, cluster_dev, annotate)
    with jax.default_device(devices[0]):
        service.publish(cluster_dev)
        with counters.watch() as warm:
            while not drainer.drained:
                drainer.cycle()
            drainer.republish()
        out.emit(bench="setup", compiles=warm.backend_compiles,
                 compile_s=warm.compile_seconds,
                 cache_hits=warm.cache_hits, cycles=len(batches))

        trace_dir = tempfile.mkdtemp(prefix="koord-bench-trace-") \
            if trace else None
        cycles = []      # (drain, batch index, wall s, end s since window)
        answers = []     # (drain, batch index, assignment, result arrays)
        drain_ends = []  # committed snapshot of each finished drain
        drain_start, drain = 0.0, 0
        traced = None    # (monotonic ns at start, at end, cycles)
        setup_s = time.perf_counter() - T_START
        with counters.watch() as win:
            t_win = time.perf_counter()
            deadline = t_win + seconds
            trace_until = t_win + min(TRACE_SECONDS, seconds)
            if trace:
                jax.profiler.start_trace(trace_dir)
                mark = annotate(SPAN_WINDOW)
                mark.__enter__()
                mono0 = time.monotonic_ns()
            while time.perf_counter() < deadline:
                wall, i, result, assignment = drainer.cycle()
                t_end = time.perf_counter() - t_win
                cycles.append((drain, i, wall, t_end - drain_start))
                answers.append((drain, i, assignment, (
                    result.numa_take, result.gpu_take, result.res_slot)))
                del result
                if drainer.drained:
                    drain_ends.append(service.store.current())
                    drainer.republish()
                    drain_start, drain = t_end, drain + 1
                if trace and traced is None \
                        and time.perf_counter() >= trace_until:
                    mark.__exit__(None, None, None)
                    traced = (mono0, time.monotonic_ns(), len(cycles))
                    jax.profiler.stop_trace()
            window_s = time.perf_counter() - t_win
        walls = np.array([c[2] for c in cycles])
        out.emit(bench="window", seconds=window_s, cycles=len(cycles),
                 drains_finished=len(drain_ends),
                 cycle_median_s=float(np.median(walls)) if cycles else 0.0,
                 cycle_max_s=float(walls.max()) if cycles else 0.0,
                 outside_cycles_s=window_s - float(walls.sum()),
                 window_compiles=win.backend_compiles,
                 window_compile_s=win.compile_seconds)
        memory_peak = peak_memory(devices)
        ladder = service.ladder
        out.emit(bench="service", ladder=ladder.level,
                 transitions=[list(t) for t in ladder.transitions],
                 health_word=service.last_health_word,
                 mesh_size=service.metrics.mesh_size.value(),
                 memory_peak_bytes=memory_peak)

        # everything the reference needs, on the host; then free the
        # program's state before the reference runs
        ends = [committed_columns(s, n) for s in drain_ends]
        ends.append(committed_columns(service.store.current(), n))
        host_answers = [(d, i, a, tuple(np.asarray(x) for x in arrs))
                        for d, i, a, arrs in answers]
        spans = (tracer.records() if tracer is not None else [])
        del drain_ends, answers, drainer, batches, cluster_dev, service
        gc.collect()

    if callable(undo):
        undo()
    t_ref = time.perf_counter()
    checks, kinds, attempted, unplaced, regret = check_run(
        cluster, backlog, batch, host_answers, ends,
        config, seed, control)
    out.emit(bench="reference", violations_by_kind=kinds,
             drains_checked=len(ends), seconds=time.perf_counter() - t_ref,
             **regret)

    waits = np.concatenate([
        np.full(batch, c[3]) for c in cycles])
    placed_mask = np.concatenate([a[2] >= 0 for a in host_answers])
    # an unbound pod counts as having waited the whole window: longer
    # than any bound pod can have waited
    waits = np.where(placed_mask, waits, window_s)
    bound = int(placed_mask.sum())
    e2e = {
        "pods_per_s": bound / window_s,
        "cycle_p95_ms": quantile(walls, 0.95) * 1e3,
        "pod_wait_p95_ms": quantile(waits, 0.95) * 1e3,
        "setup_s": setup_s,
    }
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    info = device_info(devices)
    info["memory_peak_bytes"] = memory_peak
    result = {"correct": correct, "attempted": attempted,
              "failed": unplaced}
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in spec["end_to_end"]}
    else:
        view = tracereduce.TraceView.from_run(
            trace_dir, spans, traced[0], traced[1], traced[2],
            (traced[1] - traced[0]) / 1e9, len(devices))
        result["metrics"] = {}
        for m in spec["per_layer"]:
            value = read_metric(spec["root"], m["name"], view)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        info["busy_s"] = view.busy_s()
        info["window_s"] = view.window_s
        result["breakdown"] = view.breakdown()
        tracereduce.remove(trace_dir)
    result["device"] = info
    if control:
        result["placement"] = regret
    result["checks"] = checks
    return result


def check_run(cluster, backlog, batch, answers, ends, config, seed,
              control=False):
    """The reference over every drain of the window and over a sample of
    its batches drawn from the seed: (checks with their limits,
    violations by kind, pods attempted, pods left unbound, the
    placement readings)."""
    guarantees = config["guarantees"]
    by_drain = {}
    for d, i, a, (numa_take, gpu_take, res_slot) in answers:
        by_drain.setdefault(d, []).append((i, a, numa_take, gpu_take,
                                           res_slot))
    gaps, kinds = {}, {}
    attempted = unplaced = 0
    for d, parts in sorted(by_drain.items()):
        rows = np.concatenate([np.arange(i * batch, (i + 1) * batch)
                               for i, *_ in parts])
        ans = {"assignment": np.concatenate([p[1] for p in parts]),
               "numa_take": np.concatenate([p[2] for p in parts]),
               "gpu_take": np.concatenate([p[3] for p in parts]),
               "res_slot": np.concatenate([p[4] for p in parts])}
        g, bad, u, att = reference.check_drain(cluster, backlog, rows, ans,
                                               ends[d], guarantees)
        for k, v in g.items():
            gaps[k] = max(gaps.get(k, 0.0), v)
        for k, v in bad.items():
            kinds[k] = kinds.get(k, 0) + v
        attempted += att
        unplaced += u
    regret = check_placement(cluster, backlog, batch, by_drain, config,
                             seed, control)
    limits = guarantees["limits"]
    checks = {
        "state_gap": {"value": max(gaps.values()) if gaps else 0.0,
                      "limit": limits["state_gap"]},
        "violations": {"value": sum(kinds.values()),
                       "limit": limits["violations"]},
        "unplaced_share": {"value": unplaced / max(attempted, 1),
                           "limit": limits["unplaced_share"]},
        "regret_share": {"value": regret["regret_share"],
                         "limit": limits["regret_share"]},
    }
    return checks, kinds, attempted, unplaced, regret


def check_placement(cluster, backlog, batch, by_drain, config, seed,
                    control=False):
    """The placement reference (`placement.py`) over `cfg["batches"]`
    batches of the window drawn from the seed: the share of checked pods
    whose node scores more than `cfg["gap"]` below an untouched node
    that admitted them, the widest gap, and with `control` the same of
    the bfloat16 reference's choices."""
    cfg = config["guarantees"]["placement"]
    keys = sorted((d, p[0]) for d, parts in by_drain.items()
                  for p in parts)
    rng = np.random.default_rng(seed)
    pick = sorted(rng.choice(len(keys), min(int(cfg["batches"]),
                                            len(keys)), replace=False))
    gaps, ctrl = [], []
    for k in pick:
        d, i = keys[k]
        before = [p for p in by_drain[d] if p[0] < i]
        this = next(p for p in by_drain[d] if p[0] == i)
        state = reference.state_before(cluster, backlog, batch, before)
        g, c = placement.batch_gaps(
            cluster, backlog, state, np.arange(i * batch, (i + 1) * batch),
            this[1], this[4], config, control)
        gaps.append(g)
        ctrl.append(c)
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    out = {"placement_batches": [list(keys[k]) for k in pick],
           "placement_checked": int(gaps.size),
           "regret_share": float(np.mean(gaps > cfg["gap"]))
           if gaps.size else 0.0,
           "regret_max": float(gaps.max()) if gaps.size else 0.0}
    if control:
        ctrl = np.concatenate(ctrl)
        out["control_regret_share"] = float(np.mean(ctrl > cfg["gap"])) \
            if ctrl.size else 0.0
        out["control_regret_max"] = float(ctrl.max()) if ctrl.size else 0.0
    return out


def read_metric(root: Path, name: str, view):
    """Run the per-layer reader `benchmark/metrics/<name>.py`."""
    path = root / "benchmark" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(view)


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, every program kept (the program's own helper is told the
    directory through its environment variable)."""
    import jax

    from koordinator_tpu.compilecache import enable_persistent_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        root / "benchmark" / ".jax_cache")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable_persistent_cache()


def print_checks(out: Out, checks: dict) -> None:
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        out.err(f"check {name} {c['value']!r} limit {c['limit']!r} "
                f"{verdict}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    spec = load_spec(ROOT, args.workload)
    devices = require_devices(int(spec["cell"]["chips"]))
    out = Out(devices)
    out.emit(bench="start", workload=args.workload, seed=args.seed,
             compile_cache=enable_cache(ROOT))
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      devices, out)
    print_checks(out, result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
