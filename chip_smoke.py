"""Chip smoke: the scheduler service's main path, once, on a TPU.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the node-sharded path on a v5e-4

One chip: the koord-scheduler process is built the way a user starts it
(`cmd.scheduler.build`), the full-gate cluster (10k nodes, 32 quotas)
is published through its SnapshotStore, and a 100k-pod full-gate
backlog is fed as 50 `schedule()` calls of 2,000 pods with the whole
plugin chain on (NUMA, devices, cascade). After every call the
committed snapshot must hold no overcommit, no quota `used` above
`runtime`, strict gangs all-or-nothing, and the degradation ladder at
`normal` with no retry. The first 3 batches are then run again from
the same snapshot on the CPU backend in this process: placements and
committed `requested` must be bit-identical. That is a comparison,
never a fallback.

Four chips: the snapshot is published node-sharded over all four
devices, 5 batches go through the service, each device must hold a
quarter of the node columns after every commit, and the placements
must be bit-identical to the same batches on device 0 alone.

Every line before the last is a diagnostic. Times in them are smoke
diagnostics of one run, not metrics. The last line is
`{"ok": true, "device": {...}}`, printed only when every check passed.
Without a TPU the script exits non-zero before scheduling anything.
Everything runs in this one process.
"""

import argparse
import json
import sys
import time

NUM_NODES = 10_000
NUM_PODS = 100_000
BATCH = 2_000
NUM_QUOTAS = 32
REFERENCE_BATCHES = 3
MESH_BATCHES = 5
MESH_CHIPS = 4
# the full-gate plugin chain, as the bench's full-gate line runs it
SCHEDULE_KW = dict(enable_numa=True, enable_devices=True, cascade=True)
# core.overcommit_ok's default tolerance, for the quota check as well
TOL = 1.0


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def require_tpu(count: int = 1) -> list:
    """The visible TPU devices (at least `count`), or SystemExit. There
    is no CPU branch."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: jax.devices()[0] is "
                         f"{devices[0].platform!r}")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: --chips {count} needs {count} TPU "
                         f"devices, found {len(devices)}")
    return devices


def make_inputs(num_nodes: int, num_pods: int, batch: int):
    """(host snapshot, list of host pod batches) from fixed seeds."""
    from koordinator_tpu.utils import synthetic

    snap = synthetic.full_gate_cluster(num_nodes, num_quotas=NUM_QUOTAS)
    pods = synthetic.full_gate_pods(num_pods, num_nodes,
                                    num_quotas=NUM_QUOTAS)
    batches = [synthetic.slice_batch(pods, start, batch)
               for start in range(0, num_pods, batch)]
    return snap, batches


def make_service(sharding=None):
    """A SchedulerService inside the koord-scheduler process, built
    from the command line a user gives it (metrics endpoint off)."""
    from koordinator_tpu.cmd import scheduler as scheduler_cmd
    from koordinator_tpu.scheduler.frameworkext import SchedulerService
    from koordinator_tpu.snapshot import SnapshotStore

    service = SchedulerService(store=SnapshotStore(sharding=sharding),
                               **SCHEDULE_KW)
    return scheduler_cmd.build(["--metrics-port", "-1"],
                               service=service).service


def _failures(service) -> float:
    return sum(v for _, _, v in service.metrics.failures_classified.samples())


def check_commit(service, before, pods, assignment, num_nodes: int,
                 where: str) -> None:
    """The invariants of one committed cycle."""
    import numpy as np

    from koordinator_tpu.scheduler import core
    from koordinator_tpu.scheduler.frameworkext import DegradationLadder

    after = service.store.current()
    if not core.overcommit_ok(after, num_nodes, tol=TOL):
        excess = (np.asarray(after.nodes.requested)
                  - np.asarray(after.nodes.allocatable))[:num_nodes]
        raise SmokeFailure(
            f"{where}: {int((excess > TOL).any(axis=-1).sum())} node(s) "
            f"overcommitted, by up to {float(excess.max())}")
    quotas = after.quotas
    used = np.asarray(quotas.used)[np.asarray(quotas.valid)]
    runtime = np.asarray(quotas.runtime)[np.asarray(quotas.valid)]
    check((used <= runtime + TOL).all(),
          f"{where}: quota used exceeds runtime by "
          f"{float((used - runtime).max())}")
    # strict gangs: a gang with no member left outside this batch
    # either reaches quorum or places nothing (test_invariants' rule)
    gang_id = np.asarray(pods.gang_id)
    valid = np.asarray(pods.valid)
    placed = (assignment >= 0) & valid
    assumed0 = np.asarray(before.gangs.assumed)
    min_member = np.asarray(before.gangs.min_member)
    strict = np.asarray(before.gangs.strict)
    member_count = np.asarray(before.gangs.member_count)
    for g in np.unique(gang_id[(gang_id >= 0) & valid]):
        members = (gang_id == g) & valid
        placed_g = int((placed & members).sum())
        outstanding = int(member_count[g]) - int(assumed0[g]) \
            - int(members.sum())
        if strict[g] and outstanding <= 0 \
                and int(assumed0[g]) + placed_g < int(min_member[g]):
            check(placed_g == 0,
                  f"{where}: strict gang {g} kept {placed_g} of "
                  f"{int(min_member[g])} members")
    ladder = service.ladder
    check(ladder.level == DegradationLadder.L_NORMAL
          and ladder.chunk_splits == 0 and not ladder.transitions,
          f"{where}: ladder left normal: {ladder.transitions}")
    check(_failures(service) == 0,
          f"{where}: {_failures(service)} failed attempt(s) were retried")


def drive(service, batches, num_nodes: int, label: str,
          inspect=None) -> list:
    """Feed `batches` through `service.schedule`, carrying the topology
    counts from batch to batch the way the edge's builder would, and
    check every commit. Returns per batch (assignment, requested)."""
    import jax
    import numpy as np

    from koordinator_tpu.compilecache import counters
    from koordinator_tpu.scheduler import core

    counts = tuple(np.asarray(getattr(batches[0], f))
                   for f in core.COUNT_FIELDS)
    out = []
    for i, batch in enumerate(batches):
        batch = batch.replace(**dict(zip(core.COUNT_FIELDS, counts)))
        before = service.store.current()
        with counters.watch() as w:
            t0 = time.perf_counter()
            result = service.schedule(batch)
            assignment = np.asarray(result.assignment)
            wall = time.perf_counter() - t0
        where = f"{label} batch {i}"
        check_commit(service, before, batch, assignment, num_nodes, where)
        if inspect is not None:
            inspect(service, where)
        requested = np.asarray(service.store.current().nodes.requested)
        valid = np.asarray(batch.valid)
        emit(smoke="cycle", run=label, batch=i,
             cycle_wall_s=wall, compile_s=w.compile_seconds,
             compiles=w.backend_compiles,
             placed=int(((assignment >= 0) & valid).sum()),
             unplaced=int(((assignment < 0) & valid).sum()),
             mesh_size=int(service.metrics.mesh_size.value()))
        # same shapes every batch: only the first cycle may compile
        check(i == 0 or w.backend_compiles == 0,
              f"{where}: recompiled ({w.backend_compiles} program(s), "
              f"{w.compile_seconds} s)")
        counts = tuple(np.asarray(c) for c in core.charge_all_counts(
            jax.device_put(counts), jax.device_put(batch),
            result.assignment))
        out.append((assignment, requested[:num_nodes]))
    return out


def compare(got: list, want: list, what: str) -> None:
    """Bit-identical placements and committed `requested`, batch by
    batch; the first divergence is reported with its size."""
    import numpy as np

    for i, ((a, r), (a_ref, r_ref)) in enumerate(zip(got, want)):
        differ = int((a != a_ref).sum())
        req_differ = int((r != r_ref).any(axis=-1).sum())
        if differ or req_differ:
            emit(smoke="divergence", against=what, batch=i,
                 placements_differ=differ, nodes_requested_differ=req_differ,
                 max_requested_abs_diff=float(np.abs(r - r_ref).max()))
        check(differ == 0 and req_differ == 0,
              f"batch {i}: {differ} placement(s) and {req_differ} node "
              f"row(s) of `requested` differ from {what}")


def peak_hbm(device) -> int:
    from koordinator_tpu.obs import memwatch

    sample = next(iter(memwatch.sample_devices([device]).values()))
    check(sample.source == "memory_stats",
          f"{device}: memory read from {sample.source!r}, not the "
          f"allocator's memory_stats")
    return sample.peak_bytes


def one_chip(device, num_nodes: int = NUM_NODES, num_pods: int = NUM_PODS,
             batch: int = BATCH, reference_batches: int = REFERENCE_BATCHES,
             reference_device=None) -> None:
    """The one-chip phase on `device`, and its reference on
    `reference_device` (the CPU backend unless a test passes one)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    snap, batches = make_inputs(num_nodes, num_pods, batch)
    service = make_service()
    service.publish(snap)
    with jax.default_device(device):
        got = drive(service, batches, num_nodes, device.platform)
    if device.platform == "tpu":
        emit(smoke="memory", device=str(device),
             peak_hbm_bytes=peak_hbm(device))
    ref_dev = reference_device or jax.devices("cpu")[0]
    ref = make_service(SingleDeviceSharding(ref_dev))
    ref.publish(snap)
    with jax.default_device(ref_dev):
        want = drive(ref, batches[:reference_batches], num_nodes,
                     f"{ref_dev.platform}_reference")
    compare(got, want, f"the {ref_dev.platform} backend")


def mesh_chips(devices, num_nodes: int = NUM_NODES,
               num_pods: int = NUM_PODS, batch: int = BATCH,
               num_batches: int = MESH_BATCHES) -> None:
    """The node-sharded phase over `devices`, and its comparison with
    the same batches on devices[0] alone."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from koordinator_tpu import parallel

    snap, batches = make_inputs(num_nodes, num_pods, batch)
    batches = batches[:num_batches]
    mesh = parallel.make_mesh(list(devices))
    check(parallel.padded_node_count(num_nodes, mesh) == num_nodes,
          f"{num_nodes} nodes do not split evenly over {len(devices)}")
    per_device = num_nodes // len(devices)

    def split_over_mesh(service, where):
        cols = service.store.current().nodes
        for name in ("allocatable", "requested"):
            shards = getattr(cols, name).addressable_shards
            check(sorted(s.device.id for s in shards)
                  == sorted(d.id for d in devices)
                  and all(s.data.shape[0] == per_device for s in shards),
                  f"{where}: `{name}` is not split {per_device} rows per "
                  f"device over {len(devices)} devices: "
                  f"{[(s.device.id, s.data.shape) for s in shards]}")
        check(service.metrics.mesh_size.value() == len(devices),
              f"{where}: mesh_size reports "
              f"{service.metrics.mesh_size.value()}")

    service = make_service()
    service.publish(parallel.shard_snapshot(
        parallel.pad_nodes_to_mesh(snap, mesh), mesh))
    got = drive(service, batches, num_nodes, f"mesh{len(devices)}",
                inspect=split_over_mesh)
    alone = make_service(SingleDeviceSharding(devices[0]))
    alone.publish(snap)
    with jax.default_device(devices[0]):
        want = drive(alone, batches, num_nodes, "device0")
    compare(got, want, "device 0 alone")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, MESH_CHIPS),
                    default=1)
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)

    from koordinator_tpu.compilecache import enable_persistent_cache

    emit(smoke="compile_cache", dir=enable_persistent_cache())
    try:
        if args.chips == 1:
            one_chip(devices[0])
        else:
            mesh_chips(devices[:MESH_CHIPS])
    except SmokeFailure as exc:
        print(f"chip_smoke: FAIL: {exc}", file=sys.stderr)
        return 1
    d = devices[0]
    emit(ok=True, device={"platform": d.platform, "kind": d.device_kind,
                          "count": len(devices)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
