"""Shared pieces of the node-sharded `SchedulerService` tests.

The two served configurations come from the benchmark's own files,
read as JSON (never imported): `benchmark/configs/*.json` gives the
cluster generator and the service's statics, `benchmark/traffic/*.json`
the pod generator. Sizes are the caller's, cut small for the CPU.
Batches are drained closed-loop through `SchedulerService.schedule`,
the topology counts carried between calls on the host the way
`benchmark/run.py`'s `carry_counts` carries them.
"""

import functools
import json
import os

import jax
import numpy as np

from koordinator_tpu import parallel
from koordinator_tpu.scheduler.frameworkext import (
    DegradationLadder,
    SchedulerService,
)
from koordinator_tpu.utils import synthetic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# config label -> (configuration file, traffic file) under benchmark/
CELLS = {"colocation": ("colocation-10k", "lsbe-backlog"),
         "full-gate": ("fullgate-10k", "gated-backlog")}
# the cross-batch count rule: (count field, domain field, member field)
COUNT_RULE = (("spread_count0", "spread_domain", "spread_member"),
              ("anti_count0", "anti_domain", "anti_member"),
              ("anti_carrier_count0", "anti_domain", "anti_carrier"),
              ("aff_count0", "aff_domain", "aff_member"))


def _read(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def make_inputs(config: str, num_nodes: int, batch: int, num_batches: int,
                seed: int = 0):
    """(service block, host snapshot, host batches) for `config` at
    `num_nodes` nodes: the first `num_batches` calls of `batch` pods
    of the traffic's backlog, so each batch has the cell's pod mix.
    Cached: callers never write into the arrays."""
    cfg_name, traffic_name = CELLS[config]
    cfg = _read("configs", cfg_name)
    traffic = _read("traffic", traffic_name)
    params = dict(cfg["cluster"]["params"], num_nodes=num_nodes)
    snap = getattr(synthetic, cfg["cluster"]["generator"])(seed=seed,
                                                           **params)
    spec = traffic["pods"]
    pod_params = dict(spec["params"],
                      **{k: params[k] for k in spec["from_cluster"]})
    pods = getattr(synthetic, spec["generator"])(
        int(traffic["backlog"]), seed=seed + 1, **pod_params)
    batches = tuple(synthetic.slice_batch(pods, i * batch, batch)
                    for i in range(num_batches))
    return cfg["service"], snap, batches


def make_service(block: dict, chunked: bool = False) -> SchedulerService:
    """A service with the configuration's statics; `chunked` forces the
    ladder's chunked rung (two chunks a cycle)."""
    svc = SchedulerService(guards=block["guards"],
                           auto_pack=block["auto_pack"],
                           **block["schedule_kwargs"])
    if chunked:
        svc.ladder.level = DegradationLadder.L_CHUNKED
        svc.ladder.chunk_splits = 1
    return svc


def shard_over(snap, batches, devices):
    """The snapshot node-sharded over a mesh of `devices` (node axis
    padded to the mesh) and the batches' node-indexed matrices padded
    to match."""
    mesh = parallel.make_mesh(list(devices))
    n_pad = parallel.padded_node_count(int(snap.num_nodes), mesh)
    sharded = parallel.shard_snapshot(parallel.pad_nodes_to_mesh(snap, mesh),
                                      mesh)
    return sharded, [parallel.pad_batch_nodes(b, n_pad) for b in batches]


def carry_counts(counts: dict, batch, assignment) -> dict:
    """Each placed member of group g adds one to g's count in its
    node's domain (what the edge's builder recomputes)."""
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


def drain(svc: SchedulerService, batches, on_commit=None) -> list:
    """Schedule `batches` one call each, counts carried between calls;
    returns each call's assignment on the host. `on_commit(i,
    assignment)` runs after each call."""
    counts = {f: np.asarray(getattr(batches[0], f)) for f, _, _ in COUNT_RULE}
    out = []
    for i, batch in enumerate(batches):
        batch = batch.replace(**counts)
        assignment = np.asarray(svc.schedule(batch).assignment)
        if on_commit is not None:
            on_commit(i, assignment)
        counts = carry_counts(counts, batch, assignment)
        out.append(assignment)
    return out


def moved_leaves(committed, published) -> list:
    """The paths of the committed snapshot's leaves whose placement
    differs from the published snapshot's."""
    pub = jax.tree_util.tree_leaves_with_path(published)
    com = jax.tree_util.tree_leaves(committed)
    return [jax.tree_util.keystr(path) for (path, p), c in zip(pub, com)
            if not c.sharding.is_equivalent_to(p.sharding, p.ndim)]


def check_clean(svc: SchedulerService, chunked: bool, where: str) -> None:
    """The cycle ran on the rung asked for, with no failed attempt."""
    assert svc.last_ladder_state.chunked == chunked, where
    assert not svc.ladder.transitions, (where, svc.ladder.transitions)
    failed = sum(v for _, _, v in
                 svc.metrics.failures_classified.samples())
    assert failed == 0, (where, failed)


# the conformance cases' shapes: a node count no mesh of 2, 4 or 8
# divides, and batches above SchedulerService.AUTO_PACK_MIN_BATCH so
# full-gate's prefix packing engages on the single-program rung
CONFORMANCE = dict(num_nodes=205, batch=768, num_batches=2)


def one_device_run(config: str, chunked: bool):
    """(assignments, committed snapshot on the host) of the
    conformance inputs on one device."""
    block, snap, batches = make_inputs(config, **CONFORMANCE)
    svc = make_service(block, chunked)
    svc.publish(snap)
    assignments = drain(
        svc, batches,
        lambda i, _a: check_clean(svc, chunked, f"one device, call {i}"))
    return assignments, jax.device_get(svc.store.current())


def check_sharded_conformance(config: str, chunked: bool, n_devices: int,
                              reference) -> None:
    """The conformance inputs node-sharded over `n_devices` place
    bit-identically to `reference` (one_device_run's), keep the
    published sharding, and never bind to a pad row."""
    n = CONFORMANCE["num_nodes"]
    block, snap, batches = make_inputs(config, **CONFORMANCE)
    sharded, padded = shard_over(snap, batches, jax.devices()[:n_devices])
    assert int(sharded.num_nodes) > n  # the node axis really is padded
    svc = make_service(block, chunked)
    svc.publish(sharded)
    published = svc.store.current()
    if config == "full-gate" and not chunked:
        # the cases exist to run the packed program sharded
        _, statics, _ = svc._prepare_batch(published, padded[0])
        assert {"topo_prefix", "numa_prefix", "gpu_prefix"} <= set(statics)

    def on_commit(i, assignment):
        where = f"{n_devices} devices, call {i}"
        check_clean(svc, chunked, where)
        assert svc.metrics.mesh_size.value() == n_devices, where
        assert moved_leaves(svc.store.current(), published) == [], where
        assert (assignment < n).all(), where

    got = drain(svc, padded, on_commit)
    want, want_snap = reference
    for i, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(a, b, err_msg=f"call {i}")
    committed = svc.store.current()
    assert not np.asarray(committed.nodes.requested)[n:].any()
    real = jax.device_get(parallel.unpad_nodes(committed, n))
    diverged = [jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(real),
        jax.tree_util.tree_leaves(want_snap))
        if not np.array_equal(np.asarray(a), np.asarray(b))]
    assert diverged == []
