"""The one-buffer pod batch upload (snapshot/hostbatch.py) and the
service paths that choose it: pack -> unpack is bit-identical for every
registered PodBatch field, a field the table lacks fails loudly, and a
service run on host columns (one packed transfer) places, commits and
reports health exactly as one on pre-uploaded columns (per leaf)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from koordinator_tpu.metrics import Registry
from koordinator_tpu.obs import phases
from koordinator_tpu.obs.trace import Tracer
from koordinator_tpu.scheduler import core, guards
from koordinator_tpu.scheduler.frameworkext import (
    DegradationLadder,
    SchedulerService,
)
from koordinator_tpu.scheduler.metrics_defs import SchedulerMetrics
from koordinator_tpu.scheduler.plugins.loadaware import LoadAwareConfig
from koordinator_tpu.snapshot import SnapshotStore, hostbatch, schema

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import generators  # noqa: E402
import run as bench_run  # noqa: E402

NODES, PODS = 256, 1024


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{name}.json")) as f:
        cfg = json.load(f)
    cfg["cluster"]["params"]["num_nodes"] = NODES
    return cfg


def _traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           f"{name}.json")) as f:
        tr = json.load(f)
    tr["backlog"] = tr["batch"] = PODS
    params = tr["pods"]["params"]
    if "anti_members" in params:
        # the group sizes are absolute: scaled to the small batch, the
        # topology pods stay a minority and auto-pack's prefixes engage
        params["anti_members"] = 8
        params["aff_members"] = 6
    return tr


CELLS = {"fullgate": ("fullgate-10k", "gated-backlog"),
         "colocation": ("colocation-10k", "lsbe-backlog")}


def cell_inputs(cell, seed=11):
    """(config, host snapshot, host PodBatch) of a benchmark
    configuration at 256 nodes and one 1,024-pod batch."""
    config_name, traffic_name = CELLS[cell]
    config = _config(config_name)
    cluster = generators.make_cluster(config, seed)
    backlog = generators.make_backlog(_traffic(traffic_name),
                                      config["cluster"]["params"], seed + 1)
    return (config, bench_run.to_snapshot(cluster),
            bench_run.to_batches(backlog, PODS)[0])


def _leaves_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


# --- pack -> unpack --------------------------------------------------------

@pytest.mark.parametrize("cell", sorted(CELLS))
def test_pack_unpack_bit_identical_for_every_registered_field(cell):
    _, _, pods = cell_inputs(cell)
    wire = hostbatch.pack(pods)
    fields = [n for n, s in schema.STRUCT_SPECS["PodBatch"].items()
              if "[" in s]
    assert [f[0] for f in wire.layout] == fields
    dtypes = {f[1] for f in wire.layout}
    assert {"<f4", "<i4", "|i1", "|b1"} <= dtypes
    assert wire.words.dtype == np.uint32
    assert wire.words.nbytes == sum(-(-f[4] // 4) * 4 for f in wire.layout)
    # through the device and back out of a jitted unpack
    got = jax.jit(hostbatch.unpack)(jax.device_put(wire))
    _leaves_equal(got, jax.device_put(pods))
    for name in ("has_taints", "has_spread", "has_anti", "has_aff"):
        assert getattr(got, name) == getattr(pods, name)


def test_pack_shapes_of_both_cells():
    """Colocation carries the degenerate [1, 1] domain matrices, full
    gate real [groups, nodes] ones."""
    _, _, colo = cell_inputs("colocation")
    _, _, full = cell_inputs("fullgate")
    lay_c = {f[0]: f for f in hostbatch.pack(colo).layout}
    lay_f = {f[0]: f for f in hostbatch.pack(full).layout}
    assert lay_c["spread_domain"][2] == (1, 1)
    assert lay_f["spread_domain"][2][1] == NODES
    assert lay_f["anti_domain"][2][1] == NODES


def test_odd_byte_lengths_and_empty_columns_round_trip():
    _, _, pods = cell_inputs("colocation")
    rng = np.random.default_rng(5)
    odd = pods.replace(
        qos=rng.integers(-128, 127, 7).astype(np.int8),
        numa_single=rng.random(5) < 0.5,
        spread_carrier=np.zeros((3, 0), bool),
        gpu_ratio=np.array([np.nan, -0.0, np.inf, 1e-45], np.float32),
        priority=np.array([-2 ** 31, 2 ** 31 - 1, 0], np.int32))
    got = hostbatch.unpack(jax.device_put(hostbatch.pack(odd)))
    _leaves_equal(got, jax.device_put(odd))


def test_a_field_missing_from_the_table_fails_loudly(monkeypatch):
    _, _, pods = cell_inputs("colocation")
    table = dict(schema.STRUCT_SPECS["PodBatch"])
    del table["aff_count0"]
    monkeypatch.setitem(schema.STRUCT_SPECS, "PodBatch", table)
    with pytest.raises(ValueError, match="aff_count0"):
        hostbatch.pack(pods)


def test_host_columns_only_for_all_numpy_batches():
    _, _, pods = cell_inputs("colocation")
    assert hostbatch.host_columns(pods)
    assert not hostbatch.host_columns(jax.device_put(pods))
    assert not hostbatch.host_columns(
        pods.replace(valid=jnp.asarray(pods.valid)))


def test_the_served_program_keeps_the_name_the_readers_match():
    """A packed batch runs the same jitted entries as a per-leaf one:
    the XLA module keeps its name (`jit_<function>`), which is how the
    benchmark's device readers find the served program."""
    config, snap, pods = cell_inputs("colocation")
    kw = dict(config["service"]["schedule_kwargs"])
    wire = jax.device_put(hostbatch.pack(pods))
    for fn in (guards.guarded_schedule_batch, core.schedule_batch):
        name = fn.lower(snap, wire, LoadAwareConfig.make(), **kw) \
            .compiler_ir().operation.attributes["sym_name"].value
        assert name == f"jit_{fn.__name__}"


def test_each_pack_is_a_fresh_buffer():
    _, _, pods = cell_inputs("colocation")
    a, b = hostbatch.pack(pods), hostbatch.pack(pods)
    assert a.layout == b.layout and hash(a.layout) == hash(b.layout)
    assert not np.shares_memory(a.words, b.words)


# --- the service: packed against per-leaf ----------------------------------

def make_service(config, trace=None, guards_on=None):
    svc = config["service"]
    return SchedulerService(
        store=SnapshotStore(), metrics=SchedulerMetrics(Registry()),
        trace=trace,
        guards=svc["guards"] if guards_on is None else guards_on,
        auto_pack=svc["auto_pack"], **svc["schedule_kwargs"])


def _cycle(config, snap, pods, guards_on=True, trace=None):
    svc = make_service(config, trace=trace, guards_on=guards_on)
    svc.publish(jax.device_put(snap, jax.devices()[0]))
    result = svc.schedule(pods)
    return svc, result


@pytest.mark.parametrize("guards_on", [True, False])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_service_packed_and_per_leaf_bit_identical(cell, guards_on):
    config, snap, pods = cell_inputs(cell)
    assert pods.valid.shape[0] > SchedulerService.AUTO_PACK_MIN_BATCH
    tr_p, tr_l = Tracer(), Tracer()
    svc_p, res_p = _cycle(config, snap, pods, guards_on, tr_p)
    svc_l, res_l = _cycle(config, snap, jax.device_put(pods), guards_on,
                          tr_l)
    up_p = [r for r in tr_p.records() if r.name == phases.SPAN_UPLOAD]
    up_l = [r for r in tr_l.records() if r.name == phases.SPAN_UPLOAD]
    assert [r.attrs["path"] for r in up_p] == ["packed"]
    assert [r.attrs["path"] for r in up_l] == ["leaves"]
    assert np.asarray(res_p.assignment).tobytes() \
        == np.asarray(res_l.assignment).tobytes()
    assert (np.asarray(res_p.assignment) >= 0).sum() > 0
    _leaves_equal(svc_p.store.current(), svc_l.store.current())
    _leaves_equal(res_p, res_l)
    assert svc_p.last_health_word == svc_l.last_health_word
    if cell == "fullgate":
        # auto-pack's prefixes were on: the program ran prefix-packed
        dispatch = [r for r in tr_p.records()
                    if r.name == phases.SPAN_UNPACK]
        assert dispatch


def test_quarantine_through_the_packed_path():
    """A poisoned host column crosses packed and trips the same guard
    word and quarantine as it does per leaf."""
    config, snap, pods = cell_inputs("colocation")
    req = np.array(pods.requests)
    req[3, 0] = np.nan
    bad = pods.replace(requests=req)
    svc_p, res_p = _cycle(config, snap, bad)
    svc_l, res_l = _cycle(config, snap, jax.device_put(bad))
    assert svc_p.last_health_word == svc_l.last_health_word != 0
    assert svc_p.last_health_word & guards.POD_NONFINITE
    np.testing.assert_array_equal(svc_p.last_quarantined_pods,
                                  svc_l.last_quarantined_pods)
    assert np.asarray(res_p.assignment)[3] == -1
    assert np.asarray(res_p.assignment).tobytes() \
        == np.asarray(res_l.assignment).tobytes()


# --- the upload span -------------------------------------------------------

def test_upload_span_attrs_on_each_path():
    config, snap, pods = cell_inputs("colocation")
    tr = Tracer()
    svc = make_service(config, trace=tr)
    svc.publish(jax.device_put(snap, jax.devices()[0]))
    svc.schedule(pods)
    mixed = pods.replace(valid=jnp.asarray(pods.valid),
                         requests=jnp.asarray(pods.requests))
    svc.schedule(mixed)
    svc.schedule(jax.device_put(pods))
    ups = [r for r in tr.records() if r.name == phases.SPAN_UPLOAD]
    assert [r.parent for r in ups] == [phases.SPAN_DISPATCH] * 3
    n_cols = len(hostbatch.pack(pods).layout)
    packed, mixed_up, device_up = (r.attrs for r in ups)
    assert packed == {"path": "packed", "transfers": 1,
                      "bytes": hostbatch.pack(pods).words.nbytes}
    assert mixed_up["path"] == "leaves"
    assert mixed_up["transfers"] == n_cols - 2
    assert device_up == {"path": "leaves", "transfers": 0, "bytes": 0}


def test_traced_service_keeps_the_cycle_skeleton():
    config, snap, pods = cell_inputs("colocation")
    tr = Tracer()
    svc = make_service(config, trace=tr)
    svc.publish(jax.device_put(snap, jax.devices()[0]))
    for _ in range(2):
        svc.schedule(pods)
    names = [r.name for r in tr.records()]
    for span in phases.CYCLE_SKELETON:
        if span == phases.SPAN_JOURNAL_APPEND:
            continue  # only with a commit journal attached
        assert names.count(span) == 2, span
    assert names.count(phases.SPAN_UPLOAD) == 2


def test_untraced_service_opens_no_span():
    config, snap, pods = cell_inputs("colocation")
    svc = make_service(config)
    svc.publish(jax.device_put(snap, jax.devices()[0]))
    svc.schedule(pods)
    assert svc.tracer is None


def test_single_device_rung_uploads_packed():
    config, snap, pods = cell_inputs("colocation")
    outs = []
    for batch in (pods, jax.device_put(pods)):
        tr = Tracer()
        svc = make_service(config, trace=tr)
        svc.ladder.level = DegradationLadder.L_SINGLE_DEVICE
        svc.publish(jax.device_put(snap, jax.devices()[0]))
        outs.append(np.asarray(svc.schedule(batch).assignment))
        ups = [r.attrs["path"] for r in tr.records()
               if r.name == phases.SPAN_UPLOAD]
        assert ups == ["packed" if batch is pods else "leaves"]
    np.testing.assert_array_equal(outs[0], outs[1])


def test_chunked_rung_keeps_the_per_leaf_path():
    """The chunked rung slices a device-resident batch: no upload span,
    and placements equal the per-leaf chunked run."""
    config, snap, pods = cell_inputs("colocation")
    outs = []
    for batch in (pods, jax.device_put(pods)):
        tr = Tracer()
        svc = make_service(config, trace=tr)
        svc.ladder.level = DegradationLadder.L_CHUNKED
        svc.ladder.chunk_splits = 1
        svc.publish(jax.device_put(snap, jax.devices()[0]))
        outs.append(np.asarray(svc.schedule(batch).assignment))
        assert not [r for r in tr.records()
                    if r.name == phases.SPAN_UPLOAD]
    np.testing.assert_array_equal(outs[0], outs[1])
