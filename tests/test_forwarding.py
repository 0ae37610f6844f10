"""The served cycle's output forwarding (scheduler/forwarding.py): the
program returns only the leaves it computes, and the service rebuilds
the same `(ScheduleResult, health, node_bad, pod_bad)` on the host, leaf
for leaf bit-identical to the program called directly. Forwarded leaves
are the published snapshot's own arrays, and nothing on the served path
donates them."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from koordinator_tpu.obs import phases
from koordinator_tpu.obs.trace import Tracer
from koordinator_tpu.scheduler import core, forwarding, guards
from koordinator_tpu.scheduler.frameworkext import DegradationLadder
from koordinator_tpu.testing import faults

from test_hostbatch import CELLS, _leaves_equal, cell_inputs, make_service

# outputs of the served program per configuration at its generator
# shapes, guards on: computed, and re-attached on the host
COMPUTED = {"colocation": 24, "fullgate": 30}
FORWARDED = {"colocation": 40, "fullgate": 34}


def _direct(svc):
    """`svc` dispatching its program the way the service did before
    forwarding: called directly, every output a buffer of its own."""

    def run(snap, pods, kwargs):
        if svc.guards_enabled:
            return guards.guarded_schedule_batch(snap, pods, svc.cfg,
                                                 **kwargs)
        return core.schedule_batch(snap, pods, svc.cfg, **kwargs), \
            None, None, None

    svc._run_program = run
    return svc


def _on_rung(svc, rung):
    """Put `svc` on a ladder rung: "normal", "single_device",
    "chunked" (two chunks) or "mesh_shrink" (three devices)."""
    if rung == "single_device":
        svc.ladder.level = DegradationLadder.L_SINGLE_DEVICE
    elif rung == "chunked":
        svc.ladder.level = DegradationLadder.L_CHUNKED
        svc.ladder.chunk_splits = 1
    elif rung == "mesh_shrink":
        svc.ladder.level = DegradationLadder.L_MESH_SHRINK
        svc.device_health = lambda: jax.devices()[:3]
    return svc


def _captured(svc):
    """Record the (snap, pods, kwargs) of `svc`'s program calls."""
    seen = []
    run = svc._run_program

    def record(snap, pods, kwargs):
        seen.append((snap, pods, kwargs))
        return run(snap, pods, kwargs)

    svc._run_program = record
    return seen


# --- the analysis ----------------------------------------------------------

@jax.jit
def _inner(a, b, c):
    return a, b + 1.0, c, jnp.zeros((0, 3))


@jax.jit
def _outer(x, y, z):
    a, b, c, e = _inner(y, x, z)
    return {"a": a, "b": b, "c": c, "e": e, "x": x}


def test_forwarding_resolves_through_nested_jit():
    x, y, z = jnp.ones(4), jnp.arange(3.0), jnp.zeros((2, 0))
    closed = _outer.trace(x, y, z).jaxpr
    # outputs in key order: a (= y), b (computed), c (= z), e (empty), x
    assert forwarding.forwarded_inputs(closed.jaxpr) == [1, None, 2,
                                                         None, 0]
    out, plan = forwarding.call(_outer, (x, y, z), {})
    assert (plan.outputs, plan.forwarded) == (1, 4)
    assert out["a"] is y and out["c"] is z and out["x"] is x
    assert out["e"].shape == (0, 3) and out["e"].dtype == jnp.float32
    np.testing.assert_array_equal(out["b"], x + 1.0)
    # the empty leaf is made once per signature
    assert forwarding.call(_outer, (x, y, z), {})[0]["e"] is out["e"]


def test_served_form_traces_fn_once_per_signature():
    """The analysis rides `fn`'s one trace: the served form's call, its
    lowering for a warmer (abstract inputs) and later calls trace
    `fn`'s body once."""
    traced = []

    @functools.partial(jax.jit, static_argnames=("k",))
    def f(a, b, *, k):
        traced.append(k)
        return a, b * k

    args = (jnp.ones(3), jnp.ones(2))
    abstract = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=x.sharding)
                     for x in args)
    forwarding.call(f, args, {"k": 2})
    forwarding.lower(f, abstract, {"k": 2}).compile()
    out, plan = forwarding.call(f, args, {"k": 2})
    assert traced == [2]
    assert out[0] is args[0] and (plan.outputs, plan.forwarded) == (1, 1)


def test_host_inputs_come_back_on_the_device():
    x = np.ones(4, np.float32)
    out, _ = forwarding.call(_outer, (x, np.arange(3.0), np.zeros((2, 0))),
                             {})
    assert isinstance(out["x"], jax.Array)
    ref = _outer(x, np.arange(3.0), np.zeros((2, 0)))
    for key in ref:
        assert out[key].dtype == ref[key].dtype
        assert out[key].sharding == ref[key].sharding
        assert out[key].committed == ref[key].committed
        np.testing.assert_array_equal(out[key], ref[key])


# --- the service: served against direct ------------------------------------

@pytest.mark.parametrize("rung", ["normal", "single_device", "chunked"])
@pytest.mark.parametrize("form", ["packed", "leaves"])
@pytest.mark.parametrize("guards_on", [True, False])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_served_cycle_bit_identical_to_the_direct_program(cell, guards_on,
                                                          form, rung):
    config, snap, pods = cell_inputs(cell)
    if form == "leaves":
        pods = jax.device_put(pods)
    snap = jax.device_put(snap, jax.devices()[0])
    host = jax.tree_util.tree_map(np.asarray, snap)
    served = make_service(config, guards_on=guards_on)
    direct = _direct(make_service(config, guards_on=guards_on))
    for svc in (served, direct):
        _on_rung(svc, rung).publish(snap)
    published = [snap]
    for _ in range(3):
        res_s, res_d = served.schedule(pods), direct.schedule(pods)
        _leaves_equal(res_s, res_d)
        _leaves_equal(served.store.current(), direct.store.current())
        published.append(served.store.current())
    assert served._last_forwarding.forwarded > 0
    # each version shares the leaves the program does not write with
    # the one before it (the single-device rung runs on a device_put
    # of the version); every version stays readable
    for old, new in zip(published, published[1:]):
        kept = {id(x) for x in jax.tree_util.tree_leaves(old)}
        shared = [x for x in jax.tree_util.tree_leaves(new)
                  if id(x) in kept and x.size]
        assert bool(shared) == (rung != "single_device")
    for version in published:
        for leaf in jax.tree_util.tree_leaves(version):
            assert not leaf.is_deleted()
            np.asarray(leaf)
    _leaves_equal(snap, host)


def test_mesh_shrink_rung_bit_identical_to_the_direct_program():
    """The shrunk mesh pads, shards, runs the served form and unpads:
    forwarded leaves pass through the unpad like the computed ones."""
    if jax.device_count() < 4:
        pytest.skip("needs >= 4 devices (conftest forces 8 on CPU)")
    config, snap, pods = cell_inputs("colocation")
    snap = jax.device_put(snap, jax.devices()[0])
    served = make_service(config)
    direct = _direct(make_service(config))
    for svc in (served, direct):
        _on_rung(svc, "mesh_shrink").publish(snap)
    for _ in range(2):
        _leaves_equal(served.schedule(pods), direct.schedule(pods))
        _leaves_equal(served.store.current(), direct.store.current())
    assert served.metrics.mesh_size.value() == 3
    assert served._last_forwarding.forwarded == FORWARDED["colocation"]


def test_quarantined_columns_are_computed_not_forwarded():
    """A cycle with a quarantined node publishes the scrubbed columns:
    the program writes them, so they are its own outputs."""
    config, snap, pods = cell_inputs("colocation")
    usage = np.array(snap.nodes.usage)
    usage[5, 0] = np.nan
    bad = jax.device_put(
        snap.replace(nodes=snap.nodes.replace(usage=usage)),
        jax.devices()[0])
    served = make_service(config)
    direct = _direct(make_service(config))
    for svc in (served, direct):
        svc.publish(bad)
        svc.schedule(pods)
    assert served.last_health_word & guards.NODE_METRIC_NONFINITE
    got = served.store.current()
    assert got.nodes.usage is not bad.nodes.usage
    assert np.isfinite(np.asarray(got.nodes.usage)).all()
    assert not np.asarray(got.nodes.schedulable)[5]
    _leaves_equal(got, direct.store.current())


def test_a_fault_retries_against_the_intact_published_snapshot():
    config, snap, pods = cell_inputs("colocation")
    snap = jax.device_put(snap, jax.devices()[0])
    served = make_service(config)
    direct = _direct(make_service(config))
    for svc in (served, direct):
        svc.publish(snap)
        svc.schedule(pods)
    before = served.store.current()
    # the next cycle's first attempt fails at the program seam; the
    # retry runs against the published version, which shares leaves
    # with the one before it
    served.fault_injection = faults.FaultInjector(3).xla_transient(
        fail_attempts={1})
    res_s, res_d = served.schedule(pods), direct.schedule(pods)
    assert served.metrics.failures_classified.labels(
        "xla_internal").get() == 1
    assert served.ladder.level == DegradationLadder.L_NORMAL
    for leaf in jax.tree_util.tree_leaves(before):
        assert not leaf.is_deleted()
    _leaves_equal(res_s, res_d)
    _leaves_equal(served.store.current(), direct.store.current())


def test_dispatch_span_counts_outputs_and_forwarded():
    config, snap, pods = cell_inputs("colocation")
    tr = Tracer()
    svc = make_service(config, trace=tr)
    svc.publish(jax.device_put(snap, jax.devices()[0]))
    svc.schedule(pods)
    _on_rung(svc, "chunked").schedule(pods)
    single, chunked = (r.attrs for r in tr.records()
                       if r.name == phases.SPAN_DISPATCH)
    want = (COMPUTED["colocation"], FORWARDED["colocation"])
    assert (single["outputs"], single["forwarded"]) == want
    # the chunked rung serves each chunk; the attrs are the last one's
    # (the chunk width moves no leaf between the two groups)
    assert (chunked["outputs"], chunked["forwarded"]) == want


# --- the compiled program --------------------------------------------------

def _entry_root_operands(hlo: str):
    """(operand instructions of the ENTRY computation's ROOT tuple,
    {name: instruction text} of that computation)."""
    entry = hlo[hlo.index("\nENTRY "):]
    body = entry[entry.index("{") + 1:entry.index("\n}")]
    defs = {}
    root = None
    for line in body.strip().splitlines():
        line = line.strip()
        name = line.split(" = ", 1)[0]
        if name.startswith("ROOT "):
            name = name[len("ROOT "):]
            root = line
        defs[name.lstrip("%")] = line
    args = re.sub(r"/\*.*?\*/", "",
                  root[root.rindex("tuple(") + len("tuple("):
                       root.rindex(")")])
    return [a.strip().lstrip("%") for a in args.split(",")], defs


def _zero_elements(instruction: str) -> bool:
    shape = instruction.split(" = ", 1)[1].split(" ", 1)[0]
    return bool(re.search(r"\[(\d+,)*0(,\d+)*\]", shape))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_served_program_returns_only_what_it_computes(cell):
    """No root operand of the served compiled program is a copy of a
    parameter, a parameter, or a zero-element constant; the program
    returns exactly the computed leaves."""
    config, snap, pods = cell_inputs(cell)
    svc = make_service(config)
    seen = _captured(svc)
    svc.publish(jax.device_put(snap, jax.devices()[0]))
    svc.schedule(pods)
    (s, p, kwargs), = seen
    plan = svc._last_forwarding
    assert (plan.outputs, plan.forwarded) == (COMPUTED[cell],
                                              FORWARDED[cell])
    hlo = forwarding.lower(guards.guarded_schedule_batch,
                           (s, p, svc.cfg), kwargs).compile().as_text()
    operands, defs = _entry_root_operands(hlo)
    assert len(operands) == COMPUTED[cell]
    for name in operands:
        inst = defs[name]
        op = inst.split(" = ", 1)[1].split("(", 1)[0].split()[-1]
        assert op != "parameter", inst
        if op == "copy":
            src = inst.rsplit("(", 1)[1].split(")")[0].strip().lstrip("%")
            assert "parameter(" not in defs.get(src, ""), inst
        if op in ("constant", "copy", "broadcast"):
            assert not _zero_elements(inst), inst


def test_no_served_path_donates():
    """No rung gives up a published snapshot: a version shares the
    leaves the program does not write with the version before it, so a
    program that donated its snapshot would delete what every earlier
    version still holds. Every version published on any rung, and the
    one each started from, stays readable."""
    if jax.device_count() < 4:
        pytest.skip("needs >= 4 devices (conftest forces 8 on CPU)")
    config, snap, pods = cell_inputs("colocation")
    snap = jax.device_put(snap, jax.devices()[0])
    published = [snap]
    for rung in ("normal", "single_device", "chunked", "mesh_shrink"):
        svc = _on_rung(make_service(config), rung)
        svc.publish(snap)
        for _ in range(2):
            svc.schedule(pods)
            published.append(svc.store.current())
        assert svc._last_forwarding.forwarded > 0
    for version in published:
        for leaf in jax.tree_util.tree_leaves(version):
            assert not leaf.is_deleted()
            np.asarray(leaf)
