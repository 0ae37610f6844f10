"""The main path's device programs, compiled at real width for a
described v5e (on-chip-measurement guide §2.3). Nothing runs: a compile
that passes here is not a chip run. These guard, at no chip time, what
the TPU compiler alone refuses (memory, partitioning) and pin the
precision of the capacity sums.

The topology is described inside a module fixture and every test skips
from there when it cannot be: only the worker given this file loads the
TPU compiler.
"""

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

NODES, PODS = 10_000, 2_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    """ShapeDtypeStructs of `tree`; `sharding` is one sharding or a
    matching pytree of them."""
    if not isinstance(sharding, type(tree)):
        sharding = jax.tree.map(lambda _: sharding, tree)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                          sharding=s), tree, sharding)


def _service_program(full_gate: bool):
    """(snapshot, packed batch, cfg, static kwargs) of the program the
    service dispatches for one 2000-pod batch on the 10k-node cluster,
    derived by the service's own batch preparation. The full-gate batch
    is chip_smoke's first (all 50 of its batches derive one program)."""
    import chip_smoke
    from koordinator_tpu.scheduler.frameworkext import SchedulerService
    from koordinator_tpu.utils import synthetic

    if full_gate:
        snap, batches = chip_smoke.make_inputs(NODES, chip_smoke.NUM_PODS,
                                               PODS)
        pods = batches[0]
        svc = SchedulerService(**chip_smoke.SCHEDULE_KW)
    else:
        snap = synthetic.synthetic_cluster(NODES, num_quotas=32)
        pods = synthetic.synthetic_pods(PODS, num_quotas=32)
        svc = SchedulerService(enable_numa=False)
    packed, pack_kw, _ = svc._prepare_batch(snap, pods)
    kw = dict(svc.schedule_kwargs, **pack_kw, enable_amplification=bool(
        (np.asarray(snap.nodes.cpu_amplification) > 1.0).any()))
    return snap, packed, svc.cfg, kw


def _compile(snap, pods, cfg, kw, snap_sharding, repl):
    from koordinator_tpu.scheduler import guards

    return guards.guarded_schedule_batch.lower(
        _abstract(snap, snap_sharding), _abstract(pods, repl),
        _abstract(cfg, repl), **kw).compile()


def test_canonical_chunk_compiles(one_chip):
    snap, pods, cfg, kw = _service_program(full_gate=False)
    mem = _compile(snap, pods, cfg, kw, one_chip, one_chip) \
        .memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30


def test_full_gate_chunk_compiles(one_chip):
    snap, pods, cfg, kw = _service_program(full_gate=True)
    # the service's own packing derived all three prefixes and classes
    for key in ("topo_prefix", "numa_prefix", "gpu_prefix", "dom_classes"):
        assert kw.get(key), key
    assert kw["cascade"]
    mem = _compile(snap, pods, cfg, kw, one_chip, one_chip) \
        .memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30


def test_node_sharded_step_compiles_on_2x2(topo):
    from koordinator_tpu.parallel import mesh as meshlib

    mesh = meshlib.make_mesh(list(topo.devices))
    snap, pods, cfg, kw = _service_program(full_gate=True)
    compiled = _compile(snap, pods, cfg, kw,
                        meshlib.snapshot_sharding(mesh),
                        NamedSharding(mesh, PartitionSpec()))
    # the top-k merge crosses chips; the per-device bytes fit a v5e
    assert "all-gather" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30


def test_segment_prefix_sums_run_at_highest_precision(one_chip):
    """The per-segment capacity prefix sums are an f32 [P,P]x[P,R]
    matmul; at default precision the MXU rounds milli-CPU and MiB
    requests to bf16."""
    import jax.numpy as jnp

    from koordinator_tpu.scheduler.batching import segment_prefix_ok

    p, r, s = PODS, 8, 64
    args = (jax.ShapeDtypeStruct((p,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((p, p), jnp.bool_, sharding=one_chip),
            jax.ShapeDtypeStruct((p, r), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((s, r), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((s, r), jnp.float32, sharding=one_chip))
    hlo = jax.jit(segment_prefix_ok, static_argnums=5) \
        .lower(*args, s).compile().as_text()
    assert "operand_precision={highest" in hlo


def test_full_gate_uploaded_batch_compiles(one_chip):
    """The batch uploaded as one buffer: the served program takes it
    apart and compiles at the full-gate shapes. The unpack cuts every
    column's words out of the buffer behind ONE optimization barrier;
    left free to fuse those cuts with their reshapes, the TPU compiler
    took ~32 s over the unpack alone."""
    import re

    from koordinator_tpu.scheduler import guards
    from koordinator_tpu.snapshot import hostbatch

    snap, pods, cfg, kw = _service_program(full_gate=True)
    wire = _abstract(hostbatch.pack(pods), one_chip)
    hlo = jax.jit(hostbatch.unpack).lower(wire).as_text()
    barriers = re.findall(r"%\S+:(\d+) = stablehlo\.optimization_barrier",
                          hlo)
    assert barriers == [str(len(wire.layout))]
    compiled = guards.guarded_schedule_batch.lower(
        _abstract(snap, one_chip), wire, _abstract(cfg, one_chip),
        **kw).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30
