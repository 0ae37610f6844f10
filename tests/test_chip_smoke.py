"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its
phases (checks and comparisons included) run end to end at a tiny size
on CPU devices, so a chip call only ever meets a path that already ran
here."""

import jax
import numpy as np
import pytest

import chip_smoke

NODES, PODS, BATCH = 256, 4096, 1024


def test_exits_nonzero_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_one_chip_phase_runs_on_cpu_devices():
    cpu = jax.devices()
    chip_smoke.one_chip(cpu[0], num_nodes=NODES, num_pods=PODS,
                        batch=BATCH, reference_batches=2,
                        reference_device=cpu[1])


def test_mesh_phase_splits_nodes_and_matches_one_device():
    chip_smoke.mesh_chips(jax.devices()[:4], num_nodes=NODES,
                          num_pods=PODS, batch=BATCH, num_batches=2)


def test_compare_reports_a_single_flipped_placement():
    a = np.arange(8, dtype=np.int32)
    r = np.zeros((4, 3), np.float32)
    chip_smoke.compare([(a, r)], [(a.copy(), r.copy())], "itself")
    b = a.copy()
    b[5] = -1
    with pytest.raises(chip_smoke.SmokeFailure, match="1 placement"):
        chip_smoke.compare([(b, r)], [(a, r)], "the reference")
