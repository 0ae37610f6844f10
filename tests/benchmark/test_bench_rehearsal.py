"""benchmark/run.py end to end on the CPU (JAX_PLATFORMS=cpu asks for a
rehearsal) at a tiny size, once per configuration and mix: the last
line is the contract's result object. The tiny cells are added files
and entries only (conftest.make_checkout). Without a TPU and without
the rehearsal request the run fails, and a checkout that holds only
the benchmark's own files cannot run at all."""

import json
import os
import subprocess
import sys

import pytest

from bench_tiny import ROOT

import run

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "checks"}


def bench(checkout, *args, pythonpath=ROOT, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=checkout, env=env,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell,trace", [("tiny-fullgate.gated", 0),
                                        ("tiny-colocation.lsbe", 1)])
def test_a_tiny_cell_runs_end_to_end(checkout, cell, trace):
    proc = bench(checkout, "--workload", cell, "--seed", "3000000019",
                 "--seconds", "1", "--trace", str(trace))
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    for line in lines:
        assert json.loads(line)["device"]["platform"] == "cpu"
    result = json.loads(lines[-1])
    assert set(result) - {"breakdown"} == RESULT_KEYS
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0
    with open(os.path.join(checkout, "BENCHMARK.json")) as f:
        spec = json.load(f)
    if trace:
        assert set(result["device"]) >= {"busy_s", "window_s"}
        assert "breakdown" in result
        # host spans are there on the CPU; device metrics are not
        assert {"admit_ms", "commit_ms", "dispatch_ms"} <= set(
            result["metrics"])
    else:
        assert set(result["metrics"]) == {m["name"]
                                          for m in spec["end_to_end"]}
    window = [json.loads(x) for x in lines if '"window"' in x]
    assert window and window[0]["window_compiles"] == 0
    tail = proc.stderr.strip().splitlines()[-len(result["checks"]):]
    assert [t.split("] check ")[1].split()[0] for t in tail] \
        == list(result["checks"])


def test_no_tpu_and_no_rehearsal_fails(monkeypatch, capsys):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "fullgate-10k.gated-backlog",
                  "--seed", "1", "--seconds", "1"])
    assert exc.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out


def test_only_the_benchmark_files_cannot_run(checkout):
    proc = bench(checkout, "--workload", "tiny-colocation.lsbe", "--seed",
                 "1", "--seconds", "1", pythonpath=None, timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout

