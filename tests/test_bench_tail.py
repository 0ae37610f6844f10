"""bench.py tail/cascade knob semantics (no device work — these pin the
host-side parsing and protocol selection that the heavy mesh tests rely
on).

The BENCH_MAX_TAIL_PASSES consolidation: the variable used to be read
TWICE with different semantics — once at import into a module constant
(post-import env changes invisible to it; an empty string crashed the
int() at import) and once as a raw truthiness check at run_northstar
(empty string flipped the full-gate default branch while the constant
kept the stale value). `bench.max_tail_passes` is now THE single
call-time parse; these tests pin its contract.
"""

import importlib

import pytest


@pytest.fixture()
def bench_mod(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    import bench
    return importlib.reload(bench)


def test_max_tail_passes_defaults(bench_mod, monkeypatch):
    monkeypatch.delenv("BENCH_MAX_TAIL_PASSES", raising=False)
    assert bench_mod.max_tail_passes(False) == 6
    # the narrower full-gate tail needs more passes to cover the same
    # straggler pool (3160 at the 100k capture > 6 x 512)
    assert bench_mod.max_tail_passes(True) == 10


def test_max_tail_passes_explicit_wins_both_paths(bench_mod, monkeypatch):
    # read at CALL time, not import time: this env var lands after the
    # module import and must still win on both paths
    monkeypatch.setenv("BENCH_MAX_TAIL_PASSES", "3")
    assert bench_mod.max_tail_passes(False) == 3
    assert bench_mod.max_tail_passes(True) == 3
    # 0 is the legitimate quick-run knob (skip the tail entirely)
    monkeypatch.setenv("BENCH_MAX_TAIL_PASSES", "0")
    assert bench_mod.max_tail_passes(False) == 0
    assert bench_mod.max_tail_passes(True) == 0
    # negative values clamp to 0 instead of producing a nonsense range
    monkeypatch.setenv("BENCH_MAX_TAIL_PASSES", "-2")
    assert bench_mod.max_tail_passes(True) == 0


def test_max_tail_passes_empty_string_is_unset(bench_mod, monkeypatch):
    # the old import-time `int(os.environ.get(...))` crashed on ""
    # while the run-time truthiness check treated it as unset; the
    # consolidated parse treats it as unset everywhere
    monkeypatch.setenv("BENCH_MAX_TAIL_PASSES", "")
    assert bench_mod.max_tail_passes(False) == 6
    assert bench_mod.max_tail_passes(True) == 10


def test_bench_has_single_max_tail_env_read(bench_mod):
    """Regression pin for the consolidation itself: exactly one source
    line reads the env var (the parse inside max_tail_passes)."""
    import inspect
    src = inspect.getsource(bench_mod)
    reads = [l for l in src.splitlines()
             if "BENCH_MAX_TAIL_PASSES" in l and "environ" in l]
    assert len(reads) == 1, reads


# --- run_with_ladder: the bench's device-lost recovery rung (ISSUE 14) -----

def test_ladder_device_lost_retries_on_a_shrunk_device_set(bench_mod,
                                                           monkeypatch):
    """A DEVICE_LOST-classified failure retries with the device count
    shrunk by one, and the retried line carries the `recovered` stamp
    — the bench mirror of the service's mesh-shrink rung."""
    monkeypatch.delenv("BENCH_DEVICES", raising=False)
    calls = []

    def fake_run(chunk=None, degraded=None, num_devices=None,
                 recovered=None, **kw):
        calls.append((chunk, degraded, num_devices, recovered))
        if len(calls) < 3:
            raise RuntimeError("UNAVAILABLE: device lost; socket closed")
        return {"num_devices": num_devices, "recovered": recovered,
                "degraded": degraded}

    monkeypatch.setattr(bench_mod.jax, "devices", lambda: [0, 1, 2, 3])
    line = bench_mod.run_with_ladder(max_halvings=2, _run=fake_run)
    # 4 -> 3 -> 2 devices, each retry stamped as recovered
    assert [c[2] for c in calls] == [None, 3, 2]
    assert line["recovered"] == "device_lost:devices=2"
    assert line["num_devices"] == 2
    assert line["degraded"] is None


def test_ladder_oom_still_halves_the_chunk(bench_mod, monkeypatch):
    calls = []

    def fake_run(chunk=None, degraded=None, num_devices=None,
                 recovered=None, **kw):
        calls.append(chunk)
        if len(calls) < 2:
            raise RuntimeError("RESOURCE_EXHAUSTED: OOM")
        return {"chunk": chunk, "degraded": degraded,
                "recovered": recovered}

    line = bench_mod.run_with_ladder(max_halvings=2, chunk=8,
                                     _run=fake_run)
    assert calls == [8, 4]
    assert line["degraded"] == "resource_exhausted:chunk=4"
    assert line["recovered"] is None


def test_ladder_out_of_device_rungs_propagates(bench_mod, monkeypatch):
    def fake_run(chunk=None, degraded=None, num_devices=None,
                 recovered=None, **kw):
        raise RuntimeError("UNAVAILABLE: device lost; socket closed")

    monkeypatch.setattr(bench_mod.jax, "devices", lambda: [0])
    with pytest.raises(RuntimeError, match="device lost"):
        bench_mod.run_with_ladder(max_halvings=3, _run=fake_run)


# --- BENCH_COST=1: the flagship cost stamp (koordcost satellite) -----------

class _FakeMemStats:
    argument_size_in_bytes = 1000
    output_size_in_bytes = 400
    temp_size_in_bytes = 300
    alias_size_in_bytes = 250
    generated_code_size_in_bytes = 0


class _FakeCompiled:
    """A device-free stand-in for jax's Compiled: the three methods
    costmodel.program_report reads, with known arithmetic."""

    def cost_analysis(self):
        # jax returns a LIST of per-computation dicts on CPU; the
        # stamp must read the first, and 'bytes accessed' has a space
        return [{"flops": 5000.0, "bytes accessed": 2000.0}]

    def memory_analysis(self):
        return _FakeMemStats()

    def as_text(self):
        return ('  %p.1 = f32[8]{0} parameter(0)\n'
                '  ROOT %add.2 = f32[8]{0} add(%p.1, %p.1), '
                'metadata={op_name="jit/koord/stage1_mask/add"}\n')


def test_flagship_stamp_keys_and_arithmetic():
    """The BENCH_COST stamp pins exactly the four bench-line keys, with
    hbm_peak_bytes = arg + out + tmp - alias (donation visible) and
    flops_per_pod = flops / P."""
    from koordinator_tpu.obs import costmodel

    stamp = costmodel.flagship_stamp(_FakeCompiled(), num_pods=100)
    assert set(stamp) == {"flops", "bytes_accessed", "hbm_peak_bytes",
                          "flops_per_pod"}
    assert stamp["flops"] == 5000.0
    assert stamp["bytes_accessed"] == 2000.0
    assert stamp["hbm_peak_bytes"] == 1000 + 400 + 300 - 250
    assert stamp["flops_per_pod"] == 50.0


def test_bench_cost_stamp_is_opt_in_and_spliced(bench_mod):
    """BENCH_COST is read at run time (one env read) and the stamp is
    spliced into the emitted line — absent entirely when off, so old
    trajectories and benchdiff joins see no phantom keys."""
    import inspect
    src = inspect.getsource(bench_mod)
    reads = [l for l in src.splitlines()
             if "BENCH_COST" in l and "environ" in l]
    assert len(reads) == 1, reads
    assert "**cost_stamp," in src
    assert "flagship_stamp" in src


def test_benchdiff_proxy_runs_without_a_persistent_cache(bench_mod,
                                                          monkeypatch,
                                                          tmp_path):
    """An ambient JAX_COMPILATION_CACHE_DIR (the chip machine sets one)
    must not turn the proxy line's `cache` stamp, part of benchdiff's
    join key, from the baseline's "cold" into hit/miss."""
    import jax

    from tools import benchdiff

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    seen = {}

    def fake_run(**_kw):
        seen["dir"] = jax.config.jax_compilation_cache_dir
        seen["env"] = bench_mod.os.environ.get("JAX_COMPILATION_CACHE_DIR")
        return {"metric": "proxy"}

    monkeypatch.setattr(bench_mod, "run_northstar", fake_run)
    try:
        assert benchdiff.proxy_lines() == [{"metric": "proxy"}]
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
    assert seen == {"dir": None, "env": None}
