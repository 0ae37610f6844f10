"""The warm-start seam (ISSUE 17): cache-key invalidation pins,
manifest provenance (corrupt/stale state discarded loudly, never
served), and the compile-counter-backed zero-recompile pins — a second
run, a restart recovery, and a mesh-shrink failover against a warmed
cache dir must compile zero programs.

The suite keeps the persistent cache disabled (tests/conftest.py:
XLA:CPU artifacts segfault across live-migrating hosts). Every test
here activates it only against a fresh tmp dir — artifacts are written
and read by THIS process on THIS machine — and the fixture detaches
the process-global config afterwards.
"""

import json
import os
import threading
import types

import numpy as np
import pytest

import jax

from koordinator_tpu.compilecache import counters, keys, precompile
from koordinator_tpu.compilecache.cache import (
    CompileCache,
    _reset_jax_persistent_cache,
)
from koordinator_tpu.metrics import Registry
from koordinator_tpu.scheduler.frameworkext import (
    DegradationLadder,
    SchedulerService,
)
from koordinator_tpu.scheduler.journal import CommitJournal
from koordinator_tpu.scheduler.metrics_defs import SchedulerMetrics
from koordinator_tpu.snapshot import schema
from koordinator_tpu.utils import synthetic

N, P = 16, 32


@pytest.fixture()
def cache_dir(tmp_path, monkeypatch):
    """A fresh cache dir; teardown re-disables the process-global
    persistent cache (the conftest invariant) and drops jax's
    once-per-process cache singleton so later tests can't read it.

    Setup clears the in-process executable cache: a program an EARLIER
    test already jitted would otherwise be reused by this test's cold
    run without ever being written to this test's dir — and the warm
    run would then miss on it."""
    jax.clear_caches()
    path = str(tmp_path / "cc")
    # placed from outside, as on a deployment: every CompileCache and
    # enable_persistent_cache() of the test lands here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
    yield path
    jax.config.update("jax_compilation_cache_dir", None)
    _reset_jax_persistent_cache()


def service_inputs(seed=0):
    snap = synthetic.synthetic_cluster(N, seed=seed, num_quotas=4,
                                       num_gangs=4)
    pods = synthetic.synthetic_pods(P, seed=seed + 3, num_quotas=4,
                                    num_gangs=4)
    return snap, pods


def make_service(cache, **kw):
    svc = SchedulerService(metrics=SchedulerMetrics(Registry()),
                           num_rounds=2, k_choices=4, guards=False,
                           compile_cache=cache, **kw)
    svc._sleep = lambda _s: None
    return svc


SMALL = {"P": 16, "N": 8, "G": 4, "Q": 4}


def small_ws(**kw):
    kw.setdefault("sizes", dict(SMALL))
    kw.setdefault("devices", 1)
    kw.setdefault("cascade_forms", (False,))
    kw.setdefault("tail", None)
    return precompile.WorkSet(**kw)


# --- key derivation & invalidation pins -----------------------------------

def test_fingerprint_is_deterministic():
    assert keys.contract_fingerprint() == keys.contract_fingerprint()


def test_contract_modules_in_sync_with_shapecheck():
    """The fingerprint must digest the SAME fully populated registry
    the shape gate checks — a module registered in one list but not
    the other silently weakens one of the two."""
    from tools import shapecheck
    assert set(keys.CONTRACT_MODULES) == set(shapecheck.CONTRACT_MODULES)


def test_contract_spec_edit_changes_fingerprint():
    base = keys.contract_fingerprint()
    contracts = dict(schema.SHAPE_CONTRACTS)
    name = sorted(contracts)[0]
    c = contracts[name]
    contracts[name] = types.SimpleNamespace(
        args=c.args, returns=c.returns, static=c.static,
        callables=c.callables, pad=(c.pad or "") + " (edited)")
    assert keys.contract_fingerprint(contracts=contracts) != base


def test_struct_field_dtype_edit_changes_fingerprint():
    base = keys.contract_fingerprint()
    structs = dict(schema.STRUCT_SPECS)
    ns = dict(structs["NodeState"])
    assert ns["usage"].startswith("f32[")
    ns["usage"] = "f16[" + ns["usage"].split("[", 1)[1]
    structs["NodeState"] = ns
    assert keys.contract_fingerprint(structs=structs) != base


def test_cache_key_folds_every_axis():
    fp = "a" * 64
    base = dict(program="cycle", inputs_digest="d0", statics={"k": 4},
                mesh_axes={"node": 2}, backend="cpu",
                jax_version="0.0.t", fingerprint=fp)
    k0 = keys.cache_key(**base)
    assert keys.cache_key(**base) == k0  # pure
    for field, other in [("program", "tail"), ("inputs_digest", "d1"),
                         ("statics", {"k": 8}),
                         ("mesh_axes", {"node": 4}),
                         ("mesh_axes", None), ("backend", "tpu"),
                         ("jax_version", "0.0.u"),
                         ("fingerprint", "b" * 64)]:
        assert keys.cache_key(**dict(base, **{field: other})) != k0, field


def test_callable_statics_key_on_dotted_name_not_repr():
    """A step_fn static must not bust the cache per process: its canon
    form carries the dotted name, never the object address."""
    c1 = keys._canon({"step": service_inputs})
    c2 = keys._canon({"step": service_inputs})
    assert c1 == c2 and "0x" not in c1 and "service_inputs" in c1


def test_abstract_digest_sees_shape_dtype_and_path():
    a = jax.ShapeDtypeStruct((4, 2), np.dtype("float32"))
    b = jax.ShapeDtypeStruct((4, 3), np.dtype("float32"))
    c = jax.ShapeDtypeStruct((4, 2), np.dtype("int32"))
    d0 = keys.abstract_digest({"x": a})
    assert keys.abstract_digest({"x": a}) == d0
    assert keys.abstract_digest({"x": b}) != d0  # shape
    assert keys.abstract_digest({"x": c}) != d0  # dtype
    assert keys.abstract_digest({"y": a}) != d0  # tree path


# --- manifest provenance ---------------------------------------------------

def test_corrupt_manifest_set_aside_and_discarded_loudly(cache_dir):
    os.makedirs(cache_dir)
    cache = CompileCache(fingerprint="a" * 64)
    with open(cache.manifest_path, "w") as f:
        f.write("{torn json")
    cache.activate()
    try:
        assert cache.manifest["entries"] == {}
        assert cache.discarded and "corrupt" in cache.discarded[0][1]
        aside = [p for p in os.listdir(cache_dir) if ".corrupt." in p]
        assert aside, "the torn file must be kept as evidence"
    finally:
        cache.deactivate()


def test_stale_fingerprint_entries_discarded_never_served(cache_dir):
    c1 = CompileCache(fingerprint="a" * 64).activate()
    try:
        assert c1.ensure("prog", lambda: "exe", key="k1") == "miss"
        assert c1.lookup("k1") is not None
    finally:
        c1.deactivate()
    # contract fingerprint moved -> the entry is dropped, loudly
    c2 = CompileCache(fingerprint="b" * 64).activate()
    try:
        assert c2.lookup("k1") is None
        assert c2.manifest["entries"] == {}
        assert any("fingerprint" in reason for _, reason in c2.discarded)
    finally:
        c2.deactivate()
    # same fingerprint -> still trusted
    c3 = CompileCache(fingerprint="a" * 64).activate()
    try:
        assert c3.lookup("k1") is not None and not c3.discarded
    finally:
        c3.deactivate()


def test_jax_version_and_backend_staleness(cache_dir):
    c1 = CompileCache(fingerprint="a" * 64).activate()
    try:
        c1.ensure("prog", lambda: "exe", key="k1")
    finally:
        c1.deactivate()
    with open(os.path.join(cache_dir, "manifest.json")) as f:
        raw = json.load(f)
    raw["entries"]["k1"]["jax_version"] = "0.0.0"
    with open(os.path.join(cache_dir, "manifest.json"), "w") as f:
        json.dump(raw, f)
    c2 = CompileCache(fingerprint="a" * 64).activate()
    try:
        assert c2.lookup("k1") is None
        assert any("jax 0.0.0" in reason for _, reason in c2.discarded)
    finally:
        c2.deactivate()


def test_ensure_memoizes_per_key(cache_dir):
    cache = CompileCache(fingerprint="a" * 64).activate()
    try:
        calls = {"n": 0}

        def build():
            calls["n"] += 1
            return object()

        assert cache.ensure("prog", build, key="k") == "miss"
        assert cache.ensure("prog", build, key="k") == "hit"
        assert calls["n"] == 1
        assert cache.hits == 1 and cache.misses == 1
        assert cache.stats()["entries"] == 1
    finally:
        cache.deactivate()


# --- compile-counter-backed warm-start pins --------------------------------

def test_jax_event_names_still_fire(cache_dir):
    """Pin the jax.monitoring event names counters.py listens on: with
    a cache dir active, a fresh compile fires a persistent-cache MISS;
    the same computation after clear_caches() fires a HIT."""
    cache = CompileCache().activate()
    try:
        x = np.arange(7.0, dtype=np.float32)
        with counters.watch() as w1:
            jax.jit(lambda v: v * 3 + 1)(x).block_until_ready()
        assert w1.cache_misses >= 1 and w1.backend_compiles >= 1
        assert w1.compile_seconds > 0
        jax.clear_caches()
        with counters.watch() as w2:
            jax.jit(lambda v: v * 3 + 1)(x).block_until_ready()
        assert w2.cache_hits >= 1 and w2.cache_misses == 0
    finally:
        cache.deactivate()


def test_precompile_second_run_compiles_nothing(cache_dir):
    """The headline pin: warm the (small) working set cold, then warm
    it again through a FRESH handle after clear_caches() — every
    program must come back from the persistent cache with zero XLA
    compilations."""
    ws = small_ws()
    c1 = CompileCache().activate()
    try:
        r1 = precompile.warm(c1, ws)
        assert r1["programs"] >= 1 and r1["miss"] == r1["programs"]
    finally:
        c1.deactivate()
    jax.clear_caches()
    c2 = CompileCache().activate()
    try:
        with counters.watch() as w:
            r2 = precompile.warm(c2, ws)
        assert r2["programs"] == r1["programs"]
        assert r2["miss"] == 0 and r2["warm"] == r2["programs"]
        assert w.cache_misses == 0, \
            "second warm() run must compile zero programs"
        assert c2.hits == r2["programs"] and c2.misses == 0
    finally:
        c2.deactivate()


def test_warmed_dir_serves_a_one_device_host_batch(cache_dir):
    """warm() compiles the program a one-device service dispatches for
    a batch of host columns, which crosses as one packed buffer: a
    fresh service over the warmed dir finds its cycle program there."""
    ws = small_ws()
    sizes = precompile.full_sizes(ws.sizes)

    def host(name):
        return jax.tree_util.tree_map(
            lambda s: np.ones(s.shape, s.dtype),
            precompile.abstract_struct(name, sizes))

    c1 = CompileCache().activate()
    try:
        r1 = precompile.warm(c1, ws)
        assert r1["programs"] >= 1 and r1["miss"] == r1["programs"]
    finally:
        c1.deactivate()
    jax.clear_caches()
    c2 = CompileCache()
    svc = make_service(c2)
    try:
        svc.publish(host("ClusterSnapshot"))
        svc.schedule(host("PodBatch"))
        m = svc.metrics
        assert m.compile_cache_misses.value() == 0, \
            "the served one-device program must come from the warmed dir"
        assert m.compile_cache_hits.value() == 1
    finally:
        c2.deactivate()


def test_enumerator_covers_the_shrunk_mesh_ladder():
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices (conftest forces 8 on CPU)")
    specs = precompile.enumerate_programs(
        small_ws(devices=2), fingerprint="a" * 64)
    rungs = sorted({s.meta["devices"] for s in specs})
    assert rungs == [1, 2], "device loss must fail over onto an " \
        "already-enumerated rung"
    assert len({s.key for s in specs}) == len(specs), \
        "every (program, rung) keys distinctly"


def test_service_warm_start_and_recovery_compile_nothing(tmp_path,
                                                         cache_dir):
    """End to end: a journaled service scheduling cold populates the
    cache; a restarted service over the same dir schedules AND
    recover()-replays with zero XLA compilations, bit-identical."""
    snap, pods = service_inputs(5)
    jpath = str(tmp_path / "j.bin")

    c1 = CompileCache()
    svc = make_service(c1, journal=CommitJournal(jpath))
    try:
        svc.publish(snap)
        want = np.asarray(svc.schedule(pods).assignment)
        assert c1.misses >= 1  # cold: the cycle program was built
    finally:
        c1.deactivate()

    # "restart": drop every in-process executable, fresh handles
    jax.clear_caches()
    c2 = CompileCache()
    svc2 = make_service(c2, journal=CommitJournal(jpath))
    try:
        svc2.publish(snap)
        rep = svc2.recover({1: pods})
        assert rep["compiled_programs"] == 0, \
            "recovery against a warmed cache must not compile"
        assert rep["replay_seconds"] >= 0 and rep["compile_seconds"] >= 0
        got = np.asarray(rep["results"][1].assignment)
        np.testing.assert_array_equal(got, want)
        assert c2.hits >= 1 and c2.misses == 0
        m = svc2.metrics
        assert m.compile_cache_hits.value() >= 1
        assert m.compile_cache_misses.value() == 0
    finally:
        c2.deactivate()


def test_mesh_shrink_rung_reuses_cached_executable(cache_dir):
    """The failover pin: a service landing on the mesh-shrink rung
    against a dir warmed by a PREVIOUS process run (modeled by
    clear_caches + fresh handles) dispatches the padded/sharded
    program with zero XLA compilations."""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices (conftest forces 8 on CPU)")
    snap, pods = service_inputs(7)

    c1 = CompileCache()
    svc = make_service(c1)
    try:
        svc.ladder.level = DegradationLadder.L_MESH_SHRINK
        svc.publish(snap)
        want = np.asarray(svc.schedule(pods).assignment)
        assert c1.misses >= 1
    finally:
        c1.deactivate()

    jax.clear_caches()
    c2 = CompileCache()
    svc2 = make_service(c2)
    try:
        svc2.ladder.level = DegradationLadder.L_MESH_SHRINK
        svc2.publish(snap)
        with counters.watch() as w:
            got = np.asarray(svc2.schedule(pods).assignment)
        assert w.cache_misses == 0, \
            "the mesh-shrink failover must reuse the cached executable"
        np.testing.assert_array_equal(got, want)
    finally:
        c2.deactivate()


# --- where the cache lives (compilecache.persistent_cache_dir) -----------

@pytest.mark.parametrize("from_env", [False, True])
def test_cache_dir_is_the_environments_else_the_repos(from_env, tmp_path,
                                                      monkeypatch):
    from koordinator_tpu.compilecache import persistent_cache_dir

    if from_env:
        want = str(tmp_path / "env-cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
    assert persistent_cache_dir() == want
    assert CompileCache(fingerprint="a" * 64).path == want


def test_enable_persistent_cache_points_jax_at_it(cache_dir):
    from koordinator_tpu.compilecache import enable_persistent_cache

    assert enable_persistent_cache() == cache_dir
    assert jax.config.jax_compilation_cache_dir == cache_dir
    assert os.path.isdir(cache_dir)


def test_activate_lands_in_the_environments_dir(cache_dir):
    cache = CompileCache(fingerprint="a" * 64).activate()
    try:
        cache.ensure("prog", lambda: "exe", key="k1")
        assert jax.config.jax_compilation_cache_dir == cache_dir
        assert cache.manifest_path == os.path.join(cache_dir,
                                                   "manifest.json")
        assert os.path.exists(cache.manifest_path)
    finally:
        cache.deactivate()


def test_thread_compile_seconds_counts_only_the_compiling_thread():
    counters.install()
    seen = {}

    def compile_fresh():
        before = counters.thread_compile_seconds()
        jax.jit(lambda x: x * 3.0 + 17.0)(
            np.ones(13, np.float32)).block_until_ready()
        seen["delta"] = counters.thread_compile_seconds() - before

    main_before = counters.thread_compile_seconds()
    worker = threading.Thread(target=compile_fresh)
    worker.start()
    worker.join()
    assert seen["delta"] > 0
    assert counters.thread_compile_seconds() == main_before
