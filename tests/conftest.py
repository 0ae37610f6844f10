"""Test harness: pin the platform *before* jax imports — an 8-device
virtual CPU mesh, always.

Multi-chip hardware is not available in CI; sharding tests run on a virtual
8-device CPU mesh (the driver separately dry-runs `__graft_entry__.
dryrun_multichip`). Mirrors the reference's hermetic strategy (SURVEY.md 4):
no cluster needed — fake state layers stand in for kernel/apiserver. The
chip is reached through `chip_smoke.py` only; tests/test_chip_compile.py
compiles for a described v5e without one.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# The env var alone is not enough on hosts whose site config pins
# jax_platforms; force it explicitly.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

# NO persistent compilation cache. It was enabled through round 3
# (/tmp/koord_tpu_jax_cache) and cut warm suite time to ~4 min, but the
# CI hosts live-migrate/resize between runs (observed mid-round-4:
# nproc and XLA's machine-feature probe changed), and XLA:CPU AOT
# artifacts deserialized on a different machine than the one that wrote
# them SEGFAULT the test process (jax compilation_cache
# get_executable_and_time) — even a CPU-feature-fingerprint-keyed dir
# was not sufficient. In-process compiles are always safe; paying the
# cold compile per run is the only configuration that cannot crash.
jax.config.update("jax_compilation_cache_dir", None)


def pytest_configure(config):
    # the tier-1 battery (ROADMAP.md / tools/ci.sh) runs -m 'not slow';
    # register the mark so --strict-markers stays an option and no
    # UnknownMarkWarning fires
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 battery; the equivalent check "
        "runs as a dedicated tools/ci.sh stage on every push")
