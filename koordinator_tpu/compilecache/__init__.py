"""Contract-keyed AOT compile cache (docs/DESIGN.md "Compile cache &
columnar packing").

The koordshape contract registry already names every entry point's
shapes, dtypes and pad semantics, so the scheduler's program set is
enumerable ahead of time: `precompile` walks STRUCT_SPECS + the
contract table, materializes ShapeDtypeStruct pytrees for a configured
working set (including shrunk-mesh variants and the cascade/tail
program forms), and pre-lowers them through `CompileCache` — a manifest
layer over JAX's persistent compilation cache keyed by (contract hash,
mesh axes, jax version, backend). `counters` exposes the JAX
compilation-cache telemetry the warm-start pins assert on.

Nothing here activates on import. The entry points (chip_smoke.py,
cmd/scheduler.main) call `enable_persistent_cache`, which
keeps the cache where JAX_COMPILATION_CACHE_DIR says or else in the
fixed `<repo>/.jax_cache`; the test suite never does (XLA:CPU artifacts
deserialized on a different machine can segfault — see
tests/conftest.py).
"""

from koordinator_tpu.compilecache.cache import (  # noqa: F401
    CompileCache,
    enable_persistent_cache,
    persistent_cache_dir,
)
from koordinator_tpu.compilecache.counters import (  # noqa: F401
    CompileWatcher,
)
from koordinator_tpu.compilecache.keys import (  # noqa: F401
    abstract_digest,
    cache_key,
    contract_fingerprint,
)
