"""CompileCache: a manifest layer over JAX's persistent compilation
cache.

JAX's persistent cache already keys serialized XLA executables on the
computation itself (HLO + compile options + backend fingerprint) — a
correct but OPAQUE store: nothing in it says which scheduler program a
blob belongs to, which contract revision produced it, or whether a
spec edit stranded it. The manifest adds that provenance: one JSON
entry per (program, working-set point) cache key
(keys.cache_key: contract fingerprint x abstract inputs x statics x
mesh axes x jax version x backend), so

  - a contract/spec change invalidates exactly the affected entries
    (every entry whose recorded fingerprint no longer matches), loudly;
  - a jax upgrade or backend switch drops the whole entry set, loudly;
  - a corrupt manifest is set aside and rebuilt, loudly — a cache that
    cannot prove provenance serves nothing.

The underlying XLA blobs are left to JAX's own store either way: a
dropped manifest entry merely costs a re-lower (the persistent cache
then usually still hits on the unchanged HLO); a WRONG manifest entry
would claim warmth the contracts no longer back.

Where the cache lives is decided in ONE place, `persistent_cache_dir`:
JAX_COMPILATION_CACHE_DIR when the environment sets it, else the fixed
`<repo>/.jax_cache/` (gitignored). The directory is part of what a
cached entry is found under, so it is never built from a temporary
name, a pid or the time. `enable_persistent_cache` points the process
at it (the entry points call it), and `CompileCache.activate` goes
through it; nothing takes a directory of its own. XLA:CPU
artifacts deserialized on a different machine can segfault
(live-migrating CI hosts — see tests/conftest.py), which is why the
test suite never enables any of this.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Any, Callable, Dict, List, Optional

from koordinator_tpu.compilecache import counters, keys

log = logging.getLogger(__name__)

MANIFEST_NAME = "manifest.json"
MANIFEST_VERSION = 1

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def persistent_cache_dir() -> str:
    """The persistent compilation cache directory for this process:
    JAX_COMPILATION_CACHE_DIR when set, else `<repo>/.jax_cache`."""
    return os.environ.get(CACHE_DIR_ENV) or REPO_CACHE_DIR


def enable_persistent_cache() -> str:
    """Point JAX's persistent compilation cache at
    `persistent_cache_dir()` and return the path. Called by the entry
    points (chip_smoke.py, cmd/scheduler.main) before their
    first compile."""
    import jax

    path = persistent_cache_dir()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # JAX latches the persistent cache at the FIRST compile of the
    # process: if anything compiled before this call (even a bare jnp
    # op building a snapshot), the dir change above is silently ignored
    # forever. Reset so the next compile re-initializes against it.
    _reset_jax_persistent_cache()
    return path


def _reset_jax_persistent_cache() -> None:
    """Drop JAX's once-per-process persistent-cache singleton so the
    next compile re-reads jax_compilation_cache_dir. Private API, so
    absence is tolerated — the cost is only that a pre-activate compile
    pins the old dir (warmth degrades, correctness doesn't)."""
    try:
        from jax._src import compilation_cache as jax_cc
        jax_cc.reset_cache()
    except Exception:  # pragma: no cover - jax internals moved
        log.warning("compilecache: could not reset jax persistent-cache "
                    "singleton; pre-activate compiles may pin a stale dir",
                    exc_info=True)


class CompileCache:
    """An opt-in, same-host compile cache handle.

    `activate()` points JAX's persistent compilation cache at
    `persistent_cache_dir()`, kept as `path` (clamping the min-compile-time/min-entry-size thresholds so even
    small CPU test programs persist) and loads the manifest. `ensure()`
    runs an AOT build (lower+compile) exactly once per cache key —
    in-memory memo first, then the persistent cache absorbs the XLA
    compile — and records the entry. `hits`/`misses` mirror onto the
    scheduler metrics when a catalog is attached.
    """

    def __init__(self, fingerprint: Optional[str] = None) -> None:
        self.path = persistent_cache_dir()
        self.fingerprint = (fingerprint if fingerprint is not None
                            else keys.contract_fingerprint())
        self.active = False
        self.hits = 0
        self.misses = 0
        # provenance of every loudly-dropped entry/file: (key-or-path,
        # reason) — tests pin that invalid state lands HERE, never in
        # `manifest["entries"]`
        self.discarded: List[tuple] = []
        self._programs: Dict[str, Any] = {}
        self.manifest: Dict[str, Any] = self._fresh_manifest()

    # --- manifest ---------------------------------------------------------

    def _fresh_manifest(self) -> Dict[str, Any]:
        import jax

        return {
            "version": MANIFEST_VERSION,
            "fingerprint": self.fingerprint,
            "jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "entries": {},
        }

    @property
    def manifest_path(self) -> str:
        return os.path.join(self.path, MANIFEST_NAME)

    def _discard(self, what: str, reason: str) -> None:
        self.discarded.append((what, reason))
        log.warning("compilecache: discarding %s: %s", what, reason)

    def _load_manifest(self) -> None:
        import jax

        fresh = self._fresh_manifest()
        try:
            with open(self.manifest_path) as f:
                raw = json.load(f)
        except FileNotFoundError:
            self.manifest = fresh
            return
        except (json.JSONDecodeError, OSError, UnicodeDecodeError) as exc:
            # corrupt: set the file aside (post-mortem evidence) and
            # rebuild — NEVER serve an entry whose provenance is
            # unreadable
            aside = self.manifest_path + f".corrupt.{os.getpid()}"
            try:
                os.replace(self.manifest_path, aside)
            except OSError:
                aside = "<unrenameable>"
            self._discard(self.manifest_path,
                          f"corrupt manifest ({exc!r}); moved to {aside}, "
                          "rebuilding empty")
            self.manifest = fresh
            return
        if not isinstance(raw, dict) or \
                raw.get("version") != MANIFEST_VERSION or \
                not isinstance(raw.get("entries"), dict):
            self._discard(self.manifest_path,
                          "unrecognized manifest schema; rebuilding empty")
            self.manifest = fresh
            return
        kept: Dict[str, Any] = {}
        for key, entry in raw["entries"].items():
            if not isinstance(entry, dict):
                self._discard(key, "malformed entry (not a mapping)")
                continue
            stale = []
            if entry.get("fingerprint") != self.fingerprint:
                stale.append("contract fingerprint changed")
            if entry.get("jax_version") != jax.__version__:
                stale.append(f"jax {entry.get('jax_version')} -> "
                             f"{jax.__version__}")
            if entry.get("backend") != jax.default_backend():
                stale.append(f"backend {entry.get('backend')} -> "
                             f"{jax.default_backend()}")
            if stale:
                self._discard(key, "stale entry (" + "; ".join(stale) + ")")
                continue
            kept[key] = entry
        self.manifest = dict(fresh, entries=kept)

    def _save_manifest(self) -> None:
        # atomic publish: a crash mid-write must leave either the old
        # manifest or the new one, never a torn file (the corrupt path
        # above exists for external corruption, not our own writes)
        tmp = self.manifest_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.manifest, f, indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.manifest_path)

    # --- lifecycle --------------------------------------------------------

    def activate(self) -> "CompileCache":
        """Point the process at the persistent cache
        (`enable_persistent_cache`) and load the manifest there.
        Idempotent."""
        if self.active:
            return self
        import jax

        self.path = enable_persistent_cache()
        # persist EVERYTHING: the scheduler's small CPU-test programs
        # compile in well under the default 1s threshold, and a warmer
        # that silently skips them pins nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        counters.install()
        self._load_manifest()
        self.active = True
        return self

    def deactivate(self) -> None:
        """Detach the process-global persistent cache (tests; the
        on-disk state stays for the next activate())."""
        if not self.active:
            return
        import jax

        jax.config.update("jax_compilation_cache_dir", None)
        _reset_jax_persistent_cache()
        self.active = False

    # --- the warm path ----------------------------------------------------

    def lookup(self, key: str) -> Optional[dict]:
        """The manifest entry for `key`, or None. Only entries that
        survived provenance validation at load time exist here — a
        stale/corrupt entry can never be returned."""
        return self.manifest["entries"].get(key)

    def ensure(self, program: str, build: Callable[[], Any], *,
               key: str, meta: Optional[dict] = None) -> str:
        """Make `program`'s executable warm for this working-set point.

        Returns the outcome:
          "hit"  — already ensured this process (in-memory memo);
          "warm" — built, but the XLA compile was absorbed by the
                   persistent cache (cache_misses == 0 with hits);
          "miss" — built with at least one real XLA compilation.
        "hit"/"warm" count as cache hits, "miss" as a miss.
        """
        if key in self._programs:
            self.hits += 1
            return "hit"
        import jax

        t0 = time.perf_counter()
        with counters.watch() as w:
            exe = build()
        elapsed = time.perf_counter() - t0
        if self.active and w.cache_misses == 0 and w.cache_hits > 0:
            status = "warm"
            self.hits += 1
        else:
            status = "miss"
            self.misses += 1
        self._programs[key] = exe
        self.manifest["entries"][key] = {
            "program": program,
            "fingerprint": self.fingerprint,
            "jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "status": status,
            "ensure_seconds": round(elapsed, 4),
            "compile_seconds": round(w.compile_seconds, 4),
            **(meta or {}),
        }
        if self.active:
            self._save_manifest()
        return status

    def stats(self) -> dict:
        return {
            "path": self.path,
            "active": self.active,
            "hits": self.hits,
            "misses": self.misses,
            "entries": len(self.manifest["entries"]),
            "discarded": len(self.discarded),
            "fingerprint": self.fingerprint[:16],
        }
