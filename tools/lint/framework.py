"""Core model for koordlint: findings, the parsed-module Project, the
analyzer plugin registry, and the baseline-suppression file.

Everything here is stdlib-only by design: the linter must run (and fail
CI) on hosts where jax is broken or absent, and must never pay a device
runtime import to analyze source text.
"""

from __future__ import annotations

import ast
import json
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

# directories never scanned: fixture trees hold INTENTIONAL violations,
# and environment/cache dirs hold third-party code the gate must not
# judge (site-packages ships plenty of orphan *_pb2.py)
DEFAULT_EXCLUDES = (
    ".git",
    "__pycache__",
    os.path.join("tests", "fixtures"),
    ".venv",
    "venv",
    ".tox",
    ".eggs",
    "node_modules",
    "site-packages",
    "__pypackages__",
    ".mypy_cache",
    ".pytest_cache",
)


def gitignored(root: str) -> Tuple[str, ...]:
    """The plain paths (no glob patterns) the root's .gitignore lists:
    what git never commits is never judged either."""
    try:
        with open(os.path.join(root, ".gitignore"), encoding="utf-8") as f:
            lines = f.read().splitlines()
    except OSError:
        return ()
    return tuple(line.strip().strip("/") for line in lines
                 if line.strip() and not line.startswith(("#", "!"))
                 and not any(c in line for c in "*?["))


@dataclass(frozen=True)
class Finding:
    """One lint violation.

    `key` is the analyzer-chosen stable identity (symbol names, lock
    pairs, metric names — never raw line numbers), so baseline entries
    survive unrelated edits to the file.
    """

    analyzer: str
    code: str
    path: str          # relative to the project root, "/" separators
    line: int
    message: str
    key: str = ""

    @property
    def fingerprint(self) -> str:
        key = self.key or f"L{self.line}"
        return f"{self.analyzer}:{self.code}:{self.path}:{key}"

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.code} " \
               f"[{self.analyzer}] {self.message}"


@dataclass
class Module:
    """A parsed python source file."""

    path: str        # absolute
    relpath: str     # root-relative, "/" separators
    source: str
    tree: ast.Module

    @property
    def dotted(self) -> str:
        """Dotted module name relative to the project root
        (koordinator_tpu/snapshot/store.py -> koordinator_tpu.snapshot.store)."""
        rel = self.relpath[:-3] if self.relpath.endswith(".py") else self.relpath
        parts = rel.split("/")
        if parts and parts[-1] == "__init__":
            parts = parts[:-1]
        return ".".join(parts)


class Project:
    """The cross-file analysis unit: every parsable .py under `root`
    (minus excludes), indexed by relpath and dotted name, plus the
    non-python files analyzers care about (*.proto)."""

    def __init__(self, root: str,
                 excludes: Sequence[str] = DEFAULT_EXCLUDES):
        self.root = os.path.abspath(root)
        self.modules: List[Module] = []
        self.by_relpath: Dict[str, Module] = {}
        self.by_dotted: Dict[str, Module] = {}
        self.proto_files: List[str] = []   # root-relative
        self.parse_errors: List[Finding] = []
        self._load(tuple(excludes) + gitignored(self.root))

    def _load(self, excludes: Sequence[str]) -> None:
        norm_excludes = tuple(e.replace("/", os.sep) for e in excludes)
        for dirpath, dirnames, filenames in os.walk(self.root):
            rel_dir = os.path.relpath(dirpath, self.root)
            rel_dir = "" if rel_dir == "." else rel_dir
            dirnames[:] = sorted(
                d for d in dirnames
                if not _excluded(os.path.join(rel_dir, d), norm_excludes))
            for fn in sorted(filenames):
                rel = os.path.join(rel_dir, fn) if rel_dir else fn
                if _excluded(rel, norm_excludes):
                    continue
                if fn.endswith(".proto"):
                    self.proto_files.append(rel.replace(os.sep, "/"))
                    continue
                if not fn.endswith(".py"):
                    continue
                abspath = os.path.join(dirpath, fn)
                relpath = rel.replace(os.sep, "/")
                try:
                    with open(abspath, encoding="utf-8") as f:
                        source = f.read()
                    tree = ast.parse(source, filename=abspath)
                except (SyntaxError, UnicodeDecodeError) as exc:
                    self.parse_errors.append(Finding(
                        analyzer="framework", code="KL000", path=relpath,
                        line=getattr(exc, "lineno", 0) or 0,
                        message=f"unparsable source: {exc}",
                        key="parse-error"))
                    continue
                mod = Module(abspath, relpath, source, tree)
                self.modules.append(mod)
                self.by_relpath[relpath] = mod
                self.by_dotted[mod.dotted] = mod

    def read_text(self, relpath: str) -> str:
        with open(os.path.join(self.root, relpath.replace("/", os.sep)),
                  encoding="utf-8") as f:
            return f.read()

    def read_bytes(self, relpath: str) -> bytes:
        with open(os.path.join(self.root, relpath.replace("/", os.sep)),
                  "rb") as f:
            return f.read()


def _excluded(rel: str, norm_excludes: Sequence[str]) -> bool:
    rel = rel.lstrip(os.sep)
    for e in norm_excludes:
        if rel == e or rel.startswith(e + os.sep) \
                or os.path.basename(rel) == e:
            return True
    return False


def _tree_signature(root: str,
                    norm_excludes: Sequence[str]) -> Tuple[tuple, ...]:
    """(relpath, mtime_ns, size) for every analyzable file under root —
    a stat-only walk, no reads, no parses."""
    sig: List[tuple] = []
    for dirpath, dirnames, filenames in os.walk(root):
        rel_dir = os.path.relpath(dirpath, root)
        rel_dir = "" if rel_dir == "." else rel_dir
        dirnames[:] = sorted(
            d for d in dirnames
            if not _excluded(os.path.join(rel_dir, d), norm_excludes))
        for fn in sorted(filenames):
            if not fn.endswith((".py", ".proto")):
                continue
            rel = os.path.join(rel_dir, fn) if rel_dir else fn
            if _excluded(rel, norm_excludes):
                continue
            try:
                st = os.stat(os.path.join(dirpath, fn))
            except OSError:
                sig.append((rel, -1, -1))
                continue
            sig.append((rel, st.st_mtime_ns, st.st_size))
    return tuple(sig)


# (abs root, excludes) -> (tree signature, parsed Project). One entry
# per root a process analyzes; a Project is a few MB of ASTs, so this
# is bounded by the handful of roots tests exercise.
_PROJECT_CACHE: Dict[Tuple[str, Tuple[str, ...]],
                     Tuple[Tuple[tuple, ...], "Project"]] = {}


def cached_project(root: str,
                   excludes: Sequence[str] = DEFAULT_EXCLUDES
                   ) -> "Project":
    """A Project for `root`, reusing this process's parsed tree when no
    analyzable file was added, removed, resized, or touched since the
    last call (per-file mtime_ns + size). Editing a file between runs —
    as the fingerprint-drift tests do — always yields a fresh parse;
    repeat runs over an unchanged tree skip the os.walk + ast.parse
    cost entirely."""
    key = (os.path.abspath(root), tuple(excludes))
    norm_excludes = tuple(e.replace("/", os.sep)
                          for e in key[1] + gitignored(key[0]))
    sig = _tree_signature(key[0], norm_excludes)
    hit = _PROJECT_CACHE.get(key)
    if hit is not None and hit[0] == sig:
        return hit[1]
    project = Project(root, excludes)
    _PROJECT_CACHE[key] = (sig, project)
    return project


class Analyzer:
    """Base class for lint passes. Subclasses set `name`/`description`
    and implement `run(project)` yielding Findings; `register` adds them
    to the plugin registry the runner iterates."""

    name: str = ""
    description: str = ""

    def run(self, project: Project) -> Iterable[Finding]:
        raise NotImplementedError


_REGISTRY: Dict[str, Analyzer] = {}


def register(cls: type) -> type:
    """Class decorator: instantiate and add to the analyzer registry."""
    inst = cls()
    if not inst.name:
        raise ValueError(f"{cls.__name__} has no analyzer name")
    if inst.name in _REGISTRY:
        raise ValueError(f"duplicate analyzer {inst.name!r}")
    _REGISTRY[inst.name] = inst
    return cls


def all_analyzers() -> Dict[str, Analyzer]:
    # import for the registration side effect, late to avoid cycles
    import tools.lint.analyzers  # noqa: F401
    return dict(_REGISTRY)


@dataclass
class Baseline:
    """The suppression file: a sorted list of finding fingerprints. An
    empty baseline means the tree is lint-clean; entries are only meant
    to freeze pre-existing debt, never to excuse new findings."""

    path: str
    fingerprints: Tuple[str, ...] = ()

    @classmethod
    def load(cls, path: str) -> "Baseline":
        if not os.path.exists(path):
            return cls(path=path)
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        if not isinstance(data, dict) or "suppressions" not in data:
            raise ValueError(f"{path}: expected {{'suppressions': [...]}}")
        return cls(path=path, fingerprints=tuple(data["suppressions"]))

    def save(self, findings: Sequence[Finding]) -> None:
        data = {
            "comment": "koordlint baseline: fingerprints of findings "
                       "frozen as pre-existing debt. Keep this empty; "
                       "see docs/DESIGN.md 'Hot-path hygiene rules'.",
            "suppressions": sorted({f.fingerprint for f in findings}),
        }
        with open(self.path, "w", encoding="utf-8") as f:
            json.dump(data, f, indent=2)
            f.write("\n")

    def split(self, findings: Iterable[Finding]
              ) -> Tuple[List[Finding], List[Finding]]:
        """-> (new, suppressed)"""
        known = set(self.fingerprints)
        new: List[Finding] = []
        suppressed: List[Finding] = []
        for f in findings:
            (suppressed if f.fingerprint in known else new).append(f)
        return new, suppressed
