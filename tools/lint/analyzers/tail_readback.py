"""tail-readback: flag blocking host syncs inside retry/tail loops on
the host side of the jit boundary.

The bug class: an adaptive straggler/retry loop that reads a device
value back EVERY iteration (`np.asarray(stats)`, `.item()`,
`jax.device_get`, `block_until_ready`). Each blocking transfer pays a
full device round trip, so a 10-pass tail pays 10 of them — the exact
pattern the device-resident compaction loop
(scheduler/core.tail_compaction_loop) replaced. This analyzer keeps
it out: a host sync is fine BEFORE or AFTER such a
loop (the single stats readback), never per-iteration inside one.

Heuristic scope (syntactic, per-module): a `while`/`for` statement
counts as a retry/tail loop when the pattern ``tail|retry|straggl``
(case-insensitive) matches the enclosing function's name, a name read
in the loop condition/iterator, or a callee name inside the loop body.
Loops outside that vocabulary — ordinary data walks that materialize
arrays — are never flagged; a DELIBERATE per-pass readback (a
conformance oracle) carries an inline ``# koordlint: disable=HS006``
marker.

Code:
  HS006  blocking host sync inside a retry/tail loop
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Tuple

from tools.lint.astutil import call_target
from tools.lint.callgraph import project_index
from tools.lint.framework import Analyzer, Finding, Module, Project, register

# vocabulary words must start at a name-segment boundary (start of the
# identifier or after a non-letter such as '_'), so `details`,
# `retailer` or `curtailed` never classify an innocent loop; snake_case
# is the repo convention, so segment starts are what we anchor on
TAIL_NAME_RE = re.compile(r"(?:^|[^A-Za-z])(?:tail|retry|straggl)",
                          re.IGNORECASE)
NUMPY_SINKS = {"numpy.asarray", "numpy.array"}
JAX_SINKS = {"jax.device_get", "jax.block_until_ready"}


def _names_under(node: ast.AST) -> Iterable[str]:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            yield sub.id
        elif isinstance(sub, ast.Attribute):
            yield sub.attr


def _is_tail_loop(loop: ast.AST, func_names: Tuple[str, ...]) -> bool:
    """The loop vocabulary check (see module docstring)."""
    if any(TAIL_NAME_RE.search(n) for n in func_names):
        return True
    header = [loop.test] if isinstance(loop, ast.While) \
        else [loop.target, loop.iter]
    for node in header:
        if any(TAIL_NAME_RE.search(n) for n in _names_under(node)):
            return True
    for sub in ast.walk(loop):
        if isinstance(sub, ast.Call):
            target = call_target(sub)
            if target and TAIL_NAME_RE.search(target):
                return True
    return False


@register
class TailReadbackAnalyzer(Analyzer):
    name = "tail-readback"
    description = ("blocking host sync (np.asarray, .item(), device_get, "
                   "block_until_ready) inside a retry/tail loop — the "
                   "per-pass readback pattern the device-resident tail "
                   "compaction loop deletes")

    def run(self, project: Project) -> Iterable[Finding]:
        index = project_index(project)
        findings: Dict[Tuple[str, int, str], Finding] = {}
        for mod in project.modules:
            mi = index.index_of(mod)
            self._walk(mod.tree, mod, mi, (), findings)
        return sorted(findings.values(),
                      key=lambda f: (f.path, f.line, f.code))

    def _walk(self, node: ast.AST, mod: Module, mi,
              func_names: Tuple[str, ...], findings) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._walk(child, mod, mi, func_names + (child.name,),
                           findings)
            elif isinstance(child, (ast.While, ast.For)):
                if _is_tail_loop(child, func_names):
                    self._flag_sinks(child, mod, mi, func_names, findings)
                else:
                    # nested loops/functions may still qualify
                    self._walk(child, mod, mi, func_names, findings)
            else:
                self._walk(child, mod, mi, func_names, findings)

    def _flag_sinks(self, loop: ast.AST, mod: Module, mi,
                    func_names: Tuple[str, ...], findings) -> None:
        qual = ".".join(func_names) or "<module>"
        for sub in ast.walk(loop):
            if not isinstance(sub, ast.Call):
                continue
            sink = self._sink_name(sub, mi)
            if sink is None:
                continue
            f = Finding(
                analyzer=self.name, code="HS006", path=mod.relpath,
                line=sub.lineno,
                message=(f"`{sink}` inside a retry/tail loop of `{qual}` "
                         f"blocks on a device->host transfer EVERY pass; "
                         f"keep the loop device-resident "
                         f"(core.tail_compaction_loop) and read stats back "
                         f"once after it — or mark a deliberate oracle "
                         f"with `# koordlint: disable=HS006`"),
                key=f"{qual}:{sink}")
            findings.setdefault((f.path, f.line, f.code), f)

    @staticmethod
    def _sink_name(call: ast.Call, mi) -> Optional[str]:
        if isinstance(call.func, ast.Attribute):
            if call.func.attr == "item" and not call.args:
                return ".item()"
            if call.func.attr == "block_until_ready":
                return "block_until_ready"
        dotted = call_target(call)
        resolved = mi.resolve_dotted(dotted) if dotted else ""
        if resolved in NUMPY_SINKS or resolved in JAX_SINKS:
            return dotted
        return None
