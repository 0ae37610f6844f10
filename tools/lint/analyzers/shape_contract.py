"""shape-contract: the koordshape static tier — kernel shape/dtype
contracts checked against the code, stdlib-only.

Every jitted entry point declares its tensor contract via the
`@shape_contract` decorator registry (koordinator_tpu/snapshot/schema.py);
this pass reads the declarations straight from the AST
(tools/lint/shapes/contracts.py), binds each contracted function's
parameters to their declared symbolic dims, and abstractly interprets
the body (tools/lint/shapes/abstract.py). The dynamic twin —
tools/shapecheck.py — drives jax.eval_shape over the same registry in
CI; this pass is the half that needs no jax at all.

Codes:
  SH001  dim-symbol mismatch: two distinct contract dims forced equal
         by a broadcast / concatenate / matmul contraction /
         take_along_axis, or a return value disagreeing with the
         function's own declared dims
  SH002  undeclared broadcast: implicit rank growth between non-scalar
         operands — add [None] / jnp.broadcast_to so promoted axes are
         visible in the code
  SH003  cross-kernel contract drift: an argument passed to another
         CONTRACTED kernel disagreeing with the callee's declared spec,
         or one struct registered twice with different field tables
  SH004  a module-level jax.jit entry point with no @shape_contract
         (test trees exempt; nested jit closures in drivers exempt)
  SH005  malformed contract declaration: unparsable spec, undeclared
         dim symbol, or a spec for a parameter the function lacks
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Tuple, Union

from tools.lint.astutil import dotted_name
from tools.lint.callgraph import (
    FunctionInfo,
    ModuleIndex,
    ProjectIndex,
    project_index,
)
from tools.lint.framework import Analyzer, Finding, Project, register
from tools.lint.shapes.abstract import (
    Defect,
    IntVal,
    ScalarVal,
    ShapeInterp,
    Val,
)
from tools.lint.shapes.contracts import (
    AstContract,
    ContractIndex,
    extract_contracts,
)

_DEFECT_CODE = {"conflict": "SH001", "rank_growth": "SH002",
                "cross": "SH003"}


@register
class ShapeContractAnalyzer(Analyzer):
    name = "shape-contract"
    description = ("kernel shape/dtype contracts: declared-dim abstract "
                   "interpretation, cross-kernel drift, uncontracted "
                   "jit entry points")

    def run(self, project: Project) -> Iterable[Finding]:
        pidx = project_index(project)
        cindex = extract_contracts(project)
        consts = _ConstTable(project, pidx)
        findings: List[Finding] = []

        for p in cindex.problems:
            findings.append(Finding(
                analyzer=self.name, code="SH005", path=p.relpath,
                line=p.line, message=p.message, key=p.key))
        for p in cindex.struct_drift:
            findings.append(Finding(
                analyzer=self.name, code="SH003", path=p.relpath,
                line=p.line, message=p.message, key=p.key))

        findings.extend(self._uncontracted_jits(pidx, cindex))

        for (rel, _), contract in sorted(cindex.contracts.items()):
            mi = pidx.modules.get(rel)
            if mi is None:
                continue
            findings.extend(self._interpret(pidx, mi, cindex, consts,
                                            contract))
        return sorted(findings, key=lambda f: (f.path, f.line, f.code))

    # --- SH004 -----------------------------------------------------------

    def _uncontracted_jits(self, pidx: ProjectIndex,
                           cindex: ContractIndex) -> Iterable[Finding]:
        for entry in pidx.jit_entries():
            info = entry.fn
            rel = info.module.relpath
            if rel.startswith("tests/"):
                continue  # test helpers aren't kernel entry points
            if not isinstance(info.scope_chain[-1], ast.Module):
                continue  # nested driver closures
            if cindex.contract_for(rel, info.node.name) is not None:
                continue
            yield Finding(
                analyzer=self.name, code="SH004", path=rel,
                line=entry.decorator_line,
                message=f"jitted entry point `{info.qualname}` has no "
                        f"@shape_contract: declare its dims/dtypes in "
                        f"the schema registry so koordshape (both "
                        f"tiers) can police it",
                key=f"{info.qualname}:no-contract")

    # --- the abstract interpretation per contract ------------------------

    def _interpret(self, pidx: ProjectIndex, mi: ModuleIndex,
                   cindex: ContractIndex, consts: "_ConstTable",
                   contract: AstContract) -> Iterable[Finding]:
        info = None
        for fi in mi.functions:
            if fi.node is contract.fn_node:
                info = fi
                break
        if info is None:
            return []
        scope = info.scope_chain + (info.node,)

        def resolve_contract(call: ast.Call) -> Optional[AstContract]:
            target = pidx.resolve_call(mi, scope, call)
            if target is None:
                return None
            c = cindex.contract_for(target.module.relpath,
                                    target.node.name)
            # a contract never cross-checks against itself (recursion)
            if c is contract:
                return None
            return c

        interp = ShapeInterp(
            contract,
            resolve_dotted=mi.resolve_dotted,
            resolve_const=consts.resolve,
            resolve_contract=resolve_contract,
            struct_field=lambda s, f: cindex.structs.get(s, {}).get(f),
        )
        out: List[Finding] = []
        for d in interp.run():
            out.append(Finding(
                analyzer=self.name, code=_DEFECT_CODE[d.kind],
                path=contract.relpath, line=d.line,
                message=f"`{contract.name}`: {d.detail}", key=d.key))
        return out


class _ConstTable:
    """module-level numeric constants, resolvable as
    'pkg.module.NAME' — EPS, MAX_NODE_SCORE, POLICY_NONE and friends.
    `NAME = int(...)`/`len(...)` records a scalar of unknown value, so
    resource-kind column indices still drop axes cleanly."""

    _SCALAR_CALLS = {"int", "len", "float"}

    def __init__(self, project: Project, pidx: ProjectIndex):
        self._by_module: Dict[str, Dict[str, Val]] = {}
        for m in project.modules:
            table: Dict[str, Val] = {}
            for node in m.tree.body:
                if not isinstance(node, ast.Assign):
                    continue
                val = self._const_of(node.value)
                if val is None:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        table[t.id] = val
            self._by_module[m.dotted] = table

    def _const_of(self, node: ast.AST) -> Optional[Val]:
        if isinstance(node, ast.Constant) \
                and isinstance(node.value, (int, float)) \
                and not isinstance(node.value, bool):
            if isinstance(node.value, int):
                return IntVal(node.value)
            return ScalarVal()
        if isinstance(node, ast.UnaryOp) \
                and isinstance(node.op, ast.USub):
            inner = self._const_of(node.operand)
            if isinstance(inner, IntVal):
                return IntVal(-inner.dim)
            return inner
        if isinstance(node, ast.Call):
            fn = dotted_name(node.func)
            if fn in self._SCALAR_CALLS:
                return ScalarVal()
        return None

    def resolve(self, resolved: str) -> Optional[Val]:
        mod, _, name = resolved.rpartition(".")
        if not mod:
            return None
        table = self._by_module.get(mod)
        if table is None:
            return None
        return table.get(name)
