"""koordlint runner: `python -m tools.lint` — exits non-zero on any
finding not frozen in the baseline file."""

from __future__ import annotations

import argparse
import io
import os
import re
import sys
import tokenize
import weakref
from typing import List, Optional, Sequence, Tuple

import json

from tools.lint.framework import (
    Baseline,
    Finding,
    Project,
    all_analyzers,
    cached_project,
)

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baseline.json")


# `# koordlint: disable=HS006` (or `disable=CODE1,CODE2`, or an analyzer
# name) on the FINDING's own line suppresses it in place. Unlike a
# baseline entry — which freezes pre-existing debt file-wide and is kept
# EMPTY in this repo — an inline marker is a visible, reviewed statement
# at the exact site that the flagged pattern is deliberate (e.g. a
# conformance oracle that reads back on every pass on purpose, the
# pattern the tail-readback analyzer polices everywhere else).
_INLINE_DISABLE_RE = re.compile(r"koordlint:\s*disable=([A-Za-z0-9_,\s-]+)")
# `# koordlint: disable-file=CODE` on a COMMENT line anywhere in the
# file suppresses that code (or analyzer) for the whole file — for
# generated files and conformance oracles where per-line markers would
# have to be repeated at every site. Still named, still reviewed: a
# bare `disable-file=` with no code disables nothing.
_FILE_DISABLE_RE = re.compile(r"koordlint:\s*disable-file=([A-Za-z0-9_,\s-]+)")


def _inline_disabled(project: Project, finding: Finding) -> bool:
    mod = project.by_relpath.get(finding.path)
    if mod is None:
        return False
    if finding.code in _file_disable_tokens(project, finding.path) \
            or finding.analyzer in _file_disable_tokens(project,
                                                        finding.path):
        return True
    if finding.line < 1:
        return False
    lines = mod.source.splitlines()
    if finding.line > len(lines):
        return False
    m = _INLINE_DISABLE_RE.search(lines[finding.line - 1])
    if not m:
        return False
    # split on commas AND whitespace: `disable=HS006 measured oracle`
    # (trailing prose after the code) must still disable HS006 rather
    # than producing an unmatchable space-containing token
    tokens = {t for t in re.split(r"[,\s]+", m.group(1)) if t}
    return finding.code in tokens or finding.analyzer in tokens


# per-Project cache of file-level disable tokens; weak keys so a
# GC'd Project can never alias a recycled id
_FILE_TOKEN_CACHE: "weakref.WeakKeyDictionary[Project, dict]" = \
    weakref.WeakKeyDictionary()


def _file_disable_tokens(project: Project, relpath: str) -> frozenset:
    """Codes/analyzer names disabled file-wide by `disable-file=`
    markers in COMMENTS. Real tokenization, not a line scan: a marker
    quoted inside a (multi-line) string literal — docs describing the
    pragma are the obvious case — must not silence anything."""
    per_file = _FILE_TOKEN_CACHE.setdefault(project, {})
    cached = per_file.get(relpath)
    if cached is not None:
        return cached
    mod = project.by_relpath.get(relpath)
    tokens: set = set()
    if mod is not None:
        try:
            for tok in tokenize.generate_tokens(
                    io.StringIO(mod.source).readline):
                if tok.type != tokenize.COMMENT:
                    continue
                m = _FILE_DISABLE_RE.search(tok.string)
                if m:
                    tokens |= {t for t in re.split(r"[,\s]+",
                                                   m.group(1)) if t}
        except (tokenize.TokenError, IndentationError):
            tokens = set()  # untokenizable: disable nothing
    out = frozenset(tokens)
    per_file[relpath] = out
    return out


def run_lint(root: str = REPO_ROOT,
             analyzers: Optional[Sequence[str]] = None,
             baseline_path: Optional[str] = None,
             ) -> Tuple[List[Finding], List[Finding]]:
    """-> (new findings, baseline-suppressed findings). Parse errors
    count as findings of the framework itself; inline
    `# koordlint: disable=<code>` markers drop findings on their line
    before the baseline split."""
    registry = all_analyzers()
    if analyzers is not None:
        unknown = [a for a in analyzers if a not in registry]
        if unknown:
            raise KeyError(f"unknown analyzers: {unknown}; "
                           f"known: {sorted(registry)}")
        selected = {name: registry[name] for name in analyzers}
    else:
        selected = registry
    # per-process cache: repeat runs (tests invoke run_lint dozens of
    # times) skip the walk+parse when no file's stat signature moved
    project = cached_project(root)
    findings: List[Finding] = list(project.parse_errors)
    for name in sorted(selected):
        findings.extend(selected[name].run(project))
    findings = [f for f in findings if not _inline_disabled(project, f)]
    findings.sort(key=lambda f: (f.path, f.line, f.code))
    baseline = Baseline.load(baseline_path or DEFAULT_BASELINE)
    return baseline.split(findings)


def _github_escape(s: str, properties: bool = False) -> str:
    """Workflow-command escaping: %/\\r/\\n always; , and : inside
    property values (file=..., title=...)."""
    s = s.replace("%", "%25").replace("\r", "%0D").replace("\n", "%0A")
    if properties:
        s = s.replace(",", "%2C").replace(":", "%3A")
    return s


def _github_line(f: Finding) -> str:
    """One `::error` workflow command per finding — the Actions runner
    turns these into inline PR annotations at the flagged line."""
    return (f"::error file={_github_escape(f.path, properties=True)},"
            f"line={f.line},"
            f"title={_github_escape(f.code + ' [' + f.analyzer + ']', properties=True)}"
            f"::{_github_escape(f.message)}")


def _sarif_doc(new: Sequence[Finding],
               suppressed: Sequence[Finding]) -> dict:
    """SARIF 2.1.0 for code-scanning upload. Baseline-suppressed
    findings ride along with a suppression record so dashboards show
    frozen debt without failing the gate."""
    registry = all_analyzers()
    rules: dict = {}
    results = []
    for f, is_suppressed in [(f, False) for f in new] + \
                            [(f, True) for f in suppressed]:
        an = registry.get(f.analyzer)
        rules.setdefault(f.code, {
            "id": f.code,
            "name": f.analyzer,
            "shortDescription": {
                "text": an.description if an else f.analyzer},
        })
        result = {
            "ruleId": f.code,
            "level": "error",
            "message": {"text": f.message},
            "locations": [{
                "physicalLocation": {
                    "artifactLocation": {"uri": f.path},
                    "region": {"startLine": max(f.line, 1)},
                },
            }],
            "partialFingerprints": {"koordlint/v1": f.fingerprint},
        }
        if is_suppressed:
            result["suppressions"] = [{"kind": "external",
                                       "justification": "baseline"}]
        results.append(result)
    return {
        "$schema": "https://raw.githubusercontent.com/oasis-tcs/"
                   "sarif-spec/master/Schemata/sarif-schema-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "koordlint",
                "informationUri":
                    "https://github.com/koordinator-sh/koordinator",
                "rules": sorted(rules.values(),
                                key=lambda r: r["id"]),
            }},
            "results": results,
        }],
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.lint",
        description="koordlint: AST-based hot-path purity & concurrency "
                    "lint for the koordinator_tpu tree")
    parser.add_argument("--root", default=REPO_ROOT,
                        help="tree to analyze (default: repo root)")
    parser.add_argument("--baseline", default=DEFAULT_BASELINE,
                        help="baseline suppression file")
    parser.add_argument("--write-baseline", action="store_true",
                        help="freeze current findings into the baseline "
                             "and exit 0")
    parser.add_argument("--analyzers",
                        help="comma-separated subset to run")
    parser.add_argument("--list", action="store_true",
                        help="list analyzers and exit")
    parser.add_argument("--stamp-protos", action="store_true",
                        help="write/refresh proto content stamps into "
                             "the *_pb2.py files, then exit")
    parser.add_argument("--format", default="text",
                        choices=("text", "sarif", "github"),
                        help="finding output: human text (default), a "
                             "SARIF 2.1.0 document on stdout, or "
                             "GitHub Actions ::error annotations")
    parser.add_argument("-q", "--quiet", action="store_true",
                        help="suppress the per-finding listing")
    args = parser.parse_args(argv)

    if args.list:
        for name, an in sorted(all_analyzers().items()):
            print(f"{name:24s} {an.description}")
        return 0

    if args.stamp_protos:
        from tools.lint.analyzers.proto_drift import stamp_project
        rewritten = stamp_project(Project(args.root))
        for rel in rewritten:
            print(f"stamped {rel}")
        print(f"{len(rewritten)} pb2 file(s) updated")
        return 0

    selected = args.analyzers.split(",") if args.analyzers else None
    new, suppressed = run_lint(args.root, selected, args.baseline)

    if args.write_baseline:
        Baseline(path=args.baseline).save(new + suppressed)
        print(f"baseline: froze {len(new) + len(suppressed)} finding(s) "
              f"into {args.baseline}")
        return 0

    if args.format == "sarif":
        print(json.dumps(_sarif_doc(new, suppressed), indent=2))
        return 1 if new else 0
    if not args.quiet:
        for f in new:
            print(_github_line(f) if args.format == "github"
                  else f.render())
    tally = f"koordlint: {len(new)} finding(s)"
    if suppressed:
        tally += f", {len(suppressed)} suppressed by baseline"
    print(tally)
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
