"""koordlint test battery: framework behavior (baseline, runner exit
codes, proto stamping) plus one positive and one negative fixture tree
per analyzer (tests/fixtures/lint/).

The linter is stdlib-only, so everything here runs without touching the
device runtime; the repo-wide gate test shells out exactly the way CI
does (`python -m tools.lint`).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from tools.lint.framework import Baseline, Project
from tools.lint.runner import REPO_ROOT, run_lint

FIXTURES = os.path.join(REPO_ROOT, "tests", "fixtures", "lint")


def fixture_findings(analyzer: str, tree: str, empty_baseline):
    root = os.path.join(FIXTURES, analyzer.replace("-", "_"), tree)
    assert os.path.isdir(root), f"missing fixture tree {root}"
    new, suppressed = run_lint(root, analyzers=[_name(analyzer)],
                               baseline_path=str(empty_baseline))
    assert not suppressed
    return new


_ANALYZER_NAMES = {
    "determinism": "determinism",
    "host_sync": "host-sync-in-jit",
    "recompile": "recompilation-hazard",
    "donation": "donation-aliasing",
    "lock_discipline": "lock-discipline",
    "metric_names": "metric-registry",
    "proto_drift": "proto-drift",
    "race": "race-guard",
    "robustness": "robustness",
    "shape_contract": "shape-contract",
    "tail_readback": "tail-readback",
    "pad_soundness": "pad-soundness",
    "trace_phases": "trace-phases",
}


def _name(fixture_dir: str) -> str:
    return _ANALYZER_NAMES[fixture_dir.replace("-", "_")]


@pytest.fixture()
def empty_baseline(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text('{"suppressions": []}')
    return p


# --- per-analyzer positive/negative cases --------------------------------

@pytest.mark.parametrize("fixture_dir,expected_codes", [
    ("host_sync", {"HS001", "HS002", "HS003", "HS004", "HS005"}),
    ("recompile", {"RC001", "RC002", "RC003", "RC004", "RC005"}),
    ("donation", {"DA001"}),
    ("lock_discipline", {"LK001", "LK002", "LK003", "LK004", "LK005"}),
    ("metric_names", {"MN001", "MN002", "MN003", "MN004"}),
    ("proto_drift", {"PD001", "PD002", "PD003"}),
    ("race", {"GB001", "GB002", "GB003", "GB004", "GB005"}),
    ("robustness", {"RB001"}),
    ("shape_contract", {"SH001", "SH002", "SH003", "SH004", "SH005"}),
    ("tail_readback", {"HS006"}),
    ("pad_soundness", {"PS001", "PS002", "PS003", "PS004", "PS005"}),
    ("determinism", {"ND001"}),
    ("trace_phases", {"OB001"}),
])
def test_positive_fixture(fixture_dir, expected_codes, empty_baseline):
    findings = fixture_findings(fixture_dir, "pos", empty_baseline)
    got = {f.code for f in findings}
    assert expected_codes <= got, (
        f"{fixture_dir}/pos: expected codes {sorted(expected_codes)}, "
        f"got {sorted(got)}: {[f.render() for f in findings]}")


@pytest.mark.parametrize("fixture_dir", sorted(_ANALYZER_NAMES))
def test_negative_fixture(fixture_dir, empty_baseline):
    findings = fixture_findings(fixture_dir, "neg", empty_baseline)
    assert findings == [], \
        f"{fixture_dir}/neg should be clean: " \
        f"{[f.render() for f in findings]}"


# --- targeted analyzer behavior ------------------------------------------

def test_host_sync_reports_deep_callee_site(empty_baseline):
    findings = fixture_findings("host_sync", "pos", empty_baseline)
    items = [f for f in findings if f.code == "HS001"]
    assert items and all("deep" in f.key for f in items), \
        "the .item() sink sits two calls below the entry and must be " \
        "attributed to the function that contains it"


_TAIL_LOOP_SRC = (
    "import numpy as np\n"
    "\n"
    "def adaptive(step, snap, stats, budget):\n"
    "    left = 1\n"
    "    passes = 0\n"
    "    while passes < budget and left > 0:\n"
    "        snap, stats = retry_pass(step, snap)\n"
    "        left = int(np.asarray(stats)[0]){marker}\n"
    "        passes += 1\n"
    "    return snap\n"
    "\n"
    "def retry_pass(step, snap):\n"
    "    return step(snap)\n")


def test_tail_readback_inline_disable(tmp_path, empty_baseline):
    """`# koordlint: disable=HS006` on the finding's line suppresses it
    in place (a deliberate per-pass readback, as in a conformance
    oracle, relies on this);
    the analyzer name works as the token too, and the marker only
    covers its OWN line."""
    (tmp_path / "m.py").write_text(_TAIL_LOOP_SRC.format(marker=""))
    new, _ = run_lint(str(tmp_path), analyzers=["tail-readback"],
                      baseline_path=str(empty_baseline))
    assert [f.code for f in new] == ["HS006"], [f.render() for f in new]

    for token in ("HS006", "tail-readback",
                  # trailing prose after the code must not defeat the
                  # marker (tokens split on whitespace AND commas)
                  "HS006 measured oracle"):
        (tmp_path / "m.py").write_text(_TAIL_LOOP_SRC.format(
            marker=f"  # koordlint: disable={token}"))
        new, suppressed = run_lint(str(tmp_path),
                                   analyzers=["tail-readback"],
                                   baseline_path=str(empty_baseline))
        assert new == [] and suppressed == [], \
            (token, [f.render() for f in new])

    # a marker on an UNRELATED line must not suppress the finding
    (tmp_path / "m.py").write_text(
        "# koordlint: disable=HS006\n" + _TAIL_LOOP_SRC.format(marker=""))
    new, _ = run_lint(str(tmp_path), analyzers=["tail-readback"],
                      baseline_path=str(empty_baseline))
    assert [f.code for f in new] == ["HS006"]


def test_disable_file_pragma_fixtures(empty_baseline):
    """`# koordlint: disable-file=CODE` on a comment line silences that
    code file-wide (neg tree); a marker naming a DIFFERENT code, or one
    hiding inside a string literal, silences nothing (pos tree)."""
    root = os.path.join(FIXTURES, "disable_file", "pos")
    new, _ = run_lint(root, analyzers=["tail-readback"],
                      baseline_path=str(empty_baseline))
    assert [f.code for f in new] == ["HS006"], \
        [f.render() for f in new]
    root = os.path.join(FIXTURES, "disable_file", "neg")
    new, suppressed = run_lint(root, analyzers=["tail-readback"],
                               baseline_path=str(empty_baseline))
    assert new == [] and suppressed == [], [f.render() for f in new]


def test_disable_file_accepts_analyzer_name(tmp_path, empty_baseline):
    """The analyzer name works as a file-level token too, from any
    comment line in the file (not just line 1)."""
    (tmp_path / "m.py").write_text(
        _TAIL_LOOP_SRC.format(marker="")
        + "\n# koordlint: disable-file=tail-readback\n")
    new, _ = run_lint(str(tmp_path), analyzers=["tail-readback"],
                      baseline_path=str(empty_baseline))
    assert new == [], [f.render() for f in new]


def test_tail_readback_ignores_plain_data_walks(tmp_path,
                                                empty_baseline):
    """np.asarray in a loop with no retry/tail vocabulary anywhere is
    an ordinary data walk, not the flagged bug class."""
    (tmp_path / "m.py").write_text(
        "import numpy as np\n"
        "\n"
        "def column_sums(rows):\n"
        "    out = []\n"
        "    for r in rows:\n"
        "        out.append(np.asarray(r).sum())\n"
        "    return out\n")
    new, _ = run_lint(str(tmp_path), analyzers=["tail-readback"],
                      baseline_path=str(empty_baseline))
    assert new == [], [f.render() for f in new]


def test_donation_loop_wraparound(empty_baseline):
    findings = fixture_findings("donation", "pos", empty_baseline)
    lines = {f.line for f in findings}
    assert len(findings) >= 2 and len(lines) >= 2, \
        "both the straight-line read and the loop re-donation must fire"


def test_donation_assignment_form_tracks_the_alias(tmp_path,
                                                   empty_baseline):
    """g = jax.jit(f, donate_argnums=...): donation belongs to calls
    through g; direct f(...) calls are plain and must not be flagged."""
    (tmp_path / "m.py").write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "\n"
        "def sweep(state):\n"
        "    return state + 1\n"
        "\n"
        "sweep_d = jax.jit(sweep, donate_argnums=(0,))\n"
        "\n"
        "def plain(state):\n"
        "    out = sweep(state)\n"
        "    return out, jnp.sum(state)\n"   # fine: sweep doesn't donate
        "\n"
        "def donating(state):\n"
        "    out = sweep_d(state)\n"
        "    return out, jnp.sum(state)\n")  # DA001: read after donation
    new, _ = run_lint(str(tmp_path), analyzers=["donation-aliasing"],
                      baseline_path=str(empty_baseline))
    assert len(new) == 1 and "donating" in new[0].key, \
        [f.render() for f in new]


def test_donation_read_after_loop_exit(tmp_path, empty_baseline):
    """A rebind at loop top saves the next iteration but not the
    post-loop read of the LAST iteration's donated buffer."""
    (tmp_path / "m.py").write_text(
        "import functools\n"
        "import jax\n"
        "\n"
        "@functools.partial(jax.jit, donate_argnums=(0,))\n"
        "def step(state):\n"
        "    return state + 1\n"
        "\n"
        "def drive(batches, state):\n"
        "    for b in batches:\n"
        "        state = prep(b)\n"
        "        out = step(state)\n"
        "    return state\n"                # DA001: donated on loop exit
        "\n"
        "def prep(b):\n"
        "    return b\n")
    new, _ = run_lint(str(tmp_path), analyzers=["donation-aliasing"],
                      baseline_path=str(empty_baseline))
    assert len(new) == 1 and new[0].code == "DA001", \
        [f.render() for f in new]


def test_lock_cycle_reported_once(empty_baseline):
    findings = fixture_findings("lock_discipline", "pos", empty_baseline)
    cycles = [f for f in findings if f.code == "LK001"]
    assert len(cycles) == 1, [f.render() for f in cycles]
    assert "_a" in cycles[0].message and "_b" in cycles[0].message


def test_metric_duplicate_names_resolved_through_constants(empty_baseline):
    findings = fixture_findings("metric_names", "pos", empty_baseline)
    dups = [f for f in findings if f.code == "MN001"]
    assert len(dups) == 1 and "comp_good_total" in dups[0].message


# --- framework: baseline, fingerprints, runner ---------------------------

def test_baseline_suppresses_known_findings(tmp_path, empty_baseline):
    root = os.path.join(FIXTURES, "donation", "pos")
    new, _ = run_lint(root, analyzers=["donation-aliasing"],
                      baseline_path=str(empty_baseline))
    assert new
    bl = tmp_path / "frozen.json"
    Baseline(path=str(bl)).save(new)
    new2, suppressed = run_lint(root, analyzers=["donation-aliasing"],
                                baseline_path=str(bl))
    assert new2 == [] and len(suppressed) == len(new)


@pytest.mark.parametrize("fixture_dir", sorted(_ANALYZER_NAMES))
def test_fingerprints_stable_under_line_drift(fixture_dir, tmp_path,
                                              empty_baseline):
    """Every analyzer's fingerprints must survive unrelated line drift,
    or baselined findings resurface as CI-failing 'new' ones."""
    src = os.path.join(FIXTURES, fixture_dir, "pos")
    root = tmp_path / "tree"
    shutil.copytree(src, root)
    before, _ = run_lint(str(root), analyzers=[_name(fixture_dir)],
                         baseline_path=str(empty_baseline))
    for py in sorted(root.rglob("*.py")):
        py.write_text("# padding comment\n" * 7 + py.read_text())
    after, _ = run_lint(str(root), analyzers=[_name(fixture_dir)],
                        baseline_path=str(empty_baseline))
    assert {f.fingerprint for f in before} == \
        {f.fingerprint for f in after}, \
        "baseline fingerprints must not embed line numbers"


def test_parse_error_is_a_finding(tmp_path, empty_baseline):
    (tmp_path / "bad.py").write_text("def broken(:\n")
    new, _ = run_lint(str(tmp_path), analyzers=["proto-drift"],
                      baseline_path=str(empty_baseline))
    assert any(f.code == "KL000" for f in new)


def test_unknown_analyzer_rejected(empty_baseline):
    with pytest.raises(KeyError):
        run_lint(FIXTURES, analyzers=["no-such-pass"],
                 baseline_path=str(empty_baseline))


def test_fixture_trees_excluded_from_default_scan():
    project = Project(REPO_ROOT)
    assert not any(m.relpath.startswith("tests/fixtures/")
                   for m in project.modules), \
        "fixture violations must never count against the repo"


# --- the CI gate itself --------------------------------------------------

def _run_cli(*args, cwd=REPO_ROOT):
    return subprocess.run(
        [sys.executable, "-m", "tools.lint", *args],
        cwd=cwd, capture_output=True, text=True, timeout=600)


def test_cli_green_on_repo_with_empty_baseline():
    baseline = os.path.join(REPO_ROOT, "tools", "lint", "baseline.json")
    with open(baseline) as f:
        assert json.load(f)["suppressions"] == [], \
            "the lint must stay green with an EMPTY baseline"
    proc = _run_cli("-q")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_red_on_introduced_violation(tmp_path):
    root = tmp_path / "tree"
    shutil.copytree(os.path.join(FIXTURES, "host_sync", "pos"), root)
    bl = tmp_path / "b.json"
    bl.write_text('{"suppressions": []}')
    proc = _run_cli("--root", str(root), "--baseline", str(bl))
    assert proc.returncode == 1
    assert "HS00" in proc.stdout


def test_cli_stamp_protos_roundtrip(tmp_path):
    root = tmp_path / "tree"
    root.mkdir()
    proto = root / "x.proto"
    proto.write_text('syntax = "proto3";\nmessage X {}\n')
    pb2 = root / "x_pb2.py"
    pb2.write_text("# source: x.proto\nX = None\n")
    bl = tmp_path / "b.json"
    bl.write_text('{"suppressions": []}')
    proc = _run_cli("--root", str(root), "--baseline", str(bl),
                    "--analyzers", "proto-drift")
    assert proc.returncode == 1 and "PD001" in proc.stdout
    stamp = _run_cli("--root", str(root), "--stamp-protos")
    assert stamp.returncode == 0 and "x_pb2.py" in stamp.stdout
    proc2 = _run_cli("--root", str(root), "--baseline", str(bl),
                     "--analyzers", "proto-drift")
    assert proc2.returncode == 0, proc2.stdout
    # drift the proto: the stale stamp must fail again
    proto.write_text('syntax = "proto3";\nmessage X { bool ok = 1; }\n')
    proc3 = _run_cli("--root", str(root), "--baseline", str(bl),
                     "--analyzers", "proto-drift")
    assert proc3.returncode == 1 and "PD002" in proc3.stdout


def test_repo_pb2_stamps_current():
    """The checked-in pb2 stamps must match their protos (the in-repo
    instance of the proto-drift invariant)."""
    new, suppressed = run_lint(
        REPO_ROOT, analyzers=["proto-drift"],
        baseline_path=os.path.join(REPO_ROOT, "tools", "lint",
                                   "baseline.json"))
    assert new == [] and suppressed == [], \
        [f.render() for f in new + suppressed]


def test_project_skips_what_gitignore_lists(tmp_path):
    (tmp_path / ".gitignore").write_text("# scratch\nout/\n*.pyc\n")
    (tmp_path / "kept.py").write_text("x = 1\n")
    (tmp_path / "out").mkdir()
    (tmp_path / "out" / "copy.py").write_text("x = (\n")
    project = Project(str(tmp_path))
    assert [m.relpath for m in project.modules] == ["kept.py"]
    assert not project.parse_errors
