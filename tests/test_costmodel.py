"""koordcost static accounting: the shared HLO attribution parser, the
per-program cost reports, and the drift gate's comparison semantics.

Everything here is device-free or compiles tiny throwaway programs —
the full registry walk (every contract + the flagship forms) runs as
the dedicated `tools/costcheck.py` ci.sh stage, and its self-test
mutation proof as another; only the gate's PURE logic (tolerances,
provenance, verdicts) is pinned at test speed.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from koordinator_tpu.obs import costmodel, hloattrib
from koordinator_tpu.obs import phases as obs_phases

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- hloattrib: the one parser both views share -------------------------

SYNTH_HLO = """\
ENTRY %main (p.1: f32[64,32]) -> (f32[64,32], s32[64]) {
  %p.1 = f32[64,32]{1,0} parameter(0)
  %mul.2 = f32[64,32]{1,0} multiply(%p.1, %p.1), metadata={op_name="jit/koord/stage1_mask/mul"}
  %cvt.3 = bf16[64,32]{1,0} convert(%mul.2), metadata={op_name="jit/koord/stage1_mask/koord/topk_select/cvt"}
  %iota.4 = s32[64]{0} iota(), iota_dimension=0
  ROOT %tuple.5 = (f32[64,32]{1,0}, s32[64]{0}) tuple(%mul.2, %iota.4)
}
"""


def test_parse_instructions_bytes_and_innermost_scope():
    instrs = {i.name: i for i in hloattrib.parse_instructions(SYNTH_HLO)}
    # dtype width x element count, layout annotations ignored
    assert instrs["mul.2"].output_bytes == 64 * 32 * 4
    assert instrs["cvt.3"].output_bytes == 64 * 32 * 2
    # tuple result types sum their elements
    assert instrs["tuple.5"].output_bytes == 64 * 32 * 4 + 64 * 4
    # phase resolution: plain scope, no scope, innermost of nested
    assert instrs["mul.2"].phase == obs_phases.PHASE_STAGE1_MASK
    assert instrs["iota.4"].phase == hloattrib.UNATTRIBUTED
    # op_name records the scope PATH; the rightmost koord/ component is
    # the narrowest enclosing phase and must win
    assert instrs["cvt.3"].phase == obs_phases.PHASE_TOPK


def test_attribution_closure_and_coverage_on_synthetic_hlo():
    attribution = hloattrib.attribute_bytes(SYNTH_HLO)
    cov = hloattrib.coverage(attribution)
    # every parsed instruction lands in exactly one bucket
    assert cov["instructions_total"] == 5.0
    assert cov["instructions_mapped"] == 2.0
    assert cov["instruction_coverage"] == pytest.approx(0.4)
    total_b = sum(v["output_bytes"] for v in attribution.values())
    assert cov["output_bytes_total"] == float(total_b)
    # instruction_phases exposes only the mapped set (trace join map)
    mapping = hloattrib.instruction_phases(SYNTH_HLO)
    assert mapping == {"mul.2": obs_phases.PHASE_STAGE1_MASK,
                       "cvt.3": obs_phases.PHASE_TOPK}


def test_phase_of_event_two_step_join():
    instr2phase = {"fusion.9": obs_phases.PHASE_STAGE2_NUMA}
    # exact instruction-name join first (CPU captures)
    assert hloattrib.phase_of_event("fusion.9", [], instr2phase) \
        == obs_phases.PHASE_STAGE2_NUMA
    # scope-substring over args second (TPU-style captures), innermost
    # (longest) phase winning when scopes nest in the path
    hit = hloattrib.phase_of_event(
        "region", ["jit/koord/stage1_mask/koord/stage1_static_gates/x"],
        {})
    assert hit == obs_phases.PHASE_STAGE1_STATIC
    assert hloattrib.phase_of_event("add.1", ["nothing"], {}) is None


def test_trace_fullgate_uses_the_shared_parser():
    """The sampled view must join through obs.hloattrib — a private
    regex reappearing in trace_fullgate is exactly the drift this
    extraction exists to prevent."""
    with open(os.path.join(REPO, "tools", "trace_fullgate.py")) as f:
        src = f.read()
    assert "hloattrib.instruction_phases" in src
    assert "hloattrib.phase_of_event" in src
    assert "re.compile" not in src


# --- program_report on real (tiny) compiled programs --------------------

def _compile(fn, *avals, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*avals).compile()


def test_program_report_closure_on_a_scoped_program():
    def f(x):
        with jax.named_scope(obs_phases.PHASE_STAGE1_MASK):
            y = x * 2.0 + 1.0
        with jax.named_scope(obs_phases.PHASE_TOPK):
            z = jnp.sort(y)
        return y + z

    rep = costmodel.program_report(
        _compile(f, jax.ShapeDtypeStruct((64,), jnp.float32)))
    assert rep["flops"] > 0
    assert rep["bytes_accessed"] > 0
    # the named scopes actually reach op_name metadata
    assert obs_phases.PHASE_STAGE1_MASK in rep["phases"]
    # closure: per-phase attribution sums to the totals over the SAME
    # instruction set, unattributed bucket included
    assert sum(v["instructions"] for v in rep["phases"].values()) \
        == rep["hlo_instructions"]
    assert sum(v["output_bytes"] for v in rep["phases"].values()) \
        == rep["hlo_output_bytes"]
    assert rep["peak_bytes"] == (rep["argument_bytes"]
                                 + rep["output_bytes"]
                                 + rep["temp_bytes"]
                                 - rep["alias_bytes"])


def test_donation_visible_in_memory_analysis():
    """Donated inputs alias into the outputs and must show up as
    alias_bytes shrinking the static peak — the property the tail
    program's baseline entry relies on (buffer reuse is priced, not
    assumed)."""
    rep = costmodel.program_report(
        _compile(lambda x: x + 1.0,
                 jax.ShapeDtypeStruct((1024,), jnp.float32),
                 donate_argnums=0))
    assert rep["alias_bytes"] == 1024 * 4
    assert rep["peak_bytes"] < (rep["argument_bytes"]
                                + rep["output_bytes"]
                                + rep["temp_bytes"])


def test_packing_report_prices_the_bf16_representation():
    """The packed snapshot must be strictly smaller than unpacked, with
    saved = unpacked - packed — this is the exact surface the costcheck
    self-test mutation (bf16 -> f32 upcast) moves."""
    rep = costmodel.packing_report()
    for key in ("packing/snapshot", "packing/pods"):
        entry = rep[key]
        assert entry["packed_bytes"] < entry["unpacked_bytes"]
        assert entry["saved_bytes"] == (entry["unpacked_bytes"]
                                        - entry["packed_bytes"])


# --- costcheck: baseline format, tolerances, verdicts -------------------

def _entry(**over):
    base = {"flops": 1000.0, "bytes_accessed": 500.0,
            "argument_bytes": 100, "output_bytes": 50, "temp_bytes": 30,
            "alias_bytes": 20, "peak_bytes": 160,
            "hlo_instructions": 40, "hlo_output_bytes": 2000}
    base.update(over)
    return base


def test_compare_entry_tolerance_and_exact_fields():
    from tools import costcheck

    # inside the 1% flops tolerance: no drift
    assert costcheck.compare_entry("p", _entry(),
                                   _entry(flops=1005.0)) == []
    # beyond it: drift, named field and magnitude
    drifts = costcheck.compare_entry("p", _entry(),
                                     _entry(flops=1100.0))
    assert len(drifts) == 1 and "flops" in drifts[0]
    # byte-exact fields have zero tolerance
    assert costcheck.compare_entry("p", _entry(),
                                   _entry(output_bytes=51))
    # lost donation gets the explicit callout
    drifts = costcheck.compare_entry(
        "p", _entry(), _entry(alias_bytes=0, peak_bytes=180))
    assert any("donation" in d for d in drifts)


def test_compare_flags_vanished_and_unstamped_programs():
    from tools import costcheck

    manifest = {"entries": {"a": _entry(), "b": _entry()}}
    problems = costcheck.compare(manifest, {"b": _entry(),
                                            "c": _entry()})
    joined = "\n".join(problems)
    assert "a" in joined      # vanished from the build
    assert "c" in joined      # present but not stamped
    assert costcheck.compare(manifest,
                             {"a": _entry(), "b": _entry()}) == []


def test_baseline_is_stamped_for_this_tree():
    """The checked-in manifest must carry the loud provenance triple
    and match the CURRENT contract fingerprint — a contract change
    without a restamp is exactly what the gate strict-fails on."""
    from koordinator_tpu.compilecache import keys
    from tools import costcheck

    with open(costcheck.baseline_path()) as f:
        manifest = json.load(f)
    assert manifest["version"] == costcheck.BASELINE_VERSION
    assert manifest["fingerprint"] == keys.contract_fingerprint()
    assert manifest["jax_version"] == jax.__version__
    assert manifest["entries"]


def test_mutation_anchor_still_present():
    """The self-test mutation plants a bf16 -> f32 upcast at a literal
    anchor in snapshot/packing.py; if the anchor drifts the self-test
    degrades to 'anchor not found' instead of proving anything."""
    from tools import costcheck

    path = os.path.join(REPO, "koordinator_tpu", "snapshot",
                        "packing.py")
    with open(path) as f:
        src = f.read()
    assert costcheck.PACKING_MUTATION_ANCHOR in src
    assert costcheck.PACKING_MUTATION_REPLACEMENT not in src


@pytest.mark.slow
def test_costcheck_packing_gate_passes():
    """Marked slow: tools/ci.sh runs the full costcheck gate as its own
    stage; this is the fast packing-only subset as a subprocess."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "costcheck.py"),
         "--only", "packing/"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
