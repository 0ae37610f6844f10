"""koordcost: the registry-walking static cost accountant.

Where koordtrace answers "where did this cycle's wall-clock go", this
module answers "where do its FLOPs, bytes, and HBM go" — without a
device, before any hardware run. Every program the scheduler can
dispatch is already named: the koordshape contract registry
(snapshot/schema.SHAPE_CONTRACTS) names every contracted kernel, and
the compilecache enumerator (compilecache/precompile.py) names the
flagship cycle per cascade form plus the donated tail. This module
lowers each one at a fixed proxy working set and reads XLA's own
accounting off the compiled executable:

  * `compiled.cost_analysis()` — flops and bytes accessed;
  * `compiled.memory_analysis()` — argument/output/temp bytes and the
    donation-aliased bytes (a lost `donate_argnums` shows up here as
    alias_size collapsing to zero);
  * per-phase attribution of instructions and output bytes by parsing
    `op_name="...koord/<phase>/..."` metadata through the SHARED
    parser (obs/hloattrib.py) — the same join the sampled-time view
    (tools/trace_fullgate.py) uses, so the two can never drift.

The bf16 columnar packing layer (snapshot/packing.py) has no kernel of
its own, but its packed representation IS a byte contract: the model
prices the packed snapshot/pod footprint through `jax.eval_shape` over
the real pack functions, so an accidental bf16->f32 upcast doubles a
baseline number instead of silently doubling checkpoint and transfer
volume (tools/costcheck.py's planted-mutation smoke proves exactly
that path).

Everything here is static and deterministic for a fixed
(jax version, backend, contract fingerprint) — which is what makes the
checked-in perf/COST_BASELINE.json a meaningful drift gate.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

from koordinator_tpu.obs import hloattrib

__all__ = [
    "COST_SIZES", "CostProgram", "enumerate_cost_programs",
    "program_report", "packing_report", "collect",
]

# the proxy working set the checked-in baseline is stamped at: small
# enough that the full walk lowers in well under a CI minute, large
# enough that every axis is distinct and the cascade/tail forms are
# non-degenerate (TC < P so tail windows really gather)
COST_SIZES = {"P": 64, "N": 32, "TC": 16}

# baseline fields taken from XLA's analyses, in report order
MEMORY_FIELDS = ("argument_bytes", "output_bytes", "temp_bytes",
                 "alias_bytes", "peak_bytes")


@dataclass(frozen=True)
class CostProgram:
    """One program the cost model prices: a stable label (the baseline
    key) and a thunk returning the compiled executable."""

    label: str
    build: Callable[[], Any]
    kind: str  # "contract" | "cycle" | "tail"


def _first_computation(analysis) -> Dict[str, float]:
    """cost_analysis() returns one properties dict per computation on
    newer jax (a bare dict on older); the entry computation leads."""
    if isinstance(analysis, (list, tuple)):
        return dict(analysis[0]) if analysis else {}
    return dict(analysis or {})


def enumerate_cost_programs(sizes: Optional[Dict[str, int]] = None,
                            statics: Optional[Dict[str, Any]] = None
                            ) -> List[CostProgram]:
    """Every contracted kernel (the full SHAPE_CONTRACTS registry,
    abstract inputs built by the precompile enumerator's registry walk)
    plus the flagship cycle per cascade form and the donated tail (the
    compilecache enumerator verbatim, so donation aliasing is priced
    exactly as the warm path compiles it)."""
    import importlib

    import jax

    from koordinator_tpu.compilecache import precompile
    from tools.shapecheck import CONTRACT_MODULES  # registry imports

    for mod in CONTRACT_MODULES:
        importlib.import_module(mod)
    from koordinator_tpu.snapshot.schema import SHAPE_CONTRACTS

    sizes = dict(COST_SIZES if sizes is None else sizes)
    full = precompile.full_sizes(sizes)
    programs: List[CostProgram] = []
    for key in sorted(SHAPE_CONTRACTS):
        contract = SHAPE_CONTRACTS[key]
        kwargs = {}
        for name, raw in contract.args.items():
            v = precompile.abstract_value(raw, full)
            if v is precompile._SKIP:
                continue
            kwargs[name] = v
        static_kwargs: Dict[str, Any] = {}
        for name, value in contract.static.items():
            if isinstance(value, str) and value in full:
                value = full[value]
            static_kwargs[name] = value
        for name, dotted in contract.callables.items():
            static_kwargs[name] = SHAPE_CONTRACTS[dotted].fn
        fn = functools.partial(contract.fn, **static_kwargs) \
            if static_kwargs else contract.fn

        def build(fn=fn, kwargs=kwargs):
            return jax.jit(fn).lower(**kwargs).compile()

        short = key[len("koordinator_tpu."):] \
            if key.startswith("koordinator_tpu.") else key
        programs.append(CostProgram(label=f"contract/{short}",
                                    build=build, kind="contract"))
    # the flagship forms, through the SAME enumerator the AOT warmer
    # walks — donate_argnums survives only on this path (jax.jit of an
    # already-jitted fn re-wraps without donation)
    ws = precompile.WorkSet(sizes=sizes,
                            statics=dict(precompile.DEFAULT_STATICS,
                                         **(statics or {})),
                            devices=1)
    for spec in precompile.enumerate_programs(ws):
        programs.append(CostProgram(
            label=f"flagship/{spec.label}", build=spec.build,
            kind=spec.meta.get("form", "cycle")))
    return programs


def program_report(compiled) -> Dict[str, Any]:
    """The per-program cost record: XLA's flops/bytes/memory accounting
    plus the shared-parser per-phase attribution. `phases` sums to the
    hlo_* totals by construction (hloattrib closure property)."""
    cost = _first_computation(compiled.cost_analysis())
    mem = compiled.memory_analysis()
    arg = int(mem.argument_size_in_bytes)
    out = int(mem.output_size_in_bytes)
    tmp = int(mem.temp_size_in_bytes)
    alias = int(mem.alias_size_in_bytes)
    attribution = hloattrib.attribute_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "argument_bytes": arg,
        "output_bytes": out,
        "temp_bytes": tmp,
        "alias_bytes": alias,
        # the static peak proxy: everything resident at once, minus
        # what donation aliases into the outputs
        "peak_bytes": arg + out + tmp - alias,
        "hlo_instructions": sum(v["instructions"]
                                for v in attribution.values()),
        "hlo_output_bytes": sum(v["output_bytes"]
                                for v in attribution.values()),
        "phases": {phase: dict(v)
                   for phase, v in sorted(attribution.items())},
    }


def packing_report(sizes: Optional[Dict[str, int]] = None
                   ) -> Dict[str, Dict[str, int]]:
    """The packed-representation byte contract, priced through the REAL
    pack functions under jax.eval_shape (abstract: no device values).
    Routing through snapshot/packing.py is the point — a planted or
    accidental f32 upcast in its packable path moves `packed_bytes`
    here, which is what tools/costcheck.py's mutation smoke pins."""
    import jax

    from koordinator_tpu.compilecache import precompile
    from koordinator_tpu.snapshot import packing

    full = precompile.full_sizes(
        dict(COST_SIZES if sizes is None else sizes))

    def tree_bytes(tree) -> int:
        return int(sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree_util.tree_leaves(tree)))

    out: Dict[str, Dict[str, int]] = {}
    for label, struct, pack in (
            ("packing/snapshot", "ClusterSnapshot", packing.pack_snapshot),
            ("packing/pods", "PodBatch", packing.pack_pods)):
        plain = precompile.abstract_struct(struct, full)
        packed = jax.eval_shape(pack, plain)
        pb, ub = tree_bytes(packed), tree_bytes(plain)
        out[label] = {"packed_bytes": pb, "unpacked_bytes": ub,
                      "saved_bytes": ub - pb}
    return out


def collect(sizes: Optional[Dict[str, int]] = None,
            statics: Optional[Dict[str, Any]] = None,
            log_fn: Optional[Callable[[str], None]] = None
            ) -> Dict[str, Dict[str, Any]]:
    """The full cost model at one working set: {label: report} over
    every contracted kernel, the flagship forms, and the packing byte
    contract. This is what `tools/costcheck.py --stamp` freezes into
    perf/COST_BASELINE.json and what the gate recomputes."""
    entries: Dict[str, Dict[str, Any]] = {}
    for prog in enumerate_cost_programs(sizes, statics):
        report = program_report(prog.build())
        report["kind"] = prog.kind
        entries[prog.label] = report
        if log_fn is not None:
            log_fn(f"costmodel: {prog.label} "
                   f"flops={report['flops']:.0f} "
                   f"bytes={report['bytes_accessed']:.0f} "
                   f"peak={report['peak_bytes']}")
    for label, report in packing_report(sizes).items():
        entries[label] = dict(report, kind="packing")
        if log_fn is not None:
            log_fn(f"costmodel: {label} "
                   f"packed={report['packed_bytes']} "
                   f"saved={report['saved_bytes']}")
    return entries
