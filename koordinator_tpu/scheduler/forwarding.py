"""The served cycle program without its pass-through outputs.

A jitted program that returns one of its inputs unchanged still gives
that output a fresh buffer and a device copy on every call: an output
may share a parameter's buffer only if the parameter is donated, and
the published snapshot cannot be donated (readers of the store, the
degradation ladder's retry and the caller of `schedule()` may all
still hold it). The snapshot columns the scheduling program reads and
never writes, and the zero-element pools of a configuration compiled
without reservations or devices, are more than half of its outputs.

`call(fn, args, statics)` runs `fn`'s served form, a program that
returns only the leaves `fn` computes, and rebuilds `fn`'s own output
pytree on the host: a forwarded leaf IS the input's `jax.Array` (same
buffer, same placement), a zero-element leaf is an empty array made
once per signature. Which leaves are which is read from the traced
program (`forwarded_inputs`), never from a list, so a plugin that
starts writing a column gets it computed again with no change here.
The reading happens while jit traces the served form, so jit's own
cache, keyed by the input signature (treedef, shapes, dtypes,
shardings) and the statics, holds it: a warm call pays one flatten of
its inputs and the rebuild.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.extend import core as jex_core

__all__ = ["Plan", "forwarded_inputs", "lower", "call"]


def forwarded_inputs(jaxpr) -> List[Optional[int]]:
    """For each output of `jaxpr`, the index of the input it returns
    unchanged, or None. Resolved through nested `jit` equations, where
    a nested program's forwarded output names the outer variable it was
    called with."""
    alias: Dict[Any, Any] = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "jit":
            continue
        inner = forwarded_inputs(eqn.params["jaxpr"].jaxpr)
        for out, j in zip(eqn.outvars, inner):
            src = None if j is None else eqn.invars[j]
            if isinstance(src, jex_core.Var):
                alias[out] = alias.get(src, src)
    index = {v: i for i, v in enumerate(jaxpr.invars)}
    return [index.get(alias.get(v, v)) if isinstance(v, jex_core.Var)
            else None for v in jaxpr.outvars]


@dataclasses.dataclass(frozen=True)
class Plan:
    """How one signature's full output is rebuilt: the output treedef
    and, per output leaf, ("computed", k) for the served program's k-th
    output, ("input", j) for the j-th input leaf, or ("empty", shape,
    dtype). `empties` holds the zero-element arrays made for it, by
    (placement, output leaf)."""

    tree: Any
    sources: Tuple[tuple, ...]
    empties: Dict[tuple, jax.Array] = dataclasses.field(
        default_factory=dict, compare=False, hash=False, repr=False)

    @property
    def forwarded(self) -> int:
        return sum(src[0] != "computed" for src in self.sources)

    @property
    def outputs(self) -> int:
        return len(self.sources) - self.forwarded


@dataclasses.dataclass
class _Computed:
    """What the served program returns: its computed leaves, with the
    plan as static pytree data (so jit hands the plan back with every
    call)."""

    leaves: List[jax.Array]
    plan: Plan


jax.tree_util.register_pytree_node(
    _Computed, lambda c: (c.leaves, c.plan),
    lambda plan, leaves: _Computed(list(leaves), plan))


@functools.lru_cache(maxsize=None)
def _served(fn):
    """`fn`'s served form, jitted under `fn`'s own name (the device
    trace shows it as the same program): `_served(fn)(args, statics)`
    -> `_Computed`, `statics` being `fn`'s static keywords as sorted
    (name, value) pairs."""

    def program(args, statics):
        kwargs = dict(statics)
        closed = fn.trace(*args, **kwargs).jaxpr
        leaves, tree = jax.tree_util.tree_flatten(fn(*args, **kwargs))
        sources, keep = [], []
        for leaf, j, aval in zip(leaves, forwarded_inputs(closed.jaxpr),
                                 closed.out_avals):
            if j is not None:
                sources.append(("input", j))
            elif aval.size == 0:
                sources.append(("empty", aval.shape, aval.dtype))
            else:
                sources.append(("computed", len(keep)))
                keep.append(leaf)
        return _Computed(keep, Plan(tree, tuple(sources)))

    program.__name__ = program.__qualname__ = fn.__name__
    return jax.jit(program, static_argnames=("statics",))


def _statics_key(statics: Dict[str, Any]) -> tuple:
    return tuple(sorted(statics.items()))


def lower(fn, args: tuple, statics: Dict[str, Any]):
    """Lower `fn`'s served form for `args` (concrete or abstract): what
    a warmer compiles so that `call` finds it."""
    return _served(fn).lower(args, statics=_statics_key(statics))


def _replicated(ref: jax.Array):
    """Where a leaf the program did not return would have been put:
    the computed outputs' devices, replicated; None for uncommitted
    outputs (the default device, uncommitted, as jit leaves them)."""
    if not ref.committed:
        return None
    sharding = ref.sharding
    if isinstance(sharding, jax.sharding.NamedSharding):
        return jax.sharding.NamedSharding(sharding.mesh,
                                          jax.sharding.PartitionSpec())
    return sharding


def call(fn, args: tuple, statics: Dict[str, Any]):
    """`fn(*args, **statics)`, bit-identical leaf for leaf, through its
    served form. Returns `(output, plan)`."""
    computed = _served(fn)(args, _statics_key(statics))
    plan = computed.plan
    flat = jax.tree_util.tree_leaves(args)
    place = _replicated(computed.leaves[0])
    out = []
    for i, src in enumerate(plan.sources):
        kind = src[0]
        if kind == "computed":
            out.append(computed.leaves[src[1]])
        elif kind == "input":
            leaf = flat[src[1]]
            out.append(leaf if isinstance(leaf, jax.Array)
                       else jax.device_put(leaf, place))
        else:
            empty = plan.empties.get((place, i))
            if empty is None:
                empty = plan.empties[place, i] = jax.device_put(
                    np.zeros(src[1], src[2]), place)
            out.append(empty)
    return plan.tree.unflatten(out), plan
