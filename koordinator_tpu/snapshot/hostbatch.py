"""One-buffer host upload of a pod batch.

A `PodBatch` built on the host (the informer and sidecar builders, a
benchmark backlog) is 35 numpy columns. Handed to a jitted program as
is, each column is its own host->device transfer, and every transfer
pays a fixed cost before the program can start. `pack` copies the
columns into ONE fresh uint32 buffer, so the batch crosses to the chip
in one transfer; `unpack`, traced inside the served program, takes the
columns back apart on the chip with static slices, reshapes and
bitcasts. No value changes: the unpacked columns are the host columns
bit for bit (bool columns travel as 0/1 bytes and come back as `!= 0`).

The layout is static and hashable (a tuple of per-field tuples), carried
as pytree aux data of `HostBatch`, so a jitted program specializes on it
exactly as it specializes on a `PodBatch`'s shapes: one layout per batch
shape in steady-state traffic. The fields come from the registered
`PodBatch` field table (`schema.STRUCT_SPECS`): a new `PodBatch` field
is packed with no edit here, and a pytree field the table lacks fails
loudly.

Every field starts at a word boundary; a 1- or 2-byte column is carried
in ceil(bytes / 4) words and its tail bytes are zero. A fresh buffer is
built per call on purpose: `device_put` may alias host memory (the CPU
backend does), and a reused staging buffer would rewrite a batch that
is still in flight.

Not to be confused with `snapshot/packing.py`, the bf16 precision
packing of score surfaces, which does change values.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu.snapshot.schema import PodBatch, STRUCT_SPECS

Array = Any

# one field: (name, numpy dtype string, shape, word offset, byte length)
FieldLayout = Tuple[str, str, Tuple[int, ...], int, int]


@flax.struct.dataclass
class HostBatch:
    """A `PodBatch` as one uint32 buffer plus its static layout."""

    words: Array            # u32[W] every column, byte-packed
    layout: Tuple[FieldLayout, ...] = flax.struct.field(
        pytree_node=False, default=())
    # the PodBatch's static (pytree_node=False) fields, as (name, value)
    statics: Tuple[Tuple[str, Any], ...] = flax.struct.field(
        pytree_node=False, default=())


def _table_fields() -> Tuple[str, ...]:
    """The array fields of the registered PodBatch table, in order (bare
    dim symbols such as `num_pods` are properties, not fields)."""
    return tuple(name for name, spec in STRUCT_SPECS["PodBatch"].items()
                 if isinstance(spec, str) and "[" in spec)


def _static_fields() -> Tuple[str, ...]:
    return tuple(f.name for f in dataclasses.fields(PodBatch)
                 if f.metadata.get("pytree_node", True) is False)


def _check_table(fields: Tuple[str, ...]) -> None:
    statics = set(_static_fields())
    declared = {f.name for f in dataclasses.fields(PodBatch)} - statics
    missing = sorted(declared - set(fields))
    unknown = sorted(set(fields) - declared)
    if missing or unknown:
        raise ValueError(
            f"PodBatch fields {missing} are missing from the registered "
            f"field table and {unknown} are not PodBatch fields: register "
            f"every array field in snapshot/schema.py before it can be "
            f"uploaded")


def host_columns(pods: PodBatch) -> bool:
    """Whether every array column of `pods` is a host numpy array (the
    batches the builders and the benchmark hand to `schedule()`)."""
    return all(isinstance(getattr(pods, name), np.ndarray)
               for name in _table_fields())


def _wire_dtype(dtype) -> np.dtype:
    """A column's dtype as the device receives it: 64-bit host dtypes
    narrowed the way jax canonicalizes them on upload."""
    dtype = np.dtype(jax.dtypes.canonicalize_dtype(np.dtype(dtype)))
    if dtype.itemsize not in (1, 2, 4):
        raise ValueError(f"cannot pack a {dtype} column")
    return dtype


@functools.lru_cache(maxsize=64)
def _layout(fields: Tuple[str, ...],
            columns: Tuple[Tuple[str, Tuple[int, ...], int], ...]
            ) -> Tuple[Tuple[FieldLayout, ...], int]:
    """(layout, total words) of one batch signature: per field its
    (dtype string, shape, byte length). Steady-state traffic has one."""
    _check_table(fields)
    layout, offset = [], 0
    for name, (dtype_str, shape, nbytes) in zip(fields, columns):
        layout.append((name, dtype_str, shape, offset, nbytes))
        offset += -(-nbytes // 4)
    return tuple(layout), offset


def _signature(fields, cols) -> Tuple[Tuple[FieldLayout, ...], int]:
    """The layout of columns that carry a `.dtype` and a `.shape`
    (host arrays, or ShapeDtypeStructs)."""
    sig = []
    for col in cols:
        dtype = _wire_dtype(col.dtype)
        shape = tuple(int(d) for d in col.shape)
        sig.append((dtype.str, shape,
                    int(np.prod(shape, dtype=np.int64)) * dtype.itemsize))
    return _layout(fields, tuple(sig))


def _statics(pods: PodBatch) -> Tuple[Tuple[str, Any], ...]:
    return tuple((name, getattr(pods, name)) for name in _static_fields())


def pack(pods: PodBatch) -> HostBatch:
    """Copy every column of a host `PodBatch` into one new uint32
    buffer; -> a `HostBatch` ready for a single `jax.device_put`."""
    fields = _table_fields()
    cols = [np.asarray(getattr(pods, name)) for name in fields]
    layout, n_words = _signature(fields, cols)
    words = np.empty(n_words, np.uint32)
    raw = words.view(np.uint8)
    for (_, dtype_str, _, off, nbytes), col in zip(layout, cols):
        start = off * 4
        col = np.ascontiguousarray(col, np.dtype(dtype_str))
        raw[start:start + nbytes] = col.reshape(-1).view(np.uint8)
        raw[start + nbytes:start + -(-nbytes // 4) * 4] = 0
    return HostBatch(words=words, layout=layout, statics=_statics(pods))


def abstract(pods: PodBatch) -> HostBatch:
    """The `HostBatch` that `pack` makes of a batch with these shapes,
    its buffer a ShapeDtypeStruct (`pods` may be abstract itself): what
    an ahead-of-time lowering of the served program takes."""
    fields = _table_fields()
    layout, n_words = _signature(
        fields, [getattr(pods, name) for name in fields])
    return HostBatch(words=jax.ShapeDtypeStruct((n_words,), np.uint32),
                     layout=layout, statics=_statics(pods))


def _unpack_field(seg: jnp.ndarray, dtype_str: str,
                  shape: Tuple[int, ...], nbytes: int) -> jnp.ndarray:
    """One column from its own words."""
    dtype = np.dtype(dtype_str)
    if dtype.itemsize == 4:
        col = jax.lax.bitcast_convert_type(seg, dtype)
    else:
        # u32[w] -> u8[w, 4], little-endian byte order, then the
        # column's own bytes without the word's zero tail
        raw = jax.lax.bitcast_convert_type(seg, jnp.uint8).reshape(-1)
        raw = raw[:nbytes]
        if dtype == np.bool_:
            col = raw != 0
        elif dtype.itemsize == 1:
            col = jax.lax.bitcast_convert_type(raw, dtype)
        else:
            col = jax.lax.bitcast_convert_type(
                raw.reshape(-1, dtype.itemsize), dtype)
    return col.reshape(shape)


def unpack(pods) -> PodBatch:
    """The `PodBatch` a `HostBatch` carries, rebuilt from its buffer
    (traced inside the served program); a `PodBatch` passes through."""
    if not isinstance(pods, HostBatch):
        return pods
    # each column's words are cut out of the buffer behind a barrier
    # before any bitcast or reshape: left free to fuse the cuts of one
    # shared buffer with the reshapes, compiling the full-gate batch's
    # 35 columns for a v5e took 32 s (0.5 s with the barrier)
    segs = jax.lax.optimization_barrier(tuple(
        pods.words[offset:offset + -(-nbytes // 4)]
        for _, _, _, offset, nbytes in pods.layout))
    cols: Dict[str, jnp.ndarray] = {
        name: _unpack_field(seg, dtype_str, shape, nbytes)
        for seg, (name, dtype_str, shape, _, nbytes) in zip(segs,
                                                            pods.layout)}
    return PodBatch(**cols, **dict(pods.statics))
