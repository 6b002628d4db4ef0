"""Pallas TPU chunked tier-copy kernels (the Harvest data movers).

``harvest_gather`` pulls a batch of KV blocks / expert shards out of a
source pool into a dense staging buffer, chunk by chunk.  The slot list is
a scalar-prefetch operand, so the BlockSpec index_map chases it exactly
like the runtime's reload plan — this is the TPU analogue of the batched
cudaMemcpyPeerAsync the paper issues on a reload, and Pallas's grid
pipeline gives the double-buffering (copy chunk i+1 while chunk i lands)
for free.

``harvest_copy`` is the fused gather→scatter: one kernel moves slots from
a source pool straight into destination pool slots, skipping the dense
staging round-trip entirely — the output aliases the destination pool, so
untouched slots are preserved and only the copied blocks' chunks are
written.  This is the kernel the runtime's coalesced reload plan models:
one submission, one setup, per-slot completion as the grid walks the
batch.

Non-divisible block sizes are handled by padding the trailing chunk
(gather/copy) instead of asserting; out-of-range slot ids raise instead of
silently dropping writes.

Grids: (num_blocks_to_copy, chunks_per_block).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _copy_kernel(ids_ref, src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def _fused_copy_kernel(src_ids_ref, dst_ids_ref, src_ref, dst_in_ref,
                       dst_ref):
    dst_ref[...] = src_ref[...]


def _check_slot_ids(slot_ids, n_slots: int, what: str) -> None:
    """Eagerly reject out-of-range slot ids (a scatter that silently drops
    a reload's payload is a data-loss bug, not a masking convenience).
    Traced ids (inside an outer jit) cannot be validated here — the jit'd
    wrappers in ops.py validate before tracing."""
    if isinstance(slot_ids, jax.core.Tracer):
        return
    ids = np.asarray(slot_ids)
    if ids.size and (ids.min() < 0 or ids.max() >= n_slots):
        bad = ids[(ids < 0) | (ids >= n_slots)]
        raise IndexError(
            f"{what}: slot ids {bad.tolist()} out of range for a pool of "
            f"{n_slots} slots — refusing to drop the writes")


def _chunking(elems: int, chunk: int):
    """(clamped chunk, padded elems, n_chunks): non-divisible block sizes
    are padded up to a whole trailing chunk instead of crashing."""
    chunk = max(1, min(chunk, elems))
    pad = (-elems) % chunk
    return chunk, elems + pad, (elems + pad) // chunk


def harvest_gather(src_pool, slot_ids, *, chunk: int = 512,
                   interpret: Optional[bool] = None):
    """src_pool: (n_slots, block_elems); slot_ids: (m,) int32
    -> (m, block_elems) staging buffer."""
    n_slots, elems = src_pool.shape
    _check_slot_ids(slot_ids, n_slots, "harvest_gather")
    m = slot_ids.shape[0]
    chunk, padded, n_chunks = _chunking(elems, chunk)
    if padded != elems:
        src_pool = jnp.pad(src_pool, ((0, 0), (0, padded - elems)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, n_chunks),
        in_specs=[
            pl.BlockSpec((None, chunk), lambda i, j, ids: (ids[i], j)),
        ],
        out_specs=pl.BlockSpec((None, chunk), lambda i, j, ids: (i, j)),
    )
    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, padded), src_pool.dtype),
        interpret=interpret_mode(interpret),
    )(slot_ids.astype(jnp.int32), src_pool)
    return out[:, :elems] if padded != elems else out


def harvest_copy(src_pool, dst_pool, src_ids, dst_ids, *, chunk: int = 512,
                 interpret: Optional[bool] = None):
    """Fused gather→scatter: dst_pool[dst_ids[i]] <- src_pool[src_ids[i]].

    One pallas_call, no dense staging buffer: the source BlockSpec chases
    ``src_ids`` while the output BlockSpec chases ``dst_ids``, and the
    output aliases ``dst_pool`` so every slot outside the copy set is
    preserved.  Returns the updated destination pool.
    """
    n_src, elems = src_pool.shape
    n_dst, elems_d = dst_pool.shape
    assert elems == elems_d, \
        f"pool block sizes differ: src {elems} vs dst {elems_d}"
    assert src_ids.shape == dst_ids.shape, \
        f"id list shapes differ: {src_ids.shape} vs {dst_ids.shape}"
    _check_slot_ids(src_ids, n_src, "harvest_copy(src)")
    _check_slot_ids(dst_ids, n_dst, "harvest_copy(dst)")
    m = src_ids.shape[0]
    chunk, padded, n_chunks = _chunking(elems, chunk)
    if padded != elems:
        src_pool = jnp.pad(src_pool, ((0, 0), (0, padded - elems)))
        dst_pool = jnp.pad(dst_pool, ((0, 0), (0, padded - elems)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m, n_chunks),
        in_specs=[
            pl.BlockSpec((None, chunk), lambda i, j, sids, dids: (sids[i], j)),
            pl.BlockSpec((None, chunk), lambda i, j, sids, dids: (dids[i], j)),
        ],
        out_specs=pl.BlockSpec((None, chunk),
                               lambda i, j, sids, dids: (dids[i], j)),
    )
    out = pl.pallas_call(
        _fused_copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst_pool.shape, dst_pool.dtype),
        # operand 3 = dst_pool (after the 2 scalar-prefetch id lists and
        # src_pool): aliasing it into the output preserves untouched slots
        input_output_aliases={3: 0},
        interpret=interpret_mode(interpret),
    )(src_ids.astype(jnp.int32), dst_ids.astype(jnp.int32), src_pool,
      dst_pool)
    return out[:, :elems] if padded != elems else out


# ---------------------------------------------------------------------------
# fidelity kernels: quantize-on-demote / dequantize-on-reload
# ---------------------------------------------------------------------------

#: symmetric quantization range per wire fidelity (e4m3's largest finite
#: value is 448; int4 packs two's-complement nibbles, so ±7 keeps the
#: packing sign-safe)
FIDELITY_QMAX = {"int8": 127.0, "fp8": 448.0, "int4": 7.0}

#: storage dtype of the packed value plane per wire fidelity
FIDELITY_QDTYPE = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn,
                   "int4": jnp.uint8}

#: lane width of a TPU vector register; a block is viewed as (rows, 128)
_LANES = 128
#: blocks are padded to a multiple of 64 lane rows, so the f32 source is
#: (8, 128)-tiled and int4's packed half (rows / 2) is a whole number of
#: the (32, 128) tiles 8-bit planes use
_ROW_ALIGN = 64


def _padded_elems(elems: int) -> int:
    """Elements per block after padding to whole aligned lane rows."""
    align = _ROW_ALIGN * _LANES
    return -(-elems // align) * align


def _packed_width(elems: int, fidelity: str) -> int:
    """Columns of the packed value plane for a block of ``elems`` weights.
    int4 packs element ``j`` of the padded block into the low nibble of
    byte ``j`` and element ``j + padded/2`` into its high nibble — two
    contiguous halves, no lane interleave."""
    padded = _padded_elems(elems)
    return padded // 2 if fidelity == "int4" else padded


def _row_chunk(rows: int) -> int:
    """Rows processed per loop step: bounded VMEM temporaries, and a
    multiple of 32 so 8-bit stores stay tile-aligned."""
    for c in (512, 256, 128, 64, 32):
        if rows % c == 0:
            return c
    raise ValueError(f"block of {rows} lane rows is not 32-row aligned")


def _quantize_rows(x, scale, fidelity):
    x = x.astype(jnp.float32) / scale
    if fidelity == "fp8":
        return x.astype(jnp.float8_e4m3fn)
    qmax = FIDELITY_QMAX[fidelity]
    q = jnp.clip(jnp.round(x), -qmax, qmax).astype(jnp.int32)
    return q.astype(jnp.int8) if fidelity == "int8" else q


def _unpack_nibble(b):
    """Sign-extend a two's-complement nibble held in an int32."""
    b = b & 15
    return b - 2 * (b & 8)


def _quantize_kernel(ids_ref, src_ref, val_ref, scale_ref, *, fidelity):
    """One grid step = one gathered block, viewed as (rows, 128): a first
    pass takes the absmax, a second quantizes and packs, both in row
    chunks so no full-block temporary lives in VMEM."""
    rows = src_ref.shape[0]
    out_rows = val_ref.shape[0]
    chunk = _row_chunk(out_rows)

    def absmax_step(i, acc):
        r = pl.multiple_of(i * chunk, chunk)
        x = jnp.abs(src_ref[pl.ds(r, chunk), :].astype(jnp.float32))
        return jnp.maximum(acc, jnp.max(x, axis=0, keepdims=True))

    acc = jax.lax.fori_loop(0, rows // chunk, absmax_step,
                            jnp.zeros((1, _LANES), jnp.float32))
    absmax = jnp.max(acc)
    # all-zero blocks quantize to zeros with a unit scale instead of a NaN
    scale = jnp.where(absmax == 0.0, 1.0, absmax / FIDELITY_QMAX[fidelity])
    scale_ref[...] = jnp.full(scale_ref.shape, scale, dtype=jnp.float32)

    def pack_step(i, carry):
        r = pl.multiple_of(i * chunk, chunk)
        q = _quantize_rows(src_ref[pl.ds(r, chunk), :], scale, fidelity)
        if fidelity == "int4":
            hi = _quantize_rows(src_ref[pl.ds(out_rows + r, chunk), :],
                                scale, fidelity)
            q = ((q & 15) | ((hi & 15) << 4)).astype(jnp.uint8)
        val_ref[pl.ds(r, chunk), :] = q
        return carry

    jax.lax.fori_loop(0, out_rows // chunk, pack_step, 0)


def _dequantize_kernel(ids_ref, val_ref, scale_ref, dst_in_ref, dst_ref,
                       *, fidelity):
    scale = scale_ref[0, 0]
    in_rows = val_ref.shape[0]
    chunk = _row_chunk(in_rows)

    def store(r, x):
        dst_ref[pl.ds(r, chunk), :] = (x.astype(jnp.float32) * scale
                                       ).astype(dst_ref.dtype)

    def step(i, carry):
        r = pl.multiple_of(i * chunk, chunk)
        q = val_ref[pl.ds(r, chunk), :]
        if fidelity == "int4":
            b = q.astype(jnp.int32)
            store(r, _unpack_nibble(b))
            store(in_rows + r, _unpack_nibble(b >> 4))
        else:
            store(r, q)
        return carry

    jax.lax.fori_loop(0, in_rows // chunk, step, 0)


def _as_rows(pool, padded: int):
    """(n, elems) -> (n, padded / 128, 128), zero-padding each block."""
    n, elems = pool.shape
    if padded != elems:
        pool = jnp.pad(pool, ((0, 0), (0, padded - elems)))
    return pool.reshape(n, padded // _LANES, _LANES)


def quantize_demote(src_pool, slot_ids, *, fidelity: str = "int8",
                    interpret: Optional[bool] = None):
    """Fused gather→quantize→pack for a demotion batch.

    ``src_pool``: (n_slots, block_elems) float pool; ``slot_ids``: (m,)
    rows being demoted.  Returns ``(values, scales)`` — the packed wire
    payload (m, packed_width) in the fidelity's storage dtype and the
    per-block f32 absmax scales (m, 1).  One pass: the source BlockSpec
    chases the slot list exactly like ``harvest_gather``, so the batch is
    never staged densely at full precision.  Blocks are zero-padded to
    whole aligned lane rows (the pad quantizes to zero and is sliced off
    on reload).
    """
    if fidelity not in FIDELITY_QMAX:
        raise ValueError(f"quantize_demote: unknown fidelity {fidelity!r} — "
                         f"one of {sorted(FIDELITY_QMAX)}")
    n_slots, elems = src_pool.shape
    _check_slot_ids(slot_ids, n_slots, "quantize_demote")
    m = slot_ids.shape[0]
    padded = _padded_elems(elems)
    src = _as_rows(src_pool, padded)
    rows = padded // _LANES
    out_rows = _packed_width(elems, fidelity) // _LANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((None, rows, _LANES), lambda i, ids: (ids[i], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, out_rows, _LANES), lambda i, ids: (i, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i, ids: (i, 0, 0)),
        ],
    )
    values, scales = pl.pallas_call(
        functools.partial(_quantize_kernel, fidelity=fidelity),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, out_rows, _LANES),
                                 FIDELITY_QDTYPE[fidelity]),
            jax.ShapeDtypeStruct((m, 1, 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(slot_ids.astype(jnp.int32), src)
    return values.reshape(m, out_rows * _LANES), scales.reshape(m, 1)


def dequantize_reload(dst_pool, values, scales, slot_ids, *,
                      fidelity: str = "int8",
                      interpret: Optional[bool] = None):
    """Fused unpack→dequantize→scatter for a reload batch.

    Writes ``values[i] * scales[i]`` into ``dst_pool[slot_ids[i]]``; the
    output aliases the destination pool (``input_output_aliases``) so
    every slot outside the reload set is preserved bit-exactly.  Returns
    the updated pool.  ``slot_ids`` must be unique — two reloads landing
    in one slot is a plan bug, not a race to resolve here.
    """
    if fidelity not in FIDELITY_QMAX:
        raise ValueError(f"dequantize_reload: unknown fidelity {fidelity!r} "
                         f"— one of {sorted(FIDELITY_QMAX)}")
    n_slots, elems = dst_pool.shape
    _check_slot_ids(slot_ids, n_slots, "dequantize_reload")
    m = slot_ids.shape[0]
    width = _packed_width(elems, fidelity)
    assert values.shape == (m, width), \
        f"dequantize_reload: values shape {values.shape} != ({m}, {width})"
    padded = _padded_elems(elems)
    dst = _as_rows(dst_pool, padded)
    rows = padded // _LANES
    in_rows = width // _LANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((None, in_rows, _LANES), lambda i, ids: (i, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i, ids: (i, 0, 0)),
            # the aliased pool stays in HBM: every reloaded row is
            # overwritten, so its old contents are never read
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, rows, _LANES),
                               lambda i, ids: (ids[i], 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_dequantize_kernel, fidelity=fidelity),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst.shape, dst.dtype),
        # operand 3 = dst (after the id list, values and scales):
        # aliasing it into the output preserves untouched slots
        input_output_aliases={3: 0},
        interpret=interpret_mode(interpret),
    )(slot_ids.astype(jnp.int32), values.reshape(m, in_rows, _LANES),
      scales.reshape(m, 1, 1), dst)
    return out.reshape(n_slots, padded)[:, :elems]


def harvest_scatter(dst_pool, staging, slot_ids):
    """Write staging rows back into pool slots (reload completion).

    Implemented with a jnp scatter (aliasing-safe); the gather above is the
    bandwidth-critical direction.  Out-of-range slot ids raise instead of
    silently dropping the write — a reload whose payload lands nowhere is
    data loss, not a masking convenience.
    """
    _check_slot_ids(slot_ids, dst_pool.shape[0], "harvest_scatter")
    return dst_pool.at[slot_ids].set(staging.astype(dst_pool.dtype),
                                     mode="drop")
