"""Jit'd wrappers for the harvest tier-copy kernels.

The wrappers validate slot ids EAGERLY (before tracing) so out-of-range
ids raise :class:`IndexError` instead of becoming silently dropped writes
inside the jit'd scatter — see ``harvest_scatter``'s contract.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.harvest_copy.kernel import (FIDELITY_QMAX, _check_slot_ids,
                                               _packed_width,
                                               dequantize_reload,
                                               harvest_copy, harvest_gather,
                                               harvest_scatter,
                                               quantize_demote)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _gather_jit(src_pool, slot_ids, *, chunk, interpret):
    return harvest_gather(src_pool, slot_ids, chunk=chunk, interpret=interpret)


def gather_blocks(src_pool, slot_ids, *, chunk: int = 512,
                  interpret: Optional[bool] = None):
    _check_slot_ids(slot_ids, src_pool.shape[0], "gather_blocks")
    return _gather_jit(src_pool, slot_ids, chunk=chunk, interpret=interpret)


@jax.jit
def _scatter_jit(dst_pool, staging, slot_ids):
    return harvest_scatter(dst_pool, staging, slot_ids)


def scatter_blocks(dst_pool, staging, slot_ids):
    _check_slot_ids(slot_ids, dst_pool.shape[0], "scatter_blocks")
    return _scatter_jit(dst_pool, staging, slot_ids)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _copy_jit(src_pool, dst_pool, src_ids, dst_ids, *, chunk, interpret):
    return harvest_copy(src_pool, dst_pool, src_ids, dst_ids, chunk=chunk,
                        interpret=interpret)


def copy_blocks(src_pool, dst_pool, src_ids, dst_ids, *, chunk: int = 512,
                interpret: Optional[bool] = None):
    """Fused gather→scatter: move ``src_pool[src_ids]`` straight into
    ``dst_pool[dst_ids]`` with no dense staging buffer."""
    _check_slot_ids(src_ids, src_pool.shape[0], "copy_blocks(src)")
    _check_slot_ids(dst_ids, dst_pool.shape[0], "copy_blocks(dst)")
    return _copy_jit(src_pool, dst_pool, src_ids, dst_ids, chunk=chunk,
                     interpret=interpret)


# ---------------------------------------------------------------------------
# fidelity: quantize-on-demote / dequantize-on-reload
# ---------------------------------------------------------------------------


def _check_fidelity(fidelity: str, what: str) -> None:
    if fidelity not in FIDELITY_QMAX:
        raise ValueError(f"{what}: unknown fidelity {fidelity!r} — one of "
                         f"{sorted(FIDELITY_QMAX)}")


def _check_pool(pool, what: str) -> None:
    if getattr(pool, "ndim", None) != 2:
        raise ValueError(f"{what}: pool must be 2-D (n_slots, block_elems), "
                         f"got shape {getattr(pool, 'shape', None)}")
    if not jnp.issubdtype(pool.dtype, jnp.floating):
        raise TypeError(f"{what}: pool dtype {pool.dtype} is not floating — "
                        "quantization needs a full-precision source")


@functools.partial(jax.jit, static_argnames=("fidelity", "interpret"))
def _quantize_jit(src_pool, slot_ids, *, fidelity, interpret):
    return quantize_demote(src_pool, slot_ids, fidelity=fidelity,
                           interpret=interpret)


def quantize_blocks(src_pool, slot_ids, *, fidelity: str = "int8",
                    interpret: Optional[bool] = None):
    """Quantize-on-demote: pack ``src_pool[slot_ids]`` into the wire
    fidelity's ``(values, scales)`` pair in one fused pass.  Validates
    fidelity, pool shape/dtype and slot ids EAGERLY (before tracing)."""
    _check_fidelity(fidelity, "quantize_blocks")
    _check_pool(src_pool, "quantize_blocks")
    _check_slot_ids(slot_ids, src_pool.shape[0], "quantize_blocks")
    return _quantize_jit(src_pool, slot_ids, fidelity=fidelity,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("fidelity", "interpret"))
def _dequantize_jit(dst_pool, values, scales, slot_ids, *, fidelity,
                    interpret):
    return dequantize_reload(dst_pool, values, scales, slot_ids,
                             fidelity=fidelity, interpret=interpret)


def dequantize_blocks(dst_pool, values, scales, slot_ids, *,
                      fidelity: str = "int8",
                      interpret: Optional[bool] = None):
    """Dequantize-on-reload: unpack+rescale ``values``/``scales`` into
    ``dst_pool[slot_ids]``; untouched slots are preserved via the output
    alias.  Validates shapes/dtypes/ids EAGERLY (before tracing)."""
    _check_fidelity(fidelity, "dequantize_blocks")
    _check_pool(dst_pool, "dequantize_blocks")
    _check_slot_ids(slot_ids, dst_pool.shape[0], "dequantize_blocks")
    m = slot_ids.shape[0]
    elems = dst_pool.shape[1]
    width = _packed_width(elems, fidelity)
    if tuple(values.shape) != (m, width):
        raise ValueError(
            f"dequantize_blocks: values shape {tuple(values.shape)} does not "
            f"match {m} blocks of packed width {width} at {fidelity}")
    if tuple(scales.shape) != (m, 1):
        raise ValueError(f"dequantize_blocks: scales shape "
                         f"{tuple(scales.shape)} != ({m}, 1)")
    return _dequantize_jit(dst_pool, values, scales, slot_ids,
                           fidelity=fidelity, interpret=interpret)
