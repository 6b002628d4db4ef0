"""Pallas TPU paged decode attention over a Harvest KV block pool.

One grid step attends one request's query heads (for one kv head) to one
KV block resolved through the *block table* — the table is a scalar-prefetch
operand so the BlockSpec index_map can chase it (the TPU analogue of vLLM's
pointer-chasing PagedAttention).  The pool slot dimension is the unit the
Harvest KVOffloadManager moves between tiers; this kernel only ever sees
local-HBM-resident slots (fetch mode) — in-place peer attention merges
partials at the JAX level (core/paged_attention.py).

Grid: (b, nkv, max_blocks_per_req), last dim sequential with the
online-softmax carry in VMEM scratch.

Scalar operands:
  block_table: (b, max_blk) int32 pool-slot id per request block (-1 = none)
  q_pos:       (b,) int32 current decode position (masks unfilled tail)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _paged_kernel(tclamp_ref, table_ref, qpos_ref, q_ref, pk_ref, pv_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, bs: int, n_blk: int, scale: float,
                  sliding_window: Optional[int],
                  attention_chunk: Optional[int]):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32) * scale          # (gq, hd)
    k = pk_ref[...].astype(jnp.float32)                 # (bs, hd)
    v = pv_ref[...].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (gq, bs)

    qp = qpos_ref[b]
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)[0]
    valid = (pos <= qp) & (table_ref[b, j] >= 0)
    if sliding_window is not None:
        valid &= pos > qp - sliding_window
    if attention_chunk is not None:
        valid &= (pos // attention_chunk) == (qp // attention_chunk)
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid[None, :], p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == n_blk - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).astype(o_ref.dtype)


def paged_attention(q, pool_k, pool_v, block_table, q_pos, *,
                    scale: Optional[float] = None,
                    sliding_window: Optional[int] = None,
                    attention_chunk: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q: (b, nq, hd); pool_k/v: (n_slots, bs, nkv, hd);
    block_table: (b, max_blk) int32; q_pos: (b,) int32 -> (b, nq, hd)."""
    b, nq, hd = q.shape
    n_slots, bs, nkv, _ = pool_k.shape
    gq = nq // nkv
    max_blk = block_table.shape[1]
    scale = hd ** -0.5 if scale is None else scale

    qr = q.reshape(b, nkv, gq, hd)
    table_c = jnp.maximum(block_table, 0).astype(jnp.int32)

    kern = functools.partial(
        _paged_kernel, bs=bs, n_blk=max_blk, scale=scale,
        sliding_window=sliding_window, attention_chunk=attention_chunk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nkv, max_blk),
        in_specs=[
            pl.BlockSpec((None, None, gq, hd),
                         lambda b, K, j, tc, t, qp: (b, K, 0, 0)),
            # chase the block table: slot = clamped_table[b, j]
            pl.BlockSpec((None, bs, None, hd),
                         lambda b, K, j, tc, t, qp: (tc[b, j], 0, K, 0)),
            pl.BlockSpec((None, bs, None, hd),
                         lambda b, K, j, tc, t, qp: (tc[b, j], 0, K, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, gq, hd),
                               lambda b, K, j, tc, t, qp: (b, K, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gq, 1), jnp.float32),
            pltpu.VMEM((gq, 1), jnp.float32),
            pltpu.VMEM((gq, hd), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, gq, hd), q.dtype),
        interpret=interpret_mode(interpret),
    )(table_c, block_table.astype(jnp.int32), q_pos.astype(jnp.int32),
      qr, pool_k, pool_v)
    return out.reshape(b, nq, hd)
