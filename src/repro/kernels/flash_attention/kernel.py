"""Pallas TPU flash-attention (prefill path).

Grid: (batch*kv_heads, q_blocks, kv_blocks) with the kv dimension
"arbitrary" (sequential) so the online-softmax carry lives in VMEM scratch.
GQA is handled by folding the q-head group into the q rows: q arrives as
(b*nkv, gq*sq, hd) with the group-local position = row % sq, so one kv-head's
K/V block serves all of its gq query heads without materialising repeated KV.

Block shapes are MXU-aligned (last dim = head_dim, second-to-last multiples
of 128 where the model allows).  Masks (causal / sliding-window / chunked
local attention) are computed from global positions derived from program ids.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sq: int, q_blk: int, kv_blk: int, n_kv_blocks: int,
                  scale: float, sliding_window: Optional[int],
                  attention_chunk: Optional[int]):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32) * scale          # (q_blk, hd)
    k = k_ref[...].astype(jnp.float32)                  # (kv_blk, hd)
    v = v_ref[...].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (q_blk, kv_blk)

    # positions: q rows fold the GQA group — position = global_row % sq
    rows = qi * q_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 0)
    q_pos = rows % sq
    k_pos = ki * kv_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 1)
    mask = k_pos <= q_pos
    if sliding_window is not None:
        mask &= k_pos > q_pos - sliding_window
    if attention_chunk is not None:
        mask &= (k_pos // attention_chunk) == (q_pos // attention_chunk)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, sq: int, scale: Optional[float] = None,
                    sliding_window: Optional[int] = None,
                    attention_chunk: Optional[int] = None,
                    q_block: int = 128, kv_block: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B, gq*sq, hd);  k, v: (B, sk, hd).  Returns (B, gq*sq, hd)."""
    B, qrows, hd = q.shape
    sk = k.shape[1]
    assert qrows % sq == 0, "q rows must fold the GQA group evenly"
    q_block = min(q_block, qrows)
    kv_block = min(kv_block, sk)
    assert qrows % q_block == 0, (qrows, q_block)
    assert sk % kv_block == 0, (sk, kv_block)
    n_q = qrows // q_block
    n_kv = sk // kv_block
    scale = hd ** -0.5 if scale is None else scale

    kern = functools.partial(
        _flash_kernel, sq=sq, q_blk=q_block, kv_blk=kv_block,
        n_kv_blocks=n_kv, scale=scale, sliding_window=sliding_window,
        attention_chunk=attention_chunk)

    return pl.pallas_call(
        kern,
        grid=(B, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, q_block, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, kv_block, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, kv_block, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, q_block, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, qrows, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
