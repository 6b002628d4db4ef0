"""Jit'd public wrapper for the flash-attention kernel.

``mha(q, k, v)`` takes model-layout tensors (b, s, n, hd) and handles the
GQA fold; on TPU the Pallas kernel runs compiled, elsewhere interpret=True
executes the same kernel body on CPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention


@functools.partial(jax.jit, static_argnames=(
    "sliding_window", "attention_chunk", "q_block", "kv_block", "interpret"))
def mha(q, k, v, *, sliding_window: Optional[int] = None,
        attention_chunk: Optional[int] = None, q_block: int = 128,
        kv_block: int = 128, interpret: Optional[bool] = None):
    """q: (b, sq, nq, hd);  k, v: (b, sk, nkv, hd) -> (b, sq, nq, hd)."""
    b, sq, nq, hd = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    gq = nq // nkv

    # fold: (b, sq, nkv, gq, hd) -> (b*nkv, gq*sq, hd)
    qf = q.reshape(b, sq, nkv, gq, hd).transpose(0, 2, 3, 1, 4)
    qf = qf.reshape(b * nkv, gq * sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * nkv, sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * nkv, sk, hd)

    of = flash_attention(qf, kf, vf, sq=sq, sliding_window=sliding_window,
                         attention_chunk=attention_chunk, q_block=q_block,
                         kv_block=kv_block, interpret=interpret)
    o = of.reshape(b, nkv, gq, sq, hd).transpose(0, 3, 1, 2, 4)
    return o.reshape(b, sq, nq, hd)
