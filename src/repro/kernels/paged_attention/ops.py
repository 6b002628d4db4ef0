"""Jit'd wrapper for the paged decode-attention kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.paged_attention.kernel import paged_attention


@functools.partial(jax.jit, static_argnames=(
    "sliding_window", "attention_chunk", "interpret"))
def decode_attention(q, pool_k, pool_v, block_table, q_pos, *,
                     sliding_window: Optional[int] = None,
                     attention_chunk: Optional[int] = None,
                     interpret: Optional[bool] = None):
    return paged_attention(q, pool_k, pool_v, block_table, q_pos,
                           sliding_window=sliding_window,
                           attention_chunk=attention_chunk,
                           interpret=interpret)
