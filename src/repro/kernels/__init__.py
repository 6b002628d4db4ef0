"""Pallas TPU kernels (compiled on TPU, interpreted on the CPU backend).

  flash_attention — prefill attention (causal / SWA / chunked-local, GQA fold)
  paged_attention — decode attention over the Harvest KV block pool
                    (scalar-prefetch block-table chasing)
  moe_ffn         — fused gated expert FFN over dispatch buffers
  harvest_copy    — chunked tier-to-tier block gather + fused gather→scatter
                    pool-to-pool copy (the Harvest data movers)

Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper),
ref.py (pure-jnp oracle).  Every kernel takes ``interpret=None`` and
resolves it through :func:`interpret_mode`.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in the interpreter.  An explicit choice
    stands; otherwise kernels are interpreted on the CPU backend only and
    compiled everywhere else, so a TPU never runs the interpreter
    unasked."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"
