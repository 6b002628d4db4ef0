"""Jit'd wrapper for the fused expert-FFN kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.moe_ffn.kernel import moe_ffn


@functools.partial(jax.jit, static_argnames=(
    "activation", "c_block", "f_block", "interpret"))
def expert_ffn(xd, wi, wg, wo, *, activation: str = "silu",
               c_block: int = 128, f_block: int = 256,
               interpret: Optional[bool] = None):
    return moe_ffn(xd, wi, wg, wo, activation=activation,
                   c_block=c_block, f_block=f_block, interpret=interpret)
