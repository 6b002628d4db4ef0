"""Pallas TPU fused expert-FFN kernel: out[e] = (act(x@wg) * (x@wi)) @ wo.

Grid (experts, capacity_blocks, ff_blocks); the ff dimension is sequential
and the (c_blk, d) output accumulates in VMEM scratch, so the (C, d_ff)
gated intermediate never hits HBM.  The expert grid dimension is the unit
the Harvest Expert Rebalancer places across tiers — the kernel itself only
sees dispatch buffers whose weights are already local-HBM resident.

VMEM working set per step (targets):
  x (c_blk, d) + wi/wg (d, f_blk) + wo (f_blk, d) + acc (c_blk, d)
  with c_blk=128, f_blk=256, d<=5120: ~2.6 MB weights + 2.6 MB acc  < VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _moe_ffn_kernel(x_ref, wi_ref, wg_ref, wo_ref, o_ref, acc_scr, *,
                    n_f_blocks: int, activation: str):
    fi = pl.program_id(2)

    @pl.when(fi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)            # (c_blk, d)
    wi = wi_ref[...].astype(jnp.float32)          # (d, f_blk)
    wg = wg_ref[...].astype(jnp.float32)
    wo = wo_ref[...].astype(jnp.float32)          # (f_blk, d)

    h = jnp.dot(x, wi, preferred_element_type=jnp.float32)
    g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    if activation == "silu":
        g = g * jax.nn.sigmoid(g)
    elif activation == "gelu":
        g = jax.nn.gelu(g)
    elif activation == "relu2":
        g = jnp.square(jnp.maximum(g, 0.0))
    else:
        raise ValueError(activation)
    acc_scr[...] += jnp.dot(g * h, wo, preferred_element_type=jnp.float32)

    @pl.when(fi == n_f_blocks - 1)
    def _finalize():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def moe_ffn(xd, wi, wg, wo, *, activation: str = "silu",
            c_block: int = 128, f_block: int = 256,
            interpret: Optional[bool] = None):
    """xd: (E, C, d);  wi/wg: (E, d, f);  wo: (E, f, d) -> (E, C, d)."""
    E, C, d = xd.shape
    f = wi.shape[2]
    c_block = min(c_block, C)
    f_block = min(f_block, f)
    assert C % c_block == 0 and f % f_block == 0
    n_c = C // c_block
    n_f = f // f_block

    kern = functools.partial(_moe_ffn_kernel, n_f_blocks=n_f,
                             activation=activation)
    return pl.pallas_call(
        kern,
        grid=(E, n_c, n_f),
        in_specs=[
            pl.BlockSpec((None, c_block, d), lambda e, c, fi: (e, c, 0)),
            pl.BlockSpec((None, d, f_block), lambda e, c, fi: (e, 0, fi)),
            pl.BlockSpec((None, d, f_block), lambda e, c, fi: (e, 0, fi)),
            pl.BlockSpec((None, f_block, d), lambda e, c, fi: (e, fi, 0)),
        ],
        out_specs=pl.BlockSpec((None, c_block, d), lambda e, c, fi: (e, c, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, d), xd.dtype),
        scratch_shapes=[pltpu.VMEM((c_block, d), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(xd, wi, wg, wo)
