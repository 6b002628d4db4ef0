"""Paged decode attention over tiered Harvest KV pools.

The KV cache is a pool of fixed-size blocks (vLLM PagedAttention), with an
*inverted* block table: per pool slot, which request owns it and which
position range it covers.  This layout makes the pool dimension shardable
over arbitrary mesh axes — each shard computes flash-decode partials
(m, l, acc) over its local slots and partials merge associatively via
log-sum-exp, first across pools/tiers, then across mesh shards with
pmax/psum.  That associativity is what lets Harvest's *peer tier* join the
attention in place (beyond-paper "inplace" mode) instead of being copied to
local HBM first (paper-faithful "fetch" mode).

Shapes (one shard / one tier):
  q:        (b, nq, hd)            current-token queries
  pool_k/v: (n_slots, bs, nkv, hd) block pool
  slot_req: (n_slots,) int32       owning request (-1 = free slot)
  slot_base:(n_slots,) int32       position of the block's first token
  q_pos:    (b,) int32             current decode position per request
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

NEG_INF = -1e30


class Partials(NamedTuple):
    m: jnp.ndarray    # (b, nkv, gq)        running max
    l: jnp.ndarray    # (b, nkv, gq)        running denominator
    acc: jnp.ndarray  # (b, nkv, gq, hd)    running numerator


def pool_partials(q, pool_k, pool_v, slot_req, slot_base, q_pos,
                  cfg: ModelConfig, window=None) -> Partials:
    """Flash-decode partials of one pool (tier) on one shard.  ``window``
    (the layer's own in a mixed stack, possibly traced) replaces the
    model's ``sliding_window``."""
    b, nq, hd = q.shape
    n_slots, bs, nkv, _ = pool_k.shape
    gq = nq // nkv
    f32 = jnp.float32
    scale = hd ** -0.5

    req = jnp.clip(slot_req, 0, b - 1)
    qn = jnp.take(q, req, axis=0).astype(f32) * scale        # (n, nq, hd)
    qn = qn.reshape(n_slots, nkv, gq, hd)

    # Dots take bf16 operands with f32 MXU accumulation.  Upcasting the pool
    # (pool.astype(f32)) instead makes XLA hoist a full f32 pool copy out of
    # the layer scan and rewrite the bf16 pool through a convert fusion every
    # layer — ~80% of decode HBM traffic at 80 layers (EXPERIMENTS.md §Perf).
    s = jnp.einsum("nKgh,nsKh->nKgs", qn.astype(pool_k.dtype), pool_k,
                   preferred_element_type=f32)               # (n,nkv,gq,bs)
    if cfg.logit_softcap:
        s = cfg.logit_softcap * jnp.tanh(s / cfg.logit_softcap)

    pos = slot_base[:, None] + jnp.arange(bs, dtype=jnp.int32)[None, :]
    qp = jnp.take(q_pos, req, axis=0)[:, None]               # (n, 1)
    valid = (slot_req[:, None] >= 0) & (pos <= qp) & (pos >= 0)
    if window is None:
        window = cfg.sliding_window
    if window is not None:
        valid &= pos > (qp - window)
    if cfg.attention_chunk is not None:
        valid &= (pos // cfg.attention_chunk) == (qp // cfg.attention_chunk)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)

    m_n = jnp.max(s, axis=-1)                                # (n,nkv,gq)
    p = jnp.exp(s - m_n[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l_n = jnp.sum(p, axis=-1)
    acc_n = jnp.einsum("nKgs,nsKh->nKgh", p.astype(pool_v.dtype), pool_v,
                       preferred_element_type=f32)

    # merge per-slot partials into per-request partials (segment LSE).
    # Slots are first sorted by (request, first position), so each
    # request's sums run in block order: which slot an eviction and a
    # reload put a block in must not change the rounding, or a bf16
    # near-tie could flip a token across tiers.
    seg = jnp.where(slot_req >= 0, slot_req, b)              # b = trash row
    order = jnp.lexsort((slot_base, seg))
    seg, m_n, l_n, acc_n = (jnp.take(x, order, axis=0)
                            for x in (seg, m_n, l_n, acc_n))
    m_r = jax.ops.segment_max(m_n, seg, num_segments=b + 1,
                              indices_are_sorted=True)[:b]
    m_r = jnp.maximum(m_r, NEG_INF)                          # empty -> -inf-ish
    corr = jnp.exp(m_n - jnp.take(m_r, jnp.clip(seg, 0, b - 1), axis=0))
    corr = jnp.where((seg < b)[:, None, None], corr, 0.0)
    l_r = jax.ops.segment_sum(l_n * corr, seg, num_segments=b + 1,
                              indices_are_sorted=True)[:b]
    acc_r = jax.ops.segment_sum(acc_n * corr[..., None], seg,
                                num_segments=b + 1,
                                indices_are_sorted=True)[:b]
    return Partials(m=m_r, l=l_r, acc=acc_r)


def merge_partials(parts: Sequence[Partials]) -> Partials:
    """Associative LSE merge across tiers/pools."""
    out = parts[0]
    for p in parts[1:]:
        m = jnp.maximum(out.m, p.m)
        c0 = jnp.exp(out.m - m)
        c1 = jnp.exp(p.m - m)
        out = Partials(m=m,
                       l=out.l * c0 + p.l * c1,
                       acc=out.acc * c0[..., None] + p.acc * c1[..., None])
    return out


def finalize(parts: Partials, axis_names: Sequence[str] = ()) -> jnp.ndarray:
    """Cross-shard merge (pmax/psum over ``axis_names``) and normalisation.

    Returns (b, nq, hd) f32. Call inside shard_map when the pool dim is
    mesh-sharded; with no axis names it is a plain normalisation.
    """
    m, l, acc = parts
    if axis_names:
        m_g = jax.lax.pmax(m, axis_names)
        corr = jnp.exp(m - m_g)
        l = jax.lax.psum(l * corr, axis_names)
        acc = jax.lax.psum(acc * corr[..., None], axis_names)
        m = m_g
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    b, nkv, gq, hd = out.shape
    return out.reshape(b, nkv * gq, hd)


def append_kv(pool_k, pool_v, k_new, v_new, local_slot, offset):
    """Scatter this step's (k, v) into the pool.

    k_new/v_new: (b, nkv, hd);  local_slot/offset: (b,) int32.  Requests whose
    current block lives on another shard carry local_slot == n_slots, which
    the scatter's drop mode ignores.
    """
    pool_k = pool_k.at[local_slot, offset].set(k_new.astype(pool_k.dtype),
                                               mode="drop")
    pool_v = pool_v.at[local_slot, offset].set(v_new.astype(pool_v.dtype),
                                               mode="drop")
    return pool_k, pool_v


def paged_decode_attention(q, pools, q_pos, cfg: ModelConfig,
                           axis_names: Sequence[str] = (),
                           window=None) -> jnp.ndarray:
    """Attention of one decode token against the union of KV pools.

    ``pools`` is a sequence of (pool_k, pool_v, slot_req, slot_base) tuples —
    typically [local] (paper-faithful fetch mode: peer blocks were copied in
    before the step) or [local, peer] (in-place mode: the harvested tier joins
    the softmax directly).
    """
    parts = [pool_partials(q, pk, pv, sr, sb, q_pos, cfg, window)
             for (pk, pv, sr, sb) in pools]
    merged = merge_partials(parts)
    return finalize(merged, axis_names)
