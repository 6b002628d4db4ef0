"""Core transformer layers: norms, rotary embeddings (RoPE / M-RoPE),
memory-bounded (chunked, online-softmax) attention, and MLP variants.

All functions are pure; per-layer parameters arrive as dicts of arrays.
Attention here is the *training / prefill* path (full sequence); single-token
decode against the paged Harvest KV pool lives in ``repro/core/paged_attention``
and ``repro/kernels/paged_attention``.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.sharding import shard

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def _rotate(x, angles, scale=None):
    """Rotate-half RoPE of x: (..., heads, head_dim) by ``angles``
    (..., 1, head_dim/2), cos and sin times ``scale`` where given."""
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if scale is not None:
        sin, cos = sin * scale, cos * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                          # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    return _rotate(x, angles[..., None, :])                # broadcast over heads


def apply_mrope(x, positions_3d, theta: float, sections):
    """Qwen2-VL multimodal RoPE.

    positions_3d: (..., seq, 3) — (t, h, w) position ids. ``sections`` gives
    how many of the head_dim/2 frequency slots each of t/h/w owns
    (sum(sections) == head_dim // 2).
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                          # (hd/2,)
    # section id per frequency slot: 0->t, 1->h, 2->w
    sec = jnp.concatenate([
        jnp.full((sections[0],), 0), jnp.full((sections[1],), 1),
        jnp.full((sections[2],), 2),
    ])
    pos = jnp.take_along_axis(
        positions_3d.astype(jnp.float32),                  # (..., seq, 3)
        jnp.broadcast_to(sec, positions_3d.shape[:-1] + (hd // 2,)).astype(jnp.int32),
        axis=-1,
    )                                                      # (..., seq, hd/2)
    return _rotate(x, (pos * freqs)[..., None, :])


# ---------------------------------------------------------------------------
# Mixed attention: what differs by layer, as data the layer scan indexes
# ---------------------------------------------------------------------------

#: a full-attention layer's window in a mixed stack: past any position
FULL_WINDOW = 2 ** 30


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float, beta_slow: float
                  ) -> np.ndarray:
    """YaRN's inverse frequencies (arXiv:2309.00071): interpolated by
    ``factor`` below ``beta_slow`` rotations over ``original_max``
    positions, kept above ``beta_fast``, blended linearly between, the
    correction range rounded outwards (transformers'
    ``_compute_yarn_parameters``)."""
    def dim_of(rotations):
        return (head_dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    pos = theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    return (1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)


class LayerKind(NamedTuple):
    """One layer's attention in a mixed stack, as traced values; stacked
    over the layers, the table :func:`layer_kinds` builds."""
    window: jnp.ndarray      # () int32: positions a query attends back
    inv_freq: jnp.ndarray    # (head_dim/2,) f32 RoPE inverse frequencies
    rope_scale: jnp.ndarray  # () f32: YaRN's attention factor on cos, sin


def layer_kinds(cfg: ModelConfig) -> Optional[LayerKind]:
    """The per-layer table of a mixed-attention stack (leading dim
    ``num_layers``), or None for a stack whose layers are all alike."""
    ma = cfg.mixed_attention
    if ma is None:
        return None
    hd = cfg.resolved_head_dim
    slots = np.arange(0, hd, 2, dtype=np.float32) / hd
    sliding = (1.0 / cfg.rope_theta ** slots, 1.0,
               cfg.sliding_window or FULL_WINDOW)
    if ma.yarn_factor > 1:
        full = (yarn_inv_freq(hd, ma.full_rope_theta, ma.yarn_factor,
                              ma.yarn_original_max_position_embeddings,
                              ma.yarn_beta_fast, ma.yarn_beta_slow),
                ma.yarn_attention_factor
                or 0.1 * math.log(ma.yarn_factor) + 1.0, FULL_WINDOW)
    else:
        full = (1.0 / ma.full_rope_theta ** slots, 1.0, FULL_WINDOW)
    rows = [sliding if cfg.layer_type(i) == "sliding_attention" else full
            for i in range(cfg.num_layers)]
    return LayerKind(
        window=jnp.asarray([r[2] for r in rows], jnp.int32),
        inv_freq=jnp.asarray(np.stack([r[0] for r in rows]), jnp.float32),
        rope_scale=jnp.asarray([r[1] for r in rows], jnp.float32))


def position_embedding(x, positions, cfg: ModelConfig, positions_3d=None,
                       kind: Optional[LayerKind] = None):
    if kind is not None:
        angles = positions[..., None].astype(jnp.float32) * kind.inv_freq
        return _rotate(x, angles[..., None, :], kind.rope_scale)
    if cfg.rope_style == "rope":
        return apply_rope(x, positions, cfg.rope_theta)
    if cfg.rope_style == "mrope":
        sections = cfg.modality.mrope_sections
        if positions_3d is None:  # text-only: all three sections share pos
            positions_3d = jnp.broadcast_to(positions[..., None], positions.shape + (3,))
        return apply_mrope(x, positions_3d, cfg.rope_theta, sections)
    return x  # "none": musicgen/xlstm use non-rotary positions


def sinusoidal_positions(positions, d_model: int):
    """MusicGen-style sinusoidal embedding added to the input stream."""
    half = d_model // 2
    freqs = jnp.exp(-jnp.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Chunked (online-softmax) attention — memory-bounded pure-jnp path.
# The Pallas flash kernel (repro/kernels/flash_attention) is the TPU hot path;
# this implementation is its oracle and the dry-run lowering path.
# ---------------------------------------------------------------------------


def _band_mask(q_pos, k_pos, cfg: ModelConfig, window=None):
    """(q, k) boolean mask combining causal + sliding-window + chunked-local.
    ``window`` (a layer's own, possibly traced) replaces the model's."""
    m = k_pos[None, :] <= q_pos[:, None]                   # causal
    if window is None:
        window = cfg.sliding_window
    if window is not None:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    if cfg.attention_chunk is not None:                    # llama4 chunked local
        m &= (k_pos[None, :] // cfg.attention_chunk) == (q_pos[:, None] // cfg.attention_chunk)
    return m


def _attn_layout(rules, nq, sq):
    """Pick how attention intermediates shard over the tensor axis.

    Preferred: shard the q-head dim ("heads" mode, nq divisible by the axis).
    Fallback: shard the q-sequence dim ("seq" mode — sequence parallelism;
    each chip owns a slice of q rows, no cross-chip softmax reduction).
    """
    if rules is None:
        return None, None
    ax = rules.axis("act_heads")
    size = rules.axis_size(ax)
    if ax is None or size == 1:
        return None, None
    if nq % size == 0:
        return "heads", ax
    if sq % size == 0:
        return "seq", ax
    return None, None


def chunked_attention(q, k, v, q_positions, k_positions, cfg: ModelConfig,
                      kv_chunk: int = 1024, logit_softcap=None, rules=None,
                      window=None):
    """Causal GQA attention with online softmax over KV chunks.

    q: (b, sq, nq, hd);  k, v: (b, sk, nkv, hd)
    q_positions: (b, sq);  k_positions: (b, sk)
    window: the layer's own window (see ``_band_mask``), or None
    Returns (b, sq, nq, hd).

    Sharding: the (b, sq, nq, C) score tensor must shard over the tensor
    axis or it dominates memory.  When nq divides the axis we expand KV to
    q heads (a (nq)->(nkv,gq) reshape of head-sharded q cannot propagate
    through GSPMD) and shard heads; otherwise (llama4's 40 heads on a
    16-way axis) we shard the q-*sequence* dim instead — each chip owns a
    slice of q rows end-to-end, so no collective enters the softmax.
    Operands stay bf16 with f32 MXU accumulation (preferred_element_type);
    an f32 expanded-KV stack would otherwise be hoisted out of the scan.
    """
    b, sq, nq, hd = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    gq = nq // nkv
    scale = hd ** -0.5
    mode, ax = _attn_layout(rules, nq, sq)
    bax = rules.axis("act_batch") if rules is not None else None

    def cst(x, spec):
        if rules is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(rules.mesh, spec))

    kv_chunk = min(kv_chunk, sk)
    n_chunks = -(-sk // kv_chunk)
    pad = n_chunks * kv_chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, ((0, 0), (0, pad)), constant_values=-(10 ** 9))

    kc = k.reshape(b, n_chunks, kv_chunk, nkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, kv_chunk, nkv, hd).transpose(1, 0, 2, 3, 4)
    pc = k_positions.reshape(b, n_chunks, kv_chunk).transpose(1, 0, 2)
    expand_kv = mode == "heads"
    if not expand_kv:
        # grouped-GQA einsum path; shard q rows over the tensor axis
        q = q.reshape(b, sq, nkv, gq, hd)

    if mode == "heads":      # q (b,sq,nq,hd); m/l (b,sq,nq)
        qspec, mspec = P(bax, None, ax, None), P(bax, None, ax)
        accspec = P(bax, None, ax, None)
    elif mode == "seq":      # q (b,sq,nkv,gq,hd); m/l (b,sq,nkv,gq)
        qspec, mspec = P(bax, ax), P(bax, ax)
        accspec = P(bax, ax)
    else:
        qspec = mspec = accspec = P(bax)
    qf = cst(q * jnp.asarray(scale, q.dtype), qspec)

    def body(carry, chunk):
        m_prev, l_prev, acc_prev = carry
        kj, vj, posj = chunk                        # (b, C, nkv, hd), (b, C)
        mask = jax.vmap(lambda qp, kp: _band_mask(qp, kp, cfg, window))(
            q_positions, posj)
        if expand_kv:
            kj = cst(jnp.repeat(kj, gq, axis=2), P(bax, None, ax, None))
            vj = cst(jnp.repeat(vj, gq, axis=2), P(bax, None, ax, None))
            s = jnp.einsum("bqnh,bcnh->bqnc", qf, kj,
                           preferred_element_type=jnp.float32)
            mask = mask[:, :, None, :]              # (b, sq, 1, C)
        else:
            s = jnp.einsum("bqKgh,bcKh->bqKgc", qf, kj,
                           preferred_element_type=jnp.float32)
            mask = mask[:, :, None, None, :]        # (b, sq, 1, 1, C)
        if logit_softcap:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        if expand_kv:
            upd = jnp.einsum("bqnc,bcnh->bqnh", p.astype(vj.dtype), vj,
                             preferred_element_type=jnp.float32)
        else:
            upd = jnp.einsum("bqKgc,bcKh->bqKgh", p.astype(vj.dtype), vj,
                             preferred_element_type=jnp.float32)
        acc_new = acc_prev * corr[..., None] + upd
        return (m_new, l_new, acc_new), None

    heads_shape = (nq,) if expand_kv else (nkv, gq)
    m0 = cst(jnp.full((b, sq) + heads_shape, NEG_INF, jnp.float32), mspec)
    l0 = cst(jnp.zeros((b, sq) + heads_shape, jnp.float32), mspec)
    a0 = cst(jnp.zeros((b, sq) + heads_shape + (hd,), jnp.float32), accspec)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, a0),
                                  (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, sq, nq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention layer (projections + rotary + chunked attention)
# ---------------------------------------------------------------------------


def attention_qkv(x, p, cfg: ModelConfig, rules=None):
    """Project x -> (q, k, v) with GQA head layout and optional QK-norm."""
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"])
    if cfg.attn_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = shard(q, rules, "act_batch", "act_seq", "act_heads", None)
    k = shard(k, rules, "act_batch", "act_seq", None, None)
    v = shard(v, rules, "act_batch", "act_seq", None, None)
    return q, k, v


def attention_layer(x, p, cfg: ModelConfig, positions, rules=None,
                    positions_3d=None, kind: Optional[LayerKind] = None):
    """Full-sequence attention sublayer (train / prefill). Returns (y, (k, v)).
    ``kind``: the layer's row of :func:`layer_kinds` in a mixed stack."""
    q, k, v = attention_qkv(x, p, cfg, rules)
    q = position_embedding(q, positions, cfg, positions_3d, kind)
    k = position_embedding(k, positions, cfg, positions_3d, kind)
    o = chunked_attention(q, k, v, positions, positions, cfg,
                          logit_softcap=cfg.logit_softcap, rules=rules,
                          window=None if kind is None else kind.window)
    o = shard(o, rules, "act_batch", "act_seq", "act_heads", None)
    y = jnp.einsum("bsnh,nhd->bsd", o, p["wo"])
    # emitted KV (prefill cache material) shards its seq dim over "model" —
    # kv_heads are usually < the model axis, so seq is the shardable dim
    k = shard(k, rules, "act_batch", "kv_seq", None, None)
    v = shard(v, rules, "act_batch", "kv_seq", None, None)
    return y, (k, v)


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------


def _activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":  # nemotron squared relu
        return lambda u: jnp.square(jax.nn.relu(u))
    raise ValueError(name)


def mlp(x, p, cfg: ModelConfig, rules=None):
    act = _activation(cfg.activation)
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    if cfg.mlp_bias:
        h = h + p["bi"]
    if cfg.gated_mlp:
        g = jnp.einsum("bsd,df->bsf", x, p["wg"])
        h = act(g) * h
    else:
        h = act(h)
    h = shard(h, rules, "act_batch", "act_seq", "act_ff")
    y = jnp.einsum("bsf,fd->bsd", h, p["wo"])
    if cfg.mlp_bias:
        y = y + p["bo"]
    return y
