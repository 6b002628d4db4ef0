"""Plain float32 forward of the Llama-style dense GQA decoder.

Written from the published description (pre-norm RMSNorm blocks, rotary
embedding with the rotate-half convention, grouped-query causal softmax
attention, optional sliding window, SiLU-gated MLP, untied output head),
in straightforward ``jax.numpy``.  It imports nothing of the program: it
reads the sizes from the benchmark's configuration file and the weights
the benchmark drew, by name.  Weights stay bf16 in HBM and are upcast
one layer at a time, so the reference fits beside them.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 first (accumulation stays float32), the step below the
bf16 the configuration serves in.

Its work counts are the dense decoder's, ``bench.harness.roofline``'s
:class:`~bench.harness.roofline.DenseCounts`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.harness.roofline import DenseCounts

F32 = jnp.float32
NEG = -1e30


def _cast(x, precision):
    x = x.astype(F32)
    if precision == "fp8":
        x = x.astype(jnp.float8_e4m3fn).astype(F32)
    return x


def _mm(spec, a, b, precision):
    return jnp.einsum(spec, _cast(a, precision), _cast(b, precision),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, pos, theta):
    """x: (b, s, n, h); rotate-half rotary embedding."""
    h = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, h, 2, dtype=F32) / h))
    ang = pos[..., None].astype(F32) * inv                 # (b, s, h/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :h // 2], x[..., h // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _embed(embed, tokens, cfg, precision):
    return jnp.take(embed, tokens, axis=0).astype(F32)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _layer(x, layers, i, cfg, precision):
    """Block ``i`` of the stacked bf16 ``layers`` on x: (b, s, d) f32."""
    lw = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, 0, keepdims=False), layers)
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    b, s, _ = x.shape
    nq, nkv = cfg["num_heads"], cfg["num_kv_heads"]
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = _rms(x, lw["ln1"], eps)
    a = lw["attn"]
    q = _rope(_mm("bsd,dnh->bsnh", h, a["wq"], precision), pos, theta)
    k = _rope(_mm("bsd,dnh->bsnh", h, a["wk"], precision), pos, theta)
    v = _mm("bsd,dnh->bsnh", h, a["wv"], precision)
    q = q.reshape(b, s, nkv, nq // nkv, -1) * q.shape[-1] ** -0.5
    sc = _mm("bqkgh,bskh->bkgqs", q, k, precision)
    qi, ki = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = ki <= qi
    if cfg.get("sliding_window"):
        mask &= ki > qi - cfg["sliding_window"]
    sc = jnp.where(mask, sc, NEG)
    p = jax.nn.softmax(sc, axis=-1)
    o = _mm("bkgqs,bskh->bqkgh", p, v, precision).reshape(b, s, nq, -1)
    x = x + _mm("bsnh,nhd->bsd", o, a["wo"], precision)
    h = _rms(x, lw["ln2"], eps)
    m = lw["mlp"]
    g = _mm("bsd,df->bsf", h, m["wg"], precision)
    u = _mm("bsd,df->bsf", h, m["wi"], precision)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["wo"], precision)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _head(x, final_norm, lm_head, probe, cfg, precision):
    """Returns, per position, the reference's best logit less the logit
    of ``probe``'s token there, and the reference's own top token."""
    logits = _mm("bsd,dv->bsv", _rms(x, final_norm, cfg["norm_eps"]),
                 lm_head, precision)
    best = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, probe[..., None], axis=-1)[..., 0]
    return best - at, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def gaps(weights, tokens, probe, cfg: dict, precision: str = "f32"):
    """Run the reference over ``tokens`` (b, s) int32 and read, at each
    position, how far ``probe``'s token (b, s) lies below its best logit.
    Returns ``(gap, top)``, both (b, s).  ``cfg`` is the configuration
    file's content as a hashable tuple of items (see :func:`frozen`)."""
    c = dict(cfg)
    x = _embed(weights["embed"], tokens, cfg=cfg, precision=precision)
    for i in range(c["num_layers"]):
        x = _layer(x, weights["layers"], jnp.int32(i), cfg=cfg,
                   precision=precision)
    return _head(x, weights["final_norm"], weights["lm_head"], probe,
                 cfg=cfg, precision=precision)


def counts(conf: dict) -> DenseCounts:
    """The least work of a step, from the configuration file's sizes."""
    return DenseCounts.from_config(conf)


def lower(weights, cfg, tokens) -> list:
    """The layer and the head :func:`gaps` runs, lowered for abstract
    ``weights`` and ``tokens`` (b, s), each on ``tokens``' sharding."""
    b, s = tokens.shape
    x = jax.ShapeDtypeStruct((b, s, dict(cfg)["d_model"]), F32,
                             sharding=tokens.sharding)
    i = jax.ShapeDtypeStruct((), jnp.int32, sharding=tokens.sharding)
    return [
        (f"reference layer, {b} x {s}", _layer.lower(
            x, weights["layers"], i, cfg=cfg, precision="f32")),
        ("reference head", _head.lower(
            x, weights["final_norm"], weights["lm_head"], tokens, cfg=cfg,
            precision="f32")),
    ]


class frozen(dict):
    """A configuration dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, v) for k, v in self.items()
                                 if not isinstance(v, (list, dict)))))
