"""Parameter schema: one declaration drives init, abstract shapes (dry-run)
and PartitionSpecs (GSPMD in_shardings).

Every parameter is a :class:`ParamDef` with a shape, logical sharding axes
(translated by ``ShardingRules``) and an init spec.  ``init_params`` /
``abstract_params`` / ``param_specs`` all walk the same schema, so shapes and
shardings cannot drift apart.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.sharding import ShardingRules, logical_to_spec
from repro.models.ssm import ssm_dims
from repro.models.xlstm import xlstm_dims


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]      # logical sharding axes, len == ndim
    init: str = "normal"                 # normal | zeros | ones | const:<v>
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _stack(defs: dict, n: int, extra: int = 0) -> dict:
    """Prefix every ParamDef with stacked leading dim(s) (the scan axis)."""
    out = {}
    lead = (n,) if not extra else (n, extra)
    lead_axes = ("layers",) * len(lead)
    for k, v in defs.items():
        if isinstance(v, dict):
            out[k] = _stack(v, n, extra)
        else:
            out[k] = ParamDef(lead + v.shape, lead_axes + v.axes, v.init, v.dtype)
    return out


# ---------------------------------------------------------------------------
# Per-sublayer schemas
# ---------------------------------------------------------------------------


def attention_schema(cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    s = {
        "wq": ParamDef((d, nq, hd), ("embed_fsdp", "heads", None)),
        "wk": ParamDef((d, nkv, hd), ("embed_fsdp", "kv_heads", None)),
        "wv": ParamDef((d, nkv, hd), ("embed_fsdp", "kv_heads", None)),
        "wo": ParamDef((nq, hd, d), ("heads", None, "embed_fsdp")),
    }
    if cfg.attn_bias:
        s["bq"] = ParamDef((nq, hd), ("heads", None), "zeros")
        s["bk"] = ParamDef((nkv, hd), ("kv_heads", None), "zeros")
        s["bv"] = ParamDef((nkv, hd), ("kv_heads", None), "zeros")
    if cfg.qk_norm:
        s["q_norm"] = ParamDef((hd,), (None,), "ones")
        s["k_norm"] = ParamDef((hd,), (None,), "ones")
    return s


def mlp_schema(cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    s = {
        "wi": ParamDef((d, f), ("embed_fsdp", "ff")),
        "wo": ParamDef((f, d), ("ff", "embed_fsdp")),
    }
    if cfg.gated_mlp:
        s["wg"] = ParamDef((d, f), ("embed_fsdp", "ff"))
    return s


def moe_schema(cfg: ModelConfig) -> dict:
    mc = cfg.moe
    d = cfg.d_model
    e = mc.num_held
    s = {
        "router": ParamDef((d, mc.num_experts), ("embed_fsdp", None), dtype="float32"),
        "wi": ParamDef((e, d, mc.d_ff_expert), ("experts", "embed_fsdp", None)),
        "wg": ParamDef((e, d, mc.d_ff_expert), ("experts", "embed_fsdp", None)),
        "wo": ParamDef((e, mc.d_ff_expert, d), ("experts", None, "embed_fsdp")),
    }
    if mc.num_shared_experts:
        s["shared"] = mlp_schema(cfg, mc.d_ff_shared * mc.num_shared_experts)
    return s


def mamba_schema(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    sc = cfg.ssm
    d_inner, nh, conv_dim = ssm_dims(cfg)
    in_dim = d_inner + conv_dim + nh               # z, xBC, dt
    return {
        "in_proj": ParamDef((d, in_dim), ("embed_fsdp", "ff")),
        "conv_w": ParamDef((sc.conv_width, conv_dim), (None, "ff")),
        "conv_b": ParamDef((conv_dim,), ("ff",), "zeros"),
        "dt_bias": ParamDef((nh,), (None,), "const:-2.0", "float32"),
        "A_log": ParamDef((nh,), (None,), "const:0.5", "float32"),
        "D": ParamDef((nh,), (None,), "ones", "float32"),
        "norm": ParamDef((d_inner,), ("ff",), "ones"),
        "out_proj": ParamDef((d_inner, d), ("ff", "embed_fsdp")),
    }


def mlstm_schema(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    d_inner, nh, hd = xlstm_dims(cfg)
    W = cfg.xlstm.conv_width
    return {
        "ln": ParamDef((d,), (None,), "ones"),
        "w_up": ParamDef((d, 2 * d_inner), ("embed_fsdp", "ff")),
        "conv_w": ParamDef((W, d_inner), (None, "ff")),
        "conv_b": ParamDef((d_inner,), ("ff",), "zeros"),
        "wq": ParamDef((nh, hd, hd), ("state_heads", None, None)),
        "wk": ParamDef((nh, hd, hd), ("state_heads", None, None)),
        "wv": ParamDef((nh, hd, hd), ("state_heads", None, None)),
        "w_gates": ParamDef((d_inner, 2 * nh), ("ff", None)),
        "b_gates": ParamDef((2 * nh,), (None,), "const:3.0"),
        "out_norm": ParamDef((hd,), (None,), "ones"),
        "w_down": ParamDef((d_inner, d), ("ff", "embed_fsdp")),
    }


def slstm_schema(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    nh = cfg.num_heads
    hd = d // nh
    f2 = int(d * cfg.xlstm.proj_factor_slstm)
    return {
        "ln": ParamDef((d,), (None,), "ones"),
        "w_in": ParamDef((d, nh, 4, hd), ("embed_fsdp", "state_heads", None, None)),
        "b_in": ParamDef((nh, 4, hd), ("state_heads", None, None), "zeros"),
        "w_rec": ParamDef((nh, hd, 4, hd), ("state_heads", None, None, None)),
        "gn": ParamDef((hd,), (None,), "ones"),
        "w_out": ParamDef((nh, hd, d), ("state_heads", None, "embed_fsdp")),
        "ln2": ParamDef((d,), (None,), "ones"),
        "mlp_wi": ParamDef((d, f2), ("embed_fsdp", "ff")),
        "mlp_wg": ParamDef((d, f2), ("embed_fsdp", "ff")),
        "mlp_wo": ParamDef((f2, d), ("ff", "embed_fsdp")),
    }


def _norms(cfg: ModelConfig, parallel: bool) -> dict:
    d = cfg.d_model
    s = {"ln1": ParamDef((d,), (None,), "ones")}
    if not parallel:
        s["ln2"] = ParamDef((d,), (None,), "ones")
    return s


# ---------------------------------------------------------------------------
# Whole-model schema
# ---------------------------------------------------------------------------


def build_schema(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    schema: dict = {}

    # embeddings
    if cfg.family == "audio" and cfg.modality.num_codebooks > 1:
        ncb = cfg.modality.num_codebooks
        schema["embed"] = ParamDef((ncb, cfg.vocab_size, d),
                                   (None, "vocab", "embed_fsdp"))
        schema["lm_head"] = ParamDef((ncb, d, cfg.vocab_size),
                                     (None, "embed_fsdp", "vocab"))
    else:
        schema["embed"] = ParamDef((cfg.vocab_size, d), ("vocab", "embed_fsdp"))
        if not cfg.tie_embeddings:
            schema["lm_head"] = ParamDef((d, cfg.vocab_size),
                                         ("embed_fsdp", "vocab"))
    schema["final_norm"] = ParamDef((d,), (None,), "ones")

    fam = cfg.family
    if fam in ("dense", "vlm", "audio") or (fam == "moe" and cfg.moe.layer_period == 1):
        layer = dict(_norms(cfg, cfg.parallel_block))
        layer["attn"] = attention_schema(cfg)
        if fam == "moe":
            layer["moe"] = moe_schema(cfg)
        else:
            layer["mlp"] = mlp_schema(cfg)
        schema["layers"] = _stack(layer, cfg.num_layers)
    elif fam == "moe":  # interleaved (llama4): scan over (dense, moe) pairs
        period = cfg.moe.layer_period
        assert period == 2 and cfg.num_layers % 2 == 0
        dense_layer = dict(_norms(cfg, False))
        dense_layer["attn"] = attention_schema(cfg)
        dense_layer["mlp"] = mlp_schema(cfg)
        moe_layer = dict(_norms(cfg, False))
        moe_layer["attn"] = attention_schema(cfg)
        moe_layer["moe"] = moe_schema(cfg)
        schema["blocks"] = {
            "dense": _stack(dense_layer, cfg.num_layers // 2),
            "moe": _stack(moe_layer, cfg.num_layers // 2),
        }
    elif fam == "hybrid":
        mamba_layer = {"ln1": ParamDef((d,), (None,), "ones"),
                       "mamba": mamba_schema(cfg)}
        schema["mamba_layers"] = _stack(mamba_layer, cfg.num_layers)
        # zamba2 signature: ONE shared attention+MLP block, reused periodically
        schema["shared_attn"] = dict(_norms(cfg, False))
        schema["shared_attn"]["attn"] = attention_schema(cfg)
        schema["shared_attn"]["mlp"] = mlp_schema(cfg)
    elif fam == "ssm":  # xlstm: super-blocks of (slstm_every-1 mLSTM + 1 sLSTM)
        per = cfg.xlstm.slstm_every
        assert cfg.num_layers % per == 0
        n_super = cfg.num_layers // per
        schema["supers"] = {
            "mlstm": _stack(mlstm_schema(cfg), n_super, per - 1),
            "slstm": _stack(slstm_schema(cfg), n_super),
        }
    else:
        raise ValueError(fam)
    return schema


# ---------------------------------------------------------------------------
# Schema walkers
# ---------------------------------------------------------------------------


def _walk(schema, fn, path=()):
    if isinstance(schema, ParamDef):
        return fn(path, schema)
    return {k: _walk(v, fn, path + (k,)) for k, v in schema.items()}


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "scale"))
def _normal_leaf(key, shape, dtype, scale):
    """One leaf's scaled normal draw as one program: the f32 draw, the
    scale and the cast fuse, so no full-size f32 buffer is ever live
    beside the weights already drawn."""
    x = jax.random.normal(key, shape, jnp.float32)
    # a no-op rounding that keeps XLA from folding the normal transform's
    # sqrt(2) into ``scale``: the folded product rounds differently, and
    # the values must stay those of the unfused draw
    x = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=23)
    return (x * scale).astype(dtype)


def init_params(rng, cfg: ModelConfig):
    """Materialise parameters on the default device, one leaf per
    program, at any width the device's memory holds."""
    schema = build_schema(cfg)
    counter = [0]

    def make(path, pd: ParamDef):
        counter[0] += 1
        key = jax.random.fold_in(rng, counter[0])
        dtype = jnp.dtype(pd.dtype)
        if pd.init == "zeros":
            return jnp.zeros(pd.shape, dtype)
        if pd.init == "ones":
            return jnp.ones(pd.shape, dtype)
        if pd.init.startswith("const:"):
            return jnp.full(pd.shape, float(pd.init.split(":")[1]), dtype)
        fan_in = pd.shape[-2] if len(pd.shape) >= 2 else pd.shape[-1]
        scale = min(0.02, 1.0 / math.sqrt(max(fan_in, 1)))
        return _normal_leaf(key, pd.shape, dtype, scale)

    return _walk(schema, make)


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct tree — the dry-run's allocation-free stand-in."""
    return _walk(build_schema(cfg),
                 lambda p, pd: jax.ShapeDtypeStruct(pd.shape, jnp.dtype(pd.dtype)))


def param_specs(cfg: ModelConfig, rules: Optional[ShardingRules]):
    """PartitionSpec tree (divisibility-checked against each shape)."""
    return _walk(build_schema(cfg),
                 lambda p, pd: logical_to_spec(rules, *pd.axes, shape=pd.shape))


def param_count(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
