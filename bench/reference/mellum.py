"""Plain float32 forward of Mellum2's sparse decoder, holding a share of
its experts.

Written from the published ``config.json`` (JetBrains/Mellum2-12B-A2.5B):
pre-norm RMSNorm blocks; grouped-query causal softmax attention whose
``layer_types`` say, layer by layer, whether it attends within
``sliding_window`` positions (default RoPE) or to every position (YaRN
RoPE, arXiv:2309.00071: inverse frequencies interpolated by ``factor``
below ``beta_slow`` rotations over ``original_max_position_embeddings``,
kept above ``beta_fast``, blended linearly between, the range rounded
outwards; cos and sin times ``attention_factor``); a router over every
expert, softmax, the top ``num_experts_per_tok`` renormalised over
themselves (``norm_topk_prob``); SiLU-gated experts; untied head.  It
imports nothing of the program and reads the weights the benchmark drew,
by name.  Weights stay bf16 in HBM and one layer at a time is upcast.
The sizes a test may cut are read under the names the other
configurations give them (``num_layers``, ``d_model``, ``num_heads``,
``num_kv_heads``, ``head_dim``, ``vocab_size``, ``sliding_window``), the
rest under the published ones; layer ``i`` is ``layer_types[i]``.

The chip holds ``num_experts`` of the router's ``published.num_experts``
experts, from ``moe.first_held`` on (the configuration's deployment):
each layer adds gate x expert(x) for the routes to those experts only,
each held expert computed densely for every token with its gate weight
(zero where the token did not choose it).  What the absent experts would
add is left out here as in the program.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 first (accumulation stays float32), the step below the bf16
the configuration serves in.

:func:`counts` gives the least work of a step without knowing the routes
(:class:`MoECounts`), and the routed terms the benchmark's expert readers
add from the routes the program reports.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.roofline import ELEM_BYTES

F32 = jnp.float32
NEG = -1e30
SLIDING = "sliding_attention"


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


def rope_parameters(conf: dict, kind: str) -> Tuple[np.ndarray, float]:
    """The inverse frequencies (head_dim/2,) and the cos/sin factor of a
    layer of ``kind``, from ``rope_parameters[kind]``."""
    p = conf["rope_parameters"][kind]
    hd, theta = conf["head_dim"], p["rope_theta"]
    wavelength = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if p["rope_type"] == "default":
        return (1.0 / wavelength).astype(np.float32), 1.0
    if p["rope_type"] != "yarn":
        raise ValueError(f"rope_type {p['rope_type']!r}")
    factor = p["factor"]
    orig = p["original_max_position_embeddings"]

    def dim_at(rotations):
        return hd * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(dim_at(p["beta_fast"])), 0)
    hi = min(math.ceil(dim_at(p["beta_slow"])), hd - 1)
    ramp = np.clip((np.arange(hd // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    inv = (1.0 / wavelength) * (1 - ramp) + (1.0 / (factor * wavelength)) * ramp
    scale = p.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), float(scale)


def _rope(x, pos, inv, scale):
    """x: (b, s, n, h); rotate-half rotary embedding."""
    h = x.shape[-1]
    ang = pos[..., None].astype(F32) * jnp.asarray(inv)    # (b, s, h/2)
    cos = jnp.cos(ang)[:, :, None] * scale
    sin = jnp.sin(ang)[:, :, None] * scale
    x1, x2 = x[..., :h // 2], x[..., h // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _embed(embed, tokens, cfg, precision):
    return jnp.take(embed, tokens, axis=0).astype(F32)


def _experts(h, m, cfg, precision):
    """The held experts' part of the MoE output for h: (b, s, d) f32."""
    k, first = cfg["num_experts_per_tok"], cfg["moe"]["first_held"]
    held = cfg["num_experts"]
    logits = _mm("bsd,de->bse", h, m["router"], precision)
    probs = jax.nn.softmax(logits, axis=-1)
    top, ids = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    # gate of each held expert for each token: its weight if chosen, else 0
    mine = first + jnp.arange(held)
    gate = jnp.sum(jnp.where(ids[..., None] == mine, top[..., None], 0.0),
                   axis=-2)                                # (b, s, held)
    g = _mm("bsd,edf->bsef", h, m["wg"], precision)
    u = _mm("bsd,edf->bsef", h, m["wi"], precision)
    a = jax.nn.silu(g) * u * gate[..., None]
    return _mm("bsef,efd->bsd", a, m["wo"], precision)


@functools.partial(jax.jit, static_argnames=("kind", "cfg", "precision"))
def _layer(x, layers, i, kind, cfg, precision):
    """Block ``i``, of ``kind``, of the stacked bf16 ``layers`` on x:
    (b, s, d) f32."""
    lw = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, 0, keepdims=False), layers)
    eps = cfg["rms_norm_eps"]
    b, s, _ = x.shape
    nq, nkv = cfg["num_heads"], cfg["num_kv_heads"]
    inv, scale = rope_parameters(cfg, kind)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = _rms(x, lw["ln1"], eps)
    a = lw["attn"]
    q = _rope(_mm("bsd,dnh->bsnh", h, a["wq"], precision), pos, inv, scale)
    k = _rope(_mm("bsd,dnh->bsnh", h, a["wk"], precision), pos, inv, scale)
    v = _mm("bsd,dnh->bsnh", h, a["wv"], precision)
    q = q.reshape(b, s, nkv, nq // nkv, -1) * q.shape[-1] ** -0.5
    sc = _mm("bqkgh,bskh->bkgqs", q, k, precision)
    qi, ki = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = ki <= qi
    if kind == SLIDING:
        mask &= ki > qi - cfg["sliding_window"]
    sc = jnp.where(mask, sc, NEG)
    p = jax.nn.softmax(sc, axis=-1)
    o = _mm("bkgqs,bskh->bqkgh", p, v, precision).reshape(b, s, nq, -1)
    x = x + _mm("bsnh,nhd->bsd", o, a["wo"], precision)
    return x + _experts(_rms(x, lw["ln2"], eps), lw["moe"], cfg, precision)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _head(x, final_norm, lm_head, probe, cfg, precision):
    """Returns, per position, the reference's best logit less the logit
    of ``probe``'s token there, and the reference's own top token."""
    logits = _mm("bsd,dv->bsv", _rms(x, final_norm, cfg["rms_norm_eps"]),
                 lm_head, precision)
    best = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, probe[..., None], axis=-1)[..., 0]
    return best - at, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def hidden(weights, tokens, cfg: dict, precision: str = "f32"):
    """The last layer's output over ``tokens`` (b, s): (b, s, d) f32."""
    x = _embed(weights["embed"], tokens, cfg=cfg, precision=precision)
    for i, kind in enumerate(cfg["layer_types"][:cfg["num_layers"]]):
        x = _layer(x, weights["layers"], jnp.int32(i), kind=kind, cfg=cfg,
                   precision=precision)
    return x


def gaps(weights, tokens, probe, cfg: dict, precision: str = "f32"):
    """Run the reference over ``tokens`` (b, s) int32 and read, at each
    position, how far ``probe``'s token (b, s) lies below its best logit.
    Returns ``(gap, top)``, both (b, s).  ``cfg`` is the configuration
    file's content as :func:`frozen` makes it."""
    return _head(hidden(weights, tokens, cfg, precision),
                 weights["final_norm"], weights["lm_head"], probe, cfg=cfg,
                 precision=precision)


@dataclass(frozen=True)
class MoECounts:
    """The least work of a step of the sparse decoder, and the terms the
    routes add.

    Without the routes, the least is the pigeonhole one: a token takes
    ``top_k`` distinct experts, so at least ``top_k - (experts - held)``
    of them are held here (none where the absent experts can take every
    route), and all tokens may share the same ones.  A windowed layer
    attends, at a decode step, to at most ``window`` positions of each
    row; from the rows and the positions they attend in all, the least is
    one row taking all but one position each of the others.  Weights
    count at 2 bytes (the router's too); the embedding is a lookup.
    """

    layers: int
    sliding_layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_expert: int
    experts: int
    held: int
    top_k: int
    vocab: int
    window: int

    @classmethod
    def from_config(cls, c: dict) -> "MoECounts":
        layers = c["num_layers"]
        return cls(layers, c["layer_types"][:layers].count(SLIDING),
                   c["d_model"], c["num_heads"], c["num_kv_heads"],
                   c["head_dim"], c["moe_intermediate_size"],
                   c["published"]["num_experts"], c["num_experts"],
                   c["num_experts_per_tok"], c["vocab_size"],
                   c["sliding_window"])

    # ---------------------------------------------------------- routed
    @property
    def route_flops(self) -> int:
        """One route through one expert: gate, up and down projections."""
        return 2 * 3 * self.d_model * self.d_expert

    @property
    def expert_bytes(self) -> int:
        return ELEM_BYTES * 3 * self.d_model * self.d_expert

    def least_routes(self, tokens: int) -> int:
        """Routes to held experts no routing of ``tokens`` avoids, over all
        layers."""
        return (self.layers * tokens
                * max(0, self.top_k - (self.experts - self.held)))

    def least_touched(self, tokens: int) -> int:
        """(layer, held expert) pairs no routing of ``tokens`` avoids."""
        return self.least_routes(1) if tokens else 0

    # ------------------------------------------------------ the rest
    @property
    def layer_params(self) -> int:
        """Weights one token multiplies in one layer besides the experts:
        q, k, v, o and the router."""
        d, hd = self.d_model, self.head_dim
        return (d * (self.heads + 2 * self.kv_heads) * hd
                + self.heads * hd * d + d * self.experts)

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    def _attended(self, rows: int, attended: int) -> Tuple[int, int]:
        """Positions attended in all by a full and a windowed layer."""
        if not rows:
            return 0, 0
        return attended, min(attended - rows + 1, self.window) + rows - 1

    def attn_flops(self, full: int, windowed: int) -> int:
        per = 4 * self.heads * self.head_dim
        full_layers = self.layers - self.sliding_layers
        return per * (full_layers * full + self.sliding_layers * windowed)

    def kv_bytes(self, full: int, windowed: int) -> int:
        per = ELEM_BYTES * 2 * self.kv_heads * self.head_dim
        full_layers = self.layers - self.sliding_layers
        return per * (full_layers * full + self.sliding_layers * windowed)

    def routed_decode_flops(self, rows: int, attended: int,
                            routes: int) -> int:
        """A decode step of ``rows`` queries over ``attended`` live
        positions in all, whose layers made ``routes`` routes to held
        experts."""
        return (2 * rows * (self.layers * self.layer_params
                            + self.head_params)
                + self.attn_flops(*self._attended(rows, attended))
                + routes * self.route_flops)

    def routed_decode_bytes(self, rows: int, attended: int,
                            touched: int) -> int:
        """The same step reading ``touched`` (layer, held expert) pairs'
        weights."""
        norms = (2 * self.layers + 1) * self.d_model
        weights = ELEM_BYTES * (self.layers * self.layer_params
                                + self.head_params + norms)
        return (weights + touched * self.expert_bytes
                + ELEM_BYTES * rows * self.d_model
                + self.kv_bytes(*self._attended(rows, attended)))

    def decode_flops(self, rows: int, attended: int) -> int:
        return self.routed_decode_flops(rows, attended,
                                        self.least_routes(rows))

    def decode_bytes(self, rows: int, attended: int) -> int:
        return self.routed_decode_bytes(rows, attended,
                                        self.least_touched(rows))

    def routed_prefill_flops(self, cached: int, new: int,
                             routes: int) -> int:
        """A prefill of ``new`` positions after ``cached`` served ones
        whose layers made ``routes`` routes to held experts: the layers
        for each new position, attention over what precedes it (within
        the window in a windowed layer), the head for the last."""
        if new <= 0:
            return 0
        positions = range(cached + 1, cached + new + 1)
        full = sum(positions)
        windowed = sum(min(p, self.window) for p in positions)
        return (2 * new * self.layers * self.layer_params
                + 2 * self.head_params + self.attn_flops(full, windowed)
                + routes * self.route_flops)

    def prefill_flops(self, cached: int, new: int) -> int:
        return self.routed_prefill_flops(cached, new, self.least_routes(new))


def counts(conf: dict) -> MoECounts:
    """The least work of a step, from the configuration file's sizes."""
    return MoECounts.from_config(conf)


def lower(weights, cfg, tokens) -> list:
    """Each kind of layer and the head :func:`gaps` runs, lowered for
    abstract ``weights`` and ``tokens`` (b, s), on ``tokens``' sharding."""
    b, s = tokens.shape
    x = jax.ShapeDtypeStruct((b, s, cfg["d_model"]), F32,
                             sharding=tokens.sharding)
    i = jax.ShapeDtypeStruct((), jnp.int32, sharding=tokens.sharding)
    kinds = list(dict.fromkeys(cfg["layer_types"][:cfg["num_layers"]]))
    return [
        *((f"reference {kind} layer, {b} x {s}", _layer.lower(
            x, weights["layers"], i, kind=kind, cfg=cfg, precision="f32"))
          for kind in kinds),
        ("reference head", _head.lower(
            x, weights["final_norm"], weights["lm_head"], tokens, cfg=cfg,
            precision="f32")),
    ]


class frozen(dict):
    """A configuration dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, v) for k, v in self.items()
                                 if not isinstance(v, (list, dict)))))
