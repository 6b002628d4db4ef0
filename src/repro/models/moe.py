"""Mixture-of-Experts layer with capacity-based sort dispatch.

Dispatch is the argsort/segment scheme (Megablocks-style dense capacity
buffers, no (t, E, C) one-hot): tokens are sorted by expert id, ranked within
their expert segment, and scattered into an (E, C, d) dispatch buffer.  The
expert dimension shards over the ``model`` mesh axis (expert parallelism);
expert weight d_model dims shard over ``data`` (FSDP).  GSPMD inserts the
all-to-all / all-gather collectives.

The Harvest Expert Rebalancer (repro/core/rebalancer.py) manages *which* copy
of each expert's weights is fed here (local HBM / harvested peer HBM / host
DRAM) — the math below is placement-agnostic, which is exactly the paper's
"no model code changes" property.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import _activation, mlp
from repro.models.sharding import shard


def router_topk(logits, top_k: int):
    """Top-k routing with softmax-renormalised gate weights.

    logits: (t, E) float32. Returns (weights (t,k), ids (t,k), probs (t,E)).
    """
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights, ids, probs


def load_balance_loss(probs, ids, num_experts: int) -> jnp.ndarray:
    """Switch-style auxiliary loss: E * sum_e f_e * P_e."""
    t = probs.shape[0]
    counts = jnp.zeros((num_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(ids.size, 1)
    mean_probs = probs.mean(axis=0)
    return num_experts * jnp.sum(frac_tokens * mean_probs)


def build_dispatch(ids, weights, num_experts: int, capacity: int):
    """Build capacity-buffer dispatch indices from top-k assignments.

    ids/weights: (t, k).  Returns
      slot_token: (E*C,) int32 — token index feeding each expert slot (t = empty)
      slot_weight: (E*C,) f32 — combine weight for that slot
    Tokens over capacity are dropped (standard capacity-factor semantics).
    """
    t, k = ids.shape
    flat_ids = ids.reshape(-1)                     # (t*k,)
    flat_w = weights.reshape(-1)
    token_of = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    order = jnp.argsort(flat_ids, stable=True)     # group by expert
    sorted_ids = flat_ids[order]
    # rank within expert segment
    seg_start = jnp.searchsorted(sorted_ids, jnp.arange(num_experts), side="left")
    rank = jnp.arange(t * k, dtype=jnp.int32) - seg_start[sorted_ids].astype(jnp.int32)

    slot = sorted_ids.astype(jnp.int32) * capacity + rank
    slot = jnp.where(rank < capacity, slot, num_experts * capacity)  # drop OOB

    slot_token = jnp.full((num_experts * capacity,), t, jnp.int32)
    slot_token = slot_token.at[slot].set(token_of[order], mode="drop")
    slot_weight = jnp.zeros((num_experts * capacity,), jnp.float32)
    slot_weight = slot_weight.at[slot].set(flat_w[order], mode="drop")
    return slot_token, slot_weight


def expert_ffn(xd, p, cfg: ModelConfig, rules=None):
    """Apply each expert's FFN to its dispatch buffer.

    xd: (E, C, d);  p["wi"]/p["wg"]: (E, d, ffe);  p["wo"]: (E, ffe, d).
    """
    act = _activation(cfg.activation)
    h = jnp.einsum("ecd,edf->ecf", xd, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", xd, p["wg"])
    h = act(g) * h
    h = shard(h, rules, "experts", None, None)
    return jnp.einsum("ecf,efd->ecd", h, p["wo"])


def moe_layer(x, p, cfg: ModelConfig, rules=None,
              capacity_factor: Optional[float] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN sublayer. x: (b, s, d) -> (y, aux_loss).

    With a batch-sharded mesh the dispatch/combine runs LOCALLY per data
    shard (shard_map over the batch axis, expert axis left to GSPMD):
    a global (E, C, d) buffer built from batch-sharded tokens forces either
    full replication of the expert compute across the data axis or an
    all-reduce of the combined (t, d) output — both measured catastrophic
    (EXPERIMENTS.md §Perf iterations 4-5).  Locally, each data shard routes
    its own t/16 tokens into per-shard capacity buffers; the only cross-
    shard traffic left is the per-layer FSDP weight gather and the combine
    psum over the expert (model) axis.
    """
    mc = cfg.moe
    if capacity_factor is None:
        capacity_factor = mc.capacity_factor
    ax = rules.axis("act_batch") if rules is not None else None
    eax = rules.axis("experts") if rules is not None else None
    if (ax is not None and eax is not None
            and x.shape[0] % rules.axis_size(ax) == 0
            and mc.num_experts % rules.axis_size(eax) == 0):
        return _moe_layer_local(x, p, cfg, rules, capacity_factor)
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xt, p["router"]).astype(jnp.float32)
    if mc.router_jitter:
        logits = logits  # jitter only in training loops that thread an rng
    weights, ids, probs = router_topk(logits, mc.top_k)
    aux = load_balance_loss(probs, ids, mc.num_experts)

    capacity = max(int(t * mc.top_k / mc.num_experts * capacity_factor), 4)
    slot_token, slot_weight = build_dispatch(ids, weights, mc.num_experts, capacity)

    # gather tokens into (E, C, d); empty slots read token index t -> fill 0.
    # (This path serves CPU/smoke runs, indivisible meshes and the
    # batch-replicated decode shardings; batch-sharded training uses
    # _moe_layer_local.  An "expert_capacity"@data constraint here was
    # measured to REGRESS decode — §Perf iteration 4 — and is superseded.)
    xd = jnp.take(xt, slot_token, axis=0, mode="fill", fill_value=0)
    xd = xd.reshape(mc.num_experts, capacity, d)
    xd = shard(xd, rules, "experts", None, None)

    out = expert_ffn(xd, p, cfg, rules)            # (E, C, d)
    out = out.reshape(mc.num_experts * capacity, d)

    y = jnp.zeros((t + 1, d), x.dtype)             # row t = drop bucket
    y = y.at[slot_token].add(out * slot_weight[:, None].astype(x.dtype))
    y = y[:t]

    if mc.num_shared_experts:
        y = y + mlp(xt[None], p["shared"], cfg, rules)[0]

    y = y.reshape(b, s, d)
    y = shard(y, rules, "act_batch", "act_seq", "act_embed")
    return y, aux


def _moe_core(xt, p, cfg: ModelConfig, rules, capacity_factor: float):
    """Router + dispatch + expert FFN + combine over a flat token batch."""
    mc = cfg.moe
    t, d = xt.shape
    logits = jnp.einsum("td,de->te", xt, p["router"]).astype(jnp.float32)
    weights, ids, probs = router_topk(logits, mc.top_k)
    aux = load_balance_loss(probs, ids, mc.num_experts)

    capacity = max(int(t * mc.top_k / mc.num_experts * capacity_factor), 4)
    slot_token, slot_weight = build_dispatch(ids, weights, mc.num_experts,
                                             capacity)
    xd = jnp.take(xt, slot_token, axis=0, mode="fill", fill_value=0)
    xd = xd.reshape(mc.num_experts, capacity, d)
    xd = shard(xd, rules, "experts", None, None)

    out = expert_ffn(xd, p, cfg, rules)            # (E, C, d)
    out = out.reshape(mc.num_experts * capacity, d)
    y = jnp.zeros((t + 1, d), xt.dtype)            # row t = drop bucket
    y = y.at[slot_token].add(out * slot_weight[:, None].astype(xt.dtype))
    return y[:t], aux


def _moe_layer_local(x, p, cfg: ModelConfig, rules,
                     capacity_factor: float):
    """Fully-manual expert parallelism (shard_map over BOTH mesh axes).

    Per (data i, model j) device: route the local t/|data| tokens with the
    (replicated) router, keep the E/|model| experts owned by j, gather the
    FSDP-sharded expert weights over the data axis, run the FFN, and psum
    the combined output over the model axis.  Explicit collectives per
    layer: weight all-gather (~weights/|model| bytes) + combine psum
    (~2 x local activations) — versus the global-dispatch path whose
    (E, C, d) buffer is replicated over data (16x redundant FLOPs) or
    all-reduced whole (§Perf iterations 4-5).
    """
    mc = cfg.moe
    b, s_len, d = x.shape
    dax = rules.axis("act_batch")
    eax = rules.axis("experts")
    dsize, esize = rules.axis_size(dax), rules.axis_size(eax)
    if mc.num_experts % esize:
        raise ValueError(f"{mc.num_experts} experts not divisible by "
                         f"expert axis {esize}")
    e_loc = mc.num_experts // esize
    b_loc = b // dsize
    t_loc = b_loc * s_len

    def local(xl, router, wi, wg, wo):
        # gather FSDP (data-axis) weight shards; experts stay local to j
        router = jax.lax.all_gather(router, dax, axis=0, tiled=True)
        wi = jax.lax.all_gather(wi, dax, axis=1, tiled=True)
        wg = jax.lax.all_gather(wg, dax, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, dax, axis=2, tiled=True)

        xt = xl.reshape(t_loc, d)
        logits = jnp.einsum("td,de->te", xt, router).astype(jnp.float32)
        weights, ids, probs = router_topk(logits, mc.top_k)
        aux = load_balance_loss(probs, ids, mc.num_experts)

        capacity = max(int(t_loc * mc.top_k / mc.num_experts
                           * capacity_factor), 4)
        slot_token, slot_weight = build_dispatch(ids, weights,
                                                 mc.num_experts, capacity)
        e0 = jax.lax.axis_index(eax) * (e_loc * capacity)
        own_tok = jax.lax.dynamic_slice_in_dim(slot_token, e0,
                                               e_loc * capacity)
        own_w = jax.lax.dynamic_slice_in_dim(slot_weight, e0,
                                             e_loc * capacity)

        xd = jnp.take(xt, own_tok, axis=0, mode="fill", fill_value=0)
        xd = xd.reshape(e_loc, capacity, d)
        act = _activation(cfg.activation)
        h = jnp.einsum("ecd,edf->ecf", xd, wi)
        g = jnp.einsum("ecd,edf->ecf", xd, wg)
        out = jnp.einsum("ecf,efd->ecd", act(g) * h, wo)
        out = out.reshape(e_loc * capacity, d)

        y = jnp.zeros((t_loc + 1, d), xt.dtype)    # row t_loc = drop bucket
        y = y.at[own_tok].add(out * own_w[:, None].astype(xt.dtype))
        y = jax.lax.psum(y, eax)                   # combine across experts
        return y[:t_loc].reshape(b_loc, s_len, d), aux[None] / dsize

    daxes = (dax,) if isinstance(dax, str) else tuple(dax)
    eaxes = (eax,) if isinstance(eax, str) else tuple(eax)
    y, aux = jax.shard_map(
        local, mesh=rules.mesh,
        in_specs=(P(daxes), P(daxes), P(eaxes, daxes), P(eaxes, daxes),
                  P(eaxes, None, daxes)),
        out_specs=(P(daxes), P(daxes)),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    y = shard(y, rules, "act_batch", "act_seq", "act_embed")
    if mc.num_shared_experts:
        y = y + mlp(x, p["shared"], cfg, rules)
    return y, aux.sum()
