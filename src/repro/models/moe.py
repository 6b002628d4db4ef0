"""Mixture-of-Experts layers: a capacity dispatch for training and a
drop-free one for serving.

Training (:func:`moe_layer`) uses the argsort/segment scheme (Megablocks-style
dense capacity buffers, no (t, E, C) one-hot): tokens are sorted by expert
id, ranked within their expert segment, and scattered into an (E, C, d)
dispatch buffer; routes over capacity are dropped.  The expert dimension
shards over the ``model`` mesh axis (expert parallelism); expert weight
d_model dims shard over ``data`` (FSDP).  GSPMD inserts the all-to-all /
all-gather collectives.

Serving (:func:`moe_served`) never drops a route.  The layer holds experts
``first_held .. first_held + num_held - 1`` of the router's ``num_experts``
(``MoEConfig``); the router scores all of them, and the layer adds
gate x expert(x) for each route to a held expert: through grouped matmuls
over the routes sorted by expert, or, where the routes touch most held
experts and the tokens are few enough, as one dense product of every
token through every held expert.  A route to an expert held elsewhere
adds nothing here: that part of the output is another chip's.

The expert weights are the layer's arguments, wherever they came from; the
Harvest Expert Rebalancer (repro/core/rebalancer.py) plans expert placement
on its own simulated clock and does not feed this layer.
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


def _held_ids(ids, first, held: int, experts: int):
    """Top-k ids as indices into the held experts ``first .. first + held
    - 1``; a route to an expert held elsewhere reads ``held``, past every
    held one."""
    if held == experts:
        return ids
    local = ids - first
    return jnp.where((local >= 0) & (local < held), local, held)


#: A served share computes its held experts as one dense product over all
#: tokens (:func:`_dense_experts`) when the router's experts expect at
#: least this many of the tokens' routes each, and the product spans at
#: most ``DENSE_MAX_PAIRS`` (token, held expert) pairs.  Both from timing
#: the two paths on a TPU v5e at Mellum2's widths, 16 of 64 experts held,
#: top 1 to 8, 32 to 4,096 tokens (``benchmarks/moe_path_probe.py``):
#: the grouped matmuls read the experts the routes touch at about a fifth
#: of HBM bandwidth, the dense product all held experts at ~80%, so at half
#: a route an expert (top 1 over 32 tokens, 38% of the held experts
#: touched) dense took 0.30 ms a layer and grouped 0.51.  Past its read,
#: the dense product's work grows with tokens x held experts: at 16 held
#: it led at every top-k up to 1,024 tokens (1.42 ms against 1.76-2.97)
#: and trailed at 2,048 for top 1 and 2 (2.67 against 2.00-2.28).  At top
#: 8 it still led at 4,096 (5.14 against 8.74), but its (held, t, ...)
#: intermediates grow with every token, so the bound stays.
DENSE_MIN_ROUTES_PER_EXPERT = 0.5
DENSE_MAX_PAIRS = 16 * 1024


def dense_experts(tokens: int, top_k: int, num_experts: int,
                  held: int) -> bool:
    """Whether a served share over ``tokens`` tokens computes its
    ``held`` experts as one dense product (see ``DENSE_MAX_PAIRS``)."""
    return (tokens * top_k >= DENSE_MIN_ROUTES_PER_EXPERT * num_experts
            and tokens * held <= DENSE_MAX_PAIRS)


def _route(xt, router, cfg: ModelConfig, first, held: int):
    """Top-k routes of tokens ``xt`` (t, d): (local (t, k) indices into
    the held experts ``first .. first + held - 1``, ``held`` for a route to
    an expert held elsewhere; weights (t, k) float32; routes (held,) int32
    to each held expert)."""
    logits = jnp.einsum("td,de->te", xt, router,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
    weights, ids, _ = router_topk(logits, cfg.moe.top_k)
    local = _held_ids(ids, first, held, cfg.moe.num_experts)
    routes = jnp.zeros((held + 1,), jnp.int32).at[local.reshape(-1)].add(1)
    return local, weights, routes[:held]


def _grouped_experts(xt, p, cfg: ModelConfig, local, weights, routes):
    """The routes to held experts, sorted by expert, through three grouped
    matmuls (``jax.lax.ragged_dot``): (t, d) float32."""
    t, d = xt.shape
    k = local.shape[1]
    held = routes.shape[0]
    local = local.reshape(-1)
    rows = t * min(k, held)
    order = jnp.argsort(local, stable=True)[:rows]
    tok = order // k
    live = jnp.arange(rows) < routes.sum()
    xs = jnp.take(xt, tok, axis=0)
    act = _activation(cfg.activation)
    h = act(jax.lax.ragged_dot(xs, p["wg"], routes)) * jax.lax.ragged_dot(
        xs, p["wi"], routes)
    out = jax.lax.ragged_dot(h, p["wo"], routes)
    # rows past the held routes are in no group: what a backend leaves
    # there is undefined (not zero on a TPU), so they are selected away,
    # not multiplied by a zero gate
    return jnp.zeros((t, d), jnp.float32).at[tok].add(jnp.where(
        live[:, None],
        out.astype(jnp.float32) * weights.reshape(-1)[order][:, None], 0.0))


def _dense_experts(xt, p, cfg: ModelConfig, local, weights, routes):
    """Every token through every held expert, as batched products with
    the expert leading both operands, then each token's routed pairs
    combined: (t, d) float32."""
    t, d = xt.shape
    held = routes.shape[0]
    mine = local[..., None] == jnp.arange(held)                # (t, k, held)
    gate = jnp.sum(jnp.where(mine, weights[..., None], 0.0), axis=1).T
    routed = mine.any(1).T                                     # (held, t)
    xe = jnp.broadcast_to(xt, (held, t, d))
    act = _activation(cfg.activation)
    h = act(jnp.einsum("etd,edf->etf", xe, p["wg"])) * jnp.einsum(
        "etd,edf->etf", xe, p["wi"])
    out = jnp.einsum("etf,efd->etd", h, p["wo"])
    # a pair without a route is selected away, not multiplied by a zero
    # gate, so whatever its product holds adds nothing
    return jnp.sum(jnp.where(routed[..., None],
                             gate[..., None] * out.astype(jnp.float32), 0.0),
                   axis=0)


def _served_share(xt, p, cfg: ModelConfig, first, held: int):
    """The routes of tokens ``xt`` (t, d) to experts ``first .. first +
    held - 1``, whose weights ``p`` holds: (y (t, d) float32, routes
    (held,) int32)."""
    mc = cfg.moe
    local, weights, routes = _route(xt, p["router"], cfg, first, held)
    experts = (_dense_experts
               if dense_experts(xt.shape[0], mc.top_k, mc.num_experts, held)
               else _grouped_experts)
    return experts(xt, p, cfg, local, weights, routes), routes


def moe_served(x, p, cfg: ModelConfig, rules=None):
    """The served MoE sublayer: no route dropped. x: (b, s, d) ->
    (y, routes), ``routes`` (num_held,) int32: the routes to each held
    expert.

    The path is chosen from the shapes alone (:func:`dense_experts`).
    Grouped: routes are sorted by held expert, those to absent experts
    last, and the first ``t * min(top_k, num_held)`` of them, a bound no
    token's routes can pass, run through three grouped matmuls
    (``jax.lax.ragged_dot``), so the experts' work grows with the routes
    to them, not with tokens x experts.  Rows past the held routes add
    nothing, whatever the grouped matmul leaves in them.  Dense: every
    token through every held expert, each (token, expert) pair without a
    route selected away.  Either way gates combine in float32 over the
    same bf16 products.

    On a mesh each shard of the expert axis holds its contiguous share of
    the experts, serves the routes to them, and the shares' outputs are
    summed over that axis: expert parallelism with nothing dropped.
    """
    mc = cfg.moe
    b, s, d = x.shape
    eax = rules.axis("experts") if rules is not None else None
    if eax is None:
        y, routes = _served_share(x.reshape(b * s, d), p, cfg,
                                  mc.first_held, mc.num_held)
        y = y.astype(x.dtype).reshape(b, s, d)
    else:
        y, routes = _served_sharded(x, p, cfg, rules, eax)
    if mc.num_shared_experts:
        y = y + mlp(x, p["shared"], cfg, rules)
    return y, routes


def _served_sharded(x, p, cfg: ModelConfig, rules, eax):
    """:func:`moe_served` over the mesh's expert axis ``eax`` (see there).
    Every expert must be held and the axis must split them evenly."""
    mc = cfg.moe
    esize = rules.axis_size(eax)
    if mc.num_held != mc.num_experts or mc.num_experts % esize:
        raise ValueError(
            f"a mesh serves all {mc.num_experts} experts split evenly over "
            f"its expert axis ({esize} shards); this layer holds "
            f"{mc.num_held}")
    held = mc.num_experts // esize
    dax = rules.axis("act_batch")
    if dax is not None and x.shape[0] % rules.axis_size(dax):
        dax = None
    d = x.shape[-1]

    def share(xl, router, wi, wg, wo):
        first = jax.lax.axis_index(eax) * held
        y, routes = _served_share(
            xl.reshape(-1, d), dict(router=router, wi=wi, wg=wg, wo=wo),
            cfg, first, held)
        y = jax.lax.psum(y, eax).astype(xl.dtype).reshape(xl.shape)
        if dax is not None:
            routes = jax.lax.psum(routes, dax)
        return y, routes

    y, routes = jax.shard_map(
        share, mesh=rules.mesh,
        in_specs=(P(dax), P(), P(eax), P(eax), P(eax)),
        out_specs=(P(dax), P(eax)), check_vma=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    return shard(y, rules, "act_batch", "act_seq", "act_embed"), routes


def moe_layer(x, p, cfg: ModelConfig, rules=None,
              capacity_factor: Optional[float] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The training MoE FFN sublayer. x: (b, s, d) -> (y, aux_loss).

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
            and mc.num_held == mc.num_experts
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
    slot_token, slot_weight = build_dispatch(
        _held_ids(ids, mc.first_held, mc.num_held, mc.num_experts), weights,
        mc.num_held, capacity)

    # gather tokens into (E, C, d); empty slots read token index t -> fill 0.
    # (This path trains on one device and on indivisible meshes;
    # batch-sharded training uses _moe_layer_local.)
    xd = jnp.take(xt, slot_token, axis=0, mode="fill", fill_value=0)
    xd = xd.reshape(mc.num_held, capacity, d)
    xd = shard(xd, rules, "experts", None, None)

    out = expert_ffn(xd, p, cfg, rules)            # (E, C, d)
    out = out.reshape(mc.num_held * capacity, d)

    y = jnp.zeros((t + 1, d), x.dtype)             # row t = drop bucket
    y = y.at[slot_token].add(out * slot_weight[:, None].astype(x.dtype))
    y = y[:t]

    if mc.num_shared_experts:
        y = y + mlp(xt[None], p["shared"], cfg, rules)[0]

    y = y.reshape(b, s, d)
    y = shard(y, rules, "act_batch", "act_seq", "act_embed")
    return y, aux


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
