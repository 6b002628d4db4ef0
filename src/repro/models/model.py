"""Unified decoder-only model covering all assigned families.

Entry points:
  forward(params, batch, cfg, rules)            -> (logits, aux)   train/score
  loss_fn(params, batch, cfg, rules)            -> (loss, metrics)
  prefill(params, batch, cfg, rules)            -> (logits_last, KVStack, states)
  serve_step(params, state, cfg, rules, ...)    -> (logits, new_state)  1 token

Layers are stacked and scanned (``lax.scan`` + remat) so the HLO stays
compact at 80 layers.  Decode attention runs against the tiered Harvest KV
pools (repro/core/paged_attention), with the pool slot dimension sharded
across the whole mesh (flash-decode partials + LSE merge).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import paged_attention as pa
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import xlstm as X
from repro.models.moe import moe_layer, moe_served
from repro.models.sharding import ShardingRules, shard
from repro.models.params import (  # noqa: F401  (re-exported)
    abstract_params, build_schema, init_params, param_count, param_specs)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed(params, tokens, cfg: ModelConfig, rules=None):
    if cfg.family == "audio" and cfg.modality.num_codebooks > 1:
        # tokens: (b, s, ncb) — sum the codebook embeddings (MusicGen)
        ncb = cfg.modality.num_codebooks
        x = sum(jnp.take(params["embed"][c], tokens[..., c], axis=0)
                for c in range(ncb))
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    return shard(x, rules, "act_batch", "act_seq", "act_embed")


def unembed(params, x, cfg: ModelConfig, rules=None):
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.family == "audio" and cfg.modality.num_codebooks > 1:
        logits = jnp.einsum("bsd,cdv->bscv", x, params["lm_head"])
    elif cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return shard(logits, rules, "act_batch", "act_seq", "vocab")


# ---------------------------------------------------------------------------
# Dense / MoE transformer blocks (full-sequence path)
# ---------------------------------------------------------------------------


def _attn_sublayer(x, lp, cfg, positions, rules, positions_3d, kind=None):
    u = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    a, kv = L.attention_layer(u, lp["attn"], cfg, positions, rules,
                              positions_3d, kind)
    return u, a, kv


def _moe_sublayer(u, lp, cfg: ModelConfig, rules, train: bool):
    """The MoE sublayer: ``(y, aux, routes)``.  For the training loss it
    is the capacity dispatch and its aux loss (routes None); every other
    pass serves without drops over the held experts (``routes``: (held,)
    int32 routes to each, aux 0)."""
    if train:
        y, aux = moe_layer(u, lp["moe"], cfg, rules)
        return y, aux, None
    y, routes = moe_served(u, lp["moe"], cfg, rules)
    return y, jnp.zeros((), jnp.float32), routes


def dense_block(x, lp, cfg: ModelConfig, positions, rules=None,
                positions_3d=None, is_moe=False, kind=None, train=False):
    """Pre-LN block. Returns (x, kv, aux, routes); ``routes`` is None but
    for a served MoE block (see :func:`_moe_sublayer`)."""
    aux = jnp.zeros((), jnp.float32)
    routes = None
    if cfg.parallel_block:
        u, a, kv = _attn_sublayer(x, lp, cfg, positions, rules, positions_3d)
        m = L.mlp(u, lp["mlp"], cfg, rules)
        x = x + a + m
        return x, kv, aux, routes
    u, a, kv = _attn_sublayer(x, lp, cfg, positions, rules, positions_3d,
                              kind)
    x = x + a
    u2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if is_moe:
        y, aux, routes = _moe_sublayer(u2, lp, cfg, rules, train)
    else:
        y = L.mlp(u2, lp["mlp"], cfg, rules)
    x = x + y
    x = shard(x, rules, "act_batch", "act_seq", "act_embed")
    return x, kv, aux, routes


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


class ForwardOut(NamedTuple):
    hidden: jnp.ndarray          # (b, s, d)
    kv: Optional[Any]            # stacked (L_kv, b, s, nkv, hd) k and v
    states: Optional[Any]        # SSM / xLSTM final states
    aux: jnp.ndarray             # scalar aux loss (MoE load balance)
    routes: Optional[Any] = None  # served MoE: (L_moe, held) int32 routes


def _scan(body, x, stacks, length=None):
    return jax.lax.scan(jax.checkpoint(body), x, stacks, length=length)


def backbone(params, x, positions, cfg: ModelConfig, rules=None,
             positions_3d=None, want_kv: bool = True,
             train: bool = False) -> ForwardOut:
    """``want_kv`` returns the cache material; ``train`` runs MoE layers
    through the training dispatch and its aux loss, and otherwise they
    serve without drops and count their routes."""
    fam = cfg.family

    if fam in ("dense", "vlm", "audio") or (fam == "moe" and cfg.moe.layer_period == 1):
        is_moe = fam == "moe"

        def body(h, xs):
            lp, kind = xs
            h, kv, aux, routes = dense_block(
                h, lp, cfg, positions, rules, positions_3d, is_moe=is_moe,
                kind=kind, train=train)
            return h, (kv if want_kv else None, aux, routes)

        x, (kvs, auxs, routes) = _scan(
            body, x, (params["layers"], L.layer_kinds(cfg)))
        return ForwardOut(x, kvs, None, auxs.sum(), routes)

    if fam == "moe":  # interleaved dense/moe pairs (llama4)
        def body(h, lps):
            dlp, mlp_ = lps
            h, kv1, _, _ = dense_block(h, dlp, cfg, positions, rules,
                                       positions_3d, is_moe=False)
            h, kv2, aux, routes = dense_block(h, mlp_, cfg, positions, rules,
                                              positions_3d, is_moe=True,
                                              train=train)
            kv = jax.tree.map(lambda a, b: jnp.stack([a, b]), kv1, kv2) \
                if want_kv else None
            return h, (kv, aux, routes)

        x, (kvs, auxs, routes) = _scan(body, x, (params["blocks"]["dense"],
                                                 params["blocks"]["moe"]))
        # (n_pairs, 2, b, s, nkv, hd) -> (L, b, s, nkv, hd)
        if want_kv:
            kvs = jax.tree.map(lambda t: t.reshape((-1,) + t.shape[2:]), kvs)
        return ForwardOut(x, kvs, None, auxs.sum(), routes)

    if fam == "hybrid":
        return _hybrid_backbone(params, x, positions, cfg, rules,
                                want_kv=want_kv)

    if fam == "ssm":
        return _xlstm_backbone(params, x, cfg, rules)

    raise ValueError(fam)


def _hybrid_backbone(params, x, positions, cfg: ModelConfig, rules=None,
                     in_states=None, single_token=False, want_kv=True):
    """Zamba2: mamba2 stack with ONE shared attention block every N layers."""
    per = cfg.hybrid.attn_period
    n_super = cfg.num_layers // per
    n_tail = cfg.num_layers - n_super * per
    shared = params["shared_attn"]

    def mamba_one(h, lp, st):
        u = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
        y, new_st = S.mamba2_layer(u, lp["mamba"], cfg, rules, st, single_token)
        return h + y, new_st

    def split_stack(tree, n_head, inner):
        head = jax.tree.map(lambda t: t[:n_head * inner].reshape(
            (n_head, inner) + t.shape[1:]), tree)
        tail = jax.tree.map(lambda t: t[n_head * inner:], tree)
        return head, tail

    head_params, tail_params = split_stack(params["mamba_layers"], n_super, per)
    if in_states is None:
        st0 = S.init_ssm_state(cfg, x.shape[0])
        states_head = jax.tree.map(
            lambda t: jnp.broadcast_to(t, (n_super, per) + t.shape), st0)
        states_tail = jax.tree.map(
            lambda t: jnp.broadcast_to(t, (n_tail,) + t.shape), st0)
    else:
        states_head, states_tail = split_stack(in_states, n_super, per)

    def super_body(h, inp):
        lps, sts = inp

        def inner(h2, inp2):
            lp, st = inp2
            h2, new_st = mamba_one(h2, lp, S.SSMState(*st))
            return h2, tuple(new_st)

        h, new_sts = jax.lax.scan(inner, h, (lps, tuple(sts)))
        u, a, kv = _attn_sublayer(h, shared, cfg, positions, rules, None)
        h = h + a
        h = h + L.mlp(L.rms_norm(h, shared["ln2"], cfg.norm_eps), shared["mlp"],
                      cfg, rules)
        return h, (new_sts, kv if want_kv else None)

    x, (new_head_states, kvs) = _scan(super_body, x,
                                      (head_params, tuple(states_head)))

    def tail_body(h, inp):
        lp, st = inp
        h, new_st = mamba_one(h, lp, S.SSMState(*st))
        return h, tuple(new_st)

    if n_tail:
        x, new_tail_states = jax.lax.scan(tail_body, x,
                                          (tail_params, tuple(states_tail)))
    else:
        new_tail_states = tuple(states_tail)

    states = jax.tree.map(
        lambda a, b: jnp.concatenate([a.reshape((-1,) + a.shape[2:]), b]),
        S.SSMState(*new_head_states), S.SSMState(*new_tail_states))
    return ForwardOut(x, kvs, states, jnp.zeros((), jnp.float32))


def _xlstm_backbone(params, x, cfg: ModelConfig, rules=None,
                    in_states=None, single_token=False):
    per = cfg.xlstm.slstm_every
    n_super = cfg.num_layers // per
    b = x.shape[0]

    if in_states is None:
        m0 = X.init_mlstm_state(cfg, b)
        s0 = X.init_slstm_state(cfg, b)
        m_states = jax.tree.map(
            lambda t: jnp.broadcast_to(t, (n_super, per - 1) + t.shape), m0)
        s_states = jax.tree.map(
            lambda t: jnp.broadcast_to(t, (n_super,) + t.shape), s0)
    else:
        m_states, s_states = in_states

    def super_body(h, inp):
        mlps, msts, slp, sst = inp

        def inner(h2, inp2):
            lp, st = inp2
            h2, new_st = X.mlstm_block(h2, lp, cfg, rules, X.MLSTMState(*st),
                                       single_token)
            return h2, tuple(new_st)

        h, new_msts = jax.lax.scan(inner, h, (mlps, tuple(msts)))
        h, new_sst = X.slstm_block(h, slp, cfg, rules, X.SLSTMState(*sst),
                                   single_token)
        return h, (new_msts, tuple(new_sst))

    x, (new_m, new_s) = _scan(
        super_body, x,
        (params["supers"]["mlstm"], tuple(m_states),
         params["supers"]["slstm"], tuple(s_states)))
    states = (X.MLSTMState(*new_m), X.SLSTMState(*new_s))
    return ForwardOut(x, None, states, jnp.zeros((), jnp.float32))


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def forward(params, batch, cfg: ModelConfig, rules=None, want_kv: bool = False,
            train: bool = False):
    """batch: dict(tokens, positions[, prefix_embeddings, positions_3d]).
    ``train``: the pass the training loss differentiates."""
    x = embed(params, batch["tokens"], cfg, rules)
    if cfg.modality is not None and cfg.modality.num_prefix_embeddings:
        # frontend stub: precomputed patch/frame/conditioning embeddings
        x = jnp.concatenate(
            [batch["prefix_embeddings"].astype(x.dtype), x], axis=1)
    positions = batch["positions"]
    if cfg.rope_style == "none" and cfg.family == "audio":
        x = x + L.sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)[..., :x.shape[-1]]
    out = backbone(params, x, positions, cfg, rules,
                   batch.get("positions_3d"), want_kv=want_kv, train=train)
    logits = unembed(params, out.hidden, cfg, rules)
    return logits, out


def loss_fn(params, batch, cfg: ModelConfig, rules=None):
    logits, out = forward(params, batch, cfg, rules, train=True)
    labels = batch["labels"]
    npre = (cfg.modality.num_prefix_embeddings if cfg.modality else 0)
    if npre:
        logits = logits[:, npre:]
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    if labels.ndim != logits.ndim - 1:          # (b, s) or (b, s, ncb)
        raise ValueError("labels must be one rank below logits")
    # one-hot contraction instead of take_along_axis: a vocab-dim gather
    # forces GSPMD to all-gather the (b, s, V) logits; the one-hot product
    # reduces over the sharded vocab axis locally + psum.
    onehot = jax.nn.one_hot(jnp.maximum(labels, 0), logits.shape[-1],
                            dtype=logits.dtype)
    onehot = shard(onehot, rules,
                   "act_batch", *((None,) * (logits.ndim - 2)), "vocab")
    tgt = jnp.sum(logits.astype(jnp.float32) * onehot.astype(jnp.float32),
                  axis=-1)
    mask = (labels >= 0).astype(jnp.float32)
    nll = jnp.sum((lse - tgt) * mask) / jnp.maximum(mask.sum(), 1.0)
    aux = out.aux * (cfg.moe.lb_loss_weight if cfg.moe else 0.0)
    return nll + aux, {"nll": nll, "aux": out.aux}


def prefill(params, batch, cfg: ModelConfig, rules=None):
    """Full-sequence pass returning last-token logits + cache material."""
    logits, out = forward(params, batch, cfg, rules, want_kv=True)
    return logits[:, -1], out


# ---------------------------------------------------------------------------
# Decode (serve_step) — one token against the Harvest-tiered KV pools
# ---------------------------------------------------------------------------


class KVPools(NamedTuple):
    """Paged KV state shared across attention layers (slot dim shardable)."""
    pool_k: jnp.ndarray      # (L_kv, n_slots, bs, nkv, hd)
    pool_v: jnp.ndarray
    slot_req: jnp.ndarray    # (n_slots,) int32, -1 = free
    slot_base: jnp.ndarray   # (n_slots,) int32 first position of block
    append_slot: jnp.ndarray  # (b,) int32 global slot receiving this step's kv
    append_off: jnp.ndarray   # (b,) int32 offset within that slot


class DecodeState(NamedTuple):
    tokens: jnp.ndarray      # (b,) or (b, ncb) last emitted token(s)
    pos: jnp.ndarray         # (b,) int32 current position
    kv: Optional[KVPools]
    peer: Optional[KVPools]  # harvested peer tier (in-place mode)
    states: Optional[Any]    # SSM / xLSTM recurrent states
    positions_3d: Optional[jnp.ndarray] = None
    #: out of a served MoE step: (L_moe, held) int32 routes to each held
    #: expert in each MoE layer; None in, and out of a model without
    routes: Optional[jnp.ndarray] = None


def num_kv_layers(cfg: ModelConfig) -> int:
    if cfg.family == "ssm":
        return 0
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.hybrid.attn_period
    return cfg.num_layers


def _decode_attention_carried(q, pools_full, layer, state, k_new, v_new,
                              cfg, rules, peer_full=None, window=None):
    """One layer's paged attention against the CARRIED full pools.

    ``pools_full``: (L_kv, n_slots, bs, nkv, hd) k and v, loop-carried so the
    append is a 3-index scatter writing only the b updated rows — keeping
    pools as scan xs/ys instead rewrites every layer's full slice each step
    (2x the pool traffic; EXPERIMENTS.md §Perf iteration 3).
    """
    kvp = state.kv
    mesh_shape = dict(rules.mesh.shape) if rules is not None else {}

    def local_fn(q, pkf, pvf, lyr, sr, sb, k_new, v_new, a_slot, a_off,
                 ppkf=None, ppvf=None, psr=None, psb=None, axis_names=()):
        n_slots = pkf.shape[1]
        if axis_names:
            idx = 0
            for a in axis_names:
                idx = idx * mesh_shape[a] + jax.lax.axis_index(a)
            base = idx * n_slots
        else:
            base = 0
        ls = a_slot - base
        ls = jnp.where((ls >= 0) & (ls < n_slots), ls, n_slots)
        pkf = pkf.at[lyr, ls, a_off].set(k_new.astype(pkf.dtype), mode="drop")
        pvf = pvf.at[lyr, ls, a_off].set(v_new.astype(pvf.dtype), mode="drop")
        pk = jax.lax.dynamic_index_in_dim(pkf, lyr, 0, keepdims=False)
        pv = jax.lax.dynamic_index_in_dim(pvf, lyr, 0, keepdims=False)
        pools = [(pk, pv, sr, sb)]
        if ppkf is not None:
            ppk = jax.lax.dynamic_index_in_dim(ppkf, lyr, 0, keepdims=False)
            ppv = jax.lax.dynamic_index_in_dim(ppvf, lyr, 0, keepdims=False)
            pools.append((ppk, ppv, psr, psb))
        out = pa.paged_decode_attention(q, pools, state.pos, cfg, axis_names,
                                        window)
        return out.astype(q.dtype), pkf, pvf

    pkf, pvf = pools_full
    peer_args = ()
    if peer_full is not None:
        pp = state.peer
        peer_args = (peer_full[0], peer_full[1], pp.slot_req, pp.slot_base)

    if rules is None:
        return local_fn(q, pkf, pvf, layer, kvp.slot_req, kvp.slot_base,
                        k_new, v_new, kvp.append_slot, kvp.append_off,
                        *peer_args)

    axes = rules.rules.get("kv_blocks", ("data", "model"))
    if isinstance(axes, str):
        axes = (axes,)
    pool_spec = P(None, axes)
    slot_spec = P(axes)
    rep = P()
    in_specs = [rep, pool_spec, pool_spec, rep, slot_spec, slot_spec,
                rep, rep, rep, rep]
    if peer_args:
        in_specs += [pool_spec, pool_spec, slot_spec, slot_spec]
    fn = functools.partial(local_fn, axis_names=axes)
    return jax.shard_map(
        fn, mesh=rules.mesh, in_specs=tuple(in_specs),
        out_specs=(rep, pool_spec, pool_spec), check_vma=False,
    )(q, pkf, pvf, layer, kvp.slot_req, kvp.slot_base, k_new, v_new,
      kvp.append_slot, kvp.append_off, *peer_args)


def _decode_attention(q, layer_pools, q_pos, cfg, rules, peer_layer_pools=None):
    """One layer's paged attention (+ KV append), mesh-aware."""
    b = q.shape[0]

    mesh_shape = dict(rules.mesh.shape) if rules is not None else {}

    def local_fn(q, pk, pv, sr, sb, k_new, v_new, a_slot, a_off,
                 ppk=None, ppv=None, psr=None, psb=None, axis_names=()):
        n_slots = pk.shape[0]
        if axis_names:
            idx = 0
            for a in axis_names:
                idx = idx * mesh_shape[a] + jax.lax.axis_index(a)
            base = idx * n_slots
        else:
            base = 0
        ls = a_slot - base
        ls = jnp.where((ls >= 0) & (ls < n_slots), ls, n_slots)
        pk, pv = pa.append_kv(pk, pv, k_new, v_new, ls, a_off)
        pools = [(pk, pv, sr, sb)]
        if ppk is not None:
            pools.append((ppk, ppv, psr, psb))
        out = pa.paged_decode_attention(q, pools, q_pos, cfg, axis_names)
        return out.astype(q.dtype), pk, pv

    pk, pv, sr, sb, k_new, v_new, a_slot, a_off = layer_pools
    peer_args = peer_layer_pools or ()

    if rules is None:
        return local_fn(q, pk, pv, sr, sb, k_new, v_new, a_slot, a_off,
                        *peer_args)

    axes = rules.rules.get("kv_blocks", ("data", "model"))
    if isinstance(axes, str):
        axes = (axes,)
    pool_spec = P(axes)
    rep = P()
    in_specs = [rep, pool_spec, pool_spec, pool_spec, pool_spec,
                rep, rep, rep, rep] + [pool_spec] * len(peer_args)
    fn = functools.partial(local_fn, axis_names=axes)
    return jax.shard_map(
        fn, mesh=rules.mesh, in_specs=tuple(in_specs),
        out_specs=(rep, pool_spec, pool_spec), check_vma=False,
    )(q, pk, pv, sr, sb, k_new, v_new, a_slot, a_off, *peer_args)


def _decode_attn_sublayer_carried(x, lp, cfg, state: DecodeState, pools_full,
                                  layer, rules, peer_full=None, kind=None):
    """x: (b, 1, d). Returns (attn_out (b,1,d), updated full pools).
    ``kind``: the layer's row of ``L.layer_kinds`` in a mixed stack."""
    u = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = L.attention_qkv(u, lp["attn"], cfg, rules)
    positions = state.pos[:, None]
    p3 = state.positions_3d[:, None] if state.positions_3d is not None else None
    q = L.position_embedding(q, positions, cfg, p3, kind)
    k = L.position_embedding(k, positions, cfg, p3, kind)
    o, new_pk, new_pv = _decode_attention_carried(
        q[:, 0], pools_full, layer, state, k[:, 0], v[:, 0], cfg, rules,
        peer_full, None if kind is None else kind.window)
    y = jnp.einsum("bnh,nhd->bd", o.astype(x.dtype), lp["attn"]["wo"])
    return y[:, None], (new_pk, new_pv)


def _decode_attn_sublayer(x, lp, cfg, state: DecodeState, layer_kv, rules,
                          peer_layer_kv=None):
    """x: (b, 1, d). Returns (attn_out (b,1,d), updated pool slices)."""
    u = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = L.attention_qkv(u, lp["attn"], cfg, rules)
    positions = state.pos[:, None]
    p3 = state.positions_3d[:, None] if state.positions_3d is not None else None
    q = L.position_embedding(q, positions, cfg, p3)
    k = L.position_embedding(k, positions, cfg, p3)

    pk, pv = layer_kv
    kvp = state.kv
    pools = (pk, pv, kvp.slot_req, kvp.slot_base,
             k[:, 0], v[:, 0], kvp.append_slot, kvp.append_off)
    peer = None
    if peer_layer_kv is not None:
        pp = state.peer
        peer = (peer_layer_kv[0], peer_layer_kv[1], pp.slot_req, pp.slot_base)
    o, new_pk, new_pv = _decode_attention(q[:, 0], pools, state.pos, cfg,
                                          rules, peer)
    y = jnp.einsum("bnh,nhd->bd", o.astype(x.dtype), lp["attn"]["wo"])
    return y[:, None], (new_pk, new_pv)


def serve_step(params, state: DecodeState, cfg: ModelConfig, rules=None,
               harvest_inplace: bool = False, carried_pools: bool = True):
    """Decode ONE token for every active request. Returns (logits, state)."""
    tokens = state.tokens
    x = embed(params, tokens[:, None] if tokens.ndim == 1 else tokens[:, None, :],
              cfg, rules)
    if cfg.rope_style == "none" and cfg.family == "audio":
        x = x + L.sinusoidal_positions(state.pos[:, None], cfg.d_model
                                       ).astype(x.dtype)
    fam = cfg.family
    aux_ignored = jnp.zeros((), jnp.float32)
    new_kv = state.kv
    new_states = state.states

    use_peer = harvest_inplace and state.peer is not None
    peer_kv_stack = (state.peer.pool_k, state.peer.pool_v) if use_peer else None
    routes = None

    if fam in ("dense", "vlm", "audio", "moe"):
        interleaved = fam == "moe" and cfg.moe.layer_period == 2

        def ffn(h, lp, is_moe):
            """The block's second half on h (attention added): (h, routes)."""
            u2 = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
            if is_moe:
                y, _, r = _moe_sublayer(u2, lp, cfg, rules, train=False)
                return h + y, r
            return h + L.mlp(u2, lp["mlp"], cfg, rules), None

        def one_layer(h, lp, layer_kv, peer_slice, is_moe):
            a, new_slice = _decode_attn_sublayer(h, lp, cfg, state, layer_kv,
                                                 rules, peer_slice)
            if cfg.parallel_block:
                # parallel block: attn and mlp both read ln1(x)
                u = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
                h = h + a + L.mlp(u, lp["mlp"], cfg, rules)
                return h, new_slice, None
            h, r = ffn(h + a, lp, is_moe)
            return h, new_slice, r

        if not interleaved and not carried_pools:
            # §Perf baseline variant: pools as scan xs/ys (full per-layer
            # slice rewrite each step) — kept for before/after measurement
            if cfg.mixed_attention is not None:
                raise NotImplementedError("mixed attention decodes with "
                                          "carried pools only")

            def body(h, inp):
                lp, pk, pv, peer = inp
                h, new_slice, r = one_layer(h, lp, (pk, pv),
                                            peer if use_peer else None,
                                            fam == "moe")
                return h, (new_slice, r)

            xs = (params["layers"], state.kv.pool_k, state.kv.pool_v,
                  peer_kv_stack if use_peer else state.kv.pool_k)
            x, ((pks, pvs), routes) = jax.lax.scan(body, x, xs)
        elif not interleaved:
            # pools ride in the scan CARRY: the KV append is a 3-index
            # scatter into the full pool (writes only b rows/layer) instead
            # of a full per-layer slice rewrite through scan ys (§Perf it.3)
            kinds = L.layer_kinds(cfg)

            def body(carry, lp):
                h, pkf, pvf, lyr = carry
                kind = (None if kinds is None else
                        jax.tree.map(lambda t: t[lyr], kinds))
                a, (pkf, pvf) = _decode_attn_sublayer_carried(
                    h, lp, cfg, state, (pkf, pvf), lyr, rules,
                    peer_kv_stack if use_peer else None, kind)
                r = None
                if cfg.parallel_block:
                    u = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
                    h = h + a + L.mlp(u, lp["mlp"], cfg, rules)
                else:
                    h, r = ffn(h + a, lp, fam == "moe")
                return (h, pkf, pvf, lyr + 1), r

            (x, pks, pvs, _), routes = jax.lax.scan(
                body, (x, state.kv.pool_k, state.kv.pool_v,
                       jnp.zeros((), jnp.int32)), params["layers"])
        else:
            def body(h, inp):
                dlp, mlp_, pk, pv, peer = inp
                h, s1, _ = one_layer(
                    h, dlp, (pk[0], pv[0]),
                    (peer[0][0], peer[1][0]) if use_peer else None, False)
                h, s2, r = one_layer(
                    h, mlp_, (pk[1], pv[1]),
                    (peer[0][1], peer[1][1]) if use_peer else None, True)
                return h, (jnp.stack([s1[0], s2[0]]),
                           jnp.stack([s1[1], s2[1]]), r)

            nk = num_kv_layers(cfg)
            pk2 = state.kv.pool_k.reshape((nk // 2, 2) + state.kv.pool_k.shape[1:])
            pv2 = state.kv.pool_v.reshape((nk // 2, 2) + state.kv.pool_v.shape[1:])
            if use_peer:
                ppk2 = peer_kv_stack[0].reshape(pk2.shape[:2] + peer_kv_stack[0].shape[1:])
                ppv2 = peer_kv_stack[1].reshape(pv2.shape[:2] + peer_kv_stack[1].shape[1:])
                peer_xs = (ppk2, ppv2)
            else:
                peer_xs = (pk2, pv2)
            x, (pks, pvs, routes) = jax.lax.scan(
                body, x, (params["blocks"]["dense"], params["blocks"]["moe"],
                          pk2, pv2, peer_xs))
            pks = pks.reshape((-1,) + pks.shape[2:])
            pvs = pvs.reshape((-1,) + pvs.shape[2:])
        new_kv = state.kv._replace(pool_k=pks, pool_v=pvs)

    elif fam == "hybrid":
        per = cfg.hybrid.attn_period
        n_super = cfg.num_layers // per
        n_tail = cfg.num_layers - n_super * per
        shared = params["shared_attn"]

        def split_stack(tree, n_head, inner):
            head = jax.tree.map(lambda t: t[:n_head * inner].reshape(
                (n_head, inner) + t.shape[1:]), tree)
            tail = jax.tree.map(lambda t: t[n_head * inner:], tree)
            return head, tail

        head_p, tail_p = split_stack(params["mamba_layers"], n_super, per)
        head_s, tail_s = split_stack(state.states, n_super, per)

        def mamba_one(h, lp, st):
            u = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
            y, new_st = S.mamba2_layer(u, lp["mamba"], cfg, rules,
                                       S.SSMState(*st), single_token=True)
            return h + y, tuple(new_st)

        def super_body(h, inp):
            lps, sts, pk, pv, peer = inp

            def inner(h2, inp2):
                lp, st = inp2
                return mamba_one(h2, lp, st)

            h, new_sts = jax.lax.scan(inner, h, (lps, tuple(sts)))
            a, new_slice = _decode_attn_sublayer(
                h, shared, cfg, state, (pk, pv), rules,
                peer if use_peer else None)
            h = h + a
            h = h + L.mlp(L.rms_norm(h, shared["ln2"], cfg.norm_eps),
                          shared["mlp"], cfg, rules)
            return h, (new_sts, new_slice)

        xs = (head_p, tuple(head_s), state.kv.pool_k, state.kv.pool_v,
              peer_kv_stack if use_peer else (state.kv.pool_k, state.kv.pool_v))
        x, (new_head_s, (pks, pvs)) = jax.lax.scan(super_body, x, xs)

        def tail_body(h, inp):
            lp, st = inp
            return mamba_one(h, lp, st)

        if n_tail:
            x, new_tail_s = jax.lax.scan(tail_body, x, (tail_p, tuple(tail_s)))
        else:
            new_tail_s = tuple(tail_s)
        new_states = jax.tree.map(
            lambda a, b: jnp.concatenate([a.reshape((-1,) + a.shape[2:]), b]),
            S.SSMState(*new_head_s), S.SSMState(*new_tail_s))
        new_kv = state.kv._replace(pool_k=pks, pool_v=pvs)

    elif fam == "ssm":
        out = _xlstm_backbone(params, x, cfg, rules, state.states,
                              single_token=True)
        x, new_states = out.hidden, out.states

    logits = unembed(params, x, cfg, rules)[:, 0]
    new_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    new_state = state._replace(
        tokens=new_tokens, pos=state.pos + 1, kv=new_kv, states=new_states,
        routes=routes)
    return logits, new_state
