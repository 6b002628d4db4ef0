"""xLSTM blocks [arXiv:2405.04517]: mLSTM (matrix memory, chunkwise-parallel)
and sLSTM (scalar memory, sequential scan with exponential gating).

mLSTM uses the stabilized chunkwise form (log-space gate accumulation with a
running max ``m``) so training does not store an O(seq) trail of
(hd x hd) matrix-memory carries — only chunk-boundary states.  The
single-step recurrence (`mlstm_step`) is the decode path and the oracle the
chunkwise form is tested against.

Recurrent state is Harvest's "lossy + reconstructible" durability class.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import layer_norm, rms_norm
from repro.models.sharding import shard

LOG_EPS = -1e30


class MLSTMState(NamedTuple):
    c: jnp.ndarray   # (b, nh, hd, hd) f32 stabilized matrix memory
    n: jnp.ndarray   # (b, nh, hd) f32 normalizer
    m: jnp.ndarray   # (b, nh) f32 running log-max
    conv: jnp.ndarray  # (b, W-1, d_inner) conv tail


class SLSTMState(NamedTuple):
    c: jnp.ndarray   # (b, nh, hd)
    n: jnp.ndarray   # (b, nh, hd)
    m: jnp.ndarray   # (b, nh, hd)
    h: jnp.ndarray   # (b, nh, hd)


def xlstm_dims(cfg: ModelConfig):
    xc = cfg.xlstm
    d_inner = int(cfg.d_model * xc.proj_factor_mlstm)
    nh = cfg.num_heads
    hd = d_inner // nh
    return d_inner, nh, hd


# ---------------------------------------------------------------------------
# mLSTM cell
# ---------------------------------------------------------------------------


def mlstm_step(q, k, v, i_raw, f_raw, state: MLSTMState):
    """Single-token stabilized mLSTM recurrence (decode path + oracle).

    q,k,v: (b, nh, hd);  i_raw,f_raw: (b, nh).
    """
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    hd = q.shape[-1]
    logf = jax.nn.log_sigmoid(f_raw.astype(f32))
    m_new = jnp.maximum(logf + state.m, i_raw.astype(f32))
    f_s = jnp.exp(logf + state.m - m_new)
    i_s = jnp.exp(i_raw.astype(f32) - m_new)
    k_sc = k / (hd ** 0.5)
    c_new = state.c * f_s[..., None, None] + i_s[..., None, None] * (
        k_sc[..., :, None] * v[..., None, :])
    n_new = state.n * f_s[..., None] + i_s[..., None] * k_sc
    num = jnp.einsum("bnh,bnhd->bnd", q, c_new)
    denom = jnp.maximum(jnp.abs(jnp.einsum("bnh,bnh->bn", q, n_new)),
                        jnp.exp(-m_new))
    h = num / denom[..., None]
    return h, MLSTMState(c_new, n_new, m_new, state.conv)


def mlstm_chunkwise(q, k, v, i_raw, f_raw, state: Optional[MLSTMState],
                    chunk: int = 256):
    """Chunkwise-parallel stabilized mLSTM.

    q,k,v: (b, s, nh, hd);  i_raw,f_raw: (b, s, nh).
    Returns (h: (b, s, nh, hd), final (c, n, m)).
    """
    f32 = jnp.float32
    b, s, nh, hd = q.shape
    chunk = min(chunk, s)
    nchunks = -(-s // chunk)
    pad = nchunks * chunk - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        i_raw = jnp.pad(i_raw, ((0, 0), (0, pad), (0, 0)), constant_values=LOG_EPS)
        f_raw = jnp.pad(f_raw, ((0, 0), (0, pad), (0, 0)), constant_values=30.0)

    def chunked(x):
        x = x.astype(f32)
        return x.reshape((b, nchunks, chunk) + x.shape[2:]).swapaxes(0, 1)

    qc, kc, vc = chunked(q), chunked(k), chunked(v / 1.0)
    ic, fc = chunked(i_raw), chunked(f_raw)
    kc = kc / (hd ** 0.5)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def body(carry, inp):
        C_prev, n_prev, m_prev = carry
        qq, kk, vv, ii, ff = inp                   # (b,q,nh,hd) / (b,q,nh)
        logf = jax.nn.log_sigmoid(ff)
        bcum = jnp.cumsum(logf, axis=1)            # (b,q,nh)
        btot = bcum[:, -1]                         # (b,nh)
        # intra log weights D[i,j] = bcum_i - bcum_j + ilog_j  (j <= i)
        D = bcum[:, :, None, :] - bcum[:, None, :, :] + ii[:, None, :, :]
        D = jnp.where(causal[None, :, :, None], D, LOG_EPS)
        # inter log weight g_i = bcum_i + m_prev
        g = bcum + m_prev[:, None, :]
        m_i = jnp.maximum(jnp.max(D, axis=2), g)   # (b,q,nh)
        S = jnp.einsum("binh,bjnh->bijn", qq, kk) * jnp.exp(D - m_i[:, :, None, :])
        num = jnp.einsum("bijn,bjnh->binh", S, vv)
        num = num + jnp.einsum("binh,bnhd->bind", qq, C_prev) * \
            jnp.exp(g - m_i)[..., None]
        nrm = jnp.sum(S, axis=2) + jnp.einsum("binh,bnh->bin", qq, n_prev) * \
            jnp.exp(g - m_i)
        h = num / jnp.maximum(jnp.abs(nrm), jnp.exp(-m_i))[..., None]
        # state update
        w = btot[:, None, :] - bcum + ii           # (b,q,nh) log weight per j
        m_new = jnp.maximum(btot + m_prev, jnp.max(w, axis=1))
        scale_prev = jnp.exp(btot + m_prev - m_new)
        wts = jnp.exp(w - m_new[:, None, :])
        C_new = C_prev * scale_prev[..., None, None] + jnp.einsum(
            "bjn,bjnh,bjnd->bnhd", wts, kk, vv)
        n_new = n_prev * scale_prev[..., None] + jnp.einsum("bjn,bjnh->bnh", wts, kk)
        return (C_new, n_new, m_new), h

    if state is None:
        c0 = jnp.zeros((b, nh, hd, hd), f32)
        n0 = jnp.zeros((b, nh, hd), f32)
        m0 = jnp.full((b, nh), LOG_EPS, f32)
    else:
        c0, n0, m0 = state.c, state.n, state.m
    (cf, nf, mf), hs = jax.lax.scan(
        jax.checkpoint(body), (c0, n0, m0), (qc, kc, vc, ic, fc))
    h = hs.swapaxes(0, 1).reshape(b, nchunks * chunk, nh, hd)[:, :s]
    return h, (cf, nf, mf)


def mlstm_block(x, p, cfg: ModelConfig, rules=None,
                state: Optional[MLSTMState] = None, single_token: bool = False
                ) -> Tuple[jnp.ndarray, MLSTMState]:
    """Full mLSTM block: LN -> up-proj -> conv -> qkv -> cell -> gate -> down."""
    d_inner, nh, hd = xlstm_dims(cfg)
    b, s, _ = x.shape
    u = rms_norm(x, p["ln"], cfg.norm_eps)
    up = jnp.einsum("bsd,dk->bsk", u, p["w_up"])   # (b, s, 2*d_inner)
    xm, z = jnp.split(up, 2, axis=-1)

    # causal depthwise conv feeding q/k
    W = p["conv_w"].shape[0]
    tail = state.conv if state is not None else jnp.zeros((b, W - 1, d_inner), x.dtype)
    xp = jnp.concatenate([tail, xm], axis=1)
    conv = sum(xp[:, i:i + s] * p["conv_w"][i] for i in range(W))
    conv = jax.nn.silu(conv + p["conv_b"])
    new_tail = xp[:, xp.shape[1] - (W - 1):]

    def heads(t, w):  # per-head block-diagonal projection
        return jnp.einsum("bsnh,nhk->bsnk", t.reshape(b, s, nh, hd), w)

    q = heads(conv, p["wq"])
    k = heads(conv, p["wk"])
    v = heads(xm, p["wv"])
    q = shard(q, rules, "act_batch", "act_seq", "state_heads", None)
    gates = jnp.einsum("bsk,kg->bsg", xm, p["w_gates"]) + p["b_gates"]
    i_raw, f_raw = jnp.split(gates.reshape(b, s, nh, 2), 2, axis=-1)
    i_raw, f_raw = i_raw[..., 0], f_raw[..., 0]

    if single_token:
        st = state if state is not None else init_mlstm_state(cfg, b)
        h, new_state = mlstm_step(q[:, 0], k[:, 0], v[:, 0],
                                  i_raw[:, 0], f_raw[:, 0], st)
        h = h[:, None]
        new_state = MLSTMState(new_state.c, new_state.n, new_state.m, new_tail)
    else:
        h, (cf, nf, mf) = mlstm_chunkwise(q, k, v, i_raw, f_raw, state)
        new_state = MLSTMState(cf, nf, mf, new_tail)

    h = rms_norm(h.astype(x.dtype), p["out_norm"], cfg.norm_eps)
    h = h.reshape(b, s, d_inner) * jax.nn.silu(z)
    y = jnp.einsum("bsk,kd->bsd", h, p["w_down"])
    return x + y, new_state


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------


def slstm_block(x, p, cfg: ModelConfig, rules=None,
                state: Optional[SLSTMState] = None, single_token: bool = False
                ) -> Tuple[jnp.ndarray, SLSTMState]:
    """sLSTM block: LN -> sequential exp-gated scalar cell -> GN -> GEGLU MLP."""
    nh = cfg.num_heads
    d = cfg.d_model
    hd = d // nh
    b, s, _ = x.shape
    f32 = jnp.float32

    u = rms_norm(x, p["ln"], cfg.norm_eps)
    # input contributions for 4 gates (z, i, f, o): (b, s, nh, 4, hd)
    gx = jnp.einsum("bsd,dngk->bsngk", u, p["w_in"]) + p["b_in"]

    if state is None:
        state = init_slstm_state(cfg, b)

    def cell(carry, g_t):
        c, n, m, h_prev = carry
        # recurrent contribution (block-diagonal per head)
        gr = jnp.einsum("bnh,nhgk->bngk", h_prev, p["w_rec"])
        g = g_t.astype(f32) + gr
        z_t = jnp.tanh(g[:, :, 0])
        i_t = g[:, :, 1]
        f_t = g[:, :, 2]
        o_t = jax.nn.sigmoid(g[:, :, 3])
        logf = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(logf + m, i_t)
        i_s = jnp.exp(i_t - m_new)
        f_s = jnp.exp(logf + m - m_new)
        c_new = f_s * c + i_s * z_t
        n_new = f_s * n + i_s
        h_new = o_t * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, m_new, h_new), h_new

    if single_token:
        (c, n, m, h), _ = cell((state.c, state.n, state.m, state.h), gx[:, 0])
        hs = h[:, None]
        new_state = SLSTMState(c, n, m, h)
    else:
        def scan_cells(gx_l, c0, n0, m0, h0, w_rec):
            def cell_l(carry, g_t):
                c, n, m, h_prev = carry
                gr = jnp.einsum("bnh,nhgk->bngk", h_prev, w_rec)
                g = g_t.astype(f32) + gr
                z_t = jnp.tanh(g[:, :, 0])
                i_t, f_t = g[:, :, 1], g[:, :, 2]
                o_t = jax.nn.sigmoid(g[:, :, 3])
                logf = jax.nn.log_sigmoid(f_t)
                m_new = jnp.maximum(logf + m, i_t)
                i_s = jnp.exp(i_t - m_new)
                f_s = jnp.exp(logf + m - m_new)
                c_new = f_s * c + i_s * z_t
                n_new = f_s * n + i_s
                h_new = o_t * c_new / jnp.maximum(n_new, 1e-6)
                return (c_new, n_new, m_new, h_new), h_new

            (c, n, m, h), hs = jax.lax.scan(
                cell_l, (c0, n0, m0, h0), gx_l.swapaxes(0, 1))
            return hs.swapaxes(0, 1), c, n, m, h

        dax = rules.axis("act_batch") if rules is not None else None
        if dax is not None and b % rules.axis_size(dax) == 0:
            # manual shard_map over the batch axis: the cell recurrence is
            # tiny and fully batch-parallel, but under plain GSPMD the
            # transpose of the scan psums the replicated w_rec GRADIENT
            # every token step (384 GiB/step measured at seq 4096 — §Perf
            # iteration 6); inside a manual region AD accumulates the grad
            # locally and reduces ONCE at exit.
            from jax.sharding import PartitionSpec as P
            daxes = (dax,) if isinstance(dax, str) else tuple(dax)
            bspec = P(daxes)
            hs, c, n, m, h = jax.shard_map(
                scan_cells, mesh=rules.mesh,
                in_specs=(bspec, bspec, bspec, bspec, bspec, P()),
                out_specs=(bspec,) * 5, check_vma=False,
            )(gx, state.c, state.n, state.m, state.h,
              p["w_rec"].astype(f32))
        else:
            hs, c, n, m, h = scan_cells(gx, state.c, state.n, state.m,
                                        state.h, p["w_rec"].astype(f32))
        new_state = SLSTMState(c, n, m, h)

    hs = rms_norm(hs.astype(x.dtype), p["gn"], cfg.norm_eps)
    y = x + jnp.einsum("bsnh,nhd->bsd", hs, p["w_out"])

    # post GEGLU MLP (proj factor 4/3)
    u2 = rms_norm(y, p["ln2"], cfg.norm_eps)
    hh = jnp.einsum("bsd,df->bsf", u2, p["mlp_wi"])
    gg = jnp.einsum("bsd,df->bsf", u2, p["mlp_wg"])
    y = y + jnp.einsum("bsf,fd->bsd", jax.nn.gelu(gg) * hh, p["mlp_wo"])
    return y, new_state


def init_mlstm_state(cfg: ModelConfig, batch: int) -> MLSTMState:
    d_inner, nh, hd = xlstm_dims(cfg)
    return MLSTMState(
        c=jnp.zeros((batch, nh, hd, hd), jnp.float32),
        n=jnp.zeros((batch, nh, hd), jnp.float32),
        m=jnp.full((batch, nh), LOG_EPS, jnp.float32),
        conv=jnp.zeros((batch, cfg.xlstm.conv_width - 1, d_inner), jnp.bfloat16),
    )


def init_slstm_state(cfg: ModelConfig, batch: int) -> SLSTMState:
    nh = cfg.num_heads
    hd = cfg.d_model // nh
    z = jnp.zeros((batch, nh, hd), jnp.float32)
    return SLSTMState(c=z, n=z, m=jnp.full((batch, nh, hd), LOG_EPS, jnp.float32),
                      h=z)
