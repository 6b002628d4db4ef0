"""Mellum2 on the served path, at a tiny size on the CPU, against the
plain reference the benchmark compares with (``bench/reference/mellum.py``).

The size keeps every kind of layer: one period of three windowed layers
and one full layer with YaRN, a window shorter than the prompts, and
experts 0 and 1 of a router over 8 held here, two chosen per token.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import mellum as ref
from repro.configs import get_config
from repro.core import HarvestRuntime, tracing
from repro.models import layers as L
from repro.models import model as M
from repro.models import moe
from repro.models.moe import moe_layer, moe_served
from repro.serving import engine as E

BS = 8
WINDOW = 16
HELD = 2
F32 = jnp.float32


def _conf(held=HELD, first=0):
    """The benchmark's configuration file at the tiny size."""
    with open("bench/configs/mellum2-12b-a2.5b.json") as f:
        conf = json.load(f)
    conf.update(num_layers=4, d_model=256, num_heads=4, num_kv_heads=2,
                head_dim=64,
                moe_intermediate_size=128, num_experts=held,
                num_experts_per_tok=2, vocab_size=512,
                sliding_window=WINDOW,
                published={"num_experts": 8},
                moe={"held": held, "first_held": first})
    return ref.frozen(conf)


def _cfg(held=HELD, first=0):
    c = get_config("mellum2-12b-a2.5b")
    return dataclasses.replace(
        c, num_layers=4, d_model=256, num_heads=4, num_kv_heads=2,
        head_dim=64, vocab_size=512, sliding_window=WINDOW,
        moe=dataclasses.replace(c.moe, num_experts=8, top_k=2,
                                d_ff_expert=128, held=held,
                                first_held=first))


def _params(cfg, seed=0):
    """Float32 weights: the program and the reference then differ by the
    order of their sums alone."""
    p = M.init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree.map(lambda a: a.astype(F32), p)


def _ref_logits(params, tokens, conf):
    """The reference's full forward: (b, s, vocab) float32 logits."""
    x = ref.hidden(params, tokens, conf)
    h = ref._rms(x, params["final_norm"], conf["rms_norm_eps"])
    return ref._mm("bsd,dv->bsv", h, params["lm_head"], "f32")


def _serve(cfg, params, prompts, new_tokens):
    """Serve ``prompts`` through the engine; returns the requests and the
    logits it sampled from, by (request id, position)."""
    eng = E.HarvestServingEngine(
        cfg, params, runtime=HarvestRuntime({1: 64 * 2**20}), max_batch=2,
        block_size=BS, num_local_slots=24, max_seq_len=128,
        scheduler="fcfs", mode="async")
    seen = {}
    prefill, commit, sample = (eng._prefill, eng._commit_and_sample,
                               eng._sample)
    current = []

    def spied_prefill(r):
        current.append(r)
        prefill(r)
        current.clear()

    def spied_sample(logits):
        if current:
            r = current[0]
            seen[r.req_id, len(r.prompt) - 1] = np.asarray(logits)
        return sample(logits)

    def spied_commit(logits, reqs):
        eng.commits = getattr(eng, "commits", 0) + 1
        rows = np.asarray(logits)
        for r in reqs:
            seen[r.req_id, r.pos] = rows[r.row]
        commit(logits, reqs)

    eng._prefill, eng._sample = spied_prefill, spied_sample
    eng._commit_and_sample = spied_commit
    reqs = [eng.submit(p, new_tokens) for p in prompts]
    eng.run(max_steps=500)
    assert all(r.done for r in reqs)
    return eng, reqs, seen


#: the two ways a served share computes its held experts
PATHS = {"grouped": moe._grouped_experts, "dense": moe._dense_experts}


def _through(path, x, lp, cfg):
    """The served layer over ``x`` (b, s, d) with its held experts
    computed by ``path``, whichever the shapes would choose: (y, routes)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    local, weights, routes = moe._route(xt, lp["router"], cfg,
                                        cfg.moe.first_held, cfg.moe.num_held)
    y = PATHS[path](xt, lp, cfg, local, weights, routes)
    return y.reshape(b, s, d), routes


PROMPTS = [[3 + (7 * j + 5 * i) % 500 for j in range(n)]
           for i, n in enumerate((37, 29, 44))]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = _cfg()
    params = _params(cfg)
    tracing.clear()
    with jax.profiler.trace(str(tmp_path_factory.mktemp("profile"))):
        eng, reqs, seen = _serve(cfg, params, PROMPTS, 14)
    events = [s for s in tracing.spans() if s.name == "moe.routes"]
    tracing.clear()
    return cfg, params, eng, reqs, seen, events


def test_prefill_and_paged_decode_match_the_reference(served):
    """Every logit the engine sampled from, after its prefill and at each
    step of decoding through the paged pools, against the reference's full
    forward over the request's final sequence.  The prompts are longer
    than the window, so the windowed layers drop positions at every step.
    Tolerance: float32 on both sides, so only the order of sums differs
    (measured 7e-7 of the logits' scale); a position kept past the
    window, a frequency off YaRN's, or a dropped route moves the logits
    by more than 1e-2 of it."""
    cfg, params, _, reqs, seen, _ = served
    conf = _conf()
    worst = 0.0
    for r in reqs:
        seq = jnp.asarray([r.prompt + r.output], jnp.int32)
        want = np.asarray(_ref_logits(params, seq, conf))[0]
        scale = np.abs(want).max()
        positions = [p for (rid, p) in seen if rid == r.req_id]
        assert len(positions) == len(r.output), positions
        assert max(positions) > WINDOW + len(r.prompt) // 2
        for p in positions:
            worst = max(worst, np.abs(seen[r.req_id, p] - want[p]).max()
                        / scale)
    assert worst < 1e-4, worst


def test_the_engine_records_the_routes_it_reads(served):
    cfg, _, eng, reqs, _, events = served
    er = eng.stats.expert_routes
    by_program = {p: [e for e in events if e.args["program"] == p]
                  for p in ("prefill", "decode")}
    assert len(by_program["prefill"]) == len(reqs)
    assert len(by_program["decode"]) == eng.commits
    for program, evs in by_program.items():
        assert sum(sum(e.args["routes_held"]) for e in evs) == er[program]
        for e in evs:
            a = e.args
            assert (a["layers"], a["held"]) == (4, HELD)
            assert len(a["routes_held"]) == len(a["max_routes"]) == 4
            assert all(m <= n for m, n in zip(a["max_routes"],
                                              a["routes_held"]))
            assert a["experts_touched"] <= 4 * HELD
    for e in by_program["decode"]:
        # every row the step ran routes top_k times in each layer, at
        # most both routes to held experts
        assert sum(e.args["routes_held"]) <= 4 * 2 * eng.B
    # a decode step's 2 rows route 4 times among 8 experts, half a route
    # each, and prefills more: every program here takes the dense path
    assert {e.args["path"] for e in events} == {"dense"}
    assert eng.stats.expert_dense_calls == {"prefill": len(reqs),
                                            "decode": eng.commits}


def test_a_dense_model_returns_and_records_no_routes():
    cfg = get_config("yi-6b").reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.arange(16, dtype=jnp.int32)[None]
    _, out = M.forward(params, {"tokens": toks, "positions": toks},
                       cfg, want_kv=True)
    assert out.routes is None
    assert L.layer_kinds(cfg) is None
    eng = E.HarvestServingEngine(cfg, params, max_batch=1, block_size=BS,
                                 num_local_slots=8)
    eng.submit(list(range(3, 12)), 3)
    eng.run(max_steps=50)
    assert eng.stats.expert_routes == {}
    assert eng.stats.expert_dense_calls == {}


@pytest.mark.parametrize("path", PATHS)
def test_the_shares_add_up_to_the_whole_layer(path):
    """Four chips holding two experts each: the served layer's four
    partial outputs, summed, are the uncut reference layer's output."""
    whole = _cfg(held=8)
    p = _params(whole, seed=3)["layers"]["moe"]
    lp = jax.tree.map(lambda a: a[1], p)                  # one layer
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, 256), F32)
    total = 0.0
    for chip in range(4):
        cfg = _cfg(held=2, first=2 * chip)
        part = dict(lp, **{k: lp[k][2 * chip:2 * chip + 2]
                           for k in ("wi", "wg", "wo")})
        y, routes = _through(path, x, part, cfg)
        assert routes.shape == (2,)
        total = total + y
    want = ref._experts(x, lp, _conf(held=8), "f32")
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("path", PATHS)
def test_routing_forced_onto_one_expert_drops_nothing(path):
    """A router that scores every expert alike sends every token to
    experts 0 and 1 (ties go to the lower index): expert 0 takes all 96
    routes of its layer, 16 times what a capacity of 1.25 x the mean
    load holds.  The served layer still gives the reference's output;
    the training dispatch at its capacity does not."""
    cfg = _cfg()
    lp = jax.tree.map(lambda a: a[0], _params(cfg, seed=5)["layers"]["moe"])
    lp = dict(lp, router=jnp.zeros_like(lp["router"]))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 48, 256), F32)
    want = ref._experts(x, lp, _conf(), "f32")
    y, routes = _through(path, x, lp, cfg)
    assert routes.tolist() == [96, 96]
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    dropped, _ = moe_layer(x, lp, cfg)
    assert np.abs(np.asarray(dropped) - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("path", PATHS)
def test_rows_past_the_held_routes_add_nothing(path, monkeypatch):
    """``jax.lax.ragged_dot`` leaves rows that fall in no group undefined:
    on a TPU its kernel visits only the tiles that hold a group's rows and
    writes only those rows.  A grouped matmul that fills the others with
    NaN leaves the grouped path's output as the reference's.  The dense
    path's rows are its (expert, token) pairs: filled with NaN where the
    token has no route to the expert, they leave its output so too."""
    cfg = _cfg()
    lp = jax.tree.map(lambda a: a[2], _params(cfg, seed=7)["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 256), F32)
    local, _, _ = moe._route(x.reshape(-1, 256), lp["router"], cfg, 0, HELD)
    unrouted = ~(local[..., None] == jnp.arange(HELD)).any(1).T
    real_ragged, real_einsum = jax.lax.ragged_dot, jnp.einsum

    def undefined_past_groups(lhs, rhs, group_sizes, **kw):
        out = real_ragged(lhs, rhs, group_sizes, **kw)
        past = jnp.arange(out.shape[0]) >= group_sizes.sum()
        return jnp.where(past[:, None], jnp.nan, out)

    def undefined_off_routes(spec, *ops, **kw):
        out = real_einsum(spec, *ops, **kw)
        if spec == "etf,efd->etd":
            out = jnp.where(unrouted[..., None], jnp.nan, out)
        return out

    monkeypatch.setattr(jax.lax, "ragged_dot", undefined_past_groups)
    monkeypatch.setattr(jnp, "einsum", undefined_off_routes)
    y, routes = _through(path, x, lp, cfg)
    assert 0 < int(routes.sum()) < 2 * 24 * 2     # some rows in no group
    assert bool(unrouted.any())
    want = ref._experts(x, lp, _conf(), "f32")
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)


def test_dense_path_at_a_decode_shape_with_an_idle_held_expert():
    """32 decode rows whose router never picks held expert 1 (its column
    scores every positive input far below the rest): the layer takes the
    dense path, equals the reference, and counts the grouped path's
    routes, none of them to expert 1."""
    cfg = _cfg()
    lp = jax.tree.map(lambda a: a[1], _params(cfg, seed=9)["layers"]["moe"])
    lp = dict(lp, router=lp["router"].at[:, 1].set(-0.05))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(10), (32, 1, 256), F32))
    assert moe.dense_experts(32, cfg.moe.top_k, cfg.moe.num_experts, HELD)
    y, routes = moe_served(x, lp, cfg)
    _, grouped_routes = _through("grouped", x, lp, cfg)
    assert routes[0] > 0 and routes[1] == 0, routes
    assert routes.tolist() == grouped_routes.tolist()
    want = ref._experts(x, lp, _conf(), "f32")
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)


def test_dense_path_when_every_route_goes_to_absent_experts():
    """Held experts 6 and 7 under a router that sends every token to
    experts 0 and 1: nothing is theirs to add.  The dense path gives the
    reference's zeros and the grouped path's routes, even with NaN in
    the held experts' weights."""
    cfg, conf = _cfg(first=6), _conf(first=6)
    lp = jax.tree.map(lambda a: a[0], _params(cfg, seed=11)["layers"]["moe"])
    lp = dict(lp, router=jnp.zeros_like(lp["router"]))
    x = jax.random.normal(jax.random.PRNGKey(12), (32, 1, 256), F32)
    want = ref._experts(x, lp, conf, "f32")
    assert not np.asarray(want).any()
    y, routes = _through("dense", x, lp, cfg)
    _, grouped_routes = _through("grouped", x, lp, cfg)
    assert routes.tolist() == grouped_routes.tolist() == [0, 0]
    np.testing.assert_array_equal(y, want)
    poisoned = dict(lp, wo=jnp.full_like(lp["wo"], jnp.nan))
    y, _ = _through("dense", x, poisoned, cfg)
    np.testing.assert_array_equal(y, want)


def _has_grouped_matmul(tokens, top_k=8, d=64):
    """Whether ``moe_served`` lowers through ``ragged_dot`` for ``tokens``
    tokens at Mellum's routing (16 of 64 experts held, top 8 or
    ``top_k``)."""
    c = get_config("mellum2-12b-a2.5b")
    cfg = dataclasses.replace(c, d_model=d, moe=dataclasses.replace(
        c.moe, d_ff_expert=32, held=16, top_k=top_k))
    s = jax.ShapeDtypeStruct
    p = {"router": s((d, 64), F32), "wi": s((16, d, 32), F32),
         "wg": s((16, d, 32), F32), "wo": s((16, 32, d), F32)}
    jaxpr = jax.make_jaxpr(lambda x, p: moe_served(x, p, cfg))(
        s((tokens, 1, d), F32), p)
    return "ragged_dot" in str(jaxpr)


def test_the_shapes_choose_the_path():
    """The cell's decode step of 32 rows and its longest prefill, 1,024
    positions, compute the held experts densely; 2,048 positions (32,768
    token-expert pairs), and 16 tokens routed once each (a quarter of a
    route an expert), keep the grouped matmuls."""
    assert not _has_grouped_matmul(32)
    assert not _has_grouped_matmul(1024)
    assert _has_grouped_matmul(2048)
    assert _has_grouped_matmul(16, top_k=1)


def test_yarn_frequencies_and_factor_match_transformers():
    pytest.importorskip("torch")
    from transformers import PretrainedConfig
    from transformers.modeling_rope_utils import _compute_yarn_parameters
    conf = json.load(open("bench/configs/mellum2-12b-a2.5b.json"))
    yarn = conf["rope_parameters"]["full_attention"]
    hf = PretrainedConfig(
        rope_theta=yarn["rope_theta"], hidden_size=conf["hidden_size"],
        num_attention_heads=conf["num_attention_heads"],
        head_dim=conf["head_dim"],
        max_position_embeddings=conf["max_position_embeddings"],
        rope_scaling=dict(yarn))
    inv, factor = _compute_yarn_parameters(hf, "cpu")
    inv = inv.numpy()
    cfg = get_config("mellum2-12b-a2.5b")
    kinds = L.layer_kinds(cfg)
    full = [i for i in range(cfg.num_layers)
            if cfg.layer_type(i) == "full_attention"]
    assert full == [3, 7, 11, 15, 19, 23, 27]
    for i in range(cfg.num_layers):
        want_window = 2**30 if i in full else 1024
        assert int(kinds.window[i]) == want_window
    np.testing.assert_allclose(kinds.inv_freq[3], inv, rtol=1e-6)
    assert float(kinds.rope_scale[3]) == pytest.approx(factor, rel=1e-7)
    assert float(kinds.rope_scale[0]) == 1.0
    ref_inv, ref_factor = ref.rope_parameters(conf, "full_attention")
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    assert ref_factor == pytest.approx(factor, rel=1e-12)


def test_reduced_keeps_one_layer_of_each_kind():
    r = get_config("mellum2-12b-a2.5b").reduced()
    assert r.num_layers == 2
    assert [r.layer_type(i) for i in range(2)] == ["sliding_attention",
                                                   "full_attention"]
