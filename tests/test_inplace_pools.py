"""The KV pools are updated in place: every program that writes them
takes them donated, so no write copies a whole pool.

Each scenario is served twice on a reduced model: once as the engine
runs, and once through plain reference writes (one eager, undonated
``.at[].set`` per block and pool) and an undonated decode step.  Tokens,
the final pool contents and the final recurrent states must agree
exactly.  The scenarios between them write through every path (prefill,
a prefix-cache hit that COW-splits, a reload after eviction, chunked
prefill and a disaggregated stream) and serve every family the engine
serves: dense, MoE, a VLM whose prefix embeddings sit in front of the
blocks, a hybrid whose recurrent states ride the donated decode step,
and an SSM with states and no KV pools.  (The audio family's codebook
tokens are not served by the engine: its prefill stops at the prefix
embeddings' concatenation.)
"""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import HarvestRuntime, tracing
from repro.core.tiers import get_topology
from repro.models import model as M
from repro.serving import engine as E

MiB = 2**20
BS = 8
SHARED = [3 + (i * 7) % 200 for i in range(24)]


@functools.lru_cache(maxsize=None)
def _model(arch):
    cfg = dataclasses.replace(get_config(arch).reduced(), num_layers=2)
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def served_model():
    return _model("yi-6b")


def _eager_write(pool_k, pool_v, slots, k, v):
    """The reference writes: per block, an eager slice, cast and
    ``.at[].set`` into undonated pools."""
    nb, bs = len(slots), pool_k.shape[2]
    off = k.shape[2] - nb * bs
    for i, slot in enumerate(np.asarray(slots)):
        if slot < pool_k.shape[1]:
            lo = off + i * bs
            pool_k = pool_k.at[:, slot].set(
                k[:, 0, lo:lo + bs].astype(jnp.float32))
            pool_v = pool_v.at[:, slot].set(
                v[:, 0, lo:lo + bs].astype(jnp.float32))
    return pool_k, pool_v


_undonated_step = jax.jit(M.serve_step, static_argnames=("cfg", "rules"))


def _prompts(name):
    if name == "prefix":
        # two publish the shared prefix, two hit it at once (one adopts,
        # one COW-splits), three distinct ones push it out, the last
        # reads it back from the peer tier
        return ([SHARED + [11 + i, 40 + i] for i in range(4)]
                + [[5 + 31 * i + j for j in range(30)] for i in range(3)]
                + [SHARED + [17, 18, 19]])
    # five distinct prompts of 9 to 37 tokens
    return [[3 + i * 13 + (j * 11) % 200 for j in range(9 + 7 * i)]
            for i in range(5)]


def _engine(cfg, params, name):
    kw = dict(max_batch=2, block_size=BS, num_local_slots=10,
              scheduler="fcfs", mode="async")
    if name == "prefix":
        kw.update(prefix_cache=True)
    elif name.endswith("chunked"):
        kw.update(chunk_prefill_tokens=8)
    if name == "stream":
        topo = get_topology("h100-dcn-2host")
        rt = HarvestRuntime(topo.device_budgets(64 * MiB), topology=topo)
        kw.update(disaggregated=True, prefill_workers=2)
    else:
        rt = HarvestRuntime({1: 64 * MiB})
    return E.HarvestServingEngine(cfg, params, runtime=rt, **kw)


def _donated(state):
    """The arrays a decode step takes donated: the pools and the
    recurrent states."""
    pools = () if state.kv is None else (state.kv.pool_k, state.kv.pool_v)
    return jax.tree.leaves((pools, state.states))


def _serve(model, name, *, reference):
    """Serve a scenario; returns ``(tokens, arrays, writes, steps,
    engine)``: ``arrays`` are the final pools and states on the host,
    ``writes`` and ``steps`` say, per call of the write program and of
    the decode step, whether the arrays passed in were deleted by it."""
    cfg, params = model
    writes, steps = [], []
    write, step = E._write_blocks, E._serve_step

    def spied_write(pool_k, pool_v, slots, k, v):
        out = write(pool_k, pool_v, slots, k, v)
        writes.append(pool_k.is_deleted() and pool_v.is_deleted())
        return out

    def spied_step(params, state, **kw):
        donated = _donated(state)
        out = step(params, state, **kw)
        steps.append(bool(donated) and all(x.is_deleted() for x in donated))
        return out

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(E, "_write_blocks",
                   _eager_write if reference else spied_write)
        mp.setattr(E, "_serve_step",
                   _undonated_step if reference else spied_step)
        eng = _engine(cfg, params, name)
        reqs = [eng.submit(p, 6) for p in _prompts(name)]
        eng.run(max_steps=2000)
    assert all(r.done for r in reqs)
    arrays = [np.asarray(x) for x in jax.tree.leaves(
        (eng.pool_k, eng.pool_v, eng.states))]
    return [tuple(r.output) for r in reqs], arrays, writes, steps, eng


#: scenario -> (architecture, the write paths it must engage)
SCENARIOS = {"prefix": ("yi-6b", {"prefill", "cow", "reload"}),
             "chunked": ("yi-6b", {"chunk"}),
             "stream": ("yi-6b", {"stream"}),
             "moe": ("phi-tiny-moe", {"prefill"}),
             "vlm": ("qwen2-vl-72b", {"prefill"}),
             "vlm-chunked": ("qwen2-vl-72b", {"chunk"}),
             "hybrid": ("zamba2-7b", {"prefill"}),
             "ssm": ("xlstm-1.3b", set())}


@pytest.fixture(scope="module", params=list(SCENARIOS))
def scenario(request, tmp_path_factory):
    name = request.param
    model = _model(SCENARIOS[name][0])
    ref = _serve(model, name, reference=True)
    tracing.clear()
    with jax.profiler.trace(str(tmp_path_factory.mktemp("profile"))):
        run = _serve(model, name, reference=False)
    spans = [s for s in tracing.spans() if s.name == "kv.write"]
    tracing.clear()
    return name, ref, run, spans


def test_same_tokens_and_pools_as_eager_writes(scenario):
    name, (tok0, arrays0, *_), (tok, arrays, *_), _ = scenario
    assert tok == tok0
    assert len(arrays) == len(arrays0)
    for a, a0 in zip(arrays, arrays0):
        np.testing.assert_array_equal(a, a0)


def test_every_write_and_step_updates_in_place(scenario):
    """The pools passed to the write program, and the pools and states
    passed to the decode step, are deleted by the call: it took them
    donated and wrote into them."""
    name, *_, (_, _, writes, steps, _), _ = scenario
    assert all(writes) and bool(writes) == bool(SCENARIOS[name][1])
    assert steps and all(steps)


def test_write_spans_carry_blocks_and_path(scenario):
    name, *_, (_, _, writes, _, eng), spans = scenario
    assert len(spans) == len(writes)
    assert {s.args["path"] for s in spans} == SCENARIOS[name][1]
    for s in spans:
        assert s.args["blocks"] >= 1, s
        if s.args["path"] in ("reload", "cow"):
            assert s.args["blocks"] == 1, s
    if name == "prefix":
        assert eng.prefix_cache.stats["cow_splits"] >= 1


def test_prefix_hits_compile_no_write_program(served_model):
    """The write program compiles once per padded prefill length, as the
    prefill program does, and once for single f32 blocks (reloads and
    COW splits).  Prefills whose prefixes hit the cache write fewer
    blocks, and compile nothing new."""
    cfg, params = served_model
    prompts = [[100 + n] + SHARED[:n - 1] for n in (9, 17, 26, 33, 41, 50)]
    eng = E.HarvestServingEngine(
        cfg, params, runtime=HarvestRuntime({1: 64 * MiB}), max_batch=1,
        block_size=BS, num_local_slots=16, scheduler="fcfs", mode="async",
        prefix_cache=True)
    E._write_blocks.clear_cache()
    for p in prompts:                   # no hits: every prefix is new
        eng.submit(p, 2)
    eng.run(max_steps=500)
    cold = E._write_blocks._cache_size()
    assert cold <= len({math.ceil(len(p) / BS) for p in prompts}) + 1
    for p in prompts:                   # the same prefixes, another tail
        eng.submit(p[:-1] + [p[-1] + 1], 2)
    eng.run(max_steps=500)
    hits = [r.cached_prefix_blocks for r in eng.finished[len(prompts):]]
    assert hits == [(len(p) - 1) // BS for p in prompts]
    assert E._write_blocks._cache_size() == cold
