"""Ahead-of-time compiles for a described TPU v5e chip at Yi-6B's published
widths: the served kernels, the on-device parameter draw, the prefill
forward and the decode step; and Mellum2's served expert layer at its
published widths.  Nothing runs — the chip's compiler refuses
here what it would refuse on the chip (tiling, VMEM, HBM fit).

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.configs import get_config
from repro.kernels.harvest_copy.kernel import dequantize_reload, quantize_demote
from repro.models import model as M
from repro.models.params import _normal_leaf
from repro.serving import engine as E

#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 2**30
CFG = get_config("yi-6b")
#: elements of one f32 KV block: layers x positions x K/V x kv heads x dim
BLOCK_ELEMS = (CFG.num_layers * chip_smoke.BLOCK_SIZE * 2 * CFG.num_kv_heads
               * CFG.resolved_head_dim)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)


@pytest.mark.parametrize("fidelity", ["int8", "int4", "fp8"])
def test_fidelity_kernels_compile(one_chip, fidelity):
    """quantize_demote and dequantize_reload at one Yi-6B KV block per
    row, batches of 4 blocks out of a 16-slot pool."""
    pool = jax.ShapeDtypeStruct((16, BLOCK_ELEMS), jnp.float32,
                                sharding=one_chip)
    ids = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    quant = jax.jit(functools.partial(quantize_demote, fidelity=fidelity,
                                      interpret=False))
    q = quant.lower(pool, ids).compile()
    assert "tpu_custom_call" in q.as_text()
    values, scales = jax.eval_shape(quant, pool, ids)
    dequant = jax.jit(functools.partial(dequantize_reload, fidelity=fidelity,
                                        interpret=False))
    d = dequant.lower(pool, _sds(values, one_chip), _sds(scales, one_chip),
                      ids).compile()
    assert "tpu_custom_call" in d.as_text()


def test_param_draw_has_no_f32_transient(one_chip):
    """The largest leaf (the stacked MLP input projection) is drawn by one
    program whose scratch is far below the f32 draw it replaces."""
    shape = (CFG.num_layers, CFG.d_model, CFG.d_ff)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(functools.partial(
        _normal_leaf, shape=shape, dtype=jnp.dtype(jnp.bfloat16),
        scale=0.0156)).lower(key).compile()
    ma = compiled.memory_analysis()
    f32_bytes = int(np.prod(shape)) * 4
    assert ma.output_size_in_bytes == f32_bytes // 2
    assert ma.temp_size_in_bytes < f32_bytes // 16, ma


@pytest.fixture(scope="module")
def abstract_params(one_chip):
    params = jax.eval_shape(
        functools.partial(M.init_params, cfg=CFG), jax.random.PRNGKey(0))
    return _sds(params, one_chip)


def test_prefill_forward_compiles_and_fits(one_chip, abstract_params):
    s = 48
    batch = _sds({"tokens": jnp.zeros((1, s), jnp.int32),
                  "positions": jnp.zeros((1, s), jnp.int32)}, one_chip)
    fn = jax.jit(lambda p, b: M.forward(p, b, CFG, None, want_kv=True))
    compiled = fn.lower(abstract_params, batch).compile()
    assert _bytes(compiled) < V5E_HBM_BYTES


def test_decode_step_compiles_and_fits(one_chip, abstract_params):
    """The engine's decode step at the smoke's largest pool geometry."""
    b, n_slots = chip_smoke.MAX_BATCH, chip_smoke.ROOMY_SLOTS
    L, nkv, hd = M.num_kv_layers(CFG), CFG.num_kv_heads, CFG.resolved_head_dim
    pool = jnp.zeros((L, n_slots, chip_smoke.BLOCK_SIZE, nkv, hd),
                     jnp.float32)
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    state = jax.eval_shape(lambda: M.DecodeState(
        tokens=i32((b,)), pos=i32((b,)),
        kv=M.KVPools(pool_k=pool, pool_v=pool, slot_req=i32((n_slots,)),
                     slot_base=i32((n_slots,)), append_slot=i32((b,)),
                     append_off=i32((b,))),
        peer=None, states=None))
    fn = jax.jit(lambda p, st: M.serve_step(p, st, CFG, None))
    compiled = fn.lower(abstract_params, _sds(state, one_chip)).compile()
    assert _bytes(compiled) < V5E_HBM_BYTES


#: the cells' pool geometry: 8 rows over 640 slots of 16 positions
CELL_BATCH, CELL_SLOTS = 8, 640


def _cell_pool(sharding):
    shape = (M.num_kv_layers(CFG), CELL_SLOTS, chip_smoke.BLOCK_SIZE,
             CFG.num_kv_heads, CFG.resolved_head_dim)
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _pool_copies(compiled, pool) -> list:
    """Instructions of the compiled program that copy a whole pool."""
    shape = f"f32[{','.join(map(str, pool.shape))}]"
    out = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([\w-]+)\(", line)
        if m and m.group(2).startswith("copy") and shape in m.group(1):
            out.append(line[:160])
    return out


def _assert_in_place(compiled, pool):
    assert not _pool_copies(compiled, pool), _pool_copies(compiled, pool)
    # both pools (K and V) are aliased from input to output
    pool_bytes = int(np.prod(pool.shape)) * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * pool_bytes


def test_decode_step_updates_pools_in_place(one_chip, abstract_params):
    """The engine's decode step, its state donated, at the cells' pool
    geometry: no copy of a pool, and both pools aliased in place."""
    b, pool = CELL_BATCH, _cell_pool(one_chip)
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    state = jax.eval_shape(lambda: M.DecodeState(
        tokens=i32((b,)), pos=i32((b,)),
        kv=M.KVPools(pool_k=jnp.zeros(pool.shape, pool.dtype),
                     pool_v=jnp.zeros(pool.shape, pool.dtype),
                     slot_req=i32((CELL_SLOTS,)),
                     slot_base=i32((CELL_SLOTS,)),
                     append_slot=i32((b,)), append_off=i32((b,))),
        peer=None, states=None))
    compiled = E._serve_step.lower(abstract_params, _sds(state, one_chip),
                                   cfg=CFG, rules=None).compile()
    _assert_in_place(compiled, pool)


@pytest.mark.parametrize("positions", [16, 1024])
def test_block_write_updates_pools_in_place(one_chip, positions):
    """The engine's block write, its pools donated, for one block (a
    reload) and for a 1,024-position prefill's 64 blocks in bf16."""
    pool = _cell_pool(one_chip)
    k = jax.ShapeDtypeStruct(
        (pool.shape[0], 1, positions) + pool.shape[3:], jnp.bfloat16,
        sharding=one_chip)
    slots = jax.ShapeDtypeStruct((positions // chip_smoke.BLOCK_SIZE,),
                                 jnp.int32, sharding=one_chip)
    compiled = E._write_blocks.lower(pool, pool, slots, k, k).compile()
    _assert_in_place(compiled, pool)


def _stack_copies(compiled, dims) -> list:
    """Instructions of the compiled program that copy or transpose a whole
    bf16 array of ``dims`` (in any order): a copy or a transpose, or any
    other instruction but a bitcast that lays it out in another order
    than its shape's."""
    out = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = bf16\[([\d,]+)\]\{([\d,]+)"
                     r"\S* ([\w-]+)\(", line)
        if not m or sorted(map(int, m.group(2).split(","))) != sorted(dims):
            continue
        name, layout, op = m.group(1), m.group(3).split(","), m.group(4)
        reordered = layout != sorted(layout, reverse=True)
        if (op in ("copy", "transpose")
                or name.startswith(("copy", "transpose"))
                or (reordered and op != "bitcast")):
            out.append(line[:160])
    return out


def test_mellum_decode_computes_its_held_experts_densely(one_chip):
    """Mellum2 at its published widths, one period of four layers, 16 of
    64 experts held, as served in its batch cell: the decode step of 32
    rows has no grouped matmul and copies or transposes no whole expert
    stack; nor has a prefill of 1,024 positions, the cell's longest,
    while one of 2,048 keeps the grouped matmuls."""
    c = get_config("mellum2-12b-a2.5b")
    cfg = dataclasses.replace(c, num_layers=4, moe=dataclasses.replace(
        c.moe, held=16))
    params = _sds(jax.eval_shape(functools.partial(M.init_params, cfg=cfg),
                                 jax.random.PRNGKey(0)), one_chip)
    b, n_slots = 32, 64
    pool = jnp.zeros((cfg.num_layers, n_slots, chip_smoke.BLOCK_SIZE,
                      cfg.num_kv_heads, cfg.resolved_head_dim), jnp.float32)
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    state = jax.eval_shape(lambda: M.DecodeState(
        tokens=i32((b,)), pos=i32((b,)),
        kv=M.KVPools(pool_k=pool, pool_v=pool, slot_req=i32((n_slots,)),
                     slot_base=i32((n_slots,)), append_slot=i32((b,)),
                     append_off=i32((b,))),
        peer=None, states=None))
    decode = E._serve_step.lower(params, _sds(state, one_chip), cfg=cfg,
                                 rules=None).compile()
    text = decode.as_text()
    assert "ragged-dot" not in text
    stack = (16, cfg.d_model, cfg.moe.d_ff_expert)
    assert not _stack_copies(decode, stack), _stack_copies(decode, stack)
    for s in (1024, 2048):
        batch = _sds({"tokens": jnp.zeros((1, s), jnp.int32),
                      "positions": jnp.zeros((1, s), jnp.int32)}, one_chip)
        prefill = E._forward.lower(params, batch, cfg=cfg, rules=None,
                                   want_kv=True).compile()
        assert ("ragged-dot" in prefill.as_text()) == (s > 1024), s
        if s <= 1024:
            assert not _stack_copies(prefill, stack)
