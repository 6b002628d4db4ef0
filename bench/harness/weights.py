"""Random served weights, drawn on the device in one jitted call.

The tree has the program's parameter layout (its schema names the leaves
and says which are norms), and every leaf is drawn from ``key`` alone:
the benchmark owns the weights, so the reference can read the very same
arrays without taking anything the program made.  Stacked per-layer
leaves are drawn one layer at a time inside the program, so no
full-size float32 draw is ever live beside the weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: scale of a normal weight: 0.02, or 1/sqrt(fan_in) where that is less
MAX_SCALE = 0.02


def _leaf(key, shape, dtype, init, stacked):
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init != "normal":
        raise ValueError(f"unsupported init {init!r}")
    inner = shape[1:] if stacked else shape
    fan_in = inner[-2] if len(inner) >= 2 else inner[-1]
    scale = min(MAX_SCALE, 1.0 / math.sqrt(max(fan_in, 1)))

    def one(k):
        return (jax.random.normal(k, inner, jnp.float32) * scale).astype(dtype)

    if not stacked:
        return one(key)
    return jax.lax.map(one, jax.random.split(key, shape[0]))


def _draw_impl(key, specs):
    keys = jax.random.split(key, len(specs))
    return [_leaf(k, shape, jnp.dtype(dtype), init, stacked)
            for k, (shape, dtype, init, stacked) in zip(keys, specs)]


_draw = jax.jit(_draw_impl, static_argnames=("specs",))


def draw(schema, key):
    """Materialise ``schema`` (a tree of the program's ``ParamDef``) from
    ``key`` on the default device, in one compiled program."""
    leaves, treedef = jax.tree.flatten(
        schema, is_leaf=lambda x: hasattr(x, "init") and hasattr(x, "shape"))
    specs = tuple((tuple(p.shape), str(p.dtype), p.init,
                   bool(p.axes) and p.axes[0] == "layers") for p in leaves)
    return jax.tree.unflatten(treedef, _draw(key, specs=specs))


def key_from_seed(seed: int):
    """A PRNG key from any non-negative whole number (seeds may pass
    32 bits): the low word seeds it, the high words are folded in."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
        hi >>= 32
    return key
