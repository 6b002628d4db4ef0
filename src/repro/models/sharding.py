"""Logical-axis sharding rules (MaxText-style).

Model code annotates tensors with *logical* axis names; a :class:`ShardingRules`
maps those to physical mesh axes.  With ``rules=None`` every annotation is a
no-op, so the same model code runs on a single CPU device (smoke tests) and on
the 512-chip production mesh (dry-run) unchanged.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


# Default logical -> physical mapping for the (data, model) production mesh.
# "fsdp" style: weight embed dims shard over the data axis.
DEFAULT_RULES = {
    # activations
    "act_batch": "data",
    "act_seq": None,
    "act_embed": "model",   # residual-stream tensor sharding (remat residuals)
    "act_heads": "model",
    "act_ff": "model",
    # weights
    "embed_fsdp": "data",      # d_model dim of weight matrices
    "heads": "model",          # attention head output dims
    "kv_heads": "model",       # only used when num_kv_heads % axis_size == 0
    "ff": "model",             # dense FFN hidden dim
    "experts": "model",        # MoE expert dim
    "expert_capacity": "data",  # dispatch-buffer capacity dim (see moe.py)
    "expert_ff": None,
    "vocab": "model",
    # kv-cache / recurrent state
    "kv_blocks": ("data", "model"),   # paged KV pool block dim (flash-decode)
    "kv_seq": "model",                # prefill KV stack sequence dim
    "state_heads": "model",           # SSM / xLSTM recurrent state heads
    # layer-stacking dim is never sharded
    "layers": None,
}


@dataclass(frozen=True)
class ShardingRules:
    mesh: jax.sharding.Mesh
    rules: dict = field(default_factory=lambda: dict(DEFAULT_RULES))

    def axis(self, logical: Optional[str]) -> Axis:
        if logical is None:
            return None
        return self.rules.get(logical)

    def spec(self, *logical_axes: Optional[str]) -> P:
        """Build a PartitionSpec, dropping mappings that don't divide evenly.

        Divisibility is the caller's job for weights (schema checks it); this
        just translates names.
        """
        return P(*[self.axis(a) for a in logical_axes])

    def sharding(self, *logical_axes: Optional[str]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical_axes))

    def axis_size(self, mesh_axis: Axis) -> int:
        if mesh_axis is None:
            return 1
        if isinstance(mesh_axis, str):
            mesh_axis = (mesh_axis,)
        n = 1
        for a in mesh_axis:
            n *= self.mesh.shape[a]
        return n


def logical_to_spec(rules: Optional[ShardingRules], *logical_axes, shape=None) -> P:
    """Translate logical axes to a PartitionSpec, dropping any mapping that
    does not divide the corresponding dimension of ``shape`` evenly."""
    if rules is None:
        return P()
    axes = [rules.axis(a) for a in logical_axes]
    if shape is not None:
        for i, ax in enumerate(axes):
            if ax is None:
                continue
            if shape[i] % rules.axis_size(ax) != 0:
                axes[i] = None
    return P(*axes)


def shard(x, rules: Optional[ShardingRules], *logical_axes):
    """Apply a sharding constraint by logical axis names (no-op if rules None)."""
    if rules is None:
        return x
    spec = logical_to_spec(rules, *logical_axes, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))
