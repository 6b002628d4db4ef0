"""Operations and bytes a step needs, from the configuration's sizes.

Each reference module's ``counts(conf)`` gives them for its architecture;
:class:`DenseCounts` holds those of the dense decoder.  These are the
least any implementation needs, so no change to the program can push a
share computed from them past 100%:

- weights are read once per step in the served type (bf16, 2 bytes);
- keys and values are read once per live position attended, at 2 bytes
  an element, whatever type the program keeps them in;
- a matmul of an ``m x k`` operand with a ``k x n`` weight is
  ``2 m k n`` operations; the embedding is a lookup and costs none;
- a prefill needs the output head for its last position only, and no
  work for the positions a prefix cache served.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Protocol

ELEM_BYTES = 2


class Counts(Protocol):
    """The least work of one architecture's steps, as its reference module's
    ``counts(conf)`` returns it (``bench/reference/__init__.py``)."""

    def decode_flops(self, rows: int, attended: int) -> int: ...

    def decode_bytes(self, rows: int, attended: int) -> int: ...

    def prefill_flops(self, cached: int, new: int) -> int: ...


@dataclass(frozen=True)
class DenseCounts:
    """The counts of a dense decoder layer: attention with grouped K/V
    heads and a gated MLP."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "DenseCounts":
        return cls(c["num_layers"], c["d_model"], c["num_heads"],
                   c["num_kv_heads"], c["head_dim"], c["d_ff"],
                   c["vocab_size"])

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies in one layer (q, k, v, o and the
        gated MLP's three matrices)."""
        d, hd = self.d_model, self.head_dim
        attn = d * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * d
        return attn + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def weight_bytes(self) -> int:
        """Every weight but the embedding table, plus the norms."""
        norms = (2 * self.layers + 1) * self.d_model
        return ELEM_BYTES * (self.layers * self.layer_matmul_params
                             + self.head_params + norms)

    def attn_flops(self, positions: int) -> int:
        """Scores and weighted values of one query over ``positions``."""
        return 4 * self.layers * self.heads * self.head_dim * positions

    def kv_bytes(self, positions: int) -> int:
        return (ELEM_BYTES * 2 * self.layers * self.kv_heads * self.head_dim
                * positions)

    def decode_flops(self, rows: int, attended: int) -> int:
        """One decode step of ``rows`` queries over ``attended`` live
        positions in all (each row's own, its new token included)."""
        return (2 * rows * (self.layers * self.layer_matmul_params
                            + self.head_params)
                + self.attn_flops(attended))

    def decode_bytes(self, rows: int, attended: int) -> int:
        return (self.weight_bytes + ELEM_BYTES * rows * self.d_model
                + self.kv_bytes(attended))

    def prefill_flops(self, cached: int, new: int) -> int:
        """A prefill of ``new`` positions after ``cached`` served ones: the
        layers for each new position, causal attention over what precedes
        it, and the output head for the last position."""
        if new <= 0:
            return 0
        # positions cached+1 .. cached+new attended by the new queries
        attended = new * cached + new * (new + 1) // 2
        return (2 * new * self.layers * self.layer_matmul_params
                + 2 * self.head_params + self.attn_flops(attended))


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
