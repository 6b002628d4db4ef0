"""The comparison that decides ``correct``, for a served model.

Once the window has closed, a sample of the requests the window finished
is drawn from the seed, the longest among them.  The plain reference that
the cell's configuration names (``bench/reference/<module>.py``) runs
once over each prompt with its served tokens, and the number compared is
the widest gap by which a served token's logit lies below the
reference's best logit at that position.  Greedy decoding serves the
program's own top token, so only rounding separates the two; the limit
in the cell's file was set between what sound runs read and what the
reference computed in float8 reads (``bench/calibrate.py``).
"""
from __future__ import annotations

from dataclasses import dataclass
from types import ModuleType
from typing import Any, List, Sequence

import numpy as np

#: served tokens the sample aims for, and the most requests it takes
SAMPLE_TOKENS = 320
SAMPLE_MAX_REQUESTS = 6


@dataclass
class Sample:
    tokens: np.ndarray      # (n, width) int32: prompt, served tokens, pad
    served: np.ndarray      # (n, width) int32: at position p, the token
    #                        # served after position p (0 where none)
    mask: np.ndarray        # (n, width) bool: positions that served one
    requests: int
    served_tokens: int


@dataclass(frozen=True)
class Reference:
    """A cell's plain reference: the module its configuration names, and
    the configuration as that module takes it."""
    module: ModuleType
    cfg: Any

    @classmethod
    def of(cls, cell) -> "Reference":
        return cls(cell.reference, cell.reference.frozen(cell.config))

    def gaps(self, weights, tokens, probe, precision: str):
        return self.module.gaps(weights, tokens, probe, self.cfg, precision)


def draw_sample(turns: Sequence, seed: int, width: int) -> Sample:
    """The longest finished request, then others in a seed-drawn order,
    until :data:`SAMPLE_TOKENS` served tokens or
    :data:`SAMPLE_MAX_REQUESTS` requests."""
    done = [t for t in turns if t.finished]
    if not done:
        return Sample(np.zeros((0, width), np.int32),
                      np.zeros((0, width), np.int32),
                      np.zeros((0, width), bool), 0, 0)
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].prompt) + len(done[i].tokens))
    rng = np.random.default_rng([seed, 2])
    order = [longest] + [int(i) for i in rng.permutation(len(done))
                         if i != longest]
    picked: List = []
    n_served = 0
    for i in order:
        if n_served >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX_REQUESTS:
            break
        picked.append(done[i])
        n_served += done[i].max_new
    toks = np.zeros((len(picked), width), np.int32)
    served = np.zeros_like(toks)
    mask = np.zeros(toks.shape, bool)
    for r, t in enumerate(picked):
        seq = t.prompt + t.tokens[:t.max_new]
        if len(seq) > width:
            raise ValueError(f"a served sequence of {len(seq)} positions "
                             f"exceeds the reference's width {width}")
        toks[r, :len(seq)] = seq
        p0 = len(t.prompt) - 1
        served[r, p0:p0 + t.max_new] = t.tokens[:t.max_new]
        mask[r, p0:p0 + t.max_new] = True
    return Sample(toks, served, mask, len(picked), int(mask.sum()))


def widest_gap(weights, sample: Sample, ref: Reference, probe=None,
               precision: str = "f32", block: int = 2) -> float:
    """The widest reference-logit gap of ``probe``'s tokens (default: the
    served ones) at the sampled positions; the reference runs over
    ``block`` sequences at a time."""
    import jax.numpy as jnp
    probe = sample.served if probe is None else probe
    worst = 0.0
    for lo in range(0, sample.requests, block):
        sl = slice(lo, lo + block)
        toks, pr, m = sample.tokens[sl], probe[sl], sample.mask[sl]
        pad = block - len(toks)
        if pad:   # keep one compiled shape: pad with a copy of a row
            toks, pr = (np.concatenate([a, np.repeat(a[:1], pad, 0)])
                        for a in (toks, pr))
            m = np.concatenate([m, np.zeros((pad,) + m.shape[1:], bool)])
        gap, _ = ref.gaps(weights, jnp.asarray(toks), jnp.asarray(pr),
                          precision)
        g = np.asarray(gap)[m]
        if g.size:
            worst = max(worst, float(g.max()))
    return worst


def control_tokens(weights, sample: Sample, ref: Reference, block: int = 2
                   ) -> np.ndarray:
    """At each sampled position, the token the float8 reference ranks
    first (the control)."""
    import jax.numpy as jnp
    out = np.zeros_like(sample.served)
    for lo in range(0, sample.requests, block):
        toks = sample.tokens[lo:lo + block]
        pad = block - len(toks)
        if pad:
            toks = np.concatenate([toks, np.repeat(toks[:1], pad, 0)])
        _, top = ref.gaps(weights, jnp.asarray(toks),
                          jnp.zeros(toks.shape, jnp.int32), "fp8")
        out[lo:lo + block] = np.asarray(top)[:block - pad]
    return out
