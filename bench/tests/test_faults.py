"""The whole run on the CPU at a tiny size, with the served path broken
underneath: ``correct`` has to come out false for each fault a serving
cell can have, and true without one.  (A cell on one chip has no
exchange between chips to leave out.)  The sessions cell also reads
blocks back from the peer and host tiers, so a block written back wrong
is one of its faults."""
import pytest

from bench.tests.tiny import run_tiny

SEED = 2**31 + 29


def _alter_tokens(monkeypatch):
    """A token altered where it is produced: every seventh sample."""
    from repro.serving.engine import HarvestServingEngine
    orig = HarvestServingEngine._sample
    calls = [0]

    def altered(self, logits):
        tok = orig(self, logits)
        calls[0] += 1
        return (tok + 1) % logits.shape[-1] if calls[0] % 7 == 3 else tok
    monkeypatch.setattr(HarvestServingEngine, "_sample", altered)
    return calls


def _wrap_step(monkeypatch, change):
    """``change(logits, old, new)`` on each decode step's result; ``old``
    holds a copy of the KV pools the step was given, since the step takes
    its state donated and deletes them."""
    import jax
    import jax.numpy as jnp
    import repro.serving.engine as E
    orig = E._serve_step
    calls = [0]

    def broken(params, state, **kw):
        old = state._replace(kv=jax.tree.map(jnp.copy, state.kv))
        logits, new = orig(params, state, **kw)
        calls[0] += 1
        return change(logits, old, new)
    monkeypatch.setattr(E, "_serve_step", broken)
    return calls


def _state_unchanged(monkeypatch):
    """A decode step that returns its KV state unchanged."""
    return _wrap_step(monkeypatch,
                      lambda lg, old, new: (lg, new._replace(kv=old.kv)))


def _half_batch(monkeypatch):
    """Half of the batch left out: rows past the middle get no logits."""
    def change(lg, old, new):
        half = lg.shape[0] // 2
        return lg.at[half:].set(0), new
    return _wrap_step(monkeypatch, change)


def _wrap_reload(monkeypatch, change):
    from repro.serving.engine import HarvestServingEngine
    orig = HarvestServingEngine._on_reload
    calls = [0]

    def broken(self, bid, slot):
        orig(self, bid, slot)
        calls[0] += 1
        change(self, bid, slot)
    monkeypatch.setattr(HarvestServingEngine, "_on_reload", broken)
    return calls


def _reload_zeros(monkeypatch):
    """A block read back from the peer or host tier lands as zeros."""
    def zero(eng, _bid, slot):
        eng.pool_k = eng.pool_k.at[:, slot].set(0)
        eng.pool_v = eng.pool_v.at[:, slot].set(0)
    return _wrap_reload(monkeypatch, zero)


SERVED = [_alter_tokens, _state_unchanged, _half_batch]
CASES = ([("yi6b-chat-short", f) for f in SERVED]
         + [("yi6b-chat-sessions", f) for f in SERVED
            + [_reload_zeros]])


@pytest.mark.parametrize("cell", ["yi6b-chat-short", "yi6b-chat-sessions"])
def test_sound_run_is_correct(cell):
    r = run_tiny(cell, SEED)
    assert r["correct"], r["checks"]
    assert r["checks"]["tokens_compared"]["value"] >= 64


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{c}-{f.__name__[1:]}" for c, f in CASES])
def test_fault_is_not_correct(monkeypatch, cell, fault):
    calls = fault(monkeypatch)
    r = run_tiny(cell, SEED)
    assert calls[0] > 0, "the fault never fired"
    assert not r["correct"], r["checks"]
