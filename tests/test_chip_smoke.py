"""The chip smoke's phases on the CPU at Yi-6B's reduced widths: the same
flags, requests and checks ``chip_smoke.py`` runs on the chip at full
width, so a broken phase shows here on every change."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import chip_smoke as cs
from repro.configs import get_config
from repro.core import Fidelity, FidelityPolicy
from repro.models import model as M

SEED = 0


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config(cs.ARCH).reduced()
    params = M.init_params(jax.random.PRNGKey(SEED), cfg)
    prompts = cs.make_prompts(cfg, SEED)
    roomy = cs.serve(cfg, params, prompts, cs.serve_flags(
        cfg, local_slots=cs.ROOMY_SLOTS, scheduler="fcfs", seed=SEED))
    return cfg, params, prompts, roomy


def _harvest(smoke, fidelity="off", policy=None):
    cfg, params, prompts, _ = smoke
    return cs.serve(cfg, params, prompts, cs.serve_flags(
        cfg, local_slots=cs.HARVEST_SLOTS, scheduler="fair", seed=SEED,
        fidelity=fidelity), policy)


def test_prompts_and_roomy_run(smoke):
    cfg, _, prompts, roomy = smoke
    lo, hi = cs.PROMPT_LEN
    assert len(prompts) == cs.NUM_REQUESTS
    assert all(lo <= len(p) <= hi for p in prompts)
    assert all(len(t) == cs.NEW_TOKENS for t in roomy.tokens)
    assert all(0 <= x < cfg.vocab_size for t in roomy.tokens for x in t)
    assert roomy.kv["evict_to_peer"] == roomy.kv["evict_to_host"] == 0


def test_harvest_run_moves_blocks_and_keeps_tokens(smoke):
    roomy = smoke[3]
    harvest = _harvest(smoke)
    assert harvest.kv["evict_to_peer"] > 0 and harvest.kv["evict_to_host"] > 0
    assert harvest.kv["reload_peer"] > 0 and harvest.kv["reload_host"] > 0
    assert harvest.tokens == roomy.tokens


def test_reference_check_passes_on_served_tokens(smoke):
    cfg, params, prompts, roomy = smoke
    ref = cs.reference_logits(cfg, params, prompts, roomy.tokens)
    assert ref.shape == (cs.NUM_REQUESTS, cs.NEW_TOKENS, cfg.vocab_size)
    checked, ties, failures = cs.check_reference(ref, roomy.tokens)
    assert checked == cs.NUM_REQUESTS * cs.NEW_TOKENS
    assert ties < checked
    assert failures == []


def test_reference_check_catches_a_wrong_token(smoke):
    """Swap one clear-margin token for the reference's worst one."""
    cfg, params, prompts, roomy = smoke
    ref = cs.reference_logits(cfg, params, prompts, roomy.tokens)
    margins = np.diff(np.sort(ref, axis=-1)[..., -2:], axis=-1)[..., 0]
    r, j = np.unravel_index(np.argmax(margins), margins.shape)
    assert margins[r, j] >= cs.TIE_TOL
    bad = [list(t) for t in roomy.tokens]
    bad[r][j] = int(np.argmin(ref[r, j]))
    _, _, failures = cs.check_reference(ref, bad)
    assert [(f[0], f[1]) for f in failures] == [(r, j)]


@pytest.mark.parametrize("fidelity", ["int8", "int4"])
def test_fidelity_runs_demote_quantized(smoke, fidelity):
    policy = (FidelityPolicy(mode="slo", throughput=Fidelity.INT4)
              if fidelity == "int4" else None)
    s = _harvest(smoke, "slo", policy)
    assert s.fid["demote_quantized"] > 0
    assert s.fid[f"demote_{fidelity}"] == s.fid["demote_quantized"]
    assert all(len(t) == cs.NEW_TOKENS for t in s.tokens)


def test_main_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, cs.__file__], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_fidelity_kernels_round_trip(smoke):
    assert cs.check_fidelity_kernels(smoke[0], SEED) == []


def test_compile_cache_dir(monkeypatch):
    """The environment's cache directory stands; without one the cache
    goes to the fixed directory inside the checkout."""
    from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert use_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        assert REPO_CACHE_DIR.parent == Path(cs.__file__).resolve().parent
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
