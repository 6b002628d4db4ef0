#!/usr/bin/env python3
"""Chip smoke: serve Yi-6B at its published widths on one TPU, end to end.

    python chip_smoke.py [--seed N]

Builds the server exactly as ``python -m repro.launch.serve`` does
(``HarvestRuntime`` -> ``runtime.server`` -> ``HarvestServer.run``), over
random bf16 weights drawn on the device from ``--seed``, and checks what
it serves:

  1. device   — a TPU, or exit 1 with no result line;
  2. roomy    — 8 requests (24-64 prompt tokens, 16 new tokens each),
                enough local KV slots that nothing evicts;
  3. harvest  — the same requests, fair scheduler, 8 local slots and a peer
                budget of 3 KV blocks: blocks evict to peer and host and
                reload, and every token equals the roomy run's;
  4. reference— one plain, unpaged forward over each request's prompt and
                output: every emitted token is the reference argmax, or
                within ``TIE_TOL`` of it where the top two are that close;
  5. fidelity — the harvest run with int8 (``--fidelity-policy slo``) and
                with int4 demotion, through the compiled Pallas kernels,
                and a round trip of each wire fidelity's kernels against
                its error bound.

The wall-clock seconds it prints are a smoke's (compile, serve), not a
benchmark.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import sys
import time
from collections import Counter
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

REPO = Path(__file__).resolve().parent
if not (REPO / "src" / "repro").is_dir():
    sys.exit(f"chip_smoke: no src/repro beside {Path(__file__).name}; "
             "run it from a checkout of the repository")
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import Fidelity, FidelityPolicy  # noqa: E402
from repro.core.tiers import kv_block_bytes  # noqa: E402
from repro.kernels.harvest_copy.ops import (  # noqa: E402
    dequantize_blocks, quantize_blocks)
from repro.launch import serve as serve_cli  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serving import ServeRequest  # noqa: E402

ARCH = "yi-6b"
NUM_REQUESTS = 8
PROMPT_LEN = (24, 64)          # inclusive
NEW_TOKENS = 16
BLOCK_SIZE = 16
MAX_BATCH = 4
#: roomy: every request's blocks fit at once (8 x ceil(80 / 16))
ROOMY_SLOTS = 40
HARVEST_SLOTS = 8
PEER_BLOCKS = 3
#: near-tie tolerance on reference logits.  Logits are bf16; at the
#: magnitudes random weights give (|logit| < 8) one bf16 ulp is at most
#: 2^-5, and the paged decode (f32 pools, per-block softmax partials) and
#: the plain forward (one bf16 attention over the sequence) round
#: differently, so a top-2 margin under four ulps is a tie the two paths
#: may break either way.
TIE_TOL = 0.125
#: round-trip error bound per wire fidelity, as a fraction of the block's
#: absmax: half a quantization step for int8/int4 with 2x headroom, and
#: e4m3's 2^-4 relative rounding with headroom for fp8
FID_ERR = {"int8": 1 / 127, "int4": 1 / 7, "fp8": 0.07}
#: JAX's monitoring events for tracing, lowering and compiling
_COMPILE_EVENTS = "/jax/core/compile/"


class CompileLog:
    """Seconds JAX spends tracing, lowering and compiling (persistent-cache
    reads included), and the cache's hits and misses, from JAX's own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.events: Counter = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name.startswith(_COMPILE_EVENTS):
            self.seconds += secs

    def _event(self, name, **_):
        if name.startswith("/jax/compilation_cache/cache_"):
            self.events[name.rsplit("/", 1)[1]] += 1


@dataclass
class Served:
    tokens: List[List[int]]
    kv: Dict[str, int]
    fid: Dict[str, float]
    wall_s: float
    compile_s: float


def make_prompts(cfg, seed: int) -> List[List[int]]:
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LEN
    return [rng.integers(3, cfg.vocab_size, size=int(rng.integers(lo, hi + 1)))
            .tolist() for _ in range(NUM_REQUESTS)]


def serve_flags(cfg, *, local_slots: int, scheduler: str, seed: int,
                fidelity: str = "off") -> List[str]:
    """``repro.launch.serve`` flags for one run (peer budget in blocks)."""
    peer_mb = PEER_BLOCKS * kv_block_bytes(cfg, BLOCK_SIZE) / 2**20
    return ["--block-size", str(BLOCK_SIZE), "--max-batch", str(MAX_BATCH),
            "--local-slots", str(local_slots), "--scheduler", scheduler,
            "--durability", "host_backed", "--peer-budget-mb", repr(peer_mb),
            "--fidelity-policy", fidelity, "--seed", str(seed)]


def serve(cfg, params, prompts, flags: List[str],
          fidelity_policy: Optional[FidelityPolicy] = None,
          log: Optional[CompileLog] = None) -> Served:
    """One run of the CLI's server over ``prompts`` (all arrive at once)."""
    args = serve_cli.parse_args(flags)
    if fidelity_policy is not None:
        args.fidelity_policy = fidelity_policy
    _, server = serve_cli.build_server(cfg, params, args)
    c0 = log.seconds if log else 0.0
    t0 = time.perf_counter()
    stats = server.run([ServeRequest(list(p), NEW_TOKENS) for p in prompts])
    jax.block_until_ready((server.engine.pool_k, server.engine.pool_v))
    wall = time.perf_counter() - t0
    assert all(h.state == "done" for h in server.handles), \
        [h.state for h in server.handles]
    return Served([h.tokens for h in server.handles],
                  dict(server.engine.kv_mgr.stats),
                  dict(stats.metrics.get("fid", {})), wall,
                  (log.seconds if log else 0.0) - c0)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _emitted_logits(params, tokens, at, cfg):
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    logits, _ = M.forward(params, {"tokens": tokens, "positions": positions},
                          cfg)
    return jnp.take_along_axis(logits, at[..., None], axis=1).astype(
        jnp.float32)


def reference_logits(cfg, params, prompts, outputs) -> np.ndarray:
    """(requests, new tokens, vocab) f32 logits of one plain forward over
    each prompt + output, at the positions that emitted each token.  The
    batch is right-padded to a block multiple; causal attention keeps the
    pad out of every real position."""
    seqs = [p + o for p, o in zip(prompts, outputs)]
    width = BLOCK_SIZE * math.ceil(max(map(len, seqs)) / BLOCK_SIZE)
    tokens = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    at = np.array([[len(p) - 1 + j for j in range(len(o))]
                   for p, o in zip(prompts, outputs)], np.int32)
    return np.asarray(_emitted_logits(params, jnp.asarray(tokens),
                                      jnp.asarray(at), cfg=cfg))


def check_reference(ref: np.ndarray, outputs) -> tuple:
    """Every emitted token against the reference logits.  Returns
    ``(checked, near_ties, failures)``: a token must be the argmax, except
    where the top-2 margin is under ``TIE_TOL``; there it must score within
    ``TIE_TOL`` of the top."""
    checked = ties = 0
    failures = []
    for r, out in enumerate(outputs):
        for j, tok in enumerate(out):
            row = ref[r, j]
            top2 = np.partition(row, -2)[-2:]
            best = int(np.argmax(row))
            checked += 1
            if top2[1] - top2[0] < TIE_TOL:
                ties += 1
                ok = row[tok] >= top2[1] - TIE_TOL
            else:
                ok = tok == best
            if not ok:
                failures.append((r, j, tok, best, float(row[best] - row[tok]),
                                 float(top2[1] - top2[0])))
    return checked, ties, failures


def check_fidelity_kernels(cfg, seed: int) -> List[str]:
    """Demote 3 of 6 random KV-block rows at each wire fidelity and reload
    them into another pool: each reloaded row must be within its
    fidelity's error bound of the source, every other row untouched."""
    elems = M.num_kv_layers(cfg) * BLOCK_SIZE * 2 * cfg.num_kv_heads \
        * cfg.resolved_head_dim
    key_src, key_dst = jax.random.split(jax.random.PRNGKey(seed))
    src = jax.random.normal(key_src, (6, elems), jnp.float32)
    dst = jax.random.normal(key_dst, (6, elems), jnp.float32)
    ids = jnp.asarray([4, 0, 2], jnp.int32)
    fails = []
    for fidelity, bound in FID_ERR.items():
        values, scales = quantize_blocks(src, ids, fidelity=fidelity)
        got = np.asarray(dequantize_blocks(dst, values, scales, ids,
                                           fidelity=fidelity))
        want = np.asarray(src)[np.asarray(ids)]
        err = np.abs(got[np.asarray(ids)] - want).max(axis=1)
        limit = bound * np.abs(want).max(axis=1)
        kept = np.setdiff1d(np.arange(6), np.asarray(ids))
        if not (err <= limit).all():
            fails.append(f"{fidelity} kernels: round-trip error "
                         f"{err.tolist()} over {limit.tolist()}")
        if not np.array_equal(got[kept], np.asarray(dst)[kept]):
            fails.append(f"{fidelity} kernels: a row outside the reload "
                         f"set changed")
    return fails


def _moved(kv: Dict[str, int]) -> str:
    return ", ".join(f"{k} {kv.get(k, 0)}" for k in
                     ("evict_to_peer", "evict_to_host", "reload_peer",
                      "reload_host"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    log = CompileLog()
    n_dev = len(jax.devices())
    print(f"device: {dev.platform} {dev.device_kind} x{n_dev}; "
          f"jax {jax.__version__}; compile cache {cache_dir}")

    cfg = get_config(ARCH)
    print(f"model: {cfg.name} at published widths — {cfg.num_layers} layers "
          f"(no layer cut), d_model {cfg.d_model}, {cfg.num_heads} q / "
          f"{cfg.num_kv_heads} kv heads x {cfg.resolved_head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, bf16 weights from seed "
          f"{args.seed}")
    t0 = time.perf_counter()
    params = M.init_params(jax.random.PRNGKey(args.seed), cfg)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    init_compile_s = log.seconds
    print(f"params: {M.param_count(params) / 1e9:.3f} B in "
          f"{sum(x.nbytes for x in jax.tree.leaves(params)) / 2**30:.2f} GiB")

    prompts = make_prompts(cfg, args.seed)
    fails: List[str] = []
    runs: Dict[str, Served] = {}

    def run(name, slots, scheduler, fidelity="off", policy=None):
        runs[name] = s = serve(cfg, params, prompts, serve_flags(
            cfg, local_slots=slots, scheduler=scheduler, seed=args.seed,
            fidelity=fidelity), policy, log)
        print(f"{name} run: {_moved(s.kv)}")
        return s

    roomy = run("roomy", ROOMY_SLOTS, "fcfs")
    if roomy.kv.get("evict_to_peer", 0) + roomy.kv.get("evict_to_host", 0):
        fails.append("the roomy run evicted")
    harvest = run("harvest", HARVEST_SLOTS, "fair")
    evicted = harvest.kv.get("evict_to_peer", 0), \
        harvest.kv.get("evict_to_host", 0)
    reloaded = harvest.kv.get("reload_peer", 0) + \
        harvest.kv.get("reload_host", 0)
    if min(evicted) == 0 or reloaded == 0:
        fails.append(f"harvest run must evict to peer and host and reload: "
                     f"{_moved(harvest.kv)}")

    ref = reference_logits(cfg, params, prompts, roomy.tokens)
    checked, ties, bad = check_reference(ref, roomy.tokens)
    print(f"reference: {checked} emitted tokens checked against a plain "
          f"forward, {ties} near-ties (top-2 margin < {TIE_TOL}) checked "
          f"only to score within it, {len(bad)} failed")
    for r, j, tok, best, gap, margin in bad[:8]:
        fails.append(f"reference: request {r} token {j}: emitted {tok}, "
                     f"argmax {best} ({gap:.4f} above it, top-2 margin "
                     f"{margin:.4f})")
    same = harvest.tokens == roomy.tokens
    print(f"harvest tokens identical to roomy: {same}")
    if not same:
        for r, (a, b) in enumerate(zip(roomy.tokens, harvest.tokens)):
            j = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                     None)
            if j is not None:
                top2 = np.partition(ref[r, j], -2)[-2:]
                fails.append(f"harvest request {r} differs at token {j}: "
                             f"{b[j]} vs {a[j]} (reference top-2 margin "
                             f"{top2[1] - top2[0]:.4f})")

    for name, fidelity, policy in (
            ("int8", "slo", None),
            ("int4", "slo", FidelityPolicy(mode="slo",
                                           throughput=Fidelity.INT4))):
        s = run(name, HARVEST_SLOTS, "fair", fidelity, policy)
        n = int(s.fid.get("demote_quantized", 0))
        agree = sum(x == y for a, b in zip(s.tokens, roomy.tokens)
                    for x, y in zip(a, b))
        print(f"{name} run: {n} quantized demotes, "
              f"{int(s.fid.get('reload_dequantized', 0))} dequantized "
              f"reloads, {agree}/{NUM_REQUESTS * NEW_TOKENS} tokens as roomy")
        if n == 0:
            fails.append(f"{name} run demoted no block quantized")

    bad_kernels = check_fidelity_kernels(cfg, args.seed)
    print(f"fidelity kernels ({', '.join(FID_ERR)}), compiled, one KV block "
          f"per row: {len(bad_kernels)} failed round trips")
    fails += bad_kernels

    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print("smoke wall clock (one run, not a benchmark):")
    print(f"  param init {init_s:.3f} s wall = {init_compile_s:.3f} s "
          f"compile + {init_s - init_compile_s:.3f} s drawing")
    for name, s in runs.items():
        print(f"  {name:8s} {s.wall_s:.3f} s wall = {s.compile_s:.3f} s "
              f"compile + {s.wall_s - s.compile_s:.3f} s serving")
    print(f"  compile total {log.seconds:.3f} s; persistent cache "
          f"hits {log.events['cache_hits']}, "
          f"misses {log.events['cache_misses']}")
    print(f"  peak HBM in use: "
          + (f"{peak / 2**30:.3f} GiB" if peak is not None
             else "not reported"))
    if fails:
        for f in fails:
            print(f"FAIL {f}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
