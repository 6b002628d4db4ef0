#!/usr/bin/env python3
"""Time the served expert layer's two paths on the chip, at Mellum2's
widths, over a range of token counts.

    python benchmarks/moe_path_probe.py [--tokens 8,16,...] [--top-k 8,...]
                                        [--out FILE]

One device holds 16 of the router's 64 experts (d_model 2304, 896 wide,
top 8, or each ``--top-k`` given), stacked over 28 layers as the model
stacks them.  For each token count, each path (the grouped matmuls and
the dense product of ``repro.models.moe``) runs inside a scan over the
28 layers, as in the served programs, so a copy of a layer's slice of
the stack is timed with it.  The time of one layer is the median over repeats, each ended by
``block_until_ready``, of the scan's time over 28.  Each line also gives
the held experts' bytes over that time and, for one layer on the same
input, the largest difference of the two paths' outputs relative to
their scale and whether their routes agree.  Refuses a CPU (exit 1).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models import moe  # noqa: E402
from repro.models.params import _normal_leaf  # noqa: E402

#: TPU v5e HBM bandwidth (Google Cloud documentation, "TPU v5e")
HBM_BYTES_PER_S = 819e9
REPEATS = 20


def _stack(key, shape):
    return jax.jit(functools.partial(_normal_leaf, shape=shape,
                                     dtype=jnp.dtype(jnp.bfloat16),
                                     scale=0.02))(key)


def _layer(x, lp, cfg, path):
    """One layer's held experts through ``path``: (y (t, d) float32,
    routes (held,))."""
    local, weights, routes = moe._route(x, lp["router"], cfg, 0,
                                        cfg.moe.num_held)
    return path(x, lp, cfg, local, weights, routes), routes


def _layers(cfg, path):
    """A jitted scan of ``path`` over the stacked layers: x (t, d) -> x
    plus each layer's expert output in turn."""
    def body(x, lp):
        y, _ = _layer(x, lp, cfg, path)
        return (x.astype(jnp.float32) + y).astype(x.dtype), None
    return jax.jit(lambda x, stacks: jax.lax.scan(body, x, stacks)[0])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", default="8,16,32,64,128,256,512,1024")
    ap.add_argument("--top-k", default="8")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"moe_path_probe: a TPU is needed, found {dev.platform}",
              file=sys.stderr)
        return 1
    base = get_config("mellum2-12b-a2.5b")
    d, f, n = base.d_model, base.moe.d_ff_expert, base.num_layers
    experts, held = base.moe.num_experts, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    stacks = {"router": _stack(keys[0], (n, d, experts)),
              "wg": _stack(keys[1], (n, held, d, f)),
              "wi": _stack(keys[2], (n, held, d, f)),
              "wo": _stack(keys[3], (n, held, f, d))}
    layer0 = jax.tree.map(lambda a: a[0], stacks)
    held_bytes = 3 * held * d * f * 2
    lines = []
    for k in (int(v) for v in args.top_k.split(",")):
        cfg = dataclasses.replace(base, moe=dataclasses.replace(
            base.moe, held=held, top_k=k))
        paths = {"grouped": moe._grouped_experts,
                 "dense": moe._dense_experts}
        scans = {name: _layers(cfg, path) for name, path in paths.items()}
        for t in (int(v) for v in args.tokens.split(",")):
            x = jax.random.normal(jax.random.fold_in(keys[4], t), (t, d),
                                  jnp.bfloat16)
            row = {"top_k": k, "tokens": t, "chosen": "dense"
                   if moe.dense_experts(t, k, experts, held) else "grouped"}
            for name, fn in scans.items():
                jax.block_until_ready(fn(x, stacks))          # compile
                times = []
                for _ in range(REPEATS):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(x, stacks))
                    times.append(time.perf_counter() - t0)
                ms = 1e3 * statistics.median(times) / n
                row[f"{name}_ms_per_layer"] = round(ms, 5)
                row[f"{name}_gb_per_s"] = round(held_bytes / ms / 1e6, 1)
            (yg, rg), (yd, rd) = (
                jax.jit(functools.partial(_layer, cfg=cfg, path=paths[name]))(
                    x, layer0) for name in ("grouped", "dense"))
            row["rel_gap"] = float(jnp.abs(yg - yd).max()
                                   / jnp.abs(yg).max())
            row["same_routes"] = bool((rg == rd).all())
            row["held_touched"] = float((rg > 0).mean())
            lines.append(row)
            print(json.dumps(row), flush=True)
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": jax.device_count()},
              "hbm_bytes_per_s": HBM_BYTES_PER_S, "rows": lines}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
