#!/usr/bin/env python3
"""Compile each cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python bench/aot_check.py [cell ...]

For every cell (default: all in ``BENCHMARK.json``) this compiles, for
one chip of a described ``v5e:2x2``, the weight draw, the decode step at
the cell's batch and slot count, the prefill at the longest prompt the
traffic sends, and the programs of the reference the configuration names
at its width (its ``lower``), then prints each program's memory analysis
and the sum a run holds at once: weights, both pools twice (the decode
step does not donate them), and the largest temporary.  Nothing runs,
so it says nothing about time.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
GiB = 2**30


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.harness import check, spec, weights
    from bench.harness.traffic import Traffic
    from bench.run import program_config
    from repro.launch import serve as serve_cli
    from repro.models import model as M
    from repro.models.params import build_schema

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    hbm = spec.load_peaks()["TPU v5 lite"]["hbm_bytes"]
    names = (argv or sys.argv[1:]) or [
        w["name"] for w in spec._load_json(ROOT / "BENCHMARK.json")
        ["workloads"]]

    def sds(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=one)

    def report(what, compiled):
        m = compiled.memory_analysis()
        print(f"  {what}: args {m.argument_size_in_bytes / GiB:.3f} GiB, "
              f"out {m.output_size_in_bytes / GiB:.3f} GiB, temp "
              f"{m.temp_size_in_bytes / GiB:.3f} GiB")
        return m

    for name in names:
        cell = spec.load_cell(name)
        cfg = program_config(cell.config)
        args = serve_cli.parse_args(list(cell.server["flags"]))
        traffic = Traffic(cell.traffic, 0, 1.0, 1.0, cfg.vocab_size)
        print(f"{name}: {cfg.name}, batch {args.max_batch}, "
              f"{args.local_slots} slots of {args.block_size}")
        schema = build_schema(cfg)
        abstract = jax.tree.map(sds, M.abstract_params(cfg))
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
        leaves, _ = jax.tree.flatten(
            schema, is_leaf=lambda x: hasattr(x, "init"))
        specs = tuple((tuple(p.shape), str(p.dtype), p.init,
                       bool(p.axes) and p.axes[0] == "layers")
                      for p in leaves)
        draw = report("weight draw", weights._draw.lower(
            key, specs=specs).compile())
        w_bytes = draw.output_size_in_bytes

        nkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        pool = jax.ShapeDtypeStruct(
            (M.num_kv_layers(cfg), args.local_slots, args.block_size, nkv,
             hd), jnp.float32, sharding=one)
        B = args.max_batch
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
        state = M.DecodeState(
            tokens=i32(B), pos=i32(B),
            kv=M.KVPools(pool, pool, i32(args.local_slots),
                         i32(args.local_slots), i32(B), i32(B)),
            peer=None, states=None)
        dec = report("decode step", jax.jit(
            M.serve_step, static_argnames=("cfg", "rules")).lower(
                abstract, state, cfg=cfg).compile())
        n = max(traffic.prompt_lengths())
        batch = {"tokens": i32(1, n), "positions": i32(1, n)}
        pre = report(f"prefill of {n}", jax.jit(
            M.forward, static_argnames=("cfg", "rules", "want_kv")).lower(
                abstract, batch, cfg=cfg, want_kv=True).compile())

        width = 128 * -(-traffic.max_sequence() // 128)
        ref = check.Reference.of(cell)
        refs = [report(label, lowered.compile()) for label, lowered in
                ref.module.lower(abstract, ref.cfg, i32(2, width))]

        pools = 2 * pool.size * 4
        serving = w_bytes + 2 * pools + max(dec.temp_size_in_bytes,
                                            pre.temp_size_in_bytes)
        # the weights, the largest temporary, and the activations a
        # program reads and writes
        checking = (w_bytes + max(m.temp_size_in_bytes for m in refs)
                    + 2 * max(m.output_size_in_bytes for m in refs))
        print(f"  serving holds ~{serving / GiB:.3f} GiB (weights "
              f"{w_bytes / GiB:.3f}, pools {pools / GiB:.3f} twice), the "
              f"reference ~{checking / GiB:.3f} GiB, of {hbm / GiB:.0f} GiB")
    return 0


if __name__ == "__main__":
    sys.exit(main())
