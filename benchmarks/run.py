"""Benchmark harness entrypoint: ``PYTHONPATH=src python -m benchmarks.run``.

Runs one benchmark per paper artifact (Fig 2/3/5/6/7, Table 1) plus the
roofline report derived from the multi-pod dry-run, validates every claim
band, writes per-benchmark JSON to ``results/`` and prints the summary.

Flags:
  --only fig5,fig7     run a subset
  --fast               fewer simulator trials/steps (CI mode)
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import RESULTS_DIR, Check, summarize_checks

BENCHES = ["fig2", "fig3", "table1", "fig5", "fig6", "fig7", "fig8",
           "fig9", "fig10", "fig11", "fig12", "fig13", "fig14", "fig15",
           "roofline"]


def _call(name: str, fast: bool, hw: str):
    if name == "fig2":
        from benchmarks import fig2_cluster_cdf as m
        return m.run(RESULTS_DIR)
    if name == "fig3":
        from benchmarks import fig3_transfer_latency as m
        return m.run(RESULTS_DIR)
    if name == "table1":
        from benchmarks import table1_model_zoo as m
        return m.run(RESULTS_DIR)
    if name == "fig5":
        from benchmarks import fig5_moe_throughput as m
        return m.run(RESULTS_DIR, trials=2 if fast else 5,
                     decode_steps=8 if fast else 32, timeline=not fast)
    if name == "fig6":
        from benchmarks import fig6_offload_sweep as m
        return m.run(RESULTS_DIR, decode_steps=4 if fast else 8)
    if name == "fig7":
        from benchmarks import fig7_kv_latency as m
        return m.run(RESULTS_DIR)
    if name == "fig8":
        from benchmarks import fig8_peer_scaling as m
        return m.run(RESULTS_DIR, hw=hw, fast=fast)
    if name == "fig9":
        from benchmarks import fig9_coalescing as m
        return m.run(RESULTS_DIR, fast=fast)
    if name == "fig10":
        from benchmarks import fig10_slo_serving as m
        return m.run(RESULTS_DIR, hw=hw, fast=fast)
    if name == "fig11":
        from benchmarks import fig11_prefix_sharing as m
        return m.run(RESULTS_DIR, hw=hw, fast=fast)
    if name == "fig12":
        from benchmarks import fig12_continuous_batching as m
        return m.run(RESULTS_DIR, hw=hw, fast=fast)
    if name == "fig13":
        from benchmarks import fig13_fidelity_tiers as m
        return m.run(RESULTS_DIR, hw=hw, fast=fast)
    if name == "fig14":
        from benchmarks import fig14_scaleout as m
        return m.run(RESULTS_DIR, hw=hw, fast=fast)
    if name == "fig15":
        from benchmarks import fig15_stability as m
        return m.run(RESULTS_DIR, hw=hw, fast=fast)
    if name == "roofline":
        from benchmarks import roofline as m
        return m.run(RESULTS_DIR)
    raise ValueError(f"unknown benchmark {name!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--hw", default="h100-nvlink-2gpu",
                    choices=["h100-nvlink-2gpu", "tpu-v5e"],
                    help="hardware family for the per-family benchmarks "
                         "(fig8 topology sweep, fig10 SLO serving, fig11 "
                         "prefix sharing, fig12 continuous batching, fig13 "
                         "fidelity tiers, fig14 scale-out, fig15 stability "
                         "control): NVLink mesh vs TPU v5e ICI torus")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    names = args.only.split(",") if args.only else BENCHES
    all_checks, failed = [], []
    for name in names:
        print("=" * 78)
        print(f"== {name}")
        print("=" * 78)
        t0 = time.time()
        payload = _call(name, args.fast, args.hw)
        checks = [Check(**{k: v for k, v in c.items() if k != "ok"})
                  for c in payload.get("checks", [])]
        all_checks += checks
        bad = [c for c in checks if not c.ok]
        failed += bad
        print(f"\n-- {name}: {len(checks) - len(bad)}/{len(checks)} checks "
              f"pass ({time.time() - t0:.1f}s)")
        print(summarize_checks(checks))
        print()

    print("=" * 78)
    n_ok = len(all_checks) - len(failed)
    print(f"TOTAL: {n_ok}/{len(all_checks)} claim checks pass")
    if failed:
        print("FAILED:")
        for c in failed:
            print(f"  {c.name} = {c.value:.4g} not in [{c.lo}, {c.hi}]")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
