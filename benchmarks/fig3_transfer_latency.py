"""Paper Fig 3 — GPU<->GPU vs GPU<->CPU transfer latency of memory chunks.

The paper sweeps chunk sizes on its 2xH100 NVLink testbed and annotates the
expert sizes of four MoE models; the peer/host speedup is "consistently
high, ranging from 7.5x for the very small Tiny Phi model to 9.5x for the
much bigger Mixtral 8x7B".  We run the same sweep through the calibrated
H100 hardware model (repro.core.tiers.H100_NVLINK) and check the per-model
speedups land in the paper's band.
"""
from __future__ import annotations

from pathlib import Path

from benchmarks.common import Check, fmt_table, save_result
from repro.configs import TABLE1_ARCHS, get_config
from repro.core.tiers import H100_NVLINK, expert_bytes


def run(out_dir: Path) -> dict:
    hw = H100_NVLINK

    # generic chunk sweep (the x-axis of Fig 3)
    sweep = []
    for mib in (1, 4, 16, 64, 128, 256, 512):
        nbytes = mib * 2**20
        th = hw.host_link.transfer_time(nbytes)
        tp = hw.peer_link.transfer_time(nbytes)
        sweep.append({"chunk_mib": mib, "host_ms": th * 1e3,
                      "peer_ms": tp * 1e3, "speedup": th / tp})

    # expert-size markers for the paper's four MoE models
    models = []
    for arch in TABLE1_ARCHS:
        cfg = get_config(arch)
        eb = expert_bytes(cfg)
        th = hw.host_link.transfer_time(eb)
        tp = hw.peer_link.transfer_time(eb)
        models.append({"model": arch, "expert_mib": eb / 2**20,
                       "host_ms": th * 1e3, "peer_ms": tp * 1e3,
                       "speedup": th / tp})

    by = {m["model"]: m for m in models}
    speedups = [m["speedup"] for m in models]
    checks = [
        Check("fig3.tiny_phi_speedup", by["phi-tiny-moe"]["speedup"],
              lo=7.2, hi=7.9, note="paper: 7.5x for Tiny Phi"),
        Check("fig3.mixtral_speedup", by["mixtral-8x7b"]["speedup"],
              lo=9.2, hi=9.8, note="paper: 9.5x for Mixtral-8x7B"),
        Check("fig3.min_speedup", min(speedups), lo=7.2,
              note="paper: consistently high, >=7.5x"),
        Check("fig3.max_speedup", max(speedups), hi=9.8,
              note="paper band tops out at 9.5x"),
    ]

    print("Fig 3 — transfer latency, peer (NVLink) vs host (PCIe):")
    print(fmt_table(
        ["chunk", "host ms", "peer ms", "speedup"],
        [[f"{s['chunk_mib']} MiB", f"{s['host_ms']:.3f}",
          f"{s['peer_ms']:.3f}", f"{s['speedup']:.2f}x"] for s in sweep]))
    print()
    print(fmt_table(
        ["model (expert size)", "host ms", "peer ms", "speedup"],
        [[f"{m['model']} ({m['expert_mib']:.0f} MiB)", f"{m['host_ms']:.3f}",
          f"{m['peer_ms']:.3f}", f"{m['speedup']:.2f}x"] for m in models]))

    payload = {"name": "fig3_transfer_latency", "sweep": sweep,
               "models": models, "checks": [c.to_dict() for c in checks]}
    save_result(out_dir, "fig3_transfer_latency", payload)
    return payload


if __name__ == "__main__":
    from benchmarks.common import RESULTS_DIR
    run(RESULTS_DIR)
