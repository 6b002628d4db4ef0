"""Paper Fig 5 — decode throughput gain from peer-GPU expert offload.

Setup mirrors the paper (§4.4/§4.5): MoE-Lightning test bench semantics with
micro-batch 324 x 14 micro-batches (N = 4,536 tokens), 32 decode steps, 50%
of experts offloaded, averaged over 5 trials.  Peer offload (Harvest over
NVLink) vs CPU offload (CGOPipe over PCIe).

Claims validated:
  * throughput gains range +48% .. >110% across the four models;
  * Phi-3.5-MoE's gain is ~2x Qwen2-MoE's (fewer experts + smaller fan-out
    -> higher temporal locality);
  * gains come from serving expert misses from peer HBM only (routing,
    batching, attention untouched — the simulator shares every other code
    path between the two configurations).
"""
from __future__ import annotations

from pathlib import Path

from benchmarks.common import Check, fmt_table, save_result
from repro.configs import TABLE1_ARCHS, get_config
from repro.core.runtime import HarvestRuntime
from repro.core.simulator import AccessModelConfig, simulate_moe_decode
from repro.core.tiers import H100_NVLINK

# The paper runs 5 trials x 32 generated tokens; the per-step gains are
# stationary, so the default harness uses 2x8 (the CPU-python pipeline sim
# is O(steps x layers x microbatches)); pass trials/decode_steps for the
# paper-exact setting.
TRIALS = 2
DECODE_STEPS = 8


def run(out_dir: Path, trials: int = TRIALS,
        decode_steps: int = DECODE_STEPS, timeline: bool = False) -> dict:
    """``timeline=True`` re-runs the peer configuration on the
    TransferEngine's event-driven clock (one trial, same seeds) and records
    the resulting tokens/s next to the analytic number — the claim checks
    always validate the analytic (golden) path."""
    hw = H100_NVLINK
    # one runtime for the whole figure: its TransferEngine accounts every
    # simulated peer fetch into the unified metrics snapshot saved below
    runtime = HarvestRuntime(hardware=hw)
    rows, out_rows = [], []
    gains = {}
    for arch in TABLE1_ARCHS:
        cfg = get_config(arch)
        peer_tps, host_tps = [], []
        for t in range(trials):
            am = AccessModelConfig(seed=t)
            p = simulate_moe_decode(cfg, hw, 0.5, use_peer=True,
                                    decode_steps=decode_steps, access=am,
                                    runtime=runtime)
            h = simulate_moe_decode(cfg, hw, 0.5, use_peer=False,
                                    decode_steps=decode_steps, access=am,
                                    runtime=runtime)
            peer_tps.append(p.tokens_per_s)
            host_tps.append(h.tokens_per_s)
        peer = sum(peer_tps) / trials
        host = sum(host_tps) / trials
        gain = peer / host - 1
        gains[arch] = gain
        row = {"model": arch, "host_tps": host, "peer_tps": peer,
               "gain": gain,
               "distinct_experts_per_ub": p.distinct_experts_per_ub}
        if timeline:
            # a separate runtime so the analytic metrics snapshot saved
            # below stays pure (one configuration, not a merged sum)
            tl = simulate_moe_decode(
                cfg, hw, 0.5, use_peer=True, decode_steps=decode_steps,
                access=AccessModelConfig(seed=0),
                runtime=HarvestRuntime(hardware=hw), use_timeline=True)
            row["peer_tps_timeline"] = tl.tokens_per_s
        rows.append([arch, f"{host:.0f}", f"{peer:.0f}", f"+{gain*100:.0f}%"])
        out_rows.append(row)

    checks = [
        Check("fig5.min_gain_pct", min(gains.values()) * 100, lo=40, hi=60,
              note="paper: gains start at +48%"),
        Check("fig5.max_gain_pct", max(gains.values()) * 100, lo=105,
              note="paper: gains exceed +110%"),
        Check("fig5.phi35_vs_qwen2_ratio",
              gains["phi-3.5-moe"] / gains["qwen2-moe"], lo=1.6, hi=2.6,
              note="paper: Phi-3.5-MoE ~2x the speedup of Qwen2-MoE"),
        Check("fig5.all_positive", min(gains.values()), lo=0.0,
              note="peer offload never loses to CPU offload"),
    ]

    print("Fig 5 — decode throughput at 50% experts offloaded "
          f"({trials} trials x {decode_steps} steps):")
    print(fmt_table(["model", "CPU offload tok/s", "Harvest tok/s", "gain"],
                    rows))

    snap = runtime.stats()
    payload = {"name": "fig5_moe_throughput", "rows": out_rows,
               "metrics": snap,
               "transfer_metrics": snap.get("transfer", {}),  # back-compat
               "checks": [c.to_dict() for c in checks]}
    save_result(out_dir, "fig5_moe_throughput", payload)
    return payload


if __name__ == "__main__":
    from benchmarks.common import RESULTS_DIR
    run(RESULTS_DIR)
