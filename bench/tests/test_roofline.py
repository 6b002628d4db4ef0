"""The operation and byte counts against arithmetic written out by hand."""
import pytest

from bench.harness import roofline as R
from bench.harness import spec

YI = R.Dims.from_config(spec._load_json(
    spec.BENCH_DIR / "configs" / "yi-6b.json"))
DANUBE = R.Dims.from_config(spec._load_json(
    spec.BENCH_DIR / "configs" / "danube3-4b.json"))


def test_yi_decode():
    # per layer: q 4096x32x128, k and v 4096x4x128 each, o 32x128x4096,
    # gate/up 4096x11008 each, down 11008x4096
    layer = (16_777_216 + 2 * 2_097_152 + 16_777_216 + 3 * 45_088_768)
    assert layer == 173_015_040
    head = 262_144_000
    rows, attended = 8, 8000
    flops = 2 * 8 * (32 * layer + head) + 4 * 32 * 32 * 128 * attended
    assert R.decode_flops(YI, rows, attended) == flops == 96_972_308_480
    weights = 2 * (32 * layer + head + 65 * 4096)
    kv = 2 * 2 * 32 * 4 * 128 * attended
    assert R.decode_bytes(YI, rows, attended) == weights + 2 * 8 * 4096 + kv


def test_danube_decode():
    # q 3840x32x120, k/v 3840x8x120 each, o 32x120x3840, MLP 3x3840x10240
    layer = 14_745_600 + 2 * 3_686_400 + 14_745_600 + 3 * 39_321_600
    head = 3840 * 32000
    flops = 2 * 12 * (24 * layer + head) + 4 * 24 * 32 * 120 * 12_000
    assert R.decode_flops(DANUBE, 12, 12_000) == flops
    nbytes = (2 * (24 * layer + head + 49 * 3840) + 2 * 12 * 3840
              + 2 * 2 * 24 * 8 * 120 * 12_000)
    assert R.decode_bytes(DANUBE, 12, 12_000) == nbytes


def test_prefill_counts_only_uncached_positions():
    # 64 new positions after 128 cached: queries at 129..192 attend
    # to 129..192 positions each
    attended = sum(range(129, 193))
    layer = YI.layer_matmul_params
    want = (2 * 64 * 32 * layer + 2 * 4096 * 64000
            + 4 * 32 * 32 * 128 * attended)
    assert R.prefill_flops(YI, 128, 64) == want
    assert R.prefill_flops(YI, 128, 0) == 0


def test_least_time_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    nbytes = R.decode_bytes(YI, 8, 8000)
    flops = R.decode_flops(YI, 8, 8000)
    assert R.least_seconds(flops, nbytes, peak) == pytest.approx(
        nbytes / 819e9)
    assert nbytes / 819e9 > flops / 197e12   # decode is memory bound
