"""The operation and byte counts against arithmetic written out by hand."""
import pytest

from bench.harness import roofline as R
from bench.harness import spec
from bench.reference import llama

YI_CONF = spec._load_json(spec.BENCH_DIR / "configs" / "yi-6b.json")
DANUBE_CONF = spec._load_json(spec.BENCH_DIR / "configs" / "danube3-4b.json")
YI = llama.counts(YI_CONF)
DANUBE = llama.counts(DANUBE_CONF)


def test_yi_decode():
    # per layer: q 4096x32x128, k and v 4096x4x128 each, o 32x128x4096,
    # gate/up 4096x11008 each, down 11008x4096
    layer = (16_777_216 + 2 * 2_097_152 + 16_777_216 + 3 * 45_088_768)
    assert layer == 173_015_040
    head = 262_144_000
    rows, attended = 8, 8000
    flops = 2 * 8 * (32 * layer + head) + 4 * 32 * 32 * 128 * attended
    assert YI.decode_flops(rows, attended) == flops == 96_972_308_480
    weights = 2 * (32 * layer + head + 65 * 4096)
    kv = 2 * 2 * 32 * 4 * 128 * attended
    assert YI.decode_bytes(rows, attended) == weights + 2 * 8 * 4096 + kv


def test_danube_decode():
    # q 3840x32x120, k/v 3840x8x120 each, o 32x120x3840, MLP 3x3840x10240
    layer = 14_745_600 + 2 * 3_686_400 + 14_745_600 + 3 * 39_321_600
    head = 3840 * 32000
    flops = 2 * 12 * (24 * layer + head) + 4 * 24 * 32 * 120 * 12_000
    assert DANUBE.decode_flops(12, 12_000) == flops
    nbytes = (2 * (24 * layer + head + 49 * 3840) + 2 * 12 * 3840
              + 2 * 2 * 24 * 8 * 120 * 12_000)
    assert DANUBE.decode_bytes(12, 12_000) == nbytes


def test_prefill_counts_only_uncached_positions():
    # 64 new positions after 128 cached: queries at 129..192 attend
    # to 129..192 positions each
    attended = sum(range(129, 193))
    layer = YI.layer_matmul_params
    want = (2 * 64 * 32 * layer + 2 * 4096 * 64000
            + 4 * 32 * 32 * 128 * attended)
    assert YI.prefill_flops(128, 64) == want
    assert YI.prefill_flops(128, 0) == 0


#: the counts of the module-level functions they replaced, over a grid,
#: as the parent computed them: (rows, attended) -> (flops, bytes) of a
#: decode step, (cached, new) -> flops of a prefill
PINNED = {
    "yi-6b": (YI_CONF, {
        (1, 1): (11597774848, 11597856768),
        (1, 1000): (12121538560, 11663327232),
        (1, 12000): (17888706560, 12384223232),
        (8, 1): (92778528768, 11597914112),
        (8, 1000): (93302292480, 11663384576),
        (8, 12000): (99069460480, 12384280576),
        (12, 1): (139167531008, 11597946880),
        (12, 1000): (139691294720, 11663417344),
        (12, 12000): (145458462720, 12384313344),
    }, {
        (0, 1): 11597774848, (0, 1024): 11614384291840,
        (128, 64): 714579378176, (960, 256): 2981298700288, (64, 0): 0,
    }),
    "danube3-4b": (DANUBE_CONF, {
        (1, 1): (7677911040, 7678018560),
        (1, 1000): (8046182400, 7770086400),
        (1, 12000): (12101222400, 8783846400),
        (8, 1): (61420707840, 7678072320),
        (8, 1000): (61788979200, 7770140160),
        (8, 12000): (65844019200, 8783900160),
        (12, 1): (92130877440, 7678103040),
        (12, 1000): (92499148800, 7770170880),
        (12, 12000): (96554188800, 8783930880),
    }, {
        (0, 1): 7677911040, (0, 1024): 7803853209600,
        (128, 64): 479666503680, (960, 256): 2005505802240, (64, 0): 0,
    }),
}


@pytest.mark.parametrize("config", sorted(PINNED))
def test_reference_counts_equal_the_pinned_grid(config):
    conf, decode, prefill = PINNED[config]
    counts = spec.load_reference(conf).counts(conf)
    assert {k: (counts.decode_flops(*k), counts.decode_bytes(*k))
            for k in decode} == decode
    assert {k: counts.prefill_flops(*k) for k in prefill} == prefill


def test_least_time_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    nbytes = YI.decode_bytes(8, 8000)
    flops = YI.decode_flops(8, 8000)
    assert R.least_seconds(flops, nbytes, peak) == pytest.approx(
        nbytes / 819e9)
    assert nbytes / 819e9 > flops / 197e12   # decode is memory bound
