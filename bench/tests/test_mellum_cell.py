"""The MoE batch cell: its configuration, the least work of its steps, and
the expert-layer readers of the ``moe.routes`` events."""
import json
from types import SimpleNamespace

import pytest

from bench.harness import check, record, routes, spec
from bench.harness.client import Decode, Prefill
from bench.reference import mellum
from bench.run import program_config

CELL = "mellum2-moe-batch"
CONF = spec._load_json(spec.BENCH_DIR / "configs" / "mellum2-12b-a2.5b.json")
COUNTS = mellum.counts(CONF)
PEAK = spec.load_peaks()["TPU v5 lite"]


def test_the_cell_loads_with_its_reference_and_readers():
    cell = spec.load_cell(CELL)
    assert cell.reference is mellum
    assert cell.chips == 1
    names = {m.name for m in cell.metrics}
    assert names == {
        "output_tokens_per_s", "setup_s", "batch_occupancy.batch",
        "decode_step_ms.batch", "device_idle_share.batch",
        "decode_roofline.batch", "mfu.batch",
        "moe_decode_roofline.batch", "mfu_routed.batch",
        "expert_routes_per_step.batch", "expert_load_max_over_mean.batch"}


def test_the_program_builds_the_published_model_with_its_share():
    """The program's configuration, built from the file, has the sizes the
    file's published keys give, and holds 16 of the router's 64."""
    cfg = program_config(CONF)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, cfg.vocab_size) == (
        CONF["num_hidden_layers"], CONF["hidden_size"],
        CONF["num_attention_heads"], CONF["num_key_value_heads"],
        CONF["head_dim"], CONF["vocab_size"])
    mc = cfg.moe
    assert (mc.num_experts, mc.num_held, mc.first_held, mc.top_k,
            mc.d_ff_expert) == (CONF["published"]["num_experts"],
                                CONF["num_experts"], 0,
                                CONF["num_experts_per_tok"],
                                CONF["moe_intermediate_size"])
    assert mc.num_shared_experts == 0
    assert [cfg.layer_type(i) for i in range(cfg.num_layers)] \
        == CONF["layer_types"]
    assert cfg.sliding_window == CONF["sliding_window"]
    assert cfg.norm_eps == CONF["rms_norm_eps"]
    assert not (cfg.tie_embeddings or cfg.attn_bias or cfg.qk_norm)
    rope = CONF["rope_parameters"]
    ma = cfg.mixed_attention
    assert cfg.rope_theta == rope["sliding_attention"]["rope_theta"]
    full = rope["full_attention"]
    assert (ma.full_rope_theta, ma.yarn_factor,
            ma.yarn_original_max_position_embeddings, ma.yarn_beta_fast,
            ma.yarn_beta_slow, ma.yarn_attention_factor) == (
        full["rope_theta"], full["factor"],
        full["original_max_position_embeddings"], full["beta_fast"],
        full["beta_slow"], full["attention_factor"])
    assert CONF["reduced"] == ["num_experts"]
    hash(cfg)


def test_counts_meet_the_contract_by_hand():
    """One decode step of 32 rows over 32,768 positions, by hand: per layer
    q 2304x32x128, k and v 2304x4x128, o 32x128x2304, router 2304x64;
    21 windowed layers attend to at most 1,024 + 31 positions (one row
    all but one position each of the others), 7 full ones to all."""
    assert COUNTS.least_routes(32) == 0 and COUNTS.least_touched(32) == 0
    layer = 9_437_184 + 2 * 1_179_648 + 9_437_184 + 147_456
    assert COUNTS.layer_params == layer
    head = 2304 * 98304
    attn = 4 * 32 * 128 * (7 * 32_768 + 21 * (1024 + 31))
    assert COUNTS.decode_flops(32, 32_768) == (
        2 * 32 * (28 * layer + head) + attn)
    kv = 2 * 2 * 4 * 128 * (7 * 32_768 + 21 * 1055)
    weights = 2 * (28 * layer + head + 57 * 2304)
    assert COUNTS.decode_bytes(32, 32_768) == weights + 2 * 32 * 2304 + kv
    assert COUNTS.route_flops == 6 * 2304 * 896
    assert COUNTS.expert_bytes == 2 * 3 * 2304 * 896
    # a prefill of 4 after 1,022 cached: positions 1,023..1,026
    windowed = 1023 + 1024 * 3
    assert COUNTS.prefill_flops(1022, 4) == (
        2 * 4 * 28 * layer + 2 * head
        + 4 * 32 * 128 * (7 * sum(range(1023, 1027)) + 21 * windowed))


@pytest.mark.parametrize("rows,attended", [(1, 1), (8, 9000), (32, 40_000)])
def test_counts_never_exceed_the_routed_least(rows, attended):
    """Whatever the routes, a step does at least what ``counts`` says:
    pigeonhole gives no route to a held expert where the 48 absent ones
    can take all 8 of every token's."""
    c = COUNTS
    for n in (0, 1, rows * c.top_k * c.layers):
        assert c.decode_flops(rows, attended) <= c.routed_decode_flops(
            rows, attended, n)
    for touched in (0, 1, c.layers * c.held):
        assert c.decode_bytes(rows, attended) <= c.routed_decode_bytes(
            rows, attended, touched)
    assert c.prefill_flops(0, rows) <= c.routed_prefill_flops(0, rows, 1)
    whole = mellum.MoECounts(**{**c.__dict__, "held": c.experts})
    assert whole.least_routes(rows) == 28 * rows * 8
    assert whole.least_touched(rows) == 28 * 8


def _event(t0, program, routes_held, max_routes, touched):
    return SimpleNamespace(
        name="moe.routes", t0=t0, t1=t0 + 1e-4,
        args=dict(program=program, routes_held=routes_held,
                  max_routes=max_routes, experts_touched=touched, layers=2,
                  held=4))


#: a recorded traced span: two prefills and three decode steps of a model
#: of two layers holding four experts each; the first decode step's event
#: fell before the span
EVENTS = [
    _event(10.10, "prefill", [60, 68], [20, 30], 8),
    _event(10.20, "decode", [8, 4], [4, 2], 5),
    _event(10.30, "decode", [4, 8], [1, 5], 6),
    _event(10.40, "prefill", [30, 34], [10, 12], 7),
]


def _run(events, trace=object()):
    run = record.Run(
        seconds=51.0, t0=0.0, t_end=51.0, setup_s=1.0, max_batch=4,
        counts=COUNTS, peak=PEAK, turns=[],
        prefills=[Prefill(10.11, 30, 0, 32), Prefill(10.41, 16, 0, 16)],
        decodes=[Decode(10.09, 3, 300), Decode(10.21, 3, 300),
                 Decode(10.31, 4, 400)],
        steps=[], hook_s=0.0, hook_blocks=0, trace=trace,
        traced=(10.0, 11.0))
    run._events = events
    return run


@pytest.fixture
def recorded(monkeypatch):
    from bench.harness import spans
    monkeypatch.setattr(spans, "recorded", lambda run: run._events)


def test_readers_read_the_recorded_events(recorded, monkeypatch):
    from bench.harness import trace
    run = _run(EVENTS)
    assert routes.routes_per_expert(run) == pytest.approx(
        (12 / 8 + 12 / 8) / 2)
    assert routes.load_max_over_mean(run) == pytest.approx(
        (4 * 4 / 8 + 2 * 4 / 4 + 1 * 4 / 4 + 5 * 4 / 8) / 4)
    c = COUNTS
    flops = (c.routed_prefill_flops(0, 30, 128)
             + c.routed_prefill_flops(0, 16, 64)
             + c.routed_decode_flops(3, 300, 12)
             + c.routed_decode_flops(4, 400, 12))
    assert routes.mfu(run) == pytest.approx(
        100 * flops / PEAK["bf16_flops_per_s"])
    monkeypatch.setattr(trace, "module_calls", lambda tr, name: [0.02, 0.03])
    least = [max(c.routed_decode_flops(r, a, 12) / PEAK["bf16_flops_per_s"],
                 c.routed_decode_bytes(r, a, t) / PEAK["hbm_bytes_per_s"])
             for r, a, t in ((3, 300, 5), (4, 400, 6))]
    assert routes.decode_roofline(run) == pytest.approx(
        100 * (sum(least) / 2) / 0.025)


def test_a_step_whose_event_fell_outside_the_span_is_not_paired():
    decodes = [Decode(10.09, 3, 300), Decode(10.21, 3, 300)]
    pairs = routes.paired(decodes, EVENTS[1:2])
    assert [(d.t, e.t0) for d, e in pairs] == [(10.21, 10.20)]


@pytest.mark.parametrize("events", [None, []])
def test_readers_return_none_without_events(recorded, events):
    run = _run(events)
    for read in (routes.routes_per_expert, routes.load_max_over_mean,
                 routes.mfu, routes.decode_roofline):
        assert read(run) is None


def test_readers_return_none_for_an_untraced_run():
    run = _run(EVENTS, trace=None)
    run.traced = None
    for read in (routes.routes_per_expert, routes.load_max_over_mean,
                 routes.mfu, routes.decode_roofline):
        assert read(run) is None


def test_the_file_is_the_catalog_config_but_for_what_it_lists():
    """Every key of the published config is in the file with its value,
    but the experts held here, which ``reduced`` lists."""
    published = {
        "num_hidden_layers": 28, "hidden_size": 2304, "head_dim": 128,
        "num_attention_heads": 32, "num_key_value_heads": 4,
        "moe_intermediate_size": 896, "num_experts_per_tok": 8,
        "vocab_size": 98304, "sliding_window": 1024, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 131072, "intermediate_size": 7168,
        "norm_topk_prob": True, "tie_word_embeddings": False}
    for k, v in published.items():
        assert CONF[k] == v, k
    # the sizes under the harness's names, as the reference reads them
    assert (CONF["num_layers"], CONF["d_model"], CONF["num_heads"],
            CONF["num_kv_heads"]) == (28, 2304, 32, 4)
    assert CONF["num_experts"] == 16 and CONF["published"] == {
        "num_experts": 64}
    bm = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bm["configs"] if c["name"] == CONF["name"])
    assert entry["reduced"] == CONF["reduced"] and entry["source"] == \
        CONF["source"]


#: the cell at a size a CPU test holds: two periods of layers of width
#: 1024 (the head size, a quarter of the heads), a window of 64 shorter
#: than the prompts, and the cell's routing (top 8 of a 64-expert router,
#: experts of 896) with 2 experts held.  A router of 8 experts with top 2
#: gives each route about half a token's output where Mellum's gives an
#: eighth, so a route that bf16 flips moves a sound run's logits further
#: than the cell's would (PERF.md §4)
TINY = dict(num_layers=8, d_model=1024, num_heads=8, num_kv_heads=2,
            head_dim=128, moe_intermediate_size=896, num_experts=2,
            num_experts_per_tok=8, vocab_size=2048, sliding_window=64,
            published={"num_experts": 64},
            moe={"held": 2, "first_held": 0, "num_experts": 64, "top_k": 8,
                 "d_ff_expert": 896})


#: seconds a tiny run serves: enough for the cut to finish requests on a
#: CPU that other test workers share
WINDOW_S = 20.0


def tiny_cell():
    cell = spec.load_cell(CELL)
    cell.config = dict(cell.config, **TINY)
    cell.traffic = dict(
        cell.traffic, answer=dict(median=64, sigma=0.0, min=64, max=64),
        prompt=dict(median=128, sigma=0.45, min=64, max=256))
    flags = list(cell.server["flags"])
    for flag, value in (("--max-batch", "4"), ("--local-slots", "96"),
                        ("--peer-budget-mb", "0.25")):
        flags[flags.index(flag) + 1] = value
    cell.server = dict(cell.server, flags=flags)
    return cell


def test_a_tiny_run_serves_the_reference_s_tokens():
    from bench.run import run_cell
    r = run_cell(tiny_cell(), 2**31 + 71, WINDOW_S, False,
                 require_chip=False)
    assert r["correct"], r["checks"]
    assert r["metrics"]["output_tokens_per_s"]["value"] > 0


def _control(weights, sample, ref, _run):
    top = check.control_tokens(weights, sample, ref)
    return check.widest_gap(weights, sample, ref, probe=top)


@pytest.mark.parametrize("seed", [2**31 + 41, 2**31 + 43, 2**31 + 47])
def test_the_control_fails_the_cell_s_limit(seed):
    """``test_control.py``'s property at this file's cut: the reference
    computed in float8 e4m3 in the program's place comes out not correct
    under the cell's ``logit_gap`` limit, while the program stays inside
    it."""
    from bench.run import run_cell
    r = run_cell(tiny_cell(), seed, WINDOW_S, False, require_chip=False,
                 after=_control)
    limit = r["checks"]["logit_gap"]["limit"]
    assert r["correct"], r["checks"]
    assert r["calibration"] > limit, (r["calibration"], limit)

