"""A configuration names its plain reference module, and the harness takes
the reference, its work counts and its control from that module alone; a
configuration's sub-configs are set from its file."""
import json

import pytest

from bench.harness import check, spec
from bench.run import program_config, run_cell
from bench.tests.tiny import tiny_cell

SEED = 2**31 + 53
CELL = "yi6b-chat-short"
#: the files of the harness, which may name no reference module
HARNESS = [*sorted((spec.BENCH_DIR / "harness").glob("*.py")),
           *(spec.BENCH_DIR / f for f in ("run.py", "calibrate.py",
                                          "aot_check.py"))]


def _benchmark(tmp_path, **changes):
    """``BENCHMARK.json`` with the configuration of :data:`CELL` replaced by
    a copy with ``changes``; a change to ``None`` drops the key."""
    bm = spec._load_json(spec.ROOT / "BENCHMARK.json")
    config = next(w["config"] for w in bm["workloads"] if w["name"] == CELL)
    entry = next(c for c in bm["configs"] if c["name"] == config)
    conf = spec._load_json(spec.ROOT / entry["file"])
    for k, v in changes.items():
        if v is None:
            conf.pop(k, None)
        else:
            conf[k] = v
    path = tmp_path / "config.json"
    path.write_text(json.dumps(conf))
    entry["file"] = str(path)
    return bm


def test_the_cell_runs_on_the_reference_its_configuration_names(
        monkeypatch, tmp_path):
    from bench.tests import counted
    monkeypatch.setattr(spec, "REFERENCE_PACKAGE", "bench.tests")
    counted.calls.clear()
    cell = tiny_cell(CELL, _benchmark(tmp_path, reference="counted"))
    assert cell.reference is counted

    def control(weights, sample, ref, _run):
        top = check.control_tokens(weights, sample, ref)
        return check.widest_gap(weights, sample, ref, probe=top)

    r = run_cell(cell, SEED, 15.0, False, require_chip=False, after=control)
    assert r["correct"], r["checks"]
    assert r["calibration"] > r["checks"]["logit_gap"]["limit"]
    # the run's comparison, and the control's own tokens and its gaps
    assert counted.calls["gaps", "fp8"] > 0
    assert counted.calls["gaps", "f32"] > counted.calls["gaps", "fp8"]
    assert counted.calls["counts"] == 1


@pytest.mark.parametrize("reference,says", [
    (None, "names no reference module"),
    ("", "names no reference module"),
    ("../reference/llama", "names no reference module"),
    ("no_such_reference", "no module bench.reference.no_such_reference"),
])
def test_a_configuration_without_its_reference_stops_at_load(
        tmp_path, reference, says):
    bm = _benchmark(tmp_path, reference=reference)
    with pytest.raises(ValueError, match=says):
        spec.load_cell(CELL, bm)


def test_a_reference_that_lacks_the_contract_stops_at_load(monkeypatch,
                                                            tmp_path):
    monkeypatch.setattr(spec, "REFERENCE_PACKAGE", "bench.tests")
    with pytest.raises(ValueError, match="lacks frozen, gaps, counts, lower"):
        spec.load_cell(CELL, _benchmark(tmp_path, reference="tiny"))


def test_no_harness_file_names_a_reference_module():
    for path in HARNESS:
        text = path.read_text()
        for name in ("reference.llama", "reference import llama",
                     "tests.counted", "tests import counted", '"counted"',
                     "'counted'", '"llama"', "'llama'"):
            assert name not in text, (path, name)


def _conf(arch, **sizes):
    return {"name": arch, "arch": arch, **sizes}


def test_sub_config_is_applied_over_the_architectures_own():
    from repro.configs import MoEConfig
    cfg = program_config(_conf("phi-tiny-moe", head_dim=64,
                               moe={"num_experts": 16, "top_k": 4}))
    assert cfg.moe == MoEConfig(num_experts=16, top_k=4, d_ff_expert=915,
                                layer_period=1)
    assert (cfg.d_model, cfg.num_layers) == (2304, 32)
    hash(cfg)


def test_sub_config_is_built_where_the_architecture_has_none():
    from repro.configs import ModalityConfig, MoEConfig
    cfg = program_config(_conf(
        "yi-6b", head_dim=128,
        moe={"num_experts": 16, "top_k": 2, "d_ff_expert": 896},
        modality={"kind": "vision", "mrope_sections": [16, 24, 24]}))
    assert cfg.moe == MoEConfig(num_experts=16, top_k=2, d_ff_expert=896)
    assert cfg.modality == ModalityConfig(kind="vision",
                                          mrope_sections=(16, 24, 24))
    hash(cfg)


def test_an_unknown_sub_config_key_stops():
    with pytest.raises(ValueError, match="moe has no field experts_held"):
        program_config(_conf("phi-tiny-moe", head_dim=64,
                             moe={"num_experts": 16, "experts_held": 4}))


def test_top_level_keys_still_override():
    """Danube's published rope theta and no window replace the program's."""
    from repro.configs import get_config
    conf = spec._load_json(spec.BENCH_DIR / "configs" / "danube3-4b.json")
    base = get_config(conf["arch"])
    assert (base.rope_theta, base.sliding_window) == (10_000.0, 4096)
    cfg = program_config(conf)
    assert (cfg.rope_theta, cfg.sliding_window) == (100_000.0, None)
    assert (cfg.num_kv_heads, cfg.resolved_head_dim) == (8, 120)
