import json

import pytest

from bench.harness import spec
from bench.harness.traffic import Traffic

CELLS = [w["name"] for w in json.load(open(spec.ROOT / "BENCHMARK.json"))
         ["workloads"]]
SEEDS = [0, 2**31 + 5, 3_000_000_019]


def _requests(cell, seed, seconds=40.0):
    c = spec.load_cell(cell)
    t = Traffic(c.traffic, seed, seconds, c.server.get("rate_per_s"),
                c.config["vocab_size"])
    sessions = list(t.prebuilt) + t.arrivals()
    if c.traffic["loop"] == "closed":
        sessions += [t.next_closed() for _ in range(200)]
    return c, t, sessions


@pytest.mark.parametrize("cell", CELLS)
def test_deterministic_per_seed(cell):
    _, t1, a = _requests(cell, SEEDS[1])
    _, t2, b = _requests(cell, SEEDS[1])
    assert a == b
    assert [t1.user_tokens(s, 0) for s in a[:5]] == \
        [t2.user_tokens(s, 0) for s in b[:5]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_seed_replays_one_schedule_with_its_own_tokens(cell):
    """The seed changes no arrival, length, turn or think time, and every
    message's tokens."""
    runs = [_requests(cell, seed) for seed in SEEDS]
    assert runs[0][2] == runs[1][2] == runs[2][2]
    for s in runs[0][2][:5]:
        toks = [t.user_tokens(s, 0) for _, t, _ in runs]
        assert toks[0] != toks[1] and toks[1] != toks[2]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_lengths_are_multiples_inside_the_warmed_set(cell, seed):
    c, t, sessions = _requests(cell, seed)
    m = c.traffic["multiple"]
    warmed = set(t.prompt_lengths())
    assert all(n % m == 0 for n in warmed)
    for s in sessions:
        context = 0
        for k in range(s.turns):
            assert s.users[k] % m == 0 and s.answers[k] % m == 0
            prompt = context + s.users[k]
            assert prompt in warmed, (s, k, prompt)
            assert prompt + s.answers[k] <= t.max_sequence()
            context = prompt + s.answers[k]


@pytest.mark.parametrize("cell", CELLS)
def test_pools_keep_the_sourced_means(cell):
    """The stratified pools keep the published means the mix names."""
    c = spec.load_cell(cell)
    t = Traffic(c.traffic, SEEDS[0], 40.0, c.server.get("rate_per_s"),
                c.config["vocab_size"])
    if c.traffic["prompt"]["median"] == 64:      # LMSYS-Chat-1M's shapes
        assert abs(t._prompt.values.mean() - 69.5) < 0.05 * 69.5
        assert abs(t._answer.values.mean() - 214.5) < 0.05 * 214.5
    else:                                         # the Azure trace's medians
        assert abs(sorted(t._answer.values)[len(t._answer.values) // 2]
                   - 129) <= 64


OPEN = [c for c in CELLS if spec.load_cell(c).traffic["loop"] == "open"]


@pytest.mark.parametrize("cell", OPEN)
def test_open_loop_window_receives_a_fixed_count(cell):
    c = spec.load_cell(cell)
    for seed in SEEDS:
        t = Traffic(c.traffic, seed, 40.0, c.server["rate_per_s"],
                    c.config["vocab_size"])
        starts = [s.start for s in t.arrivals()]
        assert len(starts) == round(c.server["rate_per_s"] * 40.0)
        assert starts == sorted(starts) and 0 < starts[0] and \
            starts[-1] < 40.0


def test_prebuilt_sessions_all_return():
    c = spec.load_cell("yi6b-chat-sessions")
    t = Traffic(c.traffic, SEEDS[0], 51.0, c.server["rate_per_s"],
                c.config["vocab_size"])
    assert len(t.prebuilt) == c.traffic["prebuild_sessions"]
    assert all(s.turns > 1 and 0 < s.resume < 51.0 for s in t.prebuilt)
