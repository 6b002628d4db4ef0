"""The readers of the program's spans: their arithmetic on hand-made
spans, a whole tiny run on the CPU whose spans must count what the
harness's own wrappers count, and the naming of idle gaps by span."""
import contextlib
import dataclasses

import pytest

from bench.harness import spans as S
from bench.harness import trace as T
from bench.harness.client import Turn
from repro.core.tracing import Span


def _span(i, parent, name, t0, t1, **args):
    return Span(i, parent, name, t0, t1, args)


def _hand_made():
    # two steps: the first prefills (two pool writes and a device wait)
    # and evicts twice (one eviction's two waits overlap), the second
    # only samples
    return [
        _span(1, None, "engine.step", 0.0, 10.0),
        _span(2, 1, "engine.admit", 0.5, 6.0),
        _span(3, 2, "engine.prefill", 1.0, 5.0, req=7),
        _span(4, 3, "device.wait", 4.0, 4.5),
        _span(5, 3, "kv.evict", 2.0, 3.5, req=3, block=0, slot=9),
        _span(6, 5, "device.wait", 2.1, 2.6),
        _span(7, 5, "device.wait", 2.4, 2.9),
        _span(8, 1, "kv.evict", 7.0, 8.0, req=3, block=1, slot=8),
        _span(9, 8, "device.wait", 7.2, 7.4),
        _span(10, None, "engine.step", 20.0, 24.0),
        _span(11, 10, "engine.sample", 21.0, 23.0, rows=2),
        _span(12, 11, "device.wait", 21.0, 22.0),
        _span(13, 3, "kv.write", 1.2, 1.8),
        _span(14, 3, "kv.write", 4.6, 4.9),
    ]


@contextlib.contextmanager
def _recorder_holding(spans):
    """The program's recorder holding ``spans`` alone, for a reader."""
    from repro.core import tracing
    saved = tracing.spans()
    tracing.clear()
    tracing._recorded.extend(spans)
    try:
        yield
    finally:
        tracing.clear()
        tracing._recorded.extend(saved)


def test_step_host_time_leaves_out_the_union_of_waits():
    # step 1: 10 - (0.5 + 0.8 + 0.2 waits, 0.6 + 0.3 writes) = 7.6;
    # step 2: 4 - 1 = 3
    assert S.step_host_ms(_hand_made()) == pytest.approx(1e3 * 10.6 / 2)


def test_kv_write_per_block():
    assert S.kv_write_ms_per_block(_hand_made()) == pytest.approx(
        1e3 * 0.9 / 2)


def test_prefill_share_of_step_time():
    assert S.prefill_share(_hand_made()) == pytest.approx(100 * 4.0 / 14.0)


def test_evict_wait_per_block():
    # waits inside the two evictions: 0.8 (overlap counted once) + 0.2
    assert S.evict_wait_ms_per_block(_hand_made()) == pytest.approx(
        1e3 * 1.0 / 2)


def test_readers_read_nothing_without_their_spans():
    only_steps = [s for s in _hand_made() if s.name == "engine.step"]
    assert S.evict_wait_ms_per_block(only_steps) is None
    assert S.kv_write_ms_per_block(only_steps) is None
    assert S.step_host_ms([]) is None
    assert S.prefill_share([]) is None
    assert S.prefill_share(only_steps) == 0.0
    untraced = dataclasses.make_dataclass("R", ["traced"])(None)
    assert S.recorded(untraced) is None
    assert S.read(untraced, S.step_host_ms) is None
    assert S.queue_wait_ms(_queued([])) is None
    # a program without the stamps reads nothing, not a wait so far
    unstamped = Turn(None, 0, [], 1, due=1.0, sent=1.0, handle=object())
    assert S.queue_wait_ms(_queued([unstamped])) is None


class _Handle:
    def __init__(self, queued_s):
        self.queued_s = queued_s


def _queued(turns, traced=None):
    """A run of ``turns``, its window 0..50 s."""
    run = dataclasses.make_dataclass("R", ["t0", "t_end", "due", "traced"])
    return run(0.0, 50.0, turns, traced)


def test_queue_wait_is_a_percentile_over_the_window():
    turns = [Turn(None, 0, [], 1, due=float(i), sent=float(i) + 0.01,
                  handle=_Handle(0.001 * i)) for i in range(1, 10)]
    # sent at 49.7 s, not admitted by 50: counts 0.3 s so far
    turns.append(Turn(None, 0, [], 1, due=49.6, sent=49.7,
                      handle=_Handle(None)))
    # not sent by the window's end: a client lag, not a queue wait
    turns.append(Turn(None, 0, [], 1, due=49.9, handle=_Handle(None)))
    run = _queued(turns)
    # waits 1..9 ms and 300 ms: p90 lies 0.1 of the way from 9 to 300
    assert S.queue_wait_ms(run) == pytest.approx(9.0 + 0.1 * 291.0)
    assert S.queue_wait_ms(run, 50) == pytest.approx(5.5)
    assert S.queue_wait_ms(run, 100) == pytest.approx(300.0)
    # traced 20..30 s: read up to 30 s; the wait of one sent at 8.01 s
    # and admitted at 31 s is cut at 30 s
    turns[7].handle.queued_s = 23.0
    traced = _queued(turns, traced=(20.0, 30.0))
    assert S.queue_wait_ms(traced, 100) == pytest.approx(1e3 * 21.99)
    assert S.queue_wait_ms(traced, 50) == pytest.approx(5.0)


def test_idle_by_span_names_gaps_by_the_innermost_span():
    # the trace's clock runs 100 s ahead of the recorder's
    tr = T.Trace(window=(100.0, 130.0),
                 ops={"/device:TPU:0": [("fusion.1", 100.0, 101.5),
                                        ("copy.2", 104.6, 110.0),
                                        ("fusion.3", 122.0, 129.0)]})
    run = dataclasses.make_dataclass("R", ["trace", "traced"])(
        tr, (0.0, 30.0))
    with _recorder_holding(_hand_made()):
        gaps = S.idle_by_span(run)
    # 1.5..4.6 (middle 3.05: the eviction), 10..22 (middle 16: between
    # the steps), 129..130 (middle 29.5: no span)
    assert gaps == [(S.OUTSIDE, pytest.approx(12.0)),
                    ("kv.evict", pytest.approx(3.1)),
                    (S.OUTSIDE, pytest.approx(1.0))]


@pytest.fixture(scope="module")
def traced_tiny(tmp_path_factory):
    """A tiny sessions cell on the CPU with the whole run under a profiler
    session: the engine's spans beside the harness's own records."""
    import jax
    from bench.tests.tiny import run_tiny
    from repro.core import tracing
    runs = []
    tracing.clear()
    with jax.profiler.trace(str(tmp_path_factory.mktemp("profile"))):
        run_tiny("yi6b-chat-sessions", 2**31 + 41,
                 after=lambda *a: runs.append(a[-1]))
    recorded = tracing.spans()
    tracing.clear()
    run = runs[0]
    return run, [s for s in recorded if s.t0 >= run.t0]


def test_span_args_equal_the_harness_records(traced_tiny):
    run, window = traced_tiny
    by_req = {t.handle.req_id: t for t in run.turns}
    prefills = [s for s in window if s.name == "engine.prefill"]
    assert prefills
    for s in prefills:
        assert s.args["tokens"] == len(by_req[s.args["req"]].prompt)
    assert [(s.args["tokens"], s.args["cached"], s.args["padded"])
            for s in prefills] == [(p.tokens, p.cached, p.padded)
                                   for p in run.prefills if p.t >= run.t0]
    decodes = [s for s in window if s.name == "engine.decode"]
    assert decodes
    assert [(s.args["rows"], s.args["attended"]) for s in decodes] == [
        (d.rows, d.attended) for d in run.decodes if d.t >= run.t0]
    moves = [s for s in window if s.name in ("kv.evict", "kv.reload")]
    assert any(s.name == "kv.evict" for s in moves)
    assert len(moves) == run.hook_blocks


def test_readers_read_a_traced_run(traced_tiny):
    from bench.harness.spec import load_reader
    run, window = traced_tiny
    run = dataclasses.replace(run, traced=(run.t0, run.t_end))
    with _recorder_holding(window):
        got = {n: load_reader(n)(run) for n in (
            "queue_wait_ms.chat", "step_host_ms.chat", "step_host_ms.batch",
            "prefill_share.chat", "prefill_share.batch",
            "evict_wait_ms_per_block", "kv_write_ms_per_block.chat",
            "kv_write_ms_per_block.batch")}
    assert got["queue_wait_ms.chat"] >= 0.0
    assert got["step_host_ms.chat"] == got["step_host_ms.batch"] > 0.0
    assert got["kv_write_ms_per_block.chat"] == got[
        "kv_write_ms_per_block.batch"] > 0.0
    assert 0.0 < got["prefill_share.chat"] == got["prefill_share.batch"] < 100
    assert got["evict_wait_ms_per_block"] > 0.0
