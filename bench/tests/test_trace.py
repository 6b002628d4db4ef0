"""The trace reduction: interval arithmetic on a hand-made trace, and the
whole reading of a small trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

from bench.harness import trace as T

DATA = Path(__file__).resolve().parent / "data"


def _hand_made():
    # window 0..10 s; ops overlap at 1..3 and 2..4, then 6..7
    return T.Trace(
        window=(0.0, 10.0),
        ops={"/device:TPU:0": [("fusion.1", 1.0, 3.0), ("fusion.2", 2.0, 4.0),
                               ("copy.3", 6.0, 7.0)]},
        modules={"/device:TPU:0": [("jit_serve_step(1)", 1.0, 4.0),
                                   ("jit_forward(2)", 6.0, 7.0)]},
        host=[("step", 0.5, 4.5), ("tier.reload", 4.6, 5.9),
              ("step", 4.5, 7.5), ("wait_arrival", 7.5, 10.0)])


def test_union_merges_overlaps():
    assert T.union([(2, 4), (1, 3), (6, 7)]) == [(1, 4), (6, 7)]


def test_busy_and_idle():
    tr = _hand_made()
    assert T.busy_s(tr) == pytest.approx(4.0)
    gaps = T.idle_gaps(tr)
    assert [n for n, _ in gaps] == ["wait_arrival", "tier.reload", "step"]
    assert [s for _, s in gaps] == pytest.approx([3.0, 2.0, 1.0])
    assert sum(s for _, s in gaps) + T.busy_s(tr) == pytest.approx(
        tr.window_s)


def test_op_and_module_times():
    tr = _hand_made()
    assert T.op_seconds(tr) == [("fusion.1", 2.0), ("fusion.2", 2.0),
                                ("copy.3", 1.0)]
    assert T.module_calls(tr, "serve_step") == [3.0]
    assert T.module_calls(tr, "jit_forward") == [1.0]


def _recorded():
    """Four decode steps of Yi-6B at batch 8 on one TPU v5e, inside a
    ``window`` annotation, each step inside a ``step`` annotation."""
    import gzip
    from jax.profiler import ProfileData
    raw = gzip.open(DATA / "v5e-yi6b-steps.xplane.pb.gz").read()
    return T.from_profile(ProfileData.from_serialized_xspace(raw))


def test_recorded_trace():
    tr = _recorded()
    assert tr.window_s == pytest.approx(0.145408502, abs=1e-9)
    assert T.busy_s(tr) == pytest.approx(0.118496285, abs=1e-9)
    gaps = T.idle_gaps(tr)
    assert sum(s for _, s in gaps) + T.busy_s(tr) == pytest.approx(
        tr.window_s)
    assert {n for n, _ in gaps} == {"step"}
    steps = T.module_calls(tr, "serve_step")
    assert len(steps) == 4
    assert all(s == pytest.approx(0.02962, abs=2e-5) for s in steps)
    assert T.module_calls(tr, "jit_forward") == []
    name, seconds = T.op_seconds(tr)[0]
    assert name == "while.3 (tuple)" and seconds == pytest.approx(
        0.09935, abs=1e-5)
