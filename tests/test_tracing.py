"""The engine's spans (``repro.core.tracing``): recorded only under a
``jax.profiler`` session, nested as the work is, on the profiler's clock,
and never changing a token.

One tiny workload is served twice, without and under a profiler session:
a 2-layer Yi-6B, two batch rows, ten pool slots and the prefix cache, so
the run prefills, decodes, evicts, reloads and retires.
"""
import dataclasses
import glob
from collections import defaultdict

import pytest

from repro.core import HarvestRuntime, tracing
from repro.serving import HarvestServer, ServeRequest

MiB = 2**20
SHARED = [3 + (i * 7) % 200 for i in range(24)]
# two prompts publish the shared prefix, three distinct ones push it out
# of the pool, and the last reads it back from the peer tier
PROMPTS = ([SHARED + [11 + i, 40 + i, 90 + i] for i in range(2)]
           + [[5 + 31 * i + j for j in range(30)] for i in range(3)]
           + [SHARED + [17, 18, 19]])


@pytest.fixture(scope="module")
def served_model():
    import jax
    from repro.configs import get_config
    from repro.models import model as M
    cfg = dataclasses.replace(get_config("yi-6b").reduced(), num_layers=2)
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


def _serve(served_model):
    cfg, params = served_model
    srv = HarvestServer(cfg, params, runtime=HarvestRuntime({1: 64 * MiB}),
                        max_batch=2, block_size=8, num_local_slots=10,
                        scheduler="fcfs", mode="async", prefix_cache=True)
    handles = [srv.submit(ServeRequest(p, max_new_tokens=10))
               for p in PROMPTS]
    srv.run(max_steps=500)
    return handles


@pytest.fixture(scope="module")
def untraced(served_model):
    tracing.clear()
    handles = _serve(served_model)
    return handles, tracing.spans()


@pytest.fixture(scope="module")
def traced(served_model, untraced, tmp_path_factory):
    import jax
    d = str(tmp_path_factory.mktemp("profile"))
    tracing.clear()
    with jax.profiler.trace(d):
        handles = _serve(served_model)
    recorded = tracing.spans()
    tracing.clear()
    return handles, recorded, d


def test_no_session_records_nothing(untraced):
    handles, recorded = untraced
    assert all(h.finished for h in handles)
    assert recorded == []
    assert not tracing.on()
    # off, every span is the one shared no-op context
    assert tracing.span("a", x=1) is tracing.span("b")


def test_spans_nest_and_carry_their_request(traced):
    handles, recorded, _ = traced
    by_id = {s.id: s for s in recorded}
    names = {s.name for s in recorded}
    assert {"engine.step", "engine.admit", "engine.prefill",
            "engine.prefill.write", "engine.plan", "engine.decode",
            "engine.sample", "engine.retire", "engine.submit",
            "kv.evict", "kv.reload", "kv.write", "device.wait"} <= names
    for s in recorded:
        if s.name in ("engine.step", "engine.submit"):
            assert s.parent is None, s
            continue
        p = by_id[s.parent]
        assert p.t0 <= s.t0 <= s.t1 <= p.t1, (s, p)
    parent = lambda s: by_id[s.parent].name
    for s in recorded:
        if s.name in ("engine.admit", "engine.plan", "engine.decode",
                      "engine.sample", "engine.retire"):
            assert parent(s) == "engine.step"
        elif s.name == "engine.prefill.write":
            assert parent(s) == "engine.prefill"
        elif s.name == "kv.write":
            assert parent(s) in ("engine.prefill.write", "kv.reload",
                                 "engine.prefill")
    # one request's spans share its id
    per_req = defaultdict(set)
    for s in recorded:
        if "req" in s.args:
            per_req[s.args["req"]].add(s.name)
    for h in handles:
        assert {"engine.submit", "engine.prefill"} <= per_req[h.req_id]
    admitted = [q for s in recorded if s.name == "engine.admit"
                for q in s.args["admitted"]]
    retired = [q for s in recorded if s.name == "engine.retire"
               for q in s.args["retired"]]
    assert sorted(retired) == sorted(h.req_id for h in handles)
    assert set(admitted) == set(retired)


def test_tokens_identical_with_tracing_on_and_off(untraced, traced):
    assert [h.tokens for h in untraced[0]] == [h.tokens for h in traced[0]]
    assert all(len(h.tokens) == 10 for h in traced[0])


@pytest.mark.parametrize("run", ["untraced", "traced"])
def test_queued_s_set_for_every_admitted_request(request, run):
    handles = request.getfixturevalue(run)[0]
    for h in handles:
        assert h.admit_t is not None
        assert h.queued_s is not None and h.queued_s >= 0.0
    # two rows for six requests: some waited for a row
    assert max(h.queued_s for h in handles) > 0.0


def test_queued_s_none_until_admitted(served_model):
    cfg, params = served_model
    srv = HarvestServer(cfg, params, runtime=HarvestRuntime({1: 64 * MiB}),
                        max_batch=2, block_size=8, num_local_slots=10)
    h = srv.submit(ServeRequest(PROMPTS[0], max_new_tokens=2))
    assert h.queued_s is None
    srv.run(max_steps=50)
    assert h.queued_s >= 0.0


def test_device_waits_lie_inside_their_parents(traced):
    _, recorded, _ = traced
    by_id = {s.id: s for s in recorded}
    waits = [s for s in recorded if s.name == "device.wait"]
    assert waits
    for w in waits:
        p = by_id[w.parent]
        assert p.name in ("engine.prefill", "engine.sample", "kv.evict")
        assert p.t0 <= w.t0 <= w.t1 <= p.t1
    evicts = [s for s in recorded if s.name == "kv.evict"]
    assert evicts and all(any(w.parent == e.id for w in waits)
                          for e in evicts)


def test_recorder_and_profiler_agree(traced):
    """The same spans read back from the ``.xplane.pb`` the session
    wrote: under their bare names, as long as the recorder's to 0.1 ms."""
    from jax.profiler import ProfileData
    _, recorded, d = traced
    path = sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))[-1]
    pd = ProfileData.from_file(path)
    host = defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host[e.name].append(e)
    mine = defaultdict(list)
    for s in recorded:
        mine[s.name].append(s)
    assert set(mine) <= set(host)
    parted = {}
    for name, spans in mine.items():
        events = sorted(host[name], key=lambda e: e.start_ns)
        spans.sort(key=lambda s: s.t0)
        assert len(events) == len(spans), name
        parted[name] = [abs(e.duration_ns * 1e-9 - (s.t1 - s.t0))
                        for e, s in zip(events, spans)]
    steps = sorted(host["engine.step"], key=lambda e: e.start_ns)
    assert [dict(e.stats)["step"] for e in steps] == [
        s.args["step"] for s in mine["engine.step"]]
    # the two clocks are read a few microseconds apart (at most 4.3 us a
    # step on an overloaded CPU); a thread descheduled between the two
    # reads parts a pair by more, up to 0.76 ms in 400 pool writes there
    assert max(parted["engine.step"]) < 1e-4
    assert max(max(v) for v in parted.values()) < 1e-3


def test_a_new_session_drops_the_last_ones_spans(tmp_path):
    import jax
    tracing.span("before")              # found no session
    with jax.profiler.trace(str(tmp_path / "a")):
        with tracing.span("engine.step", step=-1):
            pass
    assert [s.args for s in tracing.spans()] == [{"step": -1}]
    tracing.span("between")             # found no session
    with jax.profiler.trace(str(tmp_path / "b")):
        tracing.event("engine.submit", req=-2)
    assert [s.args for s in tracing.spans()] == [{"req": -2}]
    tracing.clear()


def test_recorder_is_bounded():
    assert tracing._recorded.maxlen == tracing.MAX_SPANS
