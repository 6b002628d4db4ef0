"""The arithmetic of the readers of the program's own spans.

The serving engine records spans (``repro.core.tracing``) while a
``jax.profiler`` session records, which in a run is the traced span of
``--trace 1``.  Each reader takes the :class:`~bench.harness.record.Run`
and returns a number, or ``None`` where there is nothing to read: an
untraced run, or a program without the spans.
"""
from __future__ import annotations

from statistics import fmean
from typing import Dict, List, Optional, Sequence, Tuple

from bench.harness import trace as T
from bench.harness.record import Run
from bench.harness.stats import percentile

#: the name of a device idle gap no program span covers
OUTSIDE = "(no engine span)"


def recorded(run: Run) -> Optional[list]:
    """The program's spans that lie inside the traced span."""
    if run.traced is None:
        return None
    try:
        from repro.core import tracing
    except ImportError:
        return None
    return tracing.spans(*run.traced)


def _ancestor(span, by_id: Dict[int, object], name: str):
    """The nearest enclosing span called ``name``, or ``None``."""
    while span.parent is not None:
        span = by_id.get(span.parent)
        if span is None:
            return None
        if span.name == name:
            return span
    return None


def _covered(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in T.union(intervals))


#: spans in which the host waits for the device: explicit reads back, and
#: the eager pool writes whose dispatch holds the host while the device
#: copies the pool
DEVICE_BOUND = ("device.wait", "kv.write")


def _waits_under(spans: Sequence, name: str,
                 kinds: Sequence[str] = ("device.wait",)
                 ) -> Dict[int, List[tuple]]:
    """Intervals of the spans named in ``kinds``, by the id of the nearest
    enclosing ``name`` span."""
    by_id = {s.id: s for s in spans}
    out: Dict[int, List[tuple]] = {}
    for w in spans:
        if w.name in kinds:
            a = _ancestor(w, by_id, name)
            if a is not None:
                out.setdefault(a.id, []).append((w.t0, w.t1))
    return out


# ------------------------------------------------------------------ readers
def step_host_ms(spans: Sequence) -> Optional[float]:
    """Host milliseconds per engine step: each ``engine.step`` less the
    union of the ``device.wait`` and ``kv.write`` spans inside it,
    averaged over steps."""
    steps = [s for s in spans if s.name == "engine.step"]
    if not steps:
        return None
    waits = _waits_under(spans, "engine.step", DEVICE_BOUND)
    return 1e3 * fmean((s.t1 - s.t0) - _covered(waits.get(s.id, []))
                       for s in steps)


def kv_write_ms_per_block(spans: Sequence) -> Optional[float]:
    """Milliseconds per ``kv.write`` span: the host's time in the eager
    writes of one block's K and V into the pools."""
    writes = [s.t1 - s.t0 for s in spans if s.name == "kv.write"]
    return 1e3 * fmean(writes) if writes else None


def prefill_share(spans: Sequence) -> Optional[float]:
    """Percent of the summed ``engine.step`` time that ``engine.prefill``
    spans cover."""
    by_id = {s.id: s for s in spans}
    total = sum(s.t1 - s.t0 for s in spans if s.name == "engine.step")
    if not total:
        return None
    prefills = [(s.t0, s.t1) for s in spans if s.name == "engine.prefill"
                and _ancestor(s, by_id, "engine.step") is not None]
    return 100.0 * _covered(prefills) / total


def evict_wait_ms_per_block(spans: Sequence) -> Optional[float]:
    """Milliseconds of ``device.wait`` inside ``kv.evict`` spans per
    ``kv.evict`` span: how long an eviction's read of its block back to
    the host waits for the device."""
    evicts = [s for s in spans if s.name == "kv.evict"]
    if not evicts:
        return None
    waits = _waits_under(spans, "kv.evict")
    return 1e3 * sum(_covered(v) for v in waits.values()) / len(evicts)


def queue_wait_ms(run: Run, q: float = 90) -> Optional[float]:
    """Percentile ``q`` of the wall-clock wait between submission and
    first admission (``RequestHandle.queued_s``) over the requests due in
    the window, beside ``ttft_p90_s``.  One not admitted counts with its
    wait so far.  A traced run is read up to its traced span's end: the
    profiler's stop then holds the client for seconds, and the backlog
    it leaves would read as queue wait."""
    end = run.traced[1] if run.traced is not None else run.t_end
    waits = []
    for t in run.due:
        if t.sent is None or t.sent > end or t.due > end:
            continue
        if not hasattr(t.handle, "queued_s"):
            return None                  # a program without the stamps
        w = t.handle.queued_s
        waits.append(end - t.sent if w is None or t.sent + w > end else w)
    return 1e3 * percentile(waits, q) if waits else None


def read(run: Run, reader) -> Optional[float]:
    """``reader`` over the run's recorded spans."""
    spans = recorded(run)
    return reader(spans) if spans else None


# ------------------------------------------------------------ idle gaps
def idle_by_span(run: Run) -> List[Tuple[str, float]]:
    """Each device idle gap of the traced window (first device), named by
    the innermost program span covering its middle, longest first.  The
    recorder's clock is mapped onto the trace's by the offset between the
    ``window`` annotation's start and the traced span's."""
    tr, spans = run.trace, recorded(run)
    if tr is None or not tr.ops or spans is None:
        return []
    shift = tr.window[0] - run.traced[0]
    dev = sorted(tr.ops)[0]
    busy = T.union([(s, e) for _, s, e in tr.ops[dev]])
    edges = [tr.window[0]] + [x for iv in busy for x in iv] + [tr.window[1]]
    out = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2 - shift
        cover = [s for s in spans if s.t0 <= mid <= s.t1]
        name = max(cover, key=lambda s: s.t0).name if cover else OUTSIDE
        out.append((name, b - a))
    return sorted(out, key=lambda x: -x[1])
