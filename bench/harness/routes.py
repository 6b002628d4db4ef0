"""The arithmetic of the expert-layer readers.

A served MoE program reports, with each prefill's logits and each decode
step's, the routes its layers sent to each held expert; the engine
records them as a ``moe.routes`` event (``repro.core.tracing``) while a
profiler session records, which in a run is the traced span of
``--trace 1``.  Its args: ``program`` ("prefill" or "decode"),
``routes_held`` and ``max_routes`` per layer (routes to held experts, and
the busiest held expert's), ``experts_touched`` (layer, held expert)
pairs with a route, ``layers``, ``held``.  Each reader returns ``None``
where there is nothing to read: an untraced run, a model without
experts, a program without the event, or counts without routed terms.
"""
from __future__ import annotations

from bisect import bisect_right
from statistics import fmean
from typing import List, Optional, Sequence, Tuple

from bench.harness import roofline as R
from bench.harness import spans as S
from bench.harness import trace as T
from bench.harness.readers import DECODE_PROGRAM
from bench.harness.record import Run

EVENT = "moe.routes"


def events(run: Run, program: str) -> Optional[list]:
    """The traced span's ``moe.routes`` events of ``program``, in order."""
    spans = S.recorded(run)
    evs = [s for s in spans or () if s.name == EVENT
           and s.args.get("program") == program]
    return sorted(evs, key=lambda s: s.t1) or None


def paired(records: Sequence, evs: Sequence) -> List[Tuple[object, object]]:
    """Each client record (a prefill or a decode step, stamped when the
    engine's call returned) with the event recorded inside that call: the
    last to end before the stamp, if it began after the record before."""
    ends = [e.t1 for e in evs]
    out, prev, used = [], float("-inf"), -1
    for r in sorted(records, key=lambda r: r.t):
        i = bisect_right(ends, r.t) - 1
        if i > used and evs[i].t0 >= prev:
            out.append((r, evs[i]))
            used = i
        prev = r.t
    return out


def _routed(run: Run) -> bool:
    return hasattr(run.counts, "routed_decode_flops")


def _traced(run: Run, t: float) -> bool:
    return run.traced is not None and run.traced[0] <= t <= run.traced[1]


def decode_roofline(run: Run) -> Optional[float]:
    """The least time of the traced decode steps given the routes each
    reported (each held expert touched read once, every route's matmuls)
    over the decode program's device time, per step, in percent."""
    evs = events(run, "decode")
    if not evs or run.trace is None or run.peak is None or not _routed(run):
        return None
    calls = T.module_calls(run.trace, DECODE_PROGRAM)
    steps = paired([d for d in run.decodes if _traced(run, d.t)], evs)
    if not calls or not steps:
        return None
    c = run.counts
    least = [R.least_seconds(
        c.routed_decode_flops(d.rows, d.attended, sum(e.args["routes_held"])),
        c.routed_decode_bytes(d.rows, d.attended, e.args["experts_touched"]),
        run.peak) for d, e in steps]
    return 100.0 * fmean(least) / fmean(calls)


def mfu(run: Run) -> Optional[float]:
    """Model operations of the prefills and decode steps of the traced
    span, each with the expert work of the routes it reported, over the
    span at the chip's peak, in percent."""
    if run.peak is None or run.traced is None or not _routed(run):
        return None
    c = run.counts
    flops = 0
    for program, records in (("prefill", run.prefills),
                             ("decode", run.decodes)):
        evs = events(run, program) or []
        for r, e in paired([r for r in records if _traced(run, r.t)], evs):
            routes = sum(e.args["routes_held"])
            flops += (c.routed_prefill_flops(r.cached, r.tokens - r.cached,
                                             routes)
                      if program == "prefill" else
                      c.routed_decode_flops(r.rows, r.attended, routes))
    seconds = run.traced[1] - run.traced[0]
    if not flops or seconds <= 0:
        return None
    return 100.0 * flops / (seconds * run.peak["bf16_flops_per_s"])


def routes_per_expert(run: Run) -> Optional[float]:
    """Mean routes per held expert per traced decode step."""
    evs = events(run, "decode")
    if not evs:
        return None
    return fmean(sum(e.args["routes_held"]) / (e.args["layers"]
                                               * e.args["held"])
                 for e in evs)


def load_max_over_mean(run: Run) -> Optional[float]:
    """The busiest held expert's routes over the mean routes of the held
    experts, per traced decode step and layer with a route, averaged."""
    evs = events(run, "decode")
    ratios = [m * e.args["held"] / n for e in evs or ()
              for m, n in zip(e.args["max_routes"], e.args["routes_held"])
              if n]
    return fmean(ratios) if ratios else None
