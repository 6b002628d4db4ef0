"""The arithmetic of the metric readers in ``bench/metrics/``.

Each reader takes the :class:`~bench.harness.record.Run` and returns a
number, or ``None`` where the run holds nothing to read (no trace, no
tier move).  A reader never stands in a 0 for nothing; ``bench/run.py``
stops a run in which a metric its cell lists reads ``None``.
"""
from __future__ import annotations

from statistics import fmean
from typing import List, Optional

from bench.harness import roofline as R
from bench.harness import trace as T
from bench.harness.record import Run
from bench.harness.stats import percentile

#: substrings of the compiled programs' names in the device trace
DECODE_PROGRAM = "serve_step"
PREFILL_PROGRAM = "jit_forward"


# -------------------------------------------------------------- end to end
def ttft_s(run: Run, q: float) -> float:
    """From when each request was due to its first token; a request with
    none by the window's end counts with its wait so far."""
    waits = [(t.times[0] if t.times and t.times[0] <= run.t_end
              else run.t_end) - t.due for t in run.due]
    return percentile(waits, q)


def itl_ms(run: Run, q: float) -> float:
    gaps = [b - a for t in run.turns for a, b in zip(t.times, t.times[1:])
            if run.inside(a) and run.inside(b)]
    return 1e3 * percentile(gaps, q)


def output_tokens_per_s(run: Run) -> float:
    n = sum(1 for t in run.turns for x in t.times if run.inside(x))
    return n / run.seconds


# ------------------------------------------------------------- per layer
def client_lag_ms(run: Run, q: float) -> Optional[float]:
    """How late requests were sent.  In a traced run only the requests due
    inside the traced span count: starting and stopping the profiler
    holds the client for seconds just outside it."""
    lo, hi = run.traced or (run.t0, run.t_end)
    lags = [t.sent - t.due for t in run.due
            if t.sent is not None and lo <= t.due <= hi]
    return 1e3 * percentile(lags, q) if lags else None


def batch_occupancy(run: Run) -> Optional[float]:
    rows = [d.rows for d in run.decodes if run.inside(d.t)]
    return fmean(rows) / run.max_batch if rows else None


def tier_move_ms_per_block(run: Run) -> Optional[float]:
    """Host time in the evict and reload hooks per block moved."""
    if not run.hook_blocks:
        return None
    return 1e3 * run.hook_s / run.hook_blocks


def blocks_moved_per_turn(run: Run) -> Optional[float]:
    moved = sum(run.kv.get(k, 0) for k in ("evict_to_peer", "evict_to_host",
                                           "reload_peer", "reload_host"))
    done = [t for t in run.turns if t.finished and run.inside(t.times[-1])]
    return moved / len(done) if done else None


def _traced(run: Run, t: float) -> bool:
    return run.traced is not None and run.traced[0] <= t <= run.traced[1]


def decode_step_ms(run: Run) -> Optional[float]:
    if run.trace is None:
        return None
    calls = T.module_calls(run.trace, DECODE_PROGRAM)
    return 1e3 * fmean(calls) if calls else None


def prefill_ms_per_ktok(run: Run) -> Optional[float]:
    if run.trace is None:
        return None
    calls = T.module_calls(run.trace, PREFILL_PROGRAM)
    padded = sum(p.padded for p in run.prefills if _traced(run, p.t))
    if not calls or not padded:
        return None
    return 1e3 * sum(calls) / (padded / 1e3)


def decode_roofline(run: Run) -> Optional[float]:
    """The least time of the traced decode steps over their device time,
    per step on average, in percent."""
    if run.trace is None or run.peak is None:
        return None
    calls = T.module_calls(run.trace, DECODE_PROGRAM)
    steps = [d for d in run.decodes if _traced(run, d.t)]
    if not calls or not steps:
        return None
    least: List[float] = [R.least_seconds(
        run.counts.decode_flops(d.rows, d.attended),
        run.counts.decode_bytes(d.rows, d.attended), run.peak)
        for d in steps]
    return 100.0 * fmean(least) / fmean(calls)


def device_idle_share(run: Run) -> Optional[float]:
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * (1.0 - T.busy_s(run.trace) / run.trace.window_s)


def mfu(run: Run, whole_window: bool) -> Optional[float]:
    """Model operations of every token the window processed (prompt
    positions prefilled that no cache served, tokens decoded) over the
    time the server had work, or the whole window, at the chip's peak."""
    if run.peak is None:
        return None
    flops = sum(run.counts.prefill_flops(p.cached, p.tokens - p.cached)
                for p in run.prefills if run.inside(p.t))
    flops += sum(run.counts.decode_flops(d.rows, d.attended)
                 for d in run.decodes if run.inside(d.t))
    seconds = run.seconds if whole_window else run.busy_s()
    if not flops or not seconds:
        return None
    return 100.0 * flops / (seconds * run.peak["bf16_flops_per_s"])
