"""From a profiler trace to device metrics.

``jax.profiler`` writes an ``.xplane.pb``; :func:`read` turns it into a
:class:`Trace`: the device operations (name, start, end) of each chip,
the compiled programs that ran (the "XLA Modules" line), and the host
annotations the benchmark put around its calls into the program, all on
one clock, clipped to the annotated ``window``.  The functions below it
are plain interval arithmetic, tested on a recorded trace.
"""
from __future__ import annotations

import glob
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # seconds on the trace's clock

#: host annotations the benchmark writes, most specific first: an idle
#: gap of the device is named after the first one that covers it
HOST_SPANS = ("tier.evict", "tier.reload", "submit", "step", "wait_arrival")
WINDOW = "window"


@dataclass
class Trace:
    window: Interval
    #: per device: (op name, start, end)
    ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    #: per device: (program name, start, end)
    modules: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    host: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def op_name(text: str) -> str:
    """An XLA op event's name is its whole HLO instruction; keep the
    instruction's name and the shape it produces."""
    name, _, rest = text.partition(" = ")
    shape = "(tuple)" if rest.startswith("(") else \
        rest.split("{")[0].split(" ")[0]
    return (name.lstrip("%") + " " + shape).strip()


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[len("/device:TPU:"):] \
        .isdigit()


def read(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def from_profile(pd) -> Trace:
    ns = 1e-9
    host, ops, modules = [], {}, {}
    window: Optional[Interval] = None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns * ns, e.end_ns * ns)
                    elif e.name in HOST_SPANS:
                        host.append((e.name, e.start_ns * ns, e.end_ns * ns))
        elif _is_device_plane(plane.name):
            for line in plane.lines:
                target = {"XLA Ops": ops, "XLA Modules": modules}.get(
                    line.name)
                if target is None:
                    continue
                name = op_name if target is ops else str
                target[plane.name] = [(name(e.name), e.start_ns * ns,
                                       e.end_ns * ns) for e in line.events]
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} host annotation")
    lo, hi = window
    clip = lambda evs: [(n, max(a, lo), min(b, hi)) for n, a, b in evs
                        if b > lo and a < hi]
    inside = lambda evs: [(n, a, b) for n, a, b in evs if a >= lo and b <= hi]
    # programs are counted whole, so a run cut by the window's edge is left
    # out rather than read as a short one
    return Trace(window,
                 {d: clip(v) for d, v in ops.items()},
                 {d: inside(v) for d, v in modules.items()},
                 clip(host))


# ------------------------------------------------------------ arithmetic
def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not tr.ops:
        return 0.0
    per = [sum(b - a for a, b in union([(s, e) for _, s, e in evs]))
           for evs in tr.ops.values()]
    return sum(per) / len(per)


def idle_gaps(tr: Trace) -> List[Tuple[str, float]]:
    """Gaps with no device operation inside the window (first device),
    each named by the most specific host span covering its middle,
    longest first."""
    if not tr.ops:
        return []
    dev = sorted(tr.ops)[0]
    busy = union([(s, e) for _, s, e in tr.ops[dev]])
    edges = [tr.window[0]] + [x for iv in busy for x in iv] + [tr.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    rank = {n: i for i, n in enumerate(HOST_SPANS)}
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        cover = [n for n, s, e in tr.host if s <= mid <= e]
        name = min(cover, key=rank.__getitem__) if cover else "host"
        out.append((name, b - a))
    return sorted(out, key=lambda x: -x[1])


def op_seconds(tr: Trace) -> List[Tuple[str, float]]:
    """Device seconds by operation name (first device), most first."""
    if not tr.ops:
        return []
    tot: Dict[str, float] = defaultdict(float)
    for n, s, e in tr.ops[sorted(tr.ops)[0]]:
        tot[n] += e - s
    return sorted(tot.items(), key=lambda x: -x[1])


def module_calls(tr: Trace, needle: str) -> List[float]:
    """Device seconds of each run of the programs whose name holds
    ``needle`` (first device)."""
    if not tr.modules:
        return []
    return [e - s for n, s, e in tr.modules[sorted(tr.modules)[0]]
            if needle in n]
