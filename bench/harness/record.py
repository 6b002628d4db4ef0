"""What one run measured, as the metric readers see it."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from bench.harness.client import Decode, Prefill, Turn
from bench.harness.roofline import Counts
from bench.harness.trace import Trace


@dataclass
class Run:
    seconds: float
    t0: float
    t_end: float
    setup_s: float
    max_batch: int
    #: the least work of a step, from the configuration's reference
    counts: Counts
    peak: Optional[dict]
    turns: List[Turn]
    prefills: List[Prefill]
    decodes: List[Decode]
    steps: List[tuple]
    hook_s: float
    hook_blocks: int
    #: the engine's tier counters over the window
    kv: Dict[str, int] = field(default_factory=dict)
    trace: Optional[Trace] = None
    #: the host-clock span the trace covers
    traced: Optional[tuple] = None

    def inside(self, t: float) -> bool:
        return self.t0 <= t <= self.t_end

    @property
    def due(self) -> List[Turn]:
        """Requests due in the window."""
        return [t for t in self.turns if self.t0 <= t.due < self.t_end]

    def busy_s(self) -> float:
        """Seconds of the window the server spent in ``step()``, i.e.
        with a running or waiting request."""
        return sum(min(b, self.t_end) - max(a, self.t0)
                   for a, b in self.steps if b > self.t0 and a < self.t_end)
