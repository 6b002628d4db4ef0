"""The client side of a run: sends the traffic to ``HarvestServer`` on
the wall clock, drives ``engine.step()``, and times every token.

Everything is measured from outside the program.  Token times are read in
the request's ``on_token`` callback, which the engine calls once the
token is on the host, so the device has produced it.  The few wrappers
below are installed from the benchmark's files on the engine instance:
they count and time what the engine does (prefills, decode batches, tier
moves) and change none of it, and they wait for nothing, so a traced run
drives the same schedule as an untraced one.  They wrap these names of
the program, which a run needs: ``engine._prefill``,
``engine._commit_and_sample`` and ``kv_mgr.evict_hook``/``reload_hook``.
"""
from __future__ import annotations

import contextlib
import heapq
import itertools
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from bench.harness.traffic import Session, Traffic

clock = time.perf_counter
#: session ids of the set-up's warm-up requests (traffic ids count from 0)
WARM_SID = 1 << 40


@dataclass(eq=False)
class Turn:
    """One request: a turn of a session."""
    session: Session
    turn: int
    prompt: List[int]
    max_new: int
    due: float
    sent: Optional[float] = None
    times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    handle: object = None

    @property
    def finished(self) -> bool:
        return len(self.tokens) >= self.max_new


@dataclass
class Prefill:
    t: float
    tokens: int         # prompt (+ output) positions the prefill covers
    cached: int         # positions adopted from the prefix cache
    padded: int         # positions the forward ran over


@dataclass
class Decode:
    t: float
    rows: int
    attended: int       # live positions over all rows, new tokens included


def no_annotation(_name):
    return contextlib.nullcontext()


class Client:
    """Open- or closed-loop client over one server."""

    def __init__(self, server, traffic: Traffic, max_batch: int, *,
                 annotate: Callable = no_annotation):
        self.server = server
        self.eng = server.engine
        self.traffic = traffic
        self.B = max_batch
        self.annotate = annotate
        self.turns: List[Turn] = []
        self.pending: list = []            # (due, seq, session, turn, prefix)
        self._seq = itertools.count()
        self._resumes: list = []           # prebuilt sessions' next turns
        self.setup_phase = True
        self.prefills: List[Prefill] = []
        self.decodes: List[Decode] = []
        self.steps: List[tuple] = []       # (start, end) of each step()
        self.hook_s = 0.0
        self.hook_blocks = 0
        self._install()

    # ---------------------------------------------------------- wrappers
    def _install(self) -> None:
        eng = self.eng
        try:
            prefill, commit = eng._prefill, eng._commit_and_sample
        except AttributeError as e:
            raise RuntimeError(f"the benchmark counts prefills and decode "
                               f"batches through {e.name!r}, which the "
                               f"engine no longer has") from e

        def counted_prefill(r):
            n = len(r.prompt) + len(r.output)
            prefill(r)
            cached = r.cached_prefix_blocks * eng.bs
            self.prefills.append(Prefill(clock(), n, cached,
                                         eng.bs * -(-n // eng.bs)))

        def counted_commit(logits, reqs):
            attended = sum(r.pos + 1 for r in reqs)
            commit(logits, reqs)
            self.decodes.append(Decode(clock(), len(reqs), attended))

        eng._prefill = counted_prefill
        eng._commit_and_sample = counted_commit
        kv = eng.kv_mgr
        for attr, label in (("evict_hook", "tier.evict"),
                            ("reload_hook", "tier.reload")):
            setattr(kv, attr, self._timed_hook(getattr(kv, attr), label))

    def _timed_hook(self, hook, label):
        """Host time inside a tier hook: an eviction's read of the block
        back to the host is synchronous and inside it; a reload's write
        into the pool is queued on the device and is not."""
        def timed(bid, slot):
            with self.annotate(label):
                t = clock()
                hook(bid, slot)
                if not self.setup_phase:
                    self.hook_s += clock() - t
                    self.hook_blocks += 1
        return timed

    # ------------------------------------------------------------ submit
    def _submit(self, s: Session, k: int, prefix: List[int], due: float
                ) -> Turn:
        from repro.serving import ServeRequest
        prompt = prefix + self.traffic.user_tokens(s, k)
        t = Turn(s, k, prompt, s.answers[k], due)

        def on_token(tok, _req, t=t):
            now = clock()
            t.times.append(now)
            t.tokens.append(int(tok))
            if t.finished:
                self._finished(t, now)

        with self.annotate("submit"):
            t.handle = self.server.submit(
                ServeRequest(prompt, t.max_new, on_token=on_token))
        t.sent = clock()
        self.turns.append(t)
        return t

    def _finished(self, t: Turn, now: float) -> None:
        s, k = t.session, t.turn + 1
        if k >= s.turns:
            return
        prefix = t.prompt + t.tokens
        if self.setup_phase:
            self._resumes.append((s, k, prefix))
        else:
            heapq.heappush(self.pending, (now + s.thinks[k - 1],
                                          next(self._seq), s, k, prefix))

    def busy(self) -> bool:
        return bool(self.eng.waiting or self.eng.running)

    def step(self) -> None:
        with self.annotate("step"):
            t = clock()
            self.eng.step()
            self.steps.append((t, clock()))

    def drain(self) -> None:
        while self.busy():
            self.step()

    # ------------------------------------------------------------- set-up
    def warm(self, lengths: List[int]) -> None:
        """Serve one request of every prompt length the traffic can send,
        so every program the window runs is compiled before it opens."""
        for i, n in enumerate(lengths):
            s = Session(WARM_SID + i, None, [n], [2], prebuilt=False)
            self._submit(s, 0, [], clock())
            self.drain()

    def push_out(self, length: int) -> None:
        """Serve warm-up requests of ``length`` positions until the local
        pool has evicted as many blocks as the prebuilt sessions published
        (their prompts' full blocks), the oldest first: a returning user's
        history then sits in the peer and host tiers when the window opens,
        and the eviction path is compiled before it."""
        bs = self.eng.bs
        target = sum(s.users[0] // bs for s in self.traffic.prebuilt)
        # enough requests to fill the whole pool twice over
        most = 2 * self.eng.pool_k.shape[1] * bs // length + 2
        for i in range(most + 1):
            kv = self.eng.kv_mgr.stats
            if kv["evict_to_peer"] + kv["evict_to_host"] >= target:
                return
            s = Session(WARM_SID + (1 << 20) + i, None, [length], [2])
            self._submit(s, 0, [], clock())
            self.drain()
        raise RuntimeError(f"{most} requests of {length} positions evicted "
                           f"fewer than the {target} blocks the prebuilt "
                           f"sessions published")

    def probe_reload(self) -> None:
        """Extend the head of the first prebuilt prompt, whose blocks
        :meth:`push_out` has moved to the tiers, so that reading blocks
        back is compiled before the window too."""
        first = next(t for t in self.turns if t.session.prebuilt)
        s = Session(WARM_SID - 1, None, [self.traffic.m], [2])
        self._submit(s, 0, first.prompt[:self.traffic.m], clock())
        self.drain()

    def prebuild(self) -> None:
        """Serve the first turns of the prebuilt sessions."""
        for s in self.traffic.prebuilt:
            self._submit(s, 0, [], clock())
        self.drain()

    # ------------------------------------------------------------- window
    def run(self, seconds: float, on_tick: Callable = None) -> tuple:
        """Measure for ``seconds``: returns the window ``(t0, t_end)``.
        ``on_tick(now)`` is called between steps (the tracer's switch)."""
        self.setup_phase = False
        t0 = clock()
        t_end = t0 + seconds
        for s, k, prefix in self._resumes:
            heapq.heappush(self.pending, (t0 + s.resume, next(self._seq),
                                          s, k, prefix))
        for s in self.traffic.arrivals():
            heapq.heappush(self.pending, (t0 + s.start, next(self._seq),
                                          s, 0, []))
        closed = self.traffic.mix["loop"] == "closed"
        target = self.B * (1 + self.traffic.mix.get("queue_per_row", 0))
        while True:
            now = clock()
            if now >= t_end:
                break
            if on_tick is not None:
                on_tick(now)
            while self.pending and self.pending[0][0] <= now:
                due, _, s, k, prefix = heapq.heappop(self.pending)
                self._submit(s, k, prefix, due)
            if closed:
                while len(self.eng.waiting) + len(self.eng.running) < target:
                    self._submit(self.traffic.next_closed(), 0, [], clock())
            if self.busy():
                self.step()
            else:
                nxt = self.pending[0][0] if self.pending else t_end
                with self.annotate("wait_arrival"):
                    time.sleep(max(0.0, min(nxt, t_end) - clock()))
        return t0, t_end
