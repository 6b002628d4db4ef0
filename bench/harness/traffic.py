"""The one traffic generator: sessions of turns, drawn from a mix file.

A mix (``bench/traffic/<mix>.json``) gives distributions, never code,
and names the public source of each shape under ``source``:

``loop``
    ``"open"``: sessions arrive as a Poisson process at the cell's
    ``rate_per_s``, each turn is due when it is due, whatever the server
    does.  ``"closed"``: the client keeps ``queue_per_row`` x
    ``max_batch`` requests waiting beside the running batch.
``multiple``
    every prompt length and answer length is a multiple of it.
``turns``
    turns per session, equally likely.
``prompt``, ``answer``
    the user message of every turn and the answer to it: lognormal
    ``median``/``sigma``, rounded to ``multiple`` and clipped to
    ``[min, max]``.
``max_turn_tokens``
    a turn's prompt (the session's history and the new message) plus its
    answer stays within it; a session ends at the turn that would pass it.
``think_s_mean``
    mean of the exponential gap from a turn's last token to the send of
    the session's next turn.
``prebuild_sessions``
    sessions of two turns or more whose first turn is served during
    set-up; their second turns are due at spread points of the window.

The schedule is a replayed trace.  Every distribution is a pool of
stratified quantiles, as many as the window needs, put in one fixed
order that no seed changes: every run of a mix offers the same arrival
times, lengths, turns and think times, as replaying a recorded trace
would.  ``seed`` draws what a trace leaves open: the tokens of every
message (and, in ``bench/run.py``, the weights).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List, Optional

import numpy as np

#: closed loop: the stream cycles through a pool of this many draws
CLOSED_POOL = 64
#: the stream that orders the schedule's pools, the same for every seed
SCHEDULE_STREAM = 20240709


@dataclass
class Session:
    sid: int
    #: offset of the first turn's due time in the window (open loop)
    start: Optional[float]
    #: user-message length of each turn
    users: List[int]
    answers: List[int]
    #: think time after each turn but the last
    thinks: List[float] = field(default_factory=list)
    #: prebuilt sessions: offset of turn 1's due time in the window
    resume: Optional[float] = None
    #: whether set-up serves the first turn
    prebuilt: bool = False

    @property
    def turns(self) -> int:
        return len(self.users)


class _Pool:
    """A fixed multiset of draws in a fixed order, read cyclically."""

    def __init__(self, values, rng: np.random.Generator):
        self.values = np.asarray(values)[rng.permutation(len(values))]
        self.i = 0

    def take(self):
        v = self.values[self.i % len(self.values)]
        self.i += 1
        return v.item()


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _round_to(x: float, multiple: int, lo: int, hi: int) -> int:
    return int(min(max(multiple * round(x / multiple), lo), hi))


def _lognormal(spec: dict, multiple: int, n: int) -> List[int]:
    z = NormalDist().inv_cdf
    return [_round_to(spec["median"] * math.exp(spec["sigma"] * z(q)),
                      multiple, spec["min"], spec["max"])
            for q in _quantiles(n)]


class Traffic:
    """The sessions of one run of a mix; ``seed`` draws the tokens."""

    def __init__(self, mix: dict, seed: int, seconds: float,
                 rate_per_s: Optional[float], vocab: int):
        self.mix = mix
        self.seed = seed
        self.seconds = seconds
        self.vocab = vocab
        self.m = int(mix["multiple"])
        rng = np.random.default_rng(SCHEDULE_STREAM)
        n_pre = int(mix.get("prebuild_sessions", 0))
        self.open = mix["loop"] == "open"
        # open loop: exactly round(rate x seconds) sessions arrive in the
        # window; closed loop: a pool the stream cycles through
        n_new = max(1, round(rate_per_s * seconds)) if self.open else \
            CLOSED_POOL
        turns = mix.get("turns", [1])
        per = (n_pre + n_new) * max(turns)
        self._turns = _Pool(np.resize(turns, n_new), rng)
        self._turns_pre = _Pool(np.resize([t for t in turns if t > 1] or [1],
                                          max(n_pre, 1)), rng)
        self._prompt = _Pool(_lognormal(mix["prompt"], self.m, per), rng)
        self._answer = _Pool(_lognormal(mix["answer"], self.m, per), rng)
        self._think = _Pool(-mix.get("think_s_mean", 0.0)
                            * np.log1p(-_quantiles(per)), rng)
        self._resume = _Pool(_quantiles(max(n_pre, 1)) * seconds, rng)
        self._starts: List[float] = []
        if self.open:
            gaps = _Pool(-np.log1p(-_quantiles(n_new)), rng).values
            # Poisson-like gaps scaled so that all n_new fall in the window
            self._starts = (seconds * np.cumsum(gaps)
                            / (gaps.sum() + gaps.mean())).tolist()
        self._next_sid = 0
        self.prebuilt: List[Session] = [self._session(None, prebuilt=True)
                                        for _ in range(n_pre)]

    # ----------------------------------------------------------- sessions
    def _session(self, start: Optional[float], prebuilt: bool = False
                 ) -> Session:
        sid = self._next_sid
        self._next_sid += 1
        n = int((self._turns_pre if prebuilt else self._turns).take())
        cap = int(self.mix.get("max_turn_tokens", 1 << 30))
        users: List[int] = []
        answers: List[int] = []
        thinks: List[float] = []
        context = 0
        for k in range(n):
            u, a = int(self._prompt.take()), int(self._answer.take())
            if k and context + u + a > cap:
                break
            if k:
                thinks.append(float(self._think.take()))
            users.append(u)
            answers.append(a)
            context += u + a
        resume = float(self._resume.take()) if prebuilt and len(users) > 1 \
            else None
        return Session(sid, start, users, answers, thinks, resume, prebuilt)

    def arrivals(self) -> List[Session]:
        """Open loop: the sessions whose first turn is due in the window,
        in order of due time."""
        return [self._session(t) for t in self._starts]

    def next_closed(self) -> Session:
        """Closed loop: the next request, due when the client sends it."""
        return self._session(None)

    def user_tokens(self, s: Session, turn: int) -> List[int]:
        rng = np.random.default_rng([self.seed, 1, s.sid, turn])
        return rng.integers(3, self.vocab, size=s.users[turn]).tolist()

    # ------------------------------------------------------------- shapes
    def prompt_lengths(self) -> List[int]:
        """Every prompt length (so every padded prefill length) this mix
        can produce: set-up warms exactly these."""
        p, m = self.mix["prompt"], self.m
        lo = _round_to(p["min"], m, p["min"], p["max"])
        if max(self.mix.get("turns", [1])) == 1:
            hi = p["max"]
        else:
            hi = int(self.mix["max_turn_tokens"]) - self.mix["answer"]["min"]
        return list(range(lo, hi + 1, m))

    def max_sequence(self) -> int:
        """The longest prompt plus answer a request of this mix can have."""
        if max(self.mix.get("turns", [1])) == 1:
            return self.mix["prompt"]["max"] + self.mix["answer"]["max"]
        return int(self.mix["max_turn_tokens"])
