"""Wall-clock load driver and the window arithmetic of the end-to-end
metrics.

The driver is the event logic of the repository's simulator
(``repro.sim.runner.Simulator``) with the host clock in place of virtual
time. A program's first turn is due at its arrival; a later turn is due
at the previous turn's last token plus its tool duration, and the driver
waits that time out for real. It passes the wall time to
``engine.submit``/``engine.step`` as ``now``, sleeps only while the
engine has no work, and stamps every event on its own clock after the
``step`` that produced it returns: admission (at the step's start),
each output token, each turn end. The engine's own finish and
first-token stamps are not used: they add the step's duration to the
``now`` they were given.

Open loop: programs arrive at fixed offsets. Closed loop: ``workers``
programs run at once, each worker starting its next program when the
last one ends (starts spread over ``stagger_s``).
"""
from __future__ import annotations

import dataclasses
import heapq
import math
import time
from typing import Callable

import numpy as np

from traffic_gen import Program


@dataclasses.dataclass
class TurnRecord:
    pid: str
    turn: int
    due: float
    prompt_len: int
    submitted: float = math.nan
    admitted: float = math.nan       # first admission (step start)
    tokens: list = dataclasses.field(default_factory=list)  # stamps
    end: float = math.nan            # last token of the turn
    rejected: bool = False
    req: object = None               # the engine's request


@dataclasses.dataclass
class StepRecord:
    start: float
    end: float
    exec_s: float                    # backend.execute wall (compile excl.)
    emitted: int                     # requests that got a token


class Driver:
    """Serves a schedule of programs through ``engine`` in real time.

    ``make_request(program, turn, due)`` builds the engine's request for
    a turn."""

    def __init__(self, engine, make_request: Callable, *,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep):
        self.engine = engine
        self.make_request = make_request
        self._clock = clock
        self._sleep = sleep
        self.t0 = clock()
        self._heap: list = []
        self._seq = 0
        self._queue: list[Program] = []     # closed loop: next programs
        self.program_due: dict[str, float] = {}
        self.program_end: dict[str, float] = {}
        self.turns: dict[tuple[str, int], TurnRecord] = {}
        self.steps: list[StepRecord] = []
        self.lateness: list[float] = []     # delivery minus due time
        self._live: dict[int, tuple] = {}   # request_id -> (req, record)
        self._programs: dict[str, Program] = {}

    def now(self) -> float:
        return self._clock() - self.t0

    # ------------------------------------------------------------ schedule
    def _push(self, due: float, prog: Program, turn: int) -> None:
        self._seq += 1
        heapq.heappush(self._heap, (due, self._seq, prog, turn))

    def open_loop(self, programs: list[Program], offsets, start: float
                  ) -> None:
        for p, off in zip(programs, offsets):
            self._start(p, start + float(off))

    def closed_loop(self, programs: list[Program], workers: int,
                    stagger_s: float, start: float) -> None:
        self._queue = list(programs[workers:])
        for w, p in enumerate(programs[:workers]):
            self._start(p, start + stagger_s * w / workers)

    def _start(self, prog: Program, due: float) -> None:
        self._programs[prog.pid] = prog
        self.program_due[prog.pid] = due
        self._push(due, prog, 0)

    # ----------------------------------------------------------------- run
    def run_until(self, t_stop: float) -> None:
        eng = self.engine
        while True:
            now = self.now()
            if now >= t_stop:
                return
            self._deliver(now)
            if not eng.has_work:
                nxt = self._heap[0][0] if self._heap else t_stop
                self._sleep(max(0.0, min(nxt, t_stop) - now))
                continue
            be = eng.backend
            n_exec = len(be.step_seconds)
            ev = eng.step(now)
            end = self.now()
            for r in ev.admitted:
                rec = self._live[r.request_id][1]
                if math.isnan(rec.admitted):
                    rec.admitted = now
            if ev.idle:
                continue
            emitted = 0
            for req, rec in self._live.values():
                new = req.generated - len(rec.tokens)
                if new > 0:
                    rec.tokens.extend([end] * new)
                    emitted += 1
            exec_s = be.step_seconds[-1] if len(be.step_seconds) > n_exec \
                else 0.0
            self.steps.append(StepRecord(now, end, exec_s, emitted))
            for r in ev.finished:
                self._finish(r, end)

    def _deliver(self, now: float) -> None:
        while self._heap and self._heap[0][0] <= now:
            due, _, prog, k = heapq.heappop(self._heap)
            req = self.make_request(prog, k, due)
            rec = TurnRecord(prog.pid, k, due, req.prompt_len, submitted=now,
                             req=req)
            self.turns[(prog.pid, k)] = rec
            self.lateness.append(now - due)
            rejected = self.engine.rejected
            self.engine.submit(req, now)
            if self.engine.rejected > rejected:
                rec.rejected = True
                continue
            self._live[req.request_id] = (req, rec)

    def _finish(self, req, end: float) -> None:
        _, rec = self._live.pop(req.request_id)
        rec.end = end
        prog = self._programs[rec.pid]
        t = prog.turns[rec.turn]
        if rec.turn + 1 < len(prog.turns):
            self._push(end + t.tool_s, prog, rec.turn + 1)
            return
        self.program_end[rec.pid] = end
        if self._queue:
            self._start(self._queue.pop(0), end)


# ------------------------------------------------------------- window math
def in_window(t: float, w0: float, w1: float) -> bool:
    return w0 <= t < w1


def p95(xs) -> float:
    """95th percentile (linear interpolation between order statistics)."""
    return float(np.percentile(np.asarray(xs, float), 95)) if len(xs) \
        else math.nan


def end_to_end(d: Driver, w0: float, w1: float) -> dict:
    """The end-to-end statistics of window [w0, w1): each over all the
    events that fall in it, with their counts."""
    jcts = [d.program_end[p] - d.program_due[p] for p in d.program_end
            if in_window(d.program_end[p], w0, w1)]
    ttfts, gaps, tokens = [], [], 0
    for rec in d.turns.values():
        ts = rec.tokens
        if ts and in_window(ts[0], w0, w1):
            ttfts.append(ts[0] - rec.due)
        for a, b in zip(ts, ts[1:]):
            if in_window(b, w0, w1):
                gaps.append(b - a)
        tokens += sum(1 for t in ts if in_window(t, w0, w1))
    return {
        "jct_mean_s": float(np.mean(jcts)) if jcts else math.nan,
        "ttft_p95_s": p95(ttfts),
        "itl_p95_s": p95(gaps),
        "output_tok_per_s": tokens / (w1 - w0),
        "n_programs": len(jcts), "n_turns": len(ttfts), "n_gaps": len(gaps),
        "n_tokens": tokens,
    }
