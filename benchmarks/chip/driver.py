"""Open- and closed-loop drivers of the serving engine.

They follow the launcher's loop (``repro.launch.serve.serve``): the
autoscaler's ``target`` is asked for Δ before every ``ServingEngine.tick``.
In addition they submit each request between ticks once it is due, and
stamp every token with the time at which the tick that produced it
returned (``tick`` blocks on the host copy of the decoded tokens, and an
admission blocks on its first token).

The drivers import nothing of JAX: the clock, the sleep and the span
annotation are handed in, so tests can drive them against a fake engine
with a scripted clock.  Times are seconds after the window opens.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from typing import Callable

from repro.serving import Request

from .arrivals import Planned

__all__ = ["Served", "Span", "Run", "drain", "run_open", "run_closed"]


@dataclass
class Served:
    """What the client saw of one request."""

    plan: Planned
    due: float
    req: Request | None = None
    submit: float | None = None
    #: one time per token, when the tick that produced it returned
    stamps: list[float] = field(default_factory=list)

    @property
    def ttft(self) -> float | None:
        return self.stamps[0] - self.due if self.stamps else None


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class Run:
    """Everything a run's metrics are taken from."""

    seconds: float
    served: list[Served]
    #: requests due in the window (the ones measured)
    window: list[Served]
    spans: list[Span]
    #: time the drain ended (all window requests finished, or the limit)
    end: float = 0.0
    #: the clock's reading when the window opened
    t0: float = 0.0


def _null_annotate(name: str):
    return contextlib.nullcontext()


class _Loop:
    def __init__(self, engine, scaler, clock: Callable[[], float],
                 annotate=_null_annotate) -> None:
        self.engine, self.scaler = engine, scaler
        self.clock, self.annotate = clock, annotate
        self.t0 = clock()
        self.spans: list[Span] = []
        self.live: list[Served] = []

    def now(self) -> float:
        return self.clock() - self.t0

    def submit(self, s: Served) -> None:
        p = s.plan
        s.req = self.engine.submit(Request(prompt=p.prompt,
                                           max_new_tokens=p.max_new))
        s.submit = self.now()
        self.live.append(s)

    def step(self) -> None:
        """Δ, then one tick, then stamp the tokens it produced."""
        eng = self.engine
        t = self.now()
        with self.annotate("governor"):
            self.scaler.target(len(eng.queue),
                               sum(r is not None for r in eng.active))
        t1 = self.now()
        self.spans.append(Span("governor", t, t1))
        admit = bool(eng.queue) and any(r is None for r in eng.active)
        name = "tick.admit" if admit else "tick.decode"
        with self.annotate(name):
            eng.tick()
        t2 = self.now()
        self.spans.append(Span(name, t1, t2))
        with self.annotate("driver"):
            still = []
            for s in self.live:
                new = len(s.req.output) - len(s.stamps)
                s.stamps += [t2] * new
                if not s.req.done:
                    still.append(s)
            self.live = still
        self.spans.append(Span("driver", t2, self.now()))


def _fire(marks: list, now: float) -> None:
    while marks and marks[0][0] <= now:
        marks.pop(0)[1]()


def run_open(engine, scaler, plan: list[Planned], seconds: float, *,
             drain_s: float, clock: Callable[[], float],
             sleep: Callable[[float], None], annotate=_null_annotate,
             marks: list | None = None) -> Run:
    """Serve ``plan`` (due times ascending) as an open loop.

    Requests are submitted once due, whatever the engine is doing, and
    load keeps arriving after the window closes until every request due
    in the window has finished, or ``drain_s`` has passed.  Each of
    ``marks``, ``(time, fn)``, is called once, at the first step at or
    after its time, once the requests due by then have been submitted.
    """
    served = [Served(p, p.due) for p in plan]
    window = [s for s in served if s.due < seconds]
    loop = _Loop(engine, scaler, clock, annotate)
    marks = sorted(marks or [], key=lambda m: m[0])
    i = 0
    while True:
        now = loop.now()
        while i < len(served) and served[i].due <= now:
            loop.submit(served[i])
            i += 1
        _fire(marks, now)
        if now >= seconds:
            if all(s.req is not None and s.req.done for s in window) \
                    or now >= seconds + drain_s:
                break
        if engine.load:
            loop.step()
        elif i < len(served):
            sleep(max(0.0, served[i].due - now))
        else:
            break
    return Run(seconds, served, window, loop.spans, loop.now(), loop.t0)


def run_closed(engine, scaler, plan: list[Planned], seconds: float, *,
               clients: int, drain_s: float, clock: Callable[[], float],
               annotate=_null_annotate, marks: list | None = None) -> Run:
    """Serve ``plan`` as a closed loop of ``clients`` callers: each sends
    its next request as soon as its previous one has finished (its due
    time).  Requests sent in the window are measured; after the window no
    caller sends again, and the drain waits for the window's requests.
    ``marks`` as for :func:`run_open`."""
    served: list[Served] = []
    loop = _Loop(engine, scaler, clock, annotate)
    nxt = iter(plan)
    free = [(c, 0.0) for c in range(clients)]   # (caller, free since)
    mine: dict[int, Served] = {}
    marks = sorted(marks or [], key=lambda m: m[0])
    while True:
        now = loop.now()
        while free and now < seconds:
            p = next(nxt, None)
            if p is None:
                break
            c, since = free.pop(0)
            p.client = c
            s = Served(p, since)
            served.append(s)
            loop.submit(s)
            mine[c] = s
        _fire(marks, now)
        if now >= seconds and (all(s.req.done for s in served)
                               or now >= seconds + drain_s):
            break
        if not engine.load:
            break
        loop.step()
        for c, s in list(mine.items()):
            if s.req.done:
                free.append((c, s.stamps[-1]))
                del mine[c]
    window = [s for s in served if s.due < seconds]
    return Run(seconds, served, window, loop.spans, loop.now(), loop.t0)


def drain(engine, scaler, plan: list[Planned], *, clock: Callable[[], float],
          annotate=_null_annotate) -> list[Served]:
    """Submit all of ``plan`` at once and step until the engine is empty:
    the warm-up, through the same calls as the window."""
    loop = _Loop(engine, scaler, clock, annotate)
    served = [Served(p, 0.0) for p in plan]
    for s in served:
        loop.submit(s)
    while engine.load:
        loop.step()
    return served
