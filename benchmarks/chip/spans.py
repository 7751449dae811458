"""The serving engine's own spans, read from a profiler trace.

``ServingEngine`` writes each phase of its work as a
``jax.profiler.TraceAnnotation`` named ``engine.*``: an admission is
``engine.admit`` around ``engine.prefill``, ``engine.first_token`` and
``engine.scatter``; a decode step is ``engine.decode`` around
``engine.decode.dispatch``, ``engine.decode.readback`` and
``engine.decode.finish``.  A span's arguments arrive as its event's
stats; a count known only at a span's end arrives on its last child, a
zero-length ``engine.counts``.

:func:`read` takes, from one ``.xplane.pb``, the stretch that
``trace.reduce`` measures (the driver's spans that begin within
``seconds`` of its first one) and returns:

- every engine span in it, with its arguments, the stats of its
  ``engine.counts`` child folded in, and the device-idle time inside it;
- the device-idle self time of each span name, the driver's spans
  around the engine's included: idle time goes to the innermost span
  open at the time, and idle time in no span to ``trace.OUTSIDE``;
- the device time of each execution of each program, by the program's
  name without its fingerprint (``jit_decode_step``), from the "XLA
  Modules" line.

Device lines are read only as far as the stretch's end: their events
come in time order, and a traced run holds minutes more.
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from .trace import MODULES_LINE, OPS_LINE, OUTSIDE, SPANS, TOP, \
    _busy_before, _merge, load

__all__ = ["PREFIX", "COUNTS", "EngineSpan", "EngineTrace", "read",
           "reduce"]

PREFIX = "engine."
COUNTS = "engine.counts"


@dataclass
class EngineSpan:
    name: str
    #: seconds after the stretch opened
    start: float
    end: float
    #: the annotation's arguments, and its ``engine.counts`` child's
    args: dict = field(default_factory=dict)
    #: device-idle seconds inside the span, averaged over the devices;
    #: None where the trace holds no device
    idle: float | None = None


@dataclass
class EngineTrace:
    spans: list[EngineSpan]
    #: device-idle self seconds by span name (driver spans included);
    #: empty where the trace holds no device
    idle_self: dict[str, float]
    #: device seconds of each execution, by program name
    programs: dict[str, list[float]]

    def named(self, name: str) -> list[EngineSpan]:
        return [s for s in self.spans if s.name == name]

    def top_idle(self) -> list:
        """The spans with most device-idle self time, as ``idle_gaps``
        lists them."""
        return [[k, v] for k, v in sorted(self.idle_self.items(),
                                          key=lambda kv: -kv[1])[:TOP]]


def _program(name: str) -> str:
    """``jit_decode_step(8213…)`` → ``jit_decode_step``."""
    return name.split("(", 1)[0]


def read(src, seconds: float | None = None) -> EngineTrace | None:
    """The engine's spans in one trace: a file's path, or the file as
    ``trace.load`` parsed it; None where it holds no driver span."""
    pd = load(src) if isinstance(src, (str, Path)) else src
    host = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name in SPANS:
                    host.append((name, e.start_ns, e.start_ns + e.duration_ns,
                                 {}))
                elif name.startswith(PREFIX):
                    host.append((name, e.start_ns, e.start_ns + e.duration_ns,
                                 dict(e.stats)))
    lo, hi = _stretch(host, seconds)
    if lo is None:
        return None
    devices, programs = {}, defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        ops = []
        for e in lines[OPS_LINE].events:
            if e.start_ns >= hi:
                break
            ops.append((e.start_ns, e.start_ns + e.duration_ns))
        devices[plane.name] = ops
        for e in lines[MODULES_LINE].events if MODULES_LINE in lines else ():
            if e.start_ns >= hi:
                break
            if e.start_ns >= lo:
                programs[_program(e.name)].append(e.duration_ns / 1e9)
    return reduce(devices, host, seconds, dict(programs))


def _stretch(host: list, seconds: float | None) -> tuple:
    """``(lo, hi)`` in ns of the driver spans that make the stretch, as
    ``trace.reduce`` takes them; ``(None, None)`` without one."""
    driver = [(s, e) for n, s, e, _ in host if n in SPANS]
    if not driver:
        return None, None
    lo = min(s for s, _ in driver)
    if seconds is not None:
        driver = [(s, e) for s, e in driver if s < lo + seconds * 1e9]
    return lo, max(e for _, e in driver)


def reduce(devices: dict, host: list, seconds: float | None = None,
           programs: dict | None = None) -> EngineTrace | None:
    """Engine spans and idle self time from device operations
    ``{plane: [(start_ns, end_ns), ...]}`` and host spans ``[(name,
    start_ns, end_ns, stats), ...]``, driver and engine alike, over the
    stretch; None without a driver span."""
    lo, hi = _stretch(host, seconds)
    if lo is None:
        return None
    kept = sorted((h for h in host if lo <= h[1] < hi),
                  key=lambda h: (h[1], -h[2]))
    # sorted by start, a span lies in the innermost open one [start, end)
    # that holds its start and ends no earlier
    spans, parent, stack = [], [], []
    for name, s, e, stats in kept:
        while stack and (s >= spans[stack[-1]][2] or e > spans[stack[-1]][2]):
            stack.pop()
        if name == COUNTS:
            if stack:
                spans[stack[-1]][3].update(stats)
            continue
        parent.append(stack[-1] if stack else None)
        stack.append(len(spans))
        spans.append((name, s, e, dict(stats)))
    if not devices:
        idle_self, idle = {}, [None] * len(spans)
    else:
        idle_self, idle = _idle(devices, spans, parent, lo, hi)
    return EngineTrace(
        spans=[EngineSpan(name, (s - lo) / 1e9, (e - lo) / 1e9, stats, v)
               for (name, s, e, stats), v in zip(spans, idle)
               if name.startswith(PREFIX)],
        idle_self=idle_self, programs=programs or {})


def _idle(devices: dict, spans: list, parent: list, lo: float, hi: float
          ) -> tuple[dict, list]:
    """Device-idle self seconds by span name, and the idle seconds inside
    each span, averaged over the devices."""
    iv = np.array([[s, e] for _, s, e, _ in spans], float).reshape(-1, 2)
    idle = np.zeros(len(spans))
    busy = 0.0
    for ops in devices.values():
        ops = np.clip(np.array(ops, float).reshape(-1, 2), lo, hi)
        merged = _merge(ops[ops[:, 1] > ops[:, 0]])
        busy += float((merged[:, 1] - merged[:, 0]).sum())
        idle += (iv[:, 1] - iv[:, 0]) - (_busy_before(merged, iv[:, 1])
                                         - _busy_before(merged, iv[:, 0]))
    idle /= len(devices)
    own = idle.copy()
    outside = (hi - lo) - busy / len(devices)
    for i, p in enumerate(parent):
        if p is None:
            outside -= idle[i]
        else:
            own[p] -= idle[i]
    idle_self = defaultdict(float)
    for (name, _, _, _), v in zip(spans, own):
        idle_self[name] += v / 1e9
    idle_self[OUTSIDE] += outside / 1e9
    return dict(idle_self), [float(v) / 1e9 for v in idle]
