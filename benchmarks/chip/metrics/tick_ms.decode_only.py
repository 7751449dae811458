"""Mean time of ``ServingEngine.tick`` over the ticks in the window that
admitted no request: one decode step with its host work."""

from benchmarks.chip.record import mean


def read(rec):
    v = mean([s.end - s.start for s in rec.spans("tick.decode")])
    return None if v is None else v * 1e3
