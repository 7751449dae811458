"""Mean time of ``ServingEngine.tick`` over the ticks in the window that
admitted at least one request: its prefills, scatters and the decode
step."""

from benchmarks.chip.record import mean


def read(rec):
    v = mean([s.end - s.start for s in rec.spans("tick.admit")])
    return None if v is None else v * 1e3
