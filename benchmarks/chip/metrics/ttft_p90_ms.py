"""90th percentile of time to first token, from each request's due time,
over every request due in the window."""

from benchmarks.chip.record import pct


def read(rec):
    v = pct(rec.ttfts(), 90)
    return None if v is None else v * 1e3
