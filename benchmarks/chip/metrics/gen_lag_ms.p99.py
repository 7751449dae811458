"""99th percentile of how late the driver submitted a request: submit
time less due time, over the requests due in the window."""

from benchmarks.chip.record import pct


def read(rec):
    lags = [s.submit - s.due for s in rec.window if s.submit is not None]
    v = pct(lags, 99)
    return None if v is None else v * 1e3
