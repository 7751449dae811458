"""90th percentile of how long a request waited in the engine's queue,
over the requests admitted in the window: ``Request.admitted_at`` less
``Request.submitted_at``, both on the engine's clock (the ``queue_ms``
of the ``engine.admit`` span).  None where the engine stamps no
admission."""

from benchmarks.chip.record import pct


def read(rec):
    waits = []
    for s in rec.run.served:
        at = getattr(s.req, "admitted_at", None)
        if at is not None and 0.0 <= at - rec.run.t0 < rec.seconds:
            waits.append(at - s.req.submitted_at)
    v = pct(waits, 90)
    return None if v is None else v * 1e3
