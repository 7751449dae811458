"""Mean device→host reads of a decode tick: the ``syncs`` that the
engine's ``engine.counts`` gives each ``engine.decode`` span of the
traced stretch (``spans``).  None without the engine's spans."""

from benchmarks.chip.record import mean


def read(rec):
    if rec.engine is None:
        return None
    return mean([s.args["syncs"] for s in rec.engine.named("engine.decode")])
