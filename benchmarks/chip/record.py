"""What a run leaves for the metric readers in ``metrics/``."""

from __future__ import annotations

from dataclasses import dataclass, field
from types import ModuleType

import numpy as np

from . import flops
from .driver import Run
from .spans import EngineTrace

__all__ = ["Record", "pct", "mean"]


@dataclass
class Record:
    """A run seen over its first ``seconds``: the whole window in an
    untraced run, the traced stretch in a traced one."""

    run: Run
    #: the configuration's ``model`` sizes
    model: dict
    setup_s: float
    device_kind: str
    seconds: float
    #: (time after the window opened, seconds) of each prefill the engine
    #: published on its bus (``TASK_COMPLETED`` of type ``prefill``)
    prefills: list = field(default_factory=list)
    #: ``trace.reduce`` of the traced run; None without a trace
    trace: dict | None = None
    #: the engine's spans and programs over the same stretch
    #: (``spans.read``); None without a trace
    engine: EngineTrace | None = None
    #: the configuration's counts module (``spec.Cell.counts``)
    counts: ModuleType = flops

    @property
    def window(self) -> list:
        """Requests due in the window."""
        return [s for s in self.run.served if s.due < self.seconds]

    def spans(self, name: str) -> list:
        """Spans of ``name`` that began inside the window."""
        return [s for s in self.run.spans
                if s.name == name and 0.0 <= s.start < self.seconds]

    def ttfts(self) -> list[float]:
        """Time to first token of every request due in the window, from
        its due time.  A request that got no token by the end of the
        drain counts with the time it had waited by then, a lower bound."""
        return [s.ttft if s.ttft is not None else self.run.end - s.due
                for s in self.window]

    def gaps(self) -> list[float]:
        """Gaps between consecutive tokens of each request due in the
        window, over its whole life."""
        out: list[float] = []
        for s in self.window:
            out += np.diff(s.stamps).tolist()
        return out


def pct(values, q: float) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None


def mean(values) -> float | None:
    return float(np.mean(values)) if len(values) else None
