"""Continuous-batching serving engine.

Fixed-slot continuous batching: a batched decode step runs every tick;
slots hold independent requests at their own depths (vector positions).
Arriving prompts are prefetched (B=1 prefill) and their caches scattered
into a free slot; finished slots free immediately — no head-of-line
blocking on long generations.

The engine feeds the paper's monitoring infrastructure: every request is
a *task* with a cost clause (prompt_len + max_new_tokens), prefill
timings are aggregated per type, and the
:class:`~repro.serving.autoscale.AutoScaler` turns Algorithm 1 into a
replica/slot target Δ.

Its phases are spans (``_Span``): each is a ``jax.profiler``
annotation, on the device trace's clock, and, where the bus has a
``SPAN`` subscriber, a ``SPAN`` event.  An admission is ``engine.admit``
(arguments ``req``, ``queue_ms``, ``prompt``, ``bucket``) around
``engine.prefill``, ``engine.first_token`` and ``engine.scatter``; a
decode step is ``engine.decode`` (``live``) around
``engine.decode.dispatch``, ``engine.decode.readback`` and
``engine.decode.finish``.  Each of the two closes with a zero-length
``engine.counts`` child whose ``syncs`` counts the device→host reads it
made.

Slot positions and token budgets live on the host (``pos``,
``remaining``); the device gets a copy of the positions with each step.
So a decode step reads the device once, for its tokens, however many
slots are live, and an admission once, for its first token.
"""

from __future__ import annotations

import functools
import itertools
import time
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core.events import EventBus, EventKind, RuntimeEvent
from ..core.governor import GovernorSpec, ResourceGovernor
from ..core.monitoring import TaskMonitor
from ..models import ModelConfig, decode_step, init_cache, prefill
from .admission import AdmissionController
from .slo import SLOClass

__all__ = ["Request", "ServingEngine"]


@dataclass
class Request:
    prompt: list[int]
    max_new_tokens: int = 32
    eos_id: int | None = None
    #: service contract (deadline/priority/…); None = plain best-effort
    #: FIFO request, byte-identical to the pre-SLO engine
    slo: SLOClass | None = None
    #: assigned by the engine at submit (ids are *per engine* — two
    #: engines in one process no longer interleave a global counter)
    request_id: int | None = None
    # -- filled by the engine ------------------------------------------
    output: list[int] = field(default_factory=list)
    submitted_at: float = 0.0
    #: when the engine took it off the queue to prefill it
    admitted_at: float | None = None
    done_at: float | None = None

    @property
    def cost(self) -> float:
        return float(len(self.prompt) + self.max_new_tokens)

    @property
    def type_name(self) -> str:
        return f"request:{self.slo.name}" if self.slo else "request"

    @property
    def priority(self) -> int:
        return self.slo.priority if self.slo else 0

    @property
    def done(self) -> bool:
        return self.done_at is not None


def _scatter_cache(dst: dict, src: dict, slot: int) -> dict:
    """Insert the B=1 cache ``src`` into batch slot ``slot`` of ``dst``.

    Stacked block caches carry batch at axis 1, remainder caches at 0.
    """
    def ins(axis):
        def f(d, s):
            idx = [0] * d.ndim
            idx[axis] = slot
            return jax.lax.dynamic_update_slice(d, s.astype(d.dtype),
                                                tuple(idx))
        return f

    return {
        "blocks": jax.tree.map(ins(1), dst["blocks"], src["blocks"]),
        "rest": jax.tree.map(ins(0), dst["rest"], src["rest"]),
    }


def _named(fn: functools.partial) -> functools.partial:
    """``fn`` under its function's name, so that ``jax.jit`` names the
    program after it (``jit_decode_step``; a bare partial compiles as
    ``jit__unknown``) and the device trace can tell the programs apart."""
    fn.__name__ = fn.func.__name__
    return fn


class _Span:
    """One phase of engine work, written to two sinks: a
    ``jax.profiler.TraceAnnotation`` (its arguments become the event's
    stats), and, only where some subscriber wants ``SPAN`` events, a
    ``RuntimeEvent`` on the engine's bus and clock (``time`` its end,
    ``elapsed`` its length, ``data`` its arguments and its parent's
    name)."""

    __slots__ = ("engine", "name", "task_id", "args", "ann", "t0",
                 "parent")

    def __init__(self, engine: "ServingEngine", name: str,
                 task_id: int | None = None, **args) -> None:
        self.engine, self.name, self.task_id = engine, name, task_id
        self.args = args
        self.ann = jax.profiler.TraceAnnotation(name, **args)
        self.t0: float | None = None

    def __enter__(self) -> "_Span":
        self.ann.__enter__()
        eng = self.engine
        if eng.bus.interested(EventKind.SPAN):
            self.parent = eng._open_spans[-1] if eng._open_spans else None
            eng._open_spans.append(self.name)
            self.t0 = eng._clock()
        return self

    def __exit__(self, *exc) -> None:
        if self.t0 is not None:
            eng = self.engine
            end = eng._clock()
            eng._open_spans.pop()
            eng.bus.publish(RuntimeEvent(
                kind=EventKind.SPAN, time=end, task_id=self.task_id,
                type_name=self.name, elapsed=end - self.t0,
                data={**self.args, "parent": self.parent}))
        self.ann.__exit__(*exc)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 max_len: int = 256, monitor: TaskMonitor | None = None,
                 governor: ResourceGovernor | None = None,
                 bus: EventBus | None = None,
                 clock: Callable[[], float] | None = None,
                 admission: AdmissionController | None = None,
                 brownout_tokens: int | None = None) -> None:
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        # Injected time source (tests/sims pass virtual clocks; the
        # default is the wall clock, referenced — never called — here).
        self._clock = clock if clock is not None else time.perf_counter
        # Overload protection (both default off = pre-SLO behaviour):
        # an AdmissionController sheds at submit; ``brownout_tokens``,
        # when set, truncates best-effort generations at admit time.
        self.admission = admission
        self.brownout_tokens = brownout_tokens
        #: requests refused by admission control (terminal; not queued)
        self.shed: list[Request] = []
        # Per-engine id stream for requests (was a module global, which
        # interleaved ids across engines and made single-engine traces
        # depend on process history).
        self._ids = itertools.count()
        # The engine is the workload side of the paper's loop: it
        # publishes request lifecycle events on ``self.bus``; the monitor
        # (owned by a governor — either one passed in and shared with an
        # AutoScaler, or a minimal monitoring-only stack assembled here)
        # subscribes, and so can a TraceRecorder for record/replay.
        self.bus = bus if bus is not None else EventBus()
        if governor is None:
            governor = ResourceGovernor(
                GovernorSpec(resources=max_batch, monitoring=True),
                monitor=monitor, bus=self.bus)
        elif monitor is not None and governor.monitor is not monitor:
            raise ValueError(
                "conflicting monitor and governor arguments: the engine "
                "feeds events to governor.monitor, so pass one or the "
                "other (or a governor built over that monitor)")
        if governor.bus is None:
            # Pull-style governors carry no worker manager, so adopting
            # the engine's bus late only affects where PREDICTION
            # samples are published — serving traces then show the
            # autoscaler's Δ decisions like every other frontend.
            governor.bus = self.bus
        self.governor = governor
        if governor.monitor is None:
            raise ValueError(
                "ServingEngine needs a monitoring governor — build it "
                "from a GovernorSpec with monitoring=True")
        self.monitor = governor.monitor
        self.monitor.subscribe(self.bus)
        self.queue: list[Request] = []
        self.active: list[Request | None] = [None] * max_batch
        self.cache = init_cache(cfg, max_batch, max_len)
        self.tokens = jnp.zeros((max_batch,), jnp.int32)
        # the only copy of the slot positions; each step gets its own
        # snapshot, so this one can change in place
        self.pos = np.zeros((max_batch,), np.int32)
        self.remaining = np.zeros((max_batch,), np.int64)
        # the step writes its new K/V into the cache it is given: donated,
        # the cache is updated in place and never copied
        self._decode = jax.jit(_named(functools.partial(decode_step,
                                                        cfg=cfg)),
                               donate_argnums=(3,))
        # Prompt-length bucketing avoids a recompile per length.  Right-
        # padding is safe for attention archs (pad slots sit after `pos`
        # and are causally invisible); recurrent states would absorb the
        # padding, so those archs prefill at exact length.
        from ..models.config import LayerKind
        self._bucketing = all(k in (LayerKind.ATTN, LayerKind.MOE)
                              for k in cfg.pattern)
        self._prefill = jax.jit(_named(functools.partial(
            prefill, cfg=cfg, max_len=max_len,
            return_all_logits=self._bucketing)))
        self.ticks = 0
        self.tokens_out = 0
        #: device→host reads so far: an admission's first token and a
        #: tick's tokens (positions are on the host)
        self.host_syncs = 0
        # names of the spans open on the bus, innermost last
        self._open_spans: list[str] = []

    # -- request lifecycle ---------------------------------------------------

    def _publish(self, kind: EventKind, task_id: int, type_name: str,
                 cost: float, elapsed: float | None = None,
                 data: dict | None = None) -> None:
        self.bus.publish(RuntimeEvent(
            kind=kind, time=self._clock(), task_id=task_id,
            type_name=type_name, cost=cost, elapsed=elapsed,
            data=data or {}))

    def _counts(self, task_id: int | None, syncs_before: int) -> None:
        """The zero-length last child of an admission or decode span,
        carrying the device→host reads made since ``syncs_before``."""
        with _Span(self, "engine.counts", task_id,
                   syncs=self.host_syncs - syncs_before):
            pass

    def _prefill_len(self, prompt_len: int) -> int:
        """Positions a prompt is prefilled at: its power-of-two bucket
        (at least 16) where bucketing applies, else its exact length."""
        if self._bucketing:
            return max(16, 1 << (prompt_len - 1).bit_length())
        return prompt_len

    def submit(self, req: Request) -> Request:
        n, padded = len(req.prompt), self._prefill_len(len(req.prompt))
        if n >= self.max_len or padded > self.max_len:
            # prefill would write past the cache (its ring branch keeps
            # the last max_len positions, mostly padding), or the first
            # decode would wrap onto position 0.
            raise ValueError(
                f"prompt of {n} tokens (prefilled at {padded} positions) "
                f"does not fit the engine's max_len={self.max_len}")
        if req.request_id is None:
            req.request_id = next(self._ids)
        req.submitted_at = self._clock()
        browned = False
        if (self.brownout_tokens is not None and req.slo is not None
                and req.slo.best_effort
                and req.max_new_tokens > self.brownout_tokens):
            # Brownout: truncate best-effort generations instead of
            # shedding them (graceful degradation under a cap).  Applied
            # before any event so the monitor accounts the served cost.
            req.max_new_tokens = self.brownout_tokens
            browned = True
        self._publish(EventKind.TASK_SUBMITTED, req.request_id,
                      req.type_name, req.cost)
        if browned:
            self._publish(EventKind.DEGRADE, req.request_id,
                          req.type_name, req.cost,
                          data={"mode": "brownout"})
        self._publish(EventKind.TASK_READY, req.request_id,
                      req.type_name, req.cost)
        if self.admission is not None:
            reason = self.admission.shed_reason(
                now=req.submitted_at, queue_depth=len(self.queue),
                slo=req.slo, submitted_at=req.submitted_at,
                est_wait_s=self._est_wait_s(),
                est_service_s=self._est_service_s(req))
            if reason is not None:
                # Monitor saw the READY above (bus-subscribed); reverse
                # it so shed work stops inflating Δ.
                self.monitor.on_task_shed(req.request_id, req.type_name,
                                          req.cost)
                req.done_at = req.submitted_at
                self.shed.append(req)
                self._publish(EventKind.SHED, req.request_id,
                              req.type_name, req.cost,
                              data={"reason": reason})
                return req
        self.queue.append(req)
        return req

    def _est_service_s(self, req: Request) -> float:
        """Predicted service seconds for ``req`` (0 while α is cold)."""
        alpha = self.monitor.unitary_cost(req.type_name)
        return req.cost * alpha if alpha is not None else 0.0

    def _est_wait_s(self) -> float:
        """Predicted queue wait: outstanding queued work over the batch
        width (0 while the α estimates are cold)."""
        total = 0.0
        for r in self.queue:
            alpha = self.monitor.unitary_cost(r.type_name)
            if alpha is not None:
                total += r.cost * alpha
        return total / max(1, self.max_batch)

    def _pop_next(self) -> Request:
        """Highest-priority queued request; FIFO within a priority
        class (all-default priorities reduce to plain ``pop(0)``)."""
        best = 0
        best_pri = self.queue[0].priority
        for i in range(1, len(self.queue)):
            pri = self.queue[i].priority
            if pri > best_pri:
                best, best_pri = i, pri
        return self.queue.pop(best)

    def _admit(self) -> None:
        for slot in range(self.max_batch):
            if self.active[slot] is not None or not self.queue:
                continue
            req = self._pop_next()
            self._publish(EventKind.TASK_EXECUTE, req.request_id,
                          req.type_name, req.cost)
            t0 = req.admitted_at = self._clock()
            n = len(req.prompt)
            bucket = self._prefill_len(n)
            rid, syncs = req.request_id, self.host_syncs
            with _Span(self, "engine.admit", rid, req=rid,
                       queue_ms=(t0 - req.submitted_at) * 1e3, prompt=n,
                       bucket=bucket):
                with _Span(self, "engine.prefill", rid):
                    prompt = jnp.asarray([req.prompt + [0] * (bucket - n)],
                                         jnp.int32)
                    logits, cache1 = self._prefill(self.params, prompt)
                    if self._bucketing:
                        logits = logits[:, n - 1]
                with _Span(self, "engine.first_token", rid):
                    self.host_syncs += 1
                    first = int(jnp.argmax(logits[0, :self.cfg.vocab]))
                with _Span(self, "engine.scatter", rid):
                    self.cache = _scatter_cache(self.cache, cache1, slot)
                    self.tokens = self.tokens.at[slot].set(first)
                    self.pos[slot] = n
                self.active[slot] = req
                req.output.append(first)
                self.tokens_out += 1
                self.remaining[slot] = req.max_new_tokens - 1
                self._counts(rid, syncs)
            elapsed = self._clock() - t0
            self._publish(EventKind.TASK_COMPLETED, rid * 2 + 1,
                          "prefill", float(n), elapsed)

    # -- decode tick ------------------------------------------------------------

    def tick(self) -> int:
        """Admit + one batched decode step.  Returns #active slots."""
        self._admit()
        live = [s for s, r in enumerate(self.active) if r is not None]
        if not live:
            return 0
        syncs = self.host_syncs
        with _Span(self, "engine.decode", live=len(live)):
            with _Span(self, "engine.decode.dispatch"):
                logits, self.cache = self._decode(self.params, self.tokens,
                                                  self.pos.copy(), self.cache)
                nxt = jnp.argmax(logits[:, :self.cfg.vocab], axis=-1) \
                    .astype(jnp.int32)
                self.tokens = nxt
                self.pos += 1
            self.ticks += 1
            with _Span(self, "engine.decode.readback"):
                self.host_syncs += 1
                nxt_host = np.asarray(nxt)
            with _Span(self, "engine.decode.finish"):
                for s in live:
                    req = self.active[s]
                    assert req is not None
                    tok = int(nxt_host[s])
                    req.output.append(tok)
                    self.tokens_out += 1
                    self.remaining[s] -= 1
                    done = (self.remaining[s] <= 0
                            or self.pos[s] >= self.max_len - 1
                            or (req.eos_id is not None
                                and tok == req.eos_id))
                    if done:
                        req.done_at = self._clock()
                        self._publish(EventKind.TASK_COMPLETED,
                                      req.request_id, req.type_name,
                                      req.cost,
                                      req.done_at - req.submitted_at)
                        self.active[s] = None
            self._counts(None, syncs)
        return len(live)

    def run_until_drained(self, max_ticks: int = 100_000) -> None:
        for _ in range(max_ticks):
            if not self.queue and all(r is None for r in self.active):
                return
            self.tick()
        now = self._clock()
        live = [r for r in self.active if r is not None]
        oldest = min((r.submitted_at for r in self.queue + live),
                     default=now)
        raise RuntimeError(
            f"engine did not drain after {max_ticks} ticks: "
            f"{len(self.queue)} queued, {len(live)} active slots, "
            f"oldest request age {now - oldest:.3f}s")

    # -- autoscaler inputs ---------------------------------------------------------

    @property
    def load(self) -> int:
        return len(self.queue) + sum(r is not None for r in self.active)
