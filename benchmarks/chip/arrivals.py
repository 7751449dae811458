"""Traffic generation: one general generator over the mix files in ``traffic/``.

A mix file is JSON data only.  Its keys:

* ``loop``: ``"open"`` (requests are due on a schedule, whatever the
  server does) or ``"closed"`` (``clients`` callers, each sending its next
  request when the previous one has finished);
* ``arrivals`` (open loop): ``{"kind": "poisson", "rate": r}`` or
  ``{"kind": "burst", "burst_size": n, "spacing": s, "gap": g,
  "jitter": j}``, rates in requests per second;
* ``clients`` (closed loop): how many callers;
* ``prompt_len`` and ``output_len``: ``{"dist": "lognormal", "median": m,
  "sigma": s, "min": a, "max": b}`` or ``{"dist": "uniform", "min": a,
  "max": b}``, in tokens.

Every seed gets the same arrival times and the same sizes, drawn as
evenly spaced quantiles of the stated distributions and dealt out in an
order fixed for every run: each burst gets the same share of the
quantiles (every k-th in sorted order), and a Poisson stream the same
sequence.  The seed draws the token ids (and the benchmark's weights),
and shuffles the sizes only among requests that arrive together, inside
a burst.  So the work of a window, and where in it each piece falls, is
the same for every seed, and the spread between runs is the system's,
not the generator's.  The arrival shapes follow
``repro.workloads.arrivals`` (``PoissonArrivals``, ``BurstArrivals``),
copied here so that a change to the program cannot move the yardstick.
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

__all__ = ["DRAIN_S", "Planned", "length_quantiles", "longest",
           "mean_rate", "plan_closed", "plan_open", "prefill_bucket",
           "seed_rng", "stratified"]

#: the first requests after the window keep the load on while the
#: window's last requests finish; this many seconds of them are planned
DRAIN_S = 60.0
#: the arrival times, and which sizes each stratum gets, are drawn once,
#: from this seed, for every run
ARRIVAL_SEED = 0


@dataclass
class Planned:
    """One request: when it is due (seconds after the window opens; None
    for a closed loop, where it is due when its caller is free), its
    prompt and how many tokens it asks for."""

    due: float | None
    prompt: list[int]
    max_new: int
    client: int = 0


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use (``stream``) of a seed of any size."""
    return np.random.default_rng([int(seed) & (2**64 - 1), stream])


def prefill_bucket(n: int) -> int:
    """Positions the engine prefills an ``n``-token prompt at: its
    power-of-two bucket, at least 16 (``ServingEngine._prefill_len``)."""
    return max(16, 1 << (n - 1).bit_length())


def length_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the evenly spaced quantiles (i + ½)/n of ``spec``,
    clipped to its ``min`` and ``max``, ascending."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(int)


def _gaps(arr: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps (the first one before the first request)."""
    kind = arr["kind"]
    if kind == "poisson":
        rate = float(arr["rate"])
        if rate <= 0:
            raise ValueError("poisson rate must be > 0")
        u = (np.arange(n) + 0.5) / n
        return rng.permutation(-np.log1p(-u) / rate)
    if kind == "burst":
        size, spacing = int(arr["burst_size"]), float(arr["spacing"])
        gap, jitter = float(arr["gap"]), float(arr.get("jitter", 0.0))
        if size < 1 or spacing < 0 or gap < 0 or not 0 <= jitter < 1:
            raise ValueError(f"bad burst arrivals {arr}")
        # one gap per burst, then size-1 spacings inside it; each is
        # scaled by a factor from [1 - j, 1 + j] at even quantiles
        n_b = -(-n // size)
        fg = rng.permutation(1 - jitter + 2 * jitter
                             * (np.arange(n_b) + 0.5) / n_b)
        fs = rng.permutation(1 - jitter + 2 * jitter
                             * (np.arange(n) + 0.5) / n)
        out = np.empty(n)
        for i in range(n):
            out[i] = gap * fg[i // size] if i % size == 0 else spacing * fs[i]
        return out
    raise ValueError(f"unknown arrival kind {kind!r}")


def mean_rate(arr: dict) -> float:
    """Requests per second that ``arr`` offers on average."""
    if arr["kind"] == "poisson":
        return float(arr["rate"])
    size = int(arr["burst_size"])
    return size / (float(arr["gap"]) + (size - 1) * float(arr["spacing"]))


def stratified(values: np.ndarray, size: int, deal: np.random.Generator,
               rng: np.random.Generator) -> np.ndarray:
    """``values`` in an order where each run of ``size`` consecutive
    places (the last may be shorter) holds an even share of them: the
    sorted values are dealt to the strata in turn, in an order that
    ``deal`` draws, and ``rng`` shuffles them inside each stratum."""
    n = len(values)
    bounds = list(range(0, n, size)) + [n]
    room = [b - a for a, b in zip(bounds, bounds[1:])]
    strata: list[list] = [[] for _ in room]
    turn = deal.permutation(len(room)).tolist()
    k = 0
    for v in np.sort(values):
        while len(strata[turn[k % len(turn)]]) >= room[turn[k % len(turn)]]:
            k += 1
        strata[turn[k % len(turn)]].append(v)
        k += 1
    return np.concatenate([rng.permutation(np.array(x)) for x in strata])


def _prompts(rng, lens, vocab):
    return [rng.integers(0, vocab, size=int(n)).tolist() for n in lens]


def plan_open(mix: dict, seed: int, seconds: float, vocab: int
              ) -> tuple[list[Planned], int]:
    """Requests of an open-loop mix, in due order: those due in the
    window, then ``DRAIN_S`` seconds more at the same load.  Returns them
    and how many are due in the window."""
    if mix["loop"] != "open":
        raise ValueError("plan_open needs an open-loop mix")
    arr = mix["arrivals"]
    rate = mean_rate(arr)
    out: list[Planned] = []
    n_window = 0
    # the window and the drain are planned as two blocks, each with its
    # own even set of sizes, so the window's work depends only on its
    # length.  A block's first request is due as it opens; its gaps are
    # scaled to average exactly 1/rate, so all of them fall inside it.
    # Bursts come whole: as many as fit the block at the rate.
    for block, (t_begin, length) in enumerate(((0.0, seconds),
                                                (seconds, DRAIN_S))):
        n = max(1, int(rate * length + 1e-9))
        if arr["kind"] == "burst":
            size = int(arr["burst_size"])
            n = max(1, int(rate * length / size + 1e-9)) * size
        fixed = seed_rng(ARRIVAL_SEED, 10 + block)
        g = _gaps(arr, n, fixed)
        due = t_begin + (np.cumsum(g) - g[0]) * (n / rate / g.sum())
        size = int(arr["burst_size"]) if arr["kind"] == "burst" else 1
        rng = seed_rng(seed, 10 + block)
        p = stratified(length_quantiles(mix["prompt_len"], n), size, fixed,
                       rng)
        o = stratified(length_quantiles(mix["output_len"], n), size, fixed,
                       rng)
        prompts = _prompts(rng, p, vocab)
        out += [Planned(float(due[i]), prompts[i], int(o[i]))
                for i in range(n)]
        if block == 0:
            n_window = n
    return out, n_window


def plan_closed(mix: dict, seed: int, n: int, vocab: int) -> list[Planned]:
    """``n`` requests for a closed loop, handed to callers in order."""
    if mix["loop"] != "closed":
        raise ValueError("plan_closed needs a closed-loop mix")
    fixed, rng = seed_rng(ARRIVAL_SEED, 20), seed_rng(seed, 20)
    p = stratified(length_quantiles(mix["prompt_len"], n), 1, fixed, rng)
    o = stratified(length_quantiles(mix["output_len"], n), 1, fixed, rng)
    prompts = _prompts(rng, p, vocab)
    return [Planned(None, prompts[i], int(o[i])) for i in range(n)]


def longest(mix: dict) -> int:
    """Most positions a request of ``mix`` can reach (prompt + output)."""
    return int(mix["prompt_len"]["max"]) + int(mix["output_len"]["max"])

