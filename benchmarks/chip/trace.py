"""Reduction of a profiler trace to the device's busy time, its idle
time by what the host was doing, and the device operations that took
most time.

The stretch measured runs from the start of the first host span that the
driver wrote (``SPANS``, with ``jax.profiler.TraceAnnotation``) to the
end of the last.  Busy time is the union of the intervals in which an
operation ran on a device ("XLA Ops" lines of the ``/device:`` planes),
within the stretch, averaged over the devices.  Idle time inside each
host span is the span's length less the busy time within it; idle time
in no span is put under ``OUTSIDE``.  An operation is named by the
program it ran in ("XLA Modules" line: the jitted function and its
fingerprint) and its HLO name, e.g. ``jit__lambda(123):%while.15``.
"""

from __future__ import annotations

import gzip
from collections import defaultdict
from pathlib import Path

import numpy as np

__all__ = ["SPANS", "OUTSIDE", "read_xplane", "reduce", "newest_xplane"]

SPANS = ("governor", "tick.admit", "tick.decode", "driver")
OUTSIDE = "outside driver spans"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def newest_xplane(log_dir: Path) -> Path:
    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def read_xplane(path: Path) -> tuple[dict, list]:
    """Device operations ``{plane: [(start_ns, end_ns, name), ...]}`` and
    host spans ``[(name, start_ns, end_ns), ...]`` of one trace file
    (``.xplane.pb``, or the same compressed, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.suffix == ".gz":
        pd = ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    devices: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            ops = [(e.start_ns, e.start_ns + e.duration_ns,
                    e.name.split(" = ")[0]) for e in lines[OPS_LINE].events]
            mods = sorted((e.start_ns, e.name) for e in
                          lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            starts = np.array([m[0] for m in mods])
            k = np.searchsorted(starts, [s for s, _, _ in ops], "right") - 1
            devices[plane.name] = [
                (s, e, f"{mods[j][1]}:{n}" if j >= 0 else n)
                for (s, e, n), j in zip(ops, k)]
        elif plane.name.startswith("/host:"):
            host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for line in plane.lines for e in line.events
                     if e.name in SPANS]
    return devices, host


def _merge(iv: np.ndarray) -> np.ndarray:
    """Union of intervals (n, 2) as disjoint sorted intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(iv) - 1)
    return np.stack([iv[idx, 0], ends[last]], axis=1)


def _busy_before(merged: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Busy time in (-inf, t] for each t, from disjoint sorted intervals."""
    if len(merged) == 0:
        return np.zeros_like(t, dtype=float)
    lens = merged[:, 1] - merged[:, 0]
    cum = np.concatenate([[0.0], np.cumsum(lens)])
    k = np.searchsorted(merged[:, 0], t, side="right")   # intervals begun
    part = np.where(k > 0, np.clip(t - merged[np.maximum(k - 1, 0), 0], 0,
                                   lens[np.maximum(k - 1, 0)]), 0.0)
    return cum[np.maximum(k - 1, 0)] * (k > 0) + part


def reduce(devices: dict, host: list, seconds: float | None = None
           ) -> dict | None:
    """Busy and window seconds, idle share, and the breakdown lists; None
    where the trace holds no device operation or no host span.  With
    ``seconds``, only the host spans that begin within that many seconds
    of the first one make the stretch."""
    if seconds is not None and host:
        t0 = min(s for _, s, _ in host)
        host = [h for h in host if h[1] < t0 + seconds * 1e9]
    if not devices or not host:
        return None
    hs = np.array([[s, e] for _, s, e in host], float)
    lo, hi = hs[:, 0].min(), hs[:, 1].max()
    window_ns = hi - lo
    busy, idle = [], defaultdict(float)
    ops = defaultdict(float)
    for plane_ops in devices.values():
        iv = np.array([[s, e] for s, e, _ in plane_ops], float)
        iv = np.clip(iv, lo, hi)
        merged = _merge(iv[iv[:, 1] > iv[:, 0]])
        b = float((merged[:, 1] - merged[:, 0]).sum())
        busy.append(b)
        bb = _busy_before(merged, hs[:, 1]) - _busy_before(merged, hs[:, 0])
        in_spans = 0.0
        for (name, s, e), bin_ in zip(host, bb):
            idle[name] += (e - s) - bin_
            in_spans += (e - s) - bin_
        idle[OUTSIDE] += (window_ns - b) - in_spans
        for s, e, name in plane_ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                ops[name] += d
    n = len(devices)
    busy_s = sum(busy) / n / 1e9
    window_s = float(window_ns) / 1e9

    def top(d):
        return [[k, float(v) / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "device_ops": top(ops), "idle_gaps": top(idle)}
