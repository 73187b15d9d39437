"""Reduction of a profiler trace to the benchmark's device numbers.

``events(path)`` reads an ``.xplane.pb`` into plain records; ``summarize``
turns records into busy time, kernel time and the breakdown, so the same
arithmetic runs on a fresh trace and on a recorded one in the tests.

A record is ``[plane, line, name, start_ns, dur_ns]``.  Kept are the device
planes' op lines (``/device:TPU:<i>``, line ``XLA Ops``) and the host's
``bench.*`` spans, which the harness writes around the calls into each
layer (``bench.window`` bounds the measured window).
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
# host spans from the innermost out: an idle gap is charged to the innermost
# one running at its midpoint
NESTING = ("bench.launch", "bench.scan_stored", "bench.scan", "bench.query")
OUTSIDE = "no_query"


def events(path: str) -> List[list]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name != OP_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    out.append([plane.name, line.name, ev.name,
                                float(ev.start_ns), float(ev.duration_ns)])
    return out


def _merge(iv: np.ndarray) -> np.ndarray:
    """Union of ``[start, end)`` intervals as sorted disjoint rows."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]
    return np.stack([starts, ends[last]], axis=1)


def _clip(iv: np.ndarray, a: float, b: float) -> np.ndarray:
    iv = np.clip(iv, a, b)
    return iv[iv[:, 1] > iv[:, 0]]


def _covers(merged: np.ndarray, t: np.ndarray) -> np.ndarray:
    if not len(merged):
        return np.zeros(len(t), bool)
    i = np.searchsorted(merged[:, 0], t, "right") - 1
    ok = i >= 0
    return ok & (merged[np.maximum(i, 0), 1] > t)


def window(records: Sequence[list]) -> Optional[tuple]:
    """``(start_ns, end_ns)`` of the ``bench.window`` span, or None."""
    win = [r for r in records if r[2] == SPAN_PREFIX + "window"]
    if not win:
        return None
    return min(r[3] for r in win), max(r[3] + r[4] for r in win)


def device_ops(records: Sequence[list], bounds) -> Dict[str, List[list]]:
    """Device op records that start inside ``bounds``, per device plane."""
    a, b = bounds
    out: Dict[str, List[list]] = defaultdict(list)
    for r in records:
        if DEVICE_PLANE.match(r[0]) and a <= r[3] < b:
            out[r[0]].append(r)
    return dict(out)


def kernel_time(records: Sequence[list], pattern: str):
    """``(seconds, events)`` of the device ops whose name matches
    ``pattern`` inside the window, summed over devices."""
    bounds = window(records)
    if bounds is None:
        return 0.0, 0
    pat = re.compile(pattern)
    hits = [r[4] for ops in device_ops(records, bounds).values()
            for r in ops if pat.search(r[2])]
    return float(sum(hits)) * 1e-9, len(hits)


def summarize(records: Sequence[list]) -> Optional[Dict]:
    """Busy and window seconds (averaged over the devices traced) and the
    breakdown, or None when the trace holds no window or no device op."""
    bounds = window(records)
    if bounds is None:
        return None
    per_plane = device_ops(records, bounds)
    if not per_plane:
        return None
    a, b = bounds
    n_dev = len(per_plane)
    busy = []
    by_op: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    spans = {name: _merge(np.array([[r[3], r[3] + r[4]] for r in records
                                    if r[2] == name]).reshape(-1, 2))
             for name in NESTING}
    for ops in per_plane.values():
        for r in ops:
            by_op[r[2]] += r[4] * 1e-9 / n_dev
        iv = _merge(_clip(np.array([[r[3], r[3] + r[4]] for r in ops])
                          .reshape(-1, 2), a, b))
        busy.append(float((iv[:, 1] - iv[:, 0]).sum()) * 1e-9)
        edges = np.r_[a, iv.ravel(), b].reshape(-1, 2)
        edges = edges[edges[:, 1] > edges[:, 0]]
        mid = edges.mean(axis=1)
        owner = np.full(len(mid), OUTSIDE, dtype=object)
        for name in reversed(NESTING):
            owner[_covers(spans[name], mid)] = name[len(SPAN_PREFIX):]
        for o, g in zip(owner, (edges[:, 1] - edges[:, 0]) * 1e-9):
            gaps[o] += g / n_dev

    def top(d):
        return [[k, float(v)] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"busy_s": float(np.mean(busy)), "window_s": (b - a) * 1e-9,
            "breakdown": {"device_ops": top(by_op), "idle_gaps": top(gaps)}}
