#!/usr/bin/env python3
"""One traced run of a cell with the program's own spans recorded.

  python3 bench/program_spans.py --workload sf1-clicks --seed 7 --seconds 50

Runs ``bench/run.py --trace 1`` with ``repro.core.trace`` recording over
the measured window and the profiler's ``predtrace.*`` host events kept,
then prints on its last stdout line: the harness's result, the span metrics
(``bench/metrics/{queue_p95_ms,walk_self_ms,launch_ms}.py``), each span
name's count and summed self time, the device's idle gaps charged to the
innermost program span covering each gap's midpoint, and the largest gap
between a root lineage span's duration and the self times under it.  The
harness itself reads no program span yet.
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import registry  # noqa: E402
from bench import run as harness  # noqa: E402
from bench import trace as btrace  # noqa: E402

SPAN_METRICS = ("queue_p95_ms", "walk_self_ms", "launch_ms")
OUTSIDE = "no_span"


def program_events(path: str, prefix: str):
    """Host events of the profiler trace whose name starts with ``prefix``,
    as ``bench/trace.py`` records."""
    from jax.profiler import ProfileData

    return [[plane.name, line.name, ev.name, float(ev.start_ns),
             float(ev.duration_ns)]
            for plane in ProfileData.from_file(path).planes
            if not btrace.DEVICE_PLANE.match(plane.name)
            for line in plane.lines for ev in line.events
            if ev.name.startswith(prefix)]


def idle_by_span(records, prefix: str):
    """Seconds of device idle time in the window charged to the innermost
    ``prefix`` span (the latest started) covering each gap's midpoint,
    averaged over the devices traced; None without a window or device op."""
    bounds = btrace.window(records)
    if bounds is None:
        return None
    per_plane = btrace.device_ops(records, bounds)
    if not per_plane:
        return None
    a, b = bounds
    spans = sorted((r[3], r[3] + r[4], r[2][len(prefix):]) for r in records
                   if r[2].startswith(prefix))
    out = defaultdict(float)
    for ops in per_plane.values():
        iv = btrace._merge(btrace._clip(
            np.array([[r[3], r[3] + r[4]] for r in ops]).reshape(-1, 2), a, b))
        edges = np.r_[a, iv.ravel(), b].reshape(-1, 2)
        edges = edges[edges[:, 1] > edges[:, 0]]
        mid = edges.mean(axis=1)  # ascending: the gaps are disjoint
        owner = np.full(len(mid), OUTSIDE, dtype=object)
        for s, e, name in spans:  # later starts paint over earlier ones
            lo, hi = np.searchsorted(mid, (s, e))
            owner[lo:hi] = name
        for o, g in zip(owner, (edges[:, 1] - edges[:, 0]) * 1e-9):
            out[o] += g / len(per_plane)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def lineage_root(s, by_id):
    """The outermost ``lineage.*`` span at or above ``s``, or None."""
    root = s if s.name.startswith("lineage.") else None
    while s.parent in by_id:
        s = by_id[s.parent]
        if s.name.startswith("lineage."):
            root = s
    return root


def root_error(spans, own):
    """Largest ``|sum of self times under a root lineage span - its
    duration|`` over that duration, across roots."""
    by_id = {s.id: s for s in spans}
    under = defaultdict(int)
    for s in spans:
        root = lineage_root(s, by_id)
        if root is not None:
            under[root.id] += own[s.id]
    errs = [abs(v - (by_id[k].end_ns - by_id[k].start_ns))
            / max(by_id[k].end_ns - by_id[k].start_ns, 1)
            for k, v in under.items()]
    return max(errs) if errs else None


def main(argv=None) -> int:
    from repro.core import trace as ptrace

    argv = list(sys.argv[1:] if argv is None else argv)
    spans, events = [], []
    drive, read_events = harness.drive, btrace.events

    def traced_drive(dep, cfg, mix, seed, seconds, cap, on_start, on_window):
        def start():
            on_start()
            ptrace.start()

        def stop():
            spans.extend(ptrace.stop())
            on_window()

        return drive(dep, cfg, mix, seed, seconds, cap, start, stop)

    def all_events(path):
        recs = read_events(path) + program_events(path, ptrace.PREFIX)
        events.extend(recs)
        return recs

    harness.drive, btrace.events = traced_drive, all_events
    try:
        out = harness.run(argv + ["--trace", "1"])
    except harness.NoDevice as e:
        print(str(e.code), file=sys.stderr)
        return 3
    finally:
        harness.drive, btrace.events = drive, read_events
    ctx = SimpleNamespace(spans=spans)
    own = ptrace.self_ns(spans)
    by_name = defaultdict(lambda: [0, 0])
    for s in spans:
        by_name[s.name][0] += 1
        by_name[s.name][1] += own[s.id]
    report = {
        "correct": out["correct"],
        "harness": out["metrics"],
        "metrics": {m: registry.reader(m)(ctx) for m in SPAN_METRICS},
        "spans": len(spans), "dropped": ptrace.dropped(),
        "self_s_by_name": {k: [n, ns * 1e-9] for k, (n, ns) in
                           sorted(by_name.items(), key=lambda kv: -kv[1][1])},
        "idle_by_span": idle_by_span(events, ptrace.PREFIX),
        "root_self_error": root_error(spans, own),
        "device": out["device"],
    }
    print(json.dumps(report), flush=True)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
