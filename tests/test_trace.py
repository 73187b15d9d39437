"""``repro.core.trace``: the off path, nesting and parent ids, self time,
the buffer cap, and what one kernel launch records and counts."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from repro.core import ScanEngine, trace
from repro.core.scan import OPS
from repro.core.table import Table


@pytest.fixture(autouse=True)
def _tracing_off_after():
    yield
    trace.stop()


def test_off_path_is_one_shared_noop_that_reads_no_clock(monkeypatch):
    reads = []
    monkeypatch.setattr(time, "perf_counter_ns",
                        lambda: reads.append(1) or 0)
    a, b = trace.span("lineage.query", rows=1), trace.span("scan")
    assert a is b
    with a as s:
        s.set(rows=2)
        trace.note(route="serial")
    trace.record("service.queue", 0, 1, 7)
    assert reads == []
    assert not trace.enabled()
    trace.start()
    assert trace.stop() == []


def test_spans_nest_by_thread_and_inherit_the_request_id():
    trace.start()
    with trace.span("a", 7):
        with trace.span("b"):
            with trace.span("c", k=1) as c:
                c.set(k=2)
                trace.note(m=3)
        with trace.span("d", 9):
            pass
        other = threading.Thread(target=lambda: trace.span("t").__enter__()
                                 .__exit__(None, None, None))
        other.start()
        other.join()
    with trace.span("e"):
        pass
    spans = {s.name: s for s in trace.stop()}
    a, b, c, d, e, t = (spans[k] for k in "abcdet")
    assert a.parent is None and e.parent is None and t.parent is None
    assert (b.parent, c.parent, d.parent) == (a.id, b.id, a.id)
    assert (a.req, b.req, c.req, d.req, e.req) == (7, 7, 7, 9, None)
    assert c.attrs == {"k": 2, "m": 3}
    assert a.start_ns <= b.start_ns <= c.start_ns <= c.end_ns <= b.end_ns
    assert d.end_ns <= a.end_ns <= e.start_ns
    assert len({s.id for s in spans.values()}) == 6


def test_self_time_is_duration_less_the_union_its_children_cover():
    S = trace.Span
    spans = [S("root", 0, 100, 1, None, None, {}),
             S("c1", 10, 30, 2, 1, None, {}),
             S("c2", 25, 60, 3, 1, None, {}),  # overlaps c1: union 10..60
             S("g", 12, 20, 4, 2, None, {}),
             S("late", 90, 120, 5, 1, None, {})]  # covers 90..100 of root
    assert trace.self_ns(spans) == {1: 40, 2: 12, 3: 35, 4: 8, 5: 30}


def test_the_cap_keeps_the_first_spans_and_counts_the_rest():
    trace.start(cap=3)
    for _ in range(4):
        with trace.span("s"):
            pass
    trace.record("service.queue", 0, 1)
    assert len(trace.stop()) == 3
    assert trace.dropped() == 2
    trace.start()
    assert trace.dropped() == 0


def test_one_interpret_launch_counts_its_bytes_and_records_its_phases():
    rng = np.random.default_rng(0)
    n = 3000  # three 1,024-row blocks once padded
    t = Table.from_dict({"a": rng.integers(0, 100, n).astype(np.int32),
                         "b": rng.integers(0, 100, n).astype(np.int32)},
                        name="t")
    eng = ScanEngine(backend="pallas", interpret=True)
    be = eng.backend
    entry = be._slab_entry(t, ("a", "b"))
    assert eng.stats.h2d_bytes == 2 * 3072 * 4  # the padded slab, once
    atoms = ((0, OPS[">="]), (1, OPS["<"]))
    thr = np.array([[10, 50], [20, 60], [30, 70]], np.int32)  # K=3 -> 4
    trace.start()
    mask = be._launch(entry, atoms, thr)
    spans = trace.stop()
    for k, (lo, hi) in enumerate(thr):
        assert np.array_equal(mask[k], (t.cols["a"] >= lo) & (t.cols["b"] < hi))
    operands = (4 * 2 * 4  # thresholds, padded to K=4
                + entry.lo[[0, 1]].nbytes + entry.hi[[0, 1]].nbytes)
    assert eng.stats.h2d_bytes == 2 * 3072 * 4 + operands
    assert eng.stats.d2h_bytes == 4 * 3072 * 4  # the [4, 3072] int32 mask
    launch = next(s for s in spans if s.name == "launch")
    assert launch.attrs == {"K": 3, "N": n}
    kids = sorted((s for s in spans if s.parent == launch.id),
                  key=lambda s: s.start_ns)
    assert [s.name for s in kids] == ["launch.upload", "launch.call",
                                      "launch.readback", "launch.mask"]
