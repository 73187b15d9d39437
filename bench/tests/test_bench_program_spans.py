"""Readers of the program's spans and counters, on synthetic windows, and
the idle split of ``bench/program_spans.py`` on a synthetic trace."""

from types import SimpleNamespace

import numpy as np
import pytest

from bench import program_spans, registry
from repro.core.trace import Span

MS = 1_000_000  # ns
HOST, DEV, OPS = "/host:CPU", "/device:TPU:0", "XLA Ops"


def read(name, **ctx):
    return registry.reader(name)(SimpleNamespace(**ctx))


def walk_window():
    """Two lineage calls: a one-row query (walk 50 ms outside scans) and a
    batch under the service's batch span (walk 30 ms)."""
    return [
        Span("lineage.query", 0, 100 * MS, 1, None, None, {"rows": 1}),
        Span("lineage.stage", 10 * MS, 40 * MS, 2, 1, None, {}),
        Span("scan", 15 * MS, 35 * MS, 3, 2, None, {}),
        Span("lineage.source", 50 * MS, 90 * MS, 4, 1, None, {}),
        Span("scan", 55 * MS, 85 * MS, 5, 4, None, {}),
        Span("launch", 60 * MS, 80 * MS, 6, 5, None, {"K": 1, "N": 9}),
        Span("service.batch", 190 * MS, 270 * MS, 7, None, 3, {}),
        Span("lineage.query_batch", 200 * MS, 260 * MS, 8, 7, 3, {}),
        Span("lineage.stage", 210 * MS, 250 * MS, 9, 8, 3, {}),
        Span("scan", 215 * MS, 245 * MS, 10, 9, 3, {}),
        Span("launch", 220 * MS, 230 * MS, 11, 10, 3, {"K": 2, "N": 9}),
    ]


@pytest.mark.parametrize("name,counter", [
    ("h2d_bytes_per_answer.clicks", "h2d_bytes"),
    ("d2h_bytes_per_answer.clicks", "d2h_bytes")])
def test_byte_counters_per_answer(name, counter):
    assert read(name, scan={counter: 5000}, answered=4) == 1250
    assert read(name, scan={counter: 5000}, answered=0) is None
    # a program without the counter reads nothing
    assert read(name, scan={"device_scans": 3}, answered=4) is None


def test_queue_p95_is_the_95th_percentile_of_queue_spans_in_ms():
    spans = [Span("service.queue", 0, k * MS, k, None, k, {"batch": 1})
             for k in range(1, 101)]
    spans.append(Span("service.batch", 0, 900 * MS, 500, None, 1, {}))
    got = read("queue_p95_ms.clicks", spans=spans)
    assert got == pytest.approx(np.percentile(np.arange(1, 101), 95))


def test_walk_self_time_is_lineage_time_outside_scans_per_root():
    assert read("walk_self_ms.clicks", spans=walk_window()) == \
        pytest.approx((50 + 30) / 2)


def test_launch_ms_is_the_mean_launch_span():
    assert read("launch_ms.clicks", spans=walk_window()) == pytest.approx(15)


@pytest.mark.parametrize("name", ["queue_p95_ms.clicks", "walk_self_ms.clicks",
                                  "launch_ms.clicks"])
def test_span_metrics_read_nothing_without_spans(name):
    assert read(name, spans=None) is None
    assert read(name, spans=[]) is None
    assert read(name) is None  # a harness that passes no spans


def test_self_times_under_each_root_add_up_to_it():
    spans = walk_window()
    from repro.core.trace import self_ns

    assert program_spans.root_error(spans, self_ns(spans)) == 0.0


def test_idle_gaps_go_to_the_innermost_program_span_at_their_midpoint():
    p = "predtrace."
    recs = [
        [HOST, "python", "bench.window", 0.0, 1000.0],
        [DEV, OPS, "fusion.1", 100.0, 100.0],  # gap [0, 100): mid 50
        [DEV, OPS, "pred_filter_batch.1", 600.0, 100.0],  # gap [200, 600)
        [DEV, OPS, "copy.2", 950.0, 10.0],  # gaps [700, 950), [960, 1000)
        [HOST, "d", p + "lineage.query", 20.0, 920.0],
        [HOST, "d", p + "launch", 300.0, 350.0],
        [HOST, "d", p + "launch.readback", 350.0, 290.0],
    ]
    got = program_spans.idle_by_span(recs, p)
    assert got == pytest.approx({"lineage.query": 350e-9,
                                 "launch.readback": 400e-9,
                                 "no_span": 40e-9})
    assert sum(got.values()) == pytest.approx(1000e-9 - 210e-9)
    assert program_spans.idle_by_span(recs[4:], p) is None
