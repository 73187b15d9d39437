"""The trace-to-metrics reduction (bench/trace.py), on a synthetic trace
whose answer is known and on a small trace recorded on a TPU v5e."""

import json
from pathlib import Path

import pytest

from bench import trace

DEV, OPS, HOST = "/device:TPU:0", "XLA Ops", "/host:CPU"


def test_busy_idle_and_gap_owners_of_a_synthetic_trace():
    recs = [
        [HOST, "python", "bench.window", 0.0, 1000.0],
        [HOST, "dispatch", "bench.query", 50.0, 850.0],
        [HOST, "dispatch", "bench.launch", 310.0, 280.0],
        [DEV, OPS, "fusion.1", 100.0, 100.0],
        [DEV, OPS, "pred_filter_batch.3", 150.0, 150.0],
        [DEV, OPS, "copy.2", 600.0, 100.0],
        [DEV, OPS, "outside.9", 2000.0, 10.0],  # after the window
    ]
    s = trace.summarize(recs)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx(300e-9)  # [100, 300) and [600, 700)
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert gaps["query"] == pytest.approx(400e-9)  # [0, 100) and [700, 1000)
    assert gaps["launch"] == pytest.approx(300e-9)  # [300, 600)
    ops = dict(s["breakdown"]["device_ops"])
    assert set(ops) == {"fusion.1", "pred_filter_batch.3", "copy.2"}
    assert trace.kernel_time(recs, r"pred_filter") == (pytest.approx(150e-9), 1)


def test_no_window_or_no_device_op_reads_nothing():
    assert trace.summarize([[DEV, OPS, "x", 0.0, 1.0]]) is None
    assert trace.summarize([[HOST, "python", "bench.window", 0.0, 9.0]]) is None
    assert trace.kernel_time([], "x") == (0.0, 0)


def test_recorded_tpu_trace_reduces_to_its_pinned_numbers():
    # a 10 s sf1-debug window traced on one TPU v5e: 75 pred_filter launches
    # recorded by the harness, op names cut after "custom-call"
    path = Path(__file__).parent / "data" / "trace_sf1_debug.json"
    recs = json.loads(path.read_text())
    s = trace.summarize(recs)
    assert s["window_s"] == pytest.approx(9.906322249)
    assert s["busy_s"] == pytest.approx(0.021445165)
    from bench.metrics import pred_filter_roofline

    seconds, n = trace.kernel_time(recs, pred_filter_roofline.KERNEL)
    assert n == 75
    assert seconds == pytest.approx(0.021387096)
    assert seconds <= s["busy_s"] <= s["window_s"]
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert set(gaps) <= {"launch", "scan_stored", "scan", "query", "no_query"}
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    assert len(s["breakdown"]["device_ops"]) == 10
