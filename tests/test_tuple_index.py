"""Tuple-membership groups of the lineage walk: the leading atom's candidates
from the engine's sorted-column index answer exactly as the whole-column
``isin`` path does, and each (table, column) index is built once."""

import numpy as np
import pytest

from repro.core import Executor, PredTrace
from repro.core.expr import Col, Lit, Param, land
from repro.core.lineage import _eval_pred
from repro.core.scan import ScanEngine
from repro.core.store import encode_table
from repro.core.table import PartitionedTable, Table, partition_table
from repro.tpch import ALL_QUERIES

BENCH_PIPELINES = ["q3", "q5", "q9", "q10", "q11", "q16", "q18", "q21"]


def _tables(kind):
    """(table, stage selection, lhs of the leading atom, route the index
    path must take) for one case."""
    rng = np.random.default_rng(7)
    n = 5000
    a = rng.integers(0, 300, n).astype(np.int32)
    b = rng.integers(0, 20, n).astype(np.int32)
    base = {"a": a, "b": b}
    sel_a, sel_b = a[[3, 9, 40, 41]], b[[3, 9, 40, 41]]
    lhs, route = Col("a"), "index"
    if kind == "int32_col_int64_stage":
        sel_a, sel_b = sel_a.astype(np.int64), sel_b.astype(np.int64)
        sel_a[0] = 2 ** 40 + int(sel_a[0])  # wraps onto a live key in int32
    elif kind == "duplicate_keys":
        base["a"] = (a // 50).astype(np.int32)  # 6 keys, ~830 rows each
        sel_a = np.repeat(base["a"][[3, 9]], 3)
        sel_b = np.repeat(b[[3, 9]], 3)
    elif kind == "empty_set":
        sel_a, sel_b = sel_a[:0], sel_b[:0]
    elif kind == "absent_values":
        sel_a = np.array([1000, 2000, 3000, -7], np.int32)
    elif kind == "null_sentinel":
        base["a"][:40] = -1
        sel_a = np.array([-1, -1, int(a[100])], np.int32)
        sel_b = np.array([int(b[0]), int(b[1]), int(b[100])], np.int32)
    elif kind == "float_nan":
        f = a.astype(np.float64) / 4
        f[::7] = np.nan
        base["a"] = f
        sel_a = np.array([np.nan, f[3], f[9], f[1]])
        sel_b = b[[0, 3, 9, 1]]
    elif kind == "expr_lhs":
        lhs, route = Col("a") + Lit(0), "isin"
    table = Table(base, name="src")
    if kind == "partitioned":
        table = partition_table(table, part_rows=512)
        assert isinstance(table, PartitionedTable) and table.num_partitions > 1
    elif kind == "stored_view":
        table = encode_table(table).to_table()
    stage = Table({"x": np.asarray(sel_a), "y": np.asarray(sel_b)}, name="st")
    return table, stage, lhs, route


def _brute(table, stage, lhs):
    from repro.core.expr import eval_np

    av = np.asarray(eval_np(lhs, table.cols, {}, n=table.nrows))
    want = set(zip(stage.cols["x"].tolist(), stage.cols["y"].tolist()))
    return np.array([(x, y) in want and x == x
                     for x, y in zip(av.tolist(), table.cols["b"].tolist())])


@pytest.mark.parametrize("kind", [
    "int32_col_int64_stage", "duplicate_keys", "empty_set", "absent_values",
    "null_sentinel", "float_nan", "expr_lhs", "partitioned", "stored_view",
])
def test_index_path_answers_as_whole_column_path(kind):
    table, stage, lhs, route = _tables(kind)
    pred = land(lhs.eq(Param("p")), Col("b").eq(Param("q")), Col("b") >= Lit(0))
    binding = {"p": stage.cols["x"], "q": stage.cols["y"]}
    args = (pred, table, binding, {"p": 0, "q": 0}, {0: stage},
            {"p": "x", "q": "y"})
    engine = ScanEngine()
    old = _eval_pred(*args)
    new = _eval_pred(*args, engine=engine)
    np.testing.assert_array_equal(new, old)
    np.testing.assert_array_equal(new, _brute(table, stage, lhs))
    stats = engine.stats()
    assert stats[f"tuple_{route}_groups"] == 1
    assert stats["tuple_index_groups"] + stats["tuple_isin_groups"] == 1


@pytest.fixture(scope="module")
def bench_pts(tpch_db):
    pts = {}
    for q in BENCH_PIPELINES:
        plan = ALL_QUERIES[q](tpch_db)
        pt = PredTrace(tpch_db, plan, scan_engine=ScanEngine())
        pt.infer(stats=Executor(tpch_db).run(plan).stats)
        pt.run()
        pts[q] = pt
    return pts


@pytest.mark.parametrize("qname", BENCH_PIPELINES)
def test_one_row_query_matches_query_batch(bench_pts, qname):
    pt = bench_pts[qname]
    rows = list(range(min(6, pt.exec_result.output.nrows)))
    assert rows, f"{qname} empty at this scale factor"
    batch = pt.query_batch(rows)
    for r, b in zip(rows, batch):
        one = pt.query(r)
        assert set(one.lineage) == set(b.lineage)
        for tab in one.lineage:
            np.testing.assert_array_equal(np.sort(one.lineage[tab]),
                                          np.sort(b.lineage[tab]))
        assert one.all_precise() and b.all_precise()
    assert pt.scan_engine.stats()["tuple_isin_groups"] == 0


def test_one_row_queries_build_each_index_once(tpch_db):
    plan = ALL_QUERIES["q9"](tpch_db)
    pt = PredTrace(tpch_db, plan, scan_engine=ScanEngine())
    pt.infer(stats=Executor(tpch_db).run(plan).stats)
    pt.run()
    engine = pt.scan_engine
    pt.query(0)
    s1 = engine.stats()
    sorts = s1["caches"]["sorts"]
    assert s1["tuple_index_groups"] > 0 and s1["tuple_isin_groups"] == 0
    built = sorts["size"]
    assert built > 0
    pt.query(1)
    s2 = engine.stats()
    assert s2["tuple_index_groups"] == 2 * s1["tuple_index_groups"]
    assert s2["tuple_isin_groups"] == 0
    assert s2["caches"]["sorts"]["size"] == built
    assert s2["caches"]["sorts"]["misses"] == sorts["misses"]
