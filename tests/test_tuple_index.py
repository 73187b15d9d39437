"""Tuple-membership groups of the lineage walk: the leading atom's candidates
from the engine's sorted-column index answer exactly as the whole-column
``isin`` path does, each (table, column) index is built once, and the
other atoms of a predicate with a tuple group run on the group's candidate
rows alone, so the walk carries row indices and launches nothing over the
table for them."""

import numpy as np
import pytest

from repro.core import Executor, PredTrace, trace
from repro.core import ops as O
from repro.core.eager import oracle_lineage_for_values
from repro.core.expr import (
    BinOp, Col, IsIn, Lit, Param, conjuncts, eval_np, land, params_of,
)
from repro.core.lineage import _binding_groups, _eval_pred, _tuple_members
from repro.core.plan import LineagePlan, Stage
from repro.core.scan import ScanEngine
from repro.core.store import encode_table
from repro.core.table import (
    PartitionedTable, Table, delta_view, partition_table,
)
from repro.tpch import ALL_QUERIES

from conftest import lineage_sets

BENCH_PIPELINES = ["q3", "q5", "q9", "q10", "q11", "q16", "q18", "q21"]
# pipelines whose one-row walk has tuple groups (sf1-clicks and sf1-reports)
TUPLE_PIPELINES = ["q5", "q9", "q10", "q11", "q12", "q14", "q16", "q21"]


def _tables(kind):
    """(table, stage selection, lhs of the leading atom, route the index
    path must take, rest atoms beyond ``b >= 0``, their extra binding) for
    one case."""
    rng = np.random.default_rng(7)
    n = 5000
    a = rng.integers(0, 300, n).astype(np.int32)
    b = rng.integers(0, 20, n).astype(np.int32)
    base = {"a": a, "b": b}
    sel_a, sel_b = a[[3, 9, 40, 41]], b[[3, 9, 40, 41]]
    lhs, route = Col("a"), "index"
    rest, extra = [], {}
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
    elif kind == "rest_set_param":
        # a param bound to an array outside any stage: set semantics
        base["a"] = (a // 10).astype(np.int32)  # ~170 rows per key
        base["c"] = rng.integers(0, 6, n).astype(np.int32)
        sel_a, sel_b = base["a"][[3, 9, 40, 41]], sel_b
        rest, extra = [Col("c").eq(Param("r"))], {"r": np.array([1, 4, 5])}
    elif kind == "rest_nan":
        base["a"] = (a // 10).astype(np.int32)
        c = rng.random(n)
        c[::3] = np.nan
        base["c"] = c
        sel_a = base["a"][[3, 9, 40, 41]]
        rest = [Col("c").ne(Lit(0.5)), Col("c") < Lit(0.75)]
    table = Table(base, name="src")
    if kind == "partitioned":
        table = partition_table(table, part_rows=512)
        assert isinstance(table, PartitionedTable) and table.num_partitions > 1
    elif kind == "stored_view":
        table = encode_table(table).to_table()
    stage = Table({"x": np.asarray(sel_a), "y": np.asarray(sel_b)}, name="st")
    return table, stage, lhs, route, rest, extra


def _brute(table, stage, lhs, rest, binding):
    """Row by row: the tuple is in the stage selection, and the rest atoms
    hold over the whole table."""
    av = np.asarray(eval_np(lhs, table.cols, {}, n=table.nrows))
    want = set(zip(stage.cols["x"].tolist(), stage.cols["y"].tolist()))
    member = np.array([(x, y) in want and x == x
                       for x, y in zip(av.tolist(), table.cols["b"].tolist())])
    return member & np.asarray(
        eval_np(land(*rest), table.cols, binding, n=table.nrows), bool)


def _whole_table_mask(pred, table, binding, param_stage, stage_sel,
                      param_col, engine=None):
    """The walk's former evaluation of a tuple-group predicate: the groups'
    candidates scattered into a whole-table mask, ANDed with a scan of the
    other atoms over every row of the table."""
    tg, rw = _binding_groups(pred, binding, param_stage)
    assert tg and not rw
    mask = np.ones(table.nrows, dtype=bool)
    consumed = []
    for sid, plist in tg.items():
        atoms = []
        for a in conjuncts(pred):
            ap = params_of(a)
            if len(ap) == 1 and next(iter(ap)) in plist and isinstance(a, BinOp):
                p = next(iter(ap))
                lhs = a.left if isinstance(a.right, Param) else a.right
                atoms.append((lhs, np.asarray(stage_sel[sid].cols[param_col[p]])))
                consumed.append(a)
        _, (idx,) = _tuple_members(table, [atoms], engine)
        m = np.zeros(table.nrows, dtype=bool)
        m[idx] = True
        mask &= m
    rest = [a for a in conjuncts(pred) if a not in consumed]
    if rest:
        if engine is None:
            mask &= np.asarray(eval_np(land(*rest), table.cols, binding,
                                       n=table.nrows), bool)
        else:
            mask &= engine.scan(land(*rest), table, binding)
    return mask


def _eval_one(pred, table, binding, param_stage, stage_sel, param_col, **kw):
    """The lineage walk's evaluator over one binding whose stage selections
    are ``stage_sel``."""
    (idx,) = _eval_pred(pred, table, [binding], param_stage, param_col,
                        [lambda: stage_sel], **kw)
    return idx


def _assert_row_ids(idx):
    assert idx.dtype == np.int64
    assert np.all(np.diff(idx) > 0), "row indices must be sorted and unique"


@pytest.mark.parametrize("kind", [
    "int32_col_int64_stage", "duplicate_keys", "empty_set", "absent_values",
    "null_sentinel", "float_nan", "expr_lhs", "partitioned", "stored_view",
    "rest_set_param", "rest_nan",
])
def test_index_path_answers_as_whole_column_path(kind):
    table, stage, lhs, route, extra_rest, extra = _tables(kind)
    rest = [Col("b") >= Lit(0)] + extra_rest
    pred = land(lhs.eq(Param("p")), Col("b").eq(Param("q")), *rest)
    binding = {"p": stage.cols["x"], "q": stage.cols["y"], **extra}
    args = (pred, table, binding, {"p": 0, "q": 0}, {0: stage},
            {"p": "x", "q": "y"})
    engine = ScanEngine()
    old = _eval_one(*args)
    new = _eval_one(*args, engine=engine)
    _assert_row_ids(new)
    np.testing.assert_array_equal(new, old)
    want = _brute(table, stage, lhs, rest, binding)
    np.testing.assert_array_equal(new, np.flatnonzero(want))
    np.testing.assert_array_equal(
        new, np.flatnonzero(_whole_table_mask(*args, engine=ScanEngine())))
    if kind in ("empty_set", "absent_values"):
        assert len(new) == 0
    stats = engine.stats()
    assert stats[f"tuple_{route}_groups"] == 1
    assert stats["tuple_index_groups"] + stats["tuple_isin_groups"] == 1
    assert stats["tuple_rest_cand"] == 1 and stats["tuple_rest_table"] == 0
    # the rest atoms ran on the candidates: no scan of the table
    assert stats["scans"] == 0


def test_rowwise_branch_counts_a_whole_table_rest():
    """A stage param used outside equality binds per stage row: the rest
    atoms scan the whole table for each row, beside a tuple group's
    candidates, and the answer is the rows both admit."""
    table, stage, lhs, _, _, _ = _tables("duplicate_keys")
    win = Table({"lo": np.array([2, 5, 5], np.int32),
                 "hi": np.array([9, 12, 12], np.int32)}, name="win")
    pred = land(lhs.eq(Param("p")), Col("b").eq(Param("q")),
                Col("b") >= Param("lo"), Col("b") <= Param("hi"))
    binding = {"p": stage.cols["x"], "q": stage.cols["y"],
               "lo": win.cols["lo"], "hi": win.cols["hi"]}
    engine = ScanEngine()
    got = _eval_one(pred, table, binding,
                    {"p": 0, "q": 0, "lo": 1, "hi": 1}, {0: stage, 1: win},
                    {"p": "x", "q": "y", "lo": "lo", "hi": "hi"},
                    scan=engine.scan, engine=engine)
    _assert_row_ids(got)
    member = _brute(table, stage, lhs, [Col("b") >= Lit(0)], {})
    bv = table.cols["b"]
    window = ((bv >= 2) & (bv <= 9)) | ((bv >= 5) & (bv <= 12))
    np.testing.assert_array_equal(got, np.flatnonzero(member & window))
    stats = engine.stats()
    assert stats["tuple_rest_cand"] == 0 and stats["tuple_rest_table"] == 1
    assert stats["scans"] == 2  # one whole-table scan per distinct window


def test_membership_only_group_scans_the_whole_table():
    """A group whose params appear only in ``IN`` atoms gives no candidates:
    each ``IN`` keeps set semantics in one scan of the whole table."""
    table, stage, _, _, _, _ = _tables("duplicate_keys")
    pred = land(IsIn(Col("a"), Param("p")), IsIn(Col("b"), Param("q")))
    binding = {"p": stage.cols["x"], "q": stage.cols["y"]}
    engine = ScanEngine()
    got = _eval_one(pred, table, binding, {"p": 0, "q": 0}, {0: stage},
                    {"p": "x", "q": "y"}, scan=engine.scan, engine=engine)
    _assert_row_ids(got)
    want = (np.isin(table.cols["a"], stage.cols["x"])
            & np.isin(table.cols["b"], stage.cols["y"]))
    np.testing.assert_array_equal(got, np.flatnonzero(want))
    stats = engine.stats()
    assert stats["tuple_rest_cand"] == 0 and stats["tuple_rest_table"] == 1
    assert stats["tuple_index_groups"] + stats["tuple_isin_groups"] == 0
    assert stats["scans"] == 1


def _hand_walk(table, pred, stored):
    """A PredTrace whose lineage plan is written by hand: output param ``o``
    selects every row of two small stages, which bind the set ``r`` and the
    stage pair ``p``, ``q`` of ``_tables``; a third stage, ``table`` plain or
    encoded, then runs ``pred`` with those params."""
    _, stage, _, _, _, extra = _tables("rest_set_param")
    key = lambda n: np.zeros(n, np.int32)
    s_r = Table({"g": key(3), "rv": extra["r"]}, name="r")
    s_pq = Table({"g": key(stage.nrows), "x": stage.cols["x"],
                  "y": stage.cols["y"]}, name="pq")
    pt = PredTrace({"src": table}, O.Source("src"), store=stored or None)
    pt.run()
    pt.lineage_plan = LineagePlan(pt.plan, {"o": "g"}, [
        Stage(1, Col("g").eq(Param("o")), {"r": "rv"}),
        Stage(2, Col("g").eq(Param("o")), {"p": "x", "q": "y"}),
        Stage(7, pred, {"z": "a"}),
    ], [])
    pt.exec_result.materialized = {
        1: s_r, 2: s_pq, 7: encode_table(table) if stored else table}
    pt.mat_plan = None
    return pt


@pytest.mark.parametrize("stored", [False, True], ids=["plain", "stored"])
def test_stage_select_returns_the_rows_of_the_whole_table_mask(stored):
    table, stage, lhs, _, rest, extra = _tables("rest_set_param")
    pred = land(lhs.eq(Param("p")), Col("b").eq(Param("q")), *rest)
    binding = {"p": stage.cols["x"], "q": stage.cols["y"], **extra}
    walk = ({"p": 0, "q": 0}, {0: stage}, {"p": "x", "q": "y"})
    pt = _hand_walk(table, pred, stored)
    ans = pt.query({"g": 0})
    sel = ans.delta_ctx[3]()[2]  # the selection of the walk's third stage
    want = table.mask(_whole_table_mask(pred, table, binding, *walk))
    assert sel.nrows == want.nrows > 0
    for c in want.cols:
        np.testing.assert_array_equal(np.asarray(sel.cols[c]),
                                      np.asarray(want.cols[c]))
    assert pt.scan_engine.stats()["tuple_rest_cand"] == 1


def _engine(backend):
    if backend == "pallas":
        return ScanEngine(backend="pallas", interpret=True)
    return ScanEngine()


@pytest.fixture(scope="module")
def walk_pts(tpch_db):
    """PredTraces by (backend, pipeline), built on first use."""
    pts = {}

    def get(backend, q):
        if (backend, q) not in pts:
            plan = ALL_QUERIES[q](tpch_db)
            pt = PredTrace(tpch_db, plan, scan_engine=_engine(backend))
            pt.infer(stats=Executor(tpch_db).run(plan).stats)
            pt.run()
            pts[backend, q] = pt
        return pts[backend, q]

    return get


@pytest.fixture(scope="module")
def oracle_sets(tpch_db):
    """The eager oracle's lineage of an output row, by (pipeline, row)."""
    memo = {}

    def get(pt, q, r):
        if (q, r) not in memo:
            out = pt.exec_result.output
            memo[q, r] = lineage_sets(oracle_lineage_for_values(
                tpch_db, pt.plan, {c: out.cols[c][r] for c in out.columns}))
        return memo[q, r]

    return get


@pytest.mark.parametrize("backend, qname", [
    pytest.param(be, q, id=q if be == "numpy" else f"{q}-pallas")
    for be in ("numpy", "pallas")
    for q in sorted(set(BENCH_PIPELINES) | set(TUPLE_PIPELINES),
                    key=lambda q: int(q[1:]))
])
def test_one_row_query_matches_query_batch(walk_pts, oracle_sets, backend,
                                           qname):
    """One-row answers equal ``query_batch`` and the eager oracle, and lie
    inside ``query_naive``'s phase-1 superset, on numpy and on the Pallas
    kernel in interpret mode."""
    pt = walk_pts(backend, qname)
    n_rows = 6 if backend == "numpy" else 3
    rows = list(range(min(n_rows, pt.exec_result.output.nrows)))
    assert rows, f"{qname} empty at this scale factor"
    batch = pt.query_batch(rows)
    for r, b in zip(rows, batch):
        one = pt.query(r)
        assert set(one.lineage) == set(b.lineage)
        for tab in one.lineage:
            np.testing.assert_array_equal(np.sort(one.lineage[tab]),
                                          np.sort(b.lineage[tab]))
        assert one.all_precise() and b.all_precise()
        got = lineage_sets(one.lineage)
        if r < 2:
            assert got == oracle_sets(pt, qname, r)
            naive = lineage_sets(pt.query_naive(r).lineage)
            for tab, ids in got.items():
                assert ids <= naive.get(tab, set()), tab
    stats = pt.scan_engine.stats()
    assert stats["tuple_isin_groups"] == 0
    assert stats["tuple_rest_table"] == 0
    if qname in ("q5", "q9", "q10", "q12", "q14", "q21"):
        assert stats["tuple_rest_cand"] > 0


def _tuple_source_preds(pt, binding, param_stage, table):
    return [sp.pred for sp in pt.lineage_plan.source_preds
            if sp.table == table and _binding_groups(
                sp.pred, binding, param_stage)[0]]


@pytest.mark.parametrize("qname", ["q10", "q21"])
def test_rest_atoms_launch_nothing_over_lineitem(tpch_db, qname):
    """On a Pallas engine the rest atoms of lineitem's tuple groups launch
    no kernel: the whole-table evaluation launched once per predicate over
    every lineitem row."""
    plan = ALL_QUERIES[qname](tpch_db)
    eng = ScanEngine(backend="pallas", interpret=True)
    pt = PredTrace(tpch_db, plan, scan_engine=eng)
    pt.infer(stats=Executor(tpch_db).run(plan).stats)
    pt.run()
    li = pt.catalog["lineitem"]
    s0 = eng.stats()
    trace.start()
    try:
        ans = pt.query(0)
    finally:
        spans = trace.stop()
    s1 = eng.stats()
    tuples = [s for s in spans if s.name == "lineage.tuple"]
    assert tuples and all(s.attrs["rest"] == "cand" for s in tuples)
    assert all(s.attrs["rest_rows"] < li.nrows for s in tuples)
    assert s1["tuple_rest_cand"] - s0["tuple_rest_cand"] == len(tuples)
    assert s1["tuple_rest_table"] == s0["tuple_rest_table"]
    # no scan (and so no launch) in a tuple group or beside it
    with_tuple = {s.parent for s in tuples} | {s.id for s in tuples}
    assert not [s for s in spans if s.name == "scan"
                and s.parent in with_tuple]

    binding, param_stage, param_col, stage_sels = ans.delta_ctx
    stage_sel = stage_sels()
    preds = _tuple_source_preds(pt, binding, param_stage, "lineitem")
    assert preds, f"{qname}: no tuple group on lineitem"
    for pred in preds:
        walk = (pred, li, binding, param_stage, stage_sel, param_col)
        before = eng.stats()
        idx = _eval_one(*walk, scan=eng.scan, engine=eng)
        mid = eng.stats()
        old = _whole_table_mask(*walk, engine=eng)
        after = eng.stats()
        np.testing.assert_array_equal(idx, np.flatnonzero(old))
        assert mid["device_scans"] == before["device_scans"]
        assert mid["device_rows"] == before["device_rows"]
        assert after["device_scans"] == mid["device_scans"] + 1
        assert after["device_rows"] - mid["device_rows"] >= li.nrows


@pytest.mark.parametrize("with_engine", [False, True],
                         ids=["rescan", "serial"])
def test_delta_view_rows_match_the_grown_table(with_engine):
    """``query_delta`` evaluates a source predicate over the appended
    partitions alone (:func:`delta_view`): tuple candidates and rest atoms
    there give the grown table's matches at or past the view's offset."""
    table, stage, lhs, _, rest, extra = _tables("rest_set_param")
    grown = partition_table(table, part_rows=512)
    old_n = 3300  # inside the seventh partition: the view starts at 3072
    view, off = delta_view(grown, old_n)
    assert off == 3072 and view.nrows == table.nrows - off
    pred = land(lhs.eq(Param("p")), Col("b").eq(Param("q")), *rest)
    binding = {"p": stage.cols["x"], "q": stage.cols["y"], **extra}
    walk = ({"p": 0, "q": 0}, {0: stage}, {"p": "x", "q": "y"})
    engine = ScanEngine() if with_engine else None
    idx = _eval_one(pred, view, binding, *walk, engine=engine)
    _assert_row_ids(idx)
    full = np.flatnonzero(_whole_table_mask(pred, grown, binding, *walk))
    assert len(full[full >= off]) > 0
    np.testing.assert_array_equal(idx + off, full[full >= off])


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
