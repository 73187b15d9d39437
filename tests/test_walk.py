"""``query`` and ``query_batch`` answer through one stage walk: a batch
answers each row as a one-row query does, in the shapes the batch entry
point once handed to ``query`` or answered its own way — budget-degraded
plans, stored stages, delta extension and the tuple-group counters."""

import numpy as np
import pytest

from repro.core import Executor, PredTrace, trace
from repro.core import ops as O
from repro.core.expr import Col
from repro.core.table import Table
from repro.tpch import ALL_QUERIES

from conftest import lineage_sets


def _prepared(db, qname, **kw):
    plan = ALL_QUERIES[qname](db)
    pt = PredTrace(db, plan, **kw)
    pt.infer(stats=Executor(db).run(plan).stats)
    pt.run()
    return pt


def _traced(call):
    trace.start()
    try:
        out = call()
    finally:
        spans = trace.stop()
    return out, spans


def _degraded(db, qname, keep):
    """A budget that keeps the ``keep`` smallest stages: the batch gives
    each row ``query``'s lineage, superset tables and precision flags, in
    one walk under one ``lineage.query_batch`` root."""
    sizes = sorted(_prepared(db, qname, store=True).store.sizes().values())
    pt = _prepared(db, qname, store=True, budget_bytes=sum(sizes[:keep]))
    assert 0 < len(pt.mat_plan.dropped) == len(sizes) - keep
    rows = list(range(min(4, pt.exec_result.output.nrows)))
    batch, spans = _traced(lambda: pt.query_batch(rows))
    assert [s.name for s in spans if s.parent is None] == [
        "lineage.query_batch"]
    assert "lineage.query" not in {s.name for s in spans}
    for r, got in zip(rows, batch):
        want = pt.query(r)
        assert lineage_sets(got.lineage) == lineage_sets(want.lineage), r
        assert got.precise == want.precise and not got.all_precise()
        for key in ("superset_tables", "iterations"):
            assert got.detail[key] == want.detail[key], (r, key)
        assert got.detail["batch"] == len(rows)
        assert "batch" not in want.detail


def _stored_stage_routes(db):
    """A stored stage of a plain conjunction is scanned in situ for one
    binding, from either entry point, and read decoded for two."""
    pt = _prepared(db, "q3", store=True)
    routes = {}
    for name, call in (("query", lambda: [pt.query(0)]),
                       ("batch1", lambda: pt.query_batch([0])),
                       ("batch2", lambda: pt.query_batch([0, 1]))):
        answers, spans = _traced(call)
        routes[name] = [s.attrs["route"] for s in spans
                        if s.name == "lineage.stage"]
        assert all(a.all_precise() for a in answers)
    assert routes == {"query": ["in_situ"], "batch1": ["in_situ"],
                      "batch2": ["decoded"]}
    one, two = pt.query_batch([0]), pt.query_batch([0, 1])
    assert lineage_sets(one[0].lineage) == lineage_sets(two[0].lineage)


def _delta_extends(db):
    """``query_delta`` extends a batch answer as it extends a one-row
    answer, and both equal a fresh query over the grown table."""
    k = np.arange(1000)
    cat = {"t": Table.from_dict({"k": k, "g": k // 50, "v": (k * 7) % 100},
                                name="t")}
    plan = O.GroupBy(O.Filter(O.Source("t"), Col("v") >= 0), ["g"],
                     {"sv": O.Agg("sum", Col("v"))})
    pt = PredTrace(cat, plan, store=True, partition_rows=100)
    pt.infer()
    pt.run()
    rows = [{"g": 19}, {"g": 0}]
    tok0 = pt.answer_generation()
    ones = [pt.query(r) for r in rows]
    batch = pt.query_batch(rows)
    pt.run_delta({"t": {"k": np.arange(1000, 1030), "g": np.full(30, 19),
                        "v": np.arange(30)}})
    for r, one, bat in zip(rows, ones, batch):
        ext_one, ext_bat = pt.query_delta(one, tok0), pt.query_delta(bat, tok0)
        assert ext_one is not None and ext_bat is not None
        want = lineage_sets(pt.query(r).lineage)
        assert lineage_sets(ext_one.lineage) == want
        assert lineage_sets(ext_bat.lineage) == want
        assert ext_bat.detail["delta"] == ext_one.detail["delta"]
    assert set(range(1000, 1030)) <= lineage_sets(
        pt.query_delta(batch[0], tok0).lineage)["t"]


def _batch_rest_counts(db):
    """A batch's tuple groups count in ``tuple_rest_cand`` and the index
    counters as the same rows' one-row queries do."""
    pt = _prepared(db, "q10")
    names = ("tuple_rest_cand", "tuple_rest_table", "tuple_index_groups",
             "tuple_isin_groups")
    rows = [0, 1, 2]
    s0 = pt.scan_engine.stats()
    for r in rows:
        pt.query(r)
    s1 = pt.scan_engine.stats()
    pt.query_batch(rows)
    s2 = pt.scan_engine.stats()
    one = {n: s1[n] - s0[n] for n in names}
    bat = {n: s2[n] - s1[n] for n in names}
    assert bat == one
    assert bat["tuple_rest_cand"] >= len(rows) and bat["tuple_rest_table"] == 0


CASES = {
    "degraded_nothing_kept": lambda db: _degraded(db, "q3", 0),
    "degraded_small_stage_kept": lambda db: _degraded(db, "q11", 1),
    "stored_stage_routes": _stored_stage_routes,
    "delta_extends_batch_answers": _delta_extends,
    "batch_tuple_rest_counts": _batch_rest_counts,
}


@pytest.mark.parametrize("case", list(CASES))
def test_batch_walk_answers_as_one_row_walk(tpch_db, case):
    CASES[case](tpch_db)
