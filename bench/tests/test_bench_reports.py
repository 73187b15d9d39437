"""The plain reference of the six TPC-H summary reports
(``bench/reference/tpch_reports.py``) against the program's precise
lineage, on every output row at SF 0.01, and the comparison failing a
planted fault."""

import ast
from pathlib import Path

import numpy as np
import pytest

from bench import check, registry

QUERIES = ["q1", "q4", "q6", "q12", "q14", "q15"]
CFG = {"reference": "tpch_reports", "pipelines": QUERIES}


@pytest.fixture(scope="module")
def db():
    from repro.tpch import generate

    return generate(sf=0.01, seed=7)


@pytest.fixture(scope="module")
def data(db):
    return {t: ({c: np.array(v) for c, v in tab.cols.items()},
                {c: list(v) for c, v in tab.dicts.items()})
            for t, tab in db.items()}


def program(db, q):
    """The pipeline executed and run with the numpy engine, and the
    group-key tuple of each of its output rows."""
    from repro.core import Executor, PredTrace
    from repro.tpch import ALL_QUERIES

    plan = ALL_QUERIES[q](db)
    pt = PredTrace(db, plan)
    pt.infer(stats=Executor(db).run(plan).stats)
    pt.run()
    out = pt.exec_result.output
    cols = [np.asarray(out.cols[c]).tolist()
            for c in registry.reference(CFG).GROUP_KEYS[q]]
    return pt, list(zip(*cols)) if cols else [()] * out.nrows


@pytest.mark.parametrize("q", QUERIES)
def test_reference_equals_precise_lineage_on_every_output_row(db, data, q):
    ref = registry.reference(CFG)
    pt, keys = program(db, q)
    want = ref.build(data, q)
    assert len(keys) == len(want) > 0
    for row, key in enumerate(keys):
        ans = pt.query(row)
        assert ans.all_precise()
        rows = want.rows(key)
        assert set(ans.lineage) == set(rows), (q, row)
        for t, rids in rows.items():
            assert len(rids) > 0, (q, row, t)
            np.testing.assert_array_equal(
                np.unique(np.asarray(ans.lineage[t], np.int64)), rids)


def test_a_dropped_q1_row_id_reads_wrong_lineage(db, data):
    ref = registry.reference(CFG)
    pt, keys = program(db, "q1")
    sample = [("q1", row, pt.query(row)) for row in range(len(keys))]
    ok = check.compare(ref, data, {"q1": keys}, sample, missing=0)
    assert check.passed(ok)
    lineage = dict(sample[0][2].lineage)
    lineage["lineitem"] = np.asarray(lineage["lineitem"])[1:]
    sample[0] = ("q1", 0, type(sample[0][2])(lineage=lineage,
                                             precise=sample[0][2].precise))
    bad = check.compare(ref, data, {"q1": keys}, sample, missing=0)
    assert bad["wrong_lineage"]["value"] == 1
    assert not check.passed(bad)


def test_reports_reference_imports_nothing_of_the_program():
    src = (registry.BENCH / "reference" / "tpch_reports.py").read_text()
    names = [a.name for n in ast.walk(ast.parse(src))
             if isinstance(n, ast.Import) for a in n.names]
    names += [n.module for n in ast.walk(ast.parse(src))
              if isinstance(n, ast.ImportFrom) and n.module]
    assert names and not any(m.split(".")[0] == "repro" for m in names)
    assert Path(registry.reference(CFG).__file__).name == "tpch_reports.py"
