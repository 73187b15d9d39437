"""Column-pair and fixed-point decimal lanes of the device scan path.

``PallasBackend`` carries ``a <op> b`` over two int32-exact columns as
``(a - b) <op> 0`` on a difference lane, and a float64 column whose values
are all ``k / scale`` as the int32 ``k`` with thresholds moved onto ``k``.
Every mask here must equal the numpy oracle (``NumpyBackend._cmp_mask``)
bit for bit, through ``scan`` and ``scan_batch_fused``, under Pallas
interpret mode and the jitted XLA twin; columns outside the lanes stay on
the host; and TPC-H q4, q6 and q12 answer the same one-row questions
through the device path as through numpy.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import Executor, PredTrace, ScanEngine, trace
from repro.core.expr import BinOp, Col, Lit, Param, land
from repro.core.scan import NumpyBackend, compile_pred
from repro.core.table import Table

N = 3000  # three 1,024-row blocks once padded
OPS = ["==", "!=", "<", "<=", ">", ">="]


def _interpret():
    return ScanEngine(backend="pallas", interpret=True)


def _xla():
    """The jitted XLA twin of the kernel with every scan held on it: no
    cost-model consult moves a tiny scan back to numpy."""
    eng = ScanEngine(backend="pallas", device_cutover=0)
    assert eng.backend.mode == "xla"
    eng.backend._forced = True
    return eng


CARRIERS = {"interpret": _interpret, "xla": _xla}


def oracle(pred, table, binding):
    """AND of ``NumpyBackend._cmp_mask`` over the predicate's atoms."""
    be = NumpyBackend()
    mask = np.ones(table.nrows, dtype=bool)
    for a in compile_pred(pred).cmp_atoms:
        mask &= be._cmp_mask(a, table, binding, table.nrows)
    return mask


def check(eng, pred, table, bindings, batched=True):
    """Each binding through ``scan``, and all of them through one
    ``scan_batch_fused`` launch, against the oracle; returns the masks."""
    want = [oracle(pred, table, b) for b in bindings]
    for b, w in zip(bindings, want):
        got = eng.scan(pred, table, b)
        assert got.dtype == np.bool_
        assert np.array_equal(got, w), b
    masks = eng.backend.scan_batch_fused(eng.compile(pred), table, bindings)
    if batched:
        assert masks is not None
    if masks is not None:
        for m, w, b in zip(masks, want, bindings):
            assert np.array_equal(m, w), b
    return want


def cmp(op, left, right):
    return BinOp(op, left, right)


# --------------------------------------------------------------------------- #
# column pairs
# --------------------------------------------------------------------------- #
def _pair_table(dtype, seed=1):
    rng = np.random.default_rng(seed)
    lo = 0 if np.dtype(dtype).kind == "u" else -40
    a = rng.integers(lo, lo + 80, N).astype(dtype)
    b = rng.integers(lo, lo + 80, N).astype(dtype)
    b[::7] = a[::7]  # equal values on every seventh row
    k = rng.integers(0, 100, N).astype(np.int32)
    return Table({"a": a, "b": b, "k": k}, {}, "t")


@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.uint16])
@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("carrier", sorted(CARRIERS))
def test_pair_lane_matches_numpy(carrier, op, dtype):
    eng = CARRIERS[carrier]()
    t = _pair_table(dtype)
    pred = land(cmp(op, Col("a"), Col("b")), Col("k") >= Param("p"))
    want = check(eng, pred, t, [{"p": 0}, {"p": 30}, {"p": 99}, {"p": 100}])
    assert any(w.any() for w in want) and not all(w.all() for w in want)
    st = eng.stats
    assert st.pair_lane_scans == 5  # four scans and one batch launch
    assert st.kernel_atoms == 4 * 2 + 2
    assert st.host_atoms == 0


def _wide_pair(kind):
    rng = np.random.default_rng(2)
    a = rng.integers(2**30, 2**31 - 1, N).astype(np.int64)
    b = -a  # a - b leaves int32 on every row
    b[::5] = a[::5]
    if kind == "float_column":
        a, b = rng.integers(0, 9, N).astype(np.float64) + 0.5, a % 10
    elif kind == "wide_column":
        a = a * 4  # a itself leaves int32
        b = a - rng.integers(-3, 3, N)
    return Table({"a": a, "b": b}, {}, "t")


@pytest.mark.parametrize("kind", ["difference_overflows", "float_column",
                                  "wide_column"])
@pytest.mark.parametrize("op", OPS)
def test_pair_outside_int32_stays_on_host(kind, op):
    eng = _interpret()
    t = _wide_pair(kind)
    pred = cmp(op, Col("a"), Col("b"))
    assert np.array_equal(eng.scan(pred, t, {}), oracle(pred, t, {}))
    assert eng.backend.scan_batch_fused(eng.compile(pred), t, [{}]) is None
    assert eng.stats.pair_lane_scans == 0 and eng.stats.device_scans == 0
    assert (eng.stats.kernel_atoms, eng.stats.host_atoms) == (0, 1)


# --------------------------------------------------------------------------- #
# fixed-point decimals
# --------------------------------------------------------------------------- #
def _decimal_table(seed=3):
    rng = np.random.default_rng(seed)
    d = np.round(rng.integers(-300, 300, N) / 100.0, 2)
    d[:11] = np.arange(11) / 100.0  # every discount code of TPC-H
    d[11] = -0.0
    k = rng.integers(0, 100, N).astype(np.int32)
    return Table({"d": d, "k": k}, {}, "t")


# thresholds at a code, between codes, one ulp either side of a code, and
# the IEEE specials
AT_CODES = [0.07, -0.05, 0.0, 2.99, -3.0]
OFF_CODES = [0.065, 0.0751, np.nextafter(0.07, np.inf),
             np.nextafter(0.07, -np.inf), 1e-300, 7.5e9]
SPECIALS = [np.nan, np.inf, -np.inf, -0.0]
SPELLINGS = {"weak": float, "strong": np.float64}


@pytest.mark.parametrize("spelling", sorted(SPELLINGS))
@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("carrier", sorted(CARRIERS))
def test_decimal_lane_matches_numpy(carrier, op, spelling):
    eng = CARRIERS[carrier]()
    t = _decimal_table()
    pred = land(cmp(op, Col("d"), Param("p")), Col("k") < Lit(90))
    cast = SPELLINGS[spelling]
    # thresholds at codes share one static atom structure: one batch
    check(eng, pred, t, [{"p": cast(v)} for v in AT_CODES])
    # the others each alone (a NaN or an empty equality plateau turns an
    # atom into a constant form of another op)
    for v in OFF_CODES + SPECIALS:
        check(eng, pred, t, [{"p": cast(v)}])
    n = len(AT_CODES) + len(OFF_CODES) + len(SPECIALS)
    assert eng.stats.decimal_lane_scans == n + 1 + len(OFF_CODES + SPECIALS)
    assert eng.stats.host_atoms == 0


def test_decimal_lane_holds_the_codes_at_the_smallest_scale():
    eng = _interpret()
    t = Table({"d": np.array([0.05, 0.07, -0.0, 1.0]),
               "w": np.array([1.0, 2.0, -3.0, 2.0**30])}, {}, "t")
    be = eng.backend
    assert be._dec_scale(t, "d") == 100 and be._dec_scale(t, "w") == 1
    assert be._table_lane(t, "d").tolist() == [5, 7, 0, 100]
    assert be._table_lane(t, "w").tolist() == [1, 2, -3, 2**30]


def _host_decimal(kind):
    t = _decimal_table(seed=4)
    d = t.cols["d"].copy()
    d[100] = {"inexact": 0.071, "nan": np.nan, "inf": -np.inf,
              "beyond_int32": 2.5e7 + 0.01}[kind]
    return Table({"d": d, "k": t.cols["k"]}, {}, "t")


@pytest.mark.parametrize("kind", ["inexact", "nan", "inf", "beyond_int32"])
def test_decimal_column_that_does_not_fit_stays_on_host(kind):
    eng = _interpret()
    t = _host_decimal(kind)
    assert eng.backend._dec_scale(t, "d") is None
    for op in OPS:
        pred = land(cmp(op, Col("d"), Param("p")), Col("k") < Lit(90))
        for v in (0.05, 0.071, np.nan):
            b = {"p": v}
            assert np.array_equal(eng.scan(pred, t, b), oracle(pred, t, b))
    assert eng.stats.decimal_lane_scans == 0
    assert eng.stats.host_atoms == eng.stats.kernel_atoms == 6 * 3


def test_store_and_scan_share_one_fit_test():
    from repro.core.store import ScaledColumn, decimal_scale, encode_column

    t = _decimal_table()
    assert decimal_scale(t.cols["d"])[0] == 100
    enc = encode_column(t.cols["d"])
    assert isinstance(enc, ScaledColumn) and enc.scale == 100
    assert decimal_scale(_host_decimal("inexact").cols["d"]) is None
    assert not isinstance(encode_column(_host_decimal("nan").cols["d"]),
                          ScaledColumn)


# --------------------------------------------------------------------------- #
# counters and the host span
# --------------------------------------------------------------------------- #
def test_counters_and_the_scan_host_span():
    rng = np.random.default_rng(5)
    t = Table({"k": rng.integers(0, 100, N).astype(np.int32),
               "a": rng.integers(0, 50, N).astype(np.int32),
               "b": rng.integers(0, 50, N).astype(np.int32),
               "d": np.round(rng.integers(0, 11, N) / 100.0, 2),
               "f": rng.normal(size=N)}, {}, "t")
    eng = _interpret()
    pred = land(Col("k") >= Param("p"), Col("a") < Col("b"),
                Col("d") <= Lit(0.07), Col("f") > Lit(0.5))
    trace.start()
    try:
        got = eng.scan(pred, t, {"p": 10})
    finally:
        spans = trace.stop()
    assert np.array_equal(got, oracle(pred, t, {"p": 10}))
    st = eng.stats
    assert (st.kernel_atoms, st.host_atoms) == (3, 1)
    assert (st.pair_lane_scans, st.decimal_lane_scans,
            st.float_lane_scans, st.device_scans) == (1, 1, 0, 1)
    scan = next(s for s in spans if s.name == "scan")
    assert scan.attrs["kernel_atoms"] == 3 and scan.attrs["host_atoms"] == 1
    assert scan.attrs["host_reasons"] == {"float": 1}
    host = [s for s in spans if s.name == "scan.host"]
    assert len(host) == 1 and host[0].parent == scan.id
    assert host[0].attrs["atoms"] == 1
    # one fused batch launch counts its atoms once
    kept = land(Col("k") >= Param("p"), Col("a") < Col("b"),
                Col("d") <= Lit(0.07))
    masks = eng.backend.scan_batch_fused(eng.compile(kept), t,
                                         [{"p": 10}, {"p": 60}])
    assert all(np.array_equal(m, oracle(kept, t, {"p": p}))
               for m, p in zip(masks, (10, 60)))
    assert (st.kernel_atoms, st.host_atoms) == (6, 1)
    assert (st.pair_lane_scans, st.decimal_lane_scans) == (2, 2)


@pytest.mark.parametrize("binding, reason", [
    ({"p": np.array([1, 2, 3])}, "set_param"),
    ({"p": 2**40}, "wide_int"),
    ({"p": 2.5}, "float"),
    ({}, "unbound"),
])
def test_host_atoms_name_their_reason(binding, reason):
    t = Table({"k": np.arange(N, dtype=np.int32)}, {}, "t")
    eng = _interpret()
    prog = eng.compile(Col("k").eq(Param("p")))
    why = {}
    kernel, host = eng.backend._split_cmp(prog, t, binding, why)
    assert kernel == [] and len(host) == 1
    assert why == {reason: 1}


# --------------------------------------------------------------------------- #
# the reports through the device path
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def tpch():
    from repro.tpch import generate

    return generate(sf=0.01, seed=7)


@pytest.mark.parametrize("q, lane", [("q4", "pair_lane_scans"),
                                     ("q6", "decimal_lane_scans"),
                                     ("q12", "pair_lane_scans")])
def test_report_lineage_through_the_device_path_equals_numpy(tpch, q, lane):
    from repro.tpch import ALL_QUERIES

    plan = ALL_QUERIES[q](tpch)
    stats = Executor(tpch).run(plan).stats
    answers = {}
    spans = []
    for name, eng in (("numpy", ScanEngine()), ("device", _interpret())):
        pt = PredTrace(tpch, plan, scan_engine=eng)
        pt.infer(stats=stats)
        pt.run()
        if name == "device":
            trace.start()
        try:
            answers[name] = [pt.query(r)
                             for r in range(pt.exec_result.output.nrows)]
        finally:
            if name == "device":
                spans = trace.stop()
    assert len(answers["numpy"]) > 0
    for want, got in zip(answers["numpy"], answers["device"]):
        assert got.precise == want.precise
        assert set(got.lineage) == set(want.lineage)
        for t in want.lineage:
            assert np.array_equal(np.sort(np.asarray(got.lineage[t])),
                                  np.sort(np.asarray(want.lineage[t]))), t
    assert getattr(eng.stats, lane) > 0
    # no comparison stayed on the host for want of a lane: the ones left
    # there are set-bound params
    scans = [s for s in spans if s.name == "scan"]
    assert scans and sum(s.attrs.get("kernel_atoms", 0) for s in scans) > 0
    for s in scans:
        assert set(s.attrs.get("host_reasons", {})) <= {"set_param"}, s.attrs
