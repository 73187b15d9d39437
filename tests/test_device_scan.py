"""Differential suite for the device-native scan path.

Every configuration of the ``PallasBackend`` carrier — Pallas interpret
mode, the forced XLA device path, fused batched launches, in-grid
zone-pruned grids, and encoded-slab (code-space) scans — must be
bit-identical to the ``NumpyBackend`` oracle.  Correctness never depends
on which side of a dispatch cutover a scan lands, so these tests force
both sides.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import ScanEngine
from repro.core.expr import Col, IsIn, Lit, Param, land, lor
from repro.core.scan import OPS, PallasBackend
from repro.core.store import BitPackColumn, DictColumn, FORColumn, StoredTable
from repro.core.table import Table, partition_table
from repro.kernels.pred_filter import (
    block_bounds,
    pred_filter_batch,
    pred_filter_batch_ref,
)

N = 4096


def _engines():
    """(name, engine) triples: the numpy oracle, the forced XLA device path
    (cutover 0 so even tiny tables take the device route), and compiled-
    kernel semantics via Pallas interpret mode."""
    return [
        ("numpy", ScanEngine()),
        ("xla", ScanEngine(backend="pallas", device_cutover=0)),
        ("pallas-interpret", ScanEngine(backend="pallas", interpret=True)),
    ]


def _check_all(pred, table, binding):
    want = None
    for name, eng in _engines():
        got = eng.scan(pred, table, binding)
        if want is None:
            want = got
        else:
            assert np.array_equal(got, want), f"{name} diverges from numpy"
    return want


# --------------------------------------------------------------------------- #
# dtype sweep
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [
    np.int8, np.int16, np.int32, np.int64, np.uint8, np.uint16, np.bool_,
])
def test_integer_dtypes_identical(dtype):
    rng = np.random.default_rng(7)
    hi = 2 if dtype == np.bool_ else min(np.iinfo(np.dtype(dtype) if dtype
                                         != np.bool_ else np.int8).max, 500)
    a = rng.integers(0, hi, N).astype(dtype)
    k = rng.integers(0, 100, N).astype(np.int32)
    t = Table({"a": a, "k": k}, {}, "t")
    pred = land(Col("a") >= Param("p"), Col("k") < Lit(80))
    _check_all(pred, t, {"p": int(hi) // 2})
    # equality + inequality through the method spelling (== on Expr is
    # structural, not columnar)
    pred2 = land(Col("a").eq(Param("p")), Col("k").ne(Lit(3)))
    _check_all(pred2, t, {"p": 1})


def test_float_columns_fall_back_identically():
    rng = np.random.default_rng(8)
    f = rng.normal(0, 100, N)
    f[::17] = np.nan
    k = rng.integers(0, 1000, N).astype(np.int32)
    t = Table({"f": f, "k": k}, {}, "t")
    pred = land(Col("f") >= Param("p"), Col("k") < Param("q"))
    m = _check_all(pred, t, {"p": -5.5, "q": 900})
    # NaN rows never satisfy an order comparison
    assert not m[::17].any()


def test_nan_and_inf_thresholds():
    rng = np.random.default_rng(9)
    k = rng.integers(-1000, 1000, N).astype(np.int64)
    t = Table({"k": k}, {}, "t")
    for p in (np.nan, np.inf, -np.inf, 0.5, -0.5, 2.0**33, -(2.0**33)):
        for pred in (Col("k") > Param("p"), Col("k") <= Param("p"),
                     Col("k").eq(Param("p")), Col("k").ne(Param("p"))):
            _check_all(pred, t, {"p": p})


def test_membership_atoms_identical():
    rng = np.random.default_rng(10)
    k = rng.integers(0, 500, N).astype(np.int32)
    j = rng.integers(0, 100, N).astype(np.int32)
    t = Table({"k": k, "j": j}, {}, "t")
    vset = np.unique(rng.integers(0, 500, 40)).astype(np.int32)
    pred = land(IsIn(Col("k"), vset.tolist()), Col("j") >= Param("p"))
    _check_all(pred, t, {"p": 20})
    pred_param = land(IsIn(Col("k"), Param("s")), Col("j") < Lit(90))
    _check_all(pred_param, t, {"s": vset})


def test_disjunction_residual_identical():
    rng = np.random.default_rng(11)
    a = rng.integers(0, 100, N).astype(np.int32)
    b = rng.integers(0, 100, N).astype(np.int32)
    t = Table({"a": a, "b": b}, {}, "t")
    pred = lor(Col("a") < Param("p"), Col("b") >= Lit(95))
    _check_all(pred, t, {"p": 5})


# --------------------------------------------------------------------------- #
# batched bindings
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("k_bindings", [1, 3, 8])
def test_batched_bindings_match_sequential(k_bindings):
    rng = np.random.default_rng(12)
    a = rng.integers(0, 10_000, N).astype(np.int32)
    b = rng.integers(0, 100, N).astype(np.int32)
    t = Table({"a": a, "b": b}, {}, "t")
    pred = land(Col("a") >= Param("p"), Col("b") < Param("q"))
    # duplicates and out-of-range values on purpose: the fused [K, A]
    # launch must answer them exactly as K separate scans would
    base = [{"p": int(v), "q": 50 + i}
            for i, v in enumerate(rng.integers(0, 12_000, k_bindings))]
    if k_bindings >= 3:
        base[1] = dict(base[0])          # duplicate binding
        base[-1] = {"p": 10**7, "q": 0}  # empty answer
    eng_np = ScanEngine()
    eng_dev = ScanEngine(backend="pallas", device_cutover=0)
    want = [np.flatnonzero(eng_np.scan(pred, t, bd)) for bd in base]
    got = eng_dev.scan_batch_idx(pred, t, base)
    assert len(got) == len(want)
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
    # and through the backend's fused hook directly
    prog = eng_dev.compile(pred)
    masks = eng_dev.backend.scan_batch_fused(prog, t, base)
    assert masks is not None
    for w, m in zip(want, masks):
        assert np.array_equal(w, np.flatnonzero(m))


def test_batch_fused_refuses_out_of_fragment():
    rng = np.random.default_rng(13)
    t = Table({"a": rng.normal(size=N), "b": rng.integers(0, 9, N).astype(np.int32)},
              {}, "t")
    be = PallasBackend(device_cutover=0, batch_cutover=0)
    eng = ScanEngine(backend=be)
    # float column: outside the int32 kernel fragment -> None, caller keeps
    # the host batch path (which must still be correct)
    prog = eng.compile(land(Col("a") >= Param("p"), Col("b") < Lit(5)))
    assert be.scan_batch_fused(prog, t, [{"p": 0.25}]) is None
    got = eng.scan_batch_idx(land(Col("a") >= Param("p"), Col("b") < Lit(5)),
                             t, [{"p": 0.25}])
    want = np.flatnonzero(ScanEngine().scan(
        land(Col("a") >= Param("p"), Col("b") < Lit(5)), t, {"p": 0.25}))
    assert np.array_equal(got[0], want)


# --------------------------------------------------------------------------- #
# in-grid zone pruning: the pruned kernel vs the zone-free oracle
# --------------------------------------------------------------------------- #
def _grid_case(kind: str, block_rows: int = 256, blocks: int = 8):
    """Block-structured data where zone pruning is total / impossible /
    partial, so the @pl.when early-out path is actually exercised."""
    n = block_rows * blocks
    base = np.repeat(np.arange(blocks) * 1000, block_rows).astype(np.int32)
    jitter = np.tile(np.arange(block_rows) % 100, blocks).astype(np.int32)
    col = base + jitter
    if kind == "all":     # no block's [min, max] can satisfy col >= 10^6
        thr = np.array([[1_000_000]], np.int32)
    elif kind == "none":  # every block min passes col >= 0
        thr = np.array([[0]], np.int32)
    else:                 # only the top half of blocks can match
        thr = np.array([[blocks // 2 * 1000]], np.int32)
    return col.reshape(1, n), thr, block_rows


@pytest.mark.parametrize("kind", ["all", "none", "partial"])
def test_pruned_grid_matches_oracle(kind):
    import jax.numpy as jnp

    cols, thr, br = _grid_case(kind)
    atoms = ((0, OPS[">="]),)
    lo, hi = block_bounds(cols, br, (0,))
    got = pred_filter_batch(jnp.asarray(cols), jnp.asarray(thr), atoms,
                            jnp.asarray(lo), jnp.asarray(hi),
                            block_rows=br, interpret=True)
    want = pred_filter_batch_ref(jnp.asarray(cols), jnp.asarray(thr), atoms)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    if kind == "all":
        assert not np.asarray(got).any()


def test_pruned_grid_multi_binding_mixed_blocks():
    import jax.numpy as jnp

    cols, _, br = _grid_case("partial")
    # bindings alive in disjoint block subsets: a block is skipped only
    # when *no* binding can match it
    thr = np.array([[0], [3000], [1_000_000]], np.int32)
    atoms = ((0, OPS[">="]),)
    lo, hi = block_bounds(cols, br, (0,))
    got = pred_filter_batch(jnp.asarray(cols), jnp.asarray(thr), atoms,
                            jnp.asarray(lo), jnp.asarray(hi),
                            block_rows=br, interpret=True)
    want = pred_filter_batch_ref(jnp.asarray(cols), jnp.asarray(thr), atoms)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got)[2].any() and np.asarray(got)[0].all()


def test_pruned_grid_set_atoms_match_oracle():
    """In-grid membership over a multi-tile set slab: ragged per-binding
    segments (one empty, one confined to a few blocks, one spanning the
    table) must answer exactly like the zone-free binary-search oracle,
    with set-zone pruning and slab-tile skipping both engaged."""
    import jax.numpy as jnp

    from repro.kernels.pred_filter import search_iters

    cols, _, br = _grid_case("partial")
    rng = np.random.default_rng(60)
    segs = [
        np.array([], np.int32),                                   # empty
        np.unique(rng.integers(3000, 3100, 40)).astype(np.int32),  # 1 block
        np.unique(rng.integers(-50, 9000, 2500)).astype(np.int32),  # all
        np.arange(0, 2000, 7, dtype=np.int32),                    # low blocks
        np.array([5000, 6099], np.int32),  # exactly a block's min / max
    ]
    off = np.cumsum([0] + [s.size for s in segs[:-1]]).astype(np.int32)
    ln = np.array([s.size for s in segs], np.int32)
    slab = np.concatenate(segs).astype(np.int32)
    K = len(segs)
    thr = np.array([[0], [-1], [1000], [0], [0]], np.int32)  # col >= t
    atoms = ((0, OPS[">="]),)
    lo, hi = block_bounds(np.vstack([cols, cols]), br, (0, 1))
    got = pred_filter_batch(jnp.asarray(cols), jnp.asarray(thr), atoms,
                            jnp.asarray(lo), jnp.asarray(hi), block_rows=br,
                            interpret=True, set_cols=(0,),
                            set_slab=jnp.asarray(slab),
                            set_off=jnp.asarray(off.reshape(K, 1)),
                            set_len=jnp.asarray(ln.reshape(K, 1)))
    want = pred_filter_batch_ref(jnp.asarray(cols), jnp.asarray(thr), atoms,
                                 set_cols=(0,), set_slab=jnp.asarray(slab),
                                 set_off=jnp.asarray(off.reshape(K, 1)),
                                 set_len=jnp.asarray(ln.reshape(K, 1)),
                                 iters=search_iters(int(ln.max())))
    got, want = np.asarray(got), np.asarray(want)
    assert np.array_equal(got, want)
    for k, s in enumerate(segs):
        assert np.array_equal(got[k] != 0,
                              np.isin(cols[0], s) & (cols[0] >= thr[k, 0]))
    assert not got[0].any() and got[2].any()


@pytest.mark.parametrize("n", [1, 1000, 1024, 1025, 4097])
def test_ragged_row_counts(n):
    """Row counts off the block boundary: slab padding must never leak
    padded rows into the answer."""
    rng = np.random.default_rng(n)
    a = rng.integers(-50, 50, n).astype(np.int32)
    t = Table({"a": a}, {}, "t")
    pred = Col("a") >= Param("p")
    m = _check_all(pred, t, {"p": 0})
    assert m.shape == (n,)


def test_empty_table():
    t = Table({"a": np.zeros(0, np.int32)}, {}, "t")
    m = _check_all(Col("a") >= Param("p"), t, {"p": 0})
    assert m.shape == (0,)


# --------------------------------------------------------------------------- #
# encoded slabs: code-space device scans over StoredTable
# --------------------------------------------------------------------------- #
def _stored(col: str, enc) -> StoredTable:
    return StoredTable({col: enc}, {}, "st", enc.n, enc.n * 8)


def _assert_stored_matches(st: StoredTable, pred, binding,
                           expect_device: bool = True):
    be = PallasBackend(device_cutover=0, batch_cutover=0)
    eng = ScanEngine(backend=be)
    prog = eng.compile(pred)
    got = be.scan_stored(prog, st, binding)
    want = ScanEngine().scan(pred, st.to_table(), binding)
    if expect_device:
        assert got is not None, "device path refused an in-fragment scan"
        assert np.array_equal(got, want)
    else:
        assert got is None
    return want


def test_stored_dict_boundaries():
    rng = np.random.default_rng(20)
    vals = np.array([-7, 3, 50, 1_000_000], np.int64)
    arr = rng.choice(vals, N)
    st = _stored("c", DictColumn.encode(arr))
    # present values, absent values, and between-codes thresholds across
    # every op: the lo/hi searchsorted mapping must hit each branch
    for v in (-7, 3, 50, 1_000_000, 4, -100, 2_000_000, 49):
        for pred in (Col("c").eq(Param("p")), Col("c").ne(Param("p")),
                     Col("c") < Param("p"), Col("c") <= Param("p"),
                     Col("c") > Param("p"), Col("c") >= Param("p")):
            _assert_stored_matches(st, pred, {"p": v})


def test_stored_dict_nan_values_gate():
    # NaN dictionary values sort last; >= / > would sweep the NaN tail into
    # the code-space answer, so the device path must refuse (and the host
    # fallback must agree with the decoded oracle)
    arr = np.array([0.5, 1.5, np.nan, 1.5, np.nan, 0.5] * 300)
    st = _stored("c", DictColumn.encode(arr))
    _assert_stored_matches(st, Col("c") >= Param("p"), {"p": 1.0},
                           expect_device=False)
    _assert_stored_matches(st, Col("c") > Param("p"), {"p": 0.5},
                           expect_device=False)
    # < / <= / == / != stay answerable in code space
    for pred in (Col("c") < Param("p"), Col("c") <= Param("p"),
                 Col("c").eq(Param("p")), Col("c").ne(Param("p"))):
        _assert_stored_matches(st, pred, {"p": 1.5})


def test_stored_for_range_and_out_of_frame():
    rng = np.random.default_rng(21)
    arr = (rng.integers(0, 1000, N) + 10_000_000_000).astype(np.int64)
    enc = FORColumn.encode(arr, np.uint16)
    st = _stored("c", enc)
    lo, hi = int(arr.min()), int(arr.max())
    for v in (lo, hi, (lo + hi) // 2, lo - 5, hi + 5, 0, 10_000_000_000.5):
        for pred in (Col("c") >= Param("p"), Col("c") < Param("p"),
                     Col("c").eq(Param("p")), Col("c").ne(Param("p"))):
            _assert_stored_matches(st, pred, {"p": v})


def test_stored_bitpack():
    rng = np.random.default_rng(22)
    arr = rng.integers(0, 2, N).astype(bool)
    st = _stored("c", BitPackColumn.encode(arr))
    for v in (0, 1):
        _assert_stored_matches(st, Col("c").eq(Param("p")), {"p": v})
        _assert_stored_matches(st, Col("c") >= Param("p"), {"p": v})


def test_stored_unbound_param_refused():
    arr = np.arange(N, dtype=np.int64)
    st = _stored("c", DictColumn.encode(arr % 16))
    be = PallasBackend(device_cutover=0)
    eng = ScanEngine(backend=be)
    prog = eng.compile(Col("c") >= Param("p"))
    assert be.scan_stored(prog, st, {}) is None  # fallback raises uniformly


# --------------------------------------------------------------------------- #
# partitioned tables through the device route
# --------------------------------------------------------------------------- #
def test_partition_executor_device_route_identical():
    from repro.core.distributed import PartitionExecutor

    rng = np.random.default_rng(30)
    n = 1 << 14
    t = Table({
        "a": np.sort(rng.integers(0, 10_000, n)).astype(np.int32),
        "b": rng.integers(0, 100, n).astype(np.int32),
    }, {}, "t")
    pt = partition_table(t, 16)
    pred = land(Col("a") >= Param("p"), Col("b") < Lit(90))
    eng_np = ScanEngine()
    eng_dev = ScanEngine(backend="pallas", device_cutover=0)
    ex_np = PartitionExecutor(eng_np, max_workers=0)
    ex_dev = PartitionExecutor(eng_dev, max_workers=0)
    for p in (0, 2_500, 9_990, 10**6):
        m_np = ex_np.scan(pred, pt, {"p": p})
        m_dev = ex_dev.scan(pred, pt, {"p": p})
        assert np.array_equal(m_np, m_dev)
    # the device route actually launched (not a silent numpy fallback)
    assert eng_dev.stats.snapshot()["device_scans"] > 0


def test_fused_carry_respects_pruning_on_host():
    """In XLA mode (no in-grid early-out on host) the carry must refuse
    when partition pruning would skip most of the table."""
    be = PallasBackend(device_cutover=0)
    eng = ScanEngine(backend=be)
    rng = np.random.default_rng(31)
    n = 1 << 14
    t = Table({"a": np.sort(rng.integers(0, 10_000, n)).astype(np.int32)}, {}, "t")
    pt = partition_table(t, 16)
    prog = eng.compile(Col("a") >= Param("p"))
    assert not be.fused_carry_ok(prog, pt, {"p": 9_990}, surviving_rows=n // 16)
    assert be.fused_carry_ok(prog, pt, {"p": 0}, surviving_rows=n)


def test_fused_carry_refusal_counted_and_stamped():
    """A carry refusal bumps ``carry_refused`` and — under a recorder —
    records the refused device route as ``fallback_from``, exactly like the
    store's ranked-walk fallback."""
    from repro.core.cost import PlanRecorder
    from repro.core.distributed import PartitionExecutor

    rng = np.random.default_rng(32)
    n = 1 << 16
    t = Table({"a": np.sort(rng.integers(0, 1000, n)).astype(np.int64)}, {}, "t")
    pt = partition_table(t, part_rows=4096)
    eng = ScanEngine(backend="pallas", device_cutover=0)
    ex = PartitionExecutor(eng, max_workers=0)
    pred = Col("a") < Param("v")
    with PlanRecorder() as rec:
        got = ex.scan(pred, pt, {"v": 5})   # prunes almost everything
    assert np.array_equal(got, t.cols["a"] < 5)
    assert eng.stats.carry_refused >= 1
    stamped = [d for d in rec.decisions if d.fallback_from == "device"]
    assert stamped and stamped[0].actual_s is not None


# --------------------------------------------------------------------------- #
# float32 key lane: order-preserving int32 keys instead of per-atom fallback
# --------------------------------------------------------------------------- #
def _f32_table():
    rng = np.random.default_rng(40)
    f = rng.normal(0, 100, N).astype(np.float32)
    f[::13] = np.nan
    f[1::97] = np.inf
    f[2::97] = -np.inf
    f[3::31] = -0.0
    f[4::31] = 0.0
    f[5::17] = np.float32(3.0)    # exact hits for the snapped thresholds
    k = rng.integers(0, 100, N).astype(np.int32)
    return Table({"f": f, "k": k}, {}, "t")


_F32_THRESHOLDS = [
    0.0, -0.0, 3.0, np.nan, np.inf, -np.inf,
    # non-representable weak scalars: NEP 50 snaps them to float32 first
    # (3.0000000001 -> 3.0, 1e40 -> inf) and the kernel must agree
    3.0000000001, 1e40, -1e40,
    # strong scalars compare in float64 -- a different answer than the
    # weak spelling of the same digits
    np.float64(3.0000000001), np.float64(1e40), np.int64(2**62),
    np.float32(0.25), np.float16(0.5), np.bool_(True), True, 7,
]


@pytest.mark.parametrize("v", _F32_THRESHOLDS,
                         ids=[f"{type(v).__name__}-{v}" for v in _F32_THRESHOLDS])
def test_float32_lane_identical(v):
    t = _f32_table()
    for pred in (Col("f") < Param("p"), Col("f") <= Param("p"),
                 Col("f") > Param("p"), Col("f") >= Param("p"),
                 Col("f").eq(Param("p")), Col("f").ne(Param("p"))):
        _check_all(pred, t, {"p": v})


def test_float32_lane_engaged_not_fallback():
    t = _f32_table()
    eng = ScanEngine(backend="pallas", device_cutover=0)
    m = eng.scan(land(Col("f") >= Param("p"), Col("k") < Lit(90)), t, {"p": -5.5})
    assert eng.stats.float_lane_scans > 0
    assert np.array_equal(
        m, ScanEngine().scan(land(Col("f") >= Param("p"), Col("k") < Lit(90)),
                             t, {"p": -5.5}))
    # NaN rows never satisfy an order comparison through the key lane
    assert not m[np.isnan(t.cols["f"])].any()


def test_float64_still_falls_back():
    # a float64 column that is not decimal (k / scale) stays outside the
    # lane fragment; answers must still match through the host fallback
    rng = np.random.default_rng(41)
    t = Table({"f": rng.normal(size=N), "k": rng.integers(0, 9, N).astype(np.int32)},
              {}, "t")
    eng = ScanEngine(backend="pallas", device_cutover=0)
    pred = land(Col("f") >= Param("p"), Col("k") < Lit(5))
    assert np.array_equal(eng.scan(pred, t, {"p": 0.25}),
                          ScanEngine().scan(pred, t, {"p": 0.25}))
    assert eng.stats.float_lane_scans == 0


# --------------------------------------------------------------------------- #
# fused membership: in-grid binary search over device-resident sorted sets
# --------------------------------------------------------------------------- #
def test_membership_fused_engaged_and_identical():
    rng = np.random.default_rng(42)
    k = rng.integers(0, 500, N).astype(np.int32)
    j = rng.integers(0, 100, N).astype(np.int32)
    t = Table({"k": k, "j": j}, {}, "t")
    vset = np.unique(rng.integers(0, 500, 40)).astype(np.int32)
    pred = land(IsIn(Col("k"), Param("s")), Col("j") >= Param("p"))
    eng = ScanEngine(backend="pallas", device_cutover=0)
    got = eng.scan(pred, t, {"s": vset, "p": 20})
    assert eng.stats.member_fused_scans > 0, "host probe ran instead of kernel"
    assert np.array_equal(got, ScanEngine().scan(pred, t, {"s": vset, "p": 20}))
    # pure-membership program (no comparison atom to ride on)
    got2 = eng.scan(IsIn(Col("k"), Param("s")), t, {"s": vset})
    assert np.array_equal(got2, np.isin(k, vset))


def test_membership_fused_empty_and_disjoint_sets():
    rng = np.random.default_rng(43)
    k = rng.integers(0, 500, N).astype(np.int32)
    t = Table({"k": k}, {}, "t")
    for s in (np.array([], np.int32),            # empty -> all False
              np.array([10**6], np.int64),       # disjoint from the column
              np.array([-1, 10**9], np.int64)):  # straddles, still disjoint
        pred = IsIn(Col("k"), Param("s"))
        m = _check_all(pred, t, {"s": s})
        assert not m.any()
    # float-valued set on an integer column: only integral members can hit
    mf = _check_all(IsIn(Col("k"), Param("s")), t,
                    {"s": np.array([3.0, 3.5, 7.0])})
    assert np.array_equal(mf, np.isin(k, [3, 7]))


def test_membership_sets_straddle_partitions():
    """Set values concentrated in a few partitions: the in-grid zone check
    must keep exactly the blocks whose [min, max] intersects the set."""
    from repro.core.distributed import PartitionExecutor

    rng = np.random.default_rng(44)
    n = 1 << 14
    a = np.sort(rng.integers(0, 10_000, n)).astype(np.int32)
    t = Table({"a": a}, {}, "t")
    pt = partition_table(t, 16)
    # values from the low and high tails plus one partition-boundary value
    vset = np.array([int(a[0]), int(a[n // 16 - 1]), int(a[n // 16]),
                     int(a[-1]), -5], np.int64)
    pred = IsIn(Col("a"), Param("s"))
    ex_np = PartitionExecutor(ScanEngine(), max_workers=0)
    ex_dev = PartitionExecutor(ScanEngine(backend="pallas", device_cutover=0),
                               max_workers=0)
    m_np = ex_np.scan(pred, pt, {"s": vset})
    m_dev = ex_dev.scan(pred, pt, {"s": vset})
    assert np.array_equal(m_np, m_dev)
    assert np.array_equal(m_dev, np.isin(a, vset))


def test_batch_fused_heterogeneous_set_sizes():
    """K coalesced bindings with different-size sets (including empty) on one
    launch: the ragged [K, S] slab layout must answer each binding exactly as
    K separate scans would."""
    rng = np.random.default_rng(45)
    k = rng.integers(0, 500, N).astype(np.int32)
    j = rng.integers(0, 100, N).astype(np.int32)
    t = Table({"k": k, "j": j}, {}, "t")
    pred = land(IsIn(Col("k"), Param("s")), Col("j") < Param("q"))
    base = [
        {"s": np.array([7], np.int32), "q": 90},
        {"s": np.unique(rng.integers(0, 500, 40)).astype(np.int64), "q": 50},
        {"s": np.array([], np.int32), "q": 99},
        {"s": np.unique(rng.integers(0, 500, 200)).astype(np.int32), "q": 10},
    ]
    be = PallasBackend(device_cutover=0, batch_cutover=0)
    eng = ScanEngine(backend=be)
    prog = eng.compile(pred)
    masks = be.scan_batch_fused(prog, t, base)
    assert masks is not None, "fused batch refused an in-fragment program"
    for bd, m in zip(base, masks):
        want = ScanEngine().scan(pred, t, bd)
        assert np.array_equal(m, want)
    assert not masks[2].any()


def test_batch_fused_float_lane_bindings():
    rng = np.random.default_rng(46)
    f = rng.normal(0, 10, N).astype(np.float32)
    f[::11] = np.nan
    j = rng.integers(0, 100, N).astype(np.int32)
    t = Table({"f": f, "j": j}, {}, "t")
    pred = land(Col("f") >= Param("p"), Col("j") < Param("q"))
    base = [{"p": -5.5, "q": 90}, {"p": np.nan, "q": 99},
            {"p": 1e40, "q": 50}, {"p": np.float64(0.1), "q": 75}]
    be = PallasBackend(device_cutover=0, batch_cutover=0)
    eng = ScanEngine(backend=be)
    masks = be.scan_batch_fused(eng.compile(pred), t, base)
    assert masks is not None
    for bd, m in zip(base, masks):
        assert np.array_equal(m, ScanEngine().scan(pred, t, bd))
    assert not masks[1].any()      # NaN threshold: order compare is empty


def test_sorted_set_cache_reuse():
    """The per-predicate sorted-set cache: re-probing the same array object
    (as every partition of one scan does) reuses the sort."""
    from repro.core.scan import _sorted_unique, sorted_set_counters

    before = sorted_set_counters()["hits"]
    s = np.array([5, 1, 3, 1, 5], np.int64)
    a = _sorted_unique(s)
    b = _sorted_unique(s)
    assert a is b and np.array_equal(a, [1, 3, 5])
    assert sorted_set_counters()["hits"] >= before + 1


# --------------------------------------------------------------------------- #
# run-space RLE scans and the widened encoded-int32 fragment
# --------------------------------------------------------------------------- #
def test_stored_rle_run_boundaries():
    from repro.core.store import RLEColumn

    # explicit runs with boundary-adjacent values: thresholds at, just
    # below, and just above each run value exercise every off-by-one
    arr = np.repeat(np.array([3, 9, 3, 15, 15, -2], np.int64),
                    [4, 1, 3, 2, 6, 5])
    enc = RLEColumn.encode(arr)
    assert enc.kind == "rle", enc.kind
    st = _stored("c", enc)
    for v in (-3, -2, -1, 2, 3, 4, 8, 9, 10, 14, 15, 16, 2.5, 3.5, np.nan):
        for pred in (Col("c").eq(Param("p")), Col("c").ne(Param("p")),
                     Col("c") < Param("p"), Col("c") <= Param("p"),
                     Col("c") > Param("p"), Col("c") >= Param("p")):
            _assert_stored_matches(st, pred, {"p": v})


def test_stored_rle_single_run_and_len_one_runs():
    from repro.core.store import RLEColumn

    # one giant run, then all length-1 runs: the two degenerate layouts
    one = RLEColumn.encode(np.full(N, 42, np.int64))
    alt = RLEColumn.encode(np.arange(64, dtype=np.int64))
    for enc, vals in ((one, (41, 42, 43)), (alt, (0, 31, 63, 64))):
        st = _stored("c", enc)
        for v in vals:
            _assert_stored_matches(st, Col("c") >= Param("p"), {"p": v})
            _assert_stored_matches(st, Col("c").eq(Param("p")), {"p": v})


def test_stored_delta_sorted_int64():
    from repro.core.store import encode_column

    rng = np.random.default_rng(50)
    arr = np.sort(rng.integers(0, 10**7, N)).astype(np.int64)
    enc = encode_column(arr)
    assert enc.kind == "delta", enc.kind
    st = _stored("c", enc)
    lo, hi = int(arr.min()), int(arr.max())
    for v in (lo, hi, (lo + hi) // 2, lo - 1, hi + 1, -10**7, 2 * 10**7, 0.5):
        for pred in (Col("c") >= Param("p"), Col("c") < Param("p"),
                     Col("c").eq(Param("p")), Col("c").ne(Param("p"))):
            _assert_stored_matches(st, pred, {"p": v})


def test_stored_scaled_two_decimal_float32():
    from repro.core.store import encode_column

    rng = np.random.default_rng(51)
    arr = (rng.integers(-10_000, 10_000, N) / 100).astype(np.float32)
    enc = encode_column(arr)
    assert enc.kind == "scaled", enc.kind
    st = _stored("c", enc)
    # representable values, between-code values, weak vs strong scalars,
    # and thresholds the verified-boundary walk must not mistranslate
    for v in (0.01, -0.01, 0.005, 0.009999999999, 99.99, -99.995, 100.5,
              np.float64(0.1), np.float32(0.25), 0, np.nan, np.inf, -np.inf):
        for pred in (Col("c").eq(Param("p")), Col("c").ne(Param("p")),
                     Col("c") < Param("p"), Col("c") <= Param("p"),
                     Col("c") > Param("p"), Col("c") >= Param("p")):
            _assert_stored_matches(st, pred, {"p": v})


def test_store_dispatch_prefers_insitu_rle():
    """An RLE-heavy stage scans in run space (no decode): the cost model
    offers and picks the ``insitu_rle`` route and the store never decodes."""
    from repro.core.store import IntermediateStore

    rng = np.random.default_rng(52)
    runs = rng.integers(50, 400, 2000)
    vals = rng.integers(0, 40, runs.size)
    a = np.repeat(vals, runs)[:200_000].astype(np.int64)
    t = Table({"a": a}, {}, "t")
    store = IntermediateStore()
    st = store.put(7, t)
    assert st.enc["a"].kind == "rle"
    eng = ScanEngine(backend="pallas", device_cutover=0)
    got = store.scan(7, Col("a") < Param("v"), {"v": 20}, eng)
    assert np.array_equal(got, a < 20)
    assert eng.stats.rle_insitu_chosen >= 1
    assert eng.stats.rle_run_scans >= 1
    assert eng.stats.decode_chosen == 0


# --------------------------------------------------------------------------- #
# device failures surface; they never become host answers
# --------------------------------------------------------------------------- #
def _raising_launch(*_args, **_kw):
    raise RuntimeError("device launch failed")


@pytest.mark.parametrize("probe", ["device_scan_probe", "member_scan_probe",
                                   "rle_scan_probe"])
def test_probe_launch_failure_propagates(probe, monkeypatch):
    from repro.core import dispatch

    for env in ("PREDTRACE_DEVICE_CUTOVER", "PREDTRACE_MEMBER_CUTOVER",
                "PREDTRACE_RLE_CUTOVER"):
        monkeypatch.delenv(env, raising=False)
    key = f"test-raising-{probe}"
    with pytest.raises(RuntimeError, match="device launch failed"):
        getattr(dispatch, probe)(key, _raising_launch)
    assert key not in dispatch.probe_info()[probe.split("_")[0]]


def test_mesh_scan_launch_failure_propagates():
    import jax

    from repro.core.distributed import PartitionExecutor

    mesh = jax.make_mesh((1,), ("data",))
    eng = ScanEngine()
    eng.jit_scan = lambda pred: _raising_launch
    ex = PartitionExecutor(eng, mesh=mesh, mesh_axes=("data",), max_workers=0)
    t = Table({"a": np.arange(N, dtype=np.int32)}, {}, "t")
    with pytest.raises(RuntimeError, match="device launch failed"):
        ex.scan(Col("a") >= Param("p"), t, {"p": 7})
    # outside the exact int32 fragment the host answers, by decision
    launched = eng.stats.mesh_scans
    tf = Table({"f": np.linspace(0, 1, N)}, {}, "tf")
    got = ex.scan(Col("f") >= Param("p"), tf, {"p": 0.5})
    assert np.array_equal(got, tf.cols["f"] >= 0.5)
    assert eng.stats.mesh_scans == launched
