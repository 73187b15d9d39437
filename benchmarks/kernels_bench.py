"""Kernel-path benchmarks: the lineage-scan hot path across backends.

Wall-clock on this container compares numpy vs jit'd-XLA fused predicate
scans (the production CPU paths); the Pallas kernels are validated in
interpret mode (timings of interpret mode are not meaningful and are
reported only as correctness checks).
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ScanEngine
from repro.core.expr import Col, Param, eval_np, land
from repro.core.table import Table
from repro.kernels.membership import probe
from repro.kernels.pred_filter import scan_mask

from .common import time_ms


def _bench_batched(rng) -> List[tuple]:
    """Batched [K, A] fused launches: K bindings answered by one launch via
    the PallasBackend carrier, vs. K sequential numpy scans.  Per-launch
    achieved bandwidth (column bytes read / wall-clock) is merged into
    ``BENCH_scan.json`` for the roofline report."""
    import json
    from pathlib import Path

    from repro.core.scan import PallasBackend
    from repro.kernels.pred_filter import pred_filter_batch, pred_filter_batch_ref

    rows: List[tuple] = []
    report = {}
    n = 1 << 21
    A = 4
    slab = rng.integers(0, 1_000_000, (A, n)).astype(np.int32)
    atoms = ((0, 5), (1, 2), (2, 3), (3, 4))  # >= < <= >
    be = PallasBackend(device_cutover=0, batch_cutover=0)
    entry = be._build_entry(slab)
    for K in (1, 8, 32):
        thr = rng.integers(0, 1_000_000, (K, A)).astype(np.int32)

        def host():
            return [(slab[0] >= t[0]) & (slab[1] < t[1])
                    & (slab[2] <= t[2]) & (slab[3] > t[3]) for t in thr]

        be._launch(entry, atoms, thr)  # warm (jit trace)
        t_np = time_ms(host, repeat=5)
        t_dev = time_ms(lambda: be._launch(entry, atoms, thr), repeat=5)
        # bytes the launch must stream: each column block read once for all
        # K bindings (the whole point of the [K, A] operand) plus the
        # [K, N] bool mask writeback
        moved_bytes = slab.nbytes + K * n
        gbps = moved_bytes / max(t_dev * 1e-3, 1e-12) / 1e9
        ok = bool(np.array_equal(
            np.stack(host()),
            be._launch(entry, atoms, thr),
        ))
        report[f"batched_k{K}"] = {
            "rows": n, "atoms": A, "bindings": K,
            "numpy_ms": t_np, "device_ms": t_dev,
            "speedup": t_np / max(t_dev, 1e-9),
            "achieved_gbps": gbps, "identical": ok,
        }
        rows.append((f"kernels.batched_scan.k{K}", t_dev * 1e3,
                     f"numpy={t_np:.2f}ms device={t_dev:.2f}ms "
                     f"speedup={t_np / max(t_dev, 1e-9):.2f}x "
                     f"bw={gbps:.1f}GB/s identical={ok}"))
    # interpret-mode correctness of the batched kernel proper (zone-pruned
    # grid vs zone-free oracle), small slice — interpret timing is meaningless
    import jax.numpy as _jnp

    from repro.kernels.pred_filter import block_bounds

    head = slab[:, :8192]
    lo, hi = block_bounds(head, 1024, tuple(range(A)))
    thr = rng.integers(0, 1_000_000, (4, A)).astype(np.int32)
    got = pred_filter_batch(_jnp.asarray(head), _jnp.asarray(thr), atoms,
                            _jnp.asarray(lo), _jnp.asarray(hi),
                            block_rows=1024, interpret=True)
    want = pred_filter_batch_ref(_jnp.asarray(head), _jnp.asarray(thr), atoms)
    report["pallas_interpret_ok"] = bool(np.array_equal(np.asarray(got),
                                                        np.asarray(want)))
    rows.append(("kernels.batched_scan.interpret", 0.0,
                 f"pallas_interpret_ok={report['pallas_interpret_ok']}"))

    # merge (not overwrite) into the shared scan report
    out = Path("BENCH_scan.json")
    data = {}
    if out.exists():
        try:
            data = json.loads(out.read_text())
        except ValueError:
            data = {}
    data["kernels.batched"] = report
    out.write_text(json.dumps(data, indent=2, sort_keys=True))
    return rows


def _bench_member_float(rng) -> List[tuple]:
    """IN-heavy and float-heavy predicate mixes through the fused kernel.

    The IN-heavy mix races the in-grid membership fusion (per-lane binary
    search over a device-resident sorted set) against the pre-fusion host
    probe (``np.isin`` over the full column); the float-heavy mix races the
    order-preserving int32 key lane against the numpy float compare.  Both
    merge into ``BENCH_scan.json``: bench-smoke gates
    ``member_fused_beats_host`` and the usual identical flags; nightly holds
    the membership launch to >= 20% of the measured roofline."""
    import json
    from pathlib import Path

    from repro.core.expr import IsIn
    from repro.core.scan import PallasBackend

    rows: List[tuple] = []
    report = {}
    n = 1 << 21
    # wide-domain keys, the lineage-membership shape: order/part keys span
    # millions of distinct values, so the host probe cannot use numpy's
    # narrow-range table lookup and pays a sort-based ``np.isin`` per scan
    k = rng.integers(0, 2**30, n).astype(np.int32)
    j = rng.integers(0, 100, n).astype(np.int32)
    t = Table({"k": k, "j": j}, {}, "bench")
    vset = np.sort(rng.choice(k, 4_000, replace=False)).astype(np.int32)
    pred = land(IsIn(Col("k"), Param("s")), Col("j") >= Param("p"))
    binding = {"s": vset, "p": 20}
    # standalone backend: cutover 0 pins the device route so the timing is
    # the fused launch itself, not a cost-model mix of routes
    from repro.core.scan import ScanStats

    be = PallasBackend(device_cutover=0, batch_cutover=0)
    be.attach_stats(ScanStats())
    prog = ScanEngine().compile(pred)
    got = be.scan(prog, t, binding)               # warm: jit trace + slabs
    fused = int(be._stats.member_fused_scans) > 0

    def host_probe():
        return np.isin(k, vset) & (j >= 20)

    t_host = time_ms(host_probe, repeat=5)
    t_dev = time_ms(lambda: be.scan(prog, t, binding), repeat=5)
    # two int32 column reads plus the bool mask writeback; the sorted set
    # rides in cache and is noise at this size
    moved = k.nbytes + j.nbytes + n
    gbps = moved / max(t_dev * 1e-3, 1e-12) / 1e9
    ok = bool(np.array_equal(got, host_probe()))
    report["in_heavy"] = {
        "rows": n, "set_size": int(vset.size), "member_fused": fused,
        "host_probe_ms": t_host, "device_ms": t_dev,
        "speedup": t_host / max(t_dev, 1e-9),
        "achieved_gbps": gbps, "identical": ok,
    }
    report["member_fused_beats_host"] = bool(fused and ok and t_dev < t_host)
    rows.append(("kernels.member_fused.n2M_m4k", t_dev * 1e3,
                 f"host_probe={t_host:.2f}ms device={t_dev:.2f}ms "
                 f"speedup={t_host / max(t_dev, 1e-9):.2f}x "
                 f"bw={gbps:.1f}GB/s identical={ok} fused={fused}"))

    f = rng.normal(0, 100, n).astype(np.float32)
    f[::31] = np.nan
    tf = Table({"f": f, "j": j}, {}, "benchf")
    predf = land(Col("f") >= Param("p"), Col("j") < Param("q"))
    bindf = {"p": -5.5, "q": 90}
    be_f = PallasBackend(device_cutover=0, batch_cutover=0)
    be_f.attach_stats(ScanStats())
    progf = ScanEngine().compile(predf)
    gotf = be_f.scan(progf, tf, bindf)            # warm
    lane = int(be_f._stats.float_lane_scans) > 0

    def host_float():
        return (f >= np.float32(-5.5)) & (j < 90)

    t_np = time_ms(host_float, repeat=5)
    t_devf = time_ms(lambda: be_f.scan(progf, tf, bindf), repeat=5)
    okf = bool(np.array_equal(gotf, host_float()))
    report["float_heavy"] = {
        "rows": n, "float_lane": lane,
        "numpy_ms": t_np, "device_ms": t_devf,
        "speedup": t_np / max(t_devf, 1e-9), "identical": okf,
    }
    rows.append(("kernels.float_lane.n2M", t_devf * 1e3,
                 f"numpy={t_np:.2f}ms device={t_devf:.2f}ms "
                 f"speedup={t_np / max(t_devf, 1e-9):.2f}x "
                 f"identical={okf} key_lane={lane}"))

    out = Path("BENCH_scan.json")
    data = {}
    if out.exists():
        try:
            data = json.loads(out.read_text())
        except ValueError:
            data = {}
    data["kernels.member_float"] = report
    out.write_text(json.dumps(data, indent=2, sort_keys=True))
    return rows


def bench_kernels() -> List[tuple]:
    rows = []
    rng = np.random.default_rng(0)
    for n in (100_000, 1_000_000):
        cols = rng.integers(0, 1_000, (6, n)).astype(np.int32)
        env = {f"c{i}": cols[i] for i in range(6)}
        pred = land(Col("c0") >= 100, Col("c1") < 900, Col("c2").eq(Param("v")),
                    Col("c3") > 50)
        binding = {"v": 7}
        t_np = time_ms(lambda: eval_np(pred, env, binding, n=n))
        # compiled atom-program scan (the engine's numpy backend)
        table = Table(dict(env), {}, "bench")
        eng = ScanEngine()
        eng.scan(pred, table, binding)
        t_eng = time_ms(lambda: eng.scan(pred, table, binding))
        order = {f"c{i}": i for i in range(6)}
        # jit'd fused scan (XLA CPU — the same graph the TPU kernel implements)
        from repro.core.expr import eval_jnp

        jcols = {k: jnp.asarray(v) for k, v in env.items()}
        f = jax.jit(lambda e: eval_jnp(pred, e, binding))
        f(jcols)[0].block_until_ready() if hasattr(f(jcols), "block_until_ready") else None
        t_jax = time_ms(lambda: np.asarray(f(jcols)))
        # interpret-mode correctness check on a slice (interpret is slow)
        m = scan_mask(cols[:, :65536], pred, order, binding, interpret=True,
                      block_rows=1024)
        ok = (m == np.asarray(eval_np(pred, {k: v[:65536] for k, v in env.items()},
                                      binding, n=65536), bool)).all()
        # ScanEngine pallas backend == numpy backend on a slice
        head = Table({k: v[:65536] for k, v in env.items()}, {}, "bench")
        pl_eng = ScanEngine(backend="pallas", interpret=True)
        eng_ok = bool(
            (pl_eng.scan(pred, head, binding) == eng.scan(pred, head, binding)).all()
        )
        rows.append((f"kernels.pred_scan.n{n}", t_np * 1e3,
                     f"numpy={t_np:.1f}ms engine={t_eng:.1f}ms jit={t_jax:.1f}ms "
                     f"pallas_interpret_ok={ok} engine_pallas_ok={eng_ok}"))
    rows += _bench_batched(rng)
    rows += _bench_member_float(rng)

    # membership probe (jit path = sorted binary search, the TPU-kernel analogue)
    vals = rng.integers(0, 100_000, 1_000_000).astype(np.int32)
    vset = rng.choice(100_000, 5_000, replace=False).astype(np.int32)
    t_np = time_ms(lambda: np.isin(vals, vset))
    jv, js = jnp.asarray(vals), jnp.asarray(np.sort(vset))
    g = jax.jit(
        lambda a, s: s[jnp.clip(jnp.searchsorted(s, a), 0, len(s) - 1)] == a
    )
    np.asarray(g(jv, js))
    t_jax = time_ms(lambda: np.asarray(g(jv, js)))
    ok = bool((probe(vals[:4096], vset, interpret=True)
               == np.isin(vals[:4096], vset)).all())
    rows.append(("kernels.membership.n1M_m5k", t_np * 1e3,
                 f"numpy={t_np:.1f}ms jit={t_jax:.1f}ms pallas_interpret_ok={ok}"))
    return rows
