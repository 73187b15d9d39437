#!/usr/bin/env python
"""Bring-up check: PredTrace's lineage path on a TPU, through the entry
points a user calls, at TPC-H SF1, every answer checked against numpy.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # the sharded mesh path on four chips
  JAX_PLATFORMS=cpu PYTHONPATH=src python chip_smoke.py --rehearse --sf 0.002

One chip, in order:
  (a) the device JAX sees and the compile-cache directory;
  (b) TPC-H data (dbgen-lite, seed 1);
  (c) ``repro.launch.lineage_serve`` with ``--backend pallas`` serving
      q3, q5 and q10 to 4 clients (64 Zipf requests); every answer is
      compared with a numpy-backend PredTrace on the same data;
  (d) a forced-kernel engine (``ScanEngine(backend="pallas",
      interpret=False)``): ``query`` and a 32-row ``query_batch`` on each
      pipeline, again with ``store=True``, and one Algorithm-3 query each,
      all compared with numpy; then direct K=32 launches at lineitem width
      with and without a set atom, compared with numpy.

``--chips 4`` runs only the pipelines of (d) through ``PredTrace(...,
mesh=..., num_partitions=...)`` over four devices, and their numpy oracle.

Every check that fails raises, so the script exits non-zero; the last line
of a passing run is ``{"ok": true, "device": {...}}``.  Without a TPU it
stops at (a) and says what JAX found.  ``--rehearse`` runs the same phases
on whatever JAX finds, with interpret-mode kernels, and prints no result
line: a rehearsal is not a chip run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

QUERIES = ("q3", "q5", "q10")
BATCH = 32  # query_batch rows, and bindings of the direct launches
SERVE_REQUESTS, SERVE_CLIENTS = 64, 4
MESH_PARTITIONS = 16


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


class Phases:
    """Per-phase wall seconds (host clock; information, not metrics)."""

    def __init__(self):
        self.seconds = {}

    @contextmanager
    def __call__(self, name: str):
        log(f"phase {name}")
        t0 = time.perf_counter()
        yield
        self.seconds[name] = time.perf_counter() - t0
        log(f"phase {name} done in {self.seconds[name]:.3f} s")


def lineage_of(ans):
    return {t: set(np.asarray(v).tolist()) for t, v in ans.lineage.items()}


def same_lineage(a, b) -> bool:
    la, lb = lineage_of(a), lineage_of(b)
    return all(la.get(t, set()) == lb.get(t, set()) for t in set(la) | set(lb))


class Checks:
    """Counts answers compared with the numpy oracle; raises on the first
    mismatch so no phase can report success after a wrong answer."""

    def __init__(self):
        self.n = 0

    def expect(self, ok: bool, what: str) -> None:
        self.n += 1
        if not ok:
            raise AssertionError(f"answer differs from numpy: {what}")


class Oracle:
    """A numpy-backend PredTrace per pipeline (the reference answers)."""

    def __init__(self, db, q: str):
        from repro.core import Executor, PredTrace
        from repro.tpch import ALL_QUERIES

        self.db, self.q = db, q
        self.plan = lambda: ALL_QUERIES[q](db)
        self.stats = Executor(db).run(self.plan()).stats
        self.pt = PredTrace(db, self.plan())
        self.pt.infer(stats=self.stats)
        self.pt.run()
        self.nrows = self.pt.exec_result.output.nrows
        self._answers = {}
        self._iterative = None

    def query(self, r: int):
        if r not in self._answers:
            self._answers[r] = self.pt.query(r)
        return self._answers[r]

    def query_iterative(self, r: int):
        from repro.core import PredTrace

        if self._iterative is None:
            self._iterative = PredTrace(self.db, self.plan())
            self._iterative.infer_iterative()
            self._iterative.run_unmodified()
        return self._iterative.query_iterative(r)


def device_info(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    log(f"device platform={d.platform} kind={d.device_kind} count={len(devs)}")
    if d.platform != "tpu" and not rehearse:
        raise SystemExit(
            f"chip_smoke: no TPU: JAX found {len(devs)} {d.platform} "
            f"device(s) of kind {d.device_kind!r}")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} devices; "
                         f"JAX found {len(devs)}")
    return info


def serve_phase(db, sf: float, oracles, checks: Checks) -> None:
    """(c): the serving entry point with the device backend."""
    from repro.launch import lineage_serve

    out = lineage_serve.main(
        ["--sf", str(sf), "--seed", "1", "--backend", "pallas",
         "--queries", ",".join(QUERIES), "--requests", str(SERVE_REQUESTS),
         "--clients", str(SERVE_CLIENTS)], db=db)
    for (q, r), ans in zip(out["requests"], out["answers"]):
        checks.expect(same_lineage(ans, oracles[q].query(r)),
                      f"served {q} row {r}")
    log(f"served answers equal numpy: {len(out['answers'])} of "
        f"{len(out['requests'])}")
    routes = {}
    for st in out["scan_stats"].values():
        for k, v in st.items():
            if isinstance(v, int) and v:
                routes[k] = routes.get(k, 0) + v
    log(f"served route histogram: {json.dumps(routes, sort_keys=True)}")


def pipeline_checks(db, oracles, checks: Checks, make_pt) -> None:
    """The pipelines of (d): ``query``, a 32-row ``query_batch``, both again
    with ``store=True``, and one Algorithm-3 query, each vs numpy.
    ``make_pt(plan, store)`` builds the PredTrace under test."""
    from repro.tpch import ALL_QUERIES

    for q in QUERIES:
        orc = oracles[q]
        nr = orc.nrows
        rows = sorted({0, nr // 2, nr - 1})
        batch = [i % nr for i in range(BATCH)]
        for store in (False, True):
            pt = make_pt(ALL_QUERIES[q](db), store)
            pt.infer(stats=orc.stats)
            pt.run()
            for r in rows:
                checks.expect(same_lineage(pt.query(r), orc.query(r)),
                              f"{q} store={store} query({r})")
            for r, ans in zip(batch, pt.query_batch(batch)):
                checks.expect(same_lineage(ans, orc.query(r)),
                              f"{q} store={store} query_batch row {r}")
            pt.close()
        pt = make_pt(ALL_QUERIES[q](db), False)
        pt.infer_iterative()
        pt.run_unmodified()
        checks.expect(same_lineage(pt.query_iterative(0),
                                   orc.query_iterative(0)),
                      f"{q} query_iterative(0)")
        pt.close()
        log(f"{q}: {len(rows)} queries + {BATCH}-row batch, store off/on, "
            f"and Algorithm 3 equal numpy")


def direct_launches(db, backend, checks: Checks) -> None:
    """One fused K=32 launch at lineitem width without and with a set atom,
    through ``PallasBackend._launch``, vs numpy."""
    from repro.core.scan import OPS, _SetOps
    from repro.kernels.pred_filter import search_iters

    li = db["lineitem"]
    names = ("l_orderkey", "l_partkey", "l_suppkey", "l_quantity")
    slab = np.stack([np.asarray(li.cols[c]).astype(np.int32) for c in names])
    rng = np.random.default_rng(1)
    n = slab.shape[1]
    entry = backend._build_entry(slab)
    # an orderkey window (sorted column: most blocks prune in-grid) plus
    # partkey and quantity compares, per binding
    start = rng.integers(int(slab[0].min()), int(slab[0].max()), BATCH)
    thr = np.stack([start, start + max(n // 100, 1),
                    rng.integers(1, int(slab[1].max()) + 1, BATCH),
                    rng.integers(0, 50, BATCH)], axis=1).astype(np.int32)
    atoms = ((0, OPS[">="]), (0, OPS["<"]), (1, OPS["<"]), (3, OPS[">"]))
    want = ((slab[0][None] >= thr[:, :1]) & (slab[0][None] < thr[:, 1:2])
            & (slab[1][None] < thr[:, 2:3]) & (slab[3][None] > thr[:, 3:4]))

    t0 = time.perf_counter()
    got = backend._launch(entry, atoms, thr)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    backend._launch(entry, atoms, thr)
    warm = time.perf_counter() - t0
    checks.expect(np.array_equal(got, want), "direct K=32 launch")
    log(f"direct launch {n} rows x {len(atoms)} atoms x {BATCH} bindings "
        f"equals numpy ({int(want.sum())} hits); first call (compile "
        f"included) {first:.3f} s, warm {warm:.3f} s")

    # ragged per-binding suppkey sets, one of them empty
    sets = [np.unique(rng.integers(1, int(slab[2].max()) + 1,
                                   int(rng.integers(0, 1500))))
            .astype(np.int32) for _ in range(BATCH)]
    sets[0] = np.zeros(0, np.int32)
    lens = np.array([s.size for s in sets], np.int32)
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    ops = _SetOps((2,), np.concatenate(sets).astype(np.int32),
                  offs[:, None], lens[:, None], search_iters(int(lens.max())))
    want_s = want & np.stack([np.isin(slab[2], s) for s in sets])
    t0 = time.perf_counter()
    got_s = backend._launch(entry, atoms, thr, set_ops=ops)
    first = time.perf_counter() - t0
    checks.expect(np.array_equal(got_s, want_s), "direct K=32 set launch")
    log(f"direct set launch ({int(lens.sum())} keys in {BATCH} segments) "
        f"equals numpy ({int(want_s.sum())} hits); first call (compile "
        f"included) {first:.3f} s")


def nonzero(stats) -> dict:
    return {k: v for k, v in sorted(stats.items())
            if isinstance(v, int) and v}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (SF1 unless rehearsing)")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on any device with interpret-mode kernels; "
                         "prints no result line")
    args = ap.parse_args(argv)
    phase = Phases()
    checks = Checks()

    with phase("a:device"):
        info = device_info(args.chips, args.rehearse)
        from repro.launch.compile_cache import setup_compile_cache

        log(f"compile cache: {setup_compile_cache()}")

    with phase("b:data"):
        from repro.tpch import generate

        db = generate(sf=args.sf, seed=1)
        log(f"TPC-H sf={args.sf} seed=1: lineitem "
            f"{db['lineitem'].nrows} rows")
        oracles = {q: Oracle(db, q) for q in QUERIES}
        log("numpy oracle output rows: " + ", ".join(
            f"{q}={o.nrows}" for q, o in oracles.items()))

    from repro.core import PredTrace, ScanEngine

    if args.chips == 4:
        import jax

        mesh = jax.make_mesh((4,), ("data",))
        placements = {}

        def make_mesh_pt(plan, store):
            pt = PredTrace(db, plan, store=store or None, mesh=mesh,
                           num_partitions=MESH_PARTITIONS)
            placements[id(pt)] = pt
            return pt

        with phase("mesh:pipelines"):
            pipeline_checks(db, oracles, checks, make_mesh_pt)
        ids = {d.id for d in jax.devices()[:4]}
        mesh_scans = 0
        for pt in placements.values():
            mesh_scans += pt.scan_engine.stats.mesh_scans
            for tab, devs in pt.partition_exec.placement().items():
                if devs != ids:
                    raise AssertionError(f"{tab} columns on devices "
                                         f"{sorted(devs)}, not {sorted(ids)}")
        placed = sorted({t for pt in placements.values()
                         for t in pt.partition_exec.placement()})
        log(f"mesh: tables {placed} sharded over devices {sorted(ids)}; "
            f"mesh_scans={mesh_scans}")
        if mesh_scans == 0:
            raise AssertionError("no scan ran on the mesh")
    else:
        with phase("c:serve"):
            serve_phase(db, args.sf, oracles, checks)
        with phase("d:forced-kernel"):
            eng = ScanEngine(backend="pallas", interpret=args.rehearse)
            pipeline_checks(db, oracles, checks,
                            lambda plan, store: PredTrace(
                                db, plan, store=store or None,
                                scan_engine=eng))
            st = eng.stats()
            log(f"forced-kernel route histogram: "
                f"{json.dumps(nonzero(st))}")
            for counter in ("device_scans", "member_fused_scans"):
                if not st[counter]:
                    raise AssertionError(f"{counter} is 0: the kernel "
                                         f"never ran on this path")
            direct_launches(db, eng.backend, checks)
            log(f"route histogram after direct launches: "
                f"{json.dumps(nonzero(eng.stats()))}")

    log(f"{checks.n} answers equal numpy; phase seconds "
        + json.dumps({k: round(v, 3) for k, v in phase.seconds.items()}))
    if args.rehearse:
        log("rehearsal passed (not a chip run: no result line)")
        return 0
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
