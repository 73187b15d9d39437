"""Partition-granular lineage execution: one scan path for single-node,
multi-core, and device-sharded queries.

:class:`PartitionExecutor` is the fan-out layer above the ScanEngine.  Its
``scan`` method is a drop-in for :meth:`ScanEngine.scan` (same signature,
bit-identical masks) and is what ``PredTrace`` / ``refine`` plug in when
partitioning, a worker pool, or a device mesh is configured:

* **Zone-map pruning** (``scan.prune_zone_maps``) runs first on partitioned
  tables — partitions whose per-column min/max statistics prove no row can
  match are never touched.
* **Surviving partitions** are scanned as slices, either serially or fanned
  out across a thread pool (NumPy releases the GIL in the comparison
  kernels); per-partition masks are merged deterministically by partition
  index, so worker scheduling never changes an answer.
* **Device meshes** (``distrib/sharding.py``): with a mesh, the int32-exact
  columns of each table are device_put row-sharded across the (pod, data)
  axes and scanned by the engine's structure-cached ``jit_scan``.  Whether
  a scan is in that exact fragment is decided from the predicate and its
  binding before launch (:meth:`_DeviceTable.exact`); the host engine
  answers the rest, and a device error raises.  Zone-map pruning still
  short-circuits all-pruned scans before any device work.  V-sets are
  padded to the next power of two with a sentinel so shrinking sets
  between refinement iterations never retrace.

``distributed_refine`` — Algorithm 3 on sharded data — is now a thin wrapper:
it routes the shared :func:`repro.core.iterative.refine` fixpoint through a
``PartitionExecutor`` scan, replacing the former ``ShardedCatalog``'s
duplicated refinement loop (which predated the ScanEngine) entirely.
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional, Tuple

import numpy as np

from .expr import (BinOp, Col, Expr, IsIn, Lit, Param, ParamSet, UnaryOp,
                   params_of)
from .iterative import IterativePlan, refine
from .lineage import LineageAnswer
from .scan import ScanEngine, default_engine
from .table import (PartitionedTable, Table, alive_runs, partition_table,
                    table_uid)

INT32_MIN, INT32_MAX = -(2**31), 2**31 - 1

# JAX keeps 64-bit types off by default, so device columns are int32.  This
# value pads V-set bindings and table rows; device columns never hold it
# (see _DeviceTable), so a padded set matches no real row
SENTINEL = np.int32(INT32_MIN)

# don't spin up threads for scans smaller than this many surviving rows —
# the pool dispatch overhead would dominate
MIN_PARALLEL_ROWS = 16384


def _pad_rows(n: int, shards: int) -> int:
    return ((n + shards - 1) // shards) * shards


def _int_value(v) -> bool:
    """A scalar that compares with an int32 lane exactly as numpy compares
    it with the host column."""
    return (isinstance(v, (bool, int, np.integer, np.bool_))
            and INT32_MIN <= int(v) <= INT32_MAX)


def _int_set(v) -> bool:
    """A set binding whose membership test on an int32 lane is exact."""
    if isinstance(v, (list, tuple)):
        return all(_int_value(x) for x in v)
    return (isinstance(v, np.ndarray) and v.ndim == 1
            and (v.dtype.kind in "iub" or v.size == 0))


def _device_set(v) -> np.ndarray:
    """int32 device form of a set binding, padded to the next power of two
    with SENTINEL so shrinking sets between refinement iterations don't
    retrace.  Members outside the device columns' range can never match,
    so dropping them is exact."""
    a = np.asarray(v)
    if a.dtype.kind == "u":
        a = a[a <= INT32_MAX]  # filter before the cast can wrap
    a = a.astype(np.int64)
    a = a[(a > INT32_MIN) & (a <= INT32_MAX)]
    padded = np.full(1 << max(int(len(a)) - 1, 0).bit_length(), SENTINEL,
                     np.int32)
    padded[:len(a)] = a
    return padded


class _DeviceTable:
    """Row-sharded device-resident view of one table's int32-exact columns.

    Only integer and bool columns whose values lie in ``(INT32_MIN,
    INT32_MAX]`` are uploaded: on the device they are int32 and compare
    exactly, and none of them holds SENTINEL.  Float columns stay on the
    host, because float64 compared in float32 gives different answers."""

    def __init__(self, table: Table, mesh, axes: Tuple[str, ...],
                 engine: ScanEngine):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        self.engine = engine
        self.axes = tuple(a for a in axes if a in mesh.axis_names)
        shards = 1
        for a in self.axes:
            shards *= mesh.shape[a]
        n = table.nrows
        self.nrows = n
        self.padded = _pad_rows(max(n, shards), shards)
        sh = NamedSharding(
            mesh, P(self.axes if len(self.axes) > 1 else self.axes[0])
        )
        self.cols: Dict[str, object] = {}
        for c in table.columns:
            arr = np.asarray(table.cols[c])
            if arr.dtype.kind not in "iub":
                continue
            if n and not (int(arr.min()) > INT32_MIN
                          and int(arr.max()) <= INT32_MAX):
                continue
            padded = np.full(self.padded, SENTINEL, np.int32)
            padded[:n] = arr
            self.cols[c] = jax.device_put(padded, sh)

    def exact(self, pred: Expr, binding: Dict[str, object]) -> bool:
        """Does the jitted scan of ``pred`` give numpy's answer bit for bit?
        True for and/or/not over comparisons and ``IN`` tests between
        device columns, in-range int scalars and int sets — the fragment
        lineage predicates live in.  Arithmetic (int32 overflow), floats,
        UDFs and conditionals stay on the host."""

        def scalar(x: Expr) -> bool:
            if isinstance(x, Col):
                return x.name in self.cols
            if isinstance(x, Lit):
                return _int_value(x.value)
            if isinstance(x, Param):
                return _int_value(binding.get(x.name))
            if isinstance(x, UnaryOp) and x.op == "year":
                return scalar(x.operand)
            return False

        def is_set(x) -> bool:
            if isinstance(x, (Param, ParamSet)):
                return _int_set(binding.get(x.name))
            return isinstance(x, tuple) and _int_set(x)

        def boolean(x: Expr) -> bool:
            if isinstance(x, BinOp):
                if x.op in ("and", "or"):
                    return boolean(x.left) and boolean(x.right)
                if x.op == "==" and (is_set(x.right) or is_set(x.left)):
                    other = x.left if is_set(x.right) else x.right
                    return scalar(other)
                return (x.op in ("==", "!=", "<", "<=", ">", ">=")
                        and scalar(x.left) and scalar(x.right))
            if isinstance(x, UnaryOp) and x.op == "not":
                return boolean(x.operand)
            if isinstance(x, IsIn):
                return scalar(x.operand) and is_set(x.values)
            return isinstance(x, Lit) and isinstance(x.value, (bool, np.bool_))

        return boolean(pred)

    def scan(self, pred: Expr, binding: Dict[str, object]) -> np.ndarray:
        """Jit-compiled predicate scan over the sharded columns -> host mask.
        Callers check :meth:`exact` first."""
        import jax.numpy as jnp

        b = {}
        for k in params_of(pred):
            v = binding[k]
            b[k] = jnp.asarray(_device_set(v)) if _int_set(v) else int(v)
        mask = self.engine.jit_scan(pred)(self.cols, b)
        m = np.asarray(mask)
        if m.ndim == 0:  # constant predicate (True/False)
            m = np.broadcast_to(m, (self.padded,))
        return m[: self.nrows]

    def devices(self) -> set:
        """Ids of the devices holding shards of the uploaded columns."""
        return {d.id for a in self.cols.values() for d in a.sharding.device_set}


class PartitionExecutor:
    """Fans predicate scans out over table partitions (and devices).

    One executor serves one PredTrace / refine loop; it shares the owning
    ScanEngine, so compiled atom programs, jit scans, and partition-slice
    views are reused across every scan it dispatches."""

    def __init__(self, engine: Optional[ScanEngine] = None,
                 max_workers: Optional[int] = None,
                 mesh=None, mesh_axes: Tuple[str, ...] = ("pod", "data"),
                 min_parallel_rows: Optional[int] = None):
        self.engine = engine or default_engine()
        self.mesh = mesh
        self.mesh_axes = mesh_axes
        self.max_workers = max_workers
        # None -> measured lazily on first fan-out decision (pool round-trip
        # overhead vs. per-row scan cost on *this* host — core/dispatch.py);
        # an explicit int is honored verbatim (tests pin 0 to force fan-out)
        self._min_parallel_rows = min_parallel_rows
        self._pool: Optional[ThreadPoolExecutor] = None
        # table uid -> (weakref, _DeviceTable); weakref eviction keeps dead
        # tables from pinning device memory
        self._device: Dict[int, Tuple[weakref.ref, _DeviceTable]] = {}
        # reentrancy: scan() may be called from many service/request threads
        # at once; the lock guards lazy pool creation and the device-table
        # install so racing callers never leak a second pool or overwrite
        # each other's device uploads
        self._lock = threading.Lock()

    # ------------------------------------------------------------------ #
    @property
    def min_parallel_rows(self) -> int:
        """Surviving-row threshold below which fan-out is not worth the pool
        round-trip.  Measured once per executor unless set explicitly."""
        v = self._min_parallel_rows
        if v is None:
            pool = self.pool()
            if pool is None:
                v = MIN_PARALLEL_ROWS
            else:
                from .dispatch import parallel_scan_cutover

                v = parallel_scan_cutover(pool, pool._max_workers)
            self._min_parallel_rows = v
        return v

    @min_parallel_rows.setter
    def min_parallel_rows(self, v: Optional[int]) -> None:
        self._min_parallel_rows = v

    def pool(self) -> Optional[ThreadPoolExecutor]:
        if self.max_workers == 0:
            return None
        if self._pool is None:
            workers = self.max_workers or min(os.cpu_count() or 1, 16)
            if workers <= 1:
                return None
            with self._lock:
                if self._pool is None:
                    self._pool = ThreadPoolExecutor(
                        max_workers=workers,
                        thread_name_prefix="predtrace-part",
                    )
        return self._pool

    def close(self) -> None:
        with self._lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)

    def __del__(self):  # pragma: no cover - GC safety net
        try:
            if self._pool is not None:
                self._pool.shutdown(wait=False)
        except Exception:
            pass

    # ------------------------------------------------------------------ #
    def scan(self, pred: Expr, table: Table,
             binding: Optional[Dict[str, object]] = None) -> np.ndarray:
        """Boolean mask of ``pred`` over ``table`` — drop-in for
        ``ScanEngine.scan`` with partition pruning, worker fan-out, and the
        device path layered on top.  Answers are identical by construction:
        pruning only skips partitions proved empty, and per-partition masks
        are merged by partition index."""
        binding = binding or {}
        self.engine.stats.bump(scans=1)
        if self.mesh is not None:
            return self._device_scan(pred, table, binding)
        plan = self.engine.partition_plan(pred, table, binding)
        if plan is None:
            return self.engine.backend.scan(
                self.engine.compile(pred), table, binding
            )
        return self._fanout_scan(pred, table, binding, plan)

    # ------------------------------------------------------------------ #
    def parallel_ratio(self) -> float:
        """Seeded marginal cost of the fan-out route relative to a serial
        scan: ``1/W`` of the work per wall-second with W pool workers,
        floored at 0.5 (matching the dispatch probe's savable fraction)."""
        pool = self.pool()
        workers = pool._max_workers if pool is not None else 1
        return min(1.0 / max(workers, 2), 0.5)

    def _parallel_seed(self) -> Dict[str, float]:
        from .cost import PARALLEL_CAL_ATOMS

        return {"cutover": float(self.min_parallel_rows) * PARALLEL_CAL_ATOMS,
                "ratio": self.parallel_ratio()}

    def _fanout_scan(self, pred: Expr, table: PartitionedTable,
                     binding: Dict[str, object], plan) -> np.ndarray:
        from .cost import active_recorder, prog_atoms

        prog, alive = plan
        n = table.nrows
        backend = self.engine.backend
        cm = self.engine.cost_model
        A = prog_atoms(prog)
        carry = getattr(backend, "fused_carry_ok", None)
        if carry is None:
            # serial shortcut before any run/bounds bookkeeping: even if
            # every surviving partition were full, the fan-out estimate must
            # lose to the serial one before any pool round-trip is worth it
            cap = float(np.count_nonzero(alive) * table.part_rows) * A
            if (self.max_workers == 0
                    or cm.estimate("parallel", cap, **self._parallel_seed())
                    >= cm.estimate("serial", cap)):
                return self.engine._scan_pruned(prog, table, binding, plan)
        runs = alive_runs(alive)
        if not runs:
            self.engine.record_prune(0, len(alive))
            return np.zeros(n, dtype=bool)
        pr = table.part_rows
        bounds = [(p0 * pr, min(p1 * pr, n)) for p0, p1 in runs]
        pool = self.pool() if getattr(backend, "parallel_safe", False) else None
        total = sum(hi - lo for lo, hi in bounds)
        # device carrier: when the backend's fused kernel can take the whole
        # scan, launch it over the full table — the kernel's in-grid zone
        # check re-prunes every block (a superset of the partition pruning
        # already computed), so surviving partitions are never sliced and
        # the per-partition jit scans disappear into one launch.  The carry
        # verdict is the backend's cost-model compare (fused_carry_ok).
        carried = carry is not None and carry(prog, table, binding, total)
        refused = None
        if carry is not None and not carried:
            # the device carry was considered and refused by the backend's
            # own cost compare — surface that exactly like the store's
            # ranked-walk fallback: the decision's ``fallback_from`` names
            # the refused route once ``done(route=...)`` reports what ran
            self.engine.stats.bump(carry_refused=1)
            if active_recorder() is not None:
                refused = cm.note(
                    f"scan:{getattr(table, 'name', None) or '?'}",
                    "device", float(total) * A,
                    meta={"rows": int(n), "atoms": int(A),
                          "rows_alive": int(total), "carry": False},
                    alternatives=[("serial", float(n) * A),
                                  ("pruned", float(total + pr) * A),
                                  ("parallel", float(total) * A,
                                   self._parallel_seed())])
        if carried:
            ns = int(np.count_nonzero(alive))
            self.engine.record_prune(ns, len(alive) - ns)
            ch = cm.note(f"scan:{getattr(table, 'name', None) or '?'}",
                         "device", float(total) * A,
                         meta={"rows": int(n), "atoms": int(A),
                               "rows_alive": int(total), "carry": True})
            t0 = time.perf_counter()
            mask = backend.scan(prog, table, binding)
            ch.done(time.perf_counter() - t0)
            return mask
        if (pool is None or len(bounds) <= 1
                or cm.estimate("parallel", float(total) * A,
                               **self._parallel_seed())
                >= min(cm.estimate("serial", float(n) * A),
                       cm.estimate("pruned", float(total + pr) * A))):
            # small / contiguous work: the engine's serial pruned scan picks
            # the cheapest shape (slice, gather, or full scan)
            t0 = time.perf_counter()
            mask = self.engine._scan_pruned(prog, table, binding, plan)
            if refused is not None:
                # visibility-only: _scan_pruned records and observes its own
                # decision for the same wall time
                refused.done(time.perf_counter() - t0, route="pruned",
                             work=float(total + pr) * A, observe=False)
            return mask
        ns = int(np.count_nonzero(alive))
        self.engine.record_prune(ns, len(alive) - ns)
        if refused is not None:
            ch = refused
        else:
            ch = cm.note(f"scan:{getattr(table, 'name', None) or '?'}",
                         "parallel", float(total) * A, meta={
                             "rows": int(n), "atoms": int(A),
                             "rows_alive": int(total), "alive": ns},
                         alternatives=[("serial", float(n) * A),
                                       ("pruned", float(total + pr) * A)])
        t0 = time.perf_counter()
        mask = self.fanout_bounds(prog, table, binding, bounds, pool)
        ch.done(time.perf_counter() - t0,
                route="parallel" if refused is not None else None,
                work=float(total) * A if refused is not None else None)
        return mask

    def fanout_bounds(self, prog, table: Table, binding: Dict[str, object],
                      bounds, pool) -> np.ndarray:
        """Pool fan-out over surviving partition runs; also the hand-off
        target of ``ScanEngine._scan_pruned`` when an engine carries this
        executor as its ``fanout`` hook."""
        backend = self.engine.backend
        self.engine.stats.bump(fanout_scans=1)
        mask = np.zeros(table.nrows, dtype=bool)
        # slices are created (and cached) serially; workers only evaluate
        subs = [self.engine.partition_slice(table, lo, hi) for lo, hi in bounds]
        results = pool.map(lambda sub: backend.scan(prog, sub, binding), subs)
        for (lo, hi), m in zip(bounds, results):
            mask[lo:hi] = m
        return mask

    # ------------------------------------------------------------------ #
    def _device_scan(self, pred: Expr, table: Table,
                     binding: Dict[str, object]) -> np.ndarray:
        # zone maps still short-circuit provably-empty scans before any
        # device work; partial pruning stays on-device (slicing per shape
        # would retrace the jit)
        plan = self.engine.partition_plan(pred, table, binding)
        if plan is not None and not plan[1].any():
            self.engine.record_prune(0, len(plan[1]))
            return np.zeros(table.nrows, dtype=bool)
        dt = self._device_table(table)
        if not dt.exact(pred, binding):
            # outside the exact int32 fragment: the host engine answers
            if plan is not None:
                return self._fanout_scan(pred, table, binding, plan)
            return self.engine.backend.scan(
                self.engine.compile(pred), table, binding
            )
        if plan is not None:
            # partial pruning stays on-device: the full sharded scan runs
            self.engine.record_prune(len(plan[1]), 0)
        self.engine.stats.bump(mesh_scans=1)
        return dt.scan(pred, binding)

    def placement(self) -> Dict[str, set]:
        """Device ids holding each uploaded table's columns, by table name
        (or uid) — where the mesh path really put the data."""
        return {getattr(t, "name", None) or str(tk): dt.devices()
                for tk, (ref, dt) in list(self._device.items())
                if (t := ref()) is not None}

    def _device_table(self, table: Table) -> _DeviceTable:
        tk = table_uid(table)
        entry = self._device.get(tk)
        if entry is not None and entry[0]() is table:
            return entry[1]
        with self._lock:
            entry = self._device.get(tk)
            if entry is not None and entry[0]() is table:
                return entry[1]
            dt = _DeviceTable(table, self.mesh, self.mesh_axes, self.engine)
            ref = weakref.ref(table,
                              lambda _, k=tk, d=self._device: d.pop(k, None))
            self._device[tk] = (ref, dt)
        return dt


def distributed_refine(
    ip: IterativePlan,
    catalog: Dict[str, Table],
    binding: Dict[str, object],
    mesh=None,
    max_iters: int = 32,
    engine: Optional[ScanEngine] = None,
    num_partitions: Optional[int] = None,
    max_workers: Optional[int] = None,
) -> LineageAnswer:
    """Algorithm 3 phase 4 with partition/device-sharded scans.

    The fixpoint itself is the shared :func:`repro.core.iterative.refine`
    loop; only the scan backend differs — a :class:`PartitionExecutor` that
    routes every predicate through the shared ScanEngine (compiled atom
    programs on the host path, structure-cached ``jit_scan`` on the mesh
    path)."""
    t0 = time.perf_counter()
    cat = catalog
    if num_partitions is not None:
        cat = {k: partition_table(t, num_partitions=num_partitions)
               for k, t in catalog.items()}
    pexec = PartitionExecutor(engine or default_engine(), mesh=mesh,
                              max_workers=max_workers)
    try:
        rr = refine(ip, cat, binding, max_iters, scan=pexec.scan)
    finally:
        pexec.close()
    ans = LineageAnswer(dict(rr.lineage), time.perf_counter() - t0)
    ans.detail["iterations"] = rr.iterations
    return ans
