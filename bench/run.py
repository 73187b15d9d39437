#!/usr/bin/env python3
"""Benchmark harness: one cell of BENCHMARK.json, one run, one result line.

  python3 bench/run.py --workload sf1-clicks --seed 7 --seconds 50 --trace 0

Set-up builds the cell's configuration (``bench/configs/<name>.json``):
TPC-H data from ``--seed`` (dbgen-lite), a copy of it for the reference,
each pipeline executed and registered as a ``PredTrace`` with the
configured scan engine, and every kernel shape the window will use warmed
by asking its rows once.  The window then drives
``LineageService.submit_many`` with the cell's traffic mix
(``bench/traffic/<name>.json``, read by ``bench/traffic.py``) for
``--seconds``, timed from the client side.  Afterwards every sampled answer
is compared with the configuration's plain reference
(``bench/reference/<stem>.py``, ``registry.reference``); see
``bench/check.py``.

The metrics are the cell's end-to-end ones, or with ``--trace 1`` its
per-layer ones from a window measured under the JAX profiler; each is read
by ``bench/metrics/<name>.py``.  The last stdout line is the result JSON;
the last stderr lines list each compared number beside its limit.

Without a TPU (or with fewer chips than the cell asks for) the harness exits
non-zero and prints no result.  ``--rehearse`` runs anywhere with
interpret-mode kernels and prints no result line (``--sf`` shrinks the
data); ``--control`` serves with a byte budget of 0, so answers take the
superset path.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from collections import deque  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, registry, roofline, trace  # noqa: E402
from bench import traffic as mixes  # noqa: E402
from bench.peaks import peaks  # noqa: E402

POLL_S = 0.001  # completion polling period of the collector
WAIT_AFTER_S = 60.0  # how long answers due in the window are waited for
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class NoDevice(SystemExit):
    """The cell's chips are not there: exit non-zero, print no result."""


# --------------------------------------------------------------------------- #
# device and compile cache
# --------------------------------------------------------------------------- #


def devices(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise NoDevice(f"bench: no TPU: JAX found {len(devs)} "
                       f"{devs[0].platform} device(s)")
    if len(devs) < chips:
        raise NoDevice(f"bench: the cell needs {chips} chips; JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping even sub-second compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts traces and backend compiles JAX reports while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_args, **_kw):
        if self.on and name in self.EVENTS:
            self.count += 1


# --------------------------------------------------------------------------- #
# the deployment
# --------------------------------------------------------------------------- #


class Deployment:
    """TPC-H data and the configured pipelines, executed and registered."""

    def __init__(self, cfg, seed: int, sf: float, interpret: bool,
                 control: bool):
        from repro.core import Executor, PredTrace, ScanEngine
        from repro.tpch import ALL_QUERIES, generate

        t0 = time.perf_counter()
        self.db = generate(sf=sf, seed=seed)
        # the reference's input: a copy taken before any pipeline runs
        self.source = {t: ({c: np.array(v) for c, v in tab.cols.items()},
                           {c: list(v) for c, v in tab.dicts.items()})
                       for t, tab in self.db.items()}
        self.seconds = {"data": time.perf_counter() - t0}
        t0 = time.perf_counter()
        self.pts = {}
        for q in cfg["pipelines"]:
            plan = ALL_QUERIES[q](self.db)
            stats = Executor(self.db).run(plan).stats
            engine = ScanEngine(backend=cfg["engine"]["backend"],
                                interpret=interpret)
            kw = {"store": True} if cfg["store"] else {}
            if control:
                kw["budget_bytes"] = 0  # every stage dropped: superset path
            pt = PredTrace(self.db, plan, scan_engine=engine, **kw)
            pt.infer(stats=stats)
            pt.run()
            self.pts[q] = pt
        self.out_rows = {q: int(pt.exec_result.output.nrows)
                         for q, pt in self.pts.items()}
        self.seconds["pipelines"] = time.perf_counter() - t0
        self.query_ms = {}

    def warm(self, widths, rows=()) -> None:
        """Every batch width the window can pad to, on every pipeline, then
        each ``(pipeline, row)`` of ``rows`` as a one-row query: a row's
        binding decides its predicate's shape (atoms, set operands), so the
        rows the window will ask are what covers its kernel shapes.  The
        service and its answer cache are not involved."""
        t0 = time.perf_counter()
        for q, pt in self.pts.items():
            n = self.out_rows[q]
            for w in sorted(widths):
                k = min(w, n)
                if k == 1:
                    pt.query(0)
                elif k > 1:
                    pt.query_batch(list(range(k)))
                if k >= n:
                    break
        took = {}
        for q, r in sorted(set(rows)):
            t1 = time.perf_counter()
            self.pts[q].query(r)
            took.setdefault(q, []).append(time.perf_counter() - t1)
        self.seconds["warm"] = time.perf_counter() - t0
        self.query_ms = {q: [round(float(np.median(v)) * 1e3, 1),
                             round(max(v) * 1e3, 1), len(v)]
                         for q, v in took.items()}

    def scan_stats(self):
        total = {}
        for pt in self.pts.values():
            for k, v in pt.scan_engine.stats().items():
                if isinstance(v, int):
                    total[k] = total.get(k, 0) + v
        return total

    def outputs(self, queries, group_keys):
        """The group-key tuple of every output row of each pipeline, by the
        reference's ``GROUP_KEYS``."""
        out = {}
        for q in queries:
            cols = self.pts[q].exec_result.output.cols
            keys = [np.asarray(cols[c]).tolist() for c in group_keys[q]]
            out[q] = (list(zip(*keys)) if keys
                      else [()] * self.out_rows[q])
        return out

    def source_changed(self) -> int:
        """Source columns and vocabularies that no longer equal the copy
        taken before the pipelines ran."""
        n = 0
        for t, (cols, dicts) in self.source.items():
            tab = self.db[t]
            n += sum(c not in tab.cols or not np.array_equal(tab.cols[c], v)
                     for c, v in cols.items())
            n += sum(list(tab.dicts.get(c, ())) != v for c, v in dicts.items())
        return n

    def close(self) -> None:
        for pt in self.pts.values():
            pt.close()


# --------------------------------------------------------------------------- #
# spans and launch records (traced runs only)
# --------------------------------------------------------------------------- #


class Instruments:
    """Host spans around the calls into each layer, and while ``recording``
    the rows (times bindings) that scan calls covered, the shapes of every
    kernel launch, and ``(rows asked, seconds)`` of every lineage query
    call."""

    def __init__(self):
        self.recording = False
        self.launches = []
        self.queries = []
        self.rows_scanned = 0
        self._lock = threading.Lock()

    def install(self) -> None:
        from jax.profiler import TraceAnnotation
        from repro.core import PredTrace, ScanEngine
        from repro.core.scan import PallasBackend
        from repro.core.store import IntermediateStore

        inst = self

        def span(cls, meth, name, rows=None, record=False, asked=None):
            orig = getattr(cls, meth)

            def wrapped(self, *a, **kw):
                t0 = time.perf_counter()
                with TraceAnnotation(name):
                    out = orig(self, *a, **kw)
                if inst.recording:
                    with inst._lock:
                        if asked is not None:
                            inst.queries.append(
                                (asked(a[0]), time.perf_counter() - t0))
                        if rows is not None:
                            inst.rows_scanned += rows(out)
                        if record:
                            sets = kw.get("set_ops", a[4] if len(a) > 4 else None)
                            inst.launches.append((a[0], tuple(a[1]),
                                                  np.array(a[2]), sets))
                return out

            setattr(cls, meth, wrapped)

        span(PredTrace, "query", "bench.query", asked=lambda row: 1)
        span(PredTrace, "query_batch", "bench.query", asked=len)
        span(ScanEngine, "scan", "bench.scan", rows=len)
        span(ScanEngine, "scan_batch", "bench.scan",
             rows=lambda masks: sum(len(m) for m in masks))
        span(IntermediateStore, "scan", "bench.scan", rows=len)
        span(PallasBackend, "scan_stored", "bench.scan_stored")
        span(PallasBackend, "_launch", "bench.launch", record=True)

    def launch_bytes(self) -> int:
        """Bytes the recorded launches needed (``bench/roofline.py``)."""
        slabs = {}
        total = 0
        for entry, atoms, thr, sets in self.launches:
            if id(entry) not in slabs:
                slabs[id(entry)] = np.asarray(entry.dev)
            s = None if sets is None else (sets.set_cols, sets.slab,
                                           sets.off, sets.len_)
            total += roofline.bytes_needed(slabs[id(entry)], entry.n, atoms,
                                           thr, s)
        return total


# --------------------------------------------------------------------------- #
# driving the service
# --------------------------------------------------------------------------- #


class Rec:
    """One request: pipeline, row, due time, handle, and its outcome."""

    __slots__ = ("q", "row", "due", "done", "req", "error")

    def __init__(self, q, row, due, req):
        self.q, self.row, self.due, self.req = q, row, due, req
        self.done = None
        self.error = None


class Collector(threading.Thread):
    """Stamps each request's completion time by polling, and keeps a sample
    of at most ``cap`` answers drawn from the seed (reservoir sampling)."""

    def __init__(self, cap: int, seed: int):
        super().__init__(name="bench-collector", daemon=True)
        self.rng = np.random.default_rng([seed, 2])
        self.cap = cap
        self.sample = []
        self.seen = 0
        self.outstanding = []
        self.all = []
        self._lock = threading.Lock()
        self._halt = threading.Event()

    def add(self, recs) -> None:
        with self._lock:
            self.outstanding.extend(recs)
            self.all.extend(recs)

    def run(self) -> None:
        while not self._halt.is_set():
            self.poll()
            time.sleep(POLL_S)

    def poll(self) -> int:
        now = time.monotonic()
        with self._lock:
            flags = [r.req.done() for r in self.outstanding]
            done = [r for r, f in zip(self.outstanding, flags) if f]
            if done:
                self.outstanding = [r for r, f in zip(self.outstanding, flags)
                                    if not f]
            left = len(self.outstanding)
        for r in done:
            r.done = now
            try:
                ans = r.req.result(timeout=0)
            except Exception as e:  # noqa: BLE001 - a failed request is data
                r.error = repr(e)
            else:
                self._keep(r, ans)
            r.req = None
        return left

    def _keep(self, r: Rec, ans) -> None:
        i = self.seen
        self.seen += 1
        if i < self.cap:
            self.sample.append((r.q, r.row, ans))
        else:
            j = int(self.rng.integers(0, i + 1))
            if j < self.cap:
                self.sample[j] = (r.q, r.row, ans)

    def drain(self, deadline: float) -> None:
        while self.poll() and time.monotonic() < deadline:
            time.sleep(POLL_S)
        self._halt.set()
        self.join()


def closed_loop(serve, mix, out_rows, seed, seconds, col: Collector):
    page = int(mix.get("page_rows", 1))
    t0 = time.monotonic()
    t_end = t0 + seconds
    errors = []
    passes = 0
    while time.monotonic() < t_end and not errors:
        svc = serve()  # each pass starts with a cold answer cache
        todo = deque(mixes.closed_pass(mix, out_rows, seed, passes))
        lock = threading.Lock()

        def client(svc=svc, todo=todo, lock=lock):
            try:
                while time.monotonic() < t_end:
                    with lock:
                        if not todo:
                            return
                        q, rows = todo.popleft()
                    for i in range(0, len(rows), page):
                        if time.monotonic() >= t_end:
                            return
                        part = rows[i:i + page]
                        sent = time.monotonic()
                        handles = svc.submit_many(part, q)
                        col.add([Rec(q, r, sent, h)
                                 for r, h in zip(part, handles)])
                        for h in handles:
                            try:
                                h.result(WAIT_AFTER_S)
                            except Exception:  # noqa: BLE001 - counted later
                                pass
            except BaseException as e:  # noqa: BLE001 - surfaced below
                errors.append(e)

        threads = [threading.Thread(target=client, name=f"bench-client-{i}",
                                    daemon=True)
                   for i in range(mix["clients"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join(seconds + WAIT_AFTER_S)
        passes += 1
    if errors:
        raise errors[0]
    return t0, t_end, {"passes": passes}


def window_rows(mix, out_rows, seed, seconds):
    """The ``(pipeline, row)`` questions the window will ask."""
    if mix["loop"] == "closed":
        return mixes.closed_rows(mix, out_rows, seed)
    return [(q, r) for _, q, r in mixes.open_schedule(mix, out_rows, seed,
                                                      seconds)]


def open_loop(serve, mix, out_rows, seed, seconds, col: Collector):
    svc = serve()
    sched = mixes.open_schedule(mix, out_rows, seed, seconds)
    t0 = time.monotonic() + 0.05
    late = []
    i = 0
    while i < len(sched):
        due = t0 + sched[i][0]
        j = i
        by_q = {}
        while j < len(sched) and sched[j][0] == sched[i][0]:
            by_q.setdefault(sched[j][1], []).append(sched[j][2])
            j += 1
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        for q, rows in by_q.items():
            sent = time.monotonic()
            col.add([Rec(q, r, due, h)
                     for r, h in zip(rows, svc.submit_many(rows, q))])
            late.append(sent - due)
        i = j
    lat = np.asarray(late) * 1e3
    return t0, t0 + seconds, {
        "sessions_per_s": mix["session_rate_per_s"], "requests": len(sched),
        "late_p50_ms": float(np.percentile(lat, 50)) if len(lat) else 0.0,
        "late_max_ms": float(lat.max()) if len(lat) else 0.0}


def drive(dep, cfg, mix, seed, seconds, cap, on_start=None, on_window=None):
    """One window; returns the records, the collector and timing facts."""
    from repro.core import LineageService

    services = []

    def serve():
        services.append(LineageService(dep.pts, **cfg["service"]))
        return services[-1]

    col = Collector(cap, seed)
    col.start()
    if on_start is not None:
        on_start()
    loop = {"closed": closed_loop, "open": open_loop}[mix["loop"]]
    before = dep.scan_stats()
    t0, t_end, facts = loop(serve, mix, dep.out_rows, seed, seconds, col)
    col.drain(t_end + WAIT_AFTER_S)
    t_drained = time.monotonic()
    if on_window is not None:
        on_window()
    after = dep.scan_stats()
    service = {}
    for svc in services:
        for k, v in svc.stats().items():
            if isinstance(v, int):
                service[k] = service.get(k, 0) + v
        svc.close()
    facts["drain_s"] = t_drained - t_end
    scan = {k: after[k] - before.get(k, 0) for k in after}
    return col, t0, t_end, service, scan, facts


# --------------------------------------------------------------------------- #
# latencies
# --------------------------------------------------------------------------- #


def latencies_ms(recs, t_end):
    horizon = t_end + WAIT_AFTER_S
    return np.asarray([((r.done if r.done is not None and r.error is None
                         else horizon) - r.due) * 1e3 for r in recs])


def by_pipeline(recs, t_end):
    lat = latencies_ms(recs, t_end)
    out = {}
    for q in sorted({r.q for r in recs}):
        v = lat[[r.q == q for r in recs]]
        out[q] = [len(v)] + [round(float(x), 1) for x in
                             (np.percentile(v, 50), np.percentile(v, 95), v.max())]
    return out


# --------------------------------------------------------------------------- #
# main
# --------------------------------------------------------------------------- #


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="any device, interpret-mode kernels, no result line")
    ap.add_argument("--sf", type=float, default=None,
                    help="TPC-H scale factor in place of the configuration's")
    ap.add_argument("--control", action="store_true",
                    help="serve with byte budget 0 (superset answers)")
    ap.add_argument("--dump-events", default=None,
                    help="write the traced window's records to this JSON file")
    return ap.parse_args(argv)


def run(argv=None):
    """One run; returns the result object."""
    args = parse(argv)
    sp = registry.spec()
    cell = registry.workload(sp, args.workload)
    cfg = registry.config(sp, cell["config"])
    ref = registry.reference(cfg)  # before any data: it must know each pipeline
    mix = registry.traffic(cell["traffic"])
    devs = devices(int(cell["chips"]), args.rehearse)
    import jax

    if not args.rehearse:
        log(f"compile cache {compile_cache()}")
    counter = CompileCounter()
    inst = Instruments() if args.trace else None
    if inst is not None:
        inst.install()
    sf = cfg["scale_factor"] if args.sf is None else args.sf
    dep = Deployment(cfg, args.seed, sf, interpret=args.rehearse,
                     control=args.control)
    rows = window_rows(mix, dep.out_rows, args.seed, args.seconds)
    if args.control:
        # the superset path answers a row about ten times slower: one row
        # per pipeline warms most kernel shapes, and the control is read
        # for its answers, not its times
        rows = sorted({q: (q, r) for q, r in rows}.values())
    dep.warm(mix["warm_batches"], rows)
    log(f"set-up phases {json.dumps({k: round(v, 3) for k, v in dep.seconds.items()})}; "
        f"output rows {json.dumps(dep.out_rows)}")
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    window_span = []

    def on_start():
        counter.on = True
        if tdir is not None:
            jax.profiler.start_trace(tdir)
            inst.recording = True
            window_span.append(jax.profiler.TraceAnnotation("bench.window"))
            window_span[0].__enter__()

    def on_window():
        counter.on = False
        if tdir is not None:
            window_span[0].__exit__(None, None, None)
            inst.recording = False
            jax.profiler.stop_trace()

    setup_s = time.perf_counter() - T_START
    col, t0, t_end, service, scan, facts = drive(
        dep, cfg, mix, args.seed, args.seconds, mix["check_sample"],
        on_start, on_window)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    recs = col.all
    failed = sum(1 for r in recs if r.error is not None or r.done is None)
    log(f"one-row query ms in warm-up [median, max, n]: {json.dumps(dep.query_ms)}")
    log(f"latency ms by pipeline [n, p50, p95, max]: "
        f"{json.dumps(by_pipeline(recs, t_end))}")
    answered = sum(1 for r in recs if r.error is None and r.done is not None
                   and r.done <= t_end)
    log(f"window: {len(recs)} requests, {answered} answered inside it, "
        f"{failed} failed or missing, compiles in window {counter.count}; "
        f"{json.dumps(facts)}")

    qs = sorted({q for q, _, _ in col.sample})
    outputs = dep.outputs(qs, ref.GROUP_KEYS)
    launch_bytes = None
    records = None
    if tdir is not None:
        launch_bytes = inst.launch_bytes() if inst.launches else None
        files = list(Path(tdir).rglob("*.xplane.pb"))
        records = trace.events(str(files[0])) if files else []
        shutil.rmtree(tdir, ignore_errors=True)
        if args.dump_events:
            with open(args.dump_events, "w") as f:
                json.dump(records, f)
    t_ref = time.perf_counter()
    changed = dep.source_changed()
    dep.close()
    checks = check.compare(ref, dep.source, outputs, col.sample, failed,
                           changed)
    ref_s = time.perf_counter() - t_ref
    correct = check.passed(checks)
    held = sum(np.asarray(v).nbytes for _, _, ans in col.sample
               for v in ans.lineage.values())
    log(f"compared {len(col.sample)} answers of {col.seen} in "
        f"{ref_s:.3f} s (reference and comparison); the sampled answers' "
        f"row ids hold {held} bytes of host memory")

    platform = devs[0].platform
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(recs), "failed": failed}
    summary = trace.summarize(records) if records else None
    breakdown = None
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = summary["breakdown"]
    if args.trace:
        log(f"trace: {json.dumps(summary and {k: summary[k] for k in ('busy_s', 'window_s')})}; "
            f"launches recorded {len(inst.launches)}, bytes needed {launch_bytes}")
    # what a metric reader (bench/metrics/<name>.py) may read
    ctx = SimpleNamespace(
        setup_s=setup_s, seconds=args.seconds, service=service, scan=scan,
        answered=answered, latencies_ms=latencies_ms(recs, t_end),
        rows_scanned=inst.rows_scanned if inst else 0,
        queries=inst.queries if inst else [], records=records,
        summary=summary, launch_bytes=launch_bytes,
        peaks=peaks(devs[0].device_kind) if platform == "tpu" else None)
    metrics = {}
    kind = "per_layer" if args.trace else "end_to_end"
    for m in registry.metrics_of(sp, kind, cell["name"]):
        v = registry.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    log(f"service {json.dumps({k: service[k] for k in ('batches', 'coalesced_requests', 'cache_hits', 'cache_misses', 'answered', 'failed')})}")
    log(f"scan routes {json.dumps({k: v for k, v in sorted(scan.items()) if v})}")
    log(f"metrics {json.dumps(metrics)}")
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    out["rehearsal"] = args.rehearse
    return out


def main(argv=None) -> int:
    try:
        out = run(argv)
    except NoDevice as e:
        print(str(e.code), file=sys.stderr)
        return 3
    if out.pop("rehearsal"):
        log(f"rehearsal on {out['device']['platform']}: "
            f"correct={out['correct']} (no result line)")
        return 0 if out["correct"] else 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
