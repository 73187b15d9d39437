"""LineageService: concurrency stress + scheduler/cache semantics.

The serving contract under test:

  1. 32 threads issuing randomized Q3/Q10/Q1 lineage rows through one
     service, across budgets {0, partial, None} x partitioning on/off,
     every answer bit-identical to serial ``PredTrace.query()``.
  2. The scheduler actually coalesces (batch counters) and the answer cache
     actually hits (duplicate questions) — asserted on service stats().
  3. Deadline-expired requests raise ``DeadlineExceeded`` cleanly; cancelled
     requests raise ``RequestCancelled``; a closed service refuses work.
  4. Store re-runs bump the answer generation: cached answers are never
     served stale (counted as ``cache_stale`` misses, then recomputed).

Every blocking wait in this file carries a timeout and every worker pool is
joined with one, so a scheduler deadlock fails the test quickly instead of
hanging the suite.
"""

import threading
import time

import numpy as np
import pytest

from repro.core import (
    DeadlineExceeded, Executor, LineageService, PredTrace, RequestCancelled,
)
from repro.tpch import ALL_QUERIES

JOIN_TIMEOUT = 120.0


def _prep(db, qname, **kw) -> PredTrace:
    plan = ALL_QUERIES[qname](db)
    res = Executor(db).run(plan)
    pt = PredTrace(db, plan, **kw)
    pt.infer(stats=res.stats)
    pt.run()
    return pt


def _identical(a, b) -> bool:
    """Bit-identical lineage: same tables, same row-id arrays."""
    if set(a) != set(b):
        return False
    return all(np.array_equal(np.sort(a[t]), np.sort(b[t])) for t in a)


@pytest.fixture(scope="module")
def pipelines(tpch_db):
    """The budgets x partitioning serving matrix over Q3/Q10/Q1."""
    db = tpch_db
    pts = {
        # budget None (everything materialized), partitioning off/on
        "q3": _prep(db, "q3"),
        "q3.part": _prep(db, "q3", num_partitions=8),
        # compressed store, partitioned
        "q10.store": _prep(db, "q10", store=True, num_partitions=8),
        # budget 0: every query degrades to the iterative superset path
        "q10.b0": _prep(db, "q10", budget_bytes=0),
        "q1": _prep(db, "q1"),
    }
    # partial budget: keep roughly half the encoded store
    full = _prep(db, "q3", store=True)
    half = max(full.store.nbytes() // 2, 1)
    pts["q3.partial"] = _prep(db, "q3", budget_bytes=half, num_partitions=8)
    yield pts
    for pt in pts.values():
        pt.close()


@pytest.fixture(scope="module")
def expected(pipelines):
    """Serial query() oracle per (pipeline, row)."""
    out = {}
    for key, pt in pipelines.items():
        n = pt.exec_result.output.nrows
        for row in range(min(n, 12)):
            out[(key, row)] = pt.query(row).lineage
    return out


def test_stress_32_threads_identical_answers(pipelines, expected):
    svc = LineageService(pipelines, max_batch=16, window_s=0.005)
    keys = sorted({k for k, _ in expected})
    results, errors = {}, []

    def client(tid):
        rng = np.random.default_rng(tid)
        try:
            for j in range(8):
                key = keys[rng.integers(len(keys))]
                n_rows = len([1 for (k, _) in expected if k == key])
                row = int(rng.integers(n_rows))
                ans = svc.submit(row, key, timeout=JOIN_TIMEOUT).result()
                results[(tid, j)] = (key, row, ans)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append((tid, e))

    threads = [threading.Thread(target=client, args=(t,), daemon=True)
               for t in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(JOIN_TIMEOUT)
    hung = [t for t in threads if t.is_alive()]
    svc.close()
    assert not hung, f"{len(hung)} client threads deadlocked"
    assert not errors, f"client errors: {errors[:3]}"
    assert len(results) == 32 * 8
    for key, row, ans in results.values():
        assert _identical(ans.lineage, expected[(key, row)]), (key, row)

    st = svc.stats()
    assert st["answered"] == st["submitted"] == 32 * 8
    assert st["failed"] == st["expired"] == 0
    # scheduler coalesced: far fewer engine dispatches than requests
    assert st["batches"] >= 1
    assert st["coalesce_width_max"] >= 2
    assert st["coalesced_requests"] + st["cache_hits"] == 32 * 8
    # 256 requests over ~70 distinct questions: the cache must have hit
    assert st["cache_hits"] > 0
    assert 0.0 < st["cache_hit_rate"] <= 1.0
    assert st["latency_ms_p99"] >= st["latency_ms_p50"] > 0.0


def test_coalesced_batch_answers_match_serial(pipelines, expected):
    """One full window of concurrent same-pipeline requests -> one
    query_batch dispatch, answers identical per request."""
    svc = LineageService(pipelines, max_batch=8, window_s=0.05)
    reqs = [svc.submit(row, "q3.part", timeout=JOIN_TIMEOUT)
            for row in [0, 1, 2, 3, 0, 1, 2, 3]]
    answers = [r.result(JOIN_TIMEOUT) for r in reqs]
    st = svc.stats()
    svc.close()
    for row, ans in zip([0, 1, 2, 3, 0, 1, 2, 3], answers):
        assert _identical(ans.lineage, expected[("q3.part", row)])
    # 8 requests, 4 distinct bindings: one batch of width 8, 4 queries
    assert st["batches"] == 1
    assert st["coalesce_width_max"] == 8
    assert st["batch_queries"] == 4


class _SlowPipeline:
    """PredTrace wrapper that stalls every query — pins the dispatcher so
    later-queued requests deterministically expire / cancel in the queue."""

    def __init__(self, pt, delay_s):
        self._pt = pt
        self._delay = delay_s

    def __getattr__(self, name):
        return getattr(self._pt, name)

    def query(self, row):
        time.sleep(self._delay)
        return self._pt.query(row)

    def query_batch(self, rows):
        time.sleep(self._delay)
        return self._pt.query_batch(rows)


def test_deadline_expired_raises_cleanly(pipelines):
    slow = _SlowPipeline(pipelines["q3"], 0.15)
    svc = LineageService({"q3": slow}, max_batch=1, window_s=0.001)
    stall = svc.submit(0, "q3", timeout=JOIN_TIMEOUT)  # occupies dispatcher
    req = svc.submit(1, "q3", timeout=0.01)  # expires while queued
    with pytest.raises(DeadlineExceeded):
        req.result()
    assert req.expired() and req.done()
    assert stall.result(JOIN_TIMEOUT).lineage  # the slow one still answers
    # an expired request never blocks later ones
    ok = svc.submit(0, "q3", timeout=JOIN_TIMEOUT).result(JOIN_TIMEOUT)
    assert ok.lineage
    # the dispatcher (the single dequeue point) accounted the expiry
    deadline = time.monotonic() + 30
    while svc.stats()["expired"] < 1:
        assert time.monotonic() < deadline, svc.stats()
    svc.close()


def test_zero_timeout_expires_without_dispatch(pipelines):
    svc = LineageService(pipelines, window_s=0.001)
    req = svc.submit(0, "q3", timeout=0.0)
    with pytest.raises(DeadlineExceeded):
        req.result()
    assert req.expired()
    svc.close()


def test_cancel_and_close_semantics(pipelines):
    slow = _SlowPipeline(pipelines["q3"], 0.15)
    svc = LineageService({"q3": slow}, max_batch=1, window_s=0.001)
    svc.submit(0, "q3", timeout=JOIN_TIMEOUT)  # occupies dispatcher
    req = svc.submit(1, "q3", timeout=30)
    assert req.cancel()
    assert req.cancel()  # idempotent
    with pytest.raises(RequestCancelled):
        req.result(JOIN_TIMEOUT)
    with pytest.raises(KeyError):
        svc.submit(0, "no-such-pipeline")
    pending = svc.submit(2, "q3", timeout=30)
    svc.close()
    with pytest.raises(RequestCancelled):
        pending.result(JOIN_TIMEOUT)
    with pytest.raises(RequestCancelled):
        svc.submit(0, "q3")


def test_enqueue_after_close_fails_request(pipelines):
    """Regression: a submit racing close() past the unlocked closed-check
    must not strand its request in a queue nobody drains — the locked
    enqueue re-checks and fails it with RequestCancelled."""
    from repro.core.service import LineageRequest

    svc = LineageService(pipelines, window_s=0.001)
    svc.close()
    req = LineageRequest("q3", 0, None)
    svc._enqueue([req])  # the state a lost submit/close race leaves behind
    with pytest.raises(RequestCancelled):
        req.result(JOIN_TIMEOUT)
    assert req.cancelled()


def test_answer_cache_hits_and_generation_invalidation(tpch_db):
    pt = _prep(tpch_db, "q10", store=True)
    svc = LineageService(pt, window_s=0.001)
    first = svc.query(0, timeout=JOIN_TIMEOUT)
    second = svc.query(0, timeout=JOIN_TIMEOUT)
    assert second.detail.get("cache") == "hit"
    assert _identical(first.lineage, second.lineage)
    gen_before = pt.answer_generation()

    # pipeline re-run: Executor.run + store puts bump the generation, so the
    # cached answer must be detected stale, recomputed, and still correct
    pt.run()
    assert pt.answer_generation() != gen_before
    third = svc.query(0, timeout=JOIN_TIMEOUT)
    st = svc.stats()
    assert st["cache_stale"] >= 1
    assert third.detail.get("cache") != "hit"
    assert _identical(third.lineage, first.lineage)

    # evict-only store mutations invalidate too
    if pt.store.stages:
        gen = pt.answer_generation()
        pt.store.evict(list(pt.store.stages)[:1])
        assert pt.answer_generation() != gen
    svc.close()
    pt.close()


class _PinnedGeneration:
    """PredTrace wrapper with a frozen answer-generation token: models the
    window where a budget/precision change is not accompanied by a data
    generation change, so only the cache KEY can keep answer kinds apart."""

    def __init__(self, pt):
        self._pt = pt
        self._gen = pt.answer_generation()

    def __getattr__(self, name):
        return getattr(self._pt, name)

    def answer_generation(self):
        return self._gen


def test_cache_key_includes_precision_mode(tpch_db):
    """Regression: the answer-cache key must include the pipeline's
    effective budget/precision mode.  A superset answer cached under a tight
    budget must never be served to a caller who restored precision (here:
    the budget is changed and the store re-attached while the generation
    token is pinned) — and vice versa."""
    inner = _prep(tpch_db, "q3", store=True)
    pt = _PinnedGeneration(inner)
    svc = LineageService({"q3": pt}, window_s=0.001)

    precise = svc.query(0, "q3", timeout=JOIN_TIMEOUT)
    assert precise.all_precise()
    token_before = inner.precision_token()

    # tighten the budget to zero and re-plan against the same store: every
    # stage drops, answers become flagged supersets
    inner.budget_bytes = 0
    inner.attach_store(inner.store)
    assert inner.precision_token() != token_before
    degraded = svc.query(0, "q3", timeout=JOIN_TIMEOUT)
    # without the precision token in the key this would be a cache hit
    # serving the PRECISE answer despite the degraded pipeline
    assert degraded.detail.get("cache") != "hit"
    assert not degraded.all_precise()
    # superset soundness across the mode flip
    for tab, rids in precise.lineage.items():
        assert set(rids.tolist()) <= set(
            degraded.lineage.get(tab, rids[:0]).tolist())

    # the degraded answer is itself cached under the degraded token, and
    # repeat queries hit it (never the precise entry)
    again = svc.query(0, "q3", timeout=JOIN_TIMEOUT)
    assert again.detail.get("cache") == "hit"
    assert not again.all_precise()

    # the service's superset accounting saw the degraded answers
    assert svc.stats()["superset_answers"] >= 2
    assert 0.0 < svc.stats()["superset_rate"] <= 1.0
    svc.close()
    inner.close()


def test_equal_bindings_share_one_cache_entry(tpch_db):
    """Cache keys are normalized output bindings, not row indexes: a dict
    row spec equal to an indexed row's binding is the same question."""
    pt = _prep(tpch_db, "q3")
    svc = LineageService(pt, window_s=0.001)
    out = pt.exec_result.output
    row0 = {c: out.cols[c][0] for c in out.columns}
    a = svc.query(0, timeout=JOIN_TIMEOUT)
    b = svc.query(row0, timeout=JOIN_TIMEOUT)
    assert b.detail.get("cache") == "hit"
    assert _identical(a.lineage, b.lineage)
    svc.close()
    pt.close()


def test_service_stats_shape(pipelines):
    svc = LineageService(pipelines, window_s=0.001)
    svc.query(0, "q3", timeout=JOIN_TIMEOUT)
    st = svc.stats()
    for k in ("submitted", "answered", "batches", "coalesce_width_avg",
              "coalesce_width_max", "cache_hit_rate", "cache_hits",
              "cache_misses", "cache_stale", "latency_ms_p50",
              "latency_ms_p99", "expired", "cancelled", "failed"):
        assert k in st, k
    svc.close()


def test_one_traced_batch_gives_a_queue_span_per_request(pipelines):
    """Four distinct rows sent in one ``submit_many`` coalesce into one
    batch: one ``service.queue`` span per request, each carrying the batch's
    id, and one root ``lineage.query_batch`` under the batch's span."""
    from repro.core import trace

    svc = LineageService({"q3": pipelines["q3"]}, max_batch=8, window_s=0.05)
    trace.start()
    try:
        for r in svc.submit_many([0, 1, 2, 3], "q3", timeout=JOIN_TIMEOUT):
            r.result(JOIN_TIMEOUT)
    finally:
        spans = trace.stop()
        svc.close()
    by_id = {s.id: s for s in spans}
    batch, = [s for s in spans if s.name == "service.batch"]
    assert batch.attrs == {"requests": 4}
    queue = [s for s in spans if s.name == "service.queue"]
    assert len(queue) == 4 and len({s.req for s in queue}) == 4
    assert all(s.attrs == {"batch": batch.req} for s in queue)
    assert all(s.end_ns <= batch.start_ns for s in queue)
    cache, = [s for s in spans if s.name == "service.cache"]
    assert cache.attrs == {"hits": 0, "misses": 4}
    roots = [s for s in spans if s.name.startswith("lineage.")
             and not by_id[s.parent].name.startswith("lineage.")]
    assert [s.name for s in roots] == ["lineage.query_batch"]
    assert roots[0].attrs == {"rows": 4}
    assert (roots[0].parent, roots[0].req) == (batch.id, batch.req)
    names = {s.name for s in spans}
    assert {"lineage.bind", "lineage.stage", "lineage.source", "scan"} <= names
