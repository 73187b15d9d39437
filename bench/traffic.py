"""The one traffic generator: turns a mix's parameter file into requests.

A mix (``bench/traffic/<name>.json``) is data alone.  Its work is a fixed
multiset of sessions: ``sessions`` of them, each one pipeline (drawn by
``pipeline_weight``: ``"uniform"`` or ``"output_rows"``) asking
``session_rows = [lo, hi]`` rows (each count equally often per pipeline),
the rows of a pipeline Zipf with exponent ``row_zipf_a`` over its output
rows (row 0 the hottest).  Every seed gets that same work in another order:
the ``(pipeline, size)`` pairs and the rows of each pipeline (quantiles of
the Zipf law) are fixed by the mix and the output row counts, and the seed
shuffles them.  Two loop kinds:

* ``"open"``: sessions arrive at ``session_rate_per_s`` (inter-arrival gaps
  are the exponential's quantiles, shuffled), each asking its rows at
  offsets drawn inside ``session_span_s`` seconds, whether or not earlier
  answers have come.  ``sessions`` is the rate times the window less one
  span, so every request falls inside the window.
* ``"closed"``: ``clients`` callers; each takes the next session of a pass
  and asks its rows ``page_rows`` at a time, each page once the last one is
  answered.  A pass is the whole multiset, in an order drawn from the seed
  and the pass number, interleaved so that every prefix holds the pipelines
  in their shares; passes repeat until the window closes.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

Session = Tuple[str, List[int]]


def weights(mix: Dict, out_rows: Dict[str, int]) -> Dict[str, float]:
    kind = mix.get("pipeline_weight", "uniform")
    if kind == "output_rows":
        w = {q: float(n) for q, n in out_rows.items()}
    elif kind == "uniform":
        w = {q: 1.0 for q in out_rows}
    else:
        raise ValueError(f"unknown pipeline_weight {kind!r}")
    live = {q: v for q, v in w.items() if out_rows[q] > 0}
    total = sum(live.values())
    return {q: v / total for q, v in sorted(live.items())}


def apportion(share: Dict[str, float], total: int) -> Dict[str, int]:
    """Largest-remainder counts that sum to ``total``."""
    raw = {q: s * total for q, s in share.items()}
    out = {q: int(v) for q, v in raw.items()}
    rest = sorted(raw, key=lambda q: (out[q] - raw[q], q))
    for q in rest[:total - sum(out.values())]:
        out[q] += 1
    return out


def zipf_quantiles(n: int, a: float, size: int) -> np.ndarray:
    """``size`` row indices in ``[0, n)`` at evenly spaced quantiles of the
    Zipf law with exponent ``a`` (row 0 the hottest): a fixed multiset
    whose counts follow the law."""
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -a)
    cdf /= cdf[-1]
    u = (np.arange(size) + 0.5) / size
    return np.minimum(np.searchsorted(cdf, u, side="left"), n - 1)


def sessions(mix: Dict, out_rows: Dict[str, int], n: int,
             rng) -> Dict[str, List[Session]]:
    """The mix's ``n`` sessions by pipeline, each pipeline's in an order
    drawn from ``rng``."""
    lo, hi = mix["session_rows"]
    width = hi - lo + 1
    counts = apportion(weights(mix, out_rows), n)
    a = float(mix["row_zipf_a"])
    out = {}
    for j, (q, c) in enumerate(sorted(counts.items())):
        sizes = [lo + (j + k) % width for k in range(c)]
        rows = [int(r) for r in
                rng.permutation(zipf_quantiles(out_rows[q], a, sum(sizes)))]
        sizes = rng.permutation(sizes)
        out[q] = [(q, rows[e - k:e]) for k, e in zip(sizes, np.cumsum(sizes))]
    return out


def open_schedule(mix: Dict, out_rows: Dict[str, int], seed: int,
                  seconds: float) -> List[Tuple[float, str, int]]:
    """``(due offset s, pipeline, row)`` for every request of an open-loop
    window, sorted by due time."""
    rng = np.random.default_rng(seed)
    span = float(mix["session_span_s"])
    room = seconds - span
    if room <= 0:
        raise ValueError(f"a window of {seconds} s holds no session of "
                         f"{span} s")
    n = max(int(round(float(mix["session_rate_per_s"]) * room)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= room / gaps.sum()
    starts = np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])
    pool = [s for by_q in sessions(mix, out_rows, n, rng).values()
            for s in by_q]
    events = []
    for t0, i in zip(starts, rng.permutation(len(pool))):
        q, rows = pool[i]
        for off, row in zip(np.sort(rng.uniform(0.0, span, len(rows))), rows):
            events.append((float(t0 + off), q, row))
    events.sort(key=lambda e: e[0])
    return events


def closed_pass(mix: Dict, out_rows: Dict[str, int], seed: int,
                pass_no: int) -> List[Session]:
    """The sessions of pass ``pass_no`` of a closed loop, in the order the
    clients take them: each pipeline's sessions shuffled, and the pipelines
    interleaved by the fractional position of each session in its own list
    (ties broken by a shuffled pipeline order)."""
    rng = np.random.default_rng([seed, pass_no])
    by_q = sessions(mix, out_rows, int(mix["sessions"]), rng)
    rank = {q: i for i, q in enumerate(rng.permutation(sorted(by_q)))}
    keyed = [((k + 0.5) / len(v), rank[q], s)
             for q, v in by_q.items() for k, s in enumerate(v)]
    return [s for _, _, s in sorted(keyed, key=lambda e: e[:2])]


def closed_rows(mix: Dict, out_rows: Dict[str, int], seed: int
                ) -> List[Tuple[str, int]]:
    """The ``(pipeline, row)`` questions of a closed loop: every pass asks
    the same ones."""
    return [(q, r) for q, rows in closed_pass(mix, out_rows, seed, 0)
            for r in rows]
