"""Bytes a ``pred_filter`` launch has to move, whatever implements it.

A launch evaluates K bindings of a conjunction of atoms against an int32
column slab.  Any implementation must read, once, every column the atoms
touch over the rows of the blocks that some binding can match, and write
one bit per row and binding.  Blocks are judged by the benchmark's own zone
maps (min/max per ``BLOCK_ROWS`` rows over the real rows), not by the
program's.  The ``[K, N]`` int32 mask the kernel writes today is not work
the predicate needs, so a packed-bit or row-id output can never read over
100% of the roofline.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

BLOCK_ROWS = 1024  # the program's block size today

# atom op codes: 0:== 1:!= 2:< 3:<= 4:> 5:>=


def zone_maps(slab: np.ndarray, n: int, block_rows: int = BLOCK_ROWS):
    """Per-column, per-block ``(lo, hi)`` over the first ``n`` rows."""
    starts = np.arange(0, n, block_rows)
    real = slab[:, :n]
    return (np.minimum.reduceat(real, starts, axis=1),
            np.maximum.reduceat(real, starts, axis=1))


def _may_match(op: int, lo, hi, t):
    """``[K, G]``: can a value in ``[lo, hi]`` satisfy ``value <op> t``?"""
    t = t[:, None]
    if op == 0:
        return (lo <= t) & (t <= hi)
    if op == 1:
        return ~((lo == hi) & (lo == t))
    if op == 2:
        return lo < t
    if op == 3:
        return lo <= t
    if op == 4:
        return hi > t
    if op == 5:
        return hi >= t
    raise ValueError(op)


def live_blocks(lo: np.ndarray, hi: np.ndarray,
                atoms: Sequence[Tuple[int, int]], thr: np.ndarray,
                sets: Optional[Tuple[Sequence[int], np.ndarray, np.ndarray,
                                     np.ndarray]] = None) -> np.ndarray:
    """``[G]`` bool: blocks some binding can match.  ``sets`` is
    ``(set columns, key slab, [K, M] offsets, [K, M] lengths)``."""
    K = thr.shape[0]
    alive = np.ones((K, lo.shape[1]), bool)
    for j, (c, op) in enumerate(atoms):
        alive &= _may_match(op, lo[c][None, :], hi[c][None, :],
                            thr[:, j].astype(np.int64))
    if sets is not None:
        cols, slab, off, ln = sets
        for m, c in enumerate(cols):
            for k in range(K):
                keys = np.sort(slab[off[k, m]:off[k, m] + ln[k, m]])
                # a key inside [lo, hi] of the block
                first = np.searchsorted(keys, lo[c], "left")
                alive[k] &= (first < keys.size) & (
                    keys[np.minimum(first, max(keys.size - 1, 0))] <= hi[c]
                    if keys.size else False)
    return alive.any(axis=0)


def bytes_needed(slab: np.ndarray, n: int, atoms: Sequence[Tuple[int, int]],
                 thr: np.ndarray, sets=None,
                 block_rows: int = BLOCK_ROWS) -> int:
    """Least bytes one launch moves: each touched int32 column over the live
    blocks' rows, plus one output bit per row and binding."""
    if n == 0:
        return 0
    lo, hi = zone_maps(slab, n, block_rows)
    live = live_blocks(lo, hi, atoms, thr, sets)
    rows = np.minimum(np.arange(len(live)) * block_rows + block_rows, n) \
        - np.arange(len(live)) * block_rows
    cols = {c for c, _ in atoms} | set(sets[0] if sets is not None else ())
    bits = thr.shape[0] * n
    return int(len(cols) * 4 * int(rows[live].sum()) + (bits + 7) // 8)
