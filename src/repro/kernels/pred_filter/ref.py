"""Pure-jnp oracles for the fused predicate scan.

``pred_filter_batch_ref`` takes no zone operands on purpose: the batched
kernel's in-grid pruning only skips blocks its (data-derived) bounds prove
empty, so kernel-with-zones must be bit-identical to this zone-free oracle —
that identity is what the differential suite asserts.  Jitted, this oracle is
also the production fused scan graph on hosts without a TPU (the same
computation the Pallas kernel implements on device).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


def search_iters(max_len: int) -> int:
    """Static iteration count for :func:`_segment_member` — enough halvings
    to collapse any segment of at most ``max_len`` elements."""
    return max(1, int(max_len).bit_length())


def _lower_bound(slab, keys, seg_lo, seg_hi, iters: int):
    """Vectorized lower bound of ``keys`` inside per-row segments of a flat
    sorted ``slab``.

    ``keys`` is ``[K, X]``; ``seg_lo``/``seg_hi`` are ``[K, 1]`` segment
    bounds (``slab[seg_lo:seg_hi]`` sorted ascending).  Runs a fixed
    ``iters`` halvings so the loop is static; gathers are clamped so empty
    segments and segments ending at ``len(slab)`` stay in bounds."""
    cap = slab.shape[0] - 1
    lo = jnp.broadcast_to(seg_lo, keys.shape).astype(jnp.int32)
    hi = jnp.broadcast_to(seg_hi, keys.shape).astype(jnp.int32)
    for _ in range(iters):
        go = lo < hi
        mid = (lo + hi) // 2
        v = slab[jnp.minimum(mid, cap)]
        below = jnp.logical_and(go, v < keys)
        lo = jnp.where(below, mid + 1, lo)
        hi = jnp.where(jnp.logical_and(go, jnp.logical_not(below)), mid, hi)
    return lo


def _segment_member(slab, keys, seg_lo, seg_hi, iters: int):
    """``keys[k, x] in slab[seg_lo[k]:seg_hi[k]]`` — ``[K, X]`` bool."""
    cap = slab.shape[0] - 1
    pos = _lower_bound(slab, keys, seg_lo, seg_hi, iters)
    hit = slab[jnp.minimum(pos, cap)] == keys
    return jnp.logical_and(pos < seg_hi, hit)


def _cmp(col, t, op: int):
    return [col == t, col != t, col < t, col <= t, col > t, col >= t][op]


def _member_acc(acc, cols, set_cols, set_slab, set_off, set_len, iters):
    """AND per-binding ragged-set membership into a ``[K, N]`` bool acc."""
    for m, ci in enumerate(set_cols):
        seg_lo = set_off[:, m][:, None]
        seg_hi = seg_lo + set_len[:, m][:, None]
        acc = jnp.logical_and(
            acc,
            _segment_member(set_slab,
                            jnp.broadcast_to(cols[ci][None, :], acc.shape),
                            seg_lo, seg_hi, iters),
        )
    return acc


def pred_filter_ref(cols, thresholds, atoms: Tuple[Tuple[int, int], ...]):
    acc = jnp.ones((cols.shape[1],), jnp.bool_)
    for j, (ci, op) in enumerate(atoms):
        acc = jnp.logical_and(acc, _cmp(cols[ci], thresholds[j], op))
    return acc.astype(jnp.int32)


def pred_filter_batch_ref(cols, thresholds, atoms: Tuple[Tuple[int, int], ...],
                          set_cols: Tuple[int, ...] = (), set_slab=None,
                          set_off=None, set_len=None, iters: int = 1):
    """Batched oracle: cols [C, N], thresholds [K, A] -> [K, N] int32 masks."""
    acc = jnp.ones((thresholds.shape[0], cols.shape[1]), jnp.bool_)
    for j, (ci, op) in enumerate(atoms):
        acc = jnp.logical_and(
            acc, _cmp(cols[ci][None, :], thresholds[:, j][:, None], op)
        )
    if set_cols:
        acc = _member_acc(acc, cols, set_cols, set_slab, set_off, set_len,
                          iters)
    return acc.astype(jnp.int32)


def _batch_bool(cols, thresholds, atoms: Tuple[Tuple[int, int], ...],
                set_cols: Tuple[int, ...] = (), set_slab=None, set_off=None,
                set_len=None, iters: int = 1):
    # bool output, not the kernel's int32: the mask readback is 1/4 the
    # bytes, which decides the CPU crossover vs. numpy
    ci, op = atoms[0]
    acc = _cmp(cols[ci][None, :], thresholds[:, 0][:, None], op)
    for j, (ci, op) in enumerate(atoms[1:], 1):
        acc = jnp.logical_and(
            acc, _cmp(cols[ci][None, :], thresholds[:, j][:, None], op)
        )
    if set_cols:
        acc = _member_acc(acc, cols, set_cols, set_slab, set_off, set_len,
                          iters)
    return acc


# jitted fused-scan graph — the CPU/GPU production path behind PallasBackend's
# auto mode; cached per static atom structure, thresholds stay a runtime
# operand; set segments ride as runtime operands too (the slab length and the
# static search depth decide the specialization)
pred_filter_batch_xla = jax.jit(
    _batch_bool, static_argnames=("atoms", "set_cols", "iters"))
