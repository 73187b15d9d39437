"""Fused conjunctive-predicate scan kernel (PredTrace's lineage-query hot path).

A pushed-down predicate is a conjunction of atoms ``col <op> const``.  The
DBMS equivalent is a sequential scan; on TPU we stream fixed-size columnar row
blocks HBM->VMEM and evaluate **all atoms in one pass** on the VPU, writing a
single int32 mask — one read of each referenced column per block, no
intermediate per-atom masks in HBM.

Layout: a block is ``[C, BN]`` (columns x rows, int32 — dictionary codes,
YYYYMMDD dates, or fixed-point cents).  The atom structure (which column,
which comparison) is *static* (baked at trace time per pushed-down predicate —
PredTrace compiles one kernel per inferred lineage plan); thresholds are a
runtime operand so re-binding ``t_o`` does NOT recompile.

Two entry points:

* :func:`pred_filter` — the original single-binding kernel (``[K]``
  thresholds, one per atom).
* :func:`pred_filter_batch` — the batched carrier: thresholds are a ``[K, A]``
  runtime operand (K target-row bindings x A atoms), the output is ``[K, N]``,
  and **zone-map pruning is fused into the grid**: per-block min/max bounds
  (``[A, G]`` operands, one row per atom) are checked against every binding's
  thresholds *before* the block's columns are touched; a block no binding can
  match early-outs via ``pl.when`` and just zeroes its output tile.  One
  launch answers an entire coalesced ``query_batch`` — one read of each
  column per block for all K predicates, no recompile per target.

The zone bounds must genuinely bound each block's column values (build them
with :func:`block_bounds`); pruning is then conservative by construction and
the batched kernel is bit-identical to the zone-free reference.

Membership atoms (``col IN set``) are fused into the same launch: the sorted
per-binding value sets are concatenated into one device-resident slab
(``set_slab``), addressed raggedly by per-``(binding, set-atom)``
offset/length operands.  The slab rides the whole grid in VMEM, and each
block evaluates membership densely — a VPU compare of every value against
each 128-key slab row, and an MXU matmul with the per-binding segment
indicator that counts hits per binding — because the TPU compiler has no
1-D gather for a per-lane binary search.  Zone pruning extends to set
atoms: a binding whose set holds no key inside a block's ``[lo, hi]``
bounds is dead for that block, and slab tiles with no such key are
skipped.  The XLA twin (``ref.py``) keeps the binary search, which XLA
compiles well off the TPU.

Zone bounds reach the kernel as SMEM tiles of 128 blocks each, so scalar
memory holds one tile however long the table is.

Float32 columns need no kernel changes: the backend folds their bits into a
monotone int32 total-order key (sign-fold, ``-0`` canonicalized to ``+0``)
and translates thresholds into key-space range atoms, so float compares —
including exact NaN/±inf semantics — ride the int32 lanes below.

Atom ops: 0:== 1:!= 2:< 3:<= 4:> 5:>=
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 1024

OPS = {"==": 0, "!=": 1, "<": 2, "<=": 3, ">": 4, ">=": 5}


def _apply_op(op_code: int, col, thr):
    if op_code == 0:
        return col == thr
    if op_code == 1:
        return col != thr
    if op_code == 2:
        return col < thr
    if op_code == 3:
        return col <= thr
    if op_code == 4:
        return col > thr
    if op_code == 5:
        return col >= thr
    raise ValueError(op_code)


def _zone_alive(op_code: int, lo, hi, thr):
    """Can *any* value in ``[lo, hi]`` satisfy ``value <op> thr``?  Exact for
    ==/</<=/>/>=; ``!=`` prunes only provably-constant blocks (lo == hi)."""
    if op_code == 0:
        return jnp.logical_and(lo <= thr, thr <= hi)
    if op_code == 1:
        return jnp.logical_not(jnp.logical_and(lo == hi, lo == thr))
    if op_code == 2:
        return lo < thr
    if op_code == 3:
        return lo <= thr
    if op_code == 4:
        return hi > thr
    if op_code == 5:
        return hi >= thr
    raise ValueError(op_code)


def _kernel(cols_ref, thr_ref, out_ref, *, atoms: Tuple[Tuple[int, int], ...]):
    """atoms: static ((col_idx, op_code), ...)."""
    acc = jnp.ones((cols_ref.shape[1],), jnp.bool_)
    for j, (ci, op) in enumerate(atoms):
        col = cols_ref[ci, :]
        thr = thr_ref[j]
        acc = jnp.logical_and(acc, _apply_op(op, col, thr))
    out_ref[...] = acc.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("atoms", "block_rows", "interpret"))
def pred_filter(
    cols: jax.Array,  # [C, N] int32 columnar block-major table slab
    thresholds: jax.Array,  # [K] int32
    atoms: Tuple[Tuple[int, int], ...],  # static (col_idx, op_code) per atom
    block_rows: int = BLOCK_ROWS,
    interpret: bool = False,
) -> jax.Array:
    C, N = cols.shape
    assert N % block_rows == 0, f"pad N={N} to a multiple of {block_rows}"
    kern = functools.partial(_kernel, atoms=atoms)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((N,), jnp.int32),
        grid=(N // block_rows,),
        in_specs=[
            pl.BlockSpec((C, block_rows), lambda i: (0, i)),  # column slab in VMEM
            pl.BlockSpec((thresholds.shape[0],), lambda i: (0,)),  # thresholds
        ],
        out_specs=pl.BlockSpec((block_rows,), lambda i: (i,)),
        interpret=interpret,
        name="pred_filter",
    )(cols, thresholds)


# --------------------------------------------------------------------------- #
# batched launch with in-grid zone-map pruning
# --------------------------------------------------------------------------- #

LANES = 128  # TPU vector lane width: the minor block dim must be a multiple
SUBLANES = 8  # ... and the second-minor one a multiple of this
_SET_TILE = SUBLANES * LANES  # set-slab keys one membership step covers


def _zone_alive_all(atoms, thr, lo_ref, hi_ref, r):
    """``[K, 1]`` bool: bindings the block's bounds cannot refute.  The
    bounds are SMEM scalars at lane ``r`` of this step's ``[A+M, LANES]``
    tile (one tile serves LANES consecutive grid steps)."""
    alive = jnp.ones((thr.shape[0], 1), jnp.bool_)
    for j, (_, op) in enumerate(atoms):
        alive = jnp.logical_and(
            alive, _zone_alive(op, lo_ref[j, r], hi_ref[j, r], thr[:, j:j + 1]))
    return alive


def _cmp_acc(atoms, thr, cols_ref, shape):
    acc = jnp.ones(shape, jnp.bool_)
    for j, (ci, op) in enumerate(atoms):
        # one read per column for all K bindings
        acc = jnp.logical_and(
            acc, _apply_op(op, cols_ref[ci:ci + 1, :], thr[:, j:j + 1]))
    return acc


def _kernel_batch(cols_ref, thr_ref, lo_ref, hi_ref, out_ref, *,
                  atoms: Tuple[Tuple[int, int], ...]):
    """One grid step = one row block x all K bindings.

    The per-block ``[lo, hi]`` bounds are checked against every binding's
    thresholds first; bindings the bounds refute are masked out, and when
    *no* binding survives the block's columns are never streamed through the
    compare pipeline — the tile is just zeroed (``pl.when`` early-out)."""
    thr = thr_ref[...]
    alive = _zone_alive_all(atoms, thr, lo_ref, hi_ref,
                            pl.program_id(0) % LANES)
    any_alive = jnp.any(alive)

    @pl.when(any_alive)
    def _eval():
        acc = _cmp_acc(atoms, thr, cols_ref, out_ref.shape)
        out_ref[...] = jnp.logical_and(acc, alive).astype(jnp.int32)

    @pl.when(jnp.logical_not(any_alive))
    def _skip():
        out_ref[...] = jnp.zeros_like(out_ref)


def _in_segment(tile_row, sub: int, seg_lo, seg_hi):
    """``[K, LANES]`` bool: which bindings' segments hold the slab keys of
    row ``sub`` of set-slab tile ``tile_row``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    pos = (tile_row * SUBLANES + sub) * LANES + lane
    return jnp.logical_and(pos >= seg_lo, pos < seg_hi)


def _kernel_batch_sets(cols_ref, thr_ref, lo_ref, hi_ref, set_slab_ref,
                       set_off_ref, set_len_ref, out_ref, cnt_ref, *,
                       atoms: Tuple[Tuple[int, int], ...],
                       set_cols: Tuple[int, ...]):
    """Set-carrying variant of :func:`_kernel_batch`.

    The zone-bound operands carry ``A + M`` rows: the first ``A`` belong to
    the cmp atoms, the trailing ``M`` to the set atoms' columns.  The set
    slab is ``[R, LANES]`` (keys in lanes, resident in VMEM for the whole
    grid); binding ``k``'s set for atom ``m`` is the flat key range
    ``[off[k, m], off[k, m] + len[k, m])``.

    Membership is a dense compare on the VPU plus a segment matmul on the
    MXU, with no gathers: for each 128-key slab row, ``eq[key, row]`` is
    the key-vs-value compare and ``seg[k, key]`` says whether the key
    belongs to binding ``k``'s set; ``seg @ eq`` counts each binding's hits
    per row (exact — keys within a segment are unique, the 0/1 operands
    are exact in bf16 and the sums in f32).  Slab tiles holding no key
    inside the block's ``[lo, hi]`` are skipped.  Set atoms also join the
    in-grid prune: a block dies for a binding whose set has no key inside
    the block's bounds."""
    thr = thr_ref[...]
    K = thr.shape[0]
    A = len(atoms)
    r = pl.program_id(0) % LANES
    n_tiles = set_slab_ref.shape[0] // SUBLANES
    off = set_off_ref[...]
    ln = set_len_ref[...]

    def tile(g):
        return set_slab_ref[pl.ds(pl.multiple_of(g * SUBLANES, SUBLANES),
                                  SUBLANES), :]

    alive = _zone_alive_all(atoms, thr, lo_ref, hi_ref, r)
    for m in range(len(set_cols)):
        b_lo, b_hi = lo_ref[A + m, r], hi_ref[A + m, r]
        seg_lo = off[:, m:m + 1]
        seg_hi = seg_lo + ln[:, m:m + 1]

        def zone_step(g, hit):
            keys = tile(g)
            inside = jnp.logical_and(keys >= b_lo, keys <= b_hi)
            for sub in range(SUBLANES):
                both = jnp.logical_and(_in_segment(g, sub, seg_lo, seg_hi),
                                       inside[sub:sub + 1, :])
                # int32 carry: Mosaic cannot carry i1 vectors through a loop
                hit = jnp.maximum(hit, jnp.max(both.astype(jnp.int32), axis=1,
                                               keepdims=True))
            return hit

        hit = jax.lax.fori_loop(0, n_tiles, zone_step,
                                jnp.zeros((K, 1), jnp.int32))
        alive = jnp.logical_and(alive, hit > 0)
    any_alive = jnp.any(alive)

    @pl.when(any_alive)
    def _eval():
        acc = _cmp_acc(atoms, thr, cols_ref, out_ref.shape)
        for m, ci in enumerate(set_cols):
            col = cols_ref[ci:ci + 1, :]
            b_lo, b_hi = lo_ref[A + m, r], hi_ref[A + m, r]
            seg_lo = off[:, m:m + 1]
            seg_hi = seg_lo + ln[:, m:m + 1]
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

            @pl.loop(0, n_tiles)
            def _member_step(g):
                keys = tile(g)
                inside = jnp.logical_and(keys >= b_lo, keys <= b_hi)

                @pl.when(jnp.any(inside))
                def _count():
                    keys_t = keys.T  # [LANES, SUBLANES]: keys down sublanes
                    for sub in range(SUBLANES):
                        eq = (keys_t[:, sub:sub + 1] == col).astype(jnp.bfloat16)
                        seg = _in_segment(g, sub, seg_lo, seg_hi).astype(
                            jnp.bfloat16)
                        cnt_ref[...] += jnp.dot(
                            seg, eq, preferred_element_type=jnp.float32)

            acc = jnp.logical_and(acc, cnt_ref[...] > 0)
        out_ref[...] = jnp.logical_and(acc, alive).astype(jnp.int32)

    @pl.when(jnp.logical_not(any_alive))
    def _skip():
        out_ref[...] = jnp.zeros_like(out_ref)


def _pad_to(x, axis: int, multiple: int):
    pad = (-x.shape[axis]) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit, static_argnames=("atoms", "block_rows", "interpret", "set_cols"))
def pred_filter_batch(
    cols: jax.Array,  # [C, N] int32 columnar slab, N % block_rows == 0
    thresholds: jax.Array,  # [K, A] int32 — K bindings x A atoms
    atoms: Tuple[Tuple[int, int], ...],  # static (col_idx, op_code) per atom
    blk_lo: jax.Array,  # [A(+M), G] int32 per-(atom, block) lower bounds
    blk_hi: jax.Array,  # [A(+M), G] int32 per-(atom, block) upper bounds
    block_rows: int = BLOCK_ROWS,
    interpret: bool = False,
    set_cols: Tuple[int, ...] = (),  # static col idx per membership atom
    set_slab: jax.Array = None,  # [S] int32 concatenated sorted sets
    set_off: jax.Array = None,  # [K, M] int32 segment offsets into set_slab
    set_len: jax.Array = None,  # [K, M] int32 segment lengths
) -> jax.Array:  # [K, N] int32 masks
    C, N = cols.shape
    K, A = thresholds.shape
    M = len(set_cols)
    G = N // block_rows
    assert N % block_rows == 0, f"pad N={N} to a multiple of {block_rows}"
    assert A == len(atoms) and blk_lo.shape == blk_hi.shape == (A + M, G)
    # zone bounds ride in SMEM as [A+M, LANES] tiles, one tile per LANES
    # grid steps: lane-aligned blocks the TPU compiler accepts, and SMEM
    # holds only the current tile however long the table is
    blk_lo, blk_hi = _pad_to(blk_lo, 1, LANES), _pad_to(blk_hi, 1, LANES)
    bounds = pl.BlockSpec((A + M, LANES), lambda i: (0, i // LANES),
                          memory_space=pltpu.SMEM)
    in_specs = [
        pl.BlockSpec((C, block_rows), lambda i: (0, i)),  # column slab
        pl.BlockSpec((K, A), lambda i: (0, 0)),  # thresholds (all bindings)
        bounds,  # this block's lo bounds
        bounds,  # this block's hi bounds
    ]
    operands = [cols, thresholds, blk_lo, blk_hi]
    scratch = []
    if set_cols:
        assert set_off.shape == set_len.shape == (K, M)
        # keys in lanes, whole slab resident in VMEM; padding keys sit past
        # every segment, so no binding counts them
        slab = _pad_to(set_slab, 0, _SET_TILE).reshape(-1, LANES)
        in_specs += [
            pl.BlockSpec(slab.shape, lambda i: (0, 0)),  # set slab
            pl.BlockSpec((K, M), lambda i: (0, 0)),  # segment offsets
            pl.BlockSpec((K, M), lambda i: (0, 0)),  # segment lengths
        ]
        operands += [slab, set_off, set_len]
        scratch = [pltpu.VMEM((K, block_rows), jnp.float32)]  # hit counts
        kern = functools.partial(_kernel_batch_sets, atoms=atoms,
                                 set_cols=set_cols)
    else:
        kern = functools.partial(_kernel_batch, atoms=atoms)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((K, N), jnp.int32),
        grid=(G,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((K, block_rows), lambda i: (0, i)),
        scratch_shapes=scratch,
        interpret=interpret,
        # a stable kernel name: the op a device trace shows
        name="pred_filter_batch",
    )(*operands)


def block_bounds(slab: np.ndarray, block_rows: int,
                 atom_cols: Tuple[int, ...]) -> Tuple[np.ndarray, np.ndarray]:
    """Per-(atom, block) ``[lo, hi]`` bounds of an ``[C, N]`` int32 slab —
    the zone operands :func:`pred_filter_batch` prunes against.  One
    ``reduceat`` pass per referenced column, computed once per cached slab."""
    C, N = slab.shape
    assert N % block_rows == 0
    starts = np.arange(0, N, block_rows)
    lo = np.empty((len(atom_cols), len(starts)), np.int32)
    hi = np.empty_like(lo)
    per_col = {}
    for j, ci in enumerate(atom_cols):
        if ci not in per_col:
            per_col[ci] = (
                np.minimum.reduceat(slab[ci], starts),
                np.maximum.reduceat(slab[ci], starts),
            )
        lo[j], hi[j] = per_col[ci]
    return lo, hi
