"""Jitted wrapper: compile a PredTrace conjunction into the fused scan kernel.

``compile_conjunction`` extracts the kernel-compatible atoms (``col <op>
int-const``) from an ``Expr``; anything else stays on the jnp fallback path —
the kernel handles the common fast path (equality/range pins from pushdown),
the expression evaluator handles the long tail.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ...core.expr import BinOp, Col, Expr, Lit, Param, conjuncts
from .pred_filter import OPS, pred_filter
from .ref import pred_filter_ref

_FLIP = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "==": "==", "!=": "!="}


def compile_conjunction(
    pred: Expr, col_order: Dict[str, int], binding: Dict[str, object]
) -> Optional[Tuple[Tuple[Tuple[int, int], ...], np.ndarray]]:
    """Returns (static atoms, thresholds) or None when not kernel-compatible."""
    atoms = []
    thresholds = []
    for a in conjuncts(pred):
        if not isinstance(a, BinOp) or a.op not in OPS:
            return None
        l, r = a.left, a.right
        op = a.op
        if not isinstance(l, Col):
            l, r, op = r, l, _FLIP[a.op]
        if not isinstance(l, Col) or l.name not in col_order:
            return None
        if isinstance(r, Lit):
            v = r.value
        elif isinstance(r, Param) and r.name in binding:
            v = binding[r.name]
        else:
            return None
        if isinstance(v, (list, tuple, np.ndarray)):
            return None  # set membership -> membership kernel
        if isinstance(v, (bool, np.bool_)):
            return None
        if isinstance(v, float) and not float(v).is_integer():
            return None  # int32 lanes only (fixed-point encode upstream)
        atoms.append((col_order[l.name], OPS[op]))
        thresholds.append(int(v))
    if not atoms:
        return None
    return tuple(atoms), np.asarray(thresholds, dtype=np.int32)


def scan_mask(
    cols: np.ndarray,  # [C, N] int32
    pred: Expr,
    col_order: Dict[str, int],
    binding: Dict[str, object],
    use_kernel: bool = True,
    interpret: bool = False,
    block_rows: int = 1024,
) -> Optional[np.ndarray]:
    """Evaluate a conjunction over a columnar slab; None if incompatible."""
    compiled = compile_conjunction(pred, col_order, binding)
    if compiled is None:
        return None
    atoms, thr = compiled
    C, N = cols.shape
    pad = (-N) % block_rows
    slab = np.pad(cols, ((0, 0), (0, pad))) if pad else cols
    if use_kernel:
        mask = pred_filter(jnp.asarray(slab), jnp.asarray(thr), atoms,
                           block_rows=block_rows, interpret=interpret)
    else:
        mask = pred_filter_ref(jnp.asarray(slab), jnp.asarray(thr), atoms)
    return np.asarray(mask[:N]).astype(bool)
