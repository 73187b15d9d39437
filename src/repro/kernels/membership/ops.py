"""Jitted wrapper with padding + sentinel handling and a VMEM-budget fallback."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .membership import BLOCK_ROWS, SET_TILE, membership
from .ref import membership_ref

SENTINEL = np.int32(-2_147_483_648)
VMEM_SET_LIMIT = 1 << 16  # 64K int32 = 256 KiB of VMEM for the set


def probe(values: np.ndarray, vset: np.ndarray, use_kernel: bool = True,
          interpret: bool = False) -> np.ndarray:
    """Boolean membership mask, any sizes (pads to kernel block shapes)."""
    values = np.asarray(values, dtype=np.int32)
    vset = np.unique(np.asarray(vset, dtype=np.int32))
    if len(vset) == 0 or len(values) == 0:
        return np.zeros(len(values), dtype=bool)
    if len(vset) > VMEM_SET_LIMIT or not use_kernel:
        return np.asarray(membership_ref(jnp.asarray(values), jnp.asarray(vset))).astype(bool)
    n_pad = (-len(values)) % BLOCK_ROWS
    m_pad = (-len(vset)) % SET_TILE
    v = np.pad(values, (0, n_pad), constant_values=SENTINEL + 1)
    s = np.pad(vset, (0, m_pad), constant_values=SENTINEL)
    mask = membership(jnp.asarray(v), jnp.asarray(s), interpret=interpret)
    return np.asarray(mask[: len(values)]).astype(bool)
