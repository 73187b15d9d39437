"""Set-membership probe kernel (``col IN V-set``) — Algorithm 3's hot path.

Each refinement iteration evaluates ``col ∈ V`` per source table.  The V-set
(typically 10^2..10^5 keys) is tiled into VMEM once per row-block; each row
block broadcasts-compares against every set tile on the VPU and OR-reduces —
a dense compare is faster than gather-based hashing on TPU for these set
sizes (no random access; everything stays in registers/VMEM).

For |V| beyond VMEM, ops.py falls back to a bitmap probe (dense domains) or
jnp.isin (host path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 1024
SET_TILE = 256


def _kernel(vals_ref, set_ref, out_ref, *, set_tiles: int):
    vals = vals_ref[...]  # [BN]
    acc = jnp.zeros(vals.shape, jnp.bool_)
    for t in range(set_tiles):  # static unroll over VMEM-resident set tiles
        tile = set_ref[t * SET_TILE : (t + 1) * SET_TILE]  # [SET_TILE]
        eq = vals[:, None] == tile[None, :]
        acc = jnp.logical_or(acc, eq.any(axis=1))
    out_ref[...] = acc.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def membership(
    values: jax.Array,  # [N] int32
    vset: jax.Array,  # [M] int32, padded with a sentinel absent from values
    block_rows: int = BLOCK_ROWS,
    interpret: bool = False,
) -> jax.Array:
    (N,) = values.shape
    (M,) = vset.shape
    assert N % block_rows == 0 and M % SET_TILE == 0
    kern = functools.partial(_kernel, set_tiles=M // SET_TILE)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((N,), jnp.int32),
        grid=(N // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows,), lambda i: (i,)),
            pl.BlockSpec((M,), lambda i: (0,)),  # whole set resident in VMEM
        ],
        out_specs=pl.BlockSpec((block_rows,), lambda i: (i,)),
        interpret=interpret,
    )(values, vset)
