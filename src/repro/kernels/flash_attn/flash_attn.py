"""Causal flash-attention forward kernel (blocked online softmax).

The LM substrate's hot spot: never materializes the S x S score matrix.
Grid: (batch*heads, q_blocks); the inner loop walks KV blocks up to the
causal frontier with running (max, sum, acc) in VMEM.  Block shapes keep the
MXU fed: BQ x D and BK x D tiles with D a multiple of 128 preferred.

Supports an optional sliding window (mixtral/hymba) by skipping KV blocks
entirely outside the window.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, seq: int,
            window: Optional[int], scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # [BQ, D]
    q_start = qi * bq

    m = jnp.full((bq,), NEG_INF, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)
    acc = jnp.zeros((bq, q.shape[-1]), jnp.float32)

    n_kv = seq // bk

    def kv_step(kj_static, carry):
        m, l, acc = carry
        k = k_ref[0, kj_static, :, :].astype(jnp.float32)  # [BK, D]
        v = v_ref[0, kj_static, :, :].astype(jnp.float32)
        s = q @ k.T  # [BQ, BK]
        k_start = kj_static * bk
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=1)
        acc = acc * alpha[:, None] + p @ v
        return m_new, l, acc

    for kj in range(n_kv):  # static loop; skipped blocks cost nothing
        k_start = kj * bk
        # blocks fully above the causal frontier are sliced away per q-block
        # by the @pl.when-style static guard below (q_start is traced via
        # program_id, so guard dynamically):
        def do(carry):
            return kv_step(kj, carry)

        within = k_start <= q_start + bq - 1
        if window is not None:
            within &= k_start + bk - 1 > q_start - window
        m, l, acc = jax.lax.cond(within, do, lambda c: c, (m, l, acc))

    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "bq", "bk", "interpret")
)
def flash_attention(
    q: jax.Array,  # [BH, S, D]  (batch*heads flattened)
    k: jax.Array,  # [BH, S, D]
    v: jax.Array,  # [BH, S, D]
    window: Optional[int] = None,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    BH, S, D = q.shape
    assert S % bq == 0 and S % bk == 0, f"pad S={S} to block multiples"
    scale = 1.0 / math.sqrt(D)
    kern = functools.partial(
        _kernel, bq=bq, bk=bk, seq=S, window=window, scale=scale
    )
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        grid=(BH, S // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S // bk, bk, D), lambda b, i: (b, 0, 0, 0)),
            pl.BlockSpec((1, S // bk, bk, D), lambda b, i: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        interpret=interpret,
    )(q, k.reshape(BH, S // bk, bk, D), v.reshape(BH, S // bk, bk, D))
