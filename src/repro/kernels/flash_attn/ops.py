"""Jitted wrapper mapping model-layout tensors onto the kernel."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attn import flash_attention
from .ref import attention_ref


def mha_flash(q, k, v, window: Optional[int] = None, interpret: bool = False):
    """q,k,v: [B, S, H, D] (H already GQA-expanded) -> [B, S, H, D]."""
    B, S, H, D = q.shape
    fold = lambda x: jnp.moveaxis(x, 2, 1).reshape(B * H, S, D)
    unfold = lambda x: jnp.moveaxis(x.reshape(B, H, S, D), 1, 2)
    out = flash_attention(fold(q), fold(k), fold(v), window=window, interpret=interpret)
    return unfold(out)


def mha_ref(q, k, v, window: Optional[int] = None):
    B, S, H, D = q.shape
    fold = lambda x: jnp.moveaxis(x, 2, 1).reshape(B * H, S, D)
    unfold = lambda x: jnp.moveaxis(x.reshape(B, H, S, D), 1, 2)
    return unfold(attention_ref(fold(q), fold(k), fold(v), window=window))
