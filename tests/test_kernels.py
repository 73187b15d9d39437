"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attn import attention_ref, flash_attention, mha_flash, mha_ref
from repro.kernels.membership import membership_ref, probe
from repro.kernels.pred_filter import OPS, pred_filter, pred_filter_ref, scan_mask

rng = np.random.default_rng(7)


# --------------------------------------------------------------------------- #
# pred_filter
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("n_rows", [512, 2048, 4096])
@pytest.mark.parametrize("n_atoms", [1, 3, 6])
def test_pred_filter_sweep(n_rows, n_atoms):
    cols = rng.integers(-50, 50, (8, n_rows)).astype(np.int32)
    atoms = tuple(
        (int(rng.integers(0, 8)), int(rng.integers(0, 6))) for _ in range(n_atoms)
    )
    thr = rng.integers(-50, 50, n_atoms).astype(np.int32)
    out = pred_filter(jnp.asarray(cols), jnp.asarray(thr), atoms, block_rows=512,
                      interpret=True)
    ref = pred_filter_ref(jnp.asarray(cols), jnp.asarray(thr), atoms)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_pred_filter_from_expr():
    from repro.core.expr import Col, Param, land

    cols = rng.integers(0, 100, (3, 1000)).astype(np.int32)
    pred = land(Col("a") >= 10, Col("b") < 50, Col("c").eq(Param("v")))
    order = {"a": 0, "b": 1, "c": 2}
    m = scan_mask(cols, pred, order, {"v": 7}, interpret=True)
    want = (cols[0] >= 10) & (cols[1] < 50) & (cols[2] == 7)
    np.testing.assert_array_equal(m, want)


def test_pred_filter_incompatible_returns_none():
    from repro.core.expr import Col, IsIn

    cols = rng.integers(0, 9, (2, 512)).astype(np.int32)
    assert scan_mask(cols, IsIn(Col("a"), (1, 2)), {"a": 0}, {}) is None


# --------------------------------------------------------------------------- #
# membership
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("n", [100, 1024, 5000])
@pytest.mark.parametrize("m", [1, 63, 256, 2000])
def test_membership_sweep(n, m):
    vals = rng.integers(0, 10_000, n).astype(np.int32)
    vset = rng.choice(10_000, m, replace=False).astype(np.int32)
    got = probe(vals, vset, interpret=True)
    np.testing.assert_array_equal(got, np.isin(vals, vset))


def test_membership_empty_set():
    vals = rng.integers(0, 10, 100).astype(np.int32)
    assert probe(vals, np.array([], np.int32), interpret=True).sum() == 0


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,d,window", [(256, 64, None), (512, 128, None), (384, 64, 128)])
def test_flash_attention_sweep(s, d, window, dtype):
    q = jnp.asarray(rng.standard_normal((2, s, d)), dtype)
    k = jnp.asarray(rng.standard_normal((2, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((2, s, d)), dtype)
    o = flash_attention(q, k, v, window=window, bq=128, bk=128, interpret=True)
    r = attention_ref(q, k, v, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(r, np.float32), rtol=tol, atol=tol
    )


def test_flash_mha_layout():
    B, S, H, D = 2, 256, 4, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(mha_flash(q, k, v, interpret=True)), np.asarray(mha_ref(q, k, v)),
        rtol=2e-5, atol=2e-5
    )


def test_flash_matches_model_attention():
    """Kernel agrees with the model's XLA chunked-attention path."""
    from repro.configs import smoke_config
    from repro.models import layers as ML

    cfg = smoke_config("llama3.2-3b")
    B, S = 2, 256
    key = jax.random.PRNGKey(0)
    p, _ = ML.init_attention(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model), jnp.float32)
    out_model = ML.attention(p, x, cfg)
    # reproduce via kernel: compute q/k/v with the same projections
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    q, k, v = ML._qkv(p, x, cfg, pos)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    k, v = ML._expand_kv(k, n_rep), ML._expand_kv(v, n_rep)
    out_kernel = jnp.einsum(
        "bqhd,hdo->bqo",
        mha_flash(q, k, v, window=cfg.sliding_window, interpret=True),
        p["wo"].astype(x.dtype),
    )
    np.testing.assert_allclose(
        np.asarray(out_model), np.asarray(out_kernel), rtol=2e-4, atol=2e-4
    )
