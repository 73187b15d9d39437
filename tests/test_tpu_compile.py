"""Compile the lineage scan kernel for a TPU v5e that is described, not
attached.

Interpret-mode tests cannot see what the TPU compiler refuses (block
shapes off the (8, 128) tiling, 1-D gathers, scalar memory overflow), so
these tests lower ``pred_filter_batch`` with ``interpret=False`` at lineitem
widths and compile it against a ``v5e:2x2`` topology description.  Nothing
runs; a compile that passes is not a chip run.

The topology is described only inside the module fixture: only one process
at a time may load the TPU library, so nothing here touches it while the
module is imported.
"""

from __future__ import annotations

import os

import pytest

from repro.kernels.pred_filter import OPS, pred_filter_batch

C, K, A, BLOCK_ROWS = 4, 32, 4, 1024
SET_KEYS = 1 << 16  # PallasBackend.SET_SLAB_LIMIT: the largest set slab
ATOMS = tuple((j, OPS[op]) for j, op in enumerate((">=", "<", "==", "<=")))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # a compile for a described chip can be written to the persistent cache
    # but never read back without the chip; keep these compiles out of it
    import jax

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(one_chip, n_rows: int, k: int, set_keys: int = 0):
    import jax
    import jax.numpy as jnp

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    G = n_rows // BLOCK_ROWS
    if set_keys:
        set_cols = (C - 1,)
        M = len(set_cols)

        def launch(cols, thr, lo, hi, slab, off, ln):
            return pred_filter_batch(cols, thr, ATOMS, lo, hi,
                                     block_rows=BLOCK_ROWS, interpret=False,
                                     set_cols=set_cols, set_slab=slab,
                                     set_off=off, set_len=ln)

        args = (spec(C, n_rows), spec(k, A), spec(A + M, G), spec(A + M, G),
                spec(set_keys), spec(k, M), spec(k, M))
    else:
        def launch(cols, thr, lo, hi):
            return pred_filter_batch(cols, thr, ATOMS, lo, hi,
                                     block_rows=BLOCK_ROWS, interpret=False)

        args = (spec(C, n_rows), spec(k, A), spec(A, G), spec(A, G))
    return jax.jit(launch).lower(*args).compile()


# lineitem at TPC-H SF1 and SF10, rounded up to the block grid; K bindings
# is a coalesced batch, K=1 the single-binding scan
SHAPES = [(6_000_640, K), (60_000_256, K), (6_000_640, 1)]


@pytest.mark.parametrize("n_rows,k", SHAPES)
def test_batch_kernel_compiles_for_v5e(one_chip, n_rows, k):
    compiled = _compile(one_chip, n_rows, k)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_rows,k", SHAPES)
def test_batch_kernel_with_set_atom_compiles_for_v5e(one_chip, n_rows, k):
    compiled = _compile(one_chip, n_rows, k, set_keys=SET_KEYS)
    assert "tpu_custom_call" in compiled.as_text()
