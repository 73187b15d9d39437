"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the leading ``pod`` axis
composes with ``data`` for DP/FSDP and carries the cross-pod (DCN-class)
collectives.

Defined as functions so importing this module never touches JAX device state
(the 512-device host-platform override must be set by the *entrypoint* before
any JAX initialization — see dryrun.py).
"""

from __future__ import annotations

from jax import make_mesh
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 1):
    """Small mesh over host devices for tests (subprocesses set
    ``--xla_force_host_platform_device_count`` accordingly)."""
    if pod > 1:
        return make_mesh(
            (pod, data, model), ("pod", "data", "model"),
            axis_types=(AxisType.Auto,) * 3,
        )
    return make_mesh(
        (data, model), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )
