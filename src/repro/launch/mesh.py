"""Production mesh builders. Functions (not module constants) so importing
never touches jax device state — only the dry-run sets the 512-host-device
XLA flag, and only before its first jax import."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

    DP spans ('pod', 'data'); TP/EP stay inside a pod's ICI ('model').
    The cross-pod axis carries only the once-per-step gradient all-reduce
    (overlapped with backward by XLA's latency-hiding scheduler)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n_model: int = 1):
    """Whatever this host has — smoke tests and examples."""
    n = jax.device_count()
    assert n % n_model == 0
    return jax.make_mesh((n // n_model, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
