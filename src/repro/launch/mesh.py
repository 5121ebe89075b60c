"""Production mesh construction.

A function (never a module-level constant) so importing this module never
touches jax device state. Production target: TPU v5e pods, 256 chips per pod
as a (data=16, model=16) mesh; the multi-pod variant adds a leading
``pod`` axis (2 pods = 512 chips). The FL mapping treats ``pod`` as the
cohort axis (each pod trains a cohort member group; staleness-weighted
aggregation is a weighted psum over ``pod`` — see DESIGN.md §4).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def _debug_mesh_shape(n_devices: int) -> tuple[int, int]:
    """Largest valid (data, model) factorization of ``n_devices``.

    Prefers the widest model axis that divides n (4, then 3, then 2) and
    falls back to ``(n, 1)`` for primes and n < 2, so every positive
    device count yields a mesh covering exactly n devices. The old
    ``(n // 4, 4)`` arithmetic built a wrong-size mesh for n not
    divisible by 4 and an invalid zero-extent one for n < 4.
    """
    n = max(int(n_devices), 1)
    for model in (4, 3, 2):
        if n >= model and n % model == 0:
            return (n // model, model)
    return (n, 1)


def make_debug_mesh(n_devices: int = 8):
    """Small mesh for unit tests (requires xla_force_host_platform_device_count)."""
    return jax.make_mesh(_debug_mesh_shape(n_devices), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
