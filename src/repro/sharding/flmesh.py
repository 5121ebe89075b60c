"""FL mesh plane: resolve and construct the (data, model) device mesh the
FL core shards over (DESIGN.md §15).

The seed shipped a sharding rule engine (``sharding.rules``) and mesh
construction (``launch.mesh``) that nothing in the FL core used; this
module is the bridge. One mesh spec — ``"<data>x<model>"`` — is resolved
through the same flag-oracle pattern as every other plane
(``FLConfig.mesh`` > ``REPRO_MESH`` > ``"1x1"``) and governs three layouts:

  * the ``UpdateStore`` ``[capacity, W]`` row buffer is sharded
    ``P("data", "model")`` — rows split over the ``data`` axis, the row
    width ``W`` split over ``model`` — so ``K*W`` update bytes stop being
    bounded by one device's HBM;
  * the jitted cohort fn's batch dimension is ``shard_map``-ed over
    ``data`` (``core.client``): each device trains ``Kp/data`` lanes
    against a replicated ``DatasetStore``, so per-lane train work and the
    minibatch gathers are shard-local;
  * aggregation becomes a weighted ``psum`` over ``data``
    (``kernels.ops.aggregate_rows_psum``): each shard reduces its local
    ``[C/d, W/m]`` tile and the partials meet over ICI instead of
    converging through one device.

``"1x1"`` (the default) is the bit-exact oracle: :func:`build_fl_mesh`
returns ``None``, no mesh object is constructed, no array is re-placed,
and every pre-existing single-device trace is byte-identical. Meshes with
more than one device are numerically equivalent, not bitwise (batch
splitting and the psum reassociate float reductions); the golden-trace
contract for them is identical selections/timing + allclose params
(tests/test_mesh_plane.py).
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

#: the update-row buffer layout: [capacity over "data", W over "model"]
ROW_SPEC = P("data", "model")


def parse_mesh(spec: str) -> tuple[int, int]:
    """``"<data>x<model>"`` -> ``(data, model)`` with validation."""
    parts = str(spec).lower().split("x")
    try:
        if len(parts) != 2:
            raise ValueError(spec)
        d, m = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"unknown mesh spec {spec!r} (expected '<data>x<model>', "
            "e.g. '1x1', '2x4', or 'auto')") from None
    if d < 1 or m < 1:
        raise ValueError(f"mesh spec {spec!r} has a non-positive axis")
    return d, m


def resolve_mesh(spec: str) -> str:
    """'1x1' (default: no mesh — the single-device path, bit-exact) |
    '<data>x<model>' (shard the FL core over a (data, model) device mesh).
    Resolution: explicit config value > ``REPRO_MESH`` > '1x1'."""
    if spec in (None, "", "auto"):
        spec = os.environ.get("REPRO_MESH", "1x1")
    parse_mesh(spec)            # validate eagerly: bad specs fail loudly
    return spec


@functools.lru_cache(maxsize=None)
def build_fl_mesh(spec: str) -> Optional[Mesh]:
    """The ("data", "model") mesh for ``spec``, or ``None`` for 1x1.

    The 1x1 oracle path constructs nothing and touches no jax device
    state, so resolution alone can never perturb a single-device trace.
    Cached per spec: every plane sharing a spec shares ONE mesh object,
    which keeps ``id(mesh)``-keyed compile caches stable for the process
    lifetime."""
    d, m = parse_mesh(resolve_mesh(spec))
    if (d, m) == (1, 1):
        return None
    n = d * m
    if jax.device_count() < n:
        raise ValueError(
            f"mesh {spec!r} needs {n} devices but only "
            f"{jax.device_count()} are visible (on CPU, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n})")
    return jax.make_mesh((d, m), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_axes(mesh: Optional[Mesh]) -> tuple[int, int]:
    """``(data, model)`` axis sizes; ``(1, 1)`` for the no-mesh path."""
    if mesh is None:
        return (1, 1)
    return int(mesh.shape["data"]), int(mesh.shape["model"])


def mesh_token(mesh: Optional[Mesh]) -> tuple:
    """Compile-cache key fragment for a mesh. Empty for the no-mesh path
    so pre-existing cache keys are unchanged; ``id()`` is safe because
    :func:`build_fl_mesh` caches meshes for the process lifetime."""
    if mesh is None:
        return ()
    return ("mesh", mesh_axes(mesh), id(mesh))


def row_sharding(mesh: Mesh) -> NamedSharding:
    """The update-row buffer's NamedSharding (``ROW_SPEC``)."""
    return NamedSharding(mesh, ROW_SPEC)


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully replicated placement (every device holds the whole array) —
    the ``DatasetStore`` layout, so cohort-shard gathers are local."""
    return NamedSharding(mesh, P())


def shard_put(x, mesh: Optional[Mesh], spec: P):
    """Place ``x`` with ``NamedSharding(mesh, spec)``; identity un-meshed."""
    if mesh is None:
        return x
    return jax.device_put(x, NamedSharding(mesh, spec))


def row_align(mesh: Optional[Mesh], base: int) -> int:
    """Row-width alignment: the kernel block, additionally divisible by
    the ``model`` axis so every device owns an equal column stripe."""
    d, m = mesh_axes(mesh)
    return math.lcm(base, m)


def capacity_align(mesh: Optional[Mesh], base: int) -> int:
    """Capacity alignment: the fp32 sublane, additionally divisible by
    the ``data`` axis so every device owns an equal row stripe."""
    d, m = mesh_axes(mesh)
    return math.lcm(base, d)
