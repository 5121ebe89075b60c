"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (the CPU tests) cannot see the chip compiler's rules: tile
alignment, scoped VMEM, device memory. These tests compile each kernel of
the main path at the paper MNIST model's width (582,026 params) for a
described ``v5e:2x2`` topology, which the installed TPU compiler builds for
without a chip attached. Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_adam import fused_adam
from repro.kernels.staleness_agg import staleness_agg
from repro.kernels.topk import block_topk

#: update-row width: 582,026 params padded to the 2048-multiple mesh row
W_ROWS = 583_680
#: fused-Adam flat width: 582,026 params padded to the kernel block (8192)
W_ADAM = 589_824
LANES = 128    # the paper cohort K = 100, bucketed


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel, not XLA
    return compiled


@pytest.mark.parametrize("rows", [128, 4096])
def test_staleness_agg_compiles(one_chip, rows):
    """4,096 rows is past the one-block VMEM limit: the row axis tiles."""
    _compile(one_chip, lambda u, w: staleness_agg(u, w, interpret=False),
             ((rows, W_ROWS), jnp.float32), ((rows,), jnp.float32))


def test_block_topk_compiles(one_chip):
    _compile(one_chip, lambda s: block_topk(s, 100, interpret=False),
             ((204_800,), jnp.float32))


@pytest.mark.parametrize("lanes", [0, LANES])
def test_fused_adam_compiles(one_chip, lanes):
    """One step, and the cohort program's form: vmapped over the lanes."""
    def step(p, m, v, g, t):
        return fused_adam(p, m, v, g, t, lr=1e-3, interpret=False)

    lead = (lanes,) if lanes else ()
    fn = jax.vmap(step, in_axes=(0, 0, 0, 0, None)) if lanes else step
    flat = (lead + (W_ADAM,), jnp.float32)
    _compile(one_chip, fn, flat, flat, flat, flat, ((), jnp.int32))
