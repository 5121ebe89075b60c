"""Pallas TPU kernel: blockwise masked top-k over a score vector.

The columnar control plane's fleet-scale cohort selection reduces to
"top-k of an ``[M]`` score vector under an eligibility mask" (the mask is
applied upstream as ``-inf`` scores — DESIGN.md §10). ``lax.top_k`` is the
XLA fast path; this kernel is the TPU variant that keeps the whole sweep
in one pass over VMEM-resident tiles:

grid over ``[G, B]`` score blocks, eight blocks (one sublane tile) per
program; each program runs k rounds of row-wise (max, first-argmax,
mask-out) over its ``[8, B]`` VMEM tile — k is tiny (a cohort, <= a few
hundred) against B — and writes each block's local top-k (values + GLOBAL
indices) to a ``[G, k]`` candidate table. G pads to a multiple of 8 with
``-inf`` blocks, which the wrapper trims, so every block shape meets the
TPU's (8, 128) tiling rule. The caller then reduces the
``G*k`` candidates with one small ``lax.top_k``. Ties break toward the
lowest index at both levels (first-argmax in-block, block-major candidate
order across blocks), matching ``lax.top_k``'s tie order, so the two paths
agree exactly on distinct-score inputs and on tie *order* as well.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_TOPK = 1024   # scores per block (lane-aligned: 8 x 128)
ROWS = 8            # blocks per grid program (the fp32 sublane count)


def _topk_kernel(x_ref, vals_ref, idx_ref, *, k: int, block: int):
    x = x_ref[...].astype(jnp.float32)                       # [8, B]
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)
    base = (pl.program_id(0) * ROWS + row) * block           # [8, 1]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)    # 2D iota (TPU)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (ROWS, k), 1)

    def body(j, carry):
        xv, vals, idx = carry
        m = jnp.max(xv, axis=1, keepdims=True)
        # first index attaining the max (ties -> lowest, lax.top_k order)
        a = jnp.min(jnp.where(xv == m, col, block), axis=1, keepdims=True)
        vals = jnp.where(kcol == j, m, vals)
        idx = jnp.where(kcol == j, base + a, idx)
        xv = jnp.where(col == a, -jnp.inf, xv)               # extract
        return xv, vals, idx

    _, vals, idx = jax.lax.fori_loop(
        0, k, body,
        (x, jnp.full((ROWS, k), -jnp.inf, jnp.float32),
         jnp.zeros((ROWS, k), jnp.int32)))
    vals_ref[...] = vals
    idx_ref[...] = idx


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def block_topk(scores: jax.Array, k: int, *, block: int = BLOCK_TOPK,
               interpret: bool = True):
    """Per-block top-k candidates of ``scores [M]`` (M % block == 0):
    returns ``(vals [G, k], global_idx [G, k])`` with G = M // block."""
    M = scores.shape[0]
    assert M % block == 0 and k <= block, (M, block, k)
    G = M // block
    Gp = pl.cdiv(G, ROWS) * ROWS
    x = scores.reshape(G, block).astype(jnp.float32)
    if Gp != G:
        x = jnp.pad(x, ((0, Gp - G), (0, 0)), constant_values=-jnp.inf)
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, block=block),
        grid=(Gp // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((ROWS, k), lambda i: (i, 0)),
                   pl.BlockSpec((ROWS, k), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Gp, k), jnp.float32),
                   jax.ShapeDtypeStruct((Gp, k), jnp.int32)],
        interpret=interpret,
    )(x)
    return vals[:G], idx[:G]


def chosen_mask(idx: jax.Array, valid: jax.Array, n: int) -> jax.Array:
    """Scatter a top-k result back to an ``[n]`` bool membership mask
    (invalid slots — ``-inf`` scores that padded the k — stay False).
    Traceable; shared by the selection kernel wrapper (``kernels.ops``)
    and the fused-round megastep's in-scan booster update."""
    return jnp.zeros((n,), bool).at[idx].set(valid)
