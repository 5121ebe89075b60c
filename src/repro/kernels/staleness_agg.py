"""Pallas TPU kernel: K-way staleness-weighted parameter aggregation.

The paper's aggregation hot loop: ``out = sum_k w[k] * updates[k, :]``
over every model parameter. Memory-bound (arithmetic intensity ~= 1 FLOP /
2 bytes), so the kernel streams [BK, BN] tiles HBM->VMEM once, accumulates
in fp32, and writes each output tile once — the roofline optimum of
(K+1)/K x N x itemsize bytes moved.

Tiling: grid (N / BN, cdiv(K, BK)) with BN = 1024 = 8x128 on the lanes and
the row axis innermost. The output tile stays resident across the row
blocks and accumulates their partial sums. BK is the whole K up to
``BLOCK_K`` rows (one block: the reduction order of a single ``sum`` over
axis 0), and ``BLOCK_K`` beyond that, which keeps the double-buffered tile
inside the chip's scoped VMEM however far the update buffer grows. A
ragged last row block is masked, so K need only be a sublane multiple.
Weights ride along as a [BK, 1] tile of the same row block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_N = 1024
#: rows per block: a [1024, 1024] fp32 tile is 4 MiB, 8 MiB double-buffered
BLOCK_K = 1024


def _agg_kernel(w_ref, u_ref, o_ref, *, n_rows: int, block_k: int):
    k = pl.program_id(1)
    u = u_ref[...].astype(jnp.float32)          # [BK, BN]
    w = w_ref[...].astype(jnp.float32)          # [BK, 1]
    if n_rows % block_k:
        # the last row block reads past the buffer: zero those rows (a
        # select, not a multiply — the padding is not guaranteed finite)
        rows = k * block_k + jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
        w = jnp.where(rows < n_rows, w, 0.0)
        u = jnp.where(rows < n_rows, u, 0.0)
    part = jnp.sum(u * w, axis=0, keepdims=True)

    @pl.when(k == 0)
    def _():
        o_ref[...] = part

    @pl.when(k > 0)
    def _():
        o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def staleness_agg(updates: jax.Array, weights: jax.Array, *,
                  interpret: bool = True, block_n: int = BLOCK_N) -> jax.Array:
    """updates [K, N] (N % block_n == 0), weights [K] -> [N] fp32."""
    K, N = updates.shape
    assert N % block_n == 0, (N, block_n)
    bk = K if K <= BLOCK_K else BLOCK_K
    w2 = weights.reshape(K, 1).astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_agg_kernel, n_rows=K, block_k=bk),
        grid=(N // block_n, pl.cdiv(K, bk)),
        in_specs=[
            pl.BlockSpec((bk, 1), lambda i, k: (k, 0)),        # weights
            pl.BlockSpec((bk, block_n), lambda i, k: (k, i)),  # update tile
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(w2, updates)
    return out.reshape(N)
