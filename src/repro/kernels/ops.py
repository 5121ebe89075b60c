"""jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True unless running on a real TPU backend — the
kernels target TPU (Mosaic); on this CPU container they execute through the
Pallas interpreter, validated against ``repro.kernels.ref`` oracles.

Higher-level conveniences:
  - ``RavelSpec``: the flattening contract (leaf order, shapes, dtypes,
    offsets) shared by every pytree<->flat-buffer boundary: the aggregation
    kernel path, the device-resident update plane, and checkpointing of
    live update rows;
  - ``aggregate_pytree``: staleness-weighted aggregation over a list of
    parameter pytrees (ravel -> kernel -> unravel), the drop-in kernel path
    for ``repro.core.aggregation``;
  - ``aggregate_rows``: index-gather entry point over a persistent [C, N]
    row buffer (the update-plane hot path — no ravel, no stack), and
    ``aggregate_rows_sparse``, its route for a buffer grown far past the
    live set (gather the K referenced rows, then the kernel);
  - ``masked_topk``: top-k of a score vector (the control plane's cohort
    selection) — XLA ``lax.top_k`` fast path, blockwise Pallas kernel on
    TPU (``REPRO_TOPK_PATH=pallas|xla|auto`` forcing);
  - ``compress_update`` / ``decompress_update``: int8 client-update
    compression with error feedback.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import flash_attention  # noqa: F401
from repro.kernels.fused_adam import fused_adam  # noqa: F401
from repro.kernels.quant8 import QBLOCK, ROWS, dequantize_q8, quantize_q8  # noqa: F401
from repro.kernels.staleness_agg import BLOCK_N, staleness_agg  # noqa: F401
from repro.kernels.topk import BLOCK_TOPK, block_topk, chosen_mask  # noqa: F401

Pytree = Any


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    return not on_tpu()


class RavelSpec:
    """Stable pytree <-> flat fp32 buffer contract.

    Built once from a template pytree; thereafter any structurally identical
    tree ravels into an ``[N]`` vector (or ``[K, N]`` rows for trees with a
    leading stacked axis) in canonical ``jax.tree.leaves`` order, and any
    ``[N]`` vector unravels back. All methods are jit-traceable; the spec
    itself is static (shapes/dtypes/offsets captured at build time)."""

    def __init__(self, template: Pytree):
        leaves = jax.tree.leaves(template)
        self.treedef = jax.tree.structure(template)
        self.shapes = tuple(tuple(l.shape) for l in leaves)
        self.dtypes = tuple(np.dtype(l.dtype) for l in leaves)
        self.sizes = tuple(int(np.prod(s)) if s else 1 for s in self.shapes)
        self.n_params = int(sum(self.sizes))

    def ravel(self, tree: Pytree) -> jax.Array:
        """tree (template structure) -> flat [N] fp32."""
        leaves = jax.tree.leaves(tree)
        return jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32) for l in leaves])

    def ravel_stacked(self, tree: Pytree) -> jax.Array:
        """tree with [K, ...]-stacked leaves -> [K, N] fp32 rows."""
        leaves = jax.tree.leaves(tree)
        K = leaves[0].shape[0]
        return jnp.concatenate(
            [l.reshape(K, -1).astype(jnp.float32) for l in leaves], axis=1)

    def unravel(self, flat: jax.Array, restore_dtype: bool = True) -> Pytree:
        out, off = [], 0
        for shape, dtype, n in zip(self.shapes, self.dtypes, self.sizes):
            x = flat[off:off + n].reshape(shape)
            out.append(x.astype(dtype) if restore_dtype else x)
            off += n
        return jax.tree.unflatten(self.treedef, out)


SUBLANE = 8  # fp32 TPU sublane; aggregate_pytree pads K to a multiple


# ----------------------------------------------------- row-buffer entry point
@functools.partial(jax.jit, static_argnames=("interpret",))
def _scatter_w_agg(buffer: jax.Array, idx: jax.Array, w: jax.Array,
                   interpret: bool) -> jax.Array:
    """Scatter the K weights to per-row weights over the FULL buffer and
    reduce with the kernel — no row gather, no materialized [K, N] copy.
    Free rows carry weight 0, an exact no-op for finite stale values; the
    NaN/Inf case (0 * inf = nan) is handled by the caller's finiteness
    guard, which falls back to ``aggregate_rows_gather``."""
    C, N = buffer.shape
    full_w = jnp.zeros((C,), jnp.float32).at[idx].add(w)
    pad_c = (-C) % SUBLANE
    pad_n = (-N) % BLOCK_N
    if pad_c or pad_n:   # non-conforming caller buffer: pad (copies)
        buffer = jnp.pad(buffer, ((0, pad_c), (0, pad_n)))
        full_w = jnp.pad(full_w, (0, pad_c))
    return staleness_agg(buffer, full_w, interpret=interpret)[:N]


@jax.jit
def _scatter_w_matvec(buffer: jax.Array, idx: jax.Array,
                      w: jax.Array) -> jax.Array:
    """XLA oracle/fallback for ``aggregate_rows`` (same scattered weights,
    one matvec over the buffer)."""
    full_w = jnp.zeros((buffer.shape[0],), jnp.float32).at[idx].add(w)
    return full_w @ buffer.astype(jnp.float32)


def _pad_rows(row_idx, weights) -> tuple[np.ndarray, np.ndarray]:
    """Pad (idx, weights) to the sublane multiple with zero-weight repeats of
    row 0 (exact no-ops under scatter-add) so round-to-round K jitter reuses
    compiled shapes."""
    idx = np.asarray(row_idx, np.int32)
    w = np.asarray(weights, np.float32)
    pad_k = (-len(idx)) % SUBLANE
    if pad_k:
        idx = np.concatenate([idx, np.repeat(idx[:1], pad_k)])
        w = np.concatenate([w, np.zeros(pad_k, np.float32)])
    return idx, w


def aggregate_rows(buffer: jax.Array, row_idx, weights,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Kernel aggregation straight off a persistent row buffer:
    ``sum_k weights[k] * buffer[row_idx[k], :]`` -> flat [W] fp32.

    The update-plane hot path: the K weights scatter-add into a [capacity]
    per-row weight vector and ``staleness_agg`` streams the whole buffer —
    no per-leaf ravel, no row gather, no host round-trip. ``UpdateStore``
    geometry (capacity % 8 == 0, width % 1024 == 0) makes this pad-free.
    Unreferenced rows ride along with weight 0 — exact for finite values;
    callers must guard the NaN/Inf case (0 * inf = nan) and recompute via
    ``aggregate_rows_gather``, as ``weighted_aggregate_rows`` does."""
    interpret = default_interpret() if interpret is None else interpret
    idx, w = _pad_rows(row_idx, weights)
    return _scatter_w_agg(buffer, jnp.asarray(idx), jnp.asarray(w), interpret)


def aggregate_rows_xla(buffer: jax.Array, row_idx, weights) -> jax.Array:
    """XLA fallback with identical semantics to ``aggregate_rows``."""
    idx, w = _pad_rows(row_idx, weights)
    return _scatter_w_matvec(buffer, jnp.asarray(idx), jnp.asarray(w))


@jax.jit
def _gather_weighted_sum(buffer: jax.Array, idx: jax.Array,
                         w: jax.Array) -> jax.Array:
    return jnp.einsum("k,kn->n", w.astype(jnp.float32),
                      buffer[idx].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_agg(buffer: jax.Array, idx: jax.Array, w: jax.Array,
                interpret: bool) -> jax.Array:
    """Gather the (sublane-padded) referenced rows, then reduce them with
    the kernel: the kernel twin of ``_gather_weighted_sum``."""
    N = buffer.shape[1]
    rows = buffer[idx].astype(jnp.float32)
    pad_n = (-N) % BLOCK_N
    if pad_n:   # non-conforming caller buffer: pad (copies)
        rows = jnp.pad(rows, ((0, 0), (0, pad_n)))
    return staleness_agg(rows, w, interpret=interpret)[:N]


def aggregate_rows_sparse(buffer: jax.Array, row_idx, weights,
                          interpret: Optional[bool] = None) -> jax.Array:
    """Kernel aggregation of ONLY the referenced rows (device gather of
    the K rows, then ``staleness_agg``): the route for a buffer grown far
    past its live set, where the full sweep of ``aggregate_rows`` would
    read mostly dead rows. Never touches freed rows, so it needs no
    finiteness guard."""
    interpret = default_interpret() if interpret is None else interpret
    idx, w = _pad_rows(row_idx, weights)
    return _gather_agg(buffer, jnp.asarray(idx), jnp.asarray(w), interpret)


def aggregate_rows_gather(buffer: jax.Array, row_idx, weights) -> jax.Array:
    """Exact-rows fallback: reduces ONLY the referenced rows (device
    gather + einsum, fused). Slower than the full-buffer sweep but immune
    to non-finite garbage in freed rows — the aggregation layer recomputes
    through this when its finiteness guard trips."""
    idx, w = _pad_rows(row_idx, weights)
    return _gather_weighted_sum(buffer, jnp.asarray(idx), jnp.asarray(w))


# ------------------------------------------------- sharded-mesh aggregation
# keyed by id(mesh); safe because repro.sharding.flmesh caches one Mesh
# object per spec for the process lifetime
_PSUM_AGG_CACHE: dict[int, Any] = {}


def _psum_agg(mesh):
    """Per-mesh jitted weighted psum over a [capacity, W] row buffer
    sharded P("data", "model"): each shard reduces its local
    [C/d, W/m] tile against its slice of the scattered per-row weight
    vector, then the d partial sums meet in one ``lax.psum`` over the
    ``data`` axis — aggregation bytes move over ICI instead of
    converging through a single device. Output: [W] sharded over
    ``model``."""
    fn = _PSUM_AGG_CACHE.get(id(mesh))
    if fn is not None:
        return fn
    from jax.sharding import PartitionSpec as P

    def _local(buf, full_w):
        part = full_w.astype(jnp.float32) @ buf.astype(jnp.float32)
        return jax.lax.psum(part, "data")

    sharded = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P("data", "model"), P("data")),
        out_specs=P("model"), check_vma=False)

    @jax.jit
    def fn(buffer, idx, w):
        full_w = jnp.zeros((buffer.shape[0],), jnp.float32).at[idx].add(w)
        return sharded(buffer, full_w)

    _PSUM_AGG_CACHE[id(mesh)] = fn
    return fn


def aggregate_rows_psum(buffer: jax.Array, row_idx, weights,
                        mesh) -> jax.Array:
    """``aggregate_rows`` semantics over a mesh-sharded buffer via a
    weighted ``lax.psum`` (see ``_psum_agg``). Same weight-0 stale-row
    contract; callers guard NaN/Inf via ``aggregate_rows_gather``."""
    idx, w = _pad_rows(row_idx, weights)
    return _psum_agg(mesh)(buffer, jnp.asarray(idx), jnp.asarray(w))


def aggregate_rows_traced(buffer: jax.Array, row_idx: jax.Array,
                          weights: jax.Array, *, sparse: bool,
                          use_pallas: bool, interpret: bool,
                          mesh=None) -> jax.Array:
    """Fully traceable twin of the ``aggregate_rows*`` dispatch for use
    INSIDE a jit (the fused-round megastep's scan body): ``row_idx`` /
    ``weights`` may be tracers, the dispatch predicates are static
    (pre-resolved by ``core.aggregation.rows_dispatch``), and the
    aggregation layer's host-sync finiteness guard becomes a ``lax.cond``
    whose true branch is the identity — bitwise equal to the stepwise
    path whenever the data is finite, and the same exact-rows recompute
    when it is not. Runs the same inner jitted kernels (jit-in-jit
    inlines); every branch sees the operands padded as the eager path
    pads them."""
    idx = jnp.asarray(row_idx, jnp.int32)
    w = jnp.asarray(weights, jnp.float32)
    pad_k = (-idx.shape[0]) % SUBLANE
    if pad_k:       # zero-weight repeats of row 0, as _pad_rows does
        idx = jnp.concatenate([idx, jnp.repeat(idx[:1], pad_k)])
        w = jnp.concatenate([w, jnp.zeros((pad_k,), jnp.float32)])
    # the mesh route mirrors weighted_aggregate_rows: with a mesh the psum
    # path is unconditional (the sparse heuristic and pallas/xla dispatch
    # only arbitrate single-device execution)
    if mesh is not None:
        flat = _psum_agg(mesh)(buffer, idx, w)
    elif sparse:
        return (_gather_agg(buffer, idx, w, interpret) if use_pallas
                else _gather_weighted_sum(buffer, idx, w))
    else:
        flat = (_scatter_w_agg(buffer, idx, w, interpret) if use_pallas
                else _scatter_w_matvec(buffer, idx, w))
    return jax.lax.cond(
        jnp.all(jnp.isfinite(flat)),
        lambda f, b, i, ww: f,
        lambda f, b, i, ww: _gather_weighted_sum(b, i, ww),
        flat, buffer, idx, w)


# --------------------------------------------------------- top-k selection
def resolve_topk_path(path: Optional[str] = None) -> str:
    """'xla' (lax.top_k — the fast path everywhere off-TPU) | 'pallas'
    (blockwise kernel) | 'auto' (pallas on a real TPU backend, xla
    otherwise). Resolution: explicit arg > ``REPRO_TOPK_PATH`` > 'auto'."""
    if path in (None, "", "auto"):
        path = os.environ.get("REPRO_TOPK_PATH", "auto")
    if path == "auto":
        return "pallas" if on_tpu() else "xla"
    if path not in ("pallas", "xla"):
        raise ValueError(f"unknown topk path {path!r} "
                         "(expected 'pallas', 'xla', or 'auto')")
    return path


def masked_topk(scores: jax.Array, k: int, *,
                path: Optional[str] = None,
                interpret: Optional[bool] = None,
                block: int = BLOCK_TOPK) -> tuple[jax.Array, jax.Array]:
    """Top-k of ``scores [M]`` -> ``(vals [k], idx [k])``, descending;
    masked entries are ``-inf`` scores (the caller filters them by value).
    Traceable (usable inside jit). The Pallas path computes per-block
    candidates (``kernels/topk.py``) and reduces them with one small
    ``lax.top_k``; both paths break ties toward the lowest index."""
    M = scores.shape[0]
    assert k <= M, (k, M)
    path = resolve_topk_path(path)
    if path == "xla" or M <= block or k > block:
        return jax.lax.top_k(scores.astype(jnp.float32), k)
    interpret = default_interpret() if interpret is None else interpret
    pad = (-M) % block
    if pad:
        scores = jnp.pad(scores.astype(jnp.float32), (0, pad),
                         constant_values=-jnp.inf)
    vals, idx = block_topk(scores, k, block=block, interpret=interpret)
    cand_v, cand_i = vals.reshape(-1), idx.reshape(-1)
    top_v, pos = jax.lax.top_k(cand_v, k)
    return top_v, cand_i[pos]


def scored_topk(num: jax.Array, den: jax.Array, booster: jax.Array,
                eligible: jax.Array, ever: jax.Array, beta,
                k: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The full Algorithm-3 top-k selection step as one traceable
    composition: CEF score (``booster * num/den``), bootstrap (+inf for
    never-invoked), eligibility masking (-inf), ``masked_topk``, and the
    in-kernel booster update (selected -> 1, idle-unselected -> * beta).
    Returns ``(idx [k], valid [k], new_booster [M])``.

    This is THE selection op: ``FleetStore.select_topk`` jits it per
    round and the fused-round megastep (``core.megastep``) inlines it in
    its ``lax.scan`` body — one definition, so both paths are bitwise the
    same program. ``k`` must be static under jit."""
    score = booster * (num / jnp.maximum(den, 1e-12))
    score = jnp.where(ever, score, jnp.inf)       # bootstrap: uninvoked
    score = jnp.where(eligible, score, -jnp.inf)  # mask busy/removed
    vals, idx = masked_topk(score, k)
    valid = vals > -jnp.inf
    chosen = chosen_mask(idx, valid, score.shape[0])
    boost = jnp.where(chosen, 1.0,
                      jnp.where(eligible, booster * beta, booster))
    return idx, valid, boost


def aggregate_pytree(updates: Sequence[Pytree], weights,
                     interpret: Optional[bool] = None, *,
                     restore_dtype: bool = True) -> Pytree:
    """Kernel-path aggregation over K parameter pytrees: ravel ->
    [K, N] buffer -> staleness_agg -> unravel. The default-dispatch
    target of ``core.aggregation.weighted_aggregate``.

    K pads to the fp32 sublane multiple with zero-weight rows (exact
    no-ops) so round-to-round K jitter reuses compiled shapes; N pads to
    the kernel block. ``restore_dtype=False`` keeps fp32 leaves
    (``weighted_aggregate``'s contract)."""
    interpret = default_interpret() if interpret is None else interpret
    spec = RavelSpec(updates[0])
    stacked = jnp.stack([spec.ravel(u) for u in updates], 0)
    w = jnp.asarray(weights, jnp.float32)
    K, N = stacked.shape
    pad_k = (-K) % SUBLANE
    pad_n = (-N) % BLOCK_N
    if pad_k or pad_n:
        stacked = jnp.pad(stacked, ((0, pad_k), (0, pad_n)))
        w = jnp.pad(w, (0, pad_k))
    agg = staleness_agg(stacked, w, interpret=interpret)
    return spec.unravel(agg[:N], restore_dtype=restore_dtype)


def compress_update(update: Pytree, error_feedback: Optional[Pytree] = None,
                    interpret: Optional[bool] = None):
    """int8-compress a client update with residual error feedback.

    Returns ((q, scales, meta), new_error_feedback)."""
    interpret = default_interpret() if interpret is None else interpret
    spec = RavelSpec(update)
    flat = spec.ravel(update)
    if error_feedback is not None:
        flat = flat + error_feedback
    N = spec.n_params
    pad = (-N) % (ROWS * QBLOCK)
    flat_p = jnp.pad(flat, (0, pad)) if pad else flat
    q, s = quantize_q8(flat_p, interpret=interpret)
    deq = dequantize_q8(q, s, interpret=interpret)[:N]
    err = flat - deq
    return (q, s, spec), err


def decompress_update(q, s, meta: "RavelSpec",
                      interpret: Optional[bool] = None) -> Pytree:
    interpret = default_interpret() if interpret is None else interpret
    flat = dequantize_q8(q, s, interpret=interpret)[:meta.n_params]
    return meta.unravel(flat)
