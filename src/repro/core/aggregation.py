"""Staleness-weighted asynchronous aggregation (paper §III-B).

Given K client models (current + up to ``max_staleness`` rounds old), the
aggregator computes

    w_{T+1} = sum_i s(t_i, T) * (n_i / n) * w^i   /   sum_i s(t_i, T) * (n_i / n)

where ``s`` is Eq. 2 (1/sqrt(T - t_i + 1)) for Apodotiko or Eq. 1 (t_i/T)
for FedLesScan. The denominator normalization matches the FedLess reference
implementation (the raw paper formula shrinks the model norm whenever any
update is stale).

The hot loop — a K-way weighted reduction over every parameter — is exactly
the paper's serverless aggregation function. Three execution paths
(DESIGN.md §2):

  * **Pallas** (default): the K update pytrees are raveled and concatenated
    into one ``[K, N]`` fp32 buffer (K padded to the fp32 sublane multiple,
    N padded to the kernel block), then reduced by the fused
    ``kernels/staleness_agg.py`` multiply-accumulate kernel — interpret mode
    on CPU/GPU, compiled Mosaic on TPU. A one-time numerical-equivalence
    self-check against a float64 reference runs before the first kernel
    dispatch; a mismatch raises, and so does any kernel error: a broken
    kernel fails the run instead of quietly moving it to XLA.
  * **XLA** (``_weighted_sum_stacked``): jit'd per-leaf stacked reduction.
    Taken off-TPU above the interpret-mode size cap, and forced via
    ``path="xla"`` or ``REPRO_AGG_PATH=xla``.
  * **Sharded**: on a mesh, stacked updates [K, ...] are sharded over the
    ``pod``/``data`` axes and the reduce lowers to a weighted psum — this is
    how the FaaS aggregation pattern maps onto TPU collectives (DESIGN.md §4).

Dispatch policy: ``path`` argument > ``REPRO_AGG_PATH`` env var > ``auto``
(Pallas on TPU, and off-TPU up to the interpret-mode size cap).
``last_path()`` reports which path produced the most recent result
(observability + tests).

``weighted_aggregate`` consumes a *list of pytrees* (the legacy blob path).
``weighted_aggregate_rows`` is the device-resident update-plane fast path:
it reads K rows straight out of an ``UpdateStore`` buffer by index and
skips the ravel/stack work entirely (DESIGN.md §2, "update plane").
"""
from __future__ import annotations

import functools
import os
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.staleness import STALENESS_FNS
from repro.kernels import ops as kernel_ops
from repro.kernels.staleness_agg import BLOCK_N

Pytree = Any

_PALLAS_CHECKED = False   # the equivalence self-check has passed
_LAST_PATH = "none"
# In interpret mode (no TPU) the kernel is a correctness path, ~100x slower
# than XLA at large N; ``auto`` only takes it below this parameter count.
# Compiled TPU dispatch ignores the cap. Env-tunable for experiments.
_INTERP_MAX_N = int(os.environ.get("REPRO_AGG_PALLAS_MAX_INTERP_N",
                                   str(1 << 18)))


def last_path() -> str:
    """Which execution path ('pallas' | 'xla' | 'psum') produced the last
    aggregate."""
    return _LAST_PATH


def staleness_weights(rounds: Sequence[int], cardinalities: Sequence[int],
                      current_round: int, fn: str = "eq2") -> np.ndarray:
    s = STALENESS_FNS[fn]
    n = float(sum(cardinalities)) or 1.0
    w = np.array([s(t_i, current_round) * (n_i / n)
                  for t_i, n_i in zip(rounds, cardinalities)], np.float64)
    total = w.sum()
    if total <= 0:
        w = np.full(len(w), 1.0 / max(len(w), 1))
        total = 1.0
    return (w / total).astype(np.float32)


# --------------------------------------------------------------- XLA path
@functools.partial(jax.jit, donate_argnums=(0,))
def _weighted_sum_stacked(stacked: Pytree, weights: jax.Array) -> Pytree:
    def one(x):
        wf = weights.astype(jnp.float32)
        shape = (x.shape[0],) + (1,) * (x.ndim - 1)
        return jnp.sum(x.astype(jnp.float32) * wf.reshape(shape), axis=0)

    return jax.tree.map(one, stacked)


# ------------------------------------------------------------ Pallas path
# The ravel -> [K, N] buffer -> kernel -> unravel plumbing (including the
# sublane/block padding) lives in kernels/ops.aggregate_pytree; this module
# only owns the dispatch policy around it.
def _check_pallas() -> None:
    """One-time numerical-equivalence check of the kernel path against a
    float64 reference, run before the first kernel dispatch.

    Runs a deterministic ragged pytree that exercises both pad paths (K not
    a sublane multiple, N not a block multiple). A mismatch raises; so does
    a kernel that fails to compile or run."""
    global _PALLAS_CHECKED
    if _PALLAS_CHECKED:
        return
    rng = np.random.default_rng(0)
    ups = [{"a": jnp.asarray(rng.normal(size=(BLOCK_N,)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(17,)), jnp.float32)}
           for _ in range(3)]
    w = staleness_weights([2, 1, 0], [5, 3, 2], 2)
    got = kernel_ops.aggregate_pytree(ups, w, restore_dtype=False)
    w64 = np.asarray(w, np.float64)
    for k in ("a", "b"):
        want = np.einsum("k,kn->n", w64,
                         np.stack([np.asarray(u[k], np.float64) for u in ups]))
        if not np.allclose(np.asarray(got[k]), want, rtol=1e-5, atol=1e-6):
            raise RuntimeError(
                "staleness_agg kernel disagrees with the float64 reference "
                f"on {jax.default_backend()} (leaf {k!r})")
    _PALLAS_CHECKED = True


def _use_pallas(path: str, n_params: int) -> bool:
    """The kernel route: forced, or ``auto`` on TPU (and off-TPU up to the
    interpret-mode size cap). Runs the self-check before the first use."""
    use = path == "pallas" or (path == "auto" and (
        kernel_ops.on_tpu() or n_params <= _INTERP_MAX_N))
    if use:
        _check_pallas()
    return use


# --------------------------------------------------------------- dispatch
def weighted_aggregate(updates: Sequence[Pytree], weights: np.ndarray,
                       out_dtype=None, path: Optional[str] = None) -> Pytree:
    """updates: list of K pytrees -> weighted average pytree (fp32 leaves
    unless ``out_dtype`` is given).

    ``path``: "auto" (default — the Pallas kernel; off-TPU the interpreter
    is only taken up to ``REPRO_AGG_PALLAS_MAX_INTERP_N`` params),
    "pallas" (force kernel), or "xla". ``REPRO_AGG_PATH`` overrides the
    default. Kernel errors propagate."""
    global _LAST_PATH
    assert len(updates) == len(weights) and len(updates) > 0
    path = path or os.environ.get("REPRO_AGG_PATH", "auto")
    if path not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown aggregation path {path!r}")

    n_params = sum(int(np.prod(l.shape)) if l.shape else 1
                   for l in jax.tree.leaves(updates[0]))
    if _use_pallas(path, n_params):
        out = kernel_ops.aggregate_pytree(updates, weights,
                                          restore_dtype=False)
        _LAST_PATH = "pallas"
    else:
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *updates)
        out = _weighted_sum_stacked(stacked, jnp.asarray(weights))
        _LAST_PATH = "xla"
    if out_dtype is not None:
        out = jax.tree.map(lambda x: x.astype(out_dtype), out)
    return out


def weighted_aggregate_rows(buffer, row_idx, weights,
                            spec: "kernel_ops.RavelSpec", out_dtype=None,
                            path: Optional[str] = None, mesh=None) -> Pytree:
    """Row-index fast path over the device-resident update plane.

    ``buffer`` is an ``UpdateStore``'s persistent [capacity, N] fp32 device
    buffer; ``row_idx`` selects the K pending updates; ``spec`` is the
    ``RavelSpec`` of the global model. One device gather feeds
    ``kernels/staleness_agg`` (or the XLA einsum fallback) directly — no
    ravel, no stack, no per-leaf work — and the flat result unravels exactly
    once to produce the new global pytree. Dispatch policy (``path`` arg,
    ``REPRO_AGG_PATH``, self-check, interpret-mode size cap) is identical to
    ``weighted_aggregate``; so is the error policy: kernel errors
    propagate.

    With ``mesh`` set (the buffer sharded P("data", "model")), the
    reduction routes to ``kernels/ops.aggregate_rows_psum``: a weighted
    ``lax.psum`` of per-shard partial matvecs over the ``data`` axis, so
    aggregation bytes move over ICI instead of through one device. Same
    weight-0 stale-row contract, same finiteness-guard recompute."""
    global _LAST_PATH
    assert len(row_idx) == len(weights) and len(row_idx) > 0
    path = path or os.environ.get("REPRO_AGG_PATH", "auto")
    if path not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown aggregation path {path!r}")

    if mesh is not None:
        flat = kernel_ops.aggregate_rows_psum(buffer, row_idx, weights, mesh)
        _LAST_PATH = "psum"
        if not _all_finite(flat):
            flat = kernel_ops.aggregate_rows_gather(buffer, row_idx, weights)
        out = spec.unravel(flat[:spec.n_params], restore_dtype=False)
        if out_dtype is not None:
            out = jax.tree.map(lambda x: x.astype(out_dtype), out)
        return out

    # The full-buffer sweep reads every row; once the reference set is a
    # small fraction of a grown buffer (capacity only doubles, never
    # shrinks), gathering just the K referenced rows is cheaper — and
    # needs no finiteness guard, since it never touches freed rows.
    sparse = (path != "pallas"
              and buffer.shape[0] >= 4 * max(len(row_idx), kernel_ops.SUBLANE))
    use_pallas = _use_pallas(path, spec.n_params)
    if sparse and use_pallas:
        flat = kernel_ops.aggregate_rows_sparse(buffer, row_idx, weights)
    elif sparse:
        flat = kernel_ops.aggregate_rows_gather(buffer, row_idx, weights)
    elif use_pallas:
        flat = kernel_ops.aggregate_rows(buffer, row_idx, weights)
    else:
        flat = kernel_ops.aggregate_rows_xla(buffer, row_idx, weights)
    _LAST_PATH = "pallas" if use_pallas else "xla"
    # Finiteness guard: the full-buffer sweep multiplies freed rows by
    # weight 0, which is only exact for finite stale values (0 * inf = nan).
    # A non-finite result triggers one exact recompute over just the
    # referenced rows, so a diverged-then-pruned client can never poison a
    # later aggregate. The check reads the [W] result, not the buffer.
    if not sparse and not _all_finite(flat):
        flat = kernel_ops.aggregate_rows_gather(buffer, row_idx, weights)
    # buffer rows are block-padded (W >= N); unravel exactly once per round
    out = spec.unravel(flat[:spec.n_params], restore_dtype=False)
    if out_dtype is not None:
        out = jax.tree.map(lambda x: x.astype(out_dtype), out)
    return out


def _all_finite(flat: jax.Array) -> bool:
    """The finiteness guard's blocking read of one device scalar."""
    finite = jnp.all(jnp.isfinite(flat))
    with obs.sync("agg_finite"):
        return bool(finite)


def rows_dispatch(buffer_rows: int, k: int, n_params: int,
                  path: Optional[str] = None) -> tuple[bool, bool, bool]:
    """Resolve the ``weighted_aggregate_rows`` dispatch predicates
    *statically* -> ``(sparse, use_pallas, interpret)``.

    The fused-round megastep must bake the aggregation route into its
    jitted scan at trace time, so the route has to be decided from static
    facts only (buffer capacity, K, model size, env/path policy). The
    expressions here are verbatim from ``weighted_aggregate_rows`` —
    keeping them in this module means a policy change cannot silently
    fork the two paths."""
    path = path or os.environ.get("REPRO_AGG_PATH", "auto")
    if path not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown aggregation path {path!r}")
    sparse = (path != "pallas"
              and buffer_rows >= 4 * max(k, kernel_ops.SUBLANE))
    use_pallas = _use_pallas(path, n_params)
    return sparse, use_pallas, kernel_ops.default_interpret()


def incremental_aggregate(acc: Optional[Pytree], update: Pytree,
                          weight: float) -> Pytree:
    """Streaming form: acc += w * update (callers normalize at the end).
    Used when K is large and stacking would blow host memory."""
    if acc is None:
        return jax.tree.map(lambda x: x.astype(jnp.float32) * weight, update)
    return jax.tree.map(lambda a, x: a + x.astype(jnp.float32) * weight,
                        acc, update)
