"""Minimal optax-style optimizers over pytrees: SGD, momentum, Adam,
Adafactor (factored second moment — used for the 480B MoE where Adam's fp32
moments do not fit HBM even fully sharded).

All states are pytrees mirroring the parameter tree so the sharding rule
engine (``repro.sharding``) can derive optimizer-state shardings (ZeRO-1)
from the parameter logical axes.

The Adam path can dispatch to the fused ``kernels/fused_adam`` Pallas
kernel (one HBM pass over p/m/v/g instead of ~12 unfused accesses),
mirroring the aggregation dispatch pattern: a one-time ref-equivalence
self-check runs before the kernel is first chosen and raises on a mismatch
or a kernel error (no quiet switch to XLA), and
``REPRO_ADAM_PATH=fused|xla|auto`` forces a path.
``auto`` only takes the kernel on a real TPU backend — off-TPU the Pallas
interpreter inside the per-step training loop would be a slowdown, unlike
the once-per-round aggregation kernel. Fused state is flat ([Np] m/v
vectors) rather than tree-shaped, so it is excluded from the sharding-rule
derivation (single-host FL clients only).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

Pytree = Any


class Optimizer(NamedTuple):
    init: Callable[[Pytree], Pytree]
    update: Callable[[Pytree, Pytree, Pytree], tuple[Pytree, Pytree]]
    name: str
    #: HBM bytes of one update of ``n`` parameters, where a kernel whose
    #: traffic is known runs it
    step_bytes: Optional[Callable[[int], int]] = None


def apply_updates(params: Pytree, updates: Pytree) -> Pytree:
    return jax.tree.map(lambda p, u: (p + u.astype(p.dtype)), params, updates)


def sgd(lr: float) -> Optimizer:
    def init(params):
        return {}

    def update(grads, state, params):
        return jax.tree.map(lambda g: -lr * g, grads), state

    return Optimizer(init, update, "sgd")


def momentum(lr: float, beta: float = 0.9) -> Optimizer:
    def init(params):
        return {"m": jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)}

    def update(grads, state, params):
        m = jax.tree.map(lambda m_, g: beta * m_ + g.astype(jnp.float32),
                         state["m"], grads)
        return jax.tree.map(lambda m_: -lr * m_, m), {"m": m}

    return Optimizer(init, update, "momentum")


def adam(lr: float, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8) -> Optimizer:
    def init(params):
        z = lambda p: jnp.zeros_like(p, jnp.float32)
        return {"m": jax.tree.map(z, params),
                "v": jax.tree.map(z, params),
                "t": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        t = state["t"] + 1
        bc1 = 1.0 - b1 ** t.astype(jnp.float32)
        bc2 = 1.0 - b2 ** t.astype(jnp.float32)
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
                         state["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g.astype(jnp.float32)),
                         state["v"], grads)
        upd = jax.tree.map(
            lambda m_, v_: -lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps), m, v)
        return upd, {"m": m, "v": v, "t": t}

    return Optimizer(init, update, "adam")


# ------------------------------------------------------- fused Adam kernel
_FUSED_ADAM_CHECKED = False   # the self-check has passed


def _check_fused_adam() -> None:
    """One-time ref-equivalence check of the fused Pallas Adam step against
    the XLA implementation on a deterministic input (mirrors the
    aggregation kernel's check), run before the kernel is first chosen. A
    mismatch raises; so does a kernel that fails to compile or run."""
    global _FUSED_ADAM_CHECKED
    if _FUSED_ADAM_CHECKED:
        return
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.fused_adam import BLOCK, fused_adam
    from repro.kernels.ops import default_interpret

    rng = np.random.default_rng(0)
    N, t, lr = BLOCK, 3, 1e-3
    p, g = rng.normal(size=(2, N)).astype(np.float32)
    m = rng.normal(size=N).astype(np.float32) * 0.1
    v = np.abs(rng.normal(size=N)).astype(np.float32) * 0.01
    got = fused_adam(jnp.asarray(p), jnp.asarray(m), jnp.asarray(v),
                     jnp.asarray(g), jnp.int32(t), lr=lr,
                     interpret=default_interpret())
    want = ref.fused_adam(jnp.asarray(p), jnp.asarray(m), jnp.asarray(v),
                          jnp.asarray(g), lr=lr, t=t)
    for name, a, b in zip("pmv", got, want):
        if not np.allclose(np.asarray(a), np.asarray(b),
                           rtol=1e-5, atol=1e-6):
            raise RuntimeError(
                "fused_adam kernel disagrees with the reference on "
                f"{jax.default_backend()} (output {name!r})")
    _FUSED_ADAM_CHECKED = True


def adam_fused(lr: float, b1: float = 0.9, b2: float = 0.999,
               eps: float = 1e-8) -> Optimizer:
    """Adam via the fused ``kernels/fused_adam`` Pallas kernel. Params and
    grads are raveled through the shared ``RavelSpec`` contract into one
    flat fp32 vector padded to the kernel block (pad lanes carry zero
    grads -> exact no-ops); m/v state is kept flat."""
    from repro.kernels.fused_adam import BLOCK, fused_adam, hbm_bytes
    from repro.kernels.ops import RavelSpec, default_interpret

    def _flat(spec, tree):
        flat = spec.ravel(tree)
        pad = (-spec.n_params) % BLOCK
        return jnp.pad(flat, (0, pad)) if pad else flat

    def init(params):
        spec = RavelSpec(params)
        n = spec.n_params + (-spec.n_params) % BLOCK
        return {"m": jnp.zeros(n, jnp.float32),
                "v": jnp.zeros(n, jnp.float32),
                "t": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        spec = RavelSpec(grads)
        p_flat = _flat(spec, params)
        t = state["t"] + 1
        po, mo, vo = fused_adam(p_flat, state["m"], state["v"],
                                _flat(spec, grads), t, lr=lr, b1=b1, b2=b2,
                                eps=eps, interpret=default_interpret())
        upd_flat = po - p_flat
        upd = spec.unravel(upd_flat[:spec.n_params], restore_dtype=False)
        return upd, {"m": mo, "v": vo, "t": t}

    return Optimizer(init, update, "adam-fused", hbm_bytes)


def adafactor(lr: float = 1e-2, eps: float = 1e-30, clip: float = 1.0) -> Optimizer:
    """Factored second-moment (Shazeer & Stern). Rank>=2 leaves keep only
    row/col statistics -> O(n+m) state instead of O(n*m); no first moment."""

    def init(params):
        def one(p):
            if p.ndim >= 2:
                return {"row": jnp.zeros(p.shape[:-1], jnp.float32),
                        "col": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)}
            return {"v": jnp.zeros_like(p, jnp.float32)}

        return {"s": jax.tree.map(one, params), "t": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        t = state["t"] + 1
        beta = 1.0 - (t.astype(jnp.float32) + 1.0) ** -0.8

        def one(g, s):
            g = g.astype(jnp.float32)
            g2 = jnp.square(g) + eps
            if g.ndim >= 2:
                row = beta * s["row"] + (1 - beta) * jnp.mean(g2, axis=-1)
                col = beta * s["col"] + (1 - beta) * jnp.mean(g2, axis=-2)
                row_mean = jnp.mean(row, axis=-1, keepdims=True)
                r = (row / jnp.maximum(row_mean, eps))[..., None]
                c = col[..., None, :]
                vhat = r * c
                upd = g * jax.lax.rsqrt(jnp.maximum(vhat, eps))
                new_s = {"row": row, "col": col}
            else:
                v = beta * s["v"] + (1 - beta) * g2
                upd = g * jax.lax.rsqrt(jnp.maximum(v, eps))
                new_s = {"v": v}
            # update clipping (RMS <= clip)
            rms = jnp.sqrt(jnp.mean(jnp.square(upd)) + 1e-12)
            upd = upd / jnp.maximum(1.0, rms / clip)
            return -lr * upd, new_s

        flat_g, tdef = jax.tree.flatten(grads)
        flat_s = tdef.flatten_up_to(state["s"])
        outs = [one(g, s) for g, s in zip(flat_g, flat_s)]
        upd = tdef.unflatten([o[0] for o in outs])
        new_s = tdef.unflatten([o[1] for o in outs])
        return upd, {"s": new_s, "t": t}

    return Optimizer(init, update, "adafactor")


def build_optimizer(name: str, lr: float) -> Optimizer:
    if name == "sgd":
        return sgd(lr)
    if name == "momentum":
        return momentum(lr)
    if name == "adam":
        path = os.environ.get("REPRO_ADAM_PATH", "auto")
        if path not in ("auto", "fused", "xla"):
            raise ValueError(f"unknown adam path {path!r}")
        if path == "auto":
            from repro.kernels.ops import on_tpu
            path = "fused" if on_tpu() else "xla"
        if path == "xla":
            return adam(lr)
        _check_fused_adam()
        return adam_fused(lr)
    if name == "adafactor":
        return adafactor(lr)
    raise ValueError(f"unknown optimizer {name}")
