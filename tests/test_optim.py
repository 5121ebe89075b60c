"""Optimizer tests: convergence on a quadratic, state shapes, adafactor
memory factorization, fused-Adam kernel dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import (
    adafactor,
    adam,
    adam_fused,
    apply_updates,
    build_optimizer,
    momentum,
    sgd,
)


def _minimize(opt, steps=200):
    target = jnp.array([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)

    def loss(p):
        return jnp.sum(jnp.square(p["w"] - target))

    for _ in range(steps):
        g = jax.grad(loss)(params)
        upd, state = opt.update(g, state, params)
        params = apply_updates(params, upd)
    return float(loss(params))


@pytest.mark.parametrize("name,lr", [("sgd", 0.1), ("momentum", 0.02),
                                     ("adam", 0.1), ("adafactor", 0.3)])
def test_optimizers_converge_on_quadratic(name, lr):
    assert _minimize(build_optimizer(name, lr)) < 1e-2


def test_adam_state_mirrors_params():
    opt = adam(1e-3)
    params = {"a": jnp.zeros((4, 5)), "b": {"c": jnp.zeros(7)}}
    st = opt.init(params)
    assert st["m"]["a"].shape == (4, 5)
    assert st["v"]["b"]["c"].shape == (7,)
    assert st["m"]["a"].dtype == jnp.float32


def test_adafactor_state_is_factored():
    opt = adafactor(1e-2)
    params = {"w": jnp.zeros((128, 256)), "b": jnp.zeros(16)}
    st = opt.init(params)
    # rank-2 leaf: row [128] + col [256] instead of 128*256
    assert st["s"]["w"]["row"].shape == (128,)
    assert st["s"]["w"]["col"].shape == (256,)
    assert st["s"]["b"]["v"].shape == (16,)
    n_state = sum(int(x.size) for x in jax.tree.leaves(st))
    n_params = 128 * 256 + 16
    assert n_state < n_params / 50  # >50x smaller than Adam's m+v


def test_adam_matches_reference_formula():
    opt = adam(0.1, b1=0.9, b2=0.999)
    params = {"w": jnp.array([1.0])}
    st = opt.init(params)
    g = {"w": jnp.array([0.5])}
    upd, st = opt.update(g, st, params)
    # t=1: mhat = g, vhat = g^2 -> update = -lr * g/(|g|+eps) ~= -lr
    np.testing.assert_allclose(np.asarray(upd["w"]), [-0.1], rtol=1e-4)


# ------------------------------------------------------- fused Adam kernel
def test_fused_adam_self_check_passes():
    from repro.optim import optimizers
    optimizers._check_fused_adam()
    assert optimizers._FUSED_ADAM_CHECKED


def test_fused_adam_self_check_mismatch_raises(monkeypatch):
    """A fused-Adam kernel that disagrees with the reference fails the
    run; Adam does not quietly become the XLA version."""
    from repro.kernels import fused_adam as fa
    from repro.optim import optimizers

    def wrong(p, m, v, g, t, **kw):
        return p + 1.0, m, v

    monkeypatch.setattr(optimizers, "_FUSED_ADAM_CHECKED", False)
    monkeypatch.setattr(fa, "fused_adam", wrong)
    with pytest.raises(RuntimeError, match="disagrees"):
        optimizers._check_fused_adam()


def test_fused_adam_matches_xla_adam_over_steps():
    rng = np.random.default_rng(1)
    params = {"w": jnp.asarray(rng.normal(size=(13, 7)), jnp.float32),
              "b": {"c": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}}
    of, ox = adam_fused(1e-3), adam(1e-3)
    sf, sx = of.init(params), ox.init(params)
    for step in range(3):
        grads = jax.tree.map(
            lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32),
            params)
        uf, sf = of.update(grads, sf, params)
        ux, sx = ox.update(grads, sx, params)
        for a, b in zip(jax.tree.leaves(uf), jax.tree.leaves(ux)):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        params = apply_updates(params, uf)


def test_fused_adam_converges_on_quadratic():
    assert _minimize(adam_fused(0.1)) < 1e-2


def test_adam_path_env_dispatch(monkeypatch):
    monkeypatch.setenv("REPRO_ADAM_PATH", "fused")
    assert build_optimizer("adam", 1e-3).name == "adam-fused"
    monkeypatch.setenv("REPRO_ADAM_PATH", "xla")
    assert build_optimizer("adam", 1e-3).name == "adam"
    monkeypatch.setenv("REPRO_ADAM_PATH", "cuda")
    with pytest.raises(ValueError, match="unknown adam path"):
        build_optimizer("adam", 1e-3)
    monkeypatch.delenv("REPRO_ADAM_PATH")
    # auto off-TPU: interpret-mode fused adam in the training inner loop
    # would be a slowdown, so auto keeps the XLA implementation
    from repro.kernels.ops import on_tpu
    if not on_tpu():
        assert build_optimizer("adam", 1e-3).name == "adam"
