"""Tests for staleness-weighted asynchronous aggregation (paper III-B)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import (
    incremental_aggregate,
    staleness_weights,
    weighted_aggregate,
)


def _trees(k, seed=0):
    rng = np.random.default_rng(seed)
    return [{"a": jnp.asarray(rng.normal(size=(4, 8)), jnp.float32),
             "b": {"w": jnp.asarray(rng.normal(size=(16,)), jnp.float32)}}
            for _ in range(k)]


def test_weighted_aggregate_matches_manual():
    ups = _trees(3)
    w = np.array([0.5, 0.3, 0.2], np.float32)
    out = weighted_aggregate(ups, w)
    manual = sum(wi * np.asarray(u["a"]) for wi, u in zip(w, ups))
    np.testing.assert_allclose(np.asarray(out["a"]), manual, rtol=1e-6)


def test_uniform_weights_equal_mean():
    ups = _trees(4)
    w = np.full(4, 0.25, np.float32)
    out = weighted_aggregate(ups, w)
    mean = np.mean([np.asarray(u["b"]["w"]) for u in ups], axis=0)
    np.testing.assert_allclose(np.asarray(out["b"]["w"]), mean, rtol=1e-6)


def test_staleness_weights_normalized():
    w = staleness_weights(rounds=[10, 9, 7], cardinalities=[100, 50, 200],
                          current_round=10)
    assert w.sum() == pytest.approx(1.0, rel=1e-6)
    assert (w > 0).all()


def test_staleness_damps_older_updates():
    # same cardinality: current-round update must outweigh stale one
    w = staleness_weights(rounds=[10, 5], cardinalities=[100, 100],
                          current_round=10)
    assert w[0] > w[1]
    assert w[0] / w[1] == pytest.approx(np.sqrt(6), rel=1e-6)


def test_cardinality_weighting():
    w = staleness_weights(rounds=[10, 10], cardinalities=[300, 100],
                          current_round=10)
    assert w[0] / w[1] == pytest.approx(3.0, rel=1e-6)


def test_eq1_option():
    w = staleness_weights(rounds=[4, 2], cardinalities=[1, 1],
                          current_round=4, fn="eq1")
    assert w[0] / w[1] == pytest.approx(2.0, rel=1e-6)


def test_incremental_matches_batch():
    ups = _trees(5, seed=3)
    w = np.array([0.1, 0.2, 0.3, 0.25, 0.15], np.float32)
    batch = weighted_aggregate(ups, w)
    acc = None
    for u, wi in zip(ups, w):
        acc = incremental_aggregate(acc, u, float(wi))
    np.testing.assert_allclose(np.asarray(acc["a"]),
                               np.asarray(batch["a"]), rtol=1e-5, atol=1e-7)


def test_kernel_path_matches_xla_path():
    from repro.kernels import ops
    ups = _trees(3, seed=7)
    w = np.array([0.6, 0.3, 0.1], np.float32)
    a = weighted_aggregate(ups, w)
    b = ops.aggregate_pytree(ups, w, interpret=True)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------------------- Pallas default dispatch
def _ragged_trees(k, seed=0):
    """Pytrees with ragged leaf shapes (incl. a scalar) whose total size is
    NOT a multiple of the kernel block — exercises both pad paths."""
    rng = np.random.default_rng(seed)
    return [{"conv": jnp.asarray(rng.normal(size=(3, 5)), jnp.float32),
             "bias": jnp.asarray(rng.normal(size=(7,)), jnp.float32),
             "scale": jnp.asarray(rng.normal(), jnp.float32),
             "head": {"w": jnp.asarray(rng.normal(size=(2, 3, 4)),
                                       jnp.float32)}}
            for _ in range(k)]


def test_default_path_is_pallas():
    from repro.core import aggregation
    ups = _ragged_trees(3, seed=1)
    w = np.array([0.5, 0.3, 0.2], np.float32)
    weighted_aggregate(ups, w)
    assert aggregation.last_path() == "pallas"


@pytest.mark.parametrize("k", [1, 3, 5, 9])  # crosses the sublane multiple
def test_pallas_matches_xla_on_ragged_pytree(k):
    rng = np.random.default_rng(k)
    ups = _ragged_trees(k, seed=k)
    w = rng.dirichlet(np.ones(k)).astype(np.float32)
    a = weighted_aggregate(ups, w, path="pallas")
    b = weighted_aggregate(ups, w, path="xla")
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.shape == y.shape
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-5)


def test_pallas_path_respects_out_dtype():
    ups = _ragged_trees(2, seed=3)
    w = np.array([0.7, 0.3], np.float32)
    out = weighted_aggregate(ups, w, out_dtype=jnp.bfloat16, path="pallas")
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(out))


def test_unknown_path_rejected():
    ups = _ragged_trees(2)
    with pytest.raises(ValueError, match="unknown aggregation path"):
        weighted_aggregate(ups, np.array([0.5, 0.5], np.float32),
                           path="cuda")


def test_auto_size_guard_off_tpu(monkeypatch):
    """Off-TPU, auto dispatch falls back to XLA above the interpret-mode
    size cap (the kernel stays available via path="pallas")."""
    from repro.core import aggregation
    monkeypatch.setattr(aggregation, "_INTERP_MAX_N", 10)
    ups = _ragged_trees(2, seed=9)  # 44 params > 10
    w = np.array([0.5, 0.5], np.float32)
    weighted_aggregate(ups, w)
    assert aggregation.last_path() == "xla"
    weighted_aggregate(ups, w, path="pallas")
    assert aggregation.last_path() == "pallas"


def test_env_var_forces_xla(monkeypatch):
    from repro.core import aggregation
    monkeypatch.setenv("REPRO_AGG_PATH", "xla")
    ups = _ragged_trees(2, seed=5)
    weighted_aggregate(ups, np.array([0.4, 0.6], np.float32))
    assert aggregation.last_path() == "xla"


def test_pallas_self_check_mismatch_raises(monkeypatch):
    """A kernel that disagrees with the float64 reference fails the run;
    it does not quietly move aggregation to XLA."""
    from repro.core import aggregation
    monkeypatch.setattr(aggregation, "_PALLAS_CHECKED", False)
    monkeypatch.setattr(
        aggregation.kernel_ops, "aggregate_pytree",
        lambda ups, w, restore_dtype: jax.tree.map(lambda x: x + 1, ups[0]))
    with pytest.raises(RuntimeError, match="disagrees"):
        weighted_aggregate(_trees(2), np.array([0.5, 0.5], np.float32))


def test_pallas_kernel_error_propagates(monkeypatch):
    from repro.core import aggregation
    from repro.core.aggregation import weighted_aggregate_rows
    from repro.core.update_store import UpdateStore
    from repro.kernels.ops import RavelSpec

    def boom(*args, **kwargs):
        raise RuntimeError("kernel failed")

    ups = _trees(2)
    spec = RavelSpec(ups[0])
    store = UpdateStore(spec.n_params, capacity=8)
    ids = store.put(jnp.stack([spec.ravel(u) for u in ups]))
    monkeypatch.setattr(aggregation.kernel_ops, "aggregate_rows", boom)
    with pytest.raises(RuntimeError, match="kernel failed"):
        weighted_aggregate_rows(store.buffer, ids,
                                np.array([0.5, 0.5], np.float32), spec)
