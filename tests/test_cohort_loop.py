"""The cohort's local-training loop runs to its longest client.

The trip count is read on the device from the ``steps`` vector, so one
compiled program serves every step count of a lane bucket, and a lane
shorter than the longest keeps its state once its own steps are done.
The oracle below is a masked ``lax.scan`` over the longest step count
rounded up to a power of two: running more masked steps changes no
value, so the two agree exactly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.client import _COMPILE_CACHE, CohortTrainer, _bucket
from repro.core.data_plane import dataset_store
from repro.core.update_store import UpdateStore
from repro.optim import apply_updates

from trace_harness import data, model  # noqa: F401

STEPS = np.array([3, 7, 11, 5, 2], np.int64)     # K 5 on 8 lanes
BATCH = 5


def _bucket_scan_oracle(tr, params0, X, y, n_i, steps, keys):
    """Each lane trained by a masked scan over the power-of-two bucket of
    the cohort's longest step count: (trained params [Kp, ...], mean
    losses [Kp])."""
    model, opt = tr.model, tr.opt
    bucket = _bucket(int(steps.max()), 8)

    def lane(Xl, yl, n, st, key):
        def body(carry, s):
            params, opt_state, key = carry
            key, k = jax.random.split(key)
            idx = jax.random.randint(k, (BATCH,), 0, jnp.maximum(n, 1))
            batch = {"x": Xl[idx], "y": yl[idx]}
            loss, grads = jax.value_and_grad(
                lambda p: model.loss(p, batch)[0])(params)
            upd, new_opt = opt.update(grads, opt_state, params)
            newp = apply_updates(params, upd)
            active = s < st
            sel = lambda a, b: jnp.where(active, a, b)
            return ((jax.tree.map(sel, newp, params),
                     jax.tree.map(sel, new_opt, opt_state), key),
                    jnp.where(active, loss, 0.0))

        (params, _, _), losses = jax.lax.scan(
            body, (params0, opt.init(params0), key), jnp.arange(bucket))
        return params, jnp.sum(losses) / jnp.maximum(st, 1)

    return jax.jit(jax.vmap(lane))(X, y, n_i, steps, keys)


def _rows(params, K):
    """The trained models as update-store rows (``RavelSpec`` leaf order)."""
    return np.concatenate([np.asarray(a[:K]).reshape(K, -1)
                           for a in jax.tree.leaves(params)], axis=1)


@pytest.mark.parametrize("plane", ["device", "host"])
def test_cohort_loop_matches_the_bucket_scan(data, model, plane):
    """A heterogeneous cohort with pad lanes: the trained rows and the
    losses equal the bucket scan's bit for bit, on both data planes."""
    tr = CohortTrainer(model, optimizer="adam", lr=1e-3, batch_size=BATCH,
                       seed=7)
    params = model.init(jax.random.PRNGKey(0))[0]
    K, Kp = len(STEPS), _bucket(len(STEPS), tr.cohort_floor)
    sel = np.arange(K)
    n_i = data.n[sel]
    keys = jax.random.split(jax.random.split(tr._key)[1], Kp)
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    sink = UpdateStore(n_params, capacity=Kp)
    if plane == "device":
        ids, _, losses = tr.train_cohort_indexed(
            params, dataset_store(data), sel, n_i, STEPS, update_sink=sink)
    else:
        ids, _, losses = tr.train_cohort(
            params, data.X[sel], data.y[sel], n_i, STEPS, update_sink=sink)

    pad = np.concatenate([sel, np.repeat(sel[-1:], Kp - K)])
    want_p, want_l = _bucket_scan_oracle(
        tr, params, jnp.asarray(data.X[pad]), jnp.asarray(data.y[pad]),
        jnp.asarray(data.n[pad]),
        jnp.asarray(np.concatenate([STEPS, np.zeros(Kp - K, np.int64)])),
        keys)
    got_rows = np.asarray(sink.gather(ids))[:, :n_params]
    np.testing.assert_array_equal(got_rows, _rows(want_p, K))
    np.testing.assert_array_equal(losses, np.asarray(want_l)[:K])
    # the longest client moved further from the start than the shortest
    start = _rows(jax.tree.map(lambda a: a[None], params), 1)[0]
    moved = np.linalg.norm(got_rows - start, axis=1)
    assert moved[2] > moved[4] > 0


def test_one_compile_serves_every_step_count(data, model):
    """Longest clients of 10 and 40 steps, in different power-of-two
    step buckets, on one lane bucket add a single compiled entry."""
    tr = CohortTrainer(model, optimizer="sgd", lr=0.05, batch_size=2, seed=3)
    store = dataset_store(data)
    params = model.init(jax.random.PRNGKey(0))[0]
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    sink = UpdateStore(n_params, capacity=8)
    before = set(_COMPILE_CACHE)
    fns = []
    for longest in (10, 40):
        steps = np.array([1, longest, 3], np.int64)
        fn, Kp, max_steps = tr.cohort_fn_indexed(store, len(steps), longest)
        assert (Kp, max_steps) == (4, longest)
        fns.append(fn)
        ids, _, losses = tr.train_cohort_indexed(
            params, store, [0, 1, 2], data.n[[0, 1, 2]], steps,
            update_sink=sink)
        sink.free(ids)
        assert np.isfinite(losses).all()
    assert fns[0] is fns[1]
    assert len(set(_COMPILE_CACHE) - before) == 1
    assert fns[0]._cache_size() == 1                # one program traced
