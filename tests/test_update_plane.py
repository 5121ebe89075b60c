"""Device-resident update plane: UpdateStore lifecycle, the row-index
aggregation fast path, blob-path equivalence over full async runs, and
checkpoint/resume of live un-aggregated rows (DESIGN.md §2)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import weighted_aggregate, weighted_aggregate_rows
from repro.core.controller import Controller, FLConfig, resolve_update_plane
from repro.core.update_store import UpdateStore
from repro.data.synthetic import make_federated_dataset
from repro.faas.hardware import paper_fleet
from repro.kernels.ops import RavelSpec
from repro.models.proxy_models import ProxyCNN

N_CLIENTS = 12


@pytest.fixture(scope="module")
def data():
    return make_federated_dataset("speech", n_clients=N_CLIENTS, scale=0.08,
                                  seed=0)


@pytest.fixture(scope="module")
def model():
    return ProxyCNN(35)


def _cfg(**kw):
    base = dict(n_clients=N_CLIENTS, clients_per_round=4, rounds=3,
                local_epochs=1, batch_size=5, base_step_time=0.5,
                round_timeout=200.0, seed=0)
    base.update(kw)
    return FLConfig(**base)


# ------------------------------------------------------------- UpdateStore
def test_store_geometry_invariants():
    """Capacity is a sublane multiple and rows are block-padded so the
    kernel path never pays a padding copy."""
    store = UpdateStore(n_params=33, capacity=3)
    assert store.capacity % 8 == 0
    assert store.row_width % 1024 == 0 and store.row_width >= 33


def test_store_put_gather_roundtrip():
    store = UpdateStore(n_params=33, capacity=2)
    rows = np.random.default_rng(0).normal(size=(5, 33)).astype(np.float32)
    ids = store.put(jnp.asarray(rows))
    assert len(ids) == 5 and store.live_count == 5
    got = np.asarray(store.gather(ids))
    np.testing.assert_array_equal(got[:, :33], rows)
    np.testing.assert_array_equal(got[:, 33:], 0.0)  # zero tail pad
    np.testing.assert_array_equal(np.asarray(store.row(int(ids[2])))[:33],
                                  rows[2])


def test_store_free_recycles_rows():
    store = UpdateStore(n_params=8, capacity=4)
    a = store.put(jnp.ones((4, 8)))
    store.free(a)
    assert store.live_count == 0
    cap = store.capacity
    b = store.put(jnp.full((4, 8), 2.0))
    # recycled, not grown: same slots, same capacity
    assert set(map(int, b)) <= set(range(cap))
    assert store.capacity == cap
    store.free(b)
    store.free(b)  # double-free is a no-op
    assert store.live_count == 0


def test_freed_nan_rows_cannot_poison_aggregate():
    """A diverged client's NaN row, freed without aggregation (failure or
    staleness prune), must not leak into later aggregates through the
    full-buffer weight-0 sweep (0 * nan = nan): the finiteness guard
    recomputes over just the referenced rows."""
    rng = np.random.default_rng(7)
    ups = [_tree(rng) for _ in range(3)]
    spec = RavelSpec(ups[0])
    store = UpdateStore(spec.n_params)
    bad = store.put(jnp.full((1, spec.n_params), jnp.nan))
    ids = store.put(jnp.stack([spec.ravel(u) for u in ups]))
    store.free(bad)  # freed but not overwritten: still NaN in the buffer
    w = np.array([0.5, 0.3, 0.2], np.float32)
    got = weighted_aggregate_rows(store.buffer, ids, w, spec)
    want = weighted_aggregate(ups, w)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.all(np.isfinite(np.asarray(x)))
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)


def test_store_grows_when_free_list_dry():
    store = UpdateStore(n_params=8, capacity=2)
    first = store.put(jnp.arange(16, dtype=jnp.float32).reshape(2, 8))
    ids = store.put(jnp.arange(80, dtype=jnp.float32).reshape(10, 8))
    assert store.capacity >= 12
    # growth preserved previously written rows
    np.testing.assert_array_equal(
        np.asarray(store.gather(first))[:, :8].ravel(),
        np.arange(16, dtype=np.float32))
    np.testing.assert_array_equal(
        np.asarray(store.gather(ids))[:, :8].ravel(),
        np.arange(80, dtype=np.float32))


def test_store_put_stacked_matches_ravel():
    rng = np.random.default_rng(2)
    tree = {"a": jnp.asarray(rng.normal(size=(3, 2, 5)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(3, 4)), jnp.float32)}
    spec = RavelSpec(jax.tree.map(lambda x: x[0], tree))
    store = UpdateStore(spec.n_params)
    ids = store.put_stacked(tree)
    want = np.asarray(spec.ravel_stacked(tree))
    got = np.asarray(store.gather(ids))[:, :spec.n_params]
    np.testing.assert_array_equal(got, want)


def test_store_write_at_specific_ids():
    store = UpdateStore(n_params=4, capacity=2)
    rows = np.arange(8, dtype=np.float32).reshape(2, 4)
    store.write_at([5, 1], rows)
    assert store.capacity >= 6
    assert store.live_count == 2
    np.testing.assert_array_equal(np.asarray(store.gather([5, 1]))[:, :4],
                                  rows)
    # freshly allocated ids never collide with the rehydrated ones
    new = store.put(jnp.zeros((3, 4)))
    assert not ({5, 1} & set(map(int, new)))


# ------------------------------------------------------ row-index fast path
def _tree(rng):
    return {"conv": jnp.asarray(rng.normal(size=(3, 5)), jnp.float32),
            "bias": jnp.asarray(rng.normal(size=(7,)), jnp.float32),
            "scale": jnp.asarray(rng.normal(), jnp.float32)}


@pytest.mark.parametrize("k", [1, 3, 8, 9])  # crosses the sublane multiple
def test_rows_path_matches_blob_path(k):
    rng = np.random.default_rng(k)
    ups = [_tree(rng) for _ in range(k)]
    w = rng.dirichlet(np.ones(k)).astype(np.float32)
    spec = RavelSpec(ups[0])
    store = UpdateStore(spec.n_params, capacity=2)
    ids = store.put(jnp.stack([spec.ravel(u) for u in ups]))
    got = weighted_aggregate_rows(store.buffer, ids, w, spec)
    want = weighted_aggregate(ups, w)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert x.shape == y.shape
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)


def test_rows_path_pallas_vs_xla(monkeypatch):
    rng = np.random.default_rng(3)
    ups = [_tree(rng) for _ in range(4)]
    w = rng.dirichlet(np.ones(4)).astype(np.float32)
    spec = RavelSpec(ups[0])
    store = UpdateStore(spec.n_params)
    ids = store.put(jnp.stack([spec.ravel(u) for u in ups]))
    from repro.core import aggregation
    a = weighted_aggregate_rows(store.buffer, ids, w, spec, path="pallas")
    assert aggregation.last_path() == "pallas"
    b = weighted_aggregate_rows(store.buffer, ids, w, spec, path="xla")
    assert aggregation.last_path() == "xla"
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)


def test_rows_path_respects_out_dtype():
    rng = np.random.default_rng(5)
    ups = [_tree(rng) for _ in range(2)]
    spec = RavelSpec(ups[0])
    store = UpdateStore(spec.n_params)
    ids = store.put(jnp.stack([spec.ravel(u) for u in ups]))
    out = weighted_aggregate_rows(store.buffer, ids,
                                  np.array([0.6, 0.4], np.float32), spec,
                                  out_dtype=jnp.bfloat16)
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(out))


def test_sparse_reference_set_uses_gather_and_stays_exact():
    """Once the buffer has grown far past the live set, aggregation reads
    only the referenced rows instead of sweeping the whole capacity."""
    rng = np.random.default_rng(11)
    ups = [_tree(rng) for _ in range(3)]
    spec = RavelSpec(ups[0])
    store = UpdateStore(spec.n_params, capacity=64)  # >= 4 * max(K, 8)
    ids = store.put(jnp.stack([spec.ravel(u) for u in ups]))
    w = np.array([0.2, 0.5, 0.3], np.float32)
    from repro.core import aggregation
    got = weighted_aggregate_rows(store.buffer, ids, w, spec)
    assert aggregation.last_path() == "pallas"   # the kernel, on K rows
    want = weighted_aggregate(ups, w)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)


def test_resolve_update_plane(monkeypatch):
    assert resolve_update_plane("blob") == "blob"
    assert resolve_update_plane("device") == "device"
    monkeypatch.setenv("REPRO_UPDATE_PLANE", "blob")
    assert resolve_update_plane("auto") == "blob"
    monkeypatch.delenv("REPRO_UPDATE_PLANE")
    assert resolve_update_plane("auto") == "device"
    with pytest.raises(ValueError, match="unknown update plane"):
        resolve_update_plane("mongo")


# -------------------------------------------- full-run numeric equivalence
def test_blob_and_device_runs_equivalent(data, model):
    """Multi-round async (apodotiko) run: both transports must produce the
    same accuracy trajectory (atol 1e-5) and the same final global model."""
    runs = {}
    for plane in ("blob", "device"):
        ctl = Controller(_cfg(strategy="apodotiko", rounds=4,
                              concurrency_ratio=0.5, update_plane=plane),
                         model, data, list(paper_fleet(N_CLIENTS)))
        m = ctl.run()
        assert m["update_plane"] == plane
        runs[plane] = (m, ctl.params)
    hb = [a for _, _, a in runs["blob"][0]["history"]]
    hd = [a for _, _, a in runs["device"][0]["history"]]
    assert len(hb) == len(hd) >= 2  # stale updates were exercised
    np.testing.assert_allclose(hd, hb, atol=1e-5)
    for x, y in zip(jax.tree.leaves(runs["device"][1]),
                    jax.tree.leaves(runs["blob"][1])):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)


def test_device_plane_moves_no_update_bytes(data, model):
    ctl = Controller(_cfg(strategy="apodotiko", update_plane="device"),
                     model, data, list(paper_fleet(N_CLIENTS)))
    m = ctl.run()
    assert m["update_host_bytes"] == 0
    ctl = Controller(_cfg(strategy="apodotiko", update_plane="blob"),
                     model, data, list(paper_fleet(N_CLIENTS)))
    m = ctl.run()
    assert m["update_host_bytes"] > 0


def test_device_plane_recycles_rows(data, model):
    ctl = Controller(_cfg(strategy="apodotiko", rounds=4,
                          update_plane="device"),
                     model, data, list(paper_fleet(N_CLIENTS)))
    ctl.run()
    # every live row is accounted for: it backs either an un-aggregated
    # pending result or an in-flight invocation (client still "running"
    # when the run ended) — aggregated/pruned/failed rows were recycled
    pending = {r.update_row for r in ctl.db.results if not r.aggregated}
    n_inflight = sum(1 for c in ctl.db.clients.values()
                     if c.status == "running")
    live = set(map(int, ctl.store.live_rows()))
    assert pending <= live
    assert len(live) == len(pending) + n_inflight


# ----------------------------------------------- checkpoint/resume of rows
def test_checkpoint_resume_live_rows_bit_exact(tmp_path, data, model):
    cfg = _cfg(strategy="apodotiko", rounds=2, update_plane="device",
               checkpoint_dir=str(tmp_path / "fl"))
    ctl = Controller(cfg, model, data, list(paper_fleet(N_CLIENTS)))
    # drive one cohort to completion WITHOUT aggregating, so the checkpoint
    # carries live un-aggregated rows (the async in-flight state)
    sel = ctl.strategy.select(ctl.db, 0)
    ctl.invoke_round(0, sel)
    assert ctl.loop.run_until(lambda: len(ctl.db.results) >= len(sel),
                              max_time=1e8)
    ctl.checkpoint()
    ids = [r.update_row for r in ctl.db.results if not r.aggregated]
    assert ids
    before = np.asarray(ctl.store.gather(ids))

    ctl2 = Controller.resume(cfg, model, data, list(paper_fleet(N_CLIENTS)))
    ids2 = [r.update_row for r in ctl2.db.results if not r.aggregated]
    assert ids2 == ids  # handles survived verbatim
    np.testing.assert_array_equal(np.asarray(ctl2.store.gather(ids2)), before)
    m = ctl2.run()  # the rehydrated rows are aggregatable
    assert m["rounds"] >= 1


def test_cross_plane_resume_with_pending_results_rejected(tmp_path, data,
                                                          model):
    """Blob records carry update_row=-1 (which would silently index the
    last buffer row); resuming a checkpoint with in-flight results under
    the other plane must fail loudly, not corrupt the aggregate."""
    cfg = _cfg(strategy="apodotiko", rounds=2, update_plane="device",
               checkpoint_dir=str(tmp_path / "fl"))
    ctl = Controller(cfg, model, data, list(paper_fleet(N_CLIENTS)))
    sel = ctl.strategy.select(ctl.db, 0)
    ctl.invoke_round(0, sel)
    assert ctl.loop.run_until(lambda: len(ctl.db.results) >= len(sel),
                              max_time=1e8)
    ctl.checkpoint()
    cfg_blob = _cfg(strategy="apodotiko", rounds=2, update_plane="blob",
                    checkpoint_dir=str(tmp_path / "fl"))
    with pytest.raises(ValueError, match="update_plane"):
        Controller.resume(cfg_blob, model, data, list(paper_fleet(N_CLIENTS)))


def test_checkpoint_resume_full_run(tmp_path, data, model):
    cfg = _cfg(strategy="apodotiko", rounds=2, update_plane="device",
               checkpoint_dir=str(tmp_path / "fl"), checkpoint_every=1)
    ctl = Controller(cfg, model, data, list(paper_fleet(N_CLIENTS)))
    ctl.run()
    ctl.checkpoint()
    cfg2 = _cfg(strategy="apodotiko", rounds=4, update_plane="device",
                checkpoint_dir=str(tmp_path / "fl"))
    ctl2 = Controller.resume(cfg2, model, data, list(paper_fleet(N_CLIENTS)))
    assert ctl2.db.round == 2
    m = ctl2.run()
    assert m["rounds"] >= 1


# ----------------------------------------------------- evaluation fast path
def test_eval_scan_matches_batched_loop(data, model):
    ctl = Controller(_cfg(), model, data, list(paper_fleet(N_CLIENTS)))
    fast = ctl.evaluate()
    # reference: exact accuracy over the whole eval set in one batch
    xs, ys = data.eval_x, data.eval_y
    acc = float(jnp.mean(
        (jnp.argmax(model.predict(ctl.params, jnp.asarray(xs)), -1)
         == jnp.asarray(ys)).astype(jnp.float32)))
    assert fast == pytest.approx(acc, abs=1e-6)


def test_eval_falls_back_without_predict(data, model):
    class AccOnly:
        def __init__(self, inner):
            self._inner = inner

        def init(self, rng):
            return self._inner.init(rng)

        def loss(self, p, b):
            return self._inner.loss(p, b)

        def accuracy(self, p, b):
            return self._inner.accuracy(p, b)

    ctl = Controller(_cfg(rounds=1), AccOnly(model), data,
                     list(paper_fleet(N_CLIENTS)))
    assert np.isfinite(ctl.evaluate())


# ------------------------------------------------------- compile-cache key
def test_compile_cache_key_not_id_based(data):
    """Two distinct model objects must never share a cache entry via id()
    reuse; the weak-token key is unique per live object and never recycled."""
    from repro.core.client import _COMPILE_CACHE, _model_token
    m1, m2 = ProxyCNN(35), ProxyCNN(35)
    t1, t2 = _model_token(m1), _model_token(m2)
    assert t1 != t2
    assert _model_token(m1) == t1  # stable across calls
    ctl1 = Controller(_cfg(rounds=1), m1, data, list(paper_fleet(N_CLIENTS)))
    n0 = len(_COMPILE_CACHE)
    ctl1.run()
    assert len(_COMPILE_CACHE) > n0
    # same model object reused by a second controller: cache entries shared
    n1 = len(_COMPILE_CACHE)
    Controller(_cfg(rounds=1), m1, data, list(paper_fleet(N_CLIENTS))).run()
    assert len(_COMPILE_CACHE) == n1
