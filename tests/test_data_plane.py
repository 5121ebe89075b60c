"""Device-resident data plane (DESIGN.md §2): DatasetStore residence,
golden-trace bit-identity of `REPRO_DATA_PLANE=device` vs `host` across
strategies / engines / update planes, zero-H2D accounting, the SCAFFOLD
device-resident control-variate buffer, the cohort bucket floor, and the
scheduler's coalesced dispatch."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.client import (_COMPILE_CACHE, CohortTrainer, _bucket,
                               cohort_bucket_floor)
from repro.core.controller import Controller, FLConfig
from repro.core.data_plane import DatasetStore, dataset_store, resolve_data_plane
from repro.core.protocol import (Aggregate, CancelInvocation, Hedge, Invoke,
                                 RoundStarted, SetTimer)
from repro.core.scheduler import Scheduler
from repro.data.synthetic import make_federated_dataset
from repro.faas.hardware import HARDWARE_PROFILES, paper_fleet

from trace_harness import (ALL_STRATEGIES, N_CLIENTS, REACTIVE, base_cfg_kw,
                           data, model, run_flag_pair)  # noqa: F401


def _cfg(**kw):
    return FLConfig(**base_cfg_kw(**kw))


def _assert_planes_identical(cfg_kw, model, data, engine_cls=Scheduler):
    """One run per data plane; everything observable must be bit-equal
    (common asserts live in trace_harness.run_flag_pair)."""
    runs = run_flag_pair(cfg_kw, "data_plane", ("device", "host"), model,
                         data, engine_cls=engine_cls)
    _, m_dev = runs["device"]
    _, m_host = runs["host"]
    # the H2D asymmetry is the whole point
    assert m_dev["data_host_bytes"] == 0
    assert m_host["data_host_bytes"] > 0
    assert m_dev["data_resident_bytes"] == data.nbytes
    assert m_host["data_resident_bytes"] == 0
    return m_dev, m_host


# ------------------------------------------------------------ golden traces
@pytest.mark.parametrize("strategy", ALL_STRATEGIES + REACTIVE)
def test_golden_dataplane_scheduler(strategy, data, model):
    _assert_planes_identical(base_cfg_kw(strategy=strategy), model, data)


@pytest.mark.parametrize("strategy", ("fedavg", "apodotiko", "scaffold"))
def test_golden_dataplane_blob_update_plane(strategy, data, model):
    _assert_planes_identical(base_cfg_kw(strategy=strategy,
                                         update_plane="blob"), model, data)


@pytest.mark.parametrize("strategy", ("fedavg", "apodotiko", "scaffold"))
def test_golden_dataplane_legacy_engine(strategy, data, model):
    _assert_planes_identical(base_cfg_kw(strategy=strategy), model, data,
                             engine_cls=Controller)


def test_golden_dataplane_legacy_engine_blob_plane(data, model):
    """The full legacy stack (poll loop + blob updates) against itself
    across data planes."""
    _assert_planes_identical(base_cfg_kw(strategy="apodotiko",
                                         update_plane="blob"), model, data,
                             engine_cls=Controller)


# ----------------------------------------------------------- resolve + store
def test_resolve_data_plane(monkeypatch):
    monkeypatch.delenv("REPRO_DATA_PLANE", raising=False)
    assert resolve_data_plane("auto") == "device"
    assert resolve_data_plane("") == "device"
    assert resolve_data_plane("host") == "host"
    monkeypatch.setenv("REPRO_DATA_PLANE", "host")
    assert resolve_data_plane("auto") == "host"
    assert resolve_data_plane("device") == "device"   # explicit beats env
    with pytest.raises(ValueError):
        resolve_data_plane("blob")


def test_dataset_store_residence_and_gather(data):
    store = DatasetStore(data)
    assert store.n_clients == N_CLIENTS
    assert store.resident_bytes == data.nbytes
    gx, gy = store.gather([3, 1])
    np.testing.assert_array_equal(np.asarray(gx), data.X[[3, 1]])
    np.testing.assert_array_equal(np.asarray(gy), data.y[[3, 1]])
    # device arrays, not host views
    assert isinstance(store.X, jnp.ndarray) and isinstance(store.y, jnp.ndarray)


def test_dataset_store_cached_per_dataset(data):
    assert dataset_store(data) is dataset_store(data)
    other = make_federated_dataset("mnist", n_clients=4, scale=0.05, seed=1)
    assert dataset_store(other) is not dataset_store(data)


def test_out_of_range_selection_raises(data, model):
    """The resident gather would clamp silently; the runtime must keep the
    host plane's failure mode."""
    sched = Scheduler(_cfg(strategy="fedavg"), model, data,
                      list(paper_fleet(N_CLIENTS)))
    with pytest.raises(IndexError):
        sched.invoke_round(0, [N_CLIENTS + 5])


# ------------------------------------------------------------- SCAFFOLD buf
def test_scaffold_variate_buffer_device_resident(data, model):
    sched = Scheduler(_cfg(strategy="scaffold", rounds=2), model, data,
                      list(paper_fleet(N_CLIENTS)))
    sched.run()
    assert sched.c_buf is not None and sched._c_cap >= N_CLIENTS
    trained = {r.client_id for r in sched.db.results}
    norms = np.asarray(
        sum(jnp.sum(jnp.abs(b), axis=tuple(range(1, b.ndim)))
            for b in jax.tree.leaves(sched.c_buf)))
    assert any(norms[cid] > 0 for cid in trained)
    # removal zeroes the rows: a rejoining id starts from fresh variates
    cid = next(iter(trained))
    sched.remove_clients([cid])
    norms = np.asarray(
        sum(jnp.sum(jnp.abs(b), axis=tuple(range(1, b.ndim)))
            for b in jax.tree.leaves(sched.c_buf)))
    assert norms[cid] == 0


# ------------------------------------------------------------- bucket floor
def test_cohort_bucket_floor_default_and_env(monkeypatch):
    monkeypatch.delenv("REPRO_COHORT_FLOOR", raising=False)
    assert cohort_bucket_floor() == 2
    monkeypatch.setenv("REPRO_COHORT_FLOOR", "8")
    assert cohort_bucket_floor() == 8
    assert _bucket(1, 2) == 2 and _bucket(3, 2) == 4 and _bucket(9, 2) == 16
    assert _bucket(1, 8) == 8 and _bucket(12, 8) == 16


def _tiny_trainer(model, **kw):
    return CohortTrainer(model, optimizer="sgd", lr=0.1, batch_size=2, **kw)


def test_solo_dispatch_pads_to_two_not_eight(data, model):
    """A K=1 dispatch (reinforcement / solo re-invocation) compiles at
    Kp=2 — and a K=2 dispatch reuses that same compiled entry."""
    trainer = _tiny_trainer(model)
    store = dataset_store(data)
    params = model.init(jax.random.PRNGKey(0))[0]
    before = dict(_COMPILE_CACHE)
    out, _, losses = trainer.train_cohort_indexed(
        params, store, [3], data.n[[3]], np.array([1], np.int64))
    new_keys = [k for k in _COMPILE_CACHE if k not in before]
    assert len(new_keys) == 1
    kp = new_keys[0][6]        # config key (6 fields) + (Kp, data shape, ...)
    assert kp == 2
    assert jax.tree.leaves(out)[0].shape[0] == 1 and losses.shape == (1,)
    n_before = len(_COMPILE_CACHE)
    trainer.train_cohort_indexed(params, store, [1, 4], data.n[[1, 4]],
                                 np.array([1, 1], np.int64))
    assert len(_COMPILE_CACHE) == n_before       # same bucket, no recompile


def test_mixed_selection_sizes_bound_compiles(data, model):
    """K = 1..7 across dispatches compiles at most O(log K) variants
    (buckets 2, 4, 8)."""
    trainer = _tiny_trainer(model)
    store = dataset_store(data)
    params = model.init(jax.random.PRNGKey(0))[0]
    before = len(_COMPILE_CACHE)
    for k in range(1, 8):
        sel = list(range(k))
        trainer.train_cohort_indexed(params, store, sel, data.n[sel],
                                     np.ones(k, np.int64))
    assert len(_COMPILE_CACHE) - before <= 3


def test_cohort_floor_parametrized(data, model):
    """cohort_floor=8 restores the legacy padding (one bucket for K<=8)."""
    trainer = _tiny_trainer(model, cohort_floor=8)
    store = dataset_store(data)
    params = model.init(jax.random.PRNGKey(0))[0]
    before = set(_COMPILE_CACHE)
    for k in (1, 3, 5, 8):
        sel = list(range(k))
        trainer.train_cohort_indexed(params, store, sel, data.n[sel],
                                     np.ones(k, np.int64))
    new_keys = [k for k in _COMPILE_CACHE if k not in before]
    # every size lands in the single Kp=8 bucket (entries may already be
    # warm from earlier tests sharing the trainer config)
    assert len(new_keys) <= 1
    assert all(k[6] == 8 for k in new_keys)


# ---------------------------------------------------------- remove_clients
def test_remove_clients_shared_profile_object(data, model):
    """Two clients sharing one HardwareProfile object: removing one must
    drop ITS fleet entry (by id->position map), not the first entry that
    compares equal — the fleet stays position-consistent with `hw`."""
    P, Q = HARDWARE_PROFILES["cpu1"], HARDWARE_PROFILES["gpu"]
    fleet = [P, Q] + [P] * (N_CLIENTS - 2)       # cids 0 and 2.. share P
    sched = Scheduler(_cfg(strategy="fedavg", rounds=1), model, data, fleet)
    sched.remove_clients([2])
    assert len(sched.fleet) == N_CLIENTS - 1
    assert sched.fleet[0] is P and sched.fleet[1] is Q
    for cid, pos in sched._fleet_pos.items():
        assert sched.fleet[pos] is sched.hw[cid]
    # removing the remaining sharers one by one never corrupts Q's slot
    sched.remove_clients([0, 3])
    assert Q in sched.fleet
    assert sched.fleet[sched._fleet_pos[1]] is Q
    assert len(sched.fleet) == N_CLIENTS - 3


# ------------------------------------------------------- coalesced dispatch
def test_coalesce_merges_invokes_and_hedges(data, model):
    sched = Scheduler(_cfg(strategy="fedavg"), model, data,
                      list(paper_fleet(N_CLIENTS)))
    acts = sched._coalesce([Invoke((0, 1)), SetTimer(5.0, "t"),
                            Invoke((1, 2)), Hedge((3,)), Hedge((4,)),
                            Aggregate(), Invoke((5,))])
    assert acts == [Invoke((0, 1, 2)), SetTimer(5.0, "t"), Hedge((3, 4)),
                    Aggregate(), Invoke((5,))]
    assert sched.n_coalesced == 2


def test_coalesce_respects_barriers(data, model):
    sched = Scheduler(_cfg(strategy="fedavg"), model, data,
                      list(paper_fleet(N_CLIENTS)))
    acts = sched._coalesce([Invoke((0,)), CancelInvocation(0), Invoke((0,))])
    assert acts == [Invoke((0,)), CancelInvocation(0), Invoke((0,))]
    assert sched.n_coalesced == 0
    # Invoke and Hedge are barriers for each other: a hedge must never be
    # reordered before the invocation it targets (and vice versa)
    acts = sched._coalesce([Hedge((3,)), Invoke((5,)), Hedge((5,))])
    assert acts == [Hedge((3,)), Invoke((5,)), Hedge((5,))]
    acts = sched._coalesce([Invoke((0,)), Hedge((0,)), Invoke((1,))])
    assert acts == [Invoke((0,)), Hedge((0,)), Invoke((1,))]
    assert sched.n_coalesced == 0


def test_coalesced_invokes_hit_one_cohort_dispatch(data, model, monkeypatch):
    """Two same-instant Invoke actions train as ONE batched cohort."""
    sched = Scheduler(_cfg(strategy="fedavg"), model, data,
                      list(paper_fleet(N_CLIENTS)))
    calls = []
    monkeypatch.setattr(
        sched, "invoke_round",
        lambda r, sel, **kw: calls.append((r, tuple(sel))))

    class TwoInvokes:
        name = "two-invokes"
        fire_timers_on_drain = True
        strategy = sched.policy.strategy

        def on_event(self, ev, view):
            return [Invoke((0, 1)), Invoke((2,))] \
                if isinstance(ev, RoundStarted) else []

        def metrics(self):
            return {}

    sched.policy = TwoInvokes()
    sched._dispatch(RoundStarted(t=0.0, round=0))
    assert calls == [(0, (0, 1, 2))]
    assert sched.n_coalesced == 1
    assert sched.metrics()["n_coalesced"] == 1
