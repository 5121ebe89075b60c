"""Fused round megastep (core.megastep, DESIGN.md §11).

The differential contract under test: a ``megastep=fused`` run must be
bit-identical — selections, round boundaries, invocation records, final
params, fleet/device/store end state, total simulated time — to the
stepwise event-driven oracle, (a) when the fused path engages, (b) when
it falls back, and (c) across the full strategy x update-plane x
data-plane matrix where it never engages at all. Fallback-boundary tests
additionally pin that an ineligible plan mutates nothing ("identical to
never entering"), and seeded randomized sweeps (the in-tree stand-in for
the hypothesis layer in test_properties.py, which needs the dev-only
dep) fuzz fleets, knobs, and churn schedules against the same contract.
"""
import heapq

import numpy as np
import pytest

from repro.core.controller import FLConfig
from repro.core.megastep import _plan
from repro.core.scheduler import Scheduler
from repro.core.services import resolve_megastep
from repro.faas.hardware import HardwareProfile, paper_fleet

from trace_harness import (ALL_STRATEGIES, N_CLIENTS, REACTIVE, base_cfg_kw,
                           assert_fleet_state_equal,
                           assert_fused_matches_stepwise, assert_params_equal,
                           data, det_fleet, megastep_cfg, model,
                           trace)  # noqa: F401


# ------------------------------------------------------- resolution order
def test_resolve_megastep(monkeypatch):
    monkeypatch.delenv("REPRO_MEGASTEP", raising=False)
    assert resolve_megastep("auto") == "fused"
    assert resolve_megastep("") == "fused"
    assert resolve_megastep("stepwise") == "stepwise"
    monkeypatch.setenv("REPRO_MEGASTEP", "stepwise")
    assert resolve_megastep("auto") == "stepwise"
    assert resolve_megastep("fused") == "fused"      # explicit beats env
    with pytest.raises(ValueError):
        resolve_megastep("turbo")


def test_scheduler_resolves_env_megastep(data, model, monkeypatch):
    monkeypatch.setenv("REPRO_MEGASTEP", "stepwise")
    eng = Scheduler(FLConfig(**megastep_cfg()), model, data,
                    det_fleet(N_CLIENTS))
    assert eng.megastep == "stepwise"
    assert eng.metrics()["megastep_rounds"] == 0


# ------------------------------------------------------------- engagement
def test_megastep_engages_and_is_bit_identical(data, model):
    """The headline: ceil(10/4)=3 stepwise bootstrap rounds (top-k invokes
    uninvoked clients first), then the remaining 5 rounds run as ONE fused
    scan — and every observable equals the stepwise oracle bitwise."""
    m_step, m_fused = assert_fused_matches_stepwise(
        megastep_cfg(), model, data, min_fused_rounds=5)
    assert m_fused["megastep_scans"] >= 1
    assert m_fused["megastep_fallback_reason"] == "eligible"
    assert m_step["megastep_rounds"] == 0


# -------------------------------------------------------- acceptance matrix
MATRIX = ALL_STRATEGIES + REACTIVE + ("apodotiko-topk",)


@pytest.mark.parametrize("data_plane", ("device", "host"))
@pytest.mark.parametrize("update_plane", ("device", "blob"))
@pytest.mark.parametrize("strategy", MATRIX)
def test_fused_vs_stepwise_matrix(strategy, update_plane, data_plane,
                                  data, model):
    """Every strategy x update plane x data plane on the (noisy) paper
    fleet: the fused scheduler must be indistinguishable from stepwise —
    here via eligibility fallback, since variability > 0."""
    assert_fused_matches_stepwise(
        base_cfg_kw(strategy=strategy, update_plane=update_plane,
                    data_plane=data_plane),
        model, data, fleet=paper_fleet(N_CLIENTS))


@pytest.mark.parametrize("kw,engages", [
    (dict(), True),
    (dict(update_plane="blob"), False),
    (dict(data_plane="host"), False),
    (dict(eval_every=1), False),
    (dict(failure_rate=0.2), False),
    (dict(concurrency_ratio=0.5), False),
])
def test_eligibility_gates(kw, engages, data, model):
    """Each gate flips exactly the engagement bit; bit-identity holds on
    both sides of it."""
    m_step, m_fused = assert_fused_matches_stepwise(
        megastep_cfg(rounds=5, **kw), model, data)
    assert (m_fused["megastep_rounds"] > 0) == engages


def test_cohort_compile_failure_raises(data, model, monkeypatch):
    """A cohort program that fails to build stops the run; it does not
    quietly continue stepwise."""
    eng = Scheduler(FLConfig(**megastep_cfg(rounds=3)), model, data,
                    det_fleet(N_CLIENTS))
    eng.run()                                # bootstrap rounds only
    eng.cfg.rounds = 5
    assert _plan(eng)[1] == "eligible"

    def boom(*args, **kwargs):
        raise RuntimeError("cohort program failed to compile")

    monkeypatch.setattr(eng.trainer, "cohort_fn_indexed", boom)
    with pytest.raises(RuntimeError, match="failed to compile"):
        _plan(eng)


# ------------------------------------------------------ fallback boundaries
def test_fallback_timer_armed_then_cleared(data, model):
    """An armed timer (the hedge barrier) must keep the fused path out —
    side-effect free — and clearing it re-admits the very same rounds."""
    eng = Scheduler(FLConfig(**megastep_cfg(rounds=3)), model, data,
                    det_fleet(N_CLIENTS))
    eng.run()
    assert eng.megastep_rounds == 0          # bootstrap rounds only
    eng.cfg.rounds = 5
    heapq.heappush(eng._timers, (eng.loop.now + 5.0, 0, eng.db.round,
                                 "hedge"))
    before = (len(eng.history), eng.db.round, list(eng.store._free))
    plan, reason = _plan(eng)
    assert plan is None and reason == "timer armed"
    assert (len(eng.history), eng.db.round, list(eng.store._free)) == before
    heapq.heappop(eng._timers)
    plan, reason = _plan(eng)
    assert plan is not None and reason == "eligible"
    m = eng.run()
    assert m["megastep_rounds"] == 2


def test_fallback_k_exceeds_idle_pool(data, model):
    """ClientLeft shrinking the idle pool below K: the plan refuses and
    mutates nothing."""
    eng = Scheduler(FLConfig(**megastep_cfg(rounds=5)), model, data,
                    det_fleet(N_CLIENTS))
    m = eng.run()
    assert m["megastep_rounds"] > 0
    eng.remove_clients(list(range(7)))       # 3 idle < K=4
    eng.cfg.rounds = 6
    before = (len(eng.history), eng.db.round, list(eng.store._free))
    plan, reason = _plan(eng)
    assert plan is None and reason == "K exceeds idle-client count"
    assert (len(eng.history), eng.db.round, list(eng.store._free)) == before


def test_fallback_noisy_hardware(data, model):
    """One client with nonzero duration variability poisons the whole
    eligibility proof — every round stays stepwise, runs stay identical."""
    fleet = det_fleet(N_CLIENTS)
    fleet[3] = HardwareProfile("noisy", speed=1.45, vcpus=1.0, mem_gib=2.0,
                               variability=0.05)
    m_step, m_fused = assert_fused_matches_stepwise(
        megastep_cfg(rounds=5), model, data, fleet=fleet)
    assert m_fused["megastep_rounds"] == 0
    assert m_fused["megastep_fallback_reason"] \
        == "client hardware has nonzero variability"


def test_fallback_cold_horizon(data, model):
    """A short keep-warm window breaks the warm-horizon proof (an
    instance would go cold mid-scan): no round fuses, runs stay
    identical including the cold-start records."""
    m_step, m_fused = assert_fused_matches_stepwise(
        megastep_cfg(rounds=5, keep_warm=0.5), model, data)
    assert m_fused["megastep_rounds"] == 0


def test_fallback_progress_callback(data, model):
    """A per-round progress callback may mutate the engine mid-run, which
    the already-computed scan could not observe — so it gates fusion."""
    logs = []
    eng = Scheduler(FLConfig(**megastep_cfg()), model, data,
                    det_fleet(N_CLIENTS))
    m = eng.run(progress=logs.append)
    assert m["megastep_rounds"] == 0
    assert "progress callback" in m["megastep_fallback_reason"]
    assert len(logs) == 8


def test_churn_between_runs_stays_identical(data, model):
    """ClientLeft between run segments: both modes remove the same
    clients, extend the horizon, and must still agree bitwise — with the
    fused path re-engaging on the shrunken fleet."""
    engines = {}
    for mode in ("stepwise", "fused"):
        eng = Scheduler(FLConfig(**megastep_cfg(rounds=5, megastep=mode)),
                        model, data, det_fleet(N_CLIENTS))
        eng.run()
        eng.remove_clients([2, 7])
        eng.cfg.rounds = 8
        eng.run()
        engines[mode] = eng
    step, fused = engines["stepwise"], engines["fused"]
    assert fused.megastep_rounds > 0
    assert trace(fused) == trace(step)
    assert_params_equal(step.params, fused.params)
    assert_fleet_state_equal(step, fused)


# --------------------------------------------------- randomized properties
@pytest.mark.parametrize("seed", range(5))
def test_eligibility_never_admits_divergent_round(seed, data, model):
    """Seeded property sweep: random fleets (mixed zero/nonzero
    variability, duration ties included), cohort sizes, CR gates,
    keep-warm windows and failure rates — whatever subset of rounds the
    eligibility check admits, the run must stay bit-identical to
    stepwise."""
    rng = np.random.default_rng(seed)
    fleet = [HardwareProfile(f"p{i}",
                             speed=float(rng.choice([1.0, 1.3, 1.7])),
                             vcpus=1.0, mem_gib=2.0,
                             variability=float(rng.choice([0.0, 0.0, 0.1])))
             for i in range(N_CLIENTS)]
    kw = megastep_cfg(rounds=int(rng.integers(3, 7)),
                      clients_per_round=int(rng.integers(2, 5)),
                      concurrency_ratio=float(rng.choice([0.5, 1.0])),
                      keep_warm=float(rng.choice([2.0, 1e9])),
                      failure_rate=float(rng.choice([0.0, 0.0, 0.25])),
                      seed=seed)
    assert_fused_matches_stepwise(kw, model, data, fleet=fleet)


@pytest.mark.parametrize("seed", range(3))
def test_random_churn_schedule_stays_identical(seed, data, model):
    """Seeded churn-schedule property: random horizon, random victims
    removed between segments, random extension — fused == stepwise on
    the full two-segment trace and end state."""
    engines = {}
    for mode in ("stepwise", "fused"):
        rng = np.random.default_rng(100 + seed)      # same draws per mode
        eng = Scheduler(
            FLConfig(**megastep_cfg(rounds=int(rng.integers(3, 6)),
                                    megastep=mode, seed=seed)),
            model, data, det_fleet(N_CLIENTS))
        eng.run()
        victims = rng.choice(N_CLIENTS, size=int(rng.integers(1, 3)),
                             replace=False)
        eng.remove_clients([int(v) for v in victims])
        eng.cfg.rounds += int(rng.integers(1, 4))
        eng.run()
        engines[mode] = eng
    step, fused = engines["stepwise"], engines["fused"]
    assert trace(fused) == trace(step)
    assert_params_equal(step.params, fused.params)
    assert_fleet_state_equal(step, fused)
