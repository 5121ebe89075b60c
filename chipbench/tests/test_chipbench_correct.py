"""``correct`` on a small cell on the CPU: a sound run passes; the control
(the reference at bfloat16 in the program's place) and each fault the
cell can have, planted in the program underneath a whole run, fail."""
import jax
import jax.numpy as jnp
import pytest

import calibrate
import tinybench
from benchlib import flref, harness


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tinybench.make(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def sound(bench):
    ex: dict = {}
    return tinybench.run(bench, extras=ex), ex


def test_sound_run_is_correct(sound):
    r, ex = sound
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        assert k in r
    assert set(r["metrics"]) == {"round_s", "setup_s"}
    assert r["metrics"]["round_s"]["value"] > 0
    assert r["device"]["platform"] == "cpu"
    assert ex["numbers"]["schedule_mismatch"] == 0
    # the kept dispatches cover every lane count of the window
    assert ex["numbers"]["lanes_uncompared"] == 0


def test_control_is_not_correct(sound):
    _, ex = sound
    ev = ex["evidence"]
    low = calibrate.control_readings(ev, harness, flref,
                                     jax.lax.Precision.DEFAULT, jnp.bfloat16)
    ok, checks = harness.decide(low, tinybench.TINY_LIMITS)
    assert not ok, checks


def _unchanged_state(monkeypatch):
    """Every local step returns the client's state unchanged."""
    from repro.core import client
    from repro.optim.optimizers import Optimizer

    def frozen(name, lr):
        return Optimizer(lambda p: {},
                         lambda g, s, p: (jax.tree.map(jnp.zeros_like, g), s),
                         "frozen")

    monkeypatch.setattr(client, "build_optimizer", frozen)


def _half_batch(monkeypatch):
    """Aggregation leaves out half of the results and averages the rest."""
    from repro.core import services

    orig = services.weighted_aggregate_rows

    def half(buffer, rows, weights, spec, **kw):
        k = max(len(rows) // 2, 1)
        w = weights[:k] / weights[:k].sum()
        return orig(buffer, rows[:k], w, spec, **kw)

    monkeypatch.setattr(services, "weighted_aggregate_rows", half)


def _altered_answer(monkeypatch):
    """The first lane's trained weights are altered as they are written."""
    from repro.core import client

    orig = client.scatter_rows

    def altered(buffer, row_ids, leaves):
        return orig(buffer, row_ids,
                    [leaves[0].at[0].multiply(1.01)] + list(leaves[1:]))

    monkeypatch.setattr(client, "scatter_rows", altered)
    monkeypatch.setattr(client, "_COMPILE_CACHE", {})   # trace it anew


def _late_results(monkeypatch):
    """The host pump's simulated platform lands every result later."""
    from repro.faas.platform import FaaSPlatform

    orig = FaaSPlatform.invoke

    def late(self, *args, **kw):
        rec = orig(self, *args, **kw)
        rec.duration *= 1.001
        rec.t_completed = rec.t_invoked + rec.duration
        return rec

    monkeypatch.setattr(FaaSPlatform, "invoke", late)


@pytest.mark.parametrize("plant", [_unchanged_state, _half_batch,
                                   _altered_answer, _late_results],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered", "schedule_altered"])
def test_fault_is_not_correct(bench, monkeypatch, plant):
    plant(monkeypatch)
    r = tinybench.run(bench)
    assert not r["correct"], r["checks"]
