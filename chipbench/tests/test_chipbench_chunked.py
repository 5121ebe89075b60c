"""The window's rounds, driven in chunks through ``run()``, are the rounds
of one ``run()``: the same history, invocations and final weights, on the
event-driven path and on the fused megastep path."""
import dataclasses

import jax
import numpy as np
import pytest

import tinybench  # noqa: F401  (puts the program on sys.path)
from benchlib import harness


def _engine(strategy: str):
    from repro.core import FLConfig, build_engine
    from repro.data.synthetic import make_federated_dataset
    from repro.faas.hardware import paper_fleet
    from repro.models.proxy_models import build_bench_model

    data = make_federated_dataset("mnist", 16, scale=0.05, seed=0)
    fleet = list(paper_fleet(16))
    kw = dict(strategy=strategy, concurrency_ratio=0.3, eval_every=2)
    if strategy == "apodotiko-topk":
        fleet = [dataclasses.replace(h, variability=0.0) for h in fleet]
        kw = dict(strategy=strategy, concurrency_ratio=1.0, eval_every=0,
                  keep_warm=1e9)
    cfg = FLConfig(n_clients=16, clients_per_round=6, local_epochs=1,
                   rounds=0, seed=3, engine="scheduler", megastep="fused",
                   data_plane="device", update_plane="device",
                   control_plane="columnar", mesh="1x1", **kw)
    return build_engine(cfg, build_bench_model("mnist"), data, fleet)


def _state(eng):
    return ([(l.round, l.t_start, l.t_end, l.n_aggregated, l.accuracy)
             for l in eng.history],
            [(r.client_id, r.round, r.t_invoked, r.duration)
             for r in eng.platform.invocations],
            [np.asarray(x) for x in jax.tree.leaves(eng.params)])


@pytest.mark.parametrize("strategy", ["apodotiko", "apodotiko-topk"])
def test_chunked_rounds_equal_one_run(strategy):
    one = _engine(strategy)
    harness.run_rounds(one, 7)
    chunked = _engine(strategy)
    for n in (3, 2, 2):
        harness.run_rounds(chunked, n)
    a, b = _state(one), _state(chunked)
    assert a[0] == b[0] and a[1] == b[1]
    assert all(np.array_equal(x, y) for x, y in zip(a[2], b[2]))
    if strategy == "apodotiko-topk":
        assert one.megastep_rounds > 0 and chunked.megastep_rounds > 0
