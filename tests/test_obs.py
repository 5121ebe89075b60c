"""The program's spans and counters (``repro.obs``).

Spans and counts cost one check while the profiler is off; while it
records, spans land on the profiler's host plane, where the benchmark's
trace reduction (``chipbench/benchlib/traces.py``) finds them by name, and
counts land in the session tally. The trainer's lane-step counters match
the lane-steps its dispatches ran (lanes × the cohort's longest client),
on the stepwise and on the fused megastep path, and the cohort program's named scopes rename nothing the benchmark
matches.
"""
import contextlib
import dataclasses
import os
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.client import CohortTrainer, _bucket
from repro.core.controller import FLConfig
from repro.core.scheduler import Scheduler
from repro.faas.hardware import paper_fleet
from repro.kernels import fused_adam as fused_adam_mod
from repro.kernels.fused_adam import BLOCK

from trace_harness import (N_CLIENTS, base_cfg_kw, data, det_fleet,  # noqa: F401
                           megastep_cfg, model)

sys.path.append(os.path.join(os.path.dirname(__file__), "..", "chipbench"))
from benchlib import traces  # noqa: E402

LANE_COUNTERS = ("train.lane_steps", "train.real_lane_steps",
                 "train.useful_steps")


@contextlib.contextmanager
def recording(logdir):
    """A CPU profiler session around the block, its tally started empty:
    a count made with the profiler off ends the previous session's."""
    assert not obs.TraceAnnotation.is_enabled()
    obs.count("test.obs.off")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _record_dispatches(eng) -> list:
    """Wrap the trainer's cohort entry: [(K, steps), ...] per dispatch."""
    seen = []
    orig = eng.trainer.train_cohort_indexed

    def hook(global_params, store, selection, n_i, steps, *args, **kw):
        seen.append((len(selection), np.asarray(steps).copy()))
        return orig(global_params, store, selection, n_i, steps, *args, **kw)

    eng.trainer.train_cohort_indexed = hook
    return seen


def _expected(dispatches, floor) -> dict:
    want = dict.fromkeys(LANE_COUNTERS, 0)
    for K, steps in dispatches:
        S = int(steps.max())
        want["train.lane_steps"] += _bucket(K, floor) * S
        want["train.real_lane_steps"] += K * S
        want["train.useful_steps"] += int(steps.sum())
    return want


def test_span_without_profiler_builds_nothing(monkeypatch):
    built = []

    class Spy(obs.TraceAnnotation):
        def __init__(self, *a, **kw):
            built.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(obs, "TraceAnnotation", Spy)
    before = obs.session()
    with obs.span("fl.train", round=0, lanes=8):
        pass
    with obs.sync("cohort_losses", round=0):
        pass
    obs.count("train.lane_steps", 5)
    assert built == []
    assert obs.span("a") is obs.span("b")          # one shared null context
    assert obs.session() == before


def test_count_adds_to_totals(tmp_path):
    """Counts add up within a session; the next session starts afresh."""
    with recording(tmp_path / "a"):
        obs.count("test.obs.things", 3)
        obs.count("test.obs.things")
    assert obs.session() == {"test.obs.things": 4}
    obs.count("test.obs.things", 7)                 # profiler off
    assert obs.session() == {"test.obs.things": 4}
    with recording(tmp_path / "b"):
        obs.count("test.obs.things", 2)
    assert obs.session() == {"test.obs.things": 2}


def test_counts_from_threads_are_not_lost(tmp_path):
    """Sweeps run engines on threads: concurrent counts all land."""
    n_threads, n_each = 4 * (os.cpu_count() or 1), 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    with recording(tmp_path):
        threads = [threading.Thread(
            target=lambda: [obs.count("test.obs.threads")
                            for _ in range(n_each)])
            for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    sys.setswitchinterval(old)
    assert obs.session() == {"test.obs.threads": n_threads * n_each}


@pytest.fixture(scope="module")
def traced(data, model, tmp_path_factory):
    """A small stepwise engine run under the profiler, reduced by the
    benchmark's trace reader: (trace, session tally, dispatches, engine)."""
    eng = Scheduler(FLConfig(**base_cfg_kw(strategy="apodotiko", rounds=3,
                                           eval_every=1)),
                    model, data, list(paper_fleet(N_CLIENTS)))
    seen = _record_dispatches(eng)
    logdir = tmp_path_factory.mktemp("trace")
    with recording(logdir):
        eng.run()
    tally = obs.session()
    return traces.load(traces.find_xplane(logdir)), tally, seen, eng


def test_traced_run_has_the_round_spans(traced):
    tr, tally, _, eng = traced
    names = {e.name for e in tr.host}
    for want in ("fl.select", "fl.train", "fl.aggregate", "fl.eval",
                 "fl.sync.cohort_losses", "fl.sync.eval"):
        assert want in names
    n = lambda name: sum(e.name == name for e in tr.host)
    # one blocking read of each dispatch's losses, of each evaluation and,
    # on this small store's dense aggregation route, of each aggregate's
    # finiteness guard
    assert n("fl.train") == n("fl.sync.cohort_losses") == len(traced[2])
    assert n("fl.sync.eval") == n("fl.eval") > 0
    assert n("fl.aggregate") == n("fl.sync.agg_finite") == len(eng.history)
    assert {e.name for e in tr.host if e.name.startswith("fl.sync.")} == {
        "fl.sync.cohort_losses", "fl.sync.eval", "fl.sync.agg_finite"}


def test_session_counts_the_dispatches(traced):
    _, tally, seen, eng = traced
    assert seen
    want = _expected(seen, eng.trainer.cohort_floor)
    assert {k: tally[k] for k in LANE_COUNTERS} == want
    # the proxy's optimizer is XLA Adam on the CPU: no fused-kernel bytes
    assert "train.adam_bytes" not in tally


def test_stepwise_counters_match_the_buckets(data, model, tmp_path):
    """Cohorts of 6 clients on 8 lanes: padded lanes and masked steps."""
    eng = Scheduler(FLConfig(**base_cfg_kw(strategy="fedavg", rounds=2,
                                           clients_per_round=6)),
                    model, data, list(paper_fleet(N_CLIENTS)))
    seen = _record_dispatches(eng)
    with recording(tmp_path):
        eng.run()
    got = obs.session()
    want = _expected(seen, eng.trainer.cohort_floor)
    assert {k: got[k] for k in LANE_COUNTERS} == want
    assert want["train.lane_steps"] > want["train.real_lane_steps"]


def test_fused_megastep_counts_what_stepwise_counts(data, model, tmp_path):
    counted = {}
    for mode in ("stepwise", "fused"):
        eng = Scheduler(FLConfig(**megastep_cfg(megastep=mode)), model, data,
                        det_fleet(N_CLIENTS))
        with recording(tmp_path / mode):
            m = eng.run()
        counted[mode] = {k: obs.session()[k] for k in LANE_COUNTERS}
        if mode == "fused":
            assert m["megastep_rounds"] >= 5
    assert counted["fused"] == counted["stepwise"]
    assert counted["fused"]["train.lane_steps"] > 0


def test_fused_megastep_counts_each_rounds_longest_client(data, model,
                                                         tmp_path):
    """Clients of 2 to 4 steps: the fused scan and the stepwise run of the
    same rounds both count lanes × each round's longest client."""
    het = dataclasses.replace(
        data, n=np.array([20, 15, 15, 15, 15, 10, 10, 10, 10, 10]))
    counted = {}
    for mode in ("stepwise", "fused"):
        eng = Scheduler(FLConfig(**megastep_cfg(megastep=mode)), model, het,
                        det_fleet(N_CLIENTS))
        seen = _record_dispatches(eng)
        with recording(tmp_path / mode):
            m = eng.run()
        counted[mode] = {k: obs.session()[k] for k in LANE_COUNTERS}
        if mode == "stepwise":
            want = _expected(seen, eng.trainer.cohort_floor)
        else:
            assert m["megastep_rounds"] >= 5
    assert counted["stepwise"] == counted["fused"] == want
    assert want["train.useful_steps"] < want["train.real_lane_steps"]


@pytest.mark.parametrize("path,counts", [("fused", True), ("xla", False)])
def test_adam_bytes_count_the_fused_kernel(monkeypatch, model, path, counts,
                                          tmp_path):
    monkeypatch.setenv("REPRO_ADAM_PATH", path)
    tr = CohortTrainer(model, optimizer="adam", lr=1e-3, batch_size=5)
    params = model.init(jax.random.PRNGKey(0))[0]
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    with recording(tmp_path):
        tr.count_lane_steps(params, Kp=8, K=5, trips=16, useful=40)
    got = obs.session()
    assert got["train.lane_steps"] == 128
    assert got["train.real_lane_steps"] == 80
    assert got["train.useful_steps"] == 40
    w_pad = -(-n // BLOCK) * BLOCK
    assert got.get("train.adam_bytes", 0) == (128 * 28 * w_pad if counts
                                              else 0)


@pytest.mark.parametrize("n", [1, BLOCK, 582_026])
def test_adam_hbm_bytes_are_the_kernel_operands(n):
    """``hbm_bytes`` is what one ``fused_adam`` call reads and writes: its
    four flat operands and three results, padded to the block."""
    w = n + (-n) % BLOCK
    flat = jax.ShapeDtypeStruct((w,), jnp.float32)
    outs = jax.eval_shape(
        lambda p, m, v, g: fused_adam_mod.fused_adam(
            p, m, v, g, jnp.int32(1), lr=1e-3),
        flat, flat, flat, flat)
    nbytes = lambda a: int(np.prod(a.shape)) * a.dtype.itemsize
    assert fused_adam_mod.hbm_bytes(n) == (4 * nbytes(flat)
                                           + sum(map(nbytes, outs)))


#: an HLO computation's header and an instruction's name and opcode, in the
#: compiled module's text
_HLO_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$", re.M)
_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(",
                        re.M)


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described v5e, which the installed TPU compiler
    builds for without a chip attached (as ``test_tpu_compile.py``)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_named_scopes_add_no_operation(data, model, monkeypatch, v5e_chip):
    """The cohort program as the chip runs it (device data plane, rows
    scattered into the update store, the fused Adam kernel) compiles for a
    v5e to the same computations and instructions, names included, with
    its named scopes as without them: the module stays ``jit_cohort_flat``
    and the kernel ``fused_adam.<n>``, which the benchmark's metrics match."""
    from repro.kernels import ops
    from repro.optim import optimizers

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    tr = CohortTrainer(model, optimizer="sgd", lr=1e-3, batch_size=5)
    tr.opt = optimizers.adam_fused(1e-3)
    params = model.init(jax.random.PRNGKey(0))[0]
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    Kp = 2
    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                        sharding=v5e_chip)
    like = lambda f: jax.tree.map(lambda p: on_chip(f(p), p.dtype), params)
    args = (like(lambda p: p.shape), on_chip((Kp,), jnp.int32),
            on_chip((Kp,), jnp.int32), on_chip((Kp,), jnp.int32),
            on_chip((Kp, 2), jnp.uint32), like(lambda p: ()),
            like(lambda p: (Kp,) + (1,) * p.ndim),
            on_chip(data.X.shape, data.X.dtype),
            on_chip(data.y.shape, data.y.dtype),
            on_chip((8, n + (-n) % 2048), jnp.float32),
            on_chip((Kp,), jnp.int32))

    def names():
        fn = tr._make_fn(flat_updates=True, indexed=True)
        hlo = fn.lower(*args).compile().as_text()
        return (hlo.split(",", 1)[0], _HLO_COMP.findall(hlo),
                _HLO_INSTR.findall(hlo))

    scoped = names()
    calls = []

    def no_scope(name):
        calls.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(jax, "named_scope", no_scope)
    plain = names()
    assert {"cohort.gather", "cohort.fwd_bwd", "cohort.optimizer",
            "cohort.mask", "cohort.scatter_rows"} <= set(calls)
    assert scoped == plain
    assert scoped[0] == "HloModule jit_cohort_flat"
    assert any(re.fullmatch(r"fused_adam(\.\d+)?", name) and op == "custom-call"
               for name, op in scoped[2])
