"""Smoke test of the main FL path on a TPU.

    python chip_smoke.py              # one chip: phases "paper", "megastep"
    python chip_smoke.py --chips 4    # four chips: phase "mesh" only

Every phase drives ``repro.core.build_engine(cfg, model, data, fleet).run()``
at the full width of the paper's MNIST client model (``MnistCNN``, 582,026
parameters) on the paper deployment (paper §IV-A, ``PAPER_SCALE`` in
``repro.sweep.grid``): 200 clients, 100 invoked per round, the 65/25/10
hardware mix, and the client settings of ``repro.configs.paper_mnist``
(Adam 1e-3, 5 local epochs, batch 10). Weights and data come from seed 0.

* ``paper``: strategy ``apodotiko`` at CR 0.3 for three aggregations,
  evaluating after the first and the last. Checks: aggregation ran the
  Pallas kernel, the optimizer is the fused-Adam kernel, the final accuracy
  and eval loss are finite, and the eval loss fell below the initial
  model's.
* ``megastep``: ``apodotiko-topk`` at CR 1 on the same hardware mix with
  zero speed variability and no evaluation, so after two bootstrap rounds
  the fused round scan (``repro.core.megastep``) compiles and runs.
  Checks: fused rounds ran, the planner's last verdict was "eligible", and
  the params are finite.
* ``mesh`` (``--chips 4``): the ``megastep`` workload on a 2x2
  ``("data", "model")`` mesh and, in the same process, at 1x1 as the
  reference. Checks: the same initial params, identical selections,
  timing and round boundaries, and final params whose distance from the
  reference's is under ``MESH_MAX_REL_ERR`` of the distance the reference
  trained.

Each phase prints one JSON line with its setup, compile and run seconds.
The last line of standard output is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
A failed check exits nonzero without that line, and so does a host where
JAX finds no TPU: the script never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

N_PARAMS = 582_026          # MnistCNN (paper §IV-A2)
FUSED_ROUNDS = 3            # megastep phase: rounds after the bootstrap
#: bound on |p_mesh - p_1x1| / |p_1x1 - p_init| (L2 over all params). The
#: psum and the 64-lane shards only reassociate float sums, but on a TPU
#: v5e the f32 convolutions at the default matmul precision round
#: differently in the two programs, and 75 local Adam steps a round
#: amplify that: 0.14 after one round and 0.21 after five, against 0.003
#: after one round at "highest" precision. Two trainings that drew
#: different batches or keys from the same init would land near sqrt(2).
MESH_MAX_REL_ERR = 0.5

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (persistent-cache
    reads included), and the cache's hits and misses, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.hits, self.misses


class Phase:
    """One phase's record: timings, facts and the checks it failed."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock
        self.rec: dict = {"phase": name}
        self.failed: list[str] = []
        self._t = time.perf_counter()
        self._c = clock.snapshot()

    def lap(self, key: str) -> None:
        """Close the current interval as ``<key>_s`` (wall seconds) and
        ``<key>_compile_s`` (the part spent compiling)."""
        t, c = time.perf_counter(), self.clock.snapshot()
        self.rec[f"{key}_s"] = t - self._t
        self.rec[f"{key}_compile_s"] = c[0] - self._c[0]
        self.rec[f"{key}_cache_hits"] = c[1] - self._c[1]
        self.rec[f"{key}_cache_misses"] = c[2] - self._c[2]
        self._t, self._c = t, c

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.failed.append(what)

    def emit(self) -> bool:
        self.rec["failed_checks"] = self.failed
        print(json.dumps(self.rec, default=str), flush=True)
        return not self.failed


def paper_setup():
    """The paper model, dataset and hardware mix, and the shared config."""
    from repro.configs import paper_mnist
    from repro.core import FLConfig
    from repro.data.synthetic import make_federated_dataset
    from repro.faas.hardware import paper_fleet
    from repro.models.proxy_models import build_bench_model
    from repro.sweep.grid import PAPER_SCALE
    from repro.sweep.runner import BASE_STEP_TIME

    s = PAPER_SCALE
    model = build_bench_model("mnist", "paper")
    data = make_federated_dataset("mnist", n_clients=s.n_clients,
                                  scale=s.data_scale, fidelity="paper",
                                  seed=s.data_seed)
    cfg = FLConfig(n_clients=s.n_clients,
                   clients_per_round=s.clients_per_round,
                   local_epochs=paper_mnist.LOCAL_EPOCHS,
                   batch_size=paper_mnist.BATCH_SIZE,
                   optimizer=paper_mnist.CONFIG.optimizer,
                   lr=paper_mnist.CONFIG.learning_rate,
                   base_step_time=BASE_STEP_TIME["mnist"], seed=0)
    return model, data, list(paper_fleet(s.n_clients)), cfg


def _eval_loss(model, params, data) -> float:
    import jax
    import jax.numpy as jnp

    loss, _ = jax.jit(model.loss)(params, {"x": jnp.asarray(data.eval_x),
                                           "y": jnp.asarray(data.eval_y)})
    return float(loss)


def _describe(ph: Phase, eng, cfg) -> None:
    import jax
    import numpy as np

    from repro.core import client

    leaves = jax.tree.leaves(eng.params)
    ph.rec["n_params"] = int(sum(l.size for l in leaves))
    ph.rec["K"] = cfg.clients_per_round
    ph.rec["lanes"] = client._bucket(cfg.clients_per_round,
                                     eng.trainer.cohort_floor)
    ph.rec["optimizer"] = eng.trainer.opt.name
    ph.check(ph.rec["n_params"] == N_PARAMS, f"n_params != {N_PARAMS}")
    ph.check(all(bool(np.all(np.isfinite(np.asarray(l)))) for l in leaves),
             "params not finite")


def phase_paper(clock, model, data, fleet, cfg) -> bool:
    """Apodotiko at CR 0.3: three aggregations, two evaluations."""
    from repro.core import aggregation, build_engine

    ph = Phase("paper", clock)
    cfg = dataclasses.replace(cfg, strategy="apodotiko", concurrency_ratio=0.3,
                              rounds=3, eval_every=2)
    eng = build_engine(cfg, model, data, fleet)
    loss0 = _eval_loss(model, eng.params, data)
    ph.lap("setup")
    m = eng.run()
    ph.lap("run")
    loss = _eval_loss(model, eng.params, data)
    _describe(ph, eng, cfg)
    ph.rec.update(rounds=m["rounds"], accuracy=m["final_accuracy"],
                  eval_loss_init=loss0, eval_loss=loss,
                  aggregation_path=aggregation.last_path(),
                  n_invocations=m["n_invocations"])
    ph.check(m["rounds"] == cfg.rounds, "not every round aggregated")
    ph.check(ph.rec["aggregation_path"] == "pallas", "aggregation not pallas")
    ph.check(ph.rec["optimizer"] == "adam-fused", "optimizer not adam-fused")
    ph.check(bool(0.0 <= m["final_accuracy"] <= 1.0), "accuracy not finite")
    ph.check(loss == loss and loss < loss0, "eval loss did not fall")
    return ph.emit()


def _megastep_cfg(cfg, mesh: str):
    return dataclasses.replace(
        cfg, strategy="apodotiko-topk", concurrency_ratio=1.0, eval_every=0,
        keep_warm=1e9, rounds=2 + FUSED_ROUNDS, mesh=mesh)


def _flat(params):
    import jax
    import numpy as np

    return np.concatenate([np.asarray(x, np.float64).ravel()
                           for x in jax.tree.leaves(params)])


def _run_megastep(model, data, fleet, cfg):
    """(engine, metrics, flat initial params)"""
    from repro.core import build_engine

    det = [dataclasses.replace(hw, variability=0.0) for hw in fleet]
    eng = build_engine(cfg, model, data, det)
    p0 = _flat(eng.params)
    return eng, eng.run(), p0


def phase_megastep(clock, model, data, fleet, cfg) -> bool:
    """apodotiko-topk at CR 1: two bootstrap rounds, then fused rounds."""
    ph = Phase("megastep", clock)
    cfg = _megastep_cfg(cfg, "1x1")
    eng, m, _ = _run_megastep(model, data, fleet, cfg)
    ph.lap("run")
    _describe(ph, eng, cfg)
    ph.rec.update(rounds=m["rounds"], megastep_rounds=m["megastep_rounds"],
                  megastep_scans=m["megastep_scans"],
                  megastep_fallback_reason=m["megastep_fallback_reason"])
    ph.check(m["rounds"] == cfg.rounds, "not every round aggregated")
    ph.check(m["megastep_rounds"] > 0, "no fused rounds")
    ph.check(m["megastep_fallback_reason"] == "eligible",
             "megastep fell back")
    return ph.emit()


def _trace(eng):
    rounds = [(l.round, l.t_start, l.t_end, l.n_aggregated)
              for l in eng.history]
    inv = [(r.client_id, r.round, r.t_invoked, r.duration)
           for r in eng.platform.invocations]
    return rounds, inv, _flat(eng.params)


def phase_mesh(clock, model, data, fleet, cfg) -> bool:
    """The megastep workload sharded 2x2 against its 1x1 reference."""
    import numpy as np

    ph = Phase("mesh", clock)
    ref_eng, ref_m, p_init = _run_megastep(model, data, fleet,
                                           _megastep_cfg(cfg, "1x1"))
    ph.lap("reference_run")
    cfg = _megastep_cfg(cfg, "2x2")
    eng, m, p_init_mesh = _run_megastep(model, data, fleet, cfg)
    ph.lap("mesh_run")
    _describe(ph, eng, cfg)
    r_ref, i_ref, p_ref = _trace(ref_eng)
    r_mesh, i_mesh, p_mesh = _trace(eng)
    trained = float(np.linalg.norm(p_ref - p_init))
    rel = float(np.linalg.norm(p_mesh - p_ref)) / trained
    shards = eng.store.buffer.addressable_shards
    ph.rec.update(mesh=m["mesh"], rounds=m["rounds"],
                  megastep_rounds=m["megastep_rounds"],
                  reference_megastep_rounds=ref_m["megastep_rounds"],
                  store_shards=len(shards),
                  store_shard_devices=sorted({str(s.device) for s in shards}),
                  selections_equal=i_mesh == i_ref,
                  rounds_equal=r_mesh == r_ref,
                  params_max_abs_err=float(np.max(np.abs(p_mesh - p_ref))),
                  params_trained_l2=trained, params_rel_err=rel)
    ph.check(m["mesh"] == "2x2", "mesh not 2x2")
    ph.check(len(shards) == 4, "update store not split over 4 devices")
    ph.check(m["rounds"] == cfg.rounds, "not every round aggregated")
    ph.check(bool(np.array_equal(p_init_mesh, p_init)),
             "initial params differ from 1x1")
    ph.check(i_mesh == i_ref, "selections differ from the 1x1 reference")
    ph.check(r_mesh == r_ref, "round boundaries differ from 1x1")
    ph.check(rel <= MESH_MAX_REL_ERR, "params not close to 1x1")
    return ph.emit()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases paper + megastep; 4: the 2x2 mesh phase")
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(json.dumps({"compile_cache": cache, "jax": jax.__version__,
                      "device_kind": dev.device_kind,
                      "device_count": len(devices)}), flush=True)

    clock = CompileClock()
    t0 = time.perf_counter()
    model, data, fleet, cfg = paper_setup()
    print(json.dumps({"phase": "data", "setup_s": time.perf_counter() - t0,
                      "clients": len(data.n),
                      "max_samples": int(data.X.shape[1]),
                      "dataset_bytes": int(data.X.nbytes + data.y.nbytes)}),
          flush=True)
    if args.chips == 4:
        ok = phase_mesh(clock, model, data, fleet, cfg)
    else:
        ok = phase_paper(clock, model, data, fleet, cfg)
        ok = phase_megastep(clock, model, data, fleet, cfg) and ok
    if not ok:
        print("chip_smoke: a check failed (see failed_checks above)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
