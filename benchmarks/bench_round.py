"""Round hot-path benchmark: blob transport vs. the device-resident update
plane (DESIGN.md §2, "update plane"), plus the scheduler-dispatch
microbenchmark (``--scheduler``, DESIGN.md §7): event-loop throughput
under hedge-style cancellation churn, end-to-end protocol dispatch rate
(events/sec), and the overhead of the hedging policy vs plain apodotiko.
The scheduler numbers land in ``BENCH_scheduler.json``.

``--controlplane`` measures the *control* plane (DESIGN.md §10): the
score+select dispatch of Algorithm 3 — candidate partition, CEF scoring,
probabilistic draw, booster bookkeeping — on the object plane (per-client
``ClientRecord`` Python loop, the oracle) vs the columnar ``FleetStore``
(vectorized f64 window scoring, bit-identical selections) vs the
device-resident masked top-k selector (``FleetStore.select_topk``), at
fleet sizes M ∈ {1e3, 1e4, 1e5, 1e6}. The object plane is skipped at
M=1e6 (that is the point: a million ClientRecord objects is the wall the
columnar plane removes). Lands in ``BENCH_controlplane.json``; exits
nonzero if object and columnar selections diverge on the shared RNG
stream (the CI equivalence gate).

``--megastep`` measures the fused round megastep (DESIGN.md §11): a
provably quiescent run driven by the stepwise event engine (one Python
pump + several jit dispatches per round) vs ``REPRO_MEGASTEP=fused``
(the whole run of rounds lowered into one jitted ``lax.scan``). Reports
wall time per round for both modes, protocol events dispatched per
round (0 for fused steady state — the headline), and the Python-overhead
share the fusion removes. Lands in ``BENCH_megastep.json``; exits
nonzero if the fused run diverges bitwise from the stepwise oracle or
dispatches any Python event during quiescent rounds (the CI gate).

``--dataplane`` measures the *input* half of the transport story
(DESIGN.md §2, "data plane"): per-cohort-dispatch latency and H2D
training-input bytes with the dataset resident on device
(``REPRO_DATA_PLANE=device``, index-vector dispatch + on-jit gather) vs
the legacy host fancy-index + per-dispatch upload, plus end-to-end FL
runs on both planes (events/s re-measure, ``data_host_bytes``
accounting). Lands in ``BENCH_dataplane.json``; exits nonzero if the
device plane moved any training-input bytes (the CI gate).

``--faults`` measures the fault-injection subsystem (DESIGN.md §12): the
same seeded run clean, under the crash-heavy chaos profile, and under
that profile with the retry/timeout/quarantine recovery layer armed —
failure/retry counts, simulated-time impact, recovery wall overhead.
Lands in ``BENCH_faults.json``; exits nonzero if a seeded fault schedule
replays differently on the two engines (the cross-engine chaos gate).

``--durability`` measures the durability subsystem (DESIGN.md §14):
journal overhead per round at each sync policy (off / round / event
fsync), coordinated-snapshot write and resume latency as the fleet
grows, and the resume-identity gate — a run crashed mid-journal must
resume bit-identically (history, simulated clock, journal bytes).
Lands in ``BENCH_durability.json``; exits nonzero if round-sync
journaling costs more than 5% wall overhead or the resumed trace
diverges.

``--traffic`` measures the open-loop traffic plane (DESIGN.md §13):
arrival-schedule compile throughput at M ∈ {1e5, 1e6}, bulk (windowed
``add_batch``/``remove_batch`` segments) vs per-event Python application
of a 1e4-client flash crowd over an M=1e5 ``FleetStore``, and the
per-strategy SLO table — p50/p99 round latency, cold-start rate,
cost-per-round — under the diurnal profile. Lands in
``BENCH_traffic.json``; exits nonzero if the bulk path diverges from the
per-event oracle (the CI gate).

``--sharding`` is a CPU-only layout check of the mesh plane (DESIGN.md
§15; the chip's mesh path is ``chip_smoke.py --chips 4``): a weak-scaling
curve over the ("data", "model") device mesh — each cell is a subprocess
on the CPU backend with its own forced host-device count (XLA fixes the
count at startup),
cohort size growing with the data axis, wall rounds/s plus the
structural metrics (bottleneck-device update-store bytes, equal-tile
split). Lands in ``BENCH_sharding.json``; exits nonzero if mesh='1x1'
diverges bitwise from the default path, the buffer does not split into
equal per-device tiles, or (on hosts with >= 8 cores) weak-scaled
throughput at 8 devices is below 1.5x the 1x1 oracle.

Measures the aggregation+transfer component of one controller round — the
path between cohort training finishing and the new global model existing —
at K ∈ {10, 100} clients x N ∈ {1e4, 1e6} parameters:

  * **blob** (legacy, ``REPRO_UPDATE_PLANE=blob``): copy the [K, ...] cohort
    output to host, slice K per-client pytrees, store them as blobs, then
    re-upload every blob and run ``weighted_aggregate`` (ravel + stack +
    kernel + unravel).
  * **plane** (default): flatten to [K, N] rows inside jit, scatter into the
    persistent ``UpdateStore`` buffer, then ``weighted_aggregate_rows``
    (index gather -> kernel -> one unravel). Zero host round-trips.

Emits ``BENCH_round.json`` next to the repo root and ``name,us,derived``
CSV lines like every other bench. ``--smoke`` runs only the smallest cell
with few iterations (the CI invocation); ``--json PATH`` overrides the
output location.
"""
from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import weighted_aggregate, weighted_aggregate_rows
from repro.core.update_store import UpdateStore
from repro.kernels.ops import RavelSpec

ITEMSIZE = 4  # fp32


def _cohort_output(K: int, N: int, seed: int = 0):
    """Stand-in for CohortTrainer's stacked device output: [K, ...] leaves.
    Two ragged leaves so the ravel/unravel work is exercised honestly."""
    rng = np.random.default_rng(seed)
    n_b = min(257, N // 2)
    tree = {"w": jnp.asarray(rng.normal(size=(K, N - n_b)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(K, n_b)), jnp.float32)}
    jax.block_until_ready(tree)
    return tree


def _blob_round(stacked, weights, template) -> tuple[object, int]:
    """The legacy path invoke_round + aggregate_round perform per round."""
    host = jax.tree.map(np.asarray, stacked)                 # device -> host
    down = sum(l.nbytes for l in jax.tree.leaves(host))
    K = weights.shape[0]
    blobs = [jax.tree.map(lambda x: x[k], host) for k in range(K)]
    ups = [jax.tree.map(jnp.asarray, b) for b in blobs]      # host -> device
    up = sum(l.nbytes for u in ups for l in jax.tree.leaves(u))
    out = weighted_aggregate(ups, weights, out_dtype=jnp.float32)
    jax.block_until_ready(out)
    return out, down + up


def _plane_round(stacked, weights, spec, store) -> tuple[object, int]:
    """The update-plane path: rows stay on device end-to-end. The ravel +
    scatter into the donated buffer happens in one fused jit (the same
    write the controller's cohort fn performs in-program)."""
    ids = store.put_stacked(stacked)
    out = weighted_aggregate_rows(store.buffer, ids, weights, spec,
                                  out_dtype=jnp.float32)
    jax.block_until_ready(out)
    store.free(ids)
    return out, 0


def bench_cell(K: int, N: int, iters: int) -> dict:
    stacked = _cohort_output(K, N)
    template = jax.tree.map(lambda x: x[0], stacked)
    spec = RavelSpec(template)
    weights = (np.ones(K) / K).astype(np.float32)
    store = UpdateStore(spec.n_params, capacity=K)

    def run(fn, *args):
        fn(*args)  # warmup/compile
        times = []
        byts = 0
        for _ in range(iters):
            t0 = time.perf_counter()
            _, byts = fn(*args)
            times.append(time.perf_counter() - t0)
        return float(np.median(times)), byts

    blob_s, blob_bytes = run(_blob_round, stacked, weights, template)
    plane_s, plane_bytes = run(_plane_round, stacked, weights, spec, store)

    # correctness guard: both transports must agree on the aggregate
    a, _ = _blob_round(stacked, weights, template)
    b, _ = _plane_round(stacked, weights, spec, store)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-5)

    return {"K": K, "N": N, "blob_s": blob_s, "plane_s": plane_s,
            "speedup": blob_s / plane_s if plane_s > 0 else float("inf"),
            "blob_host_bytes": int(blob_bytes),
            "plane_host_bytes": int(plane_bytes)}


def run(smoke: bool = False, json_path: str = "") -> list[dict]:
    cells = ([(10, 10_000)] if smoke
             else [(10, 10_000), (100, 10_000),
                   (10, 1_000_000), (100, 1_000_000)])
    iters = 3 if smoke else 5
    results = []
    for K, N in cells:
        cell = bench_cell(K, N, iters)
        results.append(cell)
        print(f"round/K{K}_N{N}/blob,{cell['blob_s'] * 1e6:.0f},"
              f"bytes={cell['blob_host_bytes']}")
        print(f"round/K{K}_N{N}/plane,{cell['plane_s'] * 1e6:.0f},"
              f"bytes={cell['plane_host_bytes']} "
              f"speedup={cell['speedup']:.2f}x")
    out = {"bench": "round_update_plane", "smoke": smoke,
           "backend": jax.default_backend(), "cells": results}
    path = json_path or os.path.join(_ROOT, "BENCH_round.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    return results


# ---------------------------------------------------- scheduler dispatch


def _bench_eventloop(n_events: int) -> dict:
    """Raw EventLoop throughput: plain schedule/pop, and a hedge-style
    churn where 60% of scheduled events are cancelled mid-flight (the
    tombstone-compaction path — the heap must stay bounded by the live
    count, not the cancellation history)."""
    from repro.faas.events import EventLoop

    loop = EventLoop()
    t0 = time.perf_counter()
    for i in range(n_events):
        loop.schedule(float(i % 97), lambda: None)
    loop.run_all()
    plain_s = time.perf_counter() - t0

    loop = EventLoop()
    t0 = time.perf_counter()
    evs = []
    peak_heap = 0
    for i in range(n_events):
        evs.append(loop.schedule(float(i % 97) + 1.0, lambda: None))
        if i % 5 == 4:                      # cancel 3 of every 5, distinct
            for j in (i, i - 1, i - 2):
                loop.cancel(evs[j])
        if i % 1024 == 0:
            peak_heap = max(peak_heap, len(loop._heap))
    peak_heap = max(peak_heap, len(loop._heap))
    loop.run_all()
    churn_s = time.perf_counter() - t0

    return {"n_events": n_events,
            "plain_events_per_s": round(n_events / plain_s),
            "cancel_churn_events_per_s": round(n_events / churn_s),
            "churn_peak_heap": peak_heap}


def _bench_protocol_overhead(sched, n: int) -> float:
    """Pure protocol cost: µs per dispatched no-op event (adapter ignores
    ClientJoined) — event construction + policy dispatch + view plumbing,
    no training, no platform work."""
    from repro.core.protocol import ClientJoined

    t0 = time.perf_counter()
    for i in range(n):
        sched._dispatch(ClientJoined(t=sched.loop.now, client_id=-1))
    return 1e6 * (time.perf_counter() - t0) / n


def _bench_dispatch(model, data, strategy: str, rounds: int,
                    **cfg_overrides) -> dict:
    """End-to-end reactive run on a tiny straggler-heavy FL setup (shared
    pre-warmed model, so compile time stays out of the comparison):
    events dispatched per wall-second including the real JAX training the
    events trigger, plus the pure protocol overhead per event."""
    from repro.core.scheduler import Scheduler
    from repro.core.services import FLConfig
    from repro.faas.hardware import HARDWARE_PROFILES

    n = len(data.n)
    fleet = [HARDWARE_PROFILES["cpu1"]] * (n - 2) + \
            [HARDWARE_PROFILES["gpu"]] * 2
    cfg = FLConfig(n_clients=n, clients_per_round=4, rounds=rounds,
                   local_epochs=1, batch_size=5, base_step_time=0.8,
                   concurrency_ratio=0.5, cold_start_s=120.0, keep_warm=30.0,
                   hedge_fraction=1.0, seed=0, strategy=strategy,
                   **cfg_overrides)
    sched = Scheduler(cfg, model, data, fleet)
    t0 = time.perf_counter()
    m = sched.run()
    wall = time.perf_counter() - t0
    overhead_us = _bench_protocol_overhead(sched, 2000)
    return {"strategy": strategy, "rounds": m["rounds"], "wall_s": round(wall, 3),
            "n_events": m["n_events"],
            "events_per_s": round(m["n_events"] / wall, 1),
            "protocol_overhead_us_per_event": round(overhead_us, 2),
            "sim_time_s": round(m["total_time"], 1),
            "n_hedges": m["n_hedges"], "n_hedge_wins": m["n_hedge_wins"],
            "n_invocations": m["n_invocations"],
            "data_plane": m["data_plane"],
            "data_host_bytes": m["data_host_bytes"]}


def run_scheduler(smoke: bool = False, json_path: str = "") -> dict:
    from repro.data.synthetic import make_federated_dataset
    from repro.models.proxy_models import build_bench_model

    n_events = 20_000 if smoke else 200_000
    rounds = 3 if smoke else 8
    ev = _bench_eventloop(n_events)
    data = make_federated_dataset("mnist", n_clients=8, scale=0.06, seed=0)
    model = build_bench_model("mnist")
    _bench_dispatch(model, data, "apodotiko", 1)   # compile warmup, discarded
    plain = _bench_dispatch(model, data, "apodotiko", rounds)
    hedge = _bench_dispatch(model, data, "apodotiko-hedge", rounds)
    overhead = {
        # wall delta of the hedging policy (can be negative at smoke
        # scale — recompile noise swamps the µs-level dispatch cost)...
        "wall_delta_s": round(hedge["wall_s"] - plain["wall_s"], 3),
        "wall_delta_per_hedge_us": (round(1e6 * (hedge["wall_s"]
                                                 - plain["wall_s"])
                                          / hedge["n_hedges"])
                                    if hedge["n_hedges"] else None),
        # ...bought this much simulated time (the point of hedging)
        "sim_speedup": (round(plain["sim_time_s"] / hedge["sim_time_s"], 2)
                        if hedge["sim_time_s"] else None),
    }
    print(f"scheduler/eventloop,{1e6 / ev['plain_events_per_s']:.2f},"
          f"churn={ev['cancel_churn_events_per_s']}ev/s "
          f"peak_heap={ev['churn_peak_heap']}")
    for d in (plain, hedge):
        print(f"scheduler/dispatch/{d['strategy']},"
              f"{d['protocol_overhead_us_per_event']},"
              f"end_to_end={d['events_per_s']}ev/s n_events={d['n_events']}")
    print(f"scheduler/hedge_overhead,"
          f"{overhead['wall_delta_per_hedge_us'] or 0},"
          f"sim_speedup={overhead['sim_speedup']}x "
          f"hedges={hedge['n_hedges']} wins={hedge['n_hedge_wins']}")
    out = {"bench": "scheduler_dispatch", "smoke": smoke,
           "eventloop": ev, "dispatch": [plain, hedge],
           "hedge_overhead": overhead}
    path = json_path or os.path.join(_ROOT, "BENCH_scheduler.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    return out


# ------------------------------------------------------------- data plane


def _synthetic_fed(M: int, n_max: int, seed: int = 0):
    """A FederatedDataset with exact shapes (proxy-model 8x8x1 features)
    so each cell's cohort-input volume is controlled precisely."""
    from repro.data.synthetic import FederatedDataset

    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (M, n_max, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, (M, n_max)).astype(np.int32)
    n = np.full((M,), n_max, np.int64)
    ex = X[0, :64].copy()
    ey = y[0, :64].copy()
    return FederatedDataset(X, y, n, ex, ey, name="bench")


class _BenchMLP:
    """Minimal real model (64 -> 16 -> 10 MLP, classifier loss surface):
    keeps the full-dispatch measurement on the real trainer path without
    XLA-CPU conv cost swamping the transport difference."""

    input_shape = (8, 8, 1)

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        p = {"w1": jax.random.normal(k1, (64, 16)) * 0.1,
             "b1": jnp.zeros((16,)),
             "w2": jax.random.normal(k2, (16, 10)) * 0.1,
             "b2": jnp.zeros((10,))}
        return p, None

    def predict(self, p, x):
        h = jax.nn.relu(x.reshape(x.shape[0], -1) @ p["w1"] + p["b1"])
        return h @ p["w2"] + p["b2"]

    def loss(self, p, batch):
        from repro.models.common import softmax_cross_entropy
        logits = self.predict(p, batch["x"])
        return softmax_cross_entropy(logits, batch["y"]), logits


def _dataplane_cell(K: int, cohort_floats: int, iters: int) -> dict:
    """One cell: K clients whose cohort training input totals
    ~``cohort_floats`` fp32 elements.

    Two measurements per plane, mirroring how the update-plane cells
    isolate the agg+transfer component:

      * **input path** (the headline, ``speedup``): what each plane does
        to get the cohort's training data in front of the jitted cohort
        fn — host: fancy-index ``X[sel]`` + pad-concat to the cohort
        bucket + device upload; device: upload of the ``[Kp] int32``
        index vector (the dataset is already resident). This is the
        component the data plane exists to remove.
      * **full dispatch** (``train_speedup``): the same comparison
        through the real ``CohortTrainer`` end to end, minimal local
        work (steps=1, tiny MLP, batch 2). On CPU the "upload" is a
        memcpy, so this improves modestly; on PCIe-attached accelerators
        the input path is the dispatch tail that the device plane
        deletes."""
    from repro.core.client import CohortTrainer, _bucket
    from repro.core.data_plane import DatasetStore

    feat = 8 * 8
    n_max = max(cohort_floats // (K * feat), 2)
    data = _synthetic_fed(2 * K, n_max)
    store = DatasetStore(data)
    model = _BenchMLP()
    params = model.init(jax.random.PRNGKey(0))[0]
    sel = np.arange(0, 2 * K, 2)                # K clients, strided gather
    n_i = data.n[sel]
    steps = np.ones(K, np.int64)

    def make_trainer():
        return CohortTrainer(model, optimizer="adam", lr=1e-3, batch_size=2)

    trainer = make_trainer()
    Kp = _bucket(K, trainer.cohort_floor)

    # -- input path only ---------------------------------------------------
    def host_input():
        X, y, n = data.cohort(sel)
        if Kp != K:
            padt = lambda a: np.concatenate(
                [a, np.repeat(a[-1:], Kp - K, axis=0)])
            X, y = padt(X), padt(y)
        up = (jnp.asarray(X), jnp.asarray(y))
        jax.block_until_ready(up)
        return X.nbytes + y.nbytes

    def device_input():
        s = sel
        if Kp != K:
            s = np.concatenate([s, np.repeat(s[-1:], Kp - K)])
        jax.block_until_ready(jnp.asarray(s))
        return 0

    def timed(fn):
        fn()                                    # warmup
        times, byts = [], 0
        for _ in range(iters):
            t0 = time.perf_counter()
            byts = fn()
            times.append(time.perf_counter() - t0)
        return float(np.median(times)), byts

    host_in_s, host_in_bytes = timed(host_input)
    dev_in_s, _ = timed(device_input)

    # -- full dispatch through the trainer ---------------------------------
    def host_dispatch():
        X, y, n = data.cohort(sel)
        trainer.train_cohort(params, X, y, n, steps)
        return 0

    def device_dispatch():
        trainer.train_cohort_indexed(params, store, sel, n_i, steps)
        return 0

    b0 = trainer.data_h2d_bytes
    host_s, _ = timed(host_dispatch)
    host_bytes = (trainer.data_h2d_bytes - b0) // (iters + 1)
    b0 = trainer.data_h2d_bytes
    dev_s, _ = timed(device_dispatch)
    dev_bytes = (trainer.data_h2d_bytes - b0) // (iters + 1)

    # correctness guard: identical trained params from identical RNG state
    trainer_a, trainer_b = make_trainer(), make_trainer()
    X, y, n = data.cohort(sel)
    out_a = trainer_a.train_cohort(params, X, y, n, steps)[0]
    out_b = trainer_b.train_cohort_indexed(params, store, sel, n_i, steps)[0]
    for a, b in zip(jax.tree.leaves(out_a), jax.tree.leaves(out_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    return {"K": K, "cohort_floats": K * n_max * feat, "n_max": n_max,
            "host_input_s": host_in_s, "device_input_s": dev_in_s,
            "speedup": (host_in_s / dev_in_s if dev_in_s > 0
                        else float("inf")),
            "host_train_s": host_s, "device_train_s": dev_s,
            "train_speedup": host_s / dev_s if dev_s > 0 else float("inf"),
            "host_h2d_bytes": int(host_bytes),
            "device_h2d_bytes": int(dev_bytes),
            "host_input_bytes": int(host_in_bytes),
            "resident_bytes": store.resident_bytes}


def run_dataplane(smoke: bool = False, json_path: str = "") -> dict:
    from repro.data.synthetic import make_federated_dataset
    from repro.models.proxy_models import build_bench_model

    cells_spec = ([(4, 50_000)] if smoke
                  else [(10, 100_000), (10, 1_000_000),
                        (100, 1_000_000), (100, 4_000_000)])
    iters = 3 if smoke else 7
    cells = []
    for K, floats in cells_spec:
        cell = _dataplane_cell(K, floats, iters)
        cells.append(cell)
        tag = f"dataplane/K{K}_F{cell['cohort_floats']}"
        print(f"{tag}/input/host,{cell['host_input_s'] * 1e6:.0f},"
              f"bytes={cell['host_input_bytes']}")
        print(f"{tag}/input/device,{cell['device_input_s'] * 1e6:.0f},"
              f"bytes=0 speedup={cell['speedup']:.2f}x")
        print(f"{tag}/train,{cell['device_train_s'] * 1e6:.0f},"
              f"host={cell['host_train_s'] * 1e6:.0f}us "
              f"train_speedup={cell['train_speedup']:.2f}x "
              f"h2d={cell['host_h2d_bytes']}->{cell['device_h2d_bytes']}")

    # end-to-end: the same scheduler microbench on both planes — dispatch
    # rate plus the run-level H2D accounting the CI gate checks (full-size
    # client shards outside smoke, so the input path is a real fraction of
    # each dispatch)
    rounds = 2 if smoke else 6
    data = make_federated_dataset("mnist", n_clients=8,
                                  scale=0.06 if smoke else 1.0, seed=0)
    model = build_bench_model("mnist")
    for dp in ("device", "host"):       # compile warmup, discarded
        _bench_dispatch(model, data, "apodotiko", 1, data_plane=dp)
    runs = [_bench_dispatch(model, data, "apodotiko", rounds, data_plane=dp)
            for dp in ("device", "host")]
    for d in runs:
        print(f"dataplane/e2e/{d['data_plane']},{d['wall_s'] * 1e6:.0f},"
              f"events_per_s={d['events_per_s']} "
              f"data_host_bytes={d['data_host_bytes']}")

    out = {"bench": "data_plane", "smoke": smoke,
           "backend": jax.default_backend(), "cells": cells,
           "end_to_end": runs}
    path = json_path or os.path.join(_ROOT, "BENCH_dataplane.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")

    # CI gate: the device plane must move ZERO training-input bytes
    leaked = [c["device_h2d_bytes"] for c in cells if c["device_h2d_bytes"]]
    e2e_dev = next(r for r in runs if r["data_plane"] == "device")
    if leaked or e2e_dev["data_host_bytes"]:
        print(f"FAIL: device data plane moved host bytes "
              f"(cells={leaked}, e2e={e2e_dev['data_host_bytes']})")
        sys.exit(1)
    return out


# ----------------------------------------------------------- control plane


def _control_states(M: int, seed: int = 0, history: int = 3,
                    planes=("object", "columnar")):
    """Identical fleet state on both control planes: M clients, everyone
    invoked `history` times with shared random durations (so selection
    exercises the scored path, not the uninvoked bootstrap)."""
    from repro.core.database import ClientRecord, Database

    rng = np.random.default_rng(seed)
    card = rng.integers(50, 500, M).astype(np.int64)
    durs = rng.uniform(1.0, 60.0, (M, history))

    col = None
    if "columnar" in planes:
        col = Database(control_plane="columnar")
        col.fleet.add_batch(np.arange(M), card, 10, 5)
        col.fleet.bulk_history(durs)

    obj = None
    if "object" in planes and M <= 200_000:
        # a million ClientRecords is the wall itself
        obj = Database(control_plane="object")
        for cid in range(M):
            rec = ClientRecord(client_id=cid, hardware="cpu1",
                               data_cardinality=int(card[cid]),
                               batch_size=10, local_epochs=5,
                               n_invocations=history,
                               durations=[float(d) for d in durs[cid]])
            obj.register_client(rec)
    return obj, col


def _controlplane_cell(M: int, K: int, iters: int) -> dict:
    """Each timed mode gets its own freshly built, identically seeded
    fleet state and its own identically seeded draw stream. Selection
    mutates the state it times (booster promotions), so sharing one state
    across modes made later sections depend on how many iterations the
    earlier ones ran — rebuilding per mode keeps every section comparable
    run-to-run and section-to-section."""
    from repro.core.selection import select_clients

    def timed(fn):
        fn(np.random.default_rng(99))               # warmup/compile
        times = []
        for i in range(iters):
            r = np.random.default_rng(1000 + i)
            t0 = time.perf_counter()
            fn(r)
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    col = _control_states(M, planes=("columnar",))[1]
    col_s = timed(lambda r: select_clients(col, K, r))
    col = _control_states(M, planes=("columnar",))[1]
    topk_s = timed(lambda r: col.fleet.select_topk(K, 1.2))
    obj = _control_states(M, planes=("object",))[0]
    obj_s = timed(lambda r: select_clients(obj, K, r)) if obj else None
    return {"M": M, "K": K, "object_s": obj_s, "columnar_s": col_s,
            "topk_s": topk_s,
            "speedup": (obj_s / col_s if obj_s else None),
            "topk_speedup": (obj_s / topk_s if obj_s else None)}


def _controlplane_gate(M: int = 1000, K: int = 64, rounds: int = 5) -> bool:
    """Object and columnar selection must stay bit-identical over evolving
    state: shared RNG stream, same completions folded back in each step."""
    from repro.core.selection import select_clients

    obj, col = _control_states(M)
    r_obj, r_col = (np.random.default_rng(7), np.random.default_rng(7))
    for t in range(rounds):
        s_obj = select_clients(obj, K, r_obj)
        s_col = select_clients(col, K, r_col)
        if s_obj != s_col:
            return False
        for db in (obj, col):
            for j, cid in enumerate(s_obj):
                db.mark_running(cid, t)
                db.mark_complete(cid, 1.0 + ((cid * 7 + j + t) % 50))
    return True


def run_controlplane(smoke: bool = False, json_path: str = "") -> dict:
    cells_spec = ([(1_000, 64)] if smoke
                  else [(1_000, 100), (10_000, 100),
                        (100_000, 100), (1_000_000, 100)])
    iters = 3 if smoke else 5
    cells = []
    for M, K in cells_spec:
        cell = _controlplane_cell(M, K, iters)
        cells.append(cell)
        obj_us = (f"{cell['object_s'] * 1e6:.0f}" if cell["object_s"]
                  else "skipped")
        sp = (f"{cell['speedup']:.1f}x" if cell["speedup"] else "n/a")
        tsp = (f"{cell['topk_speedup']:.1f}x" if cell["topk_speedup"]
               else "n/a")
        print(f"controlplane/M{M}/object,{obj_us},")
        print(f"controlplane/M{M}/columnar,{cell['columnar_s'] * 1e6:.0f},"
              f"speedup={sp}")
        print(f"controlplane/M{M}/topk,{cell['topk_s'] * 1e6:.0f},"
              f"topk_speedup={tsp}")
    identical = _controlplane_gate()
    out = {"bench": "control_plane", "smoke": smoke,
           "backend": jax.default_backend(), "cells": cells,
           "selection_identical": identical}
    path = json_path or os.path.join(_ROOT, "BENCH_controlplane.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    if not identical:
        print("FAIL: columnar selection diverged from the object oracle")
        sys.exit(1)
    return out


# --------------------------------------------------------------- megastep


def _megastep_engine(mode: str, rounds: int, model, data):
    """A run the fused path provably engages on: zero-variability fleet,
    deterministic top-k selection, CR gate = full cohort, no eval or
    checkpoint barriers, instances never cool."""
    from repro.core.scheduler import Scheduler
    from repro.core.services import FLConfig
    from repro.faas.hardware import HardwareProfile

    n = len(data.n)
    fleet = [HardwareProfile(f"det{i % 3}", speed=(1.0, 1.45, 1.9)[i % 3],
                             vcpus=1.0, mem_gib=2.0, variability=0.0)
             for i in range(n)]
    cfg = FLConfig(n_clients=n, clients_per_round=4, rounds=rounds,
                   local_epochs=1, batch_size=5, base_step_time=0.5,
                   strategy="apodotiko-topk", concurrency_ratio=1.0,
                   eval_every=0, keep_warm=1e9, seed=0, megastep=mode)
    return Scheduler(cfg, model, data, fleet)


def _run_trace(engine):
    hist = [(l.round, l.t_start, l.t_end, l.accuracy, l.n_aggregated,
             l.n_stale) for l in engine.history]
    inv = [(r.client_id, r.round, r.t_invoked, r.cold, r.duration, r.failed)
           for r in engine.platform.invocations]
    return hist, inv


def run_megastep(smoke: bool = False, json_path: str = "") -> dict:
    from repro.data.synthetic import make_federated_dataset
    from repro.models.proxy_models import build_bench_model

    B = 3                              # ceil(10/4) stepwise bootstrap rounds
    R = 6 if smoke else 32             # quiescent rounds per timed segment
    data = make_federated_dataset("mnist", n_clients=10, scale=0.05, seed=0)
    model = build_bench_model("mnist")

    def segment(mode):
        """Bootstrap, then two warmup segments of R rounds (the first
        compiles the scan on the fused path, the second settles runtime
        warmup), then a timed warm segment of R more."""
        eng = _megastep_engine(mode, B, model, data)
        eng.run()
        for _ in range(2):
            eng.cfg.rounds += R
            eng.run()
        ev0, r0 = eng.n_events, eng.db.round
        eng.cfg.rounds += R
        t0 = time.perf_counter()
        m = eng.run()
        wall = time.perf_counter() - t0
        n_rounds = eng.db.round - r0
        return m, {"mode": mode, "wall_s": round(wall, 4),
                   "rounds_timed": n_rounds,
                   "wall_us_per_round": round(1e6 * wall / n_rounds, 1),
                   "events_per_round": round(
                       (eng.n_events - ev0) / n_rounds, 3)}

    m_f, fused = segment("fused")
    _, step = segment("stepwise")
    fused["megastep_scans"] = m_f["megastep_scans"]
    fused["megastep_rounds"] = m_f["megastep_rounds"]
    share = ((step["wall_s"] - fused["wall_s"]) / step["wall_s"]
             if step["wall_s"] > 0 else 0.0)

    # divergence gate: fresh full runs on both modes, compared bitwise
    engines = {}
    for mode in ("stepwise", "fused"):
        eng = _megastep_engine(mode, B + R, model, data)
        engines[mode] = (eng, eng.run())
    s_eng, s_m = engines["stepwise"]
    f_eng, f_m = engines["fused"]
    identical = (
        _run_trace(s_eng) == _run_trace(f_eng)
        and s_m["total_time"] == f_m["total_time"]
        and all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree.leaves(s_eng.params),
                                jax.tree.leaves(f_eng.params))))

    print(f"megastep/stepwise,{step['wall_us_per_round']:.0f},"
          f"events_per_round={step['events_per_round']}")
    print(f"megastep/fused,{fused['wall_us_per_round']:.0f},"
          f"events_per_round={fused['events_per_round']} "
          f"scans={fused['megastep_scans']} "
          f"rounds={fused['megastep_rounds']}")
    print(f"megastep/python_overhead_share,{share:.3f},"
          f"speedup={step['wall_s'] / fused['wall_s']:.2f}x "
          f"bit_identical={identical}")
    out = {"bench": "megastep", "smoke": smoke,
           "backend": jax.default_backend(),
           "bootstrap_rounds": B, "rounds_per_segment": R,
           "stepwise": step, "fused": fused,
           "python_overhead_share": round(share, 4),
           "python_dispatches_per_quiescent_round":
               fused["events_per_round"],
           "bit_identical": identical}
    path = json_path or os.path.join(_ROOT, "BENCH_megastep.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    if not identical:
        print("FAIL: fused megastep diverged from the stepwise oracle")
        sys.exit(1)
    if fused["events_per_round"] != 0.0:
        print("FAIL: fused path dispatched Python events during "
              f"quiescent rounds ({fused['events_per_round']}/round)")
        sys.exit(1)
    return out


# --------------------------------------------------------------- sharding


# One worker process per mesh cell: XLA's host-device count is fixed at
# process startup, so every device count needs its own interpreter with
# XLA_FLAGS set before jax imports (the same constraint the multi-device
# tests live under — tests/test_mesh_plane.py, tests/test_sharding.py).
_SHARDING_WORKER = r"""
import os, sys, json, time, hashlib
n_dev = int(os.environ["REPRO_SH_DEVICES"])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
os.environ.pop("REPRO_MESH", None)
spec = os.environ.get("REPRO_SH_MESH", "")      # "" = config default (auto)
import numpy as np
import jax

from repro.core.scheduler import Scheduler
from repro.core.services import FLConfig
from repro.data.synthetic import make_federated_dataset
from repro.faas.hardware import HardwareProfile
from repro.models.proxy_models import build_bench_model
from repro.sharding import flmesh

K = int(os.environ["REPRO_SH_K"])
R = int(os.environ["REPRO_SH_ROUNDS"])
n_clients = max(10, 3 * K)
data = make_federated_dataset("mnist", n_clients=n_clients, scale=0.05,
                              seed=0)
model = build_bench_model("mnist")
fleet = [HardwareProfile(f"det{i % 3}", speed=(1.0, 1.45, 1.9)[i % 3],
                         vcpus=1.0, mem_gib=2.0, variability=0.0)
         for i in range(n_clients)]
kw = dict(n_clients=n_clients, clients_per_round=K, rounds=R,
          local_epochs=1, batch_size=5, base_step_time=0.5,
          strategy="apodotiko-topk", concurrency_ratio=1.0, eval_every=0,
          keep_warm=1e9, seed=0)
if spec:
    kw["mesh"] = spec
eng = Scheduler(FLConfig(**kw), model, data, fleet)
eng.run()                                       # bootstrap + compile
eng.cfg.rounds += R                             # settle runtime warmup
eng.run()
r0 = eng.db.round
eng.cfg.rounds += R                             # timed warm segment
t0 = time.perf_counter()
eng.run()
wall = time.perf_counter() - t0
n_rounds = eng.db.round - r0

flat = np.concatenate([np.asarray(x).ravel()
                       for x in jax.tree.leaves(eng.params)])
buf = eng.store.buffer
shard_bytes = [s.data.nbytes for s in buf.addressable_shards]
mesh = flmesh.build_fl_mesh(flmesh.resolve_mesh(kw.get("mesh", "auto")))
d_ax, m_ax = flmesh.mesh_axes(mesh)
print(json.dumps({
    "mesh": spec or "auto", "devices": n_dev, "K": K,
    "data_axis": d_ax, "model_axis": m_ax,
    "rounds_timed": int(n_rounds), "wall_s": round(wall, 4),
    "rounds_per_s": round(n_rounds / wall, 3),
    "clients_per_s": round(n_rounds * K / wall, 3),
    "store_total_bytes": int(buf.nbytes),
    "store_device_bytes": int(max(shard_bytes)),
    "n_shards": len(shard_bytes),
    "params_sha": hashlib.sha256(flat.tobytes()).hexdigest(),
}))
"""


def _sharding_cell(spec: str, n_dev: int, K: int, rounds: int,
                   workdir: str) -> dict:
    import subprocess

    path = os.path.join(workdir, f"sharding_{spec or 'default'}.py")
    with open(path, "w") as f:
        f.write(_SHARDING_WORKER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_MESH", None)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    # forced host devices exist only on the CPU backend; on a chip host the
    # parent already holds the accelerator, which a child could not open
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_SH_MESH"] = spec
    env["REPRO_SH_DEVICES"] = str(n_dev)
    env["REPRO_SH_K"] = str(K)
    env["REPRO_SH_ROUNDS"] = str(rounds)
    out = subprocess.run([sys.executable, path], capture_output=True,
                         text=True, env=env, timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(f"sharding worker {spec or 'default'!r} failed:\n"
                           + out.stderr[-3000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_sharding(smoke: bool = False, json_path: str = "") -> dict:
    """Sharded-plane bench (DESIGN.md §15), a CPU-only layout check: every
    cell runs in a child process on forced host devices
    (``JAX_PLATFORMS=cpu``). The mesh path on real chips is
    ``python chip_smoke.py --chips 4``. A weak-scaling curve over the
    ("data", "model") mesh — cohort size K grows with the data axis, so
    ideal scaling holds wall time per round flat while client-updates/s
    grows with the device count. On a host with fewer cores than forced
    devices the wall numbers measure oversubscription, not scaling, so
    the wall-clock gate only arms when ``os.cpu_count() >= 8``; the
    structural metrics (bottleneck-device update-store bytes, per-device
    cohort lanes) hold on any host and are always gated. The 1x1
    bitwise-identity gate — mesh resolution alone must not perturb a run
    — always arms. Lands in ``BENCH_sharding.json``."""
    import tempfile

    rounds = 3 if smoke else 5
    cells_spec = ([("", 1, 4), ("1x1", 1, 4), ("2x1", 2, 8)] if smoke
                  else [("", 1, 4), ("1x1", 1, 4), ("2x1", 2, 8),
                        ("2x2", 4, 8), ("2x4", 8, 16)])
    cells = []
    with tempfile.TemporaryDirectory(prefix="bench_sharding_") as work:
        for spec, n_dev, K in cells_spec:
            cell = _sharding_cell(spec, n_dev, K, rounds, work)
            cells.append(cell)
            print(f"sharding/{cell['mesh']}/d{cell['data_axis']}"
                  f"m{cell['model_axis']},"
                  f"{1e6 / cell['rounds_per_s']:.0f},"
                  f"K={cell['K']} clients_per_s={cell['clients_per_s']} "
                  f"device_bytes={cell['store_device_bytes']}"
                  f"/{cell['store_total_bytes']}")

    base = next(c for c in cells if c["mesh"] == "1x1")
    default = next(c for c in cells if c["mesh"] == "auto")
    identity_ok = (default["params_sha"] == base["params_sha"])

    # structural gates (host-independent): the buffer actually splits
    # into d*m equal tiles, and the bottleneck device holds 1/(d*m)
    # of the update-store bytes
    structural_ok = True
    for c in cells:
        n_tiles = c["data_axis"] * c["model_axis"]
        structural_ok &= (c["n_shards"] == n_tiles)
        structural_ok &= (c["store_device_bytes"] * n_tiles
                          == c["store_total_bytes"])

    # weak-scaled throughput relative to the 1x1 oracle
    for c in cells:
        c["throughput_vs_1x1"] = round(c["clients_per_s"]
                                       / base["clients_per_s"], 3)
    biggest = max(cells, key=lambda c: c["devices"])
    cpu_count = os.cpu_count() or 1
    wall_gate_armed = cpu_count >= 8 and biggest["devices"] >= 8
    wall_ok = (biggest["throughput_vs_1x1"] > 1.5 if wall_gate_armed
               else None)
    print(f"sharding/identity,0,bitwise={identity_ok} "
          f"structural={structural_ok}")
    print(f"sharding/scaling,{biggest['throughput_vs_1x1']},"
          f"devices={biggest['devices']} cpu_count={cpu_count} "
          f"wall_gate={'armed' if wall_gate_armed else 'skipped'}")

    out = {"bench": "sharding", "smoke": smoke,
           "backend": "cpu-subprocess", "cpu_count": cpu_count,
           "rounds_per_segment": rounds, "cells": cells,
           "identity_1x1_bitwise": identity_ok,
           "structural_ok": structural_ok,
           "wall_gate": ("armed" if wall_gate_armed else
                         f"skipped (cpu_count={cpu_count})"),
           "wall_scaling_ok": wall_ok}
    path = json_path or os.path.join(_ROOT, "BENCH_sharding.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    if not identity_ok:
        print("FAIL: mesh='1x1' diverged bitwise from the default path")
        sys.exit(1)
    if not structural_ok:
        print("FAIL: update-store buffer did not split into equal "
              "per-device tiles")
        sys.exit(1)
    if wall_gate_armed and not wall_ok:
        print(f"FAIL: weak-scaled throughput at {biggest['devices']} "
              f"devices is {biggest['throughput_vs_1x1']}x the 1x1 "
              "oracle (< 1.5x gate)")
        sys.exit(1)
    return out


# ----------------------------------------------------------------- faults


def _fault_engine(engine_cls, model, data, rounds: int, **cfg_overrides):
    """One seeded FL run under a fault profile (paper hardware mix, the
    same tiny setup as the scheduler dispatch bench)."""
    from repro.core.services import FLConfig
    from repro.faas.hardware import paper_fleet

    n = len(data.n)
    cfg_overrides.setdefault("strategy", "apodotiko")
    cfg = FLConfig(n_clients=n, clients_per_round=4, rounds=rounds,
                   local_epochs=1, batch_size=5, base_step_time=0.8,
                   concurrency_ratio=0.5, seed=0,
                   **cfg_overrides)
    eng = engine_cls(cfg, model, data, list(paper_fleet(n)))
    t0 = time.perf_counter()
    m = eng.run()
    wall = time.perf_counter() - t0
    return eng, m, wall


def _fault_trace(eng):
    """The chaos-trace observables (tests/chaos_harness.py): round history
    plus per-invocation fault attribution."""
    hist = [(l.round, l.t_start, l.t_end, l.accuracy, l.n_aggregated,
             l.n_stale) for l in eng.history]
    inv = [(r.client_id, r.round, r.t_invoked, r.cold, r.duration, r.failed,
            r.failed_phase, r.lost, r.timed_out, r.cancelled)
           for r in eng.platform.invocations]
    return hist, inv


def run_faults(smoke: bool = False, json_path: str = "") -> dict:
    """Fault-injection overhead + recovery benefit (DESIGN.md §12): the
    same seeded run clean, under the crash-heavy chaos profile, and under
    the same profile with the retry/timeout/quarantine recovery layer
    armed. Reports failure/retry counts, simulated time, and the recovery
    layer's wall-clock overhead. The CI gate replays a seeded schedule
    through both engines and exits nonzero on any trace divergence."""
    from repro.core.controller import Controller
    from repro.core.scheduler import Scheduler
    from repro.data.synthetic import make_federated_dataset
    from repro.models.proxy_models import build_bench_model

    rounds = 3 if smoke else 8
    data = make_federated_dataset("mnist", n_clients=8, scale=0.06, seed=0)
    model = build_bench_model("mnist")
    _fault_engine(Scheduler, model, data, 1)    # compile warmup, discarded

    recovery = dict(invocation_timeout=300.0, retry_budget=8,
                    quarantine_threshold=3)
    modes = [("clean", "", {}),
             ("crash-heavy", "crash-heavy", {}),
             ("crash-heavy+recovery", "crash-heavy", recovery)]
    runs = []
    for label, profile, rec in modes:
        _, m, wall = _fault_engine(Scheduler, model, data, rounds,
                                   fault_profile=profile, **rec)
        d = {"label": label, "fault_profile": profile,
             "recovery": bool(rec), "rounds": m["rounds"],
             "wall_s": round(wall, 3),
             "sim_time_s": round(m["total_time"], 1),
             "final_acc": round(m.get("final_accuracy", 0.0), 4),
             "n_invocations": m["n_invocations"],
             "n_failures": m["n_failures"], "n_retries": m["n_retries"],
             "n_timeouts": m["n_timeouts"],
             "n_quarantined": m["n_quarantined"],
             "retry_latency_s": round(m["retry_latency_s"], 1),
             "failures_by_phase": m["failures_by_phase"]}
        runs.append(d)
        print(f"faults/{label},{wall * 1e6:.0f},"
              f"sim={d['sim_time_s']}s failures={d['n_failures']} "
              f"retries={d['n_retries']} quarantined={d['n_quarantined']}")

    clean, chaos, recov = runs
    overhead = {
        # what the chaos profile costs an unprotected run
        "chaos_sim_slowdown": (round(chaos["sim_time_s"]
                                     / clean["sim_time_s"], 3)
                               if clean["sim_time_s"] else None),
        # what the recovery layer claws back (or costs) under chaos
        "recovery_sim_ratio": (round(recov["sim_time_s"]
                                     / chaos["sim_time_s"], 3)
                               if chaos["sim_time_s"] else None),
        "recovery_wall_overhead_s": round(recov["wall_s"]
                                          - chaos["wall_s"], 3),
    }
    print(f"faults/recovery_overhead,{overhead['recovery_wall_overhead_s']},"
          f"chaos_slowdown={overhead['chaos_sim_slowdown']}x "
          f"recovery_ratio={overhead['recovery_sim_ratio']}")

    # CI gate: a seeded schedule must replay bit-identically on both
    # engines (recovery off — it is scheduler-only by design)
    gate_profiles = (("crash-heavy",) if smoke
                     else ("crash-heavy", "lossy-network", "outage-window"))
    gate = {}
    for profile in gate_profiles:
        legacy = _fault_engine(Controller, model, data, rounds,
                               fault_profile=profile)[0]
        sched = _fault_engine(Scheduler, model, data, rounds,
                              fault_profile=profile)[0]
        gate[profile] = _fault_trace(legacy) == _fault_trace(sched)
        print(f"faults/gate/{profile},0,identical={gate[profile]}")

    out = {"bench": "faults", "smoke": smoke,
           "backend": jax.default_backend(), "runs": runs,
           "overhead": overhead, "cross_engine_identical": gate}
    path = json_path or os.path.join(_ROOT, "BENCH_faults.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    if not all(gate.values()):
        bad = sorted(p for p, ok in gate.items() if not ok)
        print(f"FAIL: chaos trace diverged across engines for {bad}")
        sys.exit(1)
    return out


# ---------------------------------------------------------------- traffic


def _traffic_apply_bulk(schedule, db, cards):
    """Apply a compiled schedule through the traffic plane's vectorized
    path: one ``unregister_clients_bulk`` + one ``register_clients_bulk``
    per windowed segment (what ``services._apply_traffic_segment`` runs)."""
    for seg in schedule.segments:
        if len(seg.leaves):
            db.unregister_clients_bulk(seg.leaves)
        if len(seg.joins):
            db.register_clients_bulk(seg.joins, cards[seg.joins], 5, 1)


def _traffic_apply_per_event(schedule, db, cards):
    """The per-event Python path the traffic plane replaces: one
    ``ClientRecord`` built and registered (or unregistered) per
    ClientJoined/ClientLeft event — the runtime's pre-traffic membership
    API, as used by the registration loop and churn tests."""
    from repro.core.database import ClientRecord
    for t, kind, cid in schedule.events():
        if kind == "leave":
            db.unregister_client(cid)
        else:
            db.register_client(ClientRecord(
                client_id=cid, hardware="",
                data_cardinality=int(cards[cid]),
                batch_size=5, local_epochs=1))


def _traffic_seed_store(schedule, cards):
    from repro.core.database import Database
    from repro.core.fleet_store import FleetStore
    db = Database(control_plane="columnar")
    db.fleet = FleetStore(capacity=schedule.capacity)
    init = schedule.initial
    if len(init):
        db.register_clients_bulk(init, cards[init], 5, 1)
    return db


def _traffic_cell(M: int, n_flash: int, iters: int) -> dict:
    """Bulk vs per-event application of a flash-crowd + churn schedule
    over an M-client FleetStore (the ISSUE's >=10x acceptance gate)."""
    from repro.traffic import build_traffic_schedule

    spec = (f"init:0.5,window:30,horizon:900,"
            f"flash:60:{n_flash}:300,poisson:2.0:120")
    sched = build_traffic_schedule(spec, M, seed=0)
    rng = np.random.default_rng(0)
    cards = rng.integers(20, 200, M)
    n_events = sum(len(s.joins) + len(s.leaves) for s in sched.segments)

    def _time(apply_fn):
        best = float("inf")
        for _ in range(iters):
            db = _traffic_seed_store(sched, cards)
            t0 = time.perf_counter()
            apply_fn(sched, db, cards)
            best = min(best, time.perf_counter() - t0)
        return best, db.fleet

    bulk_s, fs_bulk = _time(_traffic_apply_bulk)
    ev_s, fs_ev = _time(_traffic_apply_per_event)
    identical = (
        fs_bulk._slot == fs_ev._slot
        and fs_bulk._free == fs_ev._free
        and np.array_equal(fs_bulk.active, fs_ev.active)
        and np.array_equal(fs_bulk.ids, fs_ev.ids)
        and np.array_equal(fs_bulk.seq, fs_ev.seq)
        and np.array_equal(fs_bulk.cardinality, fs_ev.cardinality))
    return {"M": M, "segments": len(sched.segments), "events": n_events,
            "n_dropped": sched.n_dropped,
            "bulk_ms": round(bulk_s * 1e3, 3),
            "per_event_ms": round(ev_s * 1e3, 3),
            "bulk_speedup": round(ev_s / bulk_s, 1) if bulk_s else None,
            "bulk_matches_per_event": identical}


def _traffic_compile_cell(M: int, rate: float) -> dict:
    """Schedule-compile (mask-generation) throughput: arrival processes
    -> windowed bulk segments, the work that replaces per-event Python."""
    from repro.traffic import build_traffic_schedule

    spec = f"init:0.5,window:60,horizon:20000,diurnal:{rate}:0.9:3600:1800"
    t0 = time.perf_counter()
    sched = build_traffic_schedule(spec, M, seed=0)
    wall = time.perf_counter() - t0
    n_events = sum(len(s.joins) + len(s.leaves) for s in sched.segments)
    return {"M": M, "arrival_rate": rate, "segments": len(sched.segments),
            "events": n_events, "compile_ms": round(wall * 1e3, 1),
            "events_per_s": (round(n_events / wall) if wall else None)}


def run_traffic(smoke: bool = False, json_path: str = "") -> dict:
    """Open-loop traffic bench (DESIGN.md §13): schedule-compile
    throughput at fleet scale, bulk vs per-event FleetStore application
    (the vectorized availability path must beat per-event Python), and
    per-strategy SLO metrics — p50/p99 round latency, cold-start rate,
    cost-per-round — under the diurnal profile. Lands in
    ``BENCH_traffic.json``; exits nonzero if the bulk path diverges from
    the per-event oracle."""
    from repro.core.scheduler import Scheduler
    from repro.data.synthetic import make_federated_dataset
    from repro.models.proxy_models import build_bench_model

    # 1) mask-gen throughput: M=1e5 (and 1e6 outside smoke)
    compile_cells = [_traffic_compile_cell(100_000, 0.5)]
    if not smoke:
        compile_cells.append(_traffic_compile_cell(1_000_000, 5.0))
    for c in compile_cells:
        print(f"traffic/compile/M={c['M']},{c['compile_ms'] * 1e3:.0f},"
              f"events={c['events']} events_per_s={c['events_per_s']}")

    # 2) bulk vs per-event application at M=1e5 (1e4-client flash crowd)
    cell = _traffic_cell(100_000, 10_000, iters=1 if smoke else 3)
    print(f"traffic/apply/M={cell['M']},{cell['bulk_ms'] * 1e3:.0f},"
          f"per_event_ms={cell['per_event_ms']} "
          f"speedup={cell['bulk_speedup']}x "
          f"identical={cell['bulk_matches_per_event']}")

    # 3) SLO table: three strategies under diurnal load. The canned
    # "diurnal" profile's 30 s window outlives a 3-round smoke run, so
    # the bench pins an early-window variant of the same shape — churn
    # must actually fire inside every strategy's run
    diurnal = "init:0.5,window:5,diurnal:0.3:0.9:120:60"
    rounds = 3 if smoke else 8
    data = make_federated_dataset("mnist", n_clients=8, scale=0.06, seed=0)
    model = build_bench_model("mnist")
    _fault_engine(Scheduler, model, data, 1)    # compile warmup, discarded
    slo_runs = []
    for strat in ("fedavg", "apodotiko", "apodotiko-hedge"):
        _, m, wall = _fault_engine(Scheduler, model, data, rounds,
                                   strategy=strat,
                                   traffic_profile=diurnal)
        d = {"strategy": strat, "traffic_profile": diurnal,
             "rounds": m["rounds"], "wall_s": round(wall, 3),
             "sim_time_s": round(m["total_time"], 1),
             "p50_round_latency_s": round(m["p50_round_latency_s"], 2),
             "p99_round_latency_s": round(m["p99_round_latency_s"], 2),
             "cold_start_rate": round(m["cold_start_rate"], 4),
             "cost_per_round_usd": round(m["cost_per_round_usd"], 6),
             "final_acc": round(m.get("final_accuracy", 0.0), 4),
             "n_traffic_joins": m["n_traffic_joins"],
             "n_traffic_leaves": m["n_traffic_leaves"]}
        slo_runs.append(d)
        print(f"traffic/slo/{strat},{wall * 1e6:.0f},"
              f"p50={d['p50_round_latency_s']}s "
              f"p99={d['p99_round_latency_s']}s "
              f"cold={d['cold_start_rate']} "
              f"cost_per_round={d['cost_per_round_usd']}")

    out = {"bench": "traffic", "smoke": smoke,
           "backend": jax.default_backend(),
           "compile": compile_cells, "apply": cell, "slo": slo_runs}
    path = json_path or os.path.join(_ROOT, "BENCH_traffic.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    if not cell["bulk_matches_per_event"]:
        print("FAIL: bulk traffic application diverged from the "
              "per-event oracle")
        sys.exit(1)
    return out


# ------------------------------------------------------------- durability


def _durable_run(model, data, rounds: int, root: str = "", **overrides):
    """One seeded scheduler run, optionally journal-armed at ``root``."""
    from repro.core.scheduler import Scheduler
    if root:
        overrides = dict(overrides, durability="journal",
                         checkpoint_dir=root)
    return _fault_engine(Scheduler, model, data, rounds, **overrides)


def run_durability(smoke: bool = False, json_path: str = "") -> dict:
    """Durability bench (DESIGN.md §14): journal overhead per round at
    each sync policy, snapshot/resume latency vs fleet size, and the
    resume-identity CI gate. Exits nonzero if round-sync journaling
    exceeds 5% wall overhead or a crashed-and-resumed run diverges."""
    import shutil
    import tempfile

    from repro.core.journal import Journal
    from repro.core.scheduler import Scheduler
    from repro.data.synthetic import make_federated_dataset
    from repro.durability import SimulatedCrash, resume_durable
    from repro.durability.snapshot import write_snapshot
    from repro.faas.hardware import paper_fleet
    from repro.models.proxy_models import build_bench_model

    rounds = 3 if smoke else 8
    iters = 2 if smoke else 4
    data = make_federated_dataset("mnist", n_clients=8, scale=0.06, seed=0)
    model = build_bench_model("mnist")
    _durable_run(model, data, 1)               # compile warmup, discarded
    work = tempfile.mkdtemp(prefix="bench_durability_")

    # 1) journal overhead per round: off vs round-fsync vs event-fsync.
    # Snapshot cadence is pushed past the horizon so the cells isolate
    # the *journal* cost (snapshot write cost is measured in part 2).
    # best-of-N wall clock per mode; identical seeded schedule throughout
    sync_runs = []
    for label, sync in (("off", ""), ("journal+round", "round"),
                        ("journal+event", "event")):
        best, metrics = float("inf"), None
        for i in range(iters):
            d = os.path.join(work, f"{label}_{i}")
            os.makedirs(d, exist_ok=True)
            root = d if sync else ""
            kw = ({"durability_sync": sync,
                   "durability_snap_every": 10 ** 9} if sync else {})
            _, m, wall = _durable_run(model, data, rounds, root=root, **kw)
            if wall < best:
                best, metrics = wall, m
        sync_runs.append({
            "label": label, "wall_s": round(best, 3),
            "wall_per_round_ms": round(best / rounds * 1e3, 2),
            "journal_records": metrics.get("journal_records", 0),
            "journal_bytes": metrics.get("journal_bytes", 0),
            "journal_fsyncs": metrics.get("journal_fsyncs", 0),
            "n_snapshots": metrics.get("n_snapshots", 0)})
    off_wall = sync_runs[0]["wall_s"]
    for r in sync_runs[1:]:
        r["overhead_pct"] = (round((r["wall_s"] - off_wall)
                                   / off_wall * 100, 2)
                             if off_wall else None)
        print(f"durability/sync/{r['label']},{r['wall_s'] * 1e6:.0f},"
              f"overhead={r['overhead_pct']}% "
              f"fsyncs={r['journal_fsyncs']} bytes={r['journal_bytes']}")
    round_sync = sync_runs[1]
    # <5% per-round gate, with a small absolute floor so sub-second runs
    # aren't failed by scheduler jitter
    overhead_ok = (round_sync["wall_s"] - off_wall
                   < max(0.05 * off_wall, 0.05))

    # 2) snapshot write + resume latency as the fleet grows
    fleet_cells = []
    for n in ((8,) if smoke else (8, 32, 96)):
        d = os.path.join(work, f"fleet_{n}")
        os.makedirs(d, exist_ok=True)
        dd = make_federated_dataset("mnist", n_clients=n, scale=0.02, seed=0)
        eng, m, _ = _durable_run(model, dd, rounds=2, root=d)
        t0 = time.perf_counter()
        write_snapshot(eng, d, seq=10_000)     # past every journaled seq
        snap_s = time.perf_counter() - t0
        records, _ = Journal.read(os.path.join(d, "journal.wal"))
        t0 = time.perf_counter()
        resume_durable(eng.cfg, model, dd,
                       list(paper_fleet(n)))   # load + install, no run
        resume_s = time.perf_counter() - t0
        snap_bytes = sum(
            os.path.getsize(os.path.join(r, f))
            for r, _, fs in os.walk(os.path.join(d, "snap_0000010000"))
            for f in fs)
        fleet_cells.append({
            "n_clients": n, "journal_records": len(records),
            "snapshot_ms": round(snap_s * 1e3, 2),
            "snapshot_bytes": snap_bytes,
            "resume_ms": round(resume_s * 1e3, 2)})
        print(f"durability/fleet/M={n},{snap_s * 1e6:.0f},"
              f"resume_ms={fleet_cells[-1]['resume_ms']} "
              f"snap_bytes={snap_bytes}")

    # 3) resume-identity gate: crash mid-journal, resume, compare
    gold_d = os.path.join(work, "gate_gold")
    os.makedirs(gold_d, exist_ok=True)
    gold_eng, gold_m, _ = _durable_run(model, data, rounds, root=gold_d)
    with open(os.path.join(gold_d, "journal.wal"), "rb") as f:
        gold_bytes = f.read()
    crash_d = os.path.join(work, "gate_crash")
    os.makedirs(crash_d, exist_ok=True)
    k = gold_m["journal_records"] // 2
    from repro.core.services import FLConfig
    cfg = FLConfig(n_clients=8, clients_per_round=4, rounds=rounds,
                   local_epochs=1, batch_size=5, base_step_time=0.8,
                   concurrency_ratio=0.5, seed=0,
                   durability="journal", checkpoint_dir=crash_d)
    eng2 = Scheduler(cfg, model, data, list(paper_fleet(8)))
    eng2.durability.crash_after = k
    try:
        eng2.run()
        raise RuntimeError("crash injector never fired")
    except SimulatedCrash:
        pass
    t0 = time.perf_counter()
    resumed = resume_durable(cfg, model, data, list(paper_fleet(8)))
    m2 = resumed.run()
    gate_wall = time.perf_counter() - t0
    with open(os.path.join(crash_d, "journal.wal"), "rb") as f:
        crash_bytes = f.read()
    identical = (m2["history"] == gold_m["history"]
                 and m2["total_time"] == gold_m["total_time"]
                 and crash_bytes == gold_bytes)
    print(f"durability/gate/resume_identity,{gate_wall * 1e6:.0f},"
          f"crash_at={k} replayed={m2['journal_replayed']} "
          f"identical={identical}")

    out = {"bench": "durability", "smoke": smoke,
           "backend": jax.default_backend(), "rounds": rounds,
           "sync": sync_runs, "fleet": fleet_cells,
           "gate": {"crash_at": k, "replayed": m2["journal_replayed"],
                    "resume_identical": identical,
                    "round_sync_overhead_ok": overhead_ok}}
    path = json_path or os.path.join(_ROOT, "BENCH_durability.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    shutil.rmtree(work, ignore_errors=True)
    if not identical:
        print("FAIL: crashed-and-resumed run diverged from the golden run")
        sys.exit(1)
    if not overhead_ok:
        print(f"FAIL: round-sync journaling overhead "
              f"{round_sync['overhead_pct']}% exceeds the 5% gate")
        sys.exit(1)
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    smoke = "--smoke" in sys.argv
    jp = ""
    if "--json" in sys.argv:
        jp = sys.argv[sys.argv.index("--json") + 1]
    if "--scheduler" in sys.argv:
        run_scheduler(smoke=smoke, json_path=jp)
    elif "--dataplane" in sys.argv:
        run_dataplane(smoke=smoke, json_path=jp)
    elif "--controlplane" in sys.argv:
        run_controlplane(smoke=smoke, json_path=jp)
    elif "--megastep" in sys.argv:
        run_megastep(smoke=smoke, json_path=jp)
    elif "--faults" in sys.argv:
        run_faults(smoke=smoke, json_path=jp)
    elif "--traffic" in sys.argv:
        run_traffic(smoke=smoke, json_path=jp)
    elif "--durability" in sys.argv:
        run_durability(smoke=smoke, json_path=jp)
    elif "--sharding" in sys.argv:
        run_sharding(smoke=smoke, json_path=jp)
    else:
        run(smoke=smoke, json_path=jp)
