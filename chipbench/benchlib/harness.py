"""One run of one cell: build the deployment, warm up, measure a fixed range
of FL rounds (tracing a slice of it with ``--trace 1``), then decide
``correct`` against the plain reference and the cell's recorded schedule.

The entry the window drives is ``repro.core.build_engine(cfg, model,
data, fleet).run()``: the harness raises ``cfg.rounds`` by a fixed chunk and
calls ``run()`` again, which gives the same rounds as one ``run()`` to the
end. The schedule is fixed (``schedules/<cell>.json``), so every run does
the same work: the warm-up is the engine's first ``warmup_rounds`` rounds,
the window the next ``window_rounds``. A wrapper around the trainer's
cohort entry, ``train_cohort_indexed``, records each dispatch: its round,
clients and row handles, and for the dispatches the reference is held to,
the weights it was sent, its clients' mean losses and its trained rows.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchlib import flref, registry, traces
from benchlib.clock import CompileClock

#: where the program reads settings of its own from the environment
PROGRAM_ENV_PREFIX = "REPRO_"
WINDOW_SPAN = "chipbench.window"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def configure(root: str, cache: bool = True) -> list[str]:
    """Process set-up before JAX is imported: drop every ``REPRO_*``
    variable, so that only the cell's files decide what the program runs
    (returns their names), and, with ``cache``, keep JAX's persistent
    compilation cache at one fixed directory inside the checkout, every
    program in it, so that only a cell's first run there compiles."""
    names = sorted(k for k in os.environ if k.startswith(PROGRAM_ENV_PREFIX))
    for k in names:
        del os.environ[k]
    if not cache:
        return names
    cache = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return names


@functools.lru_cache(maxsize=None)
def _model(name: str):
    """One model object per class, so that runs in one process share
    their compiled programs."""
    from repro.models import paper_models

    return getattr(paper_models, name)()


def build(cell: registry.Cell, model=None):
    """(model, data, fleet, cfg) of the cell. ``cfg.seed`` is the traffic's
    ``schedule_seed``: it draws the strategy's selections, the platform's
    noise and the lane keys, so every run makes the same rounds."""
    from repro.core import FLConfig
    from repro.data.synthetic import make_federated_dataset
    from repro.faas.hardware import paper_fleet

    c = cell.config
    model = model or _model(c["model"])
    ds = c["dataset"]
    data = make_federated_dataset(ds["name"], c["n_clients"],
                                  scale=c["data_scale"],
                                  fidelity=ds["fidelity"],
                                  seed=ds["data_seed"])
    fleet = list(paper_fleet(c["n_clients"],
                             rng=np.random.default_rng(c["fleet_seed"]),
                             mix=tuple(tuple(m) for m in c["hardware_mix"])))
    cfg = FLConfig(n_clients=c["n_clients"],
                   clients_per_round=c["clients_per_round"], rounds=0,
                   local_epochs=c["local_epochs"],
                   batch_size=c["batch_size"], optimizer=c["optimizer"],
                   lr=c["lr"], base_step_time=c["base_step_time"],
                   seed=cell.traffic["schedule_seed"],
                   **cell.traffic["flconfig"])
    auto = [f.name for f in dataclasses.fields(cfg)
            if getattr(cfg, f.name) == "auto"]
    if auto:
        raise ValueError(f"traffic {cell.traffic['name']!r} leaves "
                         f"{auto} to the environment; pin them")
    return model, data, fleet, cfg


def resolved(eng) -> dict:
    """What the engine resolved each pinned setting to."""
    return {"engine": eng.engine_name, "megastep": eng.megastep,
            "data_plane": eng.data_plane, "update_plane": eng.update_plane,
            "control_plane": eng.control_plane, "mesh": eng.mesh_spec,
            "fault_profile": eng.fault_profile or "off",
            "traffic_profile": eng.traffic_profile or "off",
            "durability": "off" if eng.durability is None else "journal"}


def check_pinned(cell: registry.Cell, eng) -> dict:
    got = resolved(eng)
    want = {k: cell.traffic["flconfig"][k] for k in got}
    if got != want:
        raise ValueError(f"engine resolved {got}, the cell pins {want}")
    return got


@functools.lru_cache(maxsize=None)
def _init_fn(model):
    import jax

    return jax.jit(lambda key: model.init(key)[0])


@dataclasses.dataclass
class Kept:
    """A dispatch the reference is held to."""

    index: int                 # its place among all dispatches
    round: int
    clients: list[int]
    start: object              # the global weights it was sent (device)
    losses: np.ndarray         # each client's mean local loss
    rows: object               # its trained rows [K, W] (device copy)


class Tap:
    """Wraps the trainer's cohort entry ``train_cohort_indexed`` and records
    every dispatch: its round and clients, and each result's row handle.
    While ``keeping`` is on it also keeps, for every dispatch of round 0
    and the first dispatch of each other lane count, what ``Kept`` holds."""

    def __init__(self, eng):
        import jax.numpy as jnp

        self.dispatches: list[tuple[int, list[int]]] = []
        self.rows: dict[tuple[int, int], int] = {}
        self.kept: list[Kept] = []
        self.keeping = True
        lanes: set[int] = set()
        orig = eng.trainer.train_cohort_indexed

        def hook(global_params, store, selection, n_i, steps, *args, **kw):
            out = orig(global_params, store, selection, n_i, steps, *args,
                       **kw)
            ids, _, losses = out
            rnd = int(eng.db.round)
            sel = [int(c) for c in selection]
            ids = np.asarray(ids)
            for c, i in zip(sel, ids):
                self.rows[(c, rnd)] = int(i)
            kp = flref.lane_count(len(sel))
            if self.keeping and (rnd == 0 or kp not in lanes):
                self.kept.append(Kept(
                    len(self.dispatches), rnd, sel, global_params,
                    np.asarray(losses),
                    kw["update_sink"].buffer[jnp.asarray(ids)]))
            lanes.add(kp)
            self.dispatches.append((rnd, sel))
            return out

        eng.trainer.train_cohort_indexed = hook


def run_schedule(tap: Tap, eng) -> flref.Schedule:
    """The run's schedule, from the tap and the engine's public records."""
    return flref.Schedule(
        dispatches=list(tap.dispatches),
        invocations=[(int(r.client_id), int(r.round), float(r.t_invoked),
                      float(r.t_completed), bool(r.cold), bool(r.failed),
                      bool(r.cancelled)) for r in eng.platform.invocations],
        closes=[(int(l.round), float(l.t_end), int(l.n_aggregated))
                for l in eng.history])


def presize(eng, rows: int) -> None:
    """Grow the empty update store to ``rows`` rows, the most the recorded
    schedule held, so that its buffer keeps one shape and no program is
    compiled for a grown one inside the window."""
    eng.store.free(eng.store.alloc(rows))


def prewarm(eng, golden: flref.Schedule, first: int, last: int) -> None:
    """Compile, before the window, what rounds ``[first, last)`` of the
    recorded schedule call: the cohort program of each dispatch's lane
    count and step budget (``CohortTrainer.cohort_fn_indexed``, lowered and
    compiled, not run), its key split and pad-lane zeros, the trim of each
    cohort size, and the aggregation of each close's row count (run on the
    store's buffer, result dropped)."""
    import jax
    import jax.numpy as jnp

    from repro.core.services import weighted_aggregate_rows

    tr, ds, store, cfg = eng.trainer, eng.dataset, eng.store, eng.cfg
    params = eng.params
    compiled = set()
    for rnd, clients in golden.dispatches:
        if not first <= rnd < last:
            continue
        K = len(clients)
        n_i = np.asarray(eng.data.n[clients])
        steps = flref.local_steps(n_i, cfg.batch_size, cfg.local_epochs)
        fn, Kp, max_steps = tr.cohort_fn_indexed(ds, K, int(steps.max()))
        keys = jax.random.split(jax.random.split(jax.random.PRNGKey(0))[1],
                                Kp)
        cg = jax.tree.map(lambda p: jnp.zeros((), p.dtype), params)
        ci = jax.tree.map(
            lambda p: jnp.zeros((Kp,) + (1,) * p.ndim, p.dtype), params)
        if (Kp, max_steps) not in compiled:
            pad = lambda a: np.concatenate([a, np.repeat(a[-1:], Kp - K)])
            fn.lower(params, jnp.asarray(pad(np.asarray(clients, np.int32))),
                     jnp.asarray(pad(n_i)),
                     jnp.asarray(np.concatenate(
                         [steps, np.zeros(Kp - K, steps.dtype)])),
                     keys, cg, ci, ds.X, ds.y, store.buffer,
                     jnp.zeros(Kp, jnp.int32)).compile()
            compiled.add((Kp, max_steps))
        jax.tree.map(lambda a: a[:K], ci)
    dtype = jax.tree.leaves(params)[0].dtype
    for T, _, n in golden.closes:
        if first <= T < last and n:
            weighted_aggregate_rows(store.buffer, list(range(n)),
                                    np.full(n, 1.0 / n, np.float32),
                                    eng.spec, out_dtype=dtype,
                                    mesh=eng.mesh)


def run_rounds(eng, n: int) -> None:
    """``n`` more rounds through the entry: raise ``cfg.rounds``, run."""
    eng.cfg.rounds = eng.db.round + n
    eng.run()


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def decide(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number that has a limit is compared; a missing or NaN
    reading fails."""
    checks = {k: {"value": numbers.get(k), "limit": v}
              for k, v in limits.items()}
    ok = all(c["value"] is not None and c["value"] == c["value"]
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def run_cell(cell: registry.Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             peaks_override: dict | None = None, out=sys.stdout,
             extras: dict | None = None) -> dict:
    """One run; returns the result object (the last line of output).
    ``extras``, where given, receives the evidence the numbers were read
    from, for calibration."""
    import jax

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r})")
        if len(devices) < cell.chips:
            raise NoChip(f"the cell needs {cell.chips} chips, JAX sees "
                         f"{len(devices)}")
    devices = devices[:cell.chips]
    peak = peaks_override or registry.peaks(devices[0].device_kind)
    precision = cell.config["matmul_precision"]
    with (contextlib.nullcontext() if precision == "default"
          else jax.default_matmul_precision(precision)):
        return _run(cell, devices, peak, seed=seed, seconds=seconds,
                    trace=trace, t_start=t_start, out=out, extras=extras)


def _host(tree) -> dict:
    return {k: np.asarray(v) for k, v in tree.items()}


def _split_rows(rows: np.ndarray, segs: dict, shapes: dict) -> list[dict]:
    return [{n: rows[i, o:o + s].reshape(shapes[n])
             for n, (o, s) in segs.items()} for i in range(rows.shape[0])]


def _run(cell, devices, peak, *, seed, seconds, trace, t_start, out,
         extras) -> dict:
    import jax
    import jax.numpy as jnp

    device = device_info(devices)
    conf, wl = cell.config, cell.workload
    golden = cell.schedule
    clock = CompileClock()

    from repro.core import build_engine

    model, data, fleet, cfg = build(cell)
    # the weights come from --seed, the rounds from the traffic's seed
    init = _init_fn(model)(jax.random.PRNGKey(seed))
    eng = build_engine(cfg, model, data, fleet, init_params=init)
    presize(eng, cell.schedule_store_rows)
    pinned = check_pinned(cell, eng)
    ref_mod, arch = cell.reference, conf["architecture"]
    names = [n for n, _, _ in ref_mod.param_shapes(arch)]
    flat = jax.tree_util.tree_flatten_with_path(eng.params)[0]
    keys = [path[-1].key for path, _ in flat]
    sizes = [int(np.prod(x.shape)) for _, x in flat]
    offs = np.cumsum([0] + sizes)
    segs = {n: (int(offs[keys.index(n)]), sizes[keys.index(n)])
            for n in names}
    shapes = {k: x.shape for k, (_, x) in zip(keys, flat)}
    n_params = int(offs[-1])
    if n_params != conf["n_params"] or ref_mod.n_params(arch) != n_params:
        raise ValueError(f"model has {n_params} params, the config states "
                         f"{conf['n_params']}")
    W0 = wl["warmup_rounds"]
    W1 = W0 + wl["window_rounds"]
    chunk = wl["chunk_rounds"]

    tap = Tap(eng)
    warm: list[float] = []
    for stop in (1, W0):
        while eng.db.round < stop:
            t = time.perf_counter()
            run_rounds(eng, min(chunk, stop - eng.db.round))
            warm.append(time.perf_counter() - t)
            print(json.dumps({"warmup_round": eng.db.round,
                              "chunk_s": warm[-1]}), file=out, flush=True)
        if stop == 1:
            p_first = eng.params
    tap.keeping = False
    prewarm(eng, golden, W0, W1)
    jax.block_until_ready(eng.params)
    setup_s = time.perf_counter() - t_start
    c_setup = clock.snapshot()
    print(json.dumps({"device": device, "pinned": pinned,
                      "warmup_rounds": eng.db.round,
                      "warmup_chunk_s": warm,
                      "setup_compile": {k: v for k, v in c_setup.items()
                                        if k != "names"}}),
          file=out, flush=True)

    h0, n0 = len(eng.history), len(eng.platform.invocations)
    t_lo, t_hi = wl["trace_from"], wl["trace_from"] + wl["trace_rounds"]
    tr = logdir = None
    e_lo = e_hi = h_lo = h_hi = 0
    ann = None
    t0 = time.perf_counter()
    marks = [t0]
    while eng.db.round < W1:
        if trace and eng.db.round == t_lo:
            logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(logdir)
            ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            ann.__enter__()
            e_lo, h_lo = eng.n_events, len(eng.history)
        base = eng.params
        run_rounds(eng, min(chunk, W1 - eng.db.round))
        if trace and eng.db.round == t_hi:
            jax.block_until_ready(eng.params)
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            e_hi, h_hi = eng.n_events, len(eng.history)
        marks.append(time.perf_counter())
    jax.block_until_ready(eng.params)
    t1 = time.perf_counter()
    c_window = CompileClock.delta(c_setup, clock.snapshot())
    aggregations = len(eng.history) - h0
    window_invs = eng.platform.invocations[n0:]
    print(json.dumps({"window_s": t1 - t0, "seconds_asked": seconds,
                      "rounds": [W0, W1], "aggregations": aggregations,
                      "chunk_s": list(np.diff(marks)),
                      "window_compile": c_window}), file=out, flush=True)
    device["memory_peak_bytes"] = memory_peak(devices)

    metrics: dict = {}
    breakdown = None
    if trace:
        tr = traces.load(traces.find_xplane(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        lo, hi = traces.span(tr, WINDOW_SPAN)
        busy = traces.busy_s(tr, lo, hi)
        device["busy_s"] = busy
        device["window_s"] = (hi - lo) / 1e9
        steps = flref.local_steps(data.n, cfg.batch_size, cfg.local_epochs)
        traced = [r for r in window_invs if t_lo <= r.round < t_hi]
        ctx = SimpleNamespace(
            trace=tr, lo=lo, hi=hi, window_s=(hi - lo) / 1e9, busy_s=busy,
            aggregations=h_hi - h_lo, events=e_hi - e_lo,
            rows_aggregated=sum(l.n_aggregated
                                for l in eng.history[h_lo:h_hi]),
            samples=int(sum(steps[r.client_id] for r in traced))
            * cfg.batch_size,
            flops_per_sample=ref_mod.train_flops_per_sample(arch),
            n_params=n_params, chips=cell.chips, peak=peak,
            compile_setup=c_setup)
        for m in cell.per_layer:
            v = cell.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": traces.top_ops(tr, lo, hi),
                     "idle_gaps": traces.idle_gaps(tr, lo, hi)}
    else:
        metrics["round_s"] = {"value": (t1 - t0) / max(aggregations, 1),
                              "unit": "s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    # everything the comparison needs, on the host; then free the program
    sched = run_schedule(tap, eng).upto(W1)
    joins = flref.joins(sched, cfg.max_staleness)
    T_last, last = joins[-1] if joins else (-1, [])
    final_ok = (T_last == W1 - 1 and bool(last)
                and all(d[0] <= T_last for d in sched.dispatches))
    rows_last = (np.asarray(eng.store.buffer[jnp.asarray(
        [tap.rows[r] for r in last])]) if final_ok else None)
    p_final, p_base = _host(eng.params), _host(base)
    p_first = _host(p_first)
    kept = [dataclasses.replace(k, start=_host(k.start),
                                rows=np.asarray(k.rows)) for k in tap.kept]
    n_dispatches = len(tap.dispatches)
    attempted = len(window_invs)
    failed = sum(1 for r in window_invs if r.failed)
    window_lanes = {flref.lane_count(len(c)) for r, c in sched.dispatches
                    if W0 <= r < W1}
    del eng, tap, tr, base
    gc.collect()

    ev = Evidence(sched=sched, golden=golden.upto(W1), joins=joins,
                  kept=kept, data=data, arch_mod=ref_mod, arch=arch,
                  names=names, segs=segs, shapes=shapes, seed=seed,
                  cfg=cfg, p_first=p_first, p_final=p_final,
                  p_base=p_base, rows_last=rows_last,
                  sizes=[len(c) for _, c in sched.dispatches[:n_dispatches]])
    numbers = readings(ev, jax.lax.Precision.HIGHEST)
    numbers["lanes_uncompared"] = len(
        window_lanes - {flref.lane_count(len(k.clients)) for k in kept})
    numbers["nonfinite_params"] = int(not all(
        np.all(np.isfinite(v)) for v in p_final.values()))
    numbers["empty_window"] = int(aggregations == 0)
    if extras is not None:
        extras.update(evidence=ev, numbers=numbers)
    limits = {**conf["limits"], "schedule_mismatch": 0,
              "lanes_uncompared": 0, "nonfinite_params": 0,
              "empty_window": 0}
    correct, checks = decide(numbers, limits)
    print(json.dumps({"readings": numbers}), file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


@dataclasses.dataclass
class Evidence:
    """What a run leaves for the comparison, on the host."""

    sched: flref.Schedule
    golden: flref.Schedule
    joins: list
    kept: list[Kept]
    data: object
    arch_mod: object
    arch: dict
    names: list[str]
    segs: dict
    shapes: dict
    seed: int
    cfg: object
    p_first: dict
    p_final: dict
    p_base: dict
    rows_last: np.ndarray | None
    sizes: list[int]

    def prog_rows(self, k: Kept) -> list[dict]:
        return _split_rows(k.rows, self.segs, self.shapes)

    def start(self, k: Kept) -> dict:
        """The weights a kept dispatch starts from: the reference's own
        initial weights for round 0, else the weights the run sent."""
        if k.round == 0:
            return {n: np.asarray(v) for n, v in
                    self.arch_mod.init(self.arch, self.seed).items()}
        return k.start

    def train(self, k: Kept, precision, **kw):
        cfg = self.cfg
        keys = flref.dispatch_keys(cfg.seed, self.sizes[:k.index + 1])
        return flref.train(self.start(k), k.clients, keys[k.index],
                           self.data, arch_mod=self.arch_mod,
                           arch=self.arch, batch=cfg.batch_size,
                           epochs=cfg.local_epochs, lr=cfg.lr,
                           precision=precision, **kw)

    def counted(self, precision) -> np.ndarray:
        k = self.kept[0]
        keys = flref.dispatch_keys(self.cfg.seed, self.sizes[:1])
        return flref.counted_leaves(flref.first_grad(
            self.start(k), k.clients[0], keys[0][0], self.data,
            arch_mod=self.arch_mod, arch=self.arch,
            batch=self.cfg.batch_size, precision=precision))


def compare_training(ev: Evidence, prog: dict, ref: dict,
                     counted: np.ndarray, p_first: dict | None = None
                     ) -> dict[str, float]:
    """The training numbers: every kept result of ``prog`` (per kept
    dispatch: (losses, list of trained weights)) against ``ref`` (per kept
    dispatch: (losses, {leaf: [K, ...]})), and the change of the first
    aggregation (``global_gap``; ``p_first`` replaces the run's weights
    after it, for reading a control)."""
    gaps = []
    round0: dict = {}
    for k in ev.kept:
        pl, pw = prog[k.index]
        rl, rw = ref[k.index]
        scale = float(np.median(np.abs(rl)))
        start = ev.start(k)
        for i, c in enumerate(k.clients):
            r_i = {n: rw[n][i] for n in ev.names}
            gaps.append(flref.client_gaps(float(pl[i]), pw[i], float(rl[i]),
                                          r_i, start, ev.names, counted,
                                          scale))
            if k.round == 0:
                round0[(c, 0)] = r_i
    out = flref.summarize(gaps)
    T, joined = ev.joins[0]
    p_init = ev.start(ev.kept[0])
    if T == 0 and joined and all(r in round0 for r in joined):
        want = flref.eq2(round0, T, joined, ev.data.n, ev.names)
        out["global_gap"] = flref.change_gap(p_first or ev.p_first, want,
                                             p_init, ev.names, counted)
    else:
        out["global_gap"] = float("inf")
    return out


def first_rows(ev: Evidence) -> dict:
    """Round 0's trained rows of the run, by (client, round)."""
    return {(c, 0): w for k in ev.kept if k.round == 0
            for c, w in zip(k.clients, ev.prog_rows(k))}


def aggregate_gaps(ev: Evidence, first_agg=None, last_agg=None) -> float:
    """``agg_gap``: the run's first aggregate against the float64 Eq. 2
    average of the rows its cohort training wrote for the results the
    reference's rule joins, and the same for the window's last aggregate
    (the global weights after the window). ``first_agg``/``last_agg``
    replace the run's aggregates, for reading a control."""
    rows0 = first_rows(ev)
    T, joined = ev.joins[0]
    p_init = ev.start(ev.kept[0])
    gaps = []
    if T == 0 and joined and all(r in rows0 for r in joined):
        want = flref.eq2(rows0, T, joined, ev.data.n, ev.names)
        gaps.append(flref.agg_gap(first_agg or ev.p_first, want, p_init,
                                  ev.names))
    else:
        gaps.append(float("inf"))
    T, joined = ev.joins[-1]
    if ev.rows_last is not None:
        rows = dict(zip(joined, _split_rows(ev.rows_last, ev.segs,
                                            ev.shapes)))
        want = flref.eq2(rows, T, joined, ev.data.n, ev.names)
        gaps.append(flref.agg_gap(last_agg or ev.p_final, want, ev.p_base,
                                  ev.names))
    else:
        gaps.append(float("inf"))
    return max(gaps)


def readings(ev: Evidence, precision) -> dict[str, float]:
    """Every number the comparison reads, the reference at ``precision``."""
    counted = ev.counted(precision)
    ref = {k.index: ev.train(k, precision) for k in ev.kept}
    prog = {k.index: (k.losses, ev.prog_rows(k)) for k in ev.kept}
    out = compare_training(ev, prog, ref, counted)
    out["agg_gap"] = aggregate_gaps(ev)
    out["schedule_mismatch"] = flref.schedule_mismatch(ev.sched, ev.golden)
    ev.reference = ref
    ev.counted_leaves = counted
    return out
