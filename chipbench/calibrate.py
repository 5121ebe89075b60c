#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/calibrate.py --workload mnist-apodotiko \
        --seeds 11,12,13 [--window-rounds 2] [--faults 11,12,13]

For each seed, one process runs the cell as ``run.py`` does (with a window
of ``--window-rounds`` rounds) and prints one JSON line with every number
the program reads against the reference (``program``), then puts the
reference computed at the control's precision in the program's place and
prints what that control reads (``control``) and whether ``correct``
would pass it under the configuration's limits: ``high`` (three bf16 passes)
for float32 at ``highest``, bfloat16 operands for float32 at the default.
The control trains the kept dispatches from the weights they were sent and
aggregates with a matrix product at that precision. For the seeds in
``--faults`` it also reads each fault a cell can have, planted in the
reference put in the program's place: ``state_unchanged`` (every client
comes back with the weights it was sent), ``answer_altered`` (the first
client's change doubled), ``half_batch`` (each aggregation averages the
first half of its results).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _as_prog(ev, trained: dict) -> dict:
    """``{index: (losses, {leaf: [K, ...]})}`` -> the program's layout."""
    return {i: (l, [{n: w[n][j] for n in ev.names}
                    for j in range(len(l))]) for i, (l, w) in trained.items()}


def _matmul_eq2(rows: dict, T: int, joined: list, n, names, precision):
    """The Eq. 2 average as one float32 matrix product at ``precision``."""
    import math

    import jax.numpy as jnp
    import numpy as np

    w = np.array([n[c] / math.sqrt(T - r + 1) for c, r in joined])
    w = jnp.asarray(w / w.sum(), jnp.float32)
    return {m: np.asarray(jnp.dot(
        w, jnp.stack([jnp.asarray(rows[r][m]).ravel() for r in joined]),
        precision=precision)).reshape(np.shape(rows[joined[0]][m]))
        for m in names}


def control_readings(ev, harness, flref, precision, dtype) -> dict:
    low = {k.index: ev.train(k, precision, dtype=dtype) for k in ev.kept}
    out = harness.compare_training(ev, _as_prog(ev, low), ev.reference,
                                   ev.counted_leaves,
                                   p_first=_first_of(ev, flref, low))
    rows0 = harness.first_rows(ev)
    T, joined = ev.joins[0]
    first = _matmul_eq2(rows0, T, joined, ev.data.n, ev.names, precision)
    last = None
    if ev.rows_last is not None:
        T, joined = ev.joins[-1]
        rows = dict(zip(joined, harness._split_rows(ev.rows_last, ev.segs,
                                                    ev.shapes)))
        last = _matmul_eq2(rows, T, joined, ev.data.n, ev.names, precision)
    out["agg_gap"] = harness.aggregate_gaps(ev, first_agg=first,
                                            last_agg=last)
    out["n_results"] = int(sum(len(k.clients) for k in ev.kept))
    return out


def _first_of(ev, flref, trained: dict, half: bool = False) -> dict:
    """The first aggregate of ``trained`` round-0 results."""
    rows = {(c, 0): {n: w[n][j] for n in ev.names}
            for k in ev.kept if k.round == 0
            for j, c in enumerate(k.clients)
            for w in [trained[k.index][1]]}
    T, joined = ev.joins[0]
    if half:
        joined = joined[:max(len(joined) // 2, 1)]
    return flref.eq2(rows, T, joined, ev.data.n, ev.names)


def fault_readings(ev, harness, flref, precision) -> dict:
    import numpy as np

    ref = ev.reference
    out = {}
    frozen = {k.index: ev.train(k, precision, frozen=True) for k in ev.kept}
    out["state_unchanged"] = harness.compare_training(
        ev, _as_prog(ev, frozen), ref, ev.counted_leaves,
        p_first=_first_of(ev, flref, frozen))
    altered = {}
    for j, k in enumerate(ev.kept):
        losses, w = ref[k.index]
        w = {n: np.array(v) for n, v in w.items()}
        if j == 0:
            start = ev.start(k)
            for n in ev.names:
                w[n][0] = 2 * w[n][0] - np.asarray(start[n])
        altered[k.index] = (losses, w)
    out["answer_altered"] = harness.compare_training(
        ev, _as_prog(ev, altered), ref, ev.counted_leaves,
        p_first=_first_of(ev, flref, altered))
    half = harness.compare_training(
        ev, _as_prog(ev, ref), ref, ev.counted_leaves,
        p_first=_first_of(ev, flref, ref, half=True))
    rows0 = harness.first_rows(ev)
    T, joined = ev.joins[0]
    half["agg_gap"] = harness.aggregate_gaps(
        ev, first_agg=flref.eq2(rows0, T, joined[:max(len(joined) // 2, 1)],
                                ev.data.n, ev.names))
    out["half_batch"] = half
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--window-rounds", type=int, default=None)
    ap.add_argument("--faults", default="",
                    help="comma-separated seeds to read the faults on")
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from benchlib import flref, harness, registry

    harness.configure(ROOT)
    import jax
    import jax.numpy as jnp

    cell = registry.load_cell(args.workload)
    if args.window_rounds is not None:
        cell.workload["window_rounds"] = args.window_rounds
        cell.workload["trace_from"] = cell.workload["warmup_rounds"]
        cell.workload["trace_rounds"] = 0
    P = jax.lax.Precision
    control = {"highest": (P.HIGH, jnp.float32),
               "default": (P.DEFAULT, jnp.bfloat16)}[
        cell.config["matmul_precision"]]
    fault_seeds = {int(s) for s in args.faults.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        ex: dict = {}
        t = time.perf_counter()
        r = harness.run_cell(cell, seed=seed, seconds=0.0, trace=False,
                             t_start=t, extras=ex)
        t_ref = time.perf_counter()
        ev = ex["evidence"]
        rec = {"seed": seed, "workload": cell.name,
               "precision": cell.config["matmul_precision"],
               "correct": r["correct"], "program": ex["numbers"],
               "control": control_readings(ev, harness, flref, *control),
               "metrics": r["metrics"], "device": r["device"],
               "run_s": t_ref - t}
        limits = cell.config["limits"]
        rec["control_correct"] = harness.decide(rec["control"], limits)[0]
        if seed in fault_seeds:
            rec["faults"] = fault_readings(ev, harness, flref, P.HIGHEST)
            rec["faults_correct"] = {
                f: harness.decide(v, limits)[0]
                for f, v in rec["faults"].items()}
        rec["control_s"] = time.perf_counter() - t_ref
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
