#!/usr/bin/env python3
"""Record a cell's schedule, which every run of the cell is held to.

    JAX_PLATFORMS=cpu python3 chipbench/record_schedule.py \
        --workload mnist-apodotiko

Runs the cell's deployment (its data, fleet and traffic, the rounds of its
warm-up and window) on the CPU through ``build_engine(...).run()``, with a
stand-in client model (``benchlib/proxy.py``): the schedule is simulated on
the host and reads nothing of the model. Writes
``schedules/<cell>.json``: every dispatch (round, clients in order), every
invocation (client, round, invoked, completed, cold start, failed,
cancelled), every close (round, time, results joined) and the most rows
the update store grew to.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def record(cell) -> dict:
    from benchlib import harness, proxy

    conf = cell.config
    model = proxy.SoftmaxRegression(
        (conf["architecture"]["input_hw"],) * 2
        + (conf["architecture"]["in_channels"],),
        conf["architecture"]["n_classes"])
    from repro.core import build_engine

    model, data, fleet, cfg = harness.build(cell, model=model)
    eng = build_engine(cfg, model, data, fleet)
    tap = harness.Tap(eng)
    tap.keeping = False
    wl = cell.workload
    harness.run_rounds(eng, wl["warmup_rounds"] + wl["window_rounds"])
    sched = harness.run_schedule(tap, eng)
    return {"source": "chipbench/record_schedule.py: the cell's deployment "
                      "on the CPU with a stand-in client model",
            "rounds": eng.db.round, "store_rows": int(eng.store.capacity),
            **sched.to_json()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from benchlib import harness, registry

    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    harness.configure(ROOT, cache=False)
    import jax

    jax.config.update("jax_platforms", "cpu")
    cell = registry.load_cell(args.workload)
    out = record(cell)
    path = os.path.join(HERE, "schedules", f"{cell.name}.json")
    with open(path, "w") as f:
        json.dump(out, f, separators=(",", ":"))
        f.write("\n")
    print(json.dumps({"workload": cell.name, "rounds": out["rounds"],
                      "store_rows": out["store_rows"],
                      "dispatches": len(out["dispatches"]),
                      "invocations": len(out["invocations"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
