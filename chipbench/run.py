#!/usr/bin/env python3
"""Benchmark of the Apodotiko FL round on a TPU, one cell per run.

    python3 chipbench/run.py --workload mnist-apodotiko --seed 7 \
        --seconds 30 --trace 0

Builds the cell named in ``BENCHMARK.json`` from its files under
``chipbench/``, warms up, measures ``--seconds`` of FL rounds through
``build_engine(...).run()`` (``--trace 0``: the end-to-end metrics) or
traces a slice of rounds (``--trace 1``: the per-layer metrics), and holds
the first rounds to the plain reference. The last line of standard output
is one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and ``checks``; the numbers compared also close
standard error. Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits 3.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chipbench: no program under {ROOT}/src", file=sys.stderr)
        return 2
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from benchlib import harness, registry

    cleared = harness.configure(ROOT)
    import jax

    cell = registry.load_cell(args.workload)
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "cleared_env": cleared, "jax": jax.__version__}),
          file=sys.stderr, flush=True)
    try:
        result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                                  trace=bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
