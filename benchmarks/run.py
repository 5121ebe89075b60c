"""Benchmark runner — one module per paper table/figure, plus sweep mode.

Prints ``name,us_per_call,derived`` CSV lines (plus section markers). Scale
is bench-sized by default (1-core container); set BENCH_FULL=1 for the
paper-scale grid (hours).

  PYTHONPATH=src python -m benchmarks.run            # all benches
  PYTHONPATH=src python -m benchmarks.run fig4 cost  # substring filter

Sweep mode hands off to the strategy-sweep engine (``repro.sweep``) and
prints the paper's comparison tables (speedup vs FedAvg, cold starts, cost):

  python benchmarks/run.py --sweep paper_mnist       # Tables IV-VI, MNIST
  python benchmarks/run.py --sweep smoke             # CI-sized check
  SWEEP_WORKERS=4 python benchmarks/run.py --sweep paper_tables
"""
from __future__ import annotations

import os
import sys
import time
import traceback

# Runnable both as ``python -m benchmarks.run`` and as a plain script with
# no PYTHONPATH: make the repo root (benchmarks pkg) and src/ importable.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BENCHES = [
    ("fig2_staleness", "benchmarks.bench_staleness"),
    ("tableII_time_to_accuracy", "benchmarks.bench_time_to_accuracy"),
    ("tableIII_cost", "benchmarks.bench_cost"),
    ("fig4b_bias", "benchmarks.bench_bias"),
    ("fig4c_coldstart", "benchmarks.bench_coldstart"),
    ("fig5_fedbuff", "benchmarks.bench_fedbuff"),
    ("fig6_concurrency_ratio", "benchmarks.bench_cr"),
    ("fig7_sample_size", "benchmarks.bench_sample_size"),
    ("fig1_fig3_heterogeneity", "benchmarks.bench_heterogeneity"),
    ("aggregation_kernels", "benchmarks.bench_aggregation"),
]

SWEEP_COLUMNS = ("dataset", "scenario", "strategy", "seed", "target_acc",
                 "time_to_target_s", "speedup_vs_fedavg", "final_acc",
                 "cold_starts", "cold_start_reduction_vs_fedavg", "cost_usd",
                 "cost_vs_fedavg")


def run_sweep_mode(argv: list[str]) -> None:
    from repro.sweep import get_preset, run_sweep

    i = argv.index("--sweep")
    name = argv[i + 1] if i + 1 < len(argv) else "paper_mnist"
    try:
        spec = get_preset(name)
    except KeyError as e:
        print(f"error: {e.args[0]}", file=sys.stderr)
        sys.exit(2)
    print(f"# sweep {name}: {spec.n_runs} runs "
          f"({len(spec.strategies)} strategies x {len(spec.datasets)} "
          f"datasets), scale={spec.scale.n_clients} clients", flush=True)
    t0 = time.time()
    table = run_sweep(spec, progress=lambda i, n, r, m: print(
        f"#   [{i + 1}/{n}] {r.key}"
        + (f" FAILED: {m['error']}" if "error" in m else ""), flush=True))
    print(f"# sweep done in {time.time() - t0:.1f}s\n", flush=True)
    cols = SWEEP_COLUMNS
    if len({r["data_plane"] for r in table.rows}) > 1:
        # plane-ablation sweeps: show which transport each row ran on
        cols = SWEEP_COLUMNS[:3] + ("data_plane",) + SWEEP_COLUMNS[3:]
    if len({r["traffic_profile"] for r in table.rows}) > 1:
        # open-loop sweeps: label each comparison group's arrival process
        # and surface the SLO columns (DESIGN.md §13)
        cols = (cols[:3] + ("traffic_profile",) + cols[3:]
                + ("p50_round_latency_s", "p99_round_latency_s",
                   "cost_per_round_usd"))
    print(table.to_markdown(columns=cols))
    for s in sorted({r["strategy"] for r in table.rows}):
        if s != "fedavg":
            print(f"# mean speedup vs fedavg [{s}]: {table.mean_speedup(s)}")
    sys.exit(1 if any(r["error"] for r in table.rows) else 0)


def main() -> None:
    if "--sweep" in sys.argv:
        run_sweep_mode(sys.argv)
        return
    filters = [a for a in sys.argv[1:] if not a.startswith("-")]
    print("name,us_per_call,derived")
    failures = 0
    for name, module in BENCHES:
        if filters and not any(f in name for f in filters):
            continue
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            mod = __import__(module, fromlist=["main"])
            mod.main(lambda n, us, d="": print(f"{n},{us:.1f},{d}", flush=True))
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED:", flush=True)
            traceback.print_exc()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
