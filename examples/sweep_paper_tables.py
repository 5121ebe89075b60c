"""Reproduce the paper's strategy-comparison tables with the sweep engine.

    PYTHONPATH=src python examples/sweep_paper_tables.py [preset]

Default preset is ``paper_mnist``: all six strategies (FedAvg, FedProx,
SCAFFOLD, FedLesScan, FedBuff, Apodotiko) on the paper's heterogeneous
65/25/10 hardware mix, rendered as three tables in the shape of the paper's
Tables IV-VI — time-to-accuracy/speedup, cost, and cold starts. Bench scale
by default (minutes); SWEEP_FULL=1 for the paper-scale grid. Other presets:
``paper_tables`` (all four datasets), ``cr_sweep``, ``hardware_scenarios``,
``staleness_ablation``, ``smoke`` — see ``repro.sweep.presets``.
"""
import sys

from repro.sweep import get_preset, run_sweep

TABLE_IV = ("dataset", "strategy", "target_acc", "time_to_target_s",
            "speedup_vs_fedavg", "final_acc", "best_acc")
TABLE_V = ("dataset", "strategy", "cost_usd", "cost_vs_fedavg",
           "n_invocations")
TABLE_VI = ("dataset", "strategy", "cold_starts", "cold_start_ratio",
            "cold_start_reduction_vs_fedavg")


def main() -> None:
    preset = sys.argv[1] if len(sys.argv) > 1 else "paper_mnist"
    spec = get_preset(preset)
    print(f"sweep {spec.name}: {spec.n_runs} runs", flush=True)
    table = run_sweep(spec, progress=lambda i, n, r, m: print(
        f"  [{i + 1}/{n}] {r.key}"
        + (f" FAILED: {m['error']}" if "error" in m else ""), flush=True))

    print("\n== Table IV: time to common accuracy & speedup vs FedAvg ==")
    print(table.to_markdown(columns=TABLE_IV))
    print("== Table V: FaaS cost ==")
    print(table.to_markdown(columns=TABLE_V))
    print("== Table VI: cold starts ==")
    print(table.to_markdown(columns=TABLE_VI))
    for s in sorted({r["strategy"] for r in table.rows} - {"fedavg"}):
        print(f"mean speedup vs fedavg [{s}]: {table.mean_speedup(s)}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
