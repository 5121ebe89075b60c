"""Reproduces the paper's motivating Fig. 1: FedLesScan beats FedAvg on a
homogeneous fleet but collapses under hardware heterogeneity, while
Apodotiko's CEF scoring adapts.

    PYTHONPATH=src python examples/heterogeneous_cohort.py
"""
from repro.core.controller import Controller, FLConfig
from repro.data.synthetic import make_federated_dataset
from repro.faas.hardware import HARDWARE_PROFILES, paper_fleet
from repro.models.proxy_models import ProxyLSTM

N = 18


def fleet(scenario: str):
    if scenario == "homogeneous":
        return [HARDWARE_PROFILES["cpu2"]] * N
    if scenario == "two-tier":
        return [HARDWARE_PROFILES["cpu1"]] * 11 + [HARDWARE_PROFILES["cpu2"]] * 7
    return list(paper_fleet(N))  # cpu1/cpu2/gpu mix


def main() -> None:
    data = make_federated_dataset("shakespeare", n_clients=N, scale=0.1,
                                  seed=0)
    model = ProxyLSTM(vocab=82, seq_len=20)
    print(f"{'scenario':>14} {'strategy':>12} {'sim_time':>9} {'acc':>6} "
          f"{'cold%':>6}")
    for scenario in ("homogeneous", "two-tier", "heterogeneous"):
        for strategy in ("fedavg", "fedlesscan", "apodotiko"):
            cfg = FLConfig(n_clients=N, clients_per_round=6, rounds=8,
                           strategy=strategy, local_epochs=1, batch_size=8,
                           optimizer="sgd", lr=0.8, base_step_time=4.0,
                           round_timeout=500.0, seed=0)
            ctl = Controller(cfg, model, data, fleet(scenario))
            m = ctl.run()
            print(f"{scenario:>14} {strategy:>12} "
                  f"{m['total_time']:>8.0f}s {m['final_accuracy']:>6.3f} "
                  f"{100*m['cold_start_ratio']:>5.1f}%")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
