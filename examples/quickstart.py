"""Quickstart: federated training with Apodotiko on a simulated serverless
fleet, compared against FedAvg.

    PYTHONPATH=src python examples/quickstart.py

20 clients (65% 1vCPU / 25% 2vCPU / 10% GPU, the paper's mix), non-IID
Dirichlet data, real JAX local training, simulated FaaS timing (cold starts,
scale-to-zero). Prints time-to-accuracy for both strategies.
"""
import numpy as np

from repro.core.controller import Controller, FLConfig
from repro.data.synthetic import make_federated_dataset
from repro.faas.hardware import paper_fleet
from repro.models.proxy_models import ProxyCNN

N_CLIENTS = 20


def main() -> None:
    data = make_federated_dataset("speech", n_clients=N_CLIENTS, scale=0.15,
                                  seed=0)
    model = ProxyCNN(35)
    results = {}
    for strategy in ("fedavg", "apodotiko"):
        cfg = FLConfig(
            n_clients=N_CLIENTS, clients_per_round=8, rounds=12,
            strategy=strategy, concurrency_ratio=0.3,
            local_epochs=2, batch_size=5, base_step_time=1.5,
            round_timeout=400.0, seed=0)
        ctl = Controller(cfg, model, data, list(paper_fleet(N_CLIENTS)))
        m = ctl.run(progress=lambda log: print(
            f"  [{strategy}] round {log.round:2d} t={log.t_end:7.1f}s "
            f"acc={log.accuracy:.3f} agg={log.n_aggregated} "
            f"stale={log.n_stale}"))
        results[strategy] = m
        print(f"{strategy}: sim_time={m['total_time']:.0f}s "
              f"acc={m['final_accuracy']:.3f} "
              f"cold_starts={m['cold_start_ratio']:.2f} "
              f"cost=${m['total_cost_usd']:.3f}")

    # time to the accuracy FedAvg ended at
    target = results["fedavg"]["final_accuracy"]
    for s, m in results.items():
        t = next((t for t, _, a in m["history"] if a >= target), None)
        print(f"time to acc {target:.3f}: {s} = "
              f"{'n/a' if t is None else f'{t:.0f}s'}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
