"""A copy of the benchmark's files with one small cell, for CPU tests.

The cell keeps the MNIST client at its published width (the program's
``MnistCNN``) and shrinks the deployment: 12 clients of 20 samples, 6 per
round, one local epoch, so that one run takes seconds on the CPU.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
for p in (str(BENCH_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "tiny-apodotiko"
#: limits of the small cell, from CPU readings of 1e-7 to 1e-6 (float32
#: throughout), far below what the control and the faults read (1e-2 and
#: more)
TINY_LIMITS = {"loss_gap": 1e-4, "update_gap": 1e-3, "update_diff": 1e-3,
               "global_gap": 1e-3, "agg_gap": 1e-4}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
WORKLOAD = {"warmup_rounds": 4, "window_rounds": 2, "chunk_rounds": 1,
            "trace_from": 4, "trace_rounds": 1}


def make(tmp: Path, **workload) -> Path:
    """Copy the benchmark's files to ``tmp``, add the small cell and record
    its schedule."""
    for sub in ("configs", "reference", "traffic", "workloads", "metrics",
                "schedules"):
        shutil.copytree(BENCH_DIR / sub, tmp / sub)
    shutil.copy(BENCH_DIR / "peaks.json", tmp / "peaks.json")
    conf = json.loads((BENCH_DIR / "configs" / "mnist-cnn.json").read_text())
    conf.update(name="tiny-cnn", n_clients=12, clients_per_round=6,
                data_scale=0.01, local_epochs=1, limits=TINY_LIMITS)
    (tmp / "configs" / "tiny-cnn.json").write_text(json.dumps(conf))
    wl = {**WORKLOAD, **workload}
    (tmp / "workloads" / f"{CELL}.json").write_text(json.dumps(wl))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": CELL, "config": "tiny-cnn",
                           "traffic": "apodotiko", "chips": 1,
                           "why": "small CPU cell"}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    record(tmp, CELL)
    return tmp


def record(tmp: Path, name: str) -> None:
    """Record cell ``name``'s schedule into ``tmp``'s files."""
    import record_schedule

    out = record_schedule.record(cell(tmp, name))
    (tmp / "schedules" / f"{name}.json").write_text(json.dumps(out))


def cell(tmp: Path, name: str = CELL):
    from benchlib import registry

    return registry.load_cell(name, bench_path=tmp / "BENCHMARK.json",
                              bench_dir=tmp)


def run(tmp: Path, *, trace: bool = False, extras: dict | None = None,
        seed: int = 2**31 + 11) -> dict:
    import io
    import time

    from benchlib import harness

    os.environ.pop("XLA_FLAGS", None)
    return harness.run_cell(cell(tmp), seed=seed, seconds=1.0, trace=trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            peaks_override=CPU_PEAKS, out=io.StringIO(),
                            extras=extras)
