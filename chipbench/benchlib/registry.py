"""Finds a cell's files by the names in ``BENCHMARK.json``.

* ``configs/<config>.json``: the model, data, fleet and client settings,
  the matmul precision and the limits of ``correct``;
* ``reference/<reference>.py``: the configuration's plain reference;
* ``traffic/<traffic>.json``: the strategy and every ``FLConfig`` field
  that would otherwise resolve from the environment;
* ``workloads/<cell>.json``: rounds of warm-up, of the window, of a chunk
  of it and of the traced slice;
* ``schedules/<cell>.json``: the cell's recorded schedule (``flref``), which
  every run is held to and which sizes the update store and the warm-up;
* ``metrics/<metric>.py``: one reader per per-layer metric;
* ``peaks.json``: the device peaks, keyed by ``device_kind``.

Adding a configuration, a traffic mix, a cell or a metric adds files and
entries; no file here names one.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    bench_dir: Path = BENCH_DIR

    @property
    def reference(self):
        return load_module(self.bench_dir / "reference"
                           / f"{self.config['reference']}.py")

    @functools.cached_property
    def schedule_file(self) -> dict:
        return _json(self.bench_dir / "schedules" / f"{self.name}.json")

    @property
    def schedule(self):
        from benchlib import flref

        return flref.Schedule.from_json(self.schedule_file)

    @property
    def schedule_store_rows(self) -> int:
        return int(self.schedule_file["store_rows"])

    def metric_reader(self, name: str):
        return load_module(self.bench_dir / "metrics" / f"{name}.py").read


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json",
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = _json(bench_path)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        names = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({names})")

    def applies(m: dict) -> bool:
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m) and m["moves"] in moved]
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=_json(bench_dir / "configs" / f"{entry['config']}.json"),
        traffic=_json(bench_dir / "traffic" / f"{entry['traffic']}.json"),
        workload=_json(bench_dir / "workloads" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = _json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"({', '.join(table)})")
    return table[device_kind]
