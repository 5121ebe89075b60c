"""Every file of the benchmark loads and keeps to the contract's rules on
names and units; a cell, a configuration and a metric are added as new
files plus entries, with no edit to a file that is there; the analytic
FLOPs of the two paper CNNs match a hand count."""
import hashlib
import json
import re
import shutil

import pytest

import tinybench
from benchlib import registry

BENCH = json.loads((tinybench.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_keys_names_and_units():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert (tinybench.BENCH_DIR / "metrics" / f"{m['name']}.py").exists()
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("chipbench/")
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads(name):
    import jax

    from repro.models import paper_models

    cell = registry.load_cell(name)
    conf = cell.config
    assert conf["matmul_precision"] in ("default", "high", "highest")
    assert all(v is not None for v in conf["limits"].values())
    assert cell.reference.n_params(conf["architecture"]) == conf["n_params"]
    model = getattr(paper_models, conf["model"])()
    shapes = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == conf["n_params"]
    assert "auto" not in cell.traffic["flconfig"].values()
    assert set(cell.workload) == {"warmup_rounds", "window_rounds",
                                  "chunk_rounds", "trace_from",
                                  "trace_rounds"}
    assert all(v >= 1 for v in cell.workload.values())
    assert {m["name"] for m in cell.end_to_end} >= {"round_s", "setup_s"}
    assert cell.per_layer


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_has_its_schedule(name):
    """The recorded schedule covers the warm-up and the window; the
    window's last round aggregates (the weights after the window are held
    to it); the warm-up dispatches every lane count the window does (each
    has a kept dispatch); the traced slice is whole chunks of the window."""
    from benchlib import flref

    cell = registry.load_cell(name)
    wl, sched = cell.workload, cell.schedule
    w0, w1 = wl["warmup_rounds"], wl["warmup_rounds"] + wl["window_rounds"]
    assert cell.schedule_file["rounds"] >= w1
    assert {r for r, _ in sched.dispatches} >= set(range(w1))
    assert w1 - 1 in {T for T, _, n in sched.closes if n}
    lanes = lambda lo, hi: {flref.lane_count(len(c))
                            for r, c in sched.dispatches if lo <= r < hi}
    assert lanes(w0, w1) <= lanes(0, w0)
    t0, t1 = wl["trace_from"], wl["trace_from"] + wl["trace_rounds"]
    assert w0 <= t0 < t1 <= w1
    assert (t0 - w0) % wl["chunk_rounds"] == 0
    assert wl["trace_rounds"] % wl["chunk_rounds"] == 0
    assert cell.schedule_store_rows >= 1
    # the reference's rule joins what the program aggregated, close by close
    joined = flref.joins(sched.upto(w1), _max_staleness())
    assert [len(j) for _, j in joined] == [n for _, _, n in
                                           sched.upto(w1).closes]


def _max_staleness() -> int:
    import dataclasses

    from repro.core import FLConfig

    return next(f.default for f in dataclasses.fields(FLConfig)
                if f.name == "max_staleness")


def test_peaks_are_keyed_by_device_kind():
    assert registry.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        registry.peaks("cpu")


def _digest(d):
    return {p.relative_to(d): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*")) if p.is_file()}


def test_cell_config_and_metric_are_added_as_files(tmp_path):
    d = tinybench.make(tmp_path)
    before = _digest(d)
    conf = json.loads((d / "configs" / "tiny-cnn.json").read_text())
    conf["name"] = "tiny2-cnn"
    (d / "configs" / "tiny2-cnn.json").write_text(json.dumps(conf))
    (d / "traffic" / "tiny-sync.json").write_text(json.dumps(
        {"name": "tiny-sync", "schedule_seed": 0, "flconfig": dict(
            json.loads((d / "traffic" / "apodotiko.json").read_text())
            ["flconfig"], strategy="fedavg")}))
    (d / "workloads" / "tiny2-sync.json").write_text(json.dumps(
        dict(tinybench.WORKLOAD, window_rounds=1)))
    (d / "metrics" / "rounds_traced.py").write_text(
        "def read(ctx):\n    return ctx.aggregations\n")
    bench = json.loads((d / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny2-sync", "config": "tiny2-cnn",
                               "traffic": "tiny-sync", "chips": 1,
                               "why": "throwaway"})
    bench["per_layer"].append({"name": "rounds_traced", "unit": "rounds",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "engine and host pump",
                               "moves": "round_s",
                               "workloads": ["tiny2-sync"]})
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    tinybench.record(d, "tiny2-sync")
    cell = registry.load_cell("tiny2-sync", bench_path=d / "BENCHMARK.json",
                              bench_dir=d)
    assert len(cell.schedule.closes) == 5
    assert cell.traffic["flconfig"]["strategy"] == "fedavg"
    assert "rounds_traced" in [m["name"] for m in cell.per_layer]
    assert cell.metric_reader("rounds_traced")(
        type("Ctx", (), {"aggregations": 3})) == 3
    other = registry.load_cell(tinybench.CELL,
                               bench_path=d / "BENCHMARK.json", bench_dir=d)
    assert "rounds_traced" not in [m["name"] for m in other.per_layer]
    after = _digest(d)
    changed = [p for p in before if before[p] != after[p]]
    assert changed == [tinybench.Path("BENCHMARK.json")]


@pytest.mark.parametrize("config,fwd,train", [
    # conv1 24*24*25*1*32*2 + conv2 8*8*25*32*64*2 + fc 1024*512*2 + 512*10*2
    ("mnist-cnn", 921_600 + 6_553_600 + 1_048_576 + 10_240,
     3 * 8_534_016 - 921_600),
    # conv1 28*28*25*1*32*2 + conv2 14*14*25*32*64*2 + fc 3136*2048*2
    # + 2048*62*2
    ("femnist-cnn", 1_254_400 + 20_070_400 + 12_845_056 + 253_952,
     3 * 34_423_808 - 1_254_400),
])
def test_flops_match_hand_count(config, fwd, train):
    conf = json.loads((tinybench.BENCH_DIR / "configs"
                       / f"{config}.json").read_text())
    ref = registry.load_module(tinybench.BENCH_DIR / "reference"
                               / f"{conf['reference']}.py")
    arch = conf["architecture"]
    assert sum(f for _, f in ref.layer_flops(arch)) == fwd
    assert ref.train_flops_per_sample(arch) == train


def _run_py(cwd):
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "mnist-apodotiko",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_result_without_a_tpu():
    r = _run_py(tinybench.ROOT)
    assert r.returncode == 3 and r.stdout == "", r.stderr[-2000:]
    assert "no TPU" in r.stderr


def test_no_result_without_the_program(tmp_path):
    shutil.copy(tinybench.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tinybench.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(tmp_path)
    assert r.returncode != 0 and r.stdout == ""
