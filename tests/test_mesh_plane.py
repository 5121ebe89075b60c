"""Mesh plane (DESIGN.md §15): flag resolution, alignment, golden traces.

Contract under test:

* ``"1x1"`` (the default) is the bit-exact oracle — no mesh object is
  constructed and every observable of a run (trace, simulated time,
  params) is byte-identical to pre-mesh builds, across engines and
  update planes.
* Real meshes (``"<data>x<model>"``) keep selections/timing identical
  and params allclose (the psum and batch split reassociate float
  reductions), and the fused megastep stays BIT-identical to the
  stepwise oracle *at the same mesh* — the regression guard for traced
  code whose values could change when the SPMD partitioner shards it
  (DESIGN.md §15).

The test process itself keeps one CPU device; everything that needs a
real multi-device mesh runs in a subprocess under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the same
pattern as tests/test_sharding.py).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.core.services import FLConfig
from repro.core.scheduler import Scheduler
from repro.launch.mesh import _debug_mesh_shape
from repro.sharding import flmesh

from trace_harness import data, model  # noqa: F401
from trace_harness import base_cfg_kw, det_fleet, megastep_cfg, run_flag_pair


# ------------------------------------------------------------- unit layer
def test_parse_mesh():
    assert flmesh.parse_mesh("1x1") == (1, 1)
    assert flmesh.parse_mesh("2x4") == (2, 4)
    assert flmesh.parse_mesh("16X16") == (16, 16)
    for bad in ("", "2", "2x", "x4", "2x4x2", "axb", "0x4", "2x-1", "auto"):
        with pytest.raises(ValueError):
            flmesh.parse_mesh(bad)


def test_resolve_mesh_flag_oracle(monkeypatch):
    """Explicit config > REPRO_MESH > '1x1', validated eagerly."""
    monkeypatch.delenv("REPRO_MESH", raising=False)
    assert flmesh.resolve_mesh("auto") == "1x1"
    assert flmesh.resolve_mesh(None) == "1x1"
    assert flmesh.resolve_mesh("2x4") == "2x4"
    monkeypatch.setenv("REPRO_MESH", "2x2")
    assert flmesh.resolve_mesh("auto") == "2x2"
    assert flmesh.resolve_mesh("1x1") == "1x1"      # explicit beats env
    monkeypatch.setenv("REPRO_MESH", "nonsense")
    with pytest.raises(ValueError):
        flmesh.resolve_mesh("auto")


def test_build_fl_mesh_1x1_is_none_and_cached():
    assert flmesh.build_fl_mesh("1x1") is None
    assert flmesh.mesh_axes(None) == (1, 1)
    assert flmesh.mesh_token(None) == ()


def test_build_fl_mesh_rejects_oversubscription():
    """A spec needing more devices than are visible fails loudly, naming
    the XLA_FLAGS remedy."""
    need = jax.device_count() * 2
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        flmesh.build_fl_mesh(f"{need}x1")


class _FakeMesh:
    """Shape-only stand-in: alignment helpers read only .shape."""

    def __init__(self, data_ax, model_ax):
        self.shape = {"data": data_ax, "model": model_ax}


def test_alignment_gains_mesh_divisibility():
    assert flmesh.row_align(None, 128) == 128
    assert flmesh.capacity_align(None, 8) == 8
    m = _FakeMesh(2, 3)
    assert flmesh.row_align(m, 128) == 384          # lcm(128, model=3)
    assert flmesh.capacity_align(m, 8) == 8         # data=2 divides 8
    assert flmesh.capacity_align(_FakeMesh(16, 1), 8) == 16
    assert flmesh.mesh_axes(m) == (2, 3)
    tok = flmesh.mesh_token(m)
    assert tok[0] == "mesh" and tok[1] == (2, 3)


@pytest.mark.parametrize("n,expect", [
    (0, (1, 1)), (1, (1, 1)), (2, (1, 2)), (3, (1, 3)), (4, (1, 4)),
    (5, (5, 1)), (6, (2, 3)), (7, (7, 1)), (8, (2, 4)), (9, (3, 3)),
    (11, (11, 1)), (12, (3, 4)), (256, (64, 4)),
])
def test_debug_mesh_shape_covers_every_device_count(n, expect):
    """Every device count factorizes into a valid mesh covering exactly
    max(n, 1) devices (the old // 4 arithmetic lost devices for n % 4
    and produced a zero-extent axis for n < 4)."""
    d, m = _debug_mesh_shape(n)
    assert (d, m) == expect
    assert d * m == max(n, 1)
    assert d >= 1 and m >= 1


def test_scheduler_rejects_mesh_without_devices(data, model):
    """Engine construction resolves the mesh eagerly: asking for more
    devices than the process has is an immediate, explicit error."""
    need = jax.device_count() * 2
    cfg = FLConfig(**base_cfg_kw(mesh=f"{need}x1"))
    with pytest.raises(ValueError, match="devices"):
        Scheduler(cfg, model, data, det_fleet(10))


# ----------------------------------------------------- 1x1 oracle layer
@pytest.mark.parametrize("update_plane", ["device", "blob"])
def test_mesh_1x1_is_bit_exact_oracle(data, model, update_plane):
    """mesh='1x1' and mesh='auto' (no env) must be byte-identical on
    both update planes — resolution alone never perturbs a run."""
    os.environ.pop("REPRO_MESH", None)
    kw = base_cfg_kw(rounds=3, strategy="apodotiko",
                     update_plane=update_plane)
    run_flag_pair(kw, "mesh", ("auto", "1x1"), model, data)


def test_mesh_1x1_fused_megastep_unperturbed(data, model):
    """The megastep eligibility proof gains mesh obligations; at 1x1
    they are vacuous and the fused path still engages bit-exactly."""
    from trace_harness import assert_fused_matches_stepwise
    kw = megastep_cfg(rounds=6, mesh="1x1")
    m_step, m_fused = assert_fused_matches_stepwise(kw, model, data,
                                                    min_fused_rounds=1)
    assert m_fused["mesh"] == "1x1"


def test_metrics_report_mesh_spec(data, model):
    cfg = FLConfig(**base_cfg_kw(rounds=1, mesh="1x1"))
    eng = Scheduler(cfg, model, data, det_fleet(10))
    assert eng.run()["mesh"] == "1x1"


# ------------------------------------------------- multi-device layer
# Run in a subprocess so the test process keeps 1 device; one script
# amortizes startup + data/model build across every sharded check.
SHARDED_RUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.pop("REPRO_MESH", None)
import json
import numpy as np
import jax
import jax.numpy as jnp

from repro.core.services import FLConfig
from repro.core.scheduler import Scheduler
from repro.core.update_store import UpdateStore
from repro.core.aggregation import weighted_aggregate_rows
from repro.data.synthetic import make_federated_dataset
from repro.faas.hardware import HardwareProfile
from repro.kernels.ops import RavelSpec
from repro.models.proxy_models import build_bench_model
from repro.sharding import flmesh

out = {}
mesh = flmesh.build_fl_mesh("2x4")
d_ax, m_ax = flmesh.mesh_axes(mesh)
out["mesh_axes"] = [d_ax, m_ax]

# --- sharded UpdateStore round-trip + psum aggregation vs 1-device oracle
tpl = {"w": jnp.zeros((37, 5), jnp.float32), "b": jnp.zeros((11,), jnp.float32)}
spec = RavelSpec(tpl)
rows = [jax.random.normal(jax.random.PRNGKey(i), (spec.n_params,))
        for i in range(5)]
store_m = UpdateStore(spec.n_params, capacity=8, mesh=mesh)
store_0 = UpdateStore(spec.n_params, capacity=8, mesh=None)
ids_m = store_m.put(jnp.stack(rows))
ids_0 = store_0.put(jnp.stack(rows))
out["ids_equal"] = list(map(int, ids_m)) == list(map(int, ids_0))
out["row_spec_ok"] = (store_m.buffer.sharding.spec == flmesh.ROW_SPEC)
out["cap_aligned"] = (store_m.buffer.shape[0] % d_ax == 0
                      and store_m.buffer.shape[1] % m_ax == 0)
out["gather_equal"] = bool(np.array_equal(
    np.asarray(store_m.gather(ids_m))[:, :spec.n_params],
    np.asarray(store_0.gather(ids_0))[:, :spec.n_params]))
w = np.array([0.4, 0.3, 0.2, 0.1], np.float32)
agg_m = weighted_aggregate_rows(store_m.buffer, np.asarray(ids_m[:4]), w,
                                spec, mesh=mesh)
agg_0 = weighted_aggregate_rows(store_0.buffer, np.asarray(ids_0[:4]), w,
                                spec, mesh=None)
err = max(float(np.max(np.abs(np.asarray(agg_m[k]) - np.asarray(agg_0[k]))))
          for k in tpl)
out["agg_err"] = err

# --- blob plane is incompatible with a mesh: loud error, not corruption
data = make_federated_dataset("mnist", n_clients=10, scale=0.05, seed=0)
model = build_bench_model("mnist")

def fleet(n=10, speeds=(1.0, 1.45, 1.9)):
    return [HardwareProfile(f"det{i % len(speeds)}",
                            speed=speeds[i % len(speeds)], vcpus=1.0,
                            mem_gib=2.0, variability=0.0) for i in range(n)]

cfg_kw = dict(n_clients=10, clients_per_round=4, rounds=5, local_epochs=1,
              batch_size=5, base_step_time=0.5, strategy="apodotiko-topk",
              concurrency_ratio=1.0, eval_every=0, keep_warm=1e9, seed=0)
try:
    Scheduler(FLConfig(**cfg_kw, mesh="2x4", update_plane="blob"),
              model, data, fleet())
    out["blob_rejected"] = False
except ValueError:
    out["blob_rejected"] = True

# --- golden traces: 2x4 vs 1x1 stepwise; fused vs stepwise AT 2x4
def run(mesh_spec, megastep, K=4):
    cfg = FLConfig(**{**cfg_kw, "clients_per_round": K}, mesh=mesh_spec,
                   megastep=megastep)
    eng = Scheduler(cfg, model, data, fleet())
    m = eng.run()
    tr = ([(l.round, l.t_start, l.t_end, l.n_aggregated) for l in eng.history],
          [(r.client_id, r.round, r.t_invoked, r.duration)
           for r in eng.platform.invocations])
    flat = np.concatenate([np.asarray(x).ravel()
                           for x in jax.tree.leaves(eng.params)])
    return tr, flat, m

tr_0, p_0, m_0 = run("1x1", "stepwise")
tr_s, p_s, m_s = run("2x4", "stepwise")
tr_f, p_f, m_f = run("2x4", "fused")
out["trace_2x4_eq_1x1"] = (tr_s == tr_0)
out["params_2x4_vs_1x1_err"] = float(np.max(np.abs(p_s - p_0)))
out["fused_trace_eq"] = (tr_f == tr_s)
out["fused_bitwise_err"] = float(np.max(np.abs(p_f - p_s)))
out["fused_rounds"] = int(m_f.get("megastep_rounds", 0))
out["fallback"] = m_f.get("megastep_fallback_reason")

# K=3 exercises the Kp>K cohort pad and the sublane pad of the psum
tr_s3, p_s3, _ = run("2x4", "stepwise", K=3)
tr_f3, p_f3, m_f3 = run("2x4", "fused", K=3)
out["fused_k3_trace_eq"] = (tr_f3 == tr_s3)
out["fused_k3_bitwise_err"] = float(np.max(np.abs(p_f3 - p_s3)))
out["fused_k3_rounds"] = int(m_f3.get("megastep_rounds", 0))
print(json.dumps(out))
"""


def test_sharded_plane_on_8_devices(tmp_path):
    script = tmp_path / "sharded.py"
    script.write_text(SHARDED_RUN)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_MESH", None)
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["mesh_axes"] == [2, 4]
    # store/aggregation layer
    assert rec["ids_equal"] and rec["row_spec_ok"] and rec["cap_aligned"]
    assert rec["gather_equal"]
    assert rec["agg_err"] <= 1e-5          # psum reassociation only
    assert rec["blob_rejected"]
    # golden traces: identical selections/timing, allclose params
    assert rec["trace_2x4_eq_1x1"]
    assert rec["params_2x4_vs_1x1_err"] <= 1e-4
    # fused megastep at the SAME mesh is BIT-identical to stepwise —
    # the guard for in-trace code (threefry splits feeding the sharded
    # shard_map, the cohort and sublane pads) whose values would change
    # if the partitioner sharded it wrongly.
    assert rec["fused_trace_eq"] and rec["fused_bitwise_err"] == 0.0
    assert rec["fused_rounds"] >= 1, rec["fallback"]
    assert rec["fused_k3_trace_eq"] and rec["fused_k3_bitwise_err"] == 0.0
    assert rec["fused_k3_rounds"] >= 1
