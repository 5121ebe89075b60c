"""Sharding rule-engine tests + a subprocess mini dry-run on 8 host devices."""
import json
import os
import subprocess
import sys

import pytest
from jax.sharding import PartitionSpec as P

import jax

from repro.sharding.rules import (
    DEFAULT_RULES,
    axis_rules,
    logical_spec,
    shard_act,
    zero1_extend,
)


@pytest.fixture(scope="module")
def mesh2d():
    # 1-device test process: trivial mesh still exercises the rule engine
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


class _FakeMesh:
    """Shape-only mesh stand-in for pure rule-resolution tests."""

    def __init__(self, shape):
        self.shape = shape


def test_divisible_dims_shard():
    mesh = _FakeMesh({"data": 16, "model": 16})
    spec = logical_spec(("batch", "seq", "ffn"), (256, 4096, 14336), mesh,
                        DEFAULT_RULES)
    assert spec == P("data", None, "model")


def test_non_divisible_falls_back_to_replication():
    mesh = _FakeMesh({"data": 16, "model": 16})
    # kv_heads = 8 does not divide 16 -> replicated, never padded
    spec = logical_spec(("batch", "kv_heads", None), (128, 8, 128), mesh,
                        DEFAULT_RULES)
    assert spec == P("data")


def test_multi_axis_rule_greedy_drop():
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    # batch 16 can't take (pod,data)=32 -> drops 'pod', uses data
    spec = logical_spec(("batch",), (16,), mesh, DEFAULT_RULES)
    assert spec == P("data")
    # batch 32 takes both
    spec = logical_spec(("batch",), (32,), mesh, DEFAULT_RULES)
    assert spec == P(("pod", "data"))


def test_axis_never_used_twice():
    mesh = _FakeMesh({"data": 4, "model": 4})
    spec = logical_spec(("ffn", "ffn"), (64, 64), mesh, DEFAULT_RULES)
    # second ffn dim cannot reuse 'model'
    assert spec == P("model")


def test_zero1_extends_largest_free_dim():
    mesh = _FakeMesh({"data": 16, "model": 16})
    spec = zero1_extend(P(None, "model"), (4096, 14336), mesh)
    assert spec == P("data", "model")


def test_zero1_skips_when_nothing_divides():
    mesh = _FakeMesh({"data": 16})
    spec = zero1_extend(P(), (7, 9), mesh)
    assert spec == P()


def test_shard_act_is_noop_outside_axis_rules():
    """Un-meshed model code must run untouched: no constraint, same
    object identity semantics (value + sharding unchanged)."""
    import jax.numpy as jnp
    x = jnp.arange(12.0).reshape(3, 4)
    y = shard_act(x, ("batch", "ffn"))
    assert y is x


def test_shard_act_constrains_inside_axis_rules(mesh2d):
    """Under axis_rules the constraint is value-preserving, and the spec
    it resolves is the rule-table one (checked via logical_spec — eager
    with_sharding_constraint on one device normalizes the sharding)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    x = jnp.arange(8.0).reshape(2, 4)
    with axis_rules(mesh2d):
        y = shard_act(x, ("batch", "ffn"))
        spec = logical_spec(("batch", "ffn"), x.shape, mesh2d,
                            DEFAULT_RULES)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert spec == P("data", "model")
    assert y.sharding.is_equivalent_to(NamedSharding(mesh2d, spec), x.ndim)


def test_tuple_rule_resolves_multiple_axes():
    """A tuple rule uses every listed axis present on the mesh (in order)
    when the product divides; missing axes are skipped, not fatal."""
    mesh = _FakeMesh({"data": 4, "model": 2})
    # 'batch' rule is ('pod', 'data'); no 'pod' axis here -> just data
    assert logical_spec(("batch",), (8,), mesh, DEFAULT_RULES) == P("data")
    rules = dict(DEFAULT_RULES, batch=("data", "model"))
    assert logical_spec(("batch",), (8,), mesh, rules) == P(("data", "model"))
    # 8 % (4*2) == 0 but 4 % 8 != 0 -> greedy drop of the leading axis
    assert logical_spec(("batch",), (4,), mesh, rules) == P("model")


MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.configs.base import SHAPES, ShapeConfig, get_config
from repro.launch.steps import build_cell

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
shape = ShapeConfig("mini_train", 64, 8, "train")
cell = build_cell("qwen3-1.7b", shape, mesh,
                  overrides=dict(n_layers=2, d_model=64, n_heads=4,
                                 n_kv_heads=2, head_dim=16, d_ff=128,
                                 vocab_size=256, param_dtype="float32",
                                 compute_dtype="float32", remat=False))
compiled = cell.lower().compile()
mem = compiled.memory_analysis()
print(json.dumps({"ok": True,
                  "args_bytes": mem.argument_size_in_bytes,
                  "n_devices": mesh.size}))
"""


def test_mini_dryrun_on_8_devices(tmp_path):
    """End-to-end: build_cell -> lower -> compile on a real (2,4) mesh in a
    subprocess (the test process itself must keep 1 device)."""
    script = tmp_path / "mini.py"
    script.write_text(MINI_DRYRUN)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] and rec["n_devices"] == 8
