"""Step-function builders for training/serving under pjit + sharding specs.

Everything needed to lower one (arch x shape x mesh) cell:
  - ``build_cell``: abstract params/opt-state/batch/caches + their
    NamedShardings derived from the logical-axes trees;
  - train_step (fwd + bwd + optimizer), prefill (logits tail + cache build),
    serve_step (one decode token against a full cache).

Variants (used by the §Perf hillclimbs) are config transforms applied before
lowering — e.g. remat on/off, ZeRO-1 on/off, alternative rule tables.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig, get_config
from repro.models import build_model, input_specs
from repro.models.common import map_axes
from repro.optim import apply_updates, build_optimizer
from repro.sharding.rules import (
    DECODE_RULES,
    DEFAULT_RULES,
    LONGCTX_RULES,
    axis_rules,
    logical_spec,
    zero1_extend,
)

Pytree = Any


def rules_for(shape: ShapeConfig) -> dict:
    if shape.kind != "decode":
        return dict(DEFAULT_RULES)
    if shape.global_batch == 1:
        return dict(LONGCTX_RULES)
    return dict(DECODE_RULES)


def opt_state_axes(opt_name: str, axes_tree: Pytree) -> Pytree:
    """Logical axes for the optimizer state, mirroring the param axes."""
    if opt_name == "sgd":
        return {}
    if opt_name == "momentum":
        return {"m": axes_tree}
    if opt_name == "adam":
        return {"m": axes_tree, "v": axes_tree, "t": ()}
    if opt_name == "adafactor":
        def one(a):
            a = tuple(a)
            if len(a) >= 2:
                return {"row": a[:-1], "col": a[:-2] + a[-1:]}
            return {"v": a}
        return {"s": map_axes(axes_tree, one), "t": ()}
    raise ValueError(opt_name)


def specs_from_axes(axes_tree: Pytree, shapes_tree: Pytree, mesh: Mesh,
                    rules: dict, *, zero1: bool = False) -> Pytree:
    is_leaf = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)

    def one(names, arr):
        spec = logical_spec(names, arr.shape, mesh, rules)
        if zero1:
            spec = zero1_extend(spec, arr.shape, mesh, "data")
        return NamedSharding(mesh, spec)

    return jax.tree.map(one, axes_tree, shapes_tree, is_leaf=is_leaf)


@dataclass
class Cell:
    """One lowered (arch x shape x mesh) combination, pre-lowering."""

    arch: str
    cfg: ModelConfig
    shape: ShapeConfig
    mesh: Mesh
    rules: dict
    fn: Any                 # the function to jit
    in_args: tuple          # ShapeDtypeStructs
    in_shardings: tuple
    out_shardings: Any
    kind: str

    def lower(self):
        jitted = jax.jit(self.fn, in_shardings=self.in_shardings,
                         out_shardings=self.out_shardings)
        return jitted.lower(*self.in_args)


def _abstract_params(model, seed: int = 0):
    rng = jax.random.PRNGKey(seed)
    return jax.eval_shape(lambda r: model.init(r)[0], rng)


def _param_axes(cfg: ModelConfig):
    """Axes tree via a smoke-size init of the same family (tree topology and
    per-leaf logical axes are config-size independent)."""
    smoke = get_config(cfg.name, smoke=True)
    model = build_model(smoke)
    _, axes = model.init(jax.random.PRNGKey(0))
    return axes


def _build_cache(model, cfg: ModelConfig, B: int, S: int):
    if cfg.family == "encdec":
        return model.cache_struct(B, S, S)
    return model.cache_struct(B, S)


def build_cell(arch: str, shape: ShapeConfig, mesh: Mesh, *,
               overrides: Optional[dict] = None,
               rules_override: Optional[dict] = None,
               variant: str = "baseline") -> Cell:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.with_(**overrides)
    model = build_model(cfg)
    rules = rules_override or rules_for(shape)
    params = _abstract_params(model)
    p_axes = _param_axes(cfg)
    p_shard = specs_from_axes(p_axes, params, mesh, rules)
    B, S = shape.global_batch, shape.seq_len

    if shape.kind == "flround":
        # The paper's aggregation step on the pod: K client updates (stacked
        # on a 'cohort' axis sharded over data) -> staleness-weighted global
        # model. The weighted reduce lowers to a psum over the data axis —
        # the FaaS aggregation function mapped onto TPU collectives.
        K = shape.global_batch
        rules = dict(rules)
        rules["cohort"] = "data"
        upd = jax.tree.map(lambda s: jax.ShapeDtypeStruct((K,) + s.shape,
                                                          s.dtype), params)
        u_axes = map_axes(p_axes, lambda a: ("cohort",) + tuple(a))
        u_shard = specs_from_axes(u_axes, upd, mesh, rules)
        w = jax.ShapeDtypeStruct((K,), jnp.float32)
        w_shard = NamedSharding(mesh, P())

        if variant == "scatter_bf16":
            # perf iteration #5: explicit shard_map reduction — local fp32
            # partial sums, then a bf16-wire psum over the data axis (half
            # the all-reduce bytes; precision equals the bf16 storage dtype
            # of the model anyway). Weights ride the same cohort sharding.
            w_shard = NamedSharding(mesh, P("data"))
            leaves, treedef = jax.tree.flatten(upd)
            leaf_specs = [s.spec for s in jax.tree.leaves(u_shard)]
            out_specs = [s.spec for s in jax.tree.leaves(p_shard)]

            def fl_aggregate(updates, weights):
                lv = jax.tree.leaves(updates)

                def body(w_local, *xs):
                    outs = []
                    for x in xs:
                        wshape = (x.shape[0],) + (1,) * (x.ndim - 1)
                        part = jnp.sum(
                            x.astype(jnp.float32) * w_local.reshape(wshape)
                            .astype(jnp.float32), axis=0)
                        outs.append(jax.lax.psum(part.astype(jnp.bfloat16),
                                                 "data").astype(x.dtype))
                    return tuple(outs)

                outs = jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(P("data"),) + tuple(leaf_specs),
                    out_specs=tuple(out_specs),
                    check_vma=False)(weights, *lv)
                return jax.tree.unflatten(treedef, outs)
        else:
            def fl_aggregate(updates, weights):
                with axis_rules(mesh, rules):
                    wf = weights.astype(jnp.float32)

                    def one(x):
                        # broadcast-multiply + sum over the cohort axis: keeps
                        # every non-cohort dim's sharding intact and lowers the
                        # reduction to local partials + an all-reduce over the
                        # data axis (a rank-1 tensordot made GSPMD all-gather
                        # the model-sharded dims instead — perf iteration #3)
                        wshape = (x.shape[0],) + (1,) * (x.ndim - 1)
                        out = jnp.sum(x.astype(jnp.float32)
                                      * wf.reshape(wshape), axis=0)
                        return out.astype(x.dtype)

                    return jax.tree.map(one, updates)

        # output the aggregated model ZeRO-sharded over data as well: the
        # cohort reduction lowers to reduce-scatter instead of all-reduce
        # (each pod slice owns a shard of the new global; the next round's
        # broadcast is the all-gather the optimizer needed anyway)
        out_shard = (p_shard if variant == "scatter_bf16" else
                     specs_from_axes(p_axes, params, mesh, rules, zero1=True))
        return Cell(arch, cfg, shape, mesh, rules, fl_aggregate,
                    (upd, w), (u_shard, w_shard), out_shard, "flround")

    if shape.kind == "train":
        opt = build_optimizer(cfg.optimizer, cfg.learning_rate)
        opt_state = jax.eval_shape(opt.init, params)
        o_axes = opt_state_axes(cfg.optimizer, p_axes)
        o_shard = specs_from_axes(o_axes, opt_state, mesh, rules,
                                  zero1=cfg.zero1)
        batch, b_axes = input_specs(cfg, shape)
        b_shard = specs_from_axes(b_axes, batch, mesh, rules)

        def train_step(params, opt_state, batch):
            with axis_rules(mesh, rules):
                (loss, _), grads = jax.value_and_grad(
                    model.loss, has_aux=True)(params, batch)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = apply_updates(params, updates)
            return params, opt_state, loss

        return Cell(arch, cfg, shape, mesh, rules, train_step,
                    (params, opt_state, batch),
                    (p_shard, o_shard, b_shard),
                    (p_shard, o_shard, NamedSharding(mesh, P())), "train")

    if shape.kind == "prefill":
        batch, b_axes = input_specs(cfg, shape)
        b_shard = specs_from_axes(b_axes, batch, mesh, rules)
        cache, c_axes = _build_cache(model, cfg, B, S)
        c_shard = specs_from_axes(c_axes, cache, mesh, rules)

        def prefill(params, batch):
            with axis_rules(mesh, rules):
                logits, caches, _ = model.apply(params, batch, make_cache=True)
                return logits[:, -1:, :], caches

        return Cell(arch, cfg, shape, mesh, rules, prefill,
                    (params, batch), (p_shard, b_shard),
                    (NamedSharding(mesh, P()), c_shard), "prefill")

    # decode: one new token against a cache of length S
    cache, c_axes = _build_cache(model, cfg, B, S)
    c_shard = specs_from_axes(c_axes, cache, mesh, rules)
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    t_shard = specs_from_axes(("batch", None), tokens, mesh, rules)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    scalar = NamedSharding(mesh, P())

    def serve_step(params, caches, tokens, pos):
        with axis_rules(mesh, rules):
            return model.decode_step(params, caches, tokens, pos)

    return Cell(arch, cfg, shape, mesh, rules, serve_step,
                (params, cache, tokens, pos),
                (p_shard, c_shard, t_shard, scalar),
                (scalar, c_shard), "decode")
