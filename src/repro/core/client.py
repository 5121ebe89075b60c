"""Client_Update (paper Algorithm 1, lines 14-23) — real JAX local training.

On real hardware every client is an independent FaaS function. In the
simulator the *learning* is real but executed cohort-vectorized: the local
SGD/Adam loop of every client invoked at the same simulated instant runs
under one ``vmap``, in one loop that runs as many steps as the cohort's
longest client (read on the device, so no step count is compiled in),
with per-client step masking. Simulated durations come from the hardware
model, so the timing behaviour matches per-client execution while the
host does one batched computation (a beyond-paper systems optimization,
DESIGN.md §2).

Two data planes feed the cohort fn (DESIGN.md §2, ``core.data_plane``):

  * **device** (default): the fn takes a ``[Kp] int32`` client-index
    vector plus the ``DatasetStore``'s resident buffers and gathers each
    minibatch on device inside the jit — zero H2D training-input bytes
    per dispatch;
  * **host** (oracle): the padded ``[Kp, N_max, ...]`` cohort arrays are
    fancy-indexed on host and uploaded every dispatch (the pre-data-plane
    behaviour; ``data_h2d_bytes`` counts the uploads).

Supports the baseline strategies' client-side modifications:
  - FedProx: proximal term  mu/2 ||w - w_global||^2
  - SCAFFOLD: control-variate-corrected gradients + c_i update
"""
from __future__ import annotations

import functools
import itertools
import math
import os
import weakref
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.update_store import scatter_rows
from repro.optim import apply_updates, build_optimizer
from repro.sharding import flmesh

Pytree = Any


def _l2_sq(a: Pytree, b: Pytree) -> jax.Array:
    return sum(jnp.sum(jnp.square(x - y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _bucket(x: int, floor: int) -> int:
    """Round up to the next power-of-two multiple of ``floor``."""
    b = max(int(floor), 1)
    while b < x:
        b *= 2
    return b


# Cohort sizes bucket to powers of two: the cohort floor is 2, so solo
# dispatches (a K=1 reinforcement or re-invocation) run 2 padded lanes and
# mixed selection sizes compile O(log K) variants.
DEFAULT_COHORT_FLOOR = 2


def cohort_bucket_floor() -> int:
    """The cohort-size bucket floor (``REPRO_COHORT_FLOOR``, default 2)."""
    return int(os.environ.get("REPRO_COHORT_FLOOR", DEFAULT_COHORT_FLOOR))


# Compiled cohort-train fns shared across Controller instances (strategies
# reuse identical trainer configs; compiles are expensive on the 1-core host).
_COMPILE_CACHE: dict[tuple, Any] = {}

# Cache keys must identify the *model object* the compiled fn closed over.
# ``id(model)`` is unsafe: ids are recycled after GC, so a new model at a
# reused address would be served a stale compiled fn. A weak-keyed token is
# stable for the object's lifetime and never reused afterwards, while still
# letting every trainer built around the same shared model object (sweep
# engine, benchmarks) hit the same compiled entry.
_MODEL_TOKENS: "weakref.WeakKeyDictionary[Any, int]" = weakref.WeakKeyDictionary()
_TOKEN_COUNTER = itertools.count()


def _model_token(model) -> int:
    tok = _MODEL_TOKENS.get(model)
    if tok is None:
        tok = next(_TOKEN_COUNTER)
        _MODEL_TOKENS[model] = tok
    return tok


class CohortTrainer:
    """Vectorized local training over a cohort sharing one model/optimizer."""

    def __init__(self, model, *, optimizer: str, lr: float, batch_size: int,
                 prox_mu: float = 0.0, scaffold: bool = False, seed: int = 0,
                 cohort_floor: Optional[int] = None, mesh=None):
        self.model = model
        self.opt = build_optimizer(optimizer, lr)
        self.lr = lr
        self.batch_size = batch_size
        self.prox_mu = prox_mu
        self.scaffold = scaffold
        self.mesh = mesh
        floor = (cohort_bucket_floor() if cohort_floor is None
                 else int(cohort_floor))
        if mesh is not None:
            # every cohort bucket must split evenly over the "data" axis
            # (shard_map needs Kp % data == 0); power-of-two bucketing
            # preserves multiples of the floor, so lifting the floor to
            # lcm(floor, data) makes every Kp divisible
            floor = math.lcm(floor, flmesh.mesh_axes(mesh)[0])
        self.cohort_floor = floor
        self._key = jax.random.PRNGKey(seed)
        self.data_h2d_bytes = 0   # training-input bytes uploaded (host plane)

    # ----------------------------------------------------------- single fn
    def _make_fn(self, flat_updates: bool = False, indexed: bool = False):
        model, opt = self.model, self.opt
        B, mu, use_cv, lr = self.batch_size, self.prox_mu, self.scaffold, self.lr

        def local_train(params0, fetch, n_i, steps, key, cg, ci, trips):
            # ``fetch(idx) -> (x, y)`` abstracts the minibatch gather: the
            # host plane indexes this lane's [N_max, ...] slice, the device
            # plane gathers straight out of the resident [M, N_max, ...]
            # buffers — identical values, so the planes stay bit-identical.
            # ``trips`` is the cohort's longest step count, one unbatched
            # value, so the vmapped loop stays a single ``while``.
            opt_state = opt.init(params0)

            def body(s, carry):
                params, opt_state, key, loss_sum = carry
                key, k = jax.random.split(key)
                with jax.named_scope("cohort.gather"):
                    idx = jax.random.randint(k, (B,), 0, jnp.maximum(n_i, 1))
                    bx, by = fetch(idx)
                batch = {"x": bx, "y": by}

                def loss_fn(p):
                    l, _ = model.loss(p, batch)
                    if mu > 0:
                        l = l + 0.5 * mu * _l2_sq(p, params0)
                    return l

                with jax.named_scope("cohort.fwd_bwd"):
                    loss, grads = jax.value_and_grad(loss_fn)(params)
                if use_cv:
                    grads = jax.tree.map(lambda g, a, b: g - a + b, grads, ci, cg)
                with jax.named_scope("cohort.optimizer"):
                    upd, new_opt = opt.update(grads, opt_state, params)
                    newp = apply_updates(params, upd)
                # a lane past its own step count keeps its state: the
                # step ran and is thrown away (``train.useful_steps``)
                with jax.named_scope("cohort.mask"):
                    active = s < steps
                    sel = lambda a, b: jnp.where(active, a, b)
                    params = jax.tree.map(sel, newp, params)
                    opt_state = jax.tree.map(sel, new_opt, opt_state)
                    loss_sum = loss_sum + jnp.where(active, loss, 0.0)
                return params, opt_state, key, loss_sum

            params, _, _, loss_sum = jax.lax.fori_loop(
                0, trips, body, (params0, opt_state, key, jnp.float32(0.0)))
            mean_loss = loss_sum / jnp.maximum(steps, 1)
            if use_cv:
                # c_i' = c_i - c + (w0 - w) / (K * lr)
                denom = jnp.maximum(steps, 1).astype(jnp.float32) * lr
                ci_new = jax.tree.map(
                    lambda c, g, p0, p: c - g + (p0 - p) / denom,
                    ci, cg, params0, params)
            else:
                ci_new = ci
            return params, ci_new, mean_loss

        if indexed:
            # Device data plane: per-lane client index into the resident
            # dataset buffers (unbatched jit args — never re-uploaded, never
            # baked into the program as constants). The lane slices its
            # client's rows ONCE before the loop — a device-device gather —
            # so the per-step minibatch gather sees the same lane-local
            # operand as the host path (a per-step two-level gather from
            # the full buffer lowers to a slow batched-gather on XLA CPU).
            def client_fn(params0, cidx, n_i, steps, key, cg, ci, DX, Dy,
                          trips):
                Xl, yl = DX[cidx], Dy[cidx]
                return local_train(
                    params0, lambda idx: (Xl[idx], yl[idx]),
                    n_i, steps, key, cg, ci, trips)

            v = jax.vmap(client_fn,
                         in_axes=(None, 0, 0, 0, 0, None, 0, None, None,
                                  None))
            if self.mesh is not None:
                # Shard the cohort batch over the "data" axis: each device
                # vmaps its Kp/data lanes against the replicated dataset
                # buffers, so per-lane train work and minibatch gathers are
                # shard-local. Per-lane training is independent, so each
                # lane's outputs are the same values the unsharded vmap
                # produces — only aggregation reassociates floats. The
                # trip count is the whole cohort's, replicated, so every
                # shard loops as far as the unsharded program.
                #
                # The [Kp, 2] lane-key table enters REPLICATED (P()) and
                # each shard slices its own lane block below, so the key
                # values never depend on how the partitioner splits a
                # ``jax.random.split`` traced in the same program (the
                # fused megastep scan). With ``jax_threefry_partitionable``
                # off (on by default in the installed JAX) a sharded split
                # would change them and break the fused/stepwise
                # bit-identity contract.
                from jax.sharding import PartitionSpec as P
                vv = v

                def _shard_body(params0, cidx, n_i, steps, keys, cg, ci,
                                DX, Dy, trips):
                    kp_l = cidx.shape[0]      # this shard's lane count
                    start = jax.lax.axis_index("data") * kp_l
                    keys_l = jax.lax.dynamic_slice_in_dim(keys, start, kp_l)
                    return vv(params0, cidx, n_i, steps, keys_l, cg, ci,
                              DX, Dy, trips)

                v = jax.shard_map(
                    _shard_body, mesh=self.mesh,
                    in_specs=(P(), P("data"), P("data"), P("data"),
                              P(), P(), P("data"), P(), P(), P()),
                    out_specs=(P("data"), P("data"), P("data")),
                    check_vma=False)
            n_lead, steps_at = 9, 3
        else:
            def client_fn(params0, X, y, n_i, steps, key, cg, ci, trips):
                return local_train(params0, lambda idx: (X[idx], y[idx]),
                                   n_i, steps, key, cg, ci, trips)

            v = jax.vmap(client_fn,
                         in_axes=(None, 0, 0, 0, 0, 0, None, 0, None))
            n_lead, steps_at = 8, 4

        def cohort(*lead):
            return v(*lead, jnp.max(lead[steps_at]))

        if not flat_updates:
            return jax.jit(cohort)

        # Update-plane mode: the trained cohort never leaves the device —
        # inside the same jitted program each [K, ...] output leaf lands in
        # its column stripe of the UpdateStore buffer rows (canonical
        # jax.tree.leaves order, the RavelSpec contract; tail pad lanes
        # zeroed). The buffer is *donated* and the chained aliased scatters
        # are in-place writes: zero host round-trips, no buffer copy, no
        # concatenated [K, W] intermediate.
        def cohort_flat(*args):
            lead, (buffer, row_ids) = args[:n_lead], args[n_lead:]
            out_params, ci_new, losses = cohort(*lead)
            with jax.named_scope("cohort.scatter_rows"):
                buffer = scatter_rows(buffer, row_ids,
                                      jax.tree.leaves(out_params))
            return buffer, ci_new, losses

        return jax.jit(cohort_flat, donate_argnums=(n_lead,))

    # ------------------------------------------------------------- helpers
    def _pad_variates(self, global_params, c_global, c_clients, Kp, K):
        """Broadcastable zero trees when SCAFFOLD is off; zero-padded
        [Kp, ...] stacked variates when on (pad lanes run 0 steps)."""
        if c_global is None:
            c_global = jax.tree.map(lambda p: jnp.zeros((), p.dtype),
                                    global_params)
            c_clients = jax.tree.map(
                lambda p: jnp.zeros((Kp,) + (1,) * p.ndim, p.dtype),
                global_params)
        elif c_clients is not None and Kp != K:
            c_clients = jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.zeros((Kp - K,) + a.shape[1:], a.dtype)], axis=0),
                c_clients)
        return c_global, c_clients

    def _cohort_keys(self, Kp):
        self._key, sub = jax.random.split(self._key)
        return jax.random.split(sub, Kp)

    def _compiled(self, cache_key, flat_updates, indexed):
        if cache_key not in _COMPILE_CACHE:
            _COMPILE_CACHE[cache_key] = self._make_fn(
                flat_updates=flat_updates, indexed=indexed)
        return _COMPILE_CACHE[cache_key]

    def _config_key(self) -> tuple:
        return (_model_token(self.model), self.opt.name, self.lr,
                self.batch_size, self.prox_mu, self.scaffold,
                *flmesh.mesh_token(self.mesh))

    # --------------------------------------------------------------- train
    def train_cohort(self, global_params: Pytree, X: np.ndarray, y: np.ndarray,
                     n_i: np.ndarray, steps: np.ndarray,
                     c_global: Optional[Pytree] = None,
                     c_clients: Optional[Pytree] = None, *,
                     update_sink=None, round_: int = -1):
        """Host data plane: X [K, N_max, ...], y [K, N_max], n_i/steps [K]
        are uploaded per dispatch (counted in ``data_h2d_bytes``).
        Returns (params [K, ...] stacked, c_clients', mean losses [K]).

        With ``update_sink`` (an ``UpdateStore``) the trained client models
        instead stay on device: the jitted cohort fn flattens them to
        [K, W] fp32 rows (RavelSpec leaf order) and scatters them into the
        sink's donated buffer in the same program; the first return value
        is then the [K] allocated row ids. ``round_`` only labels the
        dispatch's spans."""
        flat_updates = update_sink is not None
        K = X.shape[0]
        # pad the cohort to a power-of-two bucket: one compile serves every
        # selection size in the bucket (padded entries run 0 active steps)
        Kp = _bucket(K, self.cohort_floor)
        if Kp != K:
            padt = lambda a: np.concatenate(
                [a, np.repeat(a[-1:], Kp - K, axis=0)], axis=0)
            X, y = padt(np.asarray(X)), padt(np.asarray(y))
            n_i = padt(np.asarray(n_i))
            steps = np.concatenate([steps, np.zeros(Kp - K, steps.dtype)])
        trips = int(steps.max())
        with obs.span("fl.train", round=round_, lanes=Kp, clients=K,
                      steps=trips):
            cache_key = self._config_key() + (Kp, X.shape[1:], y.dtype,
                                              flat_updates, "host")
            fn = self._compiled(cache_key, flat_updates, indexed=False)
            keys = self._cohort_keys(Kp)
            c_global, c_clients = self._pad_variates(global_params, c_global,
                                                     c_clients, Kp, K)
            X, y = np.asarray(X), np.asarray(y)
            self.data_h2d_bytes += X.nbytes + y.nbytes
            lead = (global_params, jnp.asarray(X), jnp.asarray(y),
                    jnp.asarray(n_i), jnp.asarray(steps), keys, c_global,
                    c_clients)
            return self._run(fn, lead, update_sink, K, Kp, trips, steps,
                             round_)

    def train_cohort_indexed(self, global_params: Pytree, store,
                             selection, n_i: np.ndarray, steps: np.ndarray,
                             c_global: Optional[Pytree] = None,
                             c_clients: Optional[Pytree] = None, *,
                             update_sink=None, round_: int = -1):
        """Device data plane: the cohort is a ``[K]`` vector of client
        indices into ``store`` (a ``DatasetStore``); every minibatch is
        gathered out of the resident buffers inside the jit — zero H2D
        training-input bytes. Pad lanes repeat the last index (mirroring
        the host path's row repeat) and run 0 active steps. The compile
        cache collapses to (cohort bucket, flat_updates): data shapes are
        fixed for the store's lifetime, and the step count is read on the
        device."""
        flat_updates = update_sink is not None
        sel = np.asarray(selection, np.int32)
        n_i = np.asarray(n_i)
        K = len(sel)
        Kp = _bucket(K, self.cohort_floor)
        if Kp != K:
            sel = np.concatenate([sel, np.repeat(sel[-1:], Kp - K)])
            n_i = np.concatenate([n_i, np.repeat(n_i[-1:], Kp - K)])
            steps = np.concatenate([steps, np.zeros(Kp - K, steps.dtype)])
        trips = int(steps.max())
        with obs.span("fl.train", round=round_, lanes=Kp, clients=K,
                      steps=trips):
            cache_key = self._config_key() + (Kp, store.X.shape[1:],
                                              store.y.dtype, flat_updates,
                                              "device")
            fn = self._compiled(cache_key, flat_updates, indexed=True)
            keys = self._cohort_keys(Kp)
            c_global, c_clients = self._pad_variates(global_params, c_global,
                                                     c_clients, Kp, K)
            lead = (global_params, jnp.asarray(sel), jnp.asarray(n_i),
                    jnp.asarray(steps), keys, c_global, c_clients,
                    store.X, store.y)
            return self._run(fn, lead, update_sink, K, Kp, trips, steps,
                             round_)

    def cohort_fn_indexed(self, store, K: int, max_steps: int):
        """The compiled indexed-flat cohort fn for ``K`` clients whose
        longest runs ``max_steps`` steps -> ``(fn, Kp, max_steps)``; one fn
        serves every step count of the lane bucket. Same cache key
        construction as ``train_cohort_indexed`` with
        ``flat_updates=True``, so the fused round megastep
        (``core.megastep``) calls through the IDENTICAL compiled entry the
        stepwise path dispatches — jit-in-jit inlines it into the scan body
        with the same traced ops."""
        Kp = _bucket(K, self.cohort_floor)
        cache_key = self._config_key() + (Kp, store.X.shape[1:],
                                          store.y.dtype, True, "device")
        fn = self._compiled(cache_key, flat_updates=True, indexed=True)
        return fn, Kp, int(max_steps)

    def count_lane_steps(self, params: Pytree, Kp: int, K: int, trips: int,
                         useful: int) -> None:
        """Count dispatches of ``K`` clients on ``Kp`` lanes whose loops ran
        ``trips`` steps in all (each dispatch loops to its longest
        client), ``useful`` of whose steps belong to a real client's own
        step count: ``train.lane_steps`` (every lane-step the program
        runs), ``train.real_lane_steps`` (those of real clients),
        ``train.useful_steps``, and, where the optimizer's kernel knows
        its traffic (``Optimizer.step_bytes``: the fused Adam kernel),
        ``train.adam_bytes``, the HBM bytes of its updates."""
        lane_steps = Kp * trips
        obs.count("train.lane_steps", lane_steps)
        obs.count("train.real_lane_steps", K * trips)
        obs.count("train.useful_steps", int(useful))
        if self.opt.step_bytes is not None:
            n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
            obs.count("train.adam_bytes", lane_steps * self.opt.step_bytes(n))

    def _run(self, fn, lead, update_sink, K, Kp, trips, steps, round_):
        """Launch a padded cohort; the losses are its one blocking read."""
        self.count_lane_steps(lead[0], Kp, K, trips, int(steps.sum()))
        trim = lambda t: jax.tree.map(lambda a: a[:K], t)
        if update_sink is None:
            out_params, ci_new, losses = fn(*lead)
            out = trim(out_params)
        else:
            # padded cohort entries run 0 active steps, so their rows hold
            # the unchanged global model — written then recycled right away
            ids = update_sink.alloc(Kp)
            new_buffer, ci_new, losses = fn(*lead, update_sink.buffer,
                                            jnp.asarray(ids))
            update_sink.buffer = new_buffer
            if Kp != K:
                update_sink.free(ids[K:])
            out = ids[:K]
        ci_new = trim(ci_new)
        with obs.sync("cohort_losses", round=round_):
            losses = np.asarray(losses)[:K]
        return out, ci_new, losses
