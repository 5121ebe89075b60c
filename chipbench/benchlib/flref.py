"""Plain reference of Apodotiko's client training and aggregation (paper
Algorithm 1 and 2, §III-B), independent of the program under test.

Local training, per client: ``steps = max(ceil(n_i / B) * E, 1)`` Adam
steps (Kingma and Ba, lr, beta 0.9/0.999, eps 1e-8) from the weights the
dispatch sent, each on a minibatch of B sample indices drawn uniformly from
the client's n_i samples with ``jax.random.randint`` on a key split off the
lane key. The lane keys of the d-th dispatch are ``split(sub_d, Kp)`` where
``key_d, sub_d = split(key_{d-1})`` from ``PRNGKey(key_seed)`` and Kp is the
dispatch's client count rounded up to a power of two (at least 2). The mean
loss of a client is the mean over its steps of the minibatch loss before
each step.

Aggregation at the close of round T: every landed, not yet aggregated
result trained in round t <= T with ``T - t <= max_staleness`` and landed
by the close joins, weighted by ``n_i / sqrt(T - t + 1)`` and normalised;
the new global weights are the weighted sum of the joined results' trained
weights (float64 here).

The schedule (who was dispatched when, when each result landed, when each
round closed) is held to a record of its own (``schedules/<cell>.json``);
this module computes every number from the seed, the data and, for a
dispatch after the first round, the weights that dispatch was sent.
"""
from __future__ import annotations

import functools
import json
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8
#: relative tolerance of a simulated time in the schedule comparison
TIME_RTOL = 1e-9


@dataclass
class Schedule:
    """A run's schedule: the dispatches in order, every invocation and
    every aggregation's round, close and count of joined results."""

    dispatches: list[tuple[int, list[int]]] = field(default_factory=list)
    #: (client, round, t_invoked, t_completed, cold, failed, cancelled)
    invocations: list[tuple] = field(default_factory=list)
    closes: list[tuple[int, float, int]] = field(default_factory=list)

    def landed(self) -> dict[tuple[int, int], float]:
        return {(c, r): t for c, r, _, t, _, failed, cancelled
                in self.invocations if not (failed or cancelled)}

    def upto(self, rounds: int) -> "Schedule":
        """The part of the schedule of rounds before ``rounds``."""
        return Schedule(
            dispatches=[d for d in self.dispatches if d[0] < rounds],
            invocations=[i for i in self.invocations if i[1] < rounds],
            closes=[c for c in self.closes if c[0] < rounds])

    def to_json(self) -> dict:
        return {"dispatches": [[r, list(c)] for r, c in self.dispatches],
                "invocations": [list(i) for i in self.invocations],
                "closes": [list(c) for c in self.closes]}

    @classmethod
    def from_json(cls, d: dict) -> "Schedule":
        return cls(dispatches=[(int(r), [int(x) for x in c])
                               for r, c in d["dispatches"]],
                   invocations=[(int(c), int(r), float(a), float(b),
                                 bool(cold), bool(f), bool(x))
                                for c, r, a, b, cold, f, x
                                in d["invocations"]],
                   closes=[(int(r), float(t), int(n))
                           for r, t, n in d["closes"]])


def schedule_mismatch(got: Schedule, want: Schedule) -> int:
    """Entries in which two schedules differ: each dispatch (round and
    clients in order), each invocation (client, round, times, cold start,
    failure) and each close (round, time, results joined), plus any entry
    that one has and the other lacks."""
    def same(a, b):
        a, b = list(_flat(a)), list(_flat(b))
        return len(a) == len(b) and all(
            (abs(x - y) <= TIME_RTOL * max(abs(x), abs(y), 1.0))
            if isinstance(x, float) or isinstance(y, float) else x == y
            for x, y in zip(a, b))

    bad = 0
    for xs, ys in ((got.dispatches, want.dispatches),
                   (got.invocations, want.invocations),
                   (got.closes, want.closes)):
        bad += abs(len(xs) - len(ys))
        bad += sum(not same(x, y) for x, y in zip(xs, ys))
    return bad


def _flat(xs):
    for x in xs:
        if isinstance(x, (list, tuple)):
            yield from _flat(x)
        else:
            yield x


def lane_count(k: int) -> int:
    kp = 2
    while kp < k:
        kp *= 2
    return kp


def local_steps(n_i, batch: int, epochs: int) -> np.ndarray:
    return np.maximum(np.ceil(np.asarray(n_i) / batch).astype(np.int64)
                      * epochs, 1)


def dispatch_keys(key_seed: int, sizes: list[int]) -> list:
    """The lane keys of each dispatch, in order."""
    key = jax.random.PRNGKey(key_seed)
    out = []
    for k in sizes:
        key, sub = jax.random.split(key)
        out.append(jax.random.split(sub, lane_count(k)))
    return out


def joins(schedule: Schedule, max_staleness: int
          ) -> list[tuple[int, list[tuple[int, int]]]]:
    """The results each close joins, by the rule above: (round, joined)."""
    landed = schedule.landed()
    done: set = set()
    out = []
    for T, t_end, _ in schedule.closes:
        joined = sorted(r for r, t in landed.items()
                        if t <= t_end and r not in done and r[1] <= T
                        and T - r[1] <= max_staleness)
        done.update(joined)
        out.append((T, joined))
    return out


def eq2(trained: dict, T: int, joined: list, n, names: list[str],
        dtype=np.float64) -> dict:
    """The Eq. 2 average, in ``dtype``, of ``trained[(client, round)]``."""
    w = np.array([n[c] / math.sqrt(T - r + 1) for c, r in joined],
                 np.float64)
    w = (w / w.sum()).astype(dtype)
    out = {}
    for name in names:
        acc = np.zeros(np.shape(trained[joined[0]][name]), dtype)
        for wi, r in zip(w, joined):
            acc = acc + wi * np.asarray(trained[r][name]).astype(dtype)
        out[name] = acc.astype(np.float64)
    return out


@functools.lru_cache(maxsize=8)
def _block_fn(arch_mod, arch_json: str, batch, lr, precision, dtype,
              frozen: bool = False):
    """One compiled block trainer per (architecture, settings);
    ``frozen`` leaves the weights where each step found them."""
    arch = json.loads(arch_json)

    def one(p0, xc, yc, n_i, steps, key):
        zeros = jax.tree.map(jnp.zeros_like, p0)

        def body(s, carry):
            p, m, v, key, acc = carry
            key, k = jax.random.split(key)
            idx = jax.random.randint(k, (batch,), 0, jnp.maximum(n_i, 1))
            l, g = jax.value_and_grad(arch_mod.loss)(
                p, xc[idx], yc[idx], arch, precision, dtype)
            t = (s + 1).astype(jnp.float32)
            m = jax.tree.map(lambda m_, g_: B1 * m_ + (1 - B1) * g_, m, g)
            v = jax.tree.map(lambda v_, g_: B2 * v_ + (1 - B2) * g_ * g_,
                             v, g)
            if not frozen:
                p = jax.tree.map(
                    lambda p_, m_, v_: p_ - lr * (m_ / (1 - B1 ** t))
                    / (jnp.sqrt(v_ / (1 - B2 ** t)) + EPS), p, m, v)
            return p, m, v, key, acc + l

        p, _, _, _, acc = jax.lax.fori_loop(
            0, steps, body, (p0, zeros, zeros, key, jnp.float32(0.0)))
        return p, acc / jnp.maximum(steps, 1).astype(jnp.float32)

    return jax.jit(jax.vmap(one, in_axes=(None, 0, 0, 0, 0, 0)))


def block_size(n_params: int, budget_bytes: float = 2e9) -> int:
    """Clients trained together: as many as keep their weights, Adam
    moments, gradients and the spare copies within ``budget_bytes``."""
    return int(max(1, min(64, budget_bytes // (24 * n_params))))


def train(start: dict, clients: list[int], keys, data, *, arch_mod,
          arch: dict, batch: int, epochs: int, lr: float, precision,
          dtype=jnp.float32, frozen: bool = False
          ) -> tuple[np.ndarray, dict[str, np.ndarray]]:
    """Each client's local training from ``start`` with its lane key:
    (mean losses [K], trained weights {leaf: [K, ...]})."""
    names = [n for n, _, _ in arch_mod.param_shapes(arch)]
    block = _block_fn(arch_mod, json.dumps(arch, sort_keys=True), batch, lr,
                      precision, dtype, frozen)
    most = block_size(arch_mod.n_params(arch))
    n_i = np.asarray(data.n[clients])
    steps = local_steps(n_i, batch, epochs)
    start = {n: jnp.asarray(start[n], jnp.float32) for n in names}
    losses, trained = [], {n: [] for n in names}
    for lo in range(0, len(clients), most):
        sel = list(range(lo, min(lo + most, len(clients))))
        pad = sel + [sel[-1]] * (min(most, lane_count(len(clients)))
                                 - len(sel))
        ids = [clients[j] for j in pad]
        p_out, l_out = block(
            start, jnp.asarray(data.X[ids]), jnp.asarray(data.y[ids]),
            jnp.asarray(n_i[pad], jnp.int32),
            jnp.asarray(steps[pad], jnp.int32),
            keys[jnp.asarray(pad)])
        losses.append(np.asarray(l_out)[:len(sel)])
        for n in names:
            trained[n].append(np.asarray(p_out[n])[:len(sel)])
    return (np.concatenate(losses),
            {n: np.concatenate(v) for n, v in trained.items()})


def first_grad(start: dict, client: int, key, data, *, arch_mod,
               arch: dict, batch: int, precision) -> np.ndarray:
    """Per-leaf norms of a client's first gradient, for the leaf rule."""
    names = [n for n, _, _ in arch_mod.param_shapes(arch)]
    idx = jax.random.randint(jax.random.split(key)[1], (batch,), 0,
                             max(int(data.n[client]), 1))
    g = jax.grad(arch_mod.loss)(
        {n: jnp.asarray(start[n]) for n in names},
        jnp.asarray(data.X[client])[idx], jnp.asarray(data.y[client])[idx],
        arch, precision, jnp.float32)
    return np.array([float(jnp.linalg.norm(g[n].ravel())) for n in names])


def counted_leaves(grad_norms: np.ndarray) -> np.ndarray:
    """A leaf counts where the reference's first gradient of it is at
    least a thousandth of the median leaf's, so that a leaf only round-off
    moves does not decide a gap (none of the CNNs' leaves falls under it)."""
    return grad_norms >= 1e-3 * np.median(grad_norms)


def _norms(tree: dict, names: list[str]) -> np.ndarray:
    return np.array([np.linalg.norm(np.asarray(tree[n], np.float64).ravel())
                     for n in names])


def client_gaps(prog_loss: float, prog: dict, ref_loss: float, ref: dict,
                start: dict, names: list[str], counted: np.ndarray,
                loss_scale: float) -> dict[str, float]:
    """One client's gaps between the program's result and the reference's:

    * ``loss``: |mean loss difference| over ``max(|ref|, loss_scale)``;
    * ``update``: worst counted leaf's gap between the norms of the two
      changes from ``start``, over the larger of the reference's norm of
      that leaf and of the median leaf;
    * ``diff``: worst counted leaf's norm of the difference between the
      two trained weights, over the same."""
    dp = _norms({n: np.asarray(prog[n], np.float64)
                 - np.asarray(start[n], np.float64) for n in names}, names)
    dr = _norms({n: np.asarray(ref[n], np.float64)
                 - np.asarray(start[n], np.float64) for n in names}, names)
    dd = _norms({n: np.asarray(prog[n], np.float64)
                 - np.asarray(ref[n], np.float64) for n in names}, names)
    den = np.maximum(dr, np.median(dr))
    return {"loss": abs(prog_loss - ref_loss) / max(abs(ref_loss),
                                                     loss_scale),
            "update": float(np.max((np.abs(dp - dr) / den)[counted])),
            "diff": float(np.max((dd / den)[counted]))}


def summarize(gaps: list[dict[str, float]]) -> dict[str, float]:
    """Worst and median client over every compared result:
    ``loss_gap``, ``update_gap``, ``update_diff`` and their ``_med``."""
    out = {}
    for key, name in (("loss", "loss_gap"), ("update", "update_gap"),
                      ("diff", "update_diff")):
        v = np.array([g[key] for g in gaps])
        out[name] = float(np.max(v))
        out[name + "_med"] = float(np.median(v))
    return out


def change_gap(prog: dict, ref: dict, base: dict, names: list[str],
               counted: np.ndarray) -> float:
    """Worst counted leaf's gap between the norms of two changes from
    ``base``, over the larger of the reference's and the median leaf's."""
    dp = _norms({n: np.asarray(prog[n], np.float64)
                 - np.asarray(base[n], np.float64) for n in names}, names)
    dr = _norms({n: np.asarray(ref[n], np.float64)
                 - np.asarray(base[n], np.float64) for n in names}, names)
    den = np.maximum(dr, np.median(dr))
    return float(np.max((np.abs(dp - dr) / den)[counted]))


def agg_gap(got: dict, want: dict, base: dict, names: list[str]) -> float:
    """Worst leaf's ``|got - want| / |want - base|``: an aggregate's error
    over the change it makes from ``base``."""
    return float(max(
        np.linalg.norm(np.asarray(got[n], np.float64) - want[n])
        / max(np.linalg.norm(want[n] - np.asarray(base[n], np.float64)),
              1e-30) for n in names))
