"""Fused round megastep: whole quiescent rounds as one jitted ``lax.scan``.

After PRs 2-5 every *plane* is device-resident, but the scheduler still
hops through Python between them each round: select (1 jit dispatch) ->
train (1) -> aggregate (1) -> EMA/booster bookkeeping, plus the event-loop
choreography around them. For rounds that are provably *quiescent* — no
hedge timer can fire, no churn or failure can land, no eval/checkpoint
boundary, every completion of the round lands before anything else could
happen — that Python traffic is pure overhead. This module lowers a run of
R such rounds into ONE jitted program:

    scan over R rounds of:
        scored_topk            (kernels.ops — the same op select_topk jits)
        cohort train           (the same compiled indexed-flat fn the
                                stepwise path dispatches; jit-in-jit inlines)
        aggregate_rows_traced  (kernels.ops — traceable twin of the
                                weighted_aggregate_rows dispatch)
        f32 EMA + booster scatter-update (the FleetStore mirror algebra)

so the steady state is zero Python dispatches per round.

**Bit-identity contract.** The event-driven engine stays the oracle; the
fused path must be bitwise indistinguishable from it. The anchors:

  * selection: the scan carries the FleetStore device score state
    (f32 twin columns, ``_flush_device``) and calls the single
    ``scored_topk`` definition ``select_topk`` jits;
  * training: the scan body calls the *same compiled fn object* out of the
    trainer's compile cache, with identically padded operands and the
    identical ``_cohort_keys`` key-split schedule (the key is a carry);
  * update rows: the scan carries the UpdateStore free-stack and replays
    its LIFO pop/push algebra, so row ids equal what ``alloc`` produces;
  * aggregation: all-current-round Eq.2 weights are integer-valued
    (``s(T,T) = 1``), so the f32 cast-then-normalize in
    ``services.aggregate_round`` is reduction-order independent and the
    in-scan ``jnp.sum`` normalization is bitwise the host one; the kernel
    dispatch predicates are pre-resolved by ``aggregation.rows_dispatch``;
  * landing order: durations are deterministic in the eligible regime
    (variability 0, warm instances), so per-slot completion ranks are
    precomputed and a stable argsort reproduces the event heap's
    (time, schedule-seq) pop order.

After the scan, a **host replay** walks the same R rounds through the REAL
bookkeeping code (``platform.invoke``, ``_launch``, the event loop,
``db.mark_complete``, result records, free-lists) with protocol emission
suppressed and zero device dispatches — the platform RNG draws are
state-advancing but value-deterministic here, so every host structure ends
bit-identical to stepwise execution. Scan-vs-replay cross-checks (row ids,
landing order) raise rather than diverge silently.

``plan_megastep`` is the eligibility check: it admits a round run only
when every condition above is statically provable and otherwise reports
why (``Scheduler.metrics()['megastep_fallback_reason']``). Anything it
cannot prove — a timer armed, pending results, a cold or noisy client,
K exceeding the idle pool — falls through to the stepwise engine.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from repro import obs
from repro.core.fleet_store import IDLE

Pytree = Any

#: compiled fused-scan programs, keyed by their static closure values
#: (the trainer fn identity pins model/optimizer/Kp; jax.jit
#: adds its own shape/dtype specialization on top)
_SCAN_CACHE: dict[tuple, Any] = {}


@dataclass
class MegastepPlan:
    """Everything the fused scan + host replay need, resolved statically."""

    R: int                  # rounds to fuse
    K: int                  # cohort size (= cfg.clients_per_round)
    Kp: int                 # padded cohort bucket
    top: int                # free-stack height at entry
    fn: Any                 # compiled indexed-flat cohort fn
    max_steps: int          # longest step budget among the slots: the most
    #                         any fused round loops (each loops to its own
    #                         cohort's longest client, read in ``fn``)
    sparse: bool            # aggregation dispatch (rows_dispatch)
    use_pallas: bool
    interpret: bool
    out_dtype: Any          # model leaf dtype (post-aggregate astype)
    beta32: np.float32      # booster promotion rate (1 + rho)
    dec32: np.float32       # EMA decay (1 - rho)
    # [capacity] per-slot columns (host); device copies are made at launch
    ids_col: np.ndarray     # client id (= dataset index), int32
    n_col: np.ndarray       # data.n[id] (trainer arg dtype)
    n32_col: np.ndarray     # f32 cast of n (aggregation weights)
    steps_col: np.ndarray   # step budget, int64 (trainer arg dtype)
    card32_col: np.ndarray  # f32 cardinality (EMA operand)
    upd32_col: np.ndarray   # FleetStore.upd32 (EMA operand)
    d64_col: np.ndarray     # deterministic invocation duration, f64
    d32_col: np.ndarray     # f32 cast (the mark_complete EMA operand)
    rank_col: np.ndarray    # dense duration rank (landing-order key), int32
    mesh: Any = None        # device mesh (None = single-device): routes the
    #                         in-scan aggregation through the weighted psum


def _plan(sched) -> tuple[Optional[MegastepPlan], str]:
    """Prove a run of rounds quiescent, or say why not (side-effect free
    apart from reading — and thereby purging — the stale-timer heap)."""
    import jax

    from repro.core.strategies.reactive import LegacyStrategyAdapter

    cfg = sched.cfg
    db = sched.db
    # config-level refusals first: they name the *user-set* knob even when
    # a knob also changes the policy object (RecoveryPolicy wrapping)
    if getattr(sched, "durability", None) is not None:
        # fused rounds dispatch no per-event Python, so the write-ahead
        # journal would record nothing at their boundaries — crash points
        # inside a fused horizon would be unresumable
        return None, "durability journal active"
    if cfg.invocation_timeout or cfg.retry_budget or cfg.quarantine_threshold:
        return None, "retry/timeout recovery enabled"
    if cfg.quorum_fraction < 1.0:
        return None, "partial-cohort quorum enabled"
    if type(sched.policy) is not LegacyStrategyAdapter \
            or sched.policy.strategy.name != "apodotiko-topk":
        return None, "strategy is not adapter-wrapped apodotiko-topk"
    if not db.columnar:
        return None, "object control plane"
    if sched.update_plane != "device" or sched.store is None:
        return None, "blob update plane"
    if sched.data_plane != "device" or sched.dataset is None:
        return None, "host data plane"
    if cfg.eval_every:
        return None, "per-round evaluation enabled"
    if cfg.checkpoint_every:
        return None, "checkpointing enabled"
    if cfg.target_accuracy:
        return None, "target-accuracy early stop enabled"
    if cfg.failure_rate != 0.0:
        return None, "nonzero failure rate"
    faults = sched.platform.faults
    if faults is not None and faults.active and faults.stochastic:
        # stochastic faults perturb any round; outage windows are handled
        # below by shrinking the horizon to stop short of the window
        return None, "stochastic fault schedule active"
    if sched.strategy.needs_scaffold:
        return None, "scaffold variates"
    K = int(cfg.clients_per_round)
    if K <= 0:
        return None, "empty cohort"
    if sched.strategy.results_needed() < K:
        return None, "CR gate closes rounds before all K land"
    if any(not r.aggregated for r in db.results):
        return None, "un-aggregated results pending"
    if sched.inflight:
        return None, "invocations in flight"
    if sched._peek_timer() is not None:
        return None, "timer armed"
    if sched._progress is not None:
        return None, "progress callback installed (may mutate mid-run)"
    if sched.loop.peek() is not None:
        return None, "event loop not quiescent"

    fleet = db.fleet
    slots = np.flatnonzero(fleet.active)
    if slots.size == 0:
        return None, "no active clients"
    if np.any(fleet.status[slots] != IDLE):
        return None, "clients not idle"
    if np.any(fleet.n_invocations[slots] <= 0):
        return None, "bootstrap rounds remain (uninvoked clients)"
    if np.any(fleet.quarantined_until[slots] > db.round):
        return None, "clients quarantined"
    if slots.size < K:
        return None, "K exceeds idle-client count"
    ids = fleet.ids[slots].astype(np.int64)
    if int(ids.max()) >= sched.dataset.n_clients:
        return None, "client id outside resident dataset"
    for cid in ids:
        hw = sched.hw.get(int(cid))
        if hw is None or hw.variability != 0.0:
            return None, "client hardware has nonzero variability"
        if int(cid) not in sched.platform._instances:
            return None, "client has no platform instance"

    stack = sched.store.free_stack()
    leaves = jax.tree.leaves(sched.params)
    if len({l.dtype for l in leaves}) != 1:
        return None, "mixed model leaf dtypes (scan carry instability)"
    out_dtype = leaves[0].dtype

    # deterministic per-slot durations: warm startup (0.15, no uniform
    # draw), speed = hw.speed * exp(N(0, 0)) = hw.speed exactly, no
    # failure — the exact f64 expression platform.invoke evaluates
    platform = sched.platform
    n_all = np.asarray(sched.data.n)
    cap = fleet.capacity
    ids_col = np.zeros(cap, np.int32)
    n_col = np.ones(cap, n_all.dtype)
    steps_col = np.ones(cap, np.int64)
    d64_col = np.zeros(cap, np.float64)
    ids_col[slots] = ids
    n_col[slots] = n_all[ids]
    steps_col[slots] = np.maximum(
        np.ceil(n_col[slots] / cfg.batch_size).astype(np.int64)
        * cfg.local_epochs, 1)
    for s in slots:
        hw = sched.hw[int(ids_col[s])]
        d64_col[s] = ((0.15 + platform.model_load_s)
                      + float(steps_col[s]) * cfg.base_step_time / hw.speed
                      ) + platform.upload_s
    if float(np.sum(n_col[slots].astype(np.float64))) >= float(2 ** 24):
        return None, "sample counts too large for exact f32 weights"

    # horizon: every invocation must hit a warm instance and every round
    # must close inside the sim budget, under the conservative per-round
    # advance bound D = max duration over active clients
    t0 = float(sched.loop.now)
    D = float(d64_col[slots].max())
    warm_min = min(platform._instances[int(c)].warm_until for c in ids)
    R = int(cfg.rounds) - int(db.round)
    if D > 0:
        if warm_min < t0:
            R = 0
        else:
            R = min(R, int(np.floor((warm_min - t0) / D)) + 1)
        R = min(R, max(int(np.ceil((cfg.max_sim_time - t0) / D)) - 1, 0))
    while R > 0 and (t0 + (R - 1) * D > warm_min
                     or t0 + R * D >= cfg.max_sim_time):
        R -= 1
    if R < 1:
        return None, "no quiescent horizon (keep-warm or sim budget)"
    if faults is not None and faults.active:
        # deterministic outage windows: fused launches happen at t0 + r*D,
        # so shrink the horizon to stop strictly before any window that
        # overlaps it. A window already behind us (end <= t0) is ignored —
        # megastep re-engages once simulated time passes the outage.
        for w in faults.outage_windows():
            if w.end <= t0 or w.start >= t0 + R * D:
                continue
            if w.start > t0 and D > 0:
                R = min(R, int(np.floor((w.start - t0) / D + 1e-12)))
            else:
                R = 0
        if R < 1:
            return None, "fault window overlaps horizon"
    traffic = getattr(sched, "traffic", None)
    if traffic is not None:
        if traffic.stochastic:
            # the schedule is pre-compiled, but whether a fused horizon
            # stays membership-quiescent under a Poisson/diurnal source
            # is not provable from static facts — stepwise is the oracle
            return None, "stochastic traffic profile active"
        nb = sched._traffic_boundary()
        if nb is not None:
            # deterministic segment boundaries work like outage windows:
            # stepwise applies a segment at the first round *open* with
            # t >= start, and fused round r opens at t0 + r*D — so the
            # horizon must stop before the next unapplied boundary and
            # re-engage after _open_round applies it.
            if nb <= t0:
                return None, "traffic boundary overlaps horizon"
            if D > 0:
                R = min(R, int(np.ceil((nb - t0) / D - 1e-12)))
            if R < 1 or t0 + (R - 1) * D >= nb:
                return None, "traffic boundary overlaps horizon"

    from repro.core.aggregation import rows_dispatch
    from repro.core.scoring import promotion_rate

    fn, Kp, max_steps = sched.trainer.cohort_fn_indexed(
        sched.dataset, K, int(steps_col[slots].max()))
    if stack.size < Kp:
        return None, "update-store free list too small (would grow)"
    try:
        sparse, use_pallas, interpret = rows_dispatch(
            sched.store.capacity, K, sched.spec.n_params)
    except ValueError:
        return None, "unknown aggregation path"
    # mesh-compatibility obligation (DESIGN.md §15): the in-scan cohort fn
    # shard_maps its batch over "data" and the buffer is row-sharded, so
    # both geometries must split evenly — guaranteed by the trainer's
    # lcm'd cohort floor and the store's mesh-aware capacity alignment,
    # but proved here so a future geometry change degrades to stepwise
    # instead of tracing a shard_map error inside the scan
    mesh = getattr(sched, "mesh", None)
    if mesh is not None:
        from repro.sharding import flmesh
        d_ax = flmesh.mesh_axes(mesh)[0]
        if Kp % d_ax != 0:
            return None, "cohort bucket not divisible by mesh data axis"
        if sched.store.capacity % d_ax != 0:
            return None, "store capacity not divisible by mesh data axis"

    _, rank_col = np.unique(d64_col, return_inverse=True)
    return MegastepPlan(
        R=R, K=K, Kp=Kp, top=int(stack.size), fn=fn, max_steps=max_steps,
        sparse=sparse, use_pallas=use_pallas, interpret=interpret,
        out_dtype=out_dtype,
        beta32=np.float32(promotion_rate(cfg.adjustment_rate)),
        dec32=np.float32(fleet.decay),
        ids_col=ids_col, n_col=n_col,
        n32_col=n_col.astype(np.float32), steps_col=steps_col,
        card32_col=fleet.cardinality[:cap].astype(np.float32),
        upd32_col=fleet.upd32[:cap].copy(),
        d64_col=d64_col, d32_col=d64_col.astype(np.float32),
        rank_col=rank_col.astype(np.int32), mesh=mesh), "eligible"


def _build_scan(plan: MegastepPlan, spec):
    """The jitted R-round program. Cached on the static closure values —
    jax.jit's own cache layers shape/dtype specialization on top."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import aggregate_rows_traced, scored_topk

    from repro.sharding import flmesh

    key_ = (id(plan.fn), id(spec), plan.R, plan.K, plan.Kp, plan.top,
            plan.sparse, plan.use_pallas, plan.interpret,
            str(plan.out_dtype), *flmesh.mesh_token(plan.mesh))
    cached = _SCAN_CACHE.get(key_)
    if cached is not None:
        return cached

    R, K, Kp, top = plan.R, plan.K, plan.Kp, plan.top
    sparse, use_pallas, interpret = \
        plan.sparse, plan.use_pallas, plan.interpret
    out_dtype = plan.out_dtype
    fn = plan.fn
    mesh = plan.mesh

    @jax.jit
    def fused(params, buffer, stack, num, den, booster, key,
              eligible, ever, X, y,
              ids_col, n_col, n32_col, steps_col,
              card32_col, upd32_col, d32_col, rank_col,
              beta32, dec32):

        def body(carry, _):
            params, buffer, stack, num, den, booster, key = carry
            # -- selection: the exact select_topk program ------------------
            sel, valid, booster = scored_topk(
                num, den, booster, eligible, ever, beta32, K)
            # -- update rows: the UpdateStore LIFO pop sequence ------------
            ids = stack[top - Kp:top][::-1]
            # -- cohort train: same compiled fn, same padding, same keys ---
            # (lane k >= K repeats lane K-1's client and runs 0 steps — the
            # values _cohort_pad/train_cohort_indexed produce)
            if Kp > K:
                sel_p = jnp.concatenate([sel, jnp.repeat(sel[-1:], Kp - K)])
                steps_sel = steps_col[sel]
                steps_p = jnp.concatenate(
                    [steps_sel, jnp.zeros((Kp - K,), steps_sel.dtype)])
            else:
                sel_p = sel
                steps_p = steps_col[sel]
            cidx = ids_col[sel_p]
            n_p = n_col[sel_p]
            ks = jax.random.split(key)          # the _cohort_keys schedule
            key = ks[0]
            keys = jax.random.split(ks[1], Kp)
            cg = jax.tree.map(lambda p: jnp.zeros((), p.dtype), params)
            ci = jax.tree.map(
                lambda p: jnp.zeros((Kp,) + (1,) * p.ndim, p.dtype), params)
            buffer, _, losses = fn(params, cidx, n_p, steps_p, keys,
                                   cg, ci, X, y, buffer, ids)
            # -- f32 EMA fold per landing (the mark_complete twin) ---------
            s32 = card32_col[sel] * (
                upd32_col[sel]
                / jnp.maximum(d32_col[sel], jnp.float32(1e-9)))
            num = num.at[sel].set(s32 + dec32 * num[sel])
            den = den.at[sel].set(jnp.float32(1.0) + dec32 * den[sel])
            # -- aggregation in landing order ------------------------------
            perm = jnp.argsort(rank_col[sel], stable=True)
            rows_land = ids[:K][perm]
            w = n32_col[sel][perm]
            w = w / jnp.sum(w)
            flat = aggregate_rows_traced(
                buffer, rows_land, w, sparse=sparse,
                use_pallas=use_pallas, interpret=interpret, mesh=mesh)
            out = spec.unravel(flat[:spec.n_params], restore_dtype=False)
            params = jax.tree.map(lambda x: x.astype(out_dtype), out)
            # -- free-stack push algebra (pad frees, then landing frees) ---
            stack = stack.at[top - Kp:top].set(
                jnp.concatenate([ids[K:], rows_land]))
            return ((params, buffer, stack, num, den, booster, key),
                    (sel, ids, losses[:K]))

        carry = (params, buffer, stack, num, den, booster, key)
        carry, ys = jax.lax.scan(body, carry, None, length=R)
        return carry, ys

    _SCAN_CACHE[key_] = fused
    return fused


def run_megastep(sched, plan: MegastepPlan) -> None:
    """Launch the fused scan, then replay the R rounds through the REAL
    host bookkeeping (platform, event loop, database, free-lists) with
    protocol emission suppressed — zero device dispatches, bit-identical
    end state. Cross-checks against the scan outputs raise on mismatch."""
    import jax.numpy as jnp

    cfg = sched.cfg
    db = sched.db
    fleet = db.fleet
    store = sched.store
    R, K, Kp = plan.R, plan.K, plan.Kp

    fleet._flush_device()               # fold pre-scan dirt into the carry
    dev = fleet._device()
    fused = _build_scan(plan, sched.spec)
    X, y = sched.dataset.arrays()
    carry, ys = fused(
        sched.params, store.buffer, jnp.asarray(store.free_stack()),
        dev.num, dev.den, dev.booster, sched.trainer._key,
        dev.eligible, dev.ever, X, y,
        jnp.asarray(plan.ids_col), jnp.asarray(plan.n_col),
        jnp.asarray(plan.n32_col), jnp.asarray(plan.steps_col),
        jnp.asarray(plan.card32_col), jnp.asarray(plan.upd32_col),
        jnp.asarray(plan.d32_col), jnp.asarray(plan.rank_col),
        jnp.float32(plan.beta32), jnp.float32(plan.dec32))
    params_f, buffer_f, _, _, _, booster_f, key_f = carry
    with obs.sync("megastep", round=db.round, rounds=R):
        sel_np = np.asarray(ys[0])      # [R, K] selected slots
        ids_np = np.asarray(ys[1])      # [R, Kp] update rows
        losses_np = np.asarray(ys[2])   # [R, K]
    steps_np = plan.steps_col[sel_np]   # [R, K]
    sched.trainer.count_lane_steps(sched.params, Kp, K,
                                   int(steps_np.max(axis=1).sum()),
                                   int(steps_np.sum()))

    # ---- host replay: the real code paths, no device work ----------------
    from repro.core.services import RoundLog, _Payload

    strat = sched.strategy
    sched._emit = lambda ev: None       # instance attr shadows the method
    try:
        for r in range(R):
            round_ = db.round
            sched._t0 = sched.loop.now
            sched._invoked_this_round = True
            sched._completed_this_round = set()
            sel = sel_np[r]
            ids = store.alloc(Kp)
            if not np.array_equal(ids, ids_np[r]):
                raise RuntimeError("megastep: scan/alloc row-id mismatch")
            if Kp > K:
                store.free(ids[K:])
            for k in range(K):
                slot = int(sel[k])
                cid = int(plan.ids_col[slot])
                payload = _Payload(row=int(ids[k]))
                inv = sched._launch(cid, round_, float(plan.steps_col[slot]),
                                    payload, int(plan.n_col[slot]),
                                    float(losses_np[r, k]))
                if inv.rec.cold or inv.rec.failed \
                        or inv.rec.duration != plan.d64_col[slot]:
                    raise RuntimeError(
                        "megastep: replayed invocation diverged from plan")
            for _ in range(K):          # drain exactly this round's landings
                sched.loop.step()
            pending = [p for p in db.pending_results(cfg.max_staleness,
                                                     round_)
                       if strat.usable(p, round_)]
            perm = np.argsort(plan.rank_col[sel], kind="stable")
            rows_land = ids[:K][perm]
            if [p.update_row for p in pending] != rows_land.tolist():
                raise RuntimeError("megastep: landing-order mismatch")
            # aggregate_round's exact close sequence (params came from the
            # scan): free landing rows, then mark aggregated
            store.free(rows_land.tolist())
            db.mark_aggregated(pending)
            log = RoundLog(round=round_, t_start=sched._t0,
                           t_end=sched.loop.now, accuracy=sched._acc,
                           n_aggregated=K, n_stale=0, mean_loss=0.0)
            sched.history.append(log)   # _plan refused if _progress was set
            db.round = round_ + 1
    finally:
        vars(sched).pop("_emit", None)  # restore the class method

    # ---- device-state handoff -------------------------------------------
    sched.params = params_f
    store.buffer = buffer_f
    dev.booster = booster_f
    sched.trainer._key = key_f
    # num/den are NOT written back: the replayed mark_complete calls marked
    # every touched slot dirty, and the next _flush_device rebuilds them
    # from the f32 mirror columns — which the scan evolved with the exact
    # same algebra, so the rebuilt values equal the final carry bitwise.
    sched.megastep_scans += 1
    sched.megastep_rounds += R


def try_megastep(sched) -> bool:
    """Scheduler hook: plan, and if eligible run, one fused scan. Returns
    True when rounds were executed (the caller re-checks termination and
    may re-enter — completions extend keep-warm windows)."""
    plan, reason = _plan(sched)
    sched.megastep_fallback_reason = reason
    if plan is None:
        return False
    with obs.span("fl.megastep", round=sched.db.round, rounds=plan.R,
                  lanes=plan.Kp, clients=plan.K, steps=plan.max_steps):
        run_megastep(sched, plan)
    return True
