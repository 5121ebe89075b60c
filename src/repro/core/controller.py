"""The legacy poll-loop driver (FedLess controller, Algorithm 1).

Train_Global_Model loop:
  1. ``Select_Clients`` via the active strategy (Algorithm 3 for Apodotiko).
  2. Invoke the selected client functions on the (simulated) FaaS platform;
     save invocation records; mark clients busy.
  3. Clients run Client_Update (real JAX training, cohort-vectorized) and
     land results in the database at their simulated completion times.
  4. The controller polls the database until the strategy's gating condition
     holds — all current-round results or timeout (sync), or
     ``ceil(CR x clientsPerRound)`` un-aggregated results from the current or
     up to five previous rounds (async, Algorithm 1 line 9).
  5. Aggregate with cardinality x staleness weights (Eq. 2), write the new
     global model, evaluate, and start the next round immediately.

Fault tolerance: failed invocations (crash/preemption — the Bernoulli
``failure_rate`` coin or any seeded ``fault_profile`` schedule,
faas/faults.py) simply never produce results — sync strategies absorb them
via the round timeout, async ones are oblivious. This engine is purely
*passive*: the active recovery layer (retry/backoff, timeouts, circuit
breaker, quorum degradation — DESIGN.md §12) is scheduler-only, so
recovery knobs must stay off for cross-engine differential runs. The
controller checkpoints {global model, client records, scores,
boosters, round} and can resume from the database (tests/test_controller.py).
Elasticity: clients may join/leave between rounds (add_clients/remove_clients).

The execution state and round services (invocation, aggregation,
evaluation) live in :class:`repro.core.services.FLRuntime`; this class
only contributes the poll loop. The event-driven replacement —
``repro.core.scheduler.Scheduler`` dispatching typed protocol events to a
reactive policy — is the default engine (DESIGN.md §7); this loop is kept
as the golden-trace equivalence oracle (tests/test_golden_trace.py) and
for ``REPRO_ENGINE=legacy``.
"""
from __future__ import annotations

from typing import Callable, Optional

from repro import obs
# Re-exported for backwards compatibility: these lived here before the
# scheduler redesign split the services out (PR 3).
from repro.core.services import (FLConfig, FLRuntime, RoundLog,  # noqa: F401
                                 UPDATE_STORE_DIRNAME, resolve_control_plane,
                                 resolve_engine, resolve_update_plane,
                                 strategy_config)


class Controller(FLRuntime):
    """Poll-based round driver: blocks in ``EventLoop.run_until`` on the
    strategy's gating predicate (see module docstring)."""

    engine_name = "controller"

    # -------------------------------------------------------------------- run
    def run(self, progress: Optional[Callable[[RoundLog], None]] = None):
        cfg, strat = self.cfg, self.strategy
        round_ = self.db.round
        traffic_round = -1
        while round_ < cfg.rounds and self.loop.now < cfg.max_sim_time:
            t0 = self.loop.now
            self._t0 = t0
            if round_ != traffic_round:
                # fresh-round open only — mid-round re-polls must not
                # shift membership, mirroring the scheduler (which applies
                # traffic in _open_round, never on adapter re-selects)
                self._apply_due_traffic()
                traffic_round = round_
                if self.durability is not None:
                    # the poll loop has no RoundStarted event; the marker
                    # gives its journal the same open boundary
                    self.durability.record_marker("round_open", round_)
            with obs.span("fl.select", round=round_):
                selection = strat.select(self.db, round_)
            if not selection:
                # every client busy: advance until something completes —
                # or, when the fleet is empty under open-loop traffic,
                # jump to the next arrival boundary
                if not self.loop.run_until(self.db.any_idle):
                    if not self._traffic_fast_forward():
                        break
                continue
            self.invoke_round(round_, selection)

            if strat.is_async:
                need = strat.results_needed()
                ok = self.loop.run_until(
                    lambda: len(self.db.pending_results(cfg.max_staleness, round_))
                    >= need, max_time=cfg.max_sim_time)
                if not ok and not self.db.pending_results(cfg.max_staleness, round_):
                    break
            else:
                deadline = t0 + cfg.round_timeout
                self.loop.run_until(
                    lambda: self._completed_this_round >= set(selection),
                    max_time=deadline)
                # guarantee progress: at least one usable result
                self.loop.run_until(
                    lambda: any(strat.usable(r, round_) for r in
                                self.db.pending_results(cfg.max_staleness, round_)),
                    max_time=cfg.max_sim_time)

            n_agg, n_stale, _ = self.aggregate_round(round_)
            if n_agg == 0:
                round_ += 1
                self.db.round = round_
                self._durability_round_closed()
                continue
            if cfg.eval_every and round_ % cfg.eval_every == 0:
                self._acc = self.evaluate()
            log = RoundLog(round=round_, t_start=t0, t_end=self.loop.now,
                           accuracy=self._acc, n_aggregated=n_agg,
                           n_stale=n_stale, mean_loss=0.0)
            self.history.append(log)
            if progress:
                progress(log)
            round_ += 1
            self.db.round = round_
            self._durability_round_closed()
            if cfg.checkpoint_every and round_ % cfg.checkpoint_every == 0:
                self.checkpoint()
            if cfg.target_accuracy and self._acc >= cfg.target_accuracy:
                break
        if self.durability is not None:
            self.durability.finish()
        return self.metrics()
