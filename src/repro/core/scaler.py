"""The Sponge scaler (paper §3.1 "Scaler"): every adaptation interval, read
the queue snapshot + lambda estimate, solve the IP, and emit a Decision the
engine applies via in-place vertical scaling.

``solver`` selects the optimizer implementation:

* ``"bruteforce"`` — the paper's Algorithm 1, a Python double loop (the
  reference semantics);
* ``"pruned"``     — the vectorized exact variant;
* ``"memo"``       — a :class:`repro.core.solver.MemoizedSolver`: the
  ``(c, b)`` grid is precomputed once and decisions are cached under a
  quantized ``(budgets, λ, wait)`` signature.  With ``budget_quantum`` and
  ``lam_quantum`` at their 0.0 defaults the cache key is exact and the
  decisions are identical to Algorithm 1; positive quanta trade a bounded,
  conservative coarsening for near-O(1) repeated decisions (the
  million-request scenario-engine configuration).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from repro.core.cost_model import CostModel, TokenCostModel
from repro.core.perf_model import PerfModel
from repro.core.queueing import EDFQueue
from repro.core.slo import Decision
from repro.core.uncertainty import UncertaintyConfig
from repro.core.solver import (DEFAULT_B, DEFAULT_C, MemoizedSolver,
                               TokenMemoizedSolver, solve_bruteforce,
                               solve_pruned, solve_token_bruteforce)


@dataclass
class SpongeScaler:
    """Conforms to ``repro.serving.api.SchedulingPolicy`` — a bare scaler
    can be handed to the ScenarioRunner directly (the live engine does).

    ``perf`` may be a ``PerfModel`` or any fixed-work-capable
    ``repro.core.cost_model.CostModel`` (they share the ``latency(b, c)``
    / ``throughput(b, c)`` surface; the ``FixedWorkCostModel`` adapter is
    decision-identical to its wrapped PerfModel by construction)."""
    perf: Union[PerfModel, CostModel]
    name: str = "sponge"
    c_set: Sequence[int] = DEFAULT_C
    b_set: Sequence[int] = DEFAULT_B
    adaptation_interval: float = 1.0
    solver: str = "bruteforce"          # bruteforce (paper Alg.1) | pruned | memo
    delta_pen: float = 1e-3
    headroom: float = 0.05              # latency safety margin (seconds)
    lam_headroom: float = 1.05          # provision for lam * this factor
    budget_quantum: float = 0.0         # memo solver: budget bucket (s)
    lam_quantum: float = 0.0            # memo solver: lambda bucket (rps)
    decisions: List[tuple[float, Decision]] = field(default_factory=list)
    _next_t: float = 0.0
    _memo: Optional[MemoizedSolver] = field(default=None, repr=False)

    def due(self, now: float) -> bool:
        return now + 1e-12 >= self._next_t

    @property
    def memo(self) -> MemoizedSolver:
        """The lazily built memoized solver (valid for solver="memo")."""
        if self._memo is None:
            self._memo = MemoizedSolver(
                self.perf, self.c_set, self.b_set,
                budget_quantum=self.budget_quantum,
                lam_quantum=self.lam_quantum)
        return self._memo

    def solver_stats(self) -> dict:
        """Cache economics of the memo solver ({} for exact solvers)."""
        if self._memo is None:
            return {}
        return {"hits": self._memo.hits, "misses": self._memo.misses,
                "hit_rate": self._memo.hit_rate}

    def decide(self, now: float, queue: EDFQueue, lam: float,
               initial_wait: float = 0.0,
               extra_budgets: tuple = ()) -> Decision:
        self._next_t = now + self.adaptation_interval
        if hasattr(queue, "remaining_array"):
            snap = queue.remaining_array(now)
        else:
            snap = np.asarray(queue.snapshot_remaining(now), np.float64)
        remaining = np.maximum(snap - self.headroom, 0.0)
        if extra_budgets:
            extra = np.maximum(
                np.asarray(extra_budgets, np.float64) - self.headroom, 0.0)
            remaining = np.sort(np.concatenate([remaining, extra]))
        lam_eff = lam * self.lam_headroom
        if self.solver == "memo":
            d = self.memo.solve(remaining, lam_eff,
                                initial_wait=initial_wait)
        else:
            fn = (solve_bruteforce if self.solver == "bruteforce"
                  else solve_pruned)
            d = fn(list(remaining), lam_eff, self.perf, self.c_set,
                   self.b_set, self.delta_pen, initial_wait=initial_wait)
        self.decisions.append((now, d))
        return d


@dataclass
class TokenSpongeScaler:
    """The Sponge scaler over the token-level cost model (ISSUE 3).

    Same control-loop role as :class:`SpongeScaler` — every adaptation
    interval, read the queue snapshot + λ estimate, solve, emit a
    Decision — but the snapshot is token-aware (per-request TTFT budgets
    + prompt-token counts + the tightest per-token SLO, via
    ``queue.token_snapshot``) and the solve runs the token-composition
    Algorithm 1 (``repro.core.solver.TokenSolverTable`` behind a
    ``TokenMemoizedSolver``; quanta 0 keep it exact).  The Decision's
    ``b`` doubles as the decode-slot cap the continuous-batching engines
    run at; ``predicted_tbt`` carries the solver's sustained decode-step
    latency for telemetry.

    Token-aware runners pass ``active_slots`` (running decode slots) and
    ``tbt_budget`` (tightest per-token budget across queued *and*
    running requests); plain runners may omit both — the scaler then
    derives the TBT bound from the queue alone.
    """
    cost: TokenCostModel
    name: str = "sponge-token"
    c_set: Sequence[int] = DEFAULT_C
    b_set: Sequence[int] = DEFAULT_B
    adaptation_interval: float = 1.0
    solver: str = "memo"                # memo (table+cache) | bruteforce
    headroom: float = 0.05              # TTFT safety margin (seconds)
    tbt_headroom: float = 0.0           # per-token safety margin (seconds)
    lam_headroom: float = 1.05
    budget_quantum: float = 0.0
    lam_quantum: float = 0.0
    token_quantum: int = 0
    # decode-steps of slot-turnover drag per EDF prefill group; None =
    # the cost model's mean decode length (a slot frees when its stream
    # finishes) — see ``repro.core.solver.solve_token_bruteforce``
    drag_steps: Optional[float] = None
    # distribution-aware admission (ISSUE 7): when the config carries a
    # non-point distribution, the solve plans drag at the admission
    # quantile and widens the TTFT headroom by the shared predictor's
    # slack factor; None or a point mass leaves the deterministic solve
    # untouched (bit-identical decisions)
    uncertainty: Optional[UncertaintyConfig] = None
    decisions: List[tuple[float, Decision]] = field(default_factory=list)
    # decisions made with a gang-true plan, and the decode steps per b
    # the last decision planned (see ``decide``)
    gang_plans: int = 0
    last_drag: Dict[int, float] = field(default_factory=dict)
    last_gang: bool = False
    _next_t: float = 0.0
    _memo: Optional[TokenMemoizedSolver] = field(default=None, repr=False)

    def due(self, now: float) -> bool:
        """Adaptation-interval gate (same cadence rule as SpongeScaler)."""
        return now + 1e-12 >= self._next_t

    @property
    def memo(self) -> TokenMemoizedSolver:
        """The lazily built token memoized solver."""
        if self._memo is None:
            self._memo = TokenMemoizedSolver(
                self.cost, self.c_set, self.b_set,
                budget_quantum=self.budget_quantum,
                lam_quantum=self.lam_quantum,
                token_quantum=self.token_quantum)
        return self._memo

    def solver_stats(self) -> dict:
        """Gang-true plans made, the per-b decode steps of the last
        decision, and the memo solver's cache economics (after its first
        use)."""
        stats = {"gang_plans": self.gang_plans, "drag": dict(self.last_drag)}
        if self._memo is not None:
            stats.update(hits=self._memo.hits, misses=self._memo.misses,
                         hit_rate=self._memo.hit_rate)
        return stats

    def decide(self, now: float, queue, lam: float,
               initial_wait: float = 0.0, active_slots: int = 0,
               tbt_budget: Optional[float] = None,
               gang_steps: Optional[Dict[int, float]] = None) -> Decision:
        """One adaptation step: snapshot, solve, log, return.

        With a non-point :class:`~repro.core.uncertainty.
        UncertaintyConfig`, the p-quantile completion estimate gates
        admission: slot-turnover drag is planned at
        ``dist.quantile(admission_quantile)`` (not the cost model's
        mean) and the TTFT headroom is multiplied by the predictor's
        running slack factor, so worsening calibration widens the
        safety margin and sustained good calibration narrows it back.

        ``gang_steps`` (b -> decode steps a b-gang holds its slots) comes
        from a runner whose backend keeps a gang's slots until its
        longest stream ends; it plans each b by its own steps in the
        drag and the λ check (``solve_token_bruteforce``).  It stands in
        for the mean decode length only: a configured ``drag_steps`` or
        uncertainty plan takes precedence.
        """
        self._next_t = now + self.adaptation_interval
        headroom, drag = self.headroom, self.drag_steps
        unc = self.uncertainty
        if unc is not None and not unc.is_point():
            headroom = self.headroom * unc.predictor.slack_factor()
            drag = unc.drag_estimate()
        if drag is not None:
            gang_steps = None
        rem, toks, queue_tbt = queue.token_snapshot(now)
        remaining = np.maximum(rem - headroom, 0.0)
        tbt = queue_tbt if tbt_budget is None else min(tbt_budget, queue_tbt)
        if np.isfinite(tbt):
            tbt = max(tbt - self.tbt_headroom, 0.0)
        lam_eff = lam * self.lam_headroom
        if self.solver == "bruteforce":
            d = solve_token_bruteforce(
                remaining, toks, lam_eff, self.cost, self.c_set, self.b_set,
                initial_wait=initial_wait, tbt_budget=tbt,
                active_slots=active_slots, drag_steps=drag,
                gang_steps=gang_steps)
        else:
            d = self.memo.solve(remaining, toks, lam_eff,
                                initial_wait=initial_wait, tbt_budget=tbt,
                                active_slots=active_slots,
                                drag_steps=drag, gang_steps=gang_steps)
        self.last_gang = gang_steps is not None
        self.gang_plans += self.last_gang
        if self.last_gang:
            self.last_drag = {int(b): float(gang_steps[b])
                              for b in self.b_set}
        else:
            flat = self.cost.mean_decode if drag is None else drag
            self.last_drag = dict.fromkeys(map(int, self.b_set), float(flat))
        self.decisions.append((now, d))
        return d
