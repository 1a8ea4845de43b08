"""Phase-aware autoregressive serving (ISSUE 3): token cost model,
token-composition solver, continuous-batching engines, scenarios, and
the satellite fixes (λ-estimator guard, shared decision resolution)."""
import time

import numpy as np
import pytest
from _hyp import given, settings, st  # guarded hypothesis import

from repro.core.cost_model import (Composition, TokenCostModel,
                                   expected_longest)
from repro.core.monitor import RateEstimator
from repro.core.perf_model import yolov5s_like
from repro.core.queueing import EDFQueue, TokenFastEDFQueue
from repro.core.scaler import TokenSpongeScaler
from repro.core.slo import Decision, Request
from repro.core.solver import (TokenMemoizedSolver, TokenSolverTable,
                               solve_token_bruteforce)
from repro.serving.api import (ScenarioRunner, TokenSimBackend,
                               resolve_decision)
from repro.serving.fastpath import FastSimRunner, TokenFastSimRunner
from repro.serving.workload import RequestBatch, lognormal_lengths

PERF = yolov5s_like()
COST = TokenCostModel.smollm_like()
C16 = tuple(range(1, 17))


def _token_batch(n=400, duration=40.0, seed=0, tbt=0.08):
    rng = np.random.default_rng(seed)
    send = np.sort(rng.uniform(0, duration, n))
    cl = rng.uniform(0.01, 0.15, n)
    pt = lognormal_lengths(rng, n, median=64, sigma=0.6, lo=8, hi=512)
    dt = lognormal_lengths(rng, n, median=24, sigma=0.5, lo=1, hi=128)
    return RequestBatch.from_send(send, cl, slo=1.0, prompt_tokens=pt,
                                  decode_tokens=dt, tbt_slo=tbt)


# --------------------------------------------------------------------------
# cost model
# --------------------------------------------------------------------------
def test_token_cost_model_surfaces_consistent():
    c = 4
    assert COST.step_latency(c, Composition(100, 0)) == pytest.approx(
        float(COST.prefill_latency(c, 100)))
    assert COST.step_latency(c, Composition(0, 8)) == pytest.approx(
        float(COST.decode_latency(c, 8)))
    assert COST.step_latency(c, Composition(0, 0)) == 0.0
    # mixed step shares one per-step overhead, so it is cheaper than the
    # two phases run separately
    mixed = COST.step_latency(c, Composition(100, 8))
    assert mixed < (float(COST.prefill_latency(c, 100))
                    + float(COST.decode_latency(c, 8)))
    # more cores never slower, more work never faster
    assert COST.decode_latency(2, 8) > COST.decode_latency(16, 8)
    assert COST.prefill_latency(4, 512) > COST.prefill_latency(4, 64)


def test_token_cost_model_fit_recovers_surface():
    pre, dec = COST.sample_profile([16, 64, 256, 1024], [1, 2, 4, 8, 16],
                                   [1, 2, 4, 8, 16], noise=0.0)
    fit = TokenCostModel.fit(pre, dec, mean_prompt=COST.mean_prompt,
                             mean_decode=COST.mean_decode)
    assert fit.r2_prefill > 0.999 and fit.r2_decode > 0.999
    for c in (1, 4, 16):
        assert float(fit.prefill_latency(c, 200)) == pytest.approx(
            float(COST.prefill_latency(c, 200)), rel=1e-3)
        assert float(fit.decode_latency(c, 12)) == pytest.approx(
            float(COST.decode_latency(c, 12)), rel=1e-3)


def test_prefill_token_allowance_inverts_step_latency():
    for c in (1, 4, 16):
        budget = 0.06
        allow = COST.prefill_token_allowance(c, 8, budget)
        assert allow > 0
        at = COST.step_latency(c, Composition(int(allow), 8))
        assert at <= budget + 1e-6
        over = COST.step_latency(c, Composition(int(allow) + 50, 8))
        assert over > budget
    assert COST.prefill_token_allowance(4, 8, float("inf")) == float("inf")


# --------------------------------------------------------------------------
# token solver: vectorized table == bruteforce reference
# --------------------------------------------------------------------------
def _random_solver_inputs(rng):
    n = int(rng.integers(0, 30))
    rem = np.sort(rng.uniform(0, 2.0, n))
    toks = rng.integers(1, 400, n).astype(np.float64)
    lam = float(rng.uniform(0, 60))
    iw = float(rng.uniform(0, 0.3))
    tbt = float(rng.choice([np.inf, 0.02, 0.05, 0.2]))
    act = int(rng.integers(0, 8))
    return rem, toks, lam, iw, tbt, act


def test_token_table_matches_bruteforce_fuzz():
    rng = np.random.default_rng(0)
    tab = TokenSolverTable(COST)
    for _ in range(400):
        rem, toks, lam, iw, tbt, act = _random_solver_inputs(rng)
        d1 = solve_token_bruteforce(rem, toks, lam, COST, initial_wait=iw,
                                    tbt_budget=tbt, active_slots=act)
        d2 = tab.solve(rem, toks, lam, initial_wait=iw, tbt_budget=tbt,
                       active_slots=act)
        assert (d1.c, d1.b, d1.feasible) == (d2.c, d2.b, d2.feasible)
        assert d1.predicted_tbt == pytest.approx(d2.predicted_tbt)


budgets = st.lists(st.floats(0.05, 3.0), min_size=0, max_size=24)


@given(budgets, st.floats(0.0, 40.0), st.floats(0.0, 0.4),
       st.floats(0.0, 0.25), st.integers(0, 123))
@settings(deadline=None)
def test_token_table_matches_bruteforce_property(rem, lam, wait, tbt,
                                                 tok_seed):
    rng = np.random.default_rng(tok_seed)
    toks = rng.integers(1, 600, len(rem)).astype(np.float64)
    tbt = tbt if tbt > 0.01 else float("inf")
    tab = TokenSolverTable(COST)
    d1 = solve_token_bruteforce(rem, toks, lam, COST, initial_wait=wait,
                                tbt_budget=tbt)
    d2 = tab.solve(rem, toks, lam, initial_wait=wait, tbt_budget=tbt)
    assert (d1.c, d1.b, d1.feasible) == (d2.c, d2.b, d2.feasible)


def test_token_solver_tbt_constraint_forces_scale_up():
    """A tight per-token budget must rule out low-core configs."""
    loose = TokenSolverTable(COST).solve([1.0], [64], 1.0,
                                         tbt_budget=float("inf"))
    tight = TokenSolverTable(COST).solve([1.0], [64], 1.0,
                                         tbt_budget=0.013)
    assert tight.c > loose.c
    assert tight.predicted_tbt <= 0.013


def test_token_solver_fixed_work_special_case():
    """Zero decode + unit prompts: TBT vacuous, TTFT drain is Algorithm 1
    with group latency = prefill of b one-token requests."""
    fw = TokenCostModel(gamma_p=COST.gamma_p, delta_p=COST.delta_p,
                        gamma_d=0.0, delta_d=0.0, eps=COST.eps,
                        eta=COST.eta, mean_prompt=1.0, mean_decode=0.0)
    d = solve_token_bruteforce([0.5, 0.7], [1, 1], 2.0, fw)
    assert d.feasible and d.predicted_tbt >= 0.0
    # no decode stream anywhere -> TBT budget is ignored entirely
    d2 = solve_token_bruteforce([0.5, 0.7], [1, 1], 2.0, fw,
                                tbt_budget=1e-9)
    assert (d.c, d.b) == (d2.c, d2.b)


def test_token_memo_exact_at_quantum_zero_and_conservative():
    rng = np.random.default_rng(7)
    tab = TokenSolverTable(COST)
    memo0 = TokenMemoizedSolver(COST)
    memoq = TokenMemoizedSolver(COST, budget_quantum=0.02,
                                lam_quantum=0.5, token_quantum=16)
    for _ in range(120):
        rem, toks, lam, iw, tbt, act = _random_solver_inputs(rng)
        exact = tab.solve(rem, toks, lam, initial_wait=iw, tbt_budget=tbt,
                          active_slots=act)
        z = memo0.solve(rem, toks, lam, initial_wait=iw, tbt_budget=tbt,
                        active_slots=act)
        assert (z.c, z.b, z.feasible) == (exact.c, exact.b, exact.feasible)
        q = memoq.solve(rem, toks, lam, initial_wait=iw, tbt_budget=tbt,
                        active_slots=act)
        if exact.feasible and q.feasible:
            assert q.c >= exact.c       # never an optimistic allocation
    assert memoq.misses <= memo0.misses


def test_token_memo_cache_hits():
    memo = TokenMemoizedSolver(COST, budget_quantum=0.01, lam_quantum=0.5,
                               token_quantum=16)
    for _ in range(5):
        memo.solve([0.5, 0.9], [100, 40], 12.3, initial_wait=0.01,
                   tbt_budget=0.08, active_slots=3)
    assert memo.misses == 1 and memo.hits == 4


# --------------------------------------------------------------------------
# gang-true plan: E[longest of b] and the solve that plans each b by it
# --------------------------------------------------------------------------
B_SET = (1, 4, 8, 16, 32)


def _chat_lengths(n=512, seed=0):
    """Decode lengths of the chat mix: log-normal, median 24, sigma 0.6,
    clipped to [1, 128]."""
    return lognormal_lengths(np.random.default_rng(seed), n, median=24,
                             sigma=0.6, lo=1, hi=128)


@pytest.mark.parametrize("sample", [[5], [3, 1], [2, 2, 7], [1, 4, 4, 9],
                                    [6, 1, 3, 8, 2]])
def test_expected_longest_matches_enumeration(sample):
    import itertools
    got = expected_longest(sample, (1, 2, 3, 4))
    for b, v in got.items():
        draws = list(itertools.product(sample, repeat=b))
        assert v == pytest.approx(np.mean([max(d) for d in draws]),
                                  rel=1e-12)


def test_expected_longest_bounds():
    x = _chat_lengths()
    got = expected_longest(x, B_SET)
    assert got[1] == pytest.approx(x.mean(), rel=1e-12)
    vals = [got[b] for b in B_SET]
    assert all(a <= b for a, b in zip(vals, vals[1:]))
    assert vals[-1] <= x.max()
    # the chat mix's gangs: ~48 steps at b=4, ~81 at b=32 against a mean
    # of ~28
    assert 44 < got[4] < 52 and 75 < got[32] < 90 < x.max()
    with pytest.raises(ValueError):
        expected_longest([], B_SET)


def _gang_plan(rng, kind):
    """Per-b decode steps: a random nondecreasing plan, or the mean."""
    if kind == "mean":
        return dict.fromkeys(C16, COST.mean_decode)
    return dict(zip(C16, np.sort(rng.uniform(1.0, 90.0, len(C16)))))


@pytest.mark.parametrize("kind", ["gang", "mean"])
def test_token_solvers_agree_under_a_gang_plan(kind):
    """Table, memo and bruteforce agree decision for decision with a
    per-b plan; a plan equal to the mean everywhere reproduces the
    default plan's decisions exactly."""
    rng = np.random.default_rng(11)
    tab = TokenSolverTable(COST)
    memo = TokenMemoizedSolver(COST)
    for _ in range(150):
        rem, toks, lam, iw, tbt, act = _random_solver_inputs(rng)
        gang = _gang_plan(rng, kind)
        kw = dict(initial_wait=iw, tbt_budget=tbt, active_slots=act)
        d1 = solve_token_bruteforce(rem, toks, lam, COST, gang_steps=gang,
                                    **kw)
        d2 = tab.solve(rem, toks, lam, gang_steps=gang, **kw)
        d3 = memo.solve(rem, toks, lam, gang_steps=gang, **kw)
        assert (d1.c, d1.b, d1.feasible) == (d2.c, d2.b, d2.feasible) \
            == (d3.c, d3.b, d3.feasible)
        assert d1.predicted_tbt == pytest.approx(d2.predicted_tbt)
        if kind == "mean":
            d0 = solve_token_bruteforce(rem, toks, lam, COST, **kw)
            t0 = tab.solve(rem, toks, lam, **kw)
            assert (d0.c, d0.b, d0.feasible, d0.predicted_tbt) \
                == (d1.c, d1.b, d1.feasible, d1.predicted_tbt)
            assert (t0.c, t0.b, t0.feasible, t0.predicted_tbt) \
                == (d2.c, d2.b, d2.feasible, d2.predicted_tbt)


def test_gang_throughput_at_the_mean_is_throughput():
    bb, cc = np.meshgrid(np.arange(1, 33), np.arange(1, 17))
    assert np.array_equal(COST.gang_throughput(bb, cc, COST.mean_decode),
                          COST.throughput(bb, cc))


def test_gang_plan_rejects_b4_above_its_knee():
    """A chip-like surface (decode 2.5 ms + 0.1 ms per slot, prefill
    2.5 ms + 1.76 us per token, 128-token prompts) at the chat mix's
    lengths: by the mean b=4 sustains 34.5 req/s x 1.05, by its longest
    stream it does not, and b=8 does."""
    x = _chat_lengths()
    cost = TokenCostModel(gamma_p=0.0, delta_p=1.76e-6, gamma_d=0.0,
                          delta_d=1e-4, eps=0.0, eta=2.5e-3,
                          mean_prompt=128.0, mean_decode=float(x.mean()))
    gang = expected_longest(x, B_SET)
    lam = 34.5 * 1.05
    assert cost.throughput(4, 1) > lam > cost.gang_throughput(4, 1, gang[4])
    assert cost.gang_throughput(8, 1, gang[8]) > lam
    for solve in (
            lambda **k: solve_token_bruteforce([], [], lam, cost, (1,),
                                               B_SET, **k),
            lambda **k: TokenSolverTable(cost, (1,), B_SET).solve(
                [], [], lam, **k)):
        assert solve().b == 4
        d = solve(gang_steps=gang)
        assert d.feasible and d.b == 8


# --------------------------------------------------------------------------
# request / queue token surfaces
# --------------------------------------------------------------------------
def test_request_token_fields_and_violation_semantics():
    r = Request.make(arrival=1.0, comm_latency=0.1, slo=1.0,
                     prompt_tokens=64, decode_tokens=10, tbt_slo=0.05)
    assert r.is_autoregressive and r.deadline == pytest.approx(1.9)
    r.first_token = 1.8
    r.finish = 5.0                      # late *completion* is fine
    assert not r.violated
    r.tbt_violations = 1                # one slow token is not
    assert r.violated
    fixed = Request.make(arrival=1.0, comm_latency=0.1, slo=1.0)
    fixed.finish = 5.0
    assert fixed.violated and not fixed.is_autoregressive


def test_queue_token_snapshots_agree():
    reqs = [Request.make(arrival=0.01, comm_latency=0.01, slo=s,
                         prompt_tokens=p, decode_tokens=4, tbt_slo=t)
            for s, p, t in ((1.0, 64, 0.08), (0.5, 32, 0.05),
                            (2.0, 400, 0.2))]
    q = EDFQueue()
    q.extend(reqs)
    rem, toks, tbt = q.token_snapshot(0.0)
    assert np.all(np.diff(rem) >= 0)
    assert toks.tolist() == [32, 64, 400]       # aligned to EDF order
    assert tbt == 0.05

    batch = RequestBatch.from_send(
        np.zeros(3), np.full(3, 0.01), slo=np.array([1.0, 0.5, 2.0]),
        prompt_tokens=np.array([64, 32, 400]),
        decode_tokens=np.full(3, 4), tbt_slo=np.array([0.08, 0.05, 0.2]))
    fq = TokenFastEDFQueue()
    fq.bind(batch.prompt_tokens, batch.tbt_slo)
    for i in range(3):
        fq.push(batch.deadline[i], i)
    frem, ftoks, ftbt = fq.token_snapshot(0.0)
    assert np.allclose(frem, rem) and ftoks.tolist() == toks.tolist()
    assert ftbt == tbt


def test_request_batch_token_columns_roundtrip():
    batch = _token_batch(n=50, seed=3)
    assert batch.total_tokens == int(batch.decode_tokens.sum()) + 50
    reqs = batch.to_requests()
    i = 25
    assert reqs[i].prompt_tokens == batch.prompt_tokens[i]
    assert reqs[i].decode_tokens == batch.decode_tokens[i]
    head = batch.head(10)
    assert len(head) == 10 and head.prompt_tokens.size == 10
    # defaults: a token-less batch is fixed work
    plain = RequestBatch.from_send(np.arange(5.0), np.full(5, 0.01),
                                   slo=1.0)
    assert plain.prompt_tokens.tolist() == [1] * 5
    assert plain.decode_tokens.tolist() == [0] * 5
    assert np.all(np.isinf(plain.tbt_slo))


# --------------------------------------------------------------------------
# continuous-batching engines
# --------------------------------------------------------------------------
def test_token_fast_runner_serves_everything():
    batch = _token_batch(n=600, duration=60.0, seed=1)
    scaler = TokenSpongeScaler(COST)
    runner = TokenFastSimRunner(scaler, COST, c0=16, prior_rps=10.0)
    rep = runner.run(batch)
    assert rep.n_requests == len(batch)
    assert rep.tokens_served == batch.total_tokens
    assert rep.backend == "token-sim-fast"
    assert np.isfinite(rep.ttft_p99) and rep.ttft_p99 > 0
    assert 0.0 <= rep.tbt_violation_rate <= 1.0
    assert rep.core_seconds > 0 and len(scaler.decisions) > 0
    assert rep.tokens_per_s > 0


def test_token_fast_runner_join_leave_semantics():
    """Two staggered requests share the decode stream: the second joins
    while the first is mid-stream and both finish in one busy period."""
    send = np.array([0.0, 0.05])
    cl = np.full(2, 0.01)
    batch = RequestBatch.from_send(send, cl, slo=5.0,
                                   prompt_tokens=np.array([32, 32]),
                                   decode_tokens=np.array([40, 5]),
                                   tbt_slo=np.inf)
    scaler = TokenSpongeScaler(COST, adaptation_interval=0.1)
    runner = TokenFastSimRunner(scaler, COST, c0=8, tick=0.1)
    rep = runner.run(batch)
    assert rep.n_requests == 2
    assert rep.tokens_served == 2 + 40 + 5
    # the short stream must finish well before the long one
    assert rep.mean_latency < rep.p99


def test_token_fast_runner_chunked_admission_protects_tbt():
    """A huge prompt arriving mid-stream must not blow the running
    slots' per-token budget: it is deferred, not interleaved."""
    send = np.array([0.0, 0.2])
    cl = np.full(2, 0.01)
    batch = RequestBatch.from_send(
        send, cl, slo=np.array([1.0, 10.0]),
        prompt_tokens=np.array([16, 4096]),
        decode_tokens=np.array([200, 4]),
        tbt_slo=np.array([0.012, np.inf]))
    # freeze the allocation at c=4 (single entry) so the scaler cannot
    # absorb the prompt by scaling up
    scaler = TokenSpongeScaler(COST, c_set=(4,), b_set=(1, 2, 4, 8))
    runner = TokenFastSimRunner(scaler, COST, c_set=(4,),
                                b_set=(1, 2, 4, 8), c0=4)
    rep = runner.run(batch)
    assert rep.n_requests == 2
    # prefill of 4096 tokens at c=4 takes ~0.2s >> the 12ms TBT budget;
    # chunk-bounded admission defers it so no decode token is late
    assert rep.tbt_violation_rate == 0.0


def test_token_sim_backend_exact_loop():
    batch = _token_batch(n=150, duration=20.0, seed=5)
    scaler = TokenSpongeScaler(COST)
    backend = TokenSimBackend(COST, C16, C16, c0=16)
    runner = ScenarioRunner(scaler, backend)
    runner.monitor.rate.prior_rps = 8
    rep = runner.run(batch.to_requests())
    assert rep.n_requests == len(batch)
    assert rep.tokens_served == batch.total_tokens
    assert backend.tokens_served == batch.total_tokens
    assert np.isfinite(rep.ttft_p99)
    # per-request finishes are heterogeneous inside a gang
    fins = {r.finish for r in runner.monitor.completed[:40]}
    assert len(fins) > 1


# --------------------------------------------------------------------------
# scenarios + launcher
# --------------------------------------------------------------------------
def test_llm_scenarios_registered_and_sane():
    from repro.serving.scenarios import SCENARIOS, build_scenario
    for name in ("llm-chat", "llm-mixed-len"):
        assert name in SCENARIOS
        batch, meta = build_scenario(name, duration=30, seed=2)
        assert meta["token"] and isinstance(meta["cost"], TokenCostModel)
        assert np.all(batch.prompt_tokens >= 1)
        assert np.all(batch.decode_tokens >= 1)
        assert np.all(np.isfinite(batch.tbt_slo))
        assert np.all(np.diff(batch.arrival) >= 0)


@pytest.mark.parametrize("name", ["llm-chat", "llm-mixed-len"])
def test_llm_scenarios_run_on_both_engines(name):
    from repro.serving.scenarios import run_scenario
    fast, stats = run_scenario(name, engine="fast", duration=60, seed=7)
    assert fast.n_requests > 0 and fast.tokens_served > 0
    assert stats["engine"] == "fast" and "solver" in stats
    exact, _ = run_scenario(name, engine="exact", duration=25, seed=7)
    assert exact.n_requests > 0 and exact.tokens_served > 0


def test_llm_scenario_rejects_fixed_work_policies():
    from repro.serving.scenarios import run_scenario
    with pytest.raises(ValueError):
        run_scenario("llm-chat", policy="static-8", duration=20)


def test_llm_scenarios_via_launcher():
    from repro.launch.serve import main
    main(["--scenario", "llm-chat", "--duration", "20", "--seed", "4"])
    main(["--scenario", "llm-mixed-len", "--duration", "20", "--seed",
          "4", "--engine", "exact"])


# --------------------------------------------------------------------------
# satellites: λ-estimator guard + shared decision resolution
# --------------------------------------------------------------------------
def test_rate_estimator_single_arrival_guard():
    est = RateEstimator(window_s=5.0)
    est.observe(100.0)                  # lone arrival exactly at the tick
    assert est.rate(100.0) == pytest.approx(1.0 / 5.0)
    est2 = RateEstimator(window_s=5.0)
    assert est2.rate(50.0) == 0.0       # empty window after idle gap


def test_fastpath_rate_matches_estimator_on_idle_gap_edge():
    """The two-pointer fast-path λ (now owned by the online session) and
    RateEstimator must agree on the degenerate single-arrival-after-idle
    case (equivalence contract)."""
    from repro.core.baselines import SpongePolicy
    from repro.core.scaler import SpongeScaler
    runner = FastSimRunner(SpongePolicy(SpongeScaler(PERF)), PERF,
                           c0=16)
    sess = runner.session()
    sess._arr = [100.0]                 # one processed arrival
    est = RateEstimator(window_s=runner.rate_window)
    est.observe(100.0)
    assert sess._rate(100.0) == pytest.approx(est.rate(100.0))
    assert sess._rate(100.0) < 1.0      # not a million-rps spike


def test_resolve_decision_shared_rule():
    assert resolve_decision((1, 2, 4, 8), Decision(c=3, b=5)) == (4, 5)
    assert resolve_decision((1, 2, 4, 8), Decision(c=9, b=0)) == (8, 1)
    assert resolve_decision((1, 2, 4, 8), Decision(c=4, b=2)) == (4, 2)


# --------------------------------------------------------------------------
# real kernels: model glue + TokenJaxBackend
# --------------------------------------------------------------------------
def test_pallas_prefill_route_matches_jnp_path():
    import dataclasses
    import jax
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("smollm-135m-reduced")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    toks = np.ones((2, 16), np.int32)
    lg0, _ = model.prefill(params, {"tokens": toks}, cache_len=24)
    kcfg = dataclasses.replace(cfg, use_pallas_prefill=True)
    lg1, _ = build_model(kcfg).prefill(params, {"tokens": toks},
                                       cache_len=24)
    assert np.allclose(np.asarray(lg0), np.asarray(lg1), atol=1e-4)


def test_token_jax_backend_end_to_end():
    from repro.serving.token_backend import run_token_jax_scenario
    rep, stats = run_token_jax_scenario("llm-chat", requests=8, seed=3,
                                        prompt_len=8, max_decode=3)
    assert rep.n_requests > 0
    assert stats["tokens_executed"] == rep.tokens_served > 0
    assert np.isfinite(rep.ttft_p99)
    assert stats["engine"] == "token-jax"


# --------------------------------------------------------------------------
# gang-true plan: calibration and wiring
# --------------------------------------------------------------------------
class _HostToken:
    """A decode call's token: fetching it to the host costs ``trip_s``."""

    def __init__(self, b, trip_s):
        self.b, self.trip_s = b, trip_s

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.trip_s)
        return np.zeros(self.b, np.int32)


def test_calibration_times_steps_with_the_token_round_trip():
    """Decode samples are per step of a run of steps, each fetching its
    token and feeding the host array back, so the fitted decode cost
    holds the round trip that one bare call leaves out.  (The fit shares
    one per-call overhead between the axes, so the fake prefill pays the
    decode step's.)"""
    from repro.serving.token_backend import (CALIBRATION_STEPS,
                                             calibrate_token_fns)
    call_s = {b: 0.002 + 0.0005 * b for b in (1, 2, 4, 8)}
    trip_s = 0.003
    fed = {b: [] for b in call_s}

    def make(b):
        def prefill(tokens):
            time.sleep(0.005 + 1e-4 * tokens.size)
            return np.zeros(b, np.int32), "cache"

        def decode(cache, tok):
            fed[b].append(type(tok))
            time.sleep(call_s[b])
            return _HostToken(b, trip_s), cache
        return prefill, decode

    pre, dec = {}, {}
    for b in call_s:
        pre[(1, b)], dec[(1, b)] = make(b)
    cost = calibrate_token_fns(pre, dec, prompt_len=8, mean_decode=5.0)
    for b, calls in fed.items():
        assert calls == [np.ndarray] * (CALIBRATION_STEPS + 1)
        step = float(cost.decode_latency(1, b))
        assert call_s[b] + 0.8 * trip_s < step < 2 * (call_s[b] + trip_s)


def _gang_runner(holds):
    backend_cls = type("GangBackend", (TokenSimBackend,),
                       {"holds_gang_slots": holds})
    scaler = TokenSpongeScaler(COST, adaptation_interval=0.25)
    runner = ScenarioRunner(scaler, backend_cls(COST, C16, C16, c0=16),
                            tick=0.25)
    runner.monitor.rate.prior_rps = 8
    return scaler, runner


def test_gang_plan_engages_only_where_the_backend_holds_gang_slots():
    from repro.serving.api import GANG_MIN_SAMPLE
    from repro.serving.token_backend import TokenJaxBackend
    assert TokenJaxBackend.holds_gang_slots
    assert not TokenSimBackend.holds_gang_slots
    batch = _token_batch(n=200, duration=20.0, seed=5)
    plain, runner = _gang_runner(False)
    runner.run(batch)
    assert plain.gang_plans == 0 and not plain.last_gang
    assert plain.solver_stats()["drag"] == dict.fromkeys(
        C16, COST.mean_decode)
    gang, runner = _gang_runner(True)
    runner.run(batch)
    assert 0 < gang.gang_plans < len(gang.decisions)
    assert gang.solver_stats()["gang_plans"] == gang.gang_plans
    assert len(runner.monitor.completed) > GANG_MIN_SAMPLE
    want = expected_longest([r.decode_tokens
                             for r in runner.monitor.completed], C16)
    assert gang.solver_stats()["drag"] == pytest.approx(want)
    # the plan is the runner's window of completed requests
    assert runner.gang_steps(C16) == pytest.approx(want)
