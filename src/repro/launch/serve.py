"""Serving launcher: Sponge end-to-end through the unified serving API.

Three modes, one control plane (``repro.serving.api.SpongeServer``):

* ``--mode live`` — real JAX inference (reduced arch, resolved through
  ``configs.registry``) behind the Sponge control plane: EDF queue, dynamic
  batching, IP-solver scaler, executable table.  This is the paper's
  Fig. 2 pipeline with an actual model.  ``--policy fa2`` exercises the
  multi-instance live path (horizontal one-core replicas over the same
  executable table).
* ``--mode sim``  — the trace-driven discrete-event study (Fig. 4):
  Sponge vs FA2 vs static 8/16 under a 4G bandwidth trace.
* ``--scenario <name>`` — run a registered workload scenario
  (``repro.serving.scenarios``; see ``docs/scenarios.md``) through the
  million-request fast engine (or ``--engine exact`` for the object-based
  loop).  ``--requests N`` sizes the run by request count instead of
  duration.  Token scenarios (``llm-chat``, ``llm-mixed-len``) run the
  continuous-batching engines and report tokens/s, TTFT p99 and the
  per-token violation rate; ``--engine jax`` serves a slice of them on
  the **real Pallas kernels** (swa_prefill + decode_attention) through
  ``repro.serving.token_backend.TokenJaxBackend``.  Fleet scenarios
  (``replica-failure``, ``rolling-restart``, ``fleet-flash-crowd``) run
  the joint horizontal + vertical engines (``repro.serving.fleet``);
  ``--replicas`` sizes the deploy-time fleet and ``--router`` picks the
  arrival router (``least-loaded`` / ``jsq`` / ``edf-deadline``).
  Degradation scenarios (``degrade-sustained-overload``,
  ``degrade-flash-overload``, ``degrade-fade-overload``) run the
  (m, n, c, b) planner over a model ladder; ``--model-ladder`` attaches
  (or overrides) the ladder, ``--accuracy-floor`` bounds the shed and
  ``--policy fixed-<arch>`` pins one rung (the fixed-model baseline).
  Multi-tenant scenarios (``mixed-zoo``, ``mixed-zoo-rush``) run the
  shared-pool engines (``repro.serving.tenancy``); ``--tenants`` picks
  the pool reallocation policy and ``--pool-cores`` the core budget.

    PYTHONPATH=src python -m repro.launch.serve --mode live \
        --arch smollm-135m-reduced --rps 10 --duration 10
    PYTHONPATH=src python -m repro.launch.serve --mode sim --duration 600
    PYTHONPATH=src python -m repro.launch.serve --scenario flash-crowd
    PYTHONPATH=src python -m repro.launch.serve --scenario steady \
        --requests 1000000
    PYTHONPATH=src python -m repro.launch.serve --scenario llm-chat \
        --requests 100000
    PYTHONPATH=src python -m repro.launch.serve --scenario llm-chat \
        --engine jax --requests 24
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.core.perf_model import yolov5s_like
from repro.core.slo import Request
from repro.network.latency import comm_latency
from repro.network.traces import synth_4g_trace
from repro.serving.api import make_live_server, make_sim_server
from repro.serving.workload import WorkloadGenerator

SIM_POLICIES = (("sponge", dict(c0=16)),
                ("fa2", dict(c0=1)),
                ("static-8", dict(c0=8)),
                ("static-16", dict(c0=16)))


def run_sim(args) -> dict:
    perf = yolov5s_like()
    trace = synth_4g_trace(args.duration, seed=args.seed)
    wl = WorkloadGenerator(rps=args.rps, slo=args.slo, size_kb=args.size_kb)

    out = {}
    for name, kw in SIM_POLICIES:
        server = make_sim_server(perf, name, prior_rps=args.rps,
                                 slo=args.slo, expected_rps=args.rps, **kw)
        out[name] = server.serve(wl, trace)
    for k, v in out.items():
        print(f"{k:10s} violations={v['violation_rate']*100:6.2f}%  "
              f"avg_cores={v['avg_cores']:6.2f}  p99={v['p99']:.3f}s")
    sp, fa = out["sponge"], out["fa2"]
    print("SLO-violation reduction vs FA2: "
          f"{fa['violation_rate']/max(sp['violation_rate'],1e-9):.1f}x "
          "(paper: >15x)")
    print("CPU reduction vs static-16: "
          f"{100*(1-sp['avg_cores']/out['static-16']['avg_cores']):.1f}% "
          "(paper: >20%)")
    return out


def run_live(args) -> dict:
    c_set, b_set = (1, 2, 4, 8), (1, 2, 4, 8)
    server, cfg = make_live_server(
        args.arch, c_set=c_set, b_set=b_set, prompt_len=args.prompt_len,
        gen_tokens=args.gen_tokens, policy=args.policy,
        adaptation_interval=0.5, prior_rps=args.rps, slo=args.slo,
        expected_rps=args.rps)
    perf = server.backend.perf
    print(f"calibrated perf model: r2={perf.r2:.3f} "
          f"l(1,1)={perf.latency(1,1)*1e3:.1f}ms")
    server.warmup(np.ones(args.prompt_len, np.int32))

    trace = synth_4g_trace(int(args.duration) + 5, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    arrivals = []
    for i in range(int(args.rps * args.duration)):
        ts = i / args.rps
        cl = comm_latency(args.size_kb, trace, ts)
        req = Request.make(arrival=ts + cl, comm_latency=cl, slo=args.slo)
        arrivals.append((req, rng.integers(
            0, cfg.vocab_size, args.prompt_len).astype(np.int32)))
    report = server.run(arrivals, horizon=args.duration + 30)
    res = {"sent": len(arrivals), "served": len(server.monitor.completed),
           "n": report.n_requests, "violations": report.n_violations,
           "violation_rate": report.violation_rate,
           "p50": report.p50, "p99": report.p99,
           "decisions": len(report.decisions or ()),
           "instances": len(server.pool)}
    print(json.dumps(res, indent=1, default=float))
    return res


def run_scenario_mode(args) -> dict:
    q = args.admission_quantile
    if q is not None and not (q == 0.0 or 0.0 < q < 1.0):
        raise SystemExit("--admission-quantile must be in [0, 1) "
                         f"(0 disables the uncertainty path), got {q}")
    if args.engine == "jax":
        if q is not None or args.no_speculative:
            raise SystemExit("--admission-quantile/--no-speculative run "
                             "on the fast/exact token engines, not "
                             "--engine jax")
        from repro.serving.token_backend import run_token_jax_scenario
        if args.policy != "sponge":
            raise SystemExit("--engine jax runs the sponge policy only "
                             f"(got --policy {args.policy!r})")
        if args.duration is not None:
            raise SystemExit("--engine jax sizes the run by --requests, "
                             "not --duration")
        report, stats = run_token_jax_scenario(
            args.scenario, requests=args.requests or 24, seed=args.seed,
            arch=args.arch, prompt_len=args.prompt_len,
            max_decode=args.gen_tokens, rps=args.rps)
    else:
        from repro.serving.scenarios import run_scenario
        report, stats = run_scenario(
            args.scenario, policy=args.policy, engine=args.engine,
            duration=args.duration, rps=args.rps,
            seed=args.seed, requests=args.requests,
            replicas=args.replicas, router=args.router,
            tenant_policy=args.tenants, pool_cores=args.pool_cores,
            mid_flight=not args.no_mid_flight,
            admission_quantile=args.admission_quantile,
            speculative=not args.no_speculative,
            model_ladder=args.model_ladder,
            accuracy_floor=args.accuracy_floor)
    ev = stats["events"]
    dt = stats["run_wall_s"]            # engine time only (no generation)
    out = {"scenario": args.scenario, "engine": stats["engine"],
           "policy": report.policy, "n": report.n_requests,
           "violation_rate": report.violation_rate,
           "p50": report.p50, "p99": report.p99,
           "avg_cores": report.avg_cores,
           "events": ev, "events_per_s": ev / max(dt, 1e-9),
           "wall_s": dt}
    if report.tokens_served:            # token scenarios: the ISSUE-3 bar
        out.update(tokens_served=report.tokens_served,
                   tokens_per_s=report.tokens_per_s,
                   ttft_p50=report.ttft_p50, ttft_p99=report.ttft_p99,
                   tbt_violation_rate=report.tbt_violation_rate)
    if "max_replicas" in stats:         # fleet scenarios: the ISSUE-4 bar
        out.update(max_replicas=stats["max_replicas"],
                   router=stats["router"])
    if "ladder" in stats:               # degradation runs: the ISSUE-9 bar
        out.update(core_seconds=report.core_seconds,
                   ladder=stats["ladder"],
                   accuracy_floor=stats["accuracy_floor"],
                   accuracy_goodput=report.accuracy_goodput,
                   mean_served_accuracy=report.mean_served_accuracy,
                   model_swaps=report.model_swaps)
    if "session" in stats:              # session scenarios: the ISSUE-5 bar
        out.update(n_cancelled=report.n_cancelled, **{
            f"mid_flight_{k}": v for k, v in stats["session"].items()})
    if "pool" in stats:                 # multi-tenant scenarios: ISSUE-6
        p = stats["pool"]
        out.update(pool_policy=p["policy"], pool_cores=p["budget"],
                   pool_caps=list(p["caps"]), pool_swaps=p["swaps"],
                   tenants={name: {"n": t["n_requests"],
                                   "violation_rate": t["violation_rate"],
                                   "core_seconds": t["core_seconds"]}
                            for name, t in stats["tenants"].items()})
    if "uncertainty" in stats:          # distribution-aware runs: ISSUE-7
        u = stats["uncertainty"]
        out.update(n_cancelled=report.n_cancelled,
                   admission_quantile=u["quantile"],
                   slack_factor=u["slack_factor"],
                   calibration_error=u["calibration_error"],
                   overrun_cancels=u["overrun_cancels"])
    if "solver" in stats:
        out["solver_hit_rate"] = stats["solver"].get("hit_rate")
    print(json.dumps(out, indent=1, default=float))
    return out


def main(argv=None) -> dict:
    """Parse ``argv`` and run the selected mode; returns its result."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("sim", "live", "scenario"),
                    default="sim")
    try:
        from repro.serving.scenarios import list_scenarios
        # argparse %-formats help text: escape literal percent signs
        scenario_help = "; ".join(f"{k}: {v}"
                                  for k, v in list_scenarios().items()
                                  ).replace("%", "%%")
    except Exception:                               # pragma: no cover
        scenario_help = "registered workload scenario"
    ap.add_argument("--scenario", default=None,
                    help=f"run a registered scenario ({scenario_help})")
    ap.add_argument("--engine", choices=("fast", "exact", "vector", "jax"),
                    default="fast",
                    help="scenario mode: struct-of-arrays fast engine, "
                         "the object-based exact loop, the batched-tick "
                         "vectorpath (plain scenarios; docs/performance.md), "
                         "or (token scenarios) the real-kernel "
                         "TokenJaxBackend")
    ap.add_argument("--requests", type=int, default=None,
                    help="scenario mode: size the run by request count")
    ap.add_argument("--replicas", type=int, default=None,
                    help="fleet scenarios: deploy-time replica count "
                         "(overrides the scenario's n0)")
    ap.add_argument("--router", default=None,
                    choices=("least-loaded", "jsq", "edf-deadline"),
                    help="fleet scenarios: arrival router across replicas")
    ap.add_argument("--tenants", default=None,
                    choices=("priority", "fair-share", "greedy-marginal"),
                    help="multi-tenant scenarios (mixed-zoo*): the pool "
                         "reallocation policy (default greedy-marginal)")
    ap.add_argument("--pool-cores", type=int, default=None,
                    help="multi-tenant scenarios: total core budget of "
                         "the shared pool (default: the scenario's, 128)")
    ap.add_argument("--no-mid-flight", action="store_true",
                    help="session scenarios: suppress the mid-flight "
                         "update_slo/cancel stream (the closed-world "
                         "replay of the same workload)")
    ap.add_argument("--admission-quantile", type=float, default=None,
                    help="token scenarios with a declared decode-length "
                         "distribution: plan admission at this quantile "
                         "(0 disables the uncertainty path — the "
                         "deterministic-cost baseline; default: the "
                         "scenario's own quantile)")
    ap.add_argument("--model-ladder", default=None,
                    help="fleet scenarios: attach a model ladder and run "
                         "the (m, n, c, b) planner — 'default', 'full' or "
                         "a comma-separated registry arch list (degrade-* "
                         "scenarios carry 'default' already); "
                         "--policy fixed-<arch> pins one rung")
    ap.add_argument("--accuracy-floor", type=float, default=None,
                    help="ladder runs: never shed below this accuracy "
                         "score (default: the scenario's own floor, 0.60 "
                         "for the degrade-* family)")
    ap.add_argument("--no-speculative", action="store_true",
                    help="distribution-aware runs: disable speculative "
                         "over-admission with cancel-on-overrun (streams "
                         "run to completion; the solver still plans at "
                         "the admission quantile)")
    ap.add_argument("--arch", default="smollm-135m-reduced")
    ap.add_argument("--policy", default="sponge")
    # None = "use the mode's default" (scenarios carry their own rps /
    # duration defaults; sim/live keep the historical 20 rps / 600 s)
    ap.add_argument("--rps", type=float, default=None)
    ap.add_argument("--slo", type=float, default=1.0)
    ap.add_argument("--size-kb", type=float, default=200.0)
    ap.add_argument("--duration", type=float, default=None)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-tokens", type=int, default=8)
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args(argv)
    if args.scenario or args.mode == "scenario":
        if not args.scenario:
            ap.error("--mode scenario requires --scenario <name>")
        return run_scenario_mode(args)
    args.rps = 20.0 if args.rps is None else args.rps
    args.duration = 600.0 if args.duration is None else args.duration
    if args.mode == "sim":
        return run_sim(args)
    return run_live(args)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
