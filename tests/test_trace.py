"""The serving path's host spans (``repro.utils.trace``), the token
backend's counters, and the names the device programs carry.

A profiled run of the real-kernel token stack on the CPU (Pallas in
interpret mode) must hold one span per layer boundary, nested as the
calls are, and as many ``model.*`` spans as the backend counts calls."""
import glob
import re
import time

import numpy as np
import pytest

SPANS = ("runner.step", "runner.tick", "control.decide", "runner.dispatch",
         "backend.gang", "backend.pad", "model.prefill", "model.decode",
         "backend.deliver")


def _profile(fn, tmp_path):
    """Run ``fn()`` under the profiler, as the benchmark does (host spans
    only, no Python tracer); returns fn's result and the host spans
    ``(start, end, name, args)`` in start order."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append((ev.start_ns, ev.end_ns, ev.name,
                                      dict(ev.stats)))
    return out, sorted(spans, key=lambda s: (s[0], -s[1]))


def test_span_records_name_and_args_while_profiling(tmp_path):
    from repro.utils.trace import span
    with span("runner.step"):       # no profiler: records nothing
        pass

    def body():
        with span("backend.gang", gang=7, b=4, ids="3 5"):
            with span("backend.pad", gang=7):
                pass
    _, spans = _profile(body, tmp_path)
    assert [(n, a) for _, _, n, a in spans] == [
        ("backend.gang", {"gang": 7, "b": 4, "ids": "3 5"}),
        ("backend.pad", {"gang": 7})]


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """A short llm-chat slice served by the real-kernel stack, profiled
    from the first submit to the drain."""
    from repro.serving.token_backend import run_token_jax_scenario
    path = tmp_path_factory.mktemp("trace")
    (rep, stats), spans = _profile(lambda: run_token_jax_scenario(
        "llm-chat", requests=10, seed=5, prompt_len=8, max_decode=3),
        path)
    return rep, stats, spans


def test_traced_run_holds_every_span(traced_run):
    _, _, spans = traced_run
    assert {n for _, _, n, _ in spans} == set(SPANS)


def test_device_calls_nest_in_their_gang(traced_run):
    _, stats, spans = traced_run
    gangs = [s for s in spans if s[2] == "backend.gang"]
    assert [g[3]["gang"] for g in gangs] == list(range(len(gangs)))
    served = {r.id for r in stats["requests"]}
    for s, e, name, args in spans:
        if name.startswith("model.") or (name.startswith("backend.")
                                         and name != "backend.gang"):
            outer = [g for g in gangs if g[0] <= s and e <= g[1]]
            assert len(outer) == 1, (name, args)
            if "gang" in args:
                assert args["gang"] == outer[0][3]["gang"]
    for _, _, _, args in gangs:
        ids = [int(i) for i in args["ids"].split()]
        assert len(ids) == args["n"] <= args["b"]
        assert set(ids) <= served
    decides = [s for s in spans if s[2] == "control.decide"]
    assert decides and all({"c", "b"} <= set(a) for *_, a in decides)
    # every decision runs inside a tick, every tick inside a runner step
    for inner, outer in (("control.decide", "runner.tick"),
                         ("runner.tick", "runner.step"),
                         ("runner.dispatch", "runner.step"),
                         ("backend.gang", "runner.dispatch")):
        outs = [s for s in spans if s[2] == outer]
        for s, e, _, _ in (x for x in spans if x[2] == inner):
            assert any(o[0] <= s and e <= o[1] for o in outs), inner


def test_decide_spans_carry_the_planned_drag(traced_run, tmp_path):
    """``control.decide`` carries the decode steps planned for the chosen
    b and whether the gang-true plan made them: not in the short real
    run (fewer completions than the plan needs), and in every decision
    of a simulated run on a gang-holding backend once enough completed."""
    from repro.core.cost_model import TokenCostModel
    from repro.core.scaler import TokenSpongeScaler
    from repro.serving.api import ScenarioRunner, TokenSimBackend
    from repro.serving.workload import RequestBatch
    rep, stats, spans = traced_run
    mean = stats["backend"].cost.mean_decode
    decides = [a for *_, n, a in spans if n == "control.decide"]
    assert decides and all(a["gang"] == 0 and a["drag"] == pytest.approx(
        mean) for a in decides)

    cost = TokenCostModel.smollm_like()
    rng = np.random.default_rng(2)
    batch = RequestBatch.from_send(
        np.sort(rng.uniform(0, 20, 200)), np.full(200, 0.05), slo=1.0,
        prompt_tokens=64, decode_tokens=rng.integers(1, 60, 200),
        tbt_slo=0.08)
    backend = type("GangBackend", (TokenSimBackend,),
                   {"holds_gang_slots": True})(cost, (8, 16), (1, 4, 8),
                                               c0=16)
    scaler = TokenSpongeScaler(cost, c_set=(8, 16), b_set=(1, 4, 8),
                               adaptation_interval=0.25)
    runner = ScenarioRunner(scaler, backend, tick=0.25)
    _, spans = _profile(lambda: runner.run(batch), tmp_path)
    decides = [a for *_, n, a in spans if n == "control.decide"]
    gang = [a for a in decides if a["gang"] == 1]
    assert len(gang) == scaler.gang_plans > 0
    assert decides[-1] == gang[-1]
    assert gang[-1]["drag"] == pytest.approx(
        scaler.last_drag[gang[-1]["b"]])


def test_span_counts_match_the_backend_counters(traced_run):
    _, stats, spans = traced_run
    n = stats["backend"].counters()
    count = {k: sum(1 for s in spans if s[2] == k) for k in SPANS}
    assert count["model.decode"] == n["decode_calls"] > 0
    assert count["model.prefill"] == count["backend.gang"] \
        == count["backend.pad"] == n["prefill_calls"] > 0
    assert count["backend.deliver"] == n["prefill_calls"] + n["decode_calls"]


def test_counters_agree_with_the_run(traced_run):
    rep, stats, _ = traced_run
    backend, reqs = stats["backend"], stats["requests"]
    n = backend.counters()
    from repro.serving.token_backend import COUNTERS
    assert list(n) == list(COUNTERS)
    buckets = rep.buckets            # (t, c, bucket b, real requests)
    assert n["prefill_calls"] == len(buckets)
    assert n["prefill_rows"] == sum(b for _, _, b, _ in buckets)
    assert n["first_tokens"] == sum(k for _, _, _, k in buckets) \
        == len(reqs)
    assert n["decode_tokens"] == sum(min(r.decode_tokens, 3) for r in reqs)
    assert n["decode_slot_steps"] >= n["decode_tokens"]
    assert backend.tokens_served == n["first_tokens"] + n["decode_tokens"] \
        == sum(len(t) for t in backend.generated.values())


def test_timed_executor_keeps_only_the_last_latency():
    from repro.core.vertical import TimedExecutor

    def slow(x):
        time.sleep(0.002)
        return x + 1
    table = TimedExecutor({(1, 2): slow}, name="model.decode")
    assert table(1, 2, np.int32(3)) == 4
    assert table.last_s >= 0.002
    assert not hasattr(table, "calls")


def test_step_programs_carry_named_scopes():
    """The prefill and decode programs name their parts, so a device
    trace can group operation time by them."""
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.token_backend import build_token_step_fns
    cfg = dataclasses.replace(get_config("smollm-135m-reduced"),
                              use_pallas_prefill=True,
                              use_pallas_decode=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    pre, dec = build_token_step_fns(model, params, (1,), (2,), 8, 3)
    pf, df = pre[(1, 2)], dec[(1, 2)]
    tokens = np.ones((2, 8), np.int32)
    first, cache = jax.eval_shape(pf, tokens)
    for text in (pf.func.lower(*pf.args, tokens).as_text(debug_info=True),
                 df.func.lower(*df.args, cache, first).as_text(
                     debug_info=True)):
        for scope in ("embed", "attention", "mlp", "lm_head", "sample"):
            assert re.search(rf'[/"]{scope}[/"]', text), scope
