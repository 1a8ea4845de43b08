"""The reduction of the program's own spans (``bench/progtrace.py``):
idle attribution and call matching on synthetic intervals, the trace
reader on a recorded chip slice, and the new readers' view of a CPU run."""
import math
import random

import pytest

from bench import harness, progtrace
from bench.metrics.context import Context
from bench.tests.conftest import tiny_cell, tiny_run

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("model.decode_launch_ms", "model.decode_device_ms",
           "backend.device_idle_share", "queue.device_idle_share")


# -- idle attribution -------------------------------------------------------
def test_innermost_span_takes_the_idle_time():
    spans = [(0, 10, "runner.step"), (1, 9, "backend.gang"),
             (2, 4, "model.decode"), (4, 5, "backend.deliver"),
             (0, 10, "bench.step"), (2.2, 3.8, "bench.decode")]
    idle, busy = progtrace.attribute_idle(spans, [(2.5, 3.5)], (0, 10))
    assert busy == pytest.approx(1.0)
    assert idle == pytest.approx({"runner.step": 2.0, "backend.gang": 5.0,
                                  "model.decode": 1.0,
                                  "backend.deliver": 1.0})


def test_wait_counts_only_where_no_program_span_is_open():
    spans = [(0, 3, "bench.wait"), (2, 4, "runner.step"),
             (6, 8, "bench.step"), (7, 9, "bench.wait")]
    idle, busy = progtrace.attribute_idle(spans, [], (0, 10))
    assert busy == 0
    assert idle == pytest.approx({"bench.wait": 4.0, "runner.step": 2.0,
                                  "none": 4.0})


def test_busy_is_merged_and_clipped_to_the_window():
    spans = [(-5, 20, "runner.step")]
    busy = [(-2, 1), (0.5, 2), (4, 6), (5, 7), (9, 12)]
    idle, b = progtrace.attribute_idle(spans, busy, (0, 10))
    assert b == pytest.approx(2 + 3 + 1)
    assert idle == pytest.approx({"runner.step": 4.0})


def test_same_start_the_shorter_span_is_innermost():
    spans = [(0, 10, "runner.dispatch"), (0, 4, "backend.gang")]
    idle, _ = progtrace.attribute_idle(spans, [], (0, 10))
    assert idle == pytest.approx({"backend.gang": 4.0,
                                  "runner.dispatch": 6.0})


def _nested(rng, a, b, depth, out):
    """Random properly nested spans inside [a, b)."""
    if depth == 0 or b - a < 1e-3:
        return
    t = a
    while t < b:
        s = t + rng.uniform(0, (b - t) / 2)
        e = min(b, s + rng.uniform(0, (b - a) / 2))
        if e <= s:
            break
        out.append((s, e, rng.choice(["runner.dispatch", "backend.gang",
                                      "model.decode", "backend.deliver",
                                      "bench.wait", "bench.decode"])))
        _nested(rng, s, e, depth - 1, out)
        t = e


@pytest.mark.parametrize("seed", range(6))
def test_idle_and_busy_add_up_to_the_slice(seed):
    rng = random.Random(seed)
    spans = []
    _nested(rng, -0.1, 2.1, 4, spans)
    busy = [(s, s + rng.uniform(0, 0.05))
            for s in (rng.uniform(-0.1, 2.1) for _ in range(300))]
    window = (0.0, 2.0)
    idle, b = progtrace.attribute_idle(spans, busy, window)
    assert set(idle) <= {"runner.dispatch", "backend.gang", "model.decode",
                         "backend.deliver", "bench.wait", "none"}
    assert sum(idle.values()) + b == pytest.approx(2.0, abs=1e-9)
    assert 0 < b < 2.0


# -- decode calls against the device's module executions --------------------
def test_decode_spans_matched_to_their_module():
    spans = [(0, 10, "model.decode"), (20, 30, "model.decode"),
             (40, 50, "model.decode"), (60, 70, "model.prefill"),
             (80, 90, "model.decode"), (-10, -5, "model.decode")]
    modules = [(3, 8, "jit_decode(123)"), (22, 24, "jit_decode(123)"),
               (26, 29, "jit_decode(123)"), (61, 65, "jit_prefill(9)"),
               (87, 93, "jit_decode(123)"), (-8, -6, "jit_decode(123)")]
    got = progtrace.match_decode(spans, modules, (0, 100))
    # the second span starts two modules, the third none, and the fourth's
    # module ends after the call returned: a clock out of step
    assert got == {"calls": 4, "one_start": 2, "one_inside": 1,
                   "launch": [3], "device": [5, 2, 3, 6]}
    assert not progtrace.clock_in_step({"decode": got})
    assert progtrace.clock_in_step({"decode": got}, share=0.25)


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(decode)/while/body/closed_call/attention/dot_general:",
     "attention"),
    ("jit(prefill)/while/body/closed_call/checkpoint/mlp/mul:", "mlp"),
    ("jit(decode)/embed/gather:", "embed"),
    ("jit(decode)/lm_head/transpose:", "lm_head"),
    ("jit(prefill)/sample", "sample"),
    ("jit(decode)/while/body/dynamic_slice:", "layers"),
    ("params['embed']:", "params"),
    ("jit(prefill)/add:", "other"),
    ("", "other"),
])
def test_scope_of_an_operation(tf_op, scope):
    assert progtrace.scope_of(tf_op) == scope


# -- readers ----------------------------------------------------------------
def test_new_readers_have_nothing_to_read_on_a_cpu_run():
    run, _ = tiny_run(21)
    ctx = Context(run, PEAKS)
    for name in READERS:
        assert harness.load_reader(name)(ctx) is None, name


def test_counters_reproduce_the_outside_readers(monkeypatch):
    """The token backend's counters give what ``backend.slot_fill`` and
    ``queue.batch_fill`` compute from the harness's stamps, over the same
    calls (all of the run's)."""
    import time

    from repro.serving import token_backend
    kept = []

    class Kept(token_backend.TokenJaxBackend):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            kept.append(self)

    monkeypatch.setattr(token_backend, "TokenJaxBackend", Kept)
    run, _ = harness.drive(tiny_cell(), 8, 1.5, t_proc=time.perf_counter(),
                           require_tpu=False, log=lambda *_: None)
    n = kept[0].counters()
    ctx = Context(run, PEAKS)
    every = (-math.inf, math.inf)
    dec, pre = ctx.calls("decode", every), ctx.calls("prefill", every)
    assert n["decode_calls"] == len(dec) > 0
    assert n["prefill_calls"] == len(pre) > 0
    assert n["decode_slot_steps"] == sum(c[0] for c in dec)
    assert n["decode_tokens"] == sum(c[4] for c in dec)
    assert n["prefill_rows"] == sum(c[0] for c in pre)
    assert n["first_tokens"] == sum(c[4] for c in pre)
    # the readers' own formulas, over those calls
    slot_fill = 100.0 * sum(c[4] for c in dec) / sum(c[0] for c in dec)
    batch_fill = 100.0 * sum(c[4] for c in pre) / sum(c[0] for c in pre)
    assert 100.0 * n["decode_tokens"] / n["decode_slot_steps"] == \
        pytest.approx(slot_fill)
    assert 100.0 * n["first_tokens"] / n["prefill_rows"] == \
        pytest.approx(batch_fill)


# -- a recorded chip slice --------------------------------------------------
# 40 ms of a smollm135m-chat-over run on a TPU v5e with the program's spans
# (one decision, one prefill of b=32, thirteen decode calls), trimmed to the
# device's XLA modules and operations and the host's program and bench spans.
TRACE = harness.BENCH / "tests" / "data" / \
    "smollm135m-chat-over.program.xplane.pb"


@pytest.fixture(scope="module")
def program():
    return progtrace.reduce_program(str(TRACE))


def test_recorded_slice_adds_up(program):
    assert program["devices"] == 1
    assert program["window_s"] == pytest.approx(0.040, abs=1e-9)
    assert program["busy_s"] == pytest.approx(0.017449826, rel=1e-6)
    idle = program["idle_by_span"]
    assert program["busy_s"] + sum(idle.values()) == \
        pytest.approx(program["window_s"], abs=1e-9)
    assert sum(program["host_by_span"].values()) == \
        pytest.approx(program["window_s"], abs=1e-9)
    # the runner's step began before the profiler, so its own idle time
    # has no span; everything else inside it has one
    assert idle["none"] < 0.001 * program["window_s"]
    assert max(idle, key=idle.get) == "model.decode"
    assert program["spans"] == {
        "runner.tick": 1, "control.decide": 1, "runner.dispatch": 6,
        "backend.gang": 1, "backend.pad": 1, "model.prefill": 1,
        "model.decode": 13, "backend.deliver": 14}


def test_recorded_decode_calls_hold_their_module(program):
    d = program["decode"]
    assert d["calls"] == d["one_start"] == d["one_inside"] == 13
    assert progtrace.clock_in_step(program)
    assert len(d["device_s"]) == 14
    assert all(0 < x < 2e-3 for x in d["launch_s"])


def test_recorded_device_time_by_scope(program):
    scope = program["device_by_scope"]
    assert {"attention", "mlp", "layers", "params", "lm_head"} <= set(scope)
    assert sum(scope.values()) <= program["busy_s"] + 1e-9
    assert scope["attention"] == pytest.approx(0.005069961, rel=1e-6)


class _Ctx:
    def __init__(self, program):
        self.trace = {"program": program}


@pytest.mark.parametrize("name,value", [
    ("model.decode_launch_ms", 0.643843),
    ("model.decode_device_ms", 1.1467495),
    ("backend.device_idle_share", 15.787320),
    ("queue.device_idle_share", 0.148250),
])
def test_readers_on_the_recorded_slice(program, name, value):
    assert harness.load_reader(name)(_Ctx(program)) == \
        pytest.approx(value, rel=1e-5)


def test_trace_metadata_read_without_protobuf():
    paths = progtrace.op_paths(str(TRACE))
    ops = paths["/device:TPU:0"]
    assert any(p.startswith("jit(decode)/") for p in ops.values())
    for kernel in ("decode_attention", "swa_prefill"):
        path, = [p for k, p in ops.items() if k.startswith(f"%{kernel}.")]
        assert f"/{kernel}/pallas_call" in path
        assert progtrace.scope_of(path) == "attention"
