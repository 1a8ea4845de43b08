"""Every metric named in BENCHMARK.json has a reader, and a run on the CPU
gives each host-clock reader a number; the device readers, which need a
profiler trace of a chip, return nothing rather than 0."""
import math

from bench import harness
from bench.metrics.context import Context
from bench.tests.conftest import tiny_run

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_every_metric_has_a_reader():
    bench = harness.load_benchmark()
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"])), m["name"]


def test_readers_on_a_cpu_run():
    run, _ = tiny_run(21)
    ctx = Context(run, PEAKS)
    bench = harness.load_benchmark()
    for m in bench["per_layer"]:
        v = harness.load_reader(m["name"])(ctx)
        if m["source"] == "device_trace":
            assert v is None, m["name"]
        else:
            assert v is not None and math.isfinite(v) and v >= 0, m["name"]
    e2e = harness.end_to_end(run)
    assert e2e["slo_attainment"] > 0 and e2e["tokens_per_s"] > 0
    assert 0 < e2e["tbt_p95_ms"] < 1e4 and 0 < e2e["ttft_p95_s"] < 10
