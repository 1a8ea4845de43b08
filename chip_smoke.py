#!/usr/bin/env python3
"""Smoke test of the serving system on one TPU chip.

Drives the system's two live paths once each, through the entry points a
user calls, at the published width of smollm-135m (30 layers, d_model 576,
9 heads with 3 KV heads, vocab 49152, bf16; random weights from a seed):

* Phase A, the real-kernel token path: ``run_token_jax_scenario`` as
  ``python -m repro.launch.serve --scenario llm-chat --engine jax`` calls
  it (128-token prompts, decode clipped to 32 tokens, the arrivals the
  scenario draws for 16 expected requests).
  Checks: every request got a first token and a finish; the tokens served
  equal each request's first token plus its clipped decode length; every
  generated id is inside the vocabulary; the compiled prefill and decode
  entries of the serving tables contain the Pallas kernels
  (``tpu_custom_call``); and the kernel build's prefill logits, and one
  decode step's, agree with the plain-attention build of the same params
  within ``LOGIT_ATOL``.
* Phase B, the fixed-work live path: ``python -m repro.launch.serve
  --mode live`` at smollm-135m.  Checks that every request sent was served.

Compile and wall seconds are printed per phase as information.  Any failed
check exits non-zero.  Without a TPU the script exits non-zero before any
phase runs; on the CPU, rehearse through ``repro.launch.serve`` instead.
The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

    python chip_smoke.py
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-135m"
SEED = 0
PROMPT_LEN, MAX_DECODE, REQUESTS = 128, 32, 16
# Largest |kernel - plain| logit gap allowed: about six bf16 ulps at this
# random-weight model's largest logit (~2.1).  A wrong kernel head mapping
# or length mask moves the logits by more than 3.
LOGIT_ATOL = 0.1


class CompileMeter:
    """Sums JAX's backend-compile durations and persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.cache_hits


def check(cond: bool, what: str) -> None:
    if not cond:
        sys.exit(f"FAILED: {what}")
    print(f"  ok: {what}")


def report_phase(name, meter, start, t0):
    secs, hits = meter.snapshot()
    print(f"{name}: wall {time.perf_counter() - t0:.2f} s, backend compile "
          f"{secs - start[0]:.2f} s, persistent-cache hits {hits - start[1]}")


def phase_token_path(meter):
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.serving.token_backend import run_token_jax_scenario
    start, t0 = meter.snapshot(), time.perf_counter()
    report, stats = run_token_jax_scenario(
        "llm-chat", requests=REQUESTS, seed=SEED, arch=ARCH,
        prompt_len=PROMPT_LEN, max_decode=MAX_DECODE)
    backend, reqs = stats["backend"], stats["requests"]
    vocab = get_config(ARCH).vocab_size
    print(f"phase A: {len(reqs)} requests, {backend.tokens_served} tokens "
          f"served, serve wall {stats['run_wall_s']:.2f} s, "
          f"ttft p50 {report.ttft_p50:.4f} s")
    check(len(reqs) > 0 and report.n_requests == len(reqs),
          f"all {len(reqs)} requests drawn for {REQUESTS} expected "
          "arrivals reached the report")
    check(all(r.first_token is not None and r.finish is not None
              for r in reqs), "every request got a first token and a finish")
    expected = sum(1 + r.decode_tokens for r in reqs)
    check(all(r.decode_tokens <= MAX_DECODE for r in reqs)
          and all(len(backend.generated[r.id]) == 1 + r.decode_tokens
                  for r in reqs),
          "each request generated 1 + its clipped decode length tokens")
    check(backend.tokens_served == report.tokens_served == expected,
          f"tokens served {backend.tokens_served} == first tokens + clipped "
          f"decode lengths {expected}")
    ids = np.concatenate([np.asarray(backend.generated[r.id]) for r in reqs])
    check(bool(((ids >= 0) & (ids < vocab)).all()),
          f"all {ids.size} generated ids in [0, {vocab})")

    # the serving tables' own entries reach the Pallas kernels
    b = max(bb for _, bb in backend.pre_table.fns)
    pf = backend.pre_table.fns[(1, b)]
    df = backend.dec_table.fns[(1, b)]
    tokens = np.ones((b, PROMPT_LEN), np.int32)
    first, cache = pf(tokens)
    pre_text = pf.func.lower(*pf.args, tokens).compile().as_text()
    dec_text = df.func.lower(*df.args, cache, first).compile().as_text()
    check("tpu_custom_call" in pre_text,
          f"compiled prefill entry (b={b}) contains tpu_custom_call")
    check("tpu_custom_call" in dec_text,
          f"compiled decode entry (b={b}) contains tpu_custom_call")
    jax.block_until_ready((first, cache))
    report_phase("phase A (token path, incl. set-up)", meter, start, t0)


def phase_logits(meter):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import build_model
    start, t0 = meter.snapshot(), time.perf_counter()
    cfg = get_config(ARCH)
    kern = build_model(dataclasses.replace(cfg, use_pallas_prefill=True,
                                           use_pallas_decode=True))
    plain = build_model(cfg)
    params = kern.init(jax.random.key(SEED))
    tokens = jax.random.randint(jax.random.key(SEED + 1), (4, PROMPT_LEN),
                                0, cfg.vocab_size, jnp.int32)
    cache_len = PROMPT_LEN + MAX_DECODE + 1

    def run(model):
        logits, cache = jax.jit(lambda p, t: model.prefill(
            p, {"tokens": t}, cache_len=cache_len))(params, tokens)
        nxt = jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)
        step, _ = jax.jit(model.decode_step)(params, cache, nxt[:, None])
        return np.asarray(logits, np.float32), np.asarray(step, np.float32)

    (pk, dk), (pp, dp) = run(kern), run(plain)
    for name, a, ref in (("prefill", pk, pp), ("decode", dk, dp)):
        gap = float(np.abs(a - ref).max())
        agree = float((a.argmax(-1) == ref.argmax(-1)).mean())
        print(f"phase A logits {name}: max|kernel - plain| {gap:.6f}, "
              f"max|plain| {float(np.abs(ref).max()):.4f}, "
              f"greedy-token agreement {agree:.2f}")
        check(bool(np.isfinite(a).all()) and a.shape == ref.shape,
              f"{name} logits finite, shape {a.shape}")
        check(gap <= LOGIT_ATOL,
              f"{name} logits within {LOGIT_ATOL} of the plain build")
    report_phase("phase A (logit agreement)", meter, start, t0)


def phase_live_path(meter):
    from repro.launch import serve
    start, t0 = meter.snapshot(), time.perf_counter()
    res = serve.main(["--mode", "live", "--arch", ARCH, "--rps", "4",
                      "--duration", "2", "--seed", str(SEED)])
    check(res["sent"] > 0 and res["served"] == res["sent"],
          f"live path served {res['served']} of {res['sent']} requests")
    report_phase("phase B (live path, incl. set-up)", meter, start, t0)


def main() -> int:
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}")
    print(f"platform {dev.platform}, device_kind {dev.device_kind}, "
          f"count {len(devices)}")
    if dev.platform != "tpu":
        print(f"no TPU found (platform {dev.platform!r}); rehearse on the "
              "CPU through repro.launch.serve instead", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compilation cache: {enable_compile_cache()}")
    meter = CompileMeter()
    phase_token_path(meter)
    phase_logits(meter)
    phase_live_path(meter)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
