"""Real-kernel autoregressive serving: the ``TokenJaxBackend`` (ISSUE 3).

This is the live counterpart of ``repro.serving.api.TokenSimBackend``:
a dispatched gang is executed phase-aware on **real jitted
executables** —

* prefill runs the model's prompt pass with attention routed through
  the Pallas ``swa_prefill`` kernel (``cfg.use_pallas_prefill``; full
  causal attention is the window >= S special case), producing every
  request's first token *and* the gang KV cache;
* each decode step runs the model's single-token pass with attention
  routed through the Pallas ``decode_attention`` flash-decode kernel
  (``cfg.use_pallas_decode``), one token per running slot.

The gang cache's batch axis is the **KV-cache slot pool**: slot i holds
request i's cache lines; requests *leave* the pool between decode steps
by masking (their slots keep stepping as padding — the real cost an
engine pays without cache compaction) and the gang ends when the
longest stream finishes.  Everything is jitted per ``(c, b)`` exactly
like the fixed-work executable table, so applying a Decision stays an
O(1) dictionary flip (the in-place vertical scaling mechanism).  Every c
maps to the same single-device executable for now, so vertical scaling
affects scheduling only; on a TPU the kernels are compiled by Mosaic, on
the CPU backend they run in the Pallas interpreter.

``calibrate_token_fns`` profiles the two tables once and fits a
``TokenCostModel``, which closes the loop: the solver plans token
compositions on the same cost surface the kernels exhibit.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core.cost_model import TokenCostModel
from repro.core.scaler import TokenSpongeScaler
from repro.core.slo import Request
from repro.core.vertical import TimedExecutor
from repro.serving.api import ScenarioRunner, _PooledBackend
from repro.utils.trace import span

# the token backend's counters, in the order ``counters()`` gives them
COUNTERS = ("prefill_calls", "prefill_rows", "first_tokens", "decode_calls",
            "decode_slot_steps", "decode_tokens")
# decode steps timed per (c, b) by ``calibrate_token_fns``, after a warm one
CALIBRATION_STEPS = 8


def build_token_step_fns(model, params, c_set: Sequence[int],
                         b_set: Sequence[int], prompt_len: int,
                         max_decode: int = 8):
    """Two executable tables for phase-aware LLM serving.

    ``prefill_fns[(c, b)](tokens)`` maps (b, prompt_len) int32 prompts to
    ``(first_token (b,), gang_cache)``; ``decode_fns[(c, b)](cache, tok)``
    advances every slot one token.  The cache holds
    ``prompt_len + max_decode + 1`` positions per slot.  Every c maps to
    the same jitted fn per b (see the module docstring).  Each entry is a
    ``functools.partial`` of a jitted function over ``params``: the
    weights are an argument, not a constant copied into every executable.
    """
    import jax
    import jax.numpy as jnp
    cache_len = prompt_len + max_decode + 1
    vocab = model.cfg.vocab_size

    @jax.jit
    def prefill(params, tokens):
        logits, cache = model.prefill(params, {"tokens": tokens},
                                      cache_len=cache_len)
        with jax.named_scope("sample"):
            first = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
        return first, cache

    @jax.jit
    def decode(params, cache, tok):
        lg, cache = model.decode_step(params, cache, tok[:, None])
        with jax.named_scope("sample"):
            nxt = jnp.argmax(lg[:, :vocab], axis=-1).astype(jnp.int32)
        return nxt, cache

    prefill_fns, decode_fns = {}, {}
    for b in b_set:
        pf, df = partial(prefill, params), partial(decode, params)
        for c in c_set:
            prefill_fns[(c, b)] = pf
            decode_fns[(c, b)] = df
    return prefill_fns, decode_fns


def pad_prompts(payloads: List[np.ndarray], b: int,
                prompt_len: int) -> np.ndarray:
    """Stack prompt-token payloads into the (b, prompt_len) bucket:
    each prompt is right-padded (zeros) or truncated to ``prompt_len``,
    the batch axis padded by repeating the last entry."""
    rows = []
    for p in payloads:
        p = np.zeros(prompt_len, np.int32) if p is None \
            else np.asarray(p, np.int32).ravel()[:prompt_len]
        if p.size < prompt_len:
            p = np.pad(p, (0, prompt_len - p.size))
        rows.append(p)
    rows += [rows[-1]] * (b - len(rows))
    return np.stack(rows)


def warmup_token_fns(prefill_fns: Dict, decode_fns: Dict,
                     prompt_len: int) -> None:
    """Compile every (c, b) entry of both tables (deploy-time pass —
    this is what makes the later resize in-place).  Entries sharing one
    function (every c maps to the same fn per b) are compiled once, not
    once per c."""
    seen: set[int] = set()
    for (c, b), pf in prefill_fns.items():
        if id(pf) in seen:
            continue
        seen.add(id(pf))
        tokens = np.ones((b, prompt_len), np.int32)
        first, cache = pf(tokens)
        decode_fns[(c, b)](cache, first)


def calibrate_token_fns(prefill_fns: Dict, decode_fns: Dict,
                        prompt_len: int, mean_prompt: float = 0.0,
                        mean_decode: float = 4.0) -> TokenCostModel:
    """Profile both tables per (c, b) and fit the token cost model.

    Prefill samples are (b·prompt_len tokens, c, wall of one call);
    decode samples are (b slots, c, the median wall of one step over
    ``CALIBRATION_STEPS`` consecutive steps after a warm one).  Each step
    runs as :meth:`TokenJaxBackend.execute` runs it: the call and its
    ``block_until_ready``, then its token fetched to the host and the
    host array fed to the next step, so the step cost the solver plans
    on includes the token's round trip.  Every step reads the prefill's
    cache, so the run never outgrows the table's ``max_decode``.  Run
    :func:`warmup_token_fns` first so compiles are excluded.
    """
    import jax
    pre_samples, dec_samples = [], []
    for (c, b), pf in prefill_fns.items():
        tokens = np.ones((b, prompt_len), np.int32)
        t0 = time.perf_counter()
        first, cache = jax.block_until_ready(pf(tokens))
        pre_samples.append((float(b * prompt_len), float(c),
                            time.perf_counter() - t0))
        df = decode_fns[(c, b)]
        tok = np.asarray(first)
        steps = []
        for _ in range(CALIBRATION_STEPS + 1):
            t0 = time.perf_counter()
            nxt, _ = jax.block_until_ready(df(cache, tok))
            tok = np.asarray(nxt)
            steps.append(time.perf_counter() - t0)
        dec_samples.append((float(b), float(c), float(np.median(steps[1:]))))
    return TokenCostModel.fit(
        pre_samples, dec_samples,
        mean_prompt=mean_prompt or float(prompt_len),
        mean_decode=mean_decode)


class TokenJaxBackend(_PooledBackend):
    """Continuous-batching execution over real Pallas-kernel executables.

    See the module docstring for the execution model (phase-aware gangs
    over a KV-cache slot pool).  ``clock`` follows ``JaxBackend``:
    ``"measured"`` advances virtual time by wall latency per phase,
    ``"modeled"`` by the calibrated :class:`TokenCostModel` (kernels
    still execute and produce real tokens).  Per-request lifecycle
    (``first_token`` / ``finish`` / ``tbt_violations``) is written here;
    generated token ids are collected in ``generated[request.id]``.

    A gang keeps all b slots stepping until its longest stream ends
    (``holds_gang_slots``), so the runner plans each b by that length.
    """

    name = "token-jax"
    holds_gang_slots = True

    def __init__(self, prefill_fns: Dict[tuple[int, int], Callable],
                 decode_fns: Dict[tuple[int, int], Callable],
                 cost: TokenCostModel, prompt_len: int,
                 max_decode: int = 8, clock: str = "measured",
                 c0: Optional[int] = None, resize_penalty: float = 0.0):
        assert clock in ("measured", "modeled"), clock
        self.pre_table = TimedExecutor(prefill_fns, name="model.prefill")
        self.dec_table = TimedExecutor(decode_fns, name="model.decode")
        self.cost = cost
        self.prompt_len = prompt_len
        self.max_decode = max_decode
        self.clock = clock
        self.generated: Dict[int, List[int]] = {}
        self._counts = dict.fromkeys(COUNTERS, 0)
        self._payloads: Dict[int, Any] = {}
        c_set = sorted({c for c, _ in prefill_fns})
        b_set = sorted({b for _, b in prefill_fns})
        super().__init__(cost, c_set, b_set, c0=c0 or max(c_set),
                         resize_penalty=resize_penalty)

    def counters(self) -> Dict[str, int]:
        """Cumulative counts of the backend's device calls (``COUNTERS``):
        prefill calls and their rows (Σ b) and first tokens (Σ real
        requests); decode calls, their slot-steps (Σ b) and tokens (Σ
        live slots)."""
        return dict(self._counts)

    @property
    def tokens_served(self) -> int:
        """Tokens handed out: first tokens plus decode tokens."""
        return self._counts["first_tokens"] + self._counts["decode_tokens"]

    def warmup(self) -> None:
        """Compile every (c, b) prefill + decode entry."""
        warmup_token_fns(self.pre_table.fns, self.dec_table.fns,
                         self.prompt_len)

    def on_submit(self, req: Request, payload: Any) -> None:
        self._payloads[req.id] = payload

    def execute(self, batch: List[Request], c: int, b: int,
                now: float) -> float:
        n = self._counts
        gang = n["prefill_calls"]
        with span("backend.gang", gang=gang, b=b, n=len(batch),
                  ids=" ".join(str(r.id) for r in batch)):
            with span("backend.pad", gang=gang):
                tokens = pad_prompts([self._payloads.pop(r.id, None)
                                      for r in batch], b, self.prompt_len)
            first, cache = self.pre_table(c, b, tokens)
            with span("backend.deliver", gang=gang, step=0):
                first = np.asarray(first)
                dt = self.pre_table.last_s
                if self.clock == "modeled":
                    total_prompt = sum(r.prompt_tokens for r in batch)
                    dt = float(self.cost.prefill_latency(c, total_prompt))
                t = now + dt
                remaining = np.zeros(b, np.int64)
                for i, r in enumerate(batch):
                    r.first_token = t
                    self.generated[r.id] = [int(first[i])]
                    remaining[i] = min(r.decode_tokens, self.max_decode)
                    if remaining[i] == 0:
                        r.finish = t
                n["prefill_calls"] += 1
                n["prefill_rows"] += b
                n["first_tokens"] += len(batch)
            tok = first
            step = 0
            while (remaining > 0).any():
                nxt, cache = self.dec_table(c, b, cache, tok)
                step += 1
                with span("backend.deliver", gang=gang, step=step):
                    nxt = np.asarray(nxt)
                    live = int((remaining > 0).sum())
                    dt = self.dec_table.last_s
                    if self.clock == "modeled":
                        dt = float(self.cost.decode_latency(c, live))
                    t += dt
                    for i, r in enumerate(batch):
                        if remaining[i] <= 0:
                            continue            # slot already left the pool
                        if dt > r.tbt_slo + 1e-12:
                            r.tbt_violations += 1
                        self.generated[r.id].append(int(nxt[i]))
                        remaining[i] -= 1
                        if remaining[i] == 0:
                            r.finish = t
                    n["decode_calls"] += 1
                    n["decode_slot_steps"] += b
                    n["decode_tokens"] += live
                tok = nxt
        return t


def make_token_live_server(arch: str = "smollm-135m-reduced", *,
                           c_set: Sequence[int] = (1, 2, 4),
                           b_set: Sequence[int] = (1, 2, 4),
                           prompt_len: int = 16, max_decode: int = 8,
                           clock: str = "measured", tick: float = 0.5,
                           prior_rps: float = 0.0,
                           cost: Optional[TokenCostModel] = None):
    """Build the full real-kernel token serving stack.

    Resolves ``arch`` through ``configs.registry`` with the Pallas
    prefill/decode kernel routes enabled, builds + compiles the two
    (c, b) executable tables, calibrates a :class:`TokenCostModel` from
    them, and wires a :class:`repro.core.scaler.TokenSpongeScaler` +
    :class:`TokenJaxBackend` behind the standard ``ScenarioRunner``.
    Returns ``(runner, backend, cfg, cost)``.
    """
    import dataclasses
    import jax
    from repro.configs import get_config
    from repro.models import build_model
    cfg = dataclasses.replace(get_config(arch), use_pallas_prefill=True,
                              use_pallas_decode=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prefill_fns, decode_fns = build_token_step_fns(
        model, params, c_set, b_set, prompt_len, max_decode=max_decode)
    warmup_token_fns(prefill_fns, decode_fns, prompt_len)
    if cost is None:
        cost = calibrate_token_fns(prefill_fns, decode_fns, prompt_len,
                                   mean_decode=max_decode / 2.0)
    scaler = TokenSpongeScaler(cost, c_set=tuple(c_set),
                               b_set=tuple(b_set),
                               adaptation_interval=tick)
    backend = TokenJaxBackend(prefill_fns, decode_fns, cost, prompt_len,
                              max_decode=max_decode, clock=clock)
    runner = ScenarioRunner(scaler, backend, tick=tick)
    runner.monitor.rate.prior_rps = prior_rps
    return runner, backend, cfg, cost


def run_token_jax_scenario(name: str, *, requests: int = 24, seed: int = 0,
                           arch: str = "smollm-135m-reduced",
                           prompt_len: int = 16, max_decode: int = 8,
                           clock: str = "measured", rps: Optional[float] =
                           None):
    """Run a slice of a registered token scenario on the real kernels.

    Materializes ``requests`` arrivals from the scenario's workload
    (prompts truncated to the table's ``prompt_len`` bucket, decode
    streams clipped to ``max_decode`` — the executable-table budget),
    serves them through :func:`make_token_live_server`, and returns
    ``(RunReport, stats)``; ``stats["backend"]`` and ``stats["requests"]``
    hold the backend (its tables and generated ids) and the served
    requests.
    """
    from repro.serving.scenarios import build_scenario
    batch, meta = build_scenario(name, requests=requests, seed=seed,
                                 rps=rps)
    if not meta.get("token"):
        raise ValueError(f"{name!r} is not a token scenario")
    runner, backend, cfg, cost = make_token_live_server(
        arch, prompt_len=prompt_len, max_decode=max_decode, clock=clock,
        prior_rps=meta["expected_rps"], tick=meta.get("tick", 0.5))
    rng = np.random.default_rng(seed)
    arrivals = []
    for r in batch.head(requests).to_requests():
        r = Request.make(arrival=r.arrival, comm_latency=r.comm_latency,
                         slo=r.slo, size_kb=r.size_kb,
                         prompt_tokens=min(r.prompt_tokens, prompt_len),
                         decode_tokens=min(r.decode_tokens, max_decode),
                         tbt_slo=r.tbt_slo)
        prompt = rng.integers(0, cfg.vocab_size,
                              r.prompt_tokens).astype(np.int32)
        arrivals.append((r, prompt))
    t0 = time.perf_counter()
    report = runner.run(arrivals)
    stats = {"engine": "token-jax", "arch": cfg.name,
             "events": runner.events_processed,
             "run_wall_s": time.perf_counter() - t0,
             "tokens_executed": backend.tokens_served,
             "cost_r2": (cost.r2_prefill, cost.r2_decode), "meta": meta,
             "backend": backend, "requests": [r for r, _ in arrivals]}
    return report, stats
