"""Open-loop traffic from a mix's data file and a seed.

One general generator reads every mix under ``bench/traffic/``.  The
arithmetic is copied from the program's scenario code, so that a change to
the program cannot move the yardstick: Poisson send times at the mix's rate
(``serving/scenarios.poisson_times``), bounded log-normal decode lengths
(``serving/workload.lognormal_lengths``), the synthetic 4G bandwidth trace
(``network/traces.synth_4g_trace``) and the uplink latency of each payload
over it (``network/latency.comm_latency_many``), with the SLOs and the
payload size per prompt token of ``llm-chat`` / ``llm-mixed-len``.

The amount of work is the same for every seed.  Each phase (lead-in,
window, tail) holds ``round(rate * seconds)`` requests, sent at times
drawn uniformly over the phase and sorted: a Poisson process at the mix's
rate, conditioned on its count, so bursts and lulls come at every time
scale as the seed draws them.  Decode lengths are the quantiles of the
bounded log-normal, in an order drawn from the seed.  The seed also draws
the prompt tokens and the bandwidth trace.  So two seeds differ in when
the requests come and which is long, not in how many come or how many
tokens they ask for.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
LEAD_IN, WINDOW, TAIL = 0, 1, 2


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    """A mix's parameters, from ``<directory>/<name>.json``."""
    return json.loads((directory / f"{name}.json").read_text())


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 64, stream])


# ---------------------------------------------------------------------------
# copied from network/traces.py and network/latency.py
# ---------------------------------------------------------------------------
def synth_4g_trace(duration_s: int, seed, lo: float = 0.5, hi: float = 7.0,
                   fade_depth: tuple = (0.15, 0.3)) -> np.ndarray:
    """1 Hz bandwidth (MB/s): log-space Ornstein-Uhlenbeck with regime
    shifts and deep fades, clipped to [lo, hi]."""
    rng = np.random.default_rng(seed)
    n = int(duration_s)
    x = np.zeros(n)
    mu = np.log(2.5)
    x[0] = mu
    theta, sigma = 0.05, 0.25
    n_regimes = max(20, n // 90 + 1)
    shift_times = np.cumsum(rng.integers(45, 150, size=n_regimes))
    shifts = {int(t): rng.uniform(np.log(lo * 1.6), np.log(hi * 0.8))
              for t in shift_times if t < n}
    for i in range(1, n):
        if i in shifts:
            mu = shifts[i]
        x[i] = x[i - 1] + theta * (mu - x[i - 1]) + sigma * rng.normal()
    bw = np.exp(x)
    if n > 20:
        n_fades = int(rng.integers(2, 5)) if n <= 1200 else n // 250
        for _ in range(n_fades):
            s = rng.integers(0, n - 15)
            bw[s:s + rng.integers(4, 12)] *= rng.uniform(*fade_depth)
    return np.clip(bw, lo, hi)


def comm_latency(size_kb: np.ndarray, mbps: np.ndarray, times: np.ndarray,
                 rtt_s: float) -> np.ndarray:
    idx = np.clip(np.asarray(times).astype(np.int64), 0, len(mbps) - 1)
    bw = np.maximum(mbps[idx], 1e-6)
    return rtt_s + (np.asarray(size_kb, np.float64) / 1024.0) / bw


# ---------------------------------------------------------------------------
# draws
# ---------------------------------------------------------------------------
def poisson_send(n: int, seconds: float, rng) -> np.ndarray:
    """Send times of a Poisson process over ``[0, seconds)`` that holds
    ``n`` requests: uniform draws, sorted."""
    return np.sort(rng.uniform(0.0, seconds, n))


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """The n-quantiles of the bounded log-normal of ``lognormal_lengths``."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(median) + sigma * z)
    return np.clip(np.round(x), lo, hi).astype(np.int64)


@dataclass
class Traffic:
    send: np.ndarray          # seconds after the lead-in starts
    phase: np.ndarray         # LEAD_IN / WINDOW / TAIL
    prompt_len: np.ndarray
    decode: np.ndarray
    slo: np.ndarray           # TTFT budget from the send time (s)
    tbt: np.ndarray           # limit on each gap between tokens (s)
    size_kb: np.ndarray
    comm: np.ndarray          # uplink latency (s)
    prompts: list             # int32 token ids per request
    window: tuple             # (start, end) of the measured window (s)

    def __len__(self) -> int:
        return self.send.size


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> Traffic:
    """Lead-in, window of ``seconds`` and tail, at the mix's fixed rate."""
    rate = float(mix["rate_rps"])
    spans = [(LEAD_IN, float(mix["lead_in_s"])), (WINDOW, float(seconds)),
             (TAIL, float(mix["tail_s"]))]
    order = rng_for(seed, 1)
    dec = mix["decode"]
    send, phase, decode = [], [], []
    start = 0.0
    for ph, dur in spans:
        n = int(round(rate * dur))
        send.append(start + poisson_send(n, dur, order))
        phase.append(np.full(n, ph))
        decode.append(order.permutation(lognormal_quantiles(
            n, dec["median"], dec["sigma"], dec["lo"], dec["hi"])))
        start += dur
    send = np.concatenate(send)
    phase = np.concatenate(phase)
    decode = np.concatenate(decode)
    n = send.size
    p = int(mix["prompt_tokens"])
    prompt_len = np.full(n, p, np.int64)
    size_kb = np.maximum(prompt_len * float(mix["kb_per_prompt_token"]), 1.0)
    net = mix["network"]
    mbps = synth_4g_trace(int(start) + 5, int(rng_for(seed, 2).integers(
        2 ** 31)), lo=net["lo"], hi=net["hi"])
    comm = comm_latency(size_kb, mbps, send, float(mix["rtt_s"]))
    toks = rng_for(seed, 3).integers(0, vocab, (n, p), dtype=np.int32)
    w0 = float(mix["lead_in_s"])
    return Traffic(send=send, phase=phase, prompt_len=prompt_len,
                   decode=decode,
                   slo=np.full(n, float(mix["ttft_slo_s"])),
                   tbt=np.full(n, float(mix["tbt_limit_s"])),
                   size_kb=size_kb, comm=comm, prompts=list(toks),
                   window=(w0, w0 + float(seconds)))
