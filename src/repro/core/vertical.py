"""In-place vertical scaling — the paper's core mechanism, TPU-adapted.

K8s in-place pod resize changes a container's CPU cores without restart.
The TPU analogue (DESIGN.md §2): a serving instance holds an *executable
table* over (c, b) — c the model-parallel submesh degree, b the batch
bucket — all lowered/compiled at deploy time.  ``resize`` flips the active
entry: no recompilation, no weight reload, no cold start; the one-off cost
is a weight re-gather onto the target submesh, modeled as ``resize_penalty``
seconds (the analogue of the pod-resize syscall, NOT of a cold start).

Two concrete executor substrates:

* ``TimedExecutor`` — wall-clock execution of real jitted JAX functions,
  batch-bucketed (used by the live serving engine / examples).
* simulation — the discrete-event simulator calls ``latency(b)`` from the
  calibrated PerfModel instead of executing (used for the Fig. 4 study).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence

from repro.core.perf_model import PerfModel
from repro.utils.trace import span


@dataclass
class ResizeEvent:
    t: float
    c_from: int
    c_to: int
    penalty: float


class VerticalScaledInstance:
    """A single servable model instance with in-place vertical scaling."""

    def __init__(self, c_set: Sequence[int], b_set: Sequence[int],
                 perf: PerfModel, c0: Optional[int] = None,
                 resize_penalty: float = 0.005,
                 weight_bytes: float = 0.0, ici_bw: float = 50e9):
        self.c_set = tuple(sorted(c_set))
        self.b_set = tuple(sorted(b_set))
        self.perf = perf
        self.c = c0 or self.c_set[0]
        assert self.c in self.c_set
        # resize penalty: explicit, or estimated re-gather time of the
        # weight shard over ICI (beyond-cold-start but not free)
        self.resize_penalty = (weight_bytes / ici_bw
                               if weight_bytes else resize_penalty)
        self.resizes: list[ResizeEvent] = []
        self.core_seconds = 0.0
        self._last_t: Optional[float] = None

    # -- the in-place resize (the paper's mechanism) ----------------------
    def resize(self, c: int, now: float = 0.0) -> float:
        """Returns the penalty (seconds) to charge; 0 if no change."""
        assert c in self.c_set, (c, self.c_set)
        self.account(now)
        if c == self.c:
            return 0.0
        self.resizes.append(ResizeEvent(now, self.c, c, self.resize_penalty))
        self.c = c
        return self.resize_penalty

    def account(self, now: float) -> None:
        """Integrate allocated core-seconds up to ``now``."""
        if self._last_t is None:
            self._last_t = now
            return
        if now > self._last_t:
            self.core_seconds += self.c * (now - self._last_t)
            self._last_t = now
        self._last_t = now

    def bucket_b(self, b: int) -> int:
        for bb in self.b_set:
            if bb >= b:
                return bb
        return self.b_set[-1]

    def latency(self, b: int) -> float:
        """Processing latency of a batch of b at the current allocation."""
        return float(self.perf.latency(self.bucket_b(b), self.c))

    def throughput(self) -> float:
        return max(float(self.perf.throughput(b, self.c))
                   for b in self.b_set)


class TimedExecutor:
    """Executable table of real jitted functions keyed by (c, b) buckets.

    ``fns[(c, b)]`` must be ready-to-call (pre-compiled at deploy — that is
    what makes the resize in-place).  Each call is one ``name`` span
    (``repro.utils.trace``) around the call and its ``block_until_ready``;
    ``last_s`` holds the wall latency of the latest call.
    """

    def __init__(self, fns: Dict[tuple[int, int], Callable],
                 name: str = "model.step"):
        self.fns = dict(fns)
        self.name = name
        self.last_s = 0.0

    def warmup(self, args_for: Callable[[int, int], tuple]) -> None:
        for (c, b), fn in self.fns.items():
            fn(*args_for(c, b))  # compile

    def __call__(self, c: int, b: int, *args) -> Any:
        with span(self.name, c=c, b=b):
            t0 = time.perf_counter()
            out = jax_block(self.fns[(c, b)](*args))
            self.last_s = time.perf_counter() - t0
        return out


def jax_block(x):
    try:
        import jax
        return jax.block_until_ready(x)
    except Exception:
        return x
