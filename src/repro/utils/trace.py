"""Host spans of the serving path, on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``.  While a
profiler runs (``jax.profiler.start_trace``) the span lands in the trace's
host plane, on the same clock as the device's XLA modules and operations,
with ``args`` (ints or strings) attached; while none runs it records
nothing and costs about a microsecond.  A span opened before the profiler
starts is not recorded.

Names follow the serving path's layers, outermost first:

* ``runner.step`` / ``runner.tick`` / ``runner.dispatch`` — the online
  session's event loop (``serving/session.py``);
* ``control.decide`` — one adaptation step of the policy
  (``serving/api.py``);
* ``backend.gang`` / ``backend.pad`` / ``backend.deliver`` — one dispatched
  gang of the token backend, its prompt padding, and the host work after
  each device call (``serving/token_backend.py``);
* ``model.prefill`` / ``model.decode`` / ``model.step`` — one device call,
  dispatch and ``block_until_ready`` (``core/vertical.py``).
"""
from __future__ import annotations

import functools


def span(name: str, **args):
    """A context manager that records ``name`` with ``args`` while a
    profiler runs."""
    return _annotation()(name, **args)


@functools.cache
def _annotation():
    # JAX is imported on first use, so simulation-only paths never load it
    from jax.profiler import TraceAnnotation
    return TraceAnnotation
