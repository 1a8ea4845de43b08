"""Share of the traced slice in which the device was idle while the
innermost program span was the token backend's (``backend.gang``,
``backend.pad``, ``backend.deliver``; serving/token_backend.py):
padding, the per-slot token loop, and the gang's own gaps
(bench/progtrace.py)."""
from bench import progtrace


def read(ctx):
    return progtrace.idle_share(ctx.trace.get("program"), "backend.")
