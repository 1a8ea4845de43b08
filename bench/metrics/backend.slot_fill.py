"""Tokens that the window's decode calls handed out, over b times those
calls: the slot-steps that held a live request (token backend,
serving/token_backend.py).  A slot whose stream ended keeps stepping as
padding until the gang's longest stream ends."""


def read(ctx):
    calls = ctx.calls("decode")
    cap = sum(c[0] for c in calls)
    return 100.0 * sum(c[4] for c in calls) / cap if cap else None
