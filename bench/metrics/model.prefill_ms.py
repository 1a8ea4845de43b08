"""Median wall time of a prefill call in the window (model step,
models/api.py prefill), host clock around the call and its
``block_until_ready``."""


def read(ctx):
    d = [c[2] - c[1] for c in ctx.calls("prefill")]
    return 1e3 * ctx.pct(d, 50) if d else None
