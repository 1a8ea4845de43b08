"""First tokens that the window's prefill calls handed out, over their
batch buckets b: real requests per padded gang (runner dispatch,
serving/api.py)."""


def read(ctx):
    calls = ctx.calls("prefill")
    cap = sum(c[0] for c in calls)
    return 100.0 * sum(c[4] for c in calls) / cap if cap else None
