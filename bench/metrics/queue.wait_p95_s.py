"""95th percentile over the window's requests of the wall time from the
request's server arrival to the start of the device call that made its
first token (runner and queue, serving/api.py).  Never served: infinitely
late."""
import math


def read(ctx):
    waits = []
    for rid, _, arrive in ctx.window_requests():
        t = ctx.run.dispatched(rid)
        waits.append(math.inf if t is None else t - arrive)
    return ctx.pct(waits, 95) if waits else None
