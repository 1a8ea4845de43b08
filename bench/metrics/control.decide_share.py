"""Share of the window's wall time spent inside the policy's ``decide()``
(core/scaler.py, core/solver.py), timed by the harness around the call."""


def read(ctx):
    a, b = ctx.window
    busy = sum(dt for t, dt in ctx.run.rec.decides if a <= t < b)
    return 100.0 * busy / (b - a)
