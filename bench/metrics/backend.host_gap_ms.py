"""Mean wall gap between a decode call's return and the next call, where
that is a decode call too: the host's work per token in the backend's loop
(serving/token_backend.py), in the window."""


def read(ctx):
    a, b = ctx.window
    calls = ctx.run.rec.calls
    gaps = [calls[j + 1][2] - calls[j][3] for j in range(len(calls) - 1)
            if calls[j][0] == calls[j + 1][0] == "decode"
            and calls[j][2] >= a and calls[j + 1][3] <= b]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
