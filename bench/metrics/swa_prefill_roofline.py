"""Roofline share of the Pallas ``swa_prefill`` kernel in the traced
slice: the least time its calls need (FLOPs over the causal pairs inside
the window over peak, or the q/k/v/out bytes over HBM bandwidth, whichever
is larger) over the kernel's device time.  The kernel is found as the
Pallas operation inside the ``prefill`` program."""


def read(ctx):
    secs = ctx.kernel_seconds("prefill")
    if secs <= 0:
        return None
    f, b = ctx.swa_prefill_work(ctx.slice_span())
    share, _ = ctx.roofline(f, b, secs)
    return share
