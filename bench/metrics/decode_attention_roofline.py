"""Roofline share of the Pallas ``decode_attention`` kernel in the traced
slice: the least time its calls need (FLOPs over peak, or K/V bytes over
the valid lengths over HBM bandwidth, whichever is larger) over the
kernel's device time.  The kernel is found as the Pallas operation inside
the ``decode`` program, since both kernels share one function name."""


def read(ctx):
    secs = ctx.kernel_seconds("decode")
    if secs <= 0:
        return None
    f, b = ctx.decode_attention_work(ctx.slice_span())
    share, _ = ctx.roofline(f, b, secs)
    return share
