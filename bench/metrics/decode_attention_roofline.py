"""Roofline share of the Pallas ``decode_attention`` kernel in the traced
slice: the least time its calls need (FLOPs over peak, or K/V bytes over
the valid lengths over HBM bandwidth, whichever is larger) over the
kernel's device time, in the layers that the configuration's adapter says
run it.  The kernel is found by its name inside the ``decode`` program."""


def read(ctx):
    secs = ctx.kernel_seconds("decode", "decode_attention")
    if secs <= 0:
        return None
    f, b = ctx.decode_attention_work(ctx.slice_span())
    share, _ = ctx.roofline(f, b, secs)
    return share
