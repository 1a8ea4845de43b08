"""Model FLOPs of the tokens served inside the window (the configuration's
adapter counts them) over the window's seconds times the chip's peak bf16
FLOP/s."""


def read(ctx):
    a, b = ctx.window
    f = ctx.served_model_flops()
    return 100.0 * f / ((b - a) * ctx.peaks["bf16_flops_per_s"]) if f \
        else None
