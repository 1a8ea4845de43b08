"""Share of the traced slice in which no operation ran on the device:
1 - (union of device-operation intervals) / slice length."""


def read(ctx):
    tr = ctx.trace
    if not tr or tr.get("window_s", 0) <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
