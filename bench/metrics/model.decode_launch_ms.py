"""Median host launch of a decode call in the traced slice: from the start
of the program's ``model.decode`` span (core/vertical.py, around the call
and its ``block_until_ready``) to the device start of the ``jit_decode``
module execution inside it (bench/progtrace.py).  Nothing where fewer than
90% of the slice's decode calls hold their execution whole: the device's
clock is then out of step with the host's, and a launch cannot be read."""
from bench import progtrace


def read(ctx):
    program = ctx.trace.get("program")
    if not progtrace.clock_in_step(program):
        return None
    return 1e3 * progtrace.median(program["decode"]["launch_s"])
