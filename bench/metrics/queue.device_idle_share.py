"""Share of the traced slice in which the device was idle while the
innermost program span was the runner's (``runner.step``,
``runner.tick``, ``runner.dispatch``; serving/session.py): the event
loop and EDF dispatch outside any gang or decision (bench/progtrace.py)."""
from bench import progtrace


def read(ctx):
    return progtrace.idle_share(ctx.trace.get("program"), "runner.")
