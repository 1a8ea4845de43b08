"""Median device time of the ``jit_decode`` module executions that start
in the traced slice (bench/progtrace.py): the decode call's time on the
device."""
from bench import progtrace


def read(ctx):
    d = (ctx.trace.get("program") or {}).get("decode") or {}
    m = progtrace.median(d.get("device_s"))
    return None if m is None else 1e3 * m
