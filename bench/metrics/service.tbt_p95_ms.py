"""p95 of every gap between consecutive tokens of the window's requests,
from the wall stamps, in the cells where it is recorded and not bounded:
the p95 falls where the share of gaps made by the largest bucket's decode
steps crosses 5%, so it jumps between the decode times of two buckets from
run to run (see service.ttft_p95_s)."""


def read(ctx):
    from bench import harness
    return harness.end_to_end(ctx.run)["tbt_p95_ms"]
