"""ttft_p95_s as the end-to-end metric measures it, in a cell where it is
recorded and not bounded: there the runner's stalls (a step that runs gang
after gang for seconds) come in some runs and not in others, so the tail
swings between runs of one seed by more than any bound may allow."""


def read(ctx):
    from bench import harness
    return harness.end_to_end(ctx.run)["ttft_p95_s"]
