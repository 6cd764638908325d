"""tpot_p90_ms (end to end, host clock): 90th percentile, over every
request that streamed at least 2 tokens in the window, of its mean gap
between tokens within the window."""
import e2e


def read(run):
    return e2e.tpot_p90_ms(run.records, run.t0, run.t1)
