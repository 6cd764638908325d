"""out_tok_s (end to end, host clock): every output token streamed to the
clients in the window, over the window's seconds."""
import e2e


def read(run):
    return e2e.out_tok_s(run.records, run.t0, run.t1)
