"""End-to-end metrics from the load generator's records (host clock).

Every time is ``time.monotonic()`` seconds of the client's side.  The
window is [t0, t1).  Definitions, as ``PERF.md`` states them:

  out_tok_s        every output token streamed to clients in the window,
                   over the window's seconds
  tpot_p90_ms      90th percentile, over every request that streamed at
                   least 2 tokens in the window, of its mean gap between
                   tokens within the window

Requests in flight when the window closes are not failures: their tokens
inside the window count like any other.  ``attempted`` counts the
requests that were live at some time in the window; ``failed`` those of
them that errored or were refused.
"""
from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def in_window(rec: dict, t0: float, t1: float) -> list[float]:
    return [t for t in rec["times"] if t0 <= t < t1]


def out_tok_s(recs, t0, t1) -> float:
    return sum(len(in_window(r, t0, t1)) for r in recs) / (t1 - t0)


def tpot_p90_ms(recs, t0, t1) -> float | None:
    gaps = []
    for r in recs:
        ts = in_window(r, t0, t1)
        if len(ts) >= 2:
            gaps.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return 1e3 * percentile(gaps, 90) if gaps else None


def attempted_failed(recs, t0, t1) -> tuple[int, int]:
    live = [r for r in recs if r["sent"] is not None and r["sent"] < t1
            and (r["end"] is None or r["end"] >= t0)]
    return len(live), sum(r["status"] == "failed" for r in live)
