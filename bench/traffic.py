"""The one traffic generator: turns a mix file of ``bench/traffic/`` and a
seed into the requests of a run.  Pure Python and NumPy, no JAX: the
load generator's child process imports it, and so does the harness,
which needs the shared prefixes for set-up and the prompts for the
reference.

A mix file is JSON with these keys (all sizes in tokens).  The loop is
closed: ``streams`` clients, each sends its next request when its last
one ends.

  loop            "closed"
  streams         number of clients
  shared          optional {"count": n, "length": <dist>}: prefixes
                  (documents) every request starts with one of; the
                  harness prefills them during set-up, and each is used
                  equally often in every round
  unique          <dist> of the request's own tokens after the prefix
  output          <dist> of max_new_tokens; every request is greedy
  stagger_first   the first request of each stream asks for a staggered
                  share of its output length, so the streams start
                  spread over their requests as a closed loop in its
                  steady state is
  rounds          requests made per stream

A <dist> is {"dist": "fixed", "value": v} or {"dist": "loguniform",
"min", "max"}, with an optional "round" (lengths become multiples of
it).  Sizes are stratified: the n draws of one round are the n quantiles
(i + 0.5) / n of the distribution, in an order drawn from the seed.
Every seed so gets the same set of sizes and only their order and the
token ids differ, which keeps runs of different seeds comparable.
"""
from __future__ import annotations

import json
import math

import numpy as np

LOOPS = ("closed",)
KEYS = {"loop", "streams", "shared", "unique", "output", "stagger_first",
        "rounds"}


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    check(mix)
    return mix


def check(mix: dict) -> None:
    """Refuse a mix the generator cannot honour, before any run."""
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"loop must be one of {LOOPS}: {mix.get('loop')}")
    if set(mix) - KEYS:
        raise ValueError(f"unknown mix keys {sorted(set(mix) - KEYS)}")
    if int(mix.get("streams", 0)) < 1:
        raise ValueError("a closed loop needs streams >= 1")
    for key in ("unique", "output"):
        quantile(mix[key], 0.5)
    if "shared" in mix:
        quantile(mix["shared"]["length"], 0.5)
        if int(mix["shared"]["count"]) < 1:
            raise ValueError("shared.count must be >= 1")


def quantile(dist: dict, q: float) -> int:
    """The q-quantile of a length distribution, as a whole length."""
    kind = dist["dist"]
    if kind == "fixed":
        v = float(dist["value"])
    elif kind == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        v = math.exp(lo + q * (hi - lo))
    else:
        raise ValueError(f"unknown dist {kind!r}")
    step = int(dist.get("round", 1))
    n = max(step, int(round(v / step)) * step)
    if n < 1:
        raise ValueError(f"{dist} gives a length < 1")
    return n


def stratified(dist: dict, n: int, rng) -> list[int]:
    """n lengths at the quantiles (i + 0.5) / n, in a seeded order."""
    vals = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    return [vals[i] for i in rng.permutation(n)]


def _requests(mix: dict, n: int, rng, vocab: int) -> list[dict]:
    """One round of n requests: sizes and prefixes."""
    uniq = stratified(mix["unique"], n, rng)
    outs = stratified(mix["output"], n, rng)
    picks = [None] * n
    if "shared" in mix:
        count = int(mix["shared"]["count"])
        picks = [int(i) % count for i in rng.permutation(n)]
    return [{"prefix": picks[i],
             "unique": rng.integers(1, vocab, uniq[i]).tolist(),
             "max_new_tokens": outs[i]} for i in range(n)]


def build(mix: dict, seed: int, vocab: int, max_seq: int) -> dict:
    """The run's plan: {"shared": [token lists], "streams": [[request,
    ...] per client]}.  Deterministic in (mix, seed, vocab, max_seq)."""
    check(mix)
    rng = np.random.default_rng(seed)
    shared = []
    if "shared" in mix:
        lens = stratified(mix["shared"]["length"],
                          int(mix["shared"]["count"]), rng)
        shared = [rng.integers(1, vocab, n).tolist() for n in lens]
    plan = {"shared": shared}
    streams = int(mix["streams"])
    per = [[] for _ in range(streams)]
    for _ in range(int(mix.get("rounds", 64))):
        for k, req in enumerate(_requests(mix, streams, rng, vocab)):
            per[k].append(req)
    if mix.get("stagger_first", False):
        frac = [(i + 0.5) / streams for i in rng.permutation(streams)]
        for k in range(streams):
            first = per[k][0]
            first["max_new_tokens"] = max(
                1, int(round(first["max_new_tokens"] * frac[k])))
    plan["streams"] = per
    for req in (r for s in per for r in s):
        n = prompt_len(plan, req) + req["max_new_tokens"]
        if n > max_seq:
            raise ValueError(f"a request needs {n} tokens, over max_seq "
                             f"{max_seq}: size the traffic to the engine")
    return plan


def prompt(plan: dict, req: dict) -> list[int]:
    pre = [] if req["prefix"] is None else plan["shared"][req["prefix"]]
    return pre + req["unique"]


def prompt_len(plan: dict, req: dict) -> int:
    pre = 0 if req["prefix"] is None else len(plan["shared"][req["prefix"]])
    return pre + len(req["unique"])


def request_at(plan: dict, stream: int, idx: int) -> dict:
    """The request a load-generator record names."""
    return plan["streams"][stream][idx]
