"""Serving benchmarks: paged continuous batching vs dense fixed-batch.

Workloads:

  churn (default): staggered arrivals, variable output lengths,
    retirements every few steps.  The dense baseline processes requests
    in fixed batches of ``--batch``: every batch runs until its
    *longest* request finishes, so short requests hold slots idle
    (head-of-line blocking).  The paged engine refills slots the step
    they free up and allocates KV by the page.

  shared-prefix: every request opens with the same system prompt and
    adds a unique tail, and every third request drags in a long unique
    prompt (the prompt-churn stressor).  This exercises the two serving
    pillars this benchmark is the scoreboard for:
      (a) *chunked prefill*: with ``--prefill-budget`` the long prompts
          stream in bounded chunks, so running decodes never stall -
          the harness counts steps where a decoding slot produced no
          token ("decode stalls") and expects zero;
      (b) *prefix caching*: the shared system prompt's full pages are
          claimed from the cache's chain-hash table instead of being
          recomputed - ``prefill_tokens`` (computed) drops well below
          the total prompt tokens submitted.

  parallel-sample: shared-prefix prompts served as *sequence groups* -
    each request fans ``--n`` sampled branches (or ``--beam-width``
    beams) out of one prefill over COW forks.  The scoreboard is the
    shared-page fraction: of all page-table references held by group
    branches, how many point at pages physically shared between
    branches (refcount > 1) - a fork costs one table row + refcounts,
    so n-best serving scales with distinct tokens, not with n.  The
    harness re-checks the cache's refcount invariants after every
    engine step; ``--smoke`` asserts zero violations and a shared
    fraction above 0.5.

  open-loop: Poisson arrivals through the asyncio streaming front-end
    (repro.serving.frontend) - requests arrive at ``--rate``/sec
    regardless of completions, mixed across latency classes per
    ``--class-mix``, with every ``--cancel-every``-th client abandoning
    its stream mid-flight.  Reports client-side p50/p99 TTFT and TPOT
    per class - the SLA scoreboard - and re-checks pool invariants
    after the cancellations.  This is the workload behind the committed
    ``BENCH_serving.json`` baseline (see tools/check_bench.py).

Both paths run the identical model + greedy decode; tok/s counts useful
generated tokens.

``--json PATH`` writes the run's headline metrics as a flat JSON dict -
the raw material of the CI perf-trajectory gate.

``--tp N`` switches to the tensor-parallel scoreboard: the same paged
workload runs single-shard and with the KV pools KV-head-sharded over an
N-way "model" mesh axis (the cascaded ACC merge), asserting the token
streams are identical and reporting per-shard pool bytes plus the
(m, l, o~) triplet collective volume.  On CPU the mesh is simulated:
jax must see N devices before it initializes, so this module imports
jax only after argument parsing and sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` itself.

``--dp N`` composes a data axis onto the mesh (``make_tp_dp_mesh``):
the decode batch is sharded over N data shards on top of any KV-head
sharding, and the scoreboard asserts the composed mesh stays
token-identical to the tp-only (or single-shard) run.

``--disagg`` switches to the disaggregated-serving scoreboard: the
same paged workload runs on a single engine and through a
:class:`repro.serving.disagg.DisaggPair` (prefill worker + decode
worker, prompt KV pages shipped across pools), asserting the two
streams are token-identical and reporting handoff page/dedup/fallback
counts.

  PYTHONPATH=src python benchmarks/serving.py [--arch qwen3-1.7b] [--n 16]
  PYTHONPATH=src python benchmarks/serving.py --workload shared-prefix
  PYTHONPATH=src python benchmarks/serving.py --smoke       # CI gate
  PYTHONPATH=src python benchmarks/serving.py --tp 2 --smoke   # TP gate
  PYTHONPATH=src python benchmarks/serving.py --disagg --smoke # PD gate
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

# jax-free imports (serve.py / serve_async.py defer their own jax import
# past argparse): shares the pre-jax-init simulated-device bootstrap for
# --tp runs and the open-loop workload helpers.
from repro.launch.serve import ensure_host_devices
from repro.launch.serve_async import parse_class_mix, poisson_gaps


def _write_json(path: str | None, metrics: dict) -> None:
    """Persist a run's headline metrics (tools/check_bench.py input)."""
    if not path:
        return
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(metrics, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"metrics -> {path}")


def make_workload(n, prompt_len, vocab, seed=0):
    """n requests, fixed prompt length, variable decode budgets."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, vocab, prompt_len).tolist() for _ in range(n)]
    budgets = rng.integers(4, 24, n).astype(int)
    return prompts, budgets


def make_shared_prefix_workload(n, sys_len, uniq_len, long_len, vocab,
                                seed=0):
    """n requests sharing one system prompt; every 3rd has a long
    unique prompt instead (long-prompt churn)."""
    rng = np.random.default_rng(seed)
    sysp = rng.integers(1, vocab, sys_len).tolist()
    prompts = []
    for i in range(n):
        if i % 3 == 2:
            prompts.append(rng.integers(1, vocab, long_len).tolist())
        else:
            prompts.append(sysp + rng.integers(1, vocab, uniq_len).tolist())
    budgets = rng.integers(4, 16, n).astype(int)
    return prompts, budgets


def _fp_bytes_per_token(cfg) -> int:
    """Reference pool bytes/token of raw ``fp`` storage for this model -
    the denominator of the equal-pool-bytes slot multiplier (layers,
    heads and head dim identical across codecs, so the ratio is exactly
    the per-row storage ratio)."""
    from repro.kernels import page_codec
    from repro.models.model import _dtype
    return cfg.n_layers * page_codec.bytes_per_token(
        "fp", cfg.n_kv_heads, cfg.d_head, _dtype(cfg.compute_dtype))


def _dense_jits(model):
    """One jit wrapper pair per model, so the timed run reuses the
    warmup run's compile cache (mirrors the engine's shared jits)."""
    import jax
    jits = getattr(model, "_dense_bench_jits", None)
    if jits is None:
        jits = (jax.jit(model.prefill), jax.jit(model.decode_step))
        model._dense_bench_jits = jits
    return jits


def run_dense(model, params, prompts, budgets, batch, max_seq):
    """Fixed-batch greedy loop: each batch runs to its longest budget.
    Prompts are right-padded to the batch max (dense caches can't share
    or chunk them); prompts that don't fit the max_seq reservation are
    skipped outright - the dense baseline's equivalent of the paged
    engine's reason="rejected"."""
    import jax
    import jax.numpy as jnp
    prefill, decode = _dense_jits(model)
    keep = [i for i in range(len(prompts)) if len(prompts[i]) < max_seq]
    if len(keep) < len(prompts):
        print(f"dense baseline: skipping {len(prompts) - len(keep)} "
              f"oversized prompt(s)")
    prompts = [prompts[i] for i in keep]
    budgets = np.asarray(budgets)[keep]
    n = len(prompts)
    useful = 0
    t0 = time.perf_counter()
    for start in range(0, n, batch):
        grp = prompts[start:start + batch]
        b = budgets[start:start + batch]
        if len(grp) < batch:   # ragged tail still occupies a full batch
            pad = batch - len(grp)
            grp = grp + [grp[-1]] * pad
            b = np.concatenate([b, np.zeros(pad, int)])
        lmax = max(len(p) for p in grp)
        p = np.zeros((batch, lmax), np.int32)
        for i, row in enumerate(grp):
            p[i, :len(row)] = row
        cache = model.init_cache(params, batch, max_seq)
        logits, cache = prefill(params, cache, jnp.asarray(p))
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        useful += int(np.sum(b >= 1))
        for step in range(1, int(b.max())):
            logits, cache = decode(params, cache, tok)
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            useful += int(np.sum(b >= step + 1))
        jax.block_until_ready(tok)
    return useful, time.perf_counter() - t0


def run_paged(model, params, prompts, budgets, batch, max_seq, page_size,
              prefill_budget=None, spec_k=0, sampling=None, mesh=None,
              group=None, check_every_step=False, kv_codec="fp"):
    """Continuous batching with chunked prefill + prefix caching, and
    optionally self-speculative decode (``spec_k`` drafts per step),
    per-request stochastic sampling, tensor parallelism (``mesh``
    KV-head-shards the paged pools over its "model" axis), and sequence
    groups (``group`` = dict of n/best_of/beam_width/length_penalty
    applied to every request).

    Drives the engine step by step (same policy as ``engine.run``) so it
    can count decode stalls: steps where at least one slot was decoding
    but no token came out - the latency spike chunked prefill removes.
    (A speculative step always yields >= 1 token per decoding slot, so
    the stall gate holds for every spec_k.)  Group branches are excluded
    from the stall accounting (a beam reorder legitimately drops a
    branch's stream), and with ``check_every_step`` the cache's full
    refcount/partition invariants are re-verified after every engine
    step - the returned stats carry the violation count (an invariant
    failure raises) and the shared-page fraction over group slots.
    """
    from repro.serving import (FinishedRequest, InvalidRequestError,
                               Request, SamplingParams, ServingEngine)
    engine = ServingEngine(model, params, max_batch=batch,
                           page_size=page_size, max_seq=max_seq,
                           prefill_budget=prefill_budget, spec_k=spec_k,
                           mesh=mesh, kv_codec=kv_codec)
    def samp(i):
        if sampling is None:
            return None
        return SamplingParams(temperature=sampling["temperature"],
                              top_k=sampling["top_k"],
                              top_p=sampling["top_p"],
                              seed=sampling["seed"] + i)
    gkw = group or {}
    pending = [(i, Request(rid=i, prompt=list(prompts[i]),
                           max_new_tokens=int(budgets[i]),
                           sampling=samp(i), **gkw))
               for i in range(len(prompts))]
    finished = []
    stalls = 0
    step = 0
    shared_refs = total_refs = 0
    peak_frac = 0.0
    t0 = time.perf_counter()
    while pending or engine.sched.has_work:
        while pending and pending[0][0] <= step:
            _, req = pending.pop(0)
            try:
                engine.submit(req)
            except InvalidRequestError:
                raise                               # mirror engine.run
            except ValueError:      # over the per-sequence ceiling:
                engine.stats["rejected"] += 1
                finished.append(FinishedRequest(
                    rid=req.rid, prompt=req.prompt, tokens=[],
                    reason="rejected"))
        # Per-slot stall check: every sequence that was decoding at step
        # start must have one more token after the step, wherever it
        # ended up (still running, preempted back to waiting, finished).
        # An aggregate token-count delta would hide a stalled decode
        # behind another request's prefill completion.
        before = {st.req.rid: len(st.generated)
                  for st in engine.sched.running.values()
                  if st.decoding and st.group is None}
        finished.extend(engine.step())
        after = {st.req.rid: len(st.generated)
                 for st in engine.sched.running.values()
                 if st.group is None}
        after.update((st.req.rid, len(st.generated))
                     for st in engine.sched.waiting)
        after.update((f.rid, len(f.tokens)) for f in finished)
        stalls += sum(1 for rid, n in before.items()
                      if after.get(rid, n) <= n)
        if check_every_step:
            engine.cache.check_invariants()     # raises on any violation
        gslots = engine.sched.group_slots()
        if gslots:
            refs = [p for s in sorted(gslots)
                    for p in engine.cache.slot_pages(s)]
            if refs:
                sh = sum(1 for p in refs if engine.cache.refcount(p) > 1)
                shared_refs += sh
                total_refs += len(refs)
                peak_frac = max(peak_frac, sh / len(refs))
        step += 1
        assert step < 100000, "benchmark runaway"
    dt = time.perf_counter() - t0
    engine.cache.check_invariants()
    assert len(finished) == len(prompts)
    stats = dict(engine.stats)
    stats["shared_page_frac"] = shared_refs / max(total_refs, 1)
    stats["shared_page_frac_peak"] = peak_frac
    stats["refcount_violations"] = 0            # check_invariants raised
    return (engine.stats["generated_tokens"], dt, stats, stalls,
            finished, engine)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--full", action="store_true",
                    help="full-size config (default: reduced smoke scale)")
    ap.add_argument("--workload",
                    choices=("churn", "shared-prefix", "parallel-sample",
                             "open-loop"),
                    default="churn")
    ap.add_argument("--n", type=int, default=16,
                    help="total requests (churn/shared-prefix) / sampled "
                         "branches per request (parallel-sample)")
    ap.add_argument("--groups", type=int, default=3,
                    help="sequence-group requests (parallel-sample)")
    ap.add_argument("--best-of", type=int, default=None,
                    help="branches sampled per request, n best returned "
                         "(parallel-sample)")
    ap.add_argument("--beam-width", type=int, default=0,
                    help="beam search with this many beams instead of "
                         "parallel sampling (parallel-sample workload)")
    ap.add_argument("--length-penalty", type=float, default=1.0,
                    help="score = cum_logprob / len**length_penalty")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--sys-len", type=int, default=32,
                    help="shared system prompt length (shared-prefix)")
    ap.add_argument("--long-len", type=int, default=64,
                    help="long churn prompt length (shared-prefix)")
    ap.add_argument("--max-seq", type=int, default=256,
                    help="dense reserves this per slot up front; paged "
                         "allocates pages on demand - the gap is the win")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--kv-codec", choices=("fp", "int8", "log16"),
                    default="fp",
                    help="paged KV page codec (see repro.kernels."
                         "page_codec): quantized codecs shrink pool "
                         "bytes/token, so a fixed byte budget admits "
                         "proportionally more concurrent sequences; "
                         "with --smoke, a non-fp codec additionally "
                         "gates on >= 2x equal-pool-bytes slots vs fp")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="prefill token budget per engine step (chunked "
                         "prefill); default: unbounded")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="max prompt-lookup draft tokens verified per "
                         "decode step (0 = no speculation)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed (request i uses seed + i)")
    ap.add_argument("--decode-len", type=int, default=0,
                    help="fixed per-request decode budget (0 = the "
                         "workload's randomized 4..16/4..24 budgets)")
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate, requests/sec "
                         "(open-loop; <= 0: all arrive at t=0)")
    ap.add_argument("--class-mix", type=parse_class_mix,
                    default="interactive=0.25,standard=0.5,batch=0.25",
                    help="latency-class weights (open-loop), e.g. "
                         "interactive=0.5,standard=0.3,batch=0.2")
    ap.add_argument("--cancel-every", type=int, default=0,
                    help="every k-th open-loop client abandons its "
                         "stream after --cancel-after tokens (0 = never)")
    ap.add_argument("--cancel-after", type=int, default=4)
    ap.add_argument("--transport", choices=("inproc", "http"),
                    default="inproc",
                    help="open-loop only: 'inproc' consumes the "
                         "AsyncFrontend generators directly; 'http' "
                         "starts the SSE server on an ephemeral port "
                         "and drives the identical workload through "
                         "real sockets (client-side TTFT/TPOT include "
                         "the wire; abandonment = socket close)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the run's headline metrics as JSON "
                         "(the tools/check_bench.py input)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel scoreboard: run the paged "
                         "workload single-shard AND with the KV pools "
                         "head-sharded over an N-way 'model' mesh axis, "
                         "asserting token-identical output (simulated "
                         "CPU mesh via XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel degree composed with --tp: the "
                         "decode batch shards over a 'data' mesh axis "
                         "(must divide --batch; simulated CPU devices "
                         "as with --tp)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated-serving scoreboard: run the "
                         "workload on a single engine AND through a "
                         "prefill-worker/decode-worker pair with KV "
                         "page handoff, asserting token-identical "
                         "streams and reporting handoff counts")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: reduced shared-prefix run asserting "
                         "zero decode stalls + prefix-cache reuse (and, "
                         "with --spec-k, accept-rate > 0 and "
                         "tokens/step >= 1; with --tp, token-identical "
                         "TP output and per-shard pool bytes / tp)")
    args = ap.parse_args()
    if args.tp < 1:
        ap.error("--tp must be >= 1")
    if args.dp < 1:
        ap.error("--dp must be >= 1")
    if args.dp > 1 and args.batch % args.dp:
        ap.error(f"--dp {args.dp} must divide --batch {args.batch}")
    if args.disagg and (args.tp > 1 or args.dp > 1):
        ap.error("--disagg is a single-mesh scoreboard; drop --tp/--dp")
    ensure_host_devices(args.tp * args.dp)
    if isinstance(args.class_mix, str):      # argparse skips the default
        args.class_mix = parse_class_mix(args.class_mix)
    if args.workload == "open-loop" and args.smoke:
        args.full = False
        args.n = min(args.n, 8)
        args.rate = 50.0
        args.decode_len = args.decode_len or 8
        if args.cancel_every == 0:
            args.cancel_every = 3
    if args.smoke and args.workload not in ("parallel-sample",
                                            "open-loop"):
        args.workload = "shared-prefix"
        args.full = False
        args.n = min(args.n, 9)
        if args.prefill_budget is None:
            args.prefill_budget = 16
        if args.spec_k and not args.decode_len:
            # Speculation pays where output repeats itself: give the
            # reduced random-weight model enough budget to fall into
            # its greedy/low-temperature cycles.
            args.decode_len = 48
        if args.spec_k and args.temperature > 0 and not args.top_k \
                and args.top_p >= 1.0:
            # The reduced random-weight model is near-uniform over the
            # vocab at temperature: untruncated sampling would accept a
            # draft once in ~vocab_size tries, gating CI on a coin
            # flip.  Truncating to the top few tokens keeps the stream
            # stochastic while making prompt-lookup hits realistic -
            # and exercises the temperature+top-k+categorical pipeline.
            args.top_k = 4

    if args.workload == "parallel-sample":
        if args.smoke:
            args.full = False
            args.groups = min(args.groups, 3)
            args.decode_len = args.decode_len or 8
        if args.beam_width > 0:
            width = args.beam_width
        else:
            args.n = max(args.n, 2)
            width = args.best_of if args.best_of is not None else args.n
        args.batch = max(args.batch, width)

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import build_model

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.workload == "open-loop":
        return _run_open_loop(model, params, args)
    if args.workload == "parallel-sample":
        return _run_parallel_sample(model, params, args)
    if args.workload == "shared-prefix":
        prompts, budgets = make_shared_prefix_workload(
            args.n, args.sys_len, args.prompt_len, args.long_len,
            cfg.vocab_size)
    else:
        prompts, budgets = make_workload(args.n, args.prompt_len,
                                         cfg.vocab_size)
    if args.decode_len:
        budgets = np.full(args.n, args.decode_len, int)
    sampling = None
    if args.temperature > 0 or args.top_k or args.top_p < 1.0:
        sampling = {"temperature": args.temperature, "top_k": args.top_k,
                    "top_p": args.top_p, "seed": args.seed}

    if args.disagg:
        return _run_disagg(model, params, prompts, budgets, sampling,
                           args)
    if args.tp > 1 or args.dp > 1:
        return _run_tp(model, params, prompts, budgets, sampling, args)

    # Warm both paths with the identical workload so every jit shape
    # (prefill group sizes, resumed lengths) compiles outside the timed
    # region; engines share one compile cache via the model.
    run_dense(model, params, prompts, budgets, args.batch, args.max_seq)
    run_paged(model, params, prompts, budgets, args.batch, args.max_seq,
              args.page_size, args.prefill_budget, args.spec_k, sampling,
              kv_codec=args.kv_codec)

    d_tok, d_dt = run_dense(model, params, prompts, budgets, args.batch,
                            args.max_seq)
    p_tok, p_dt, stats, stalls, _, engine = run_paged(
        model, params, prompts, budgets, args.batch, args.max_seq,
        args.page_size, args.prefill_budget, args.spec_k, sampling,
        kv_codec=args.kv_codec)
    d_tps = d_tok / d_dt
    p_tps = p_tok / p_dt
    total_prompt = sum(len(p) for p in prompts)
    print(f"dense fixed-batch:  {d_tok} tok in {d_dt:.2f}s -> "
          f"{d_tps:.1f} tok/s")
    print(f"paged continuous:   {p_tok} tok in {p_dt:.2f}s -> "
          f"{p_tps:.1f} tok/s  (steps={stats['steps']}, "
          f"chunks={stats['prefill_chunks']}, "
          f"preemptions={stats['preemptions']})")
    print(f"prefill tokens:     {stats['prefill_tokens']} computed / "
          f"{total_prompt} submitted "
          f"({stats['cached_prefill_tokens']} reused from prefix cache)")
    print(f"decode stalls:      {stalls} steps")
    # Byte accounting: pool bytes per stored KV token-row under this
    # codec vs raw fp storage.  At a fixed pool byte budget the codec
    # admits equal_bytes_slots_x times the concurrent sequences.
    bpt = engine.bytes_per_token()
    fp_bpt = _fp_bytes_per_token(model.cfg)
    slots_x = fp_bpt / bpt
    print(f"kv codec {args.kv_codec}: {bpt} B/token vs fp {fp_bpt} "
          f"-> {slots_x:.2f}x concurrent slots at equal pool bytes")
    accept_rate = stats["draft_accepted"] / max(stats["draft_tokens"], 1)
    # Accepted tokens per slot per decode step: 1.0 = plain decode,
    # spec_k + 1 = every draft accepted every step.
    tok_per_step = stats["decode_tokens"] / max(stats["decode_slot_steps"],
                                                1)
    if args.spec_k:
        print(f"speculation:        {stats['draft_accepted']}/"
              f"{stats['draft_tokens']} drafts accepted "
              f"({accept_rate:.0%}), "
              f"{tok_per_step:.2f} accepted tokens/step, "
              f"{stats['rollbacks']} rollbacks")
    print(f"speedup paged/dense: {p_tps / d_tps:.2f}x")

    # Structural metrics (token/page/step counts) are deterministic for
    # a fixed workload+seed; tok/s metrics are wall-clock (check_bench
    # applies loose tolerances to those).
    metrics = {
        "workload": args.workload,
        "dense_tok_s": d_tps,
        "paged_tok_s": p_tps,
        "decode_stalls": stalls,
        "prefill_tokens": stats["prefill_tokens"],
        "cached_prefill_tokens": stats["cached_prefill_tokens"],
        "accept_rate": accept_rate,
        "tokens_per_step": tok_per_step,
        "steps": stats["steps"],
        "preemptions": stats["preemptions"],
        "kv_codec": args.kv_codec,
        "bytes_per_token": bpt,
        "equal_bytes_slots_x": slots_x,
    }
    ok = p_tps >= d_tps
    if args.smoke:
        ok = True
        if stalls != 0:
            print("SMOKE FAIL: decode stalled during chunked prefill")
            ok = False
        if stats["cached_prefill_tokens"] == 0 or \
                stats["prefill_tokens"] >= total_prompt:
            print("SMOKE FAIL: prefix cache reused nothing")
            ok = False
        if args.spec_k:
            if stats["draft_accepted"] == 0:
                print("SMOKE FAIL: speculation accepted no draft")
                ok = False
            # >= 1.0 holds by construction (every verify step emits at
            # least the correction token); the greedy run must show
            # real draft-acceptance lift to catch proposer/accept
            # regressions, while the sampled run only has to stay sane.
            floor = 1.1 if args.temperature == 0 else 1.0
            if tok_per_step < floor:
                print(f"SMOKE FAIL: spec decode below {floor} tokens/step")
                ok = False
        if args.kv_codec != "fp" and slots_x < 2.0:
            # The codec tentpole's capacity claim: a quantized pool
            # must at least double the sequences a fixed byte budget
            # can hold.
            print(f"SMOKE FAIL: {args.kv_codec} equal-pool-bytes slots "
                  f"{slots_x:.2f}x < 2x vs fp")
            ok = False
        print("smoke:", "OK" if ok else "FAIL")
    metrics["smoke_ok"] = bool(ok)
    _write_json(args.json, metrics)
    return ok


def _run_parallel_sample(model, params, args):
    """Sequence-group scoreboard: ``--groups`` shared-prefix requests,
    each fanned into ``--n`` sampled branches (or ``--beam-width``
    beams) over COW forks.  Reports the shared-page fraction - the
    fraction of group page-table references that point at physically
    shared pages - plus fork counts and completion throughput, and
    re-checks the cache's refcount invariants after every step.

    ``--smoke`` is the CI gate: shared-page fraction > 0.5 on this
    shared-prefix workload, zero refcount-invariant violations, every
    group returning its full completion set.
    """
    cfg = model.cfg
    beam = args.beam_width > 0
    if beam:
        group = {"beam_width": args.beam_width, "n": args.beam_width,
                 "length_penalty": args.length_penalty}
        sampling = None
        width = args.beam_width
    else:
        width = args.best_of if args.best_of is not None else args.n
        group = {"n": args.n, "best_of": args.best_of,
                 "length_penalty": args.length_penalty}
        sampling = {"temperature": args.temperature or 0.8,
                    "top_k": args.top_k or 8, "top_p": args.top_p,
                    "seed": args.seed}
    # shared-prefix prompts: one system prompt, unique per-group tails
    rng = np.random.default_rng(7)
    sysp = rng.integers(1, cfg.vocab_size, args.sys_len).tolist()
    prompts = [sysp + rng.integers(1, cfg.vocab_size,
                                   args.prompt_len).tolist()
               for _ in range(args.groups)]
    budgets = np.full(args.groups, args.decode_len or 12, int)

    common = dict(batch=args.batch, max_seq=args.max_seq,
                  page_size=args.page_size,
                  prefill_budget=args.prefill_budget, spec_k=args.spec_k,
                  sampling=sampling, group=group, check_every_step=True,
                  kv_codec=args.kv_codec)
    if args.tp > 1:
        from repro.launch.mesh import make_tp_mesh
        common["mesh"] = make_tp_mesh(args.tp)
    run_paged(model, params, prompts, budgets, **common)      # warm jits
    tok, dt, stats, _, finished, engine = run_paged(
        model, params, prompts, budgets, **common)

    n_comp = sum(len(f.completions or []) for f in finished)
    comp_tokens = sum(len(c.tokens) for f in finished
                      for c in (f.completions or []))
    kind = f"beam-{args.beam_width}" if beam else \
        f"n={args.n}" + (f"/best-of-{args.best_of}" if args.best_of
                         else "")
    print(f"parallel-sample ({kind}): {args.groups} groups x width "
          f"{width} over {stats['steps']} steps, "
          f"{tok} tokens in {dt:.2f}s -> {tok / dt:.1f} tok/s")
    print(f"fan-out:            {stats['groups']} groups admitted, "
          f"{stats['forks']} COW forks (zero KV copied at fork), "
          f"{stats['cow_copies']} divergence copies")
    print(f"completions:        {n_comp} returned "
          f"({comp_tokens} tokens); prefill computed "
          f"{stats['prefill_tokens']} of "
          f"{sum(len(p) for p in prompts)} submitted prompt tokens "
          f"({stats['cached_prefill_tokens']} reused)")
    print(f"shared pages:       {stats['shared_page_frac']:.0%} of group "
          f"page refs shared (peak {stats['shared_page_frac_peak']:.0%})")
    print(f"refcount invariants: "
          f"{stats['refcount_violations']} violations over "
          f"{stats['steps']} per-step checks")

    ok = True
    if args.smoke:
        if stats["shared_page_frac"] <= 0.5:
            print("SMOKE FAIL: groups share <= 50% of their pages")
            ok = False
        if stats["refcount_violations"] != 0:
            print("SMOKE FAIL: refcount invariant violated")
            ok = False
        if stats["forks"] == 0:
            print("SMOKE FAIL: no fork ever taken")
            ok = False
        if n_comp != args.groups * (args.beam_width or args.n):
            print(f"SMOKE FAIL: expected "
                  f"{args.groups * (args.beam_width or args.n)} "
                  f"completions, got {n_comp}")
            ok = False
        print("smoke:", "OK" if ok else "FAIL")
    _write_json(args.json, {
        "workload": "parallel-sample",
        "shared_page_frac": stats["shared_page_frac"],
        "shared_page_frac_peak": stats["shared_page_frac_peak"],
        "forks": stats["forks"],
        "completions": n_comp,
        "paged_tok_s": tok / dt,
        "steps": stats["steps"],
        "smoke_ok": bool(ok),
    })
    return ok


async def _http_open_loop(engine, arrivals, *, cancel_every, cancel_after):
    """Open-loop traffic over the HTTP/SSE transport: an ephemeral-port
    :class:`repro.serving.http.HttpServer` in-process, one raw-socket
    SSE client per request (round-robined across four tenant headers).
    Abandonment closes the socket mid-stream - the server's
    disconnect-cancellation path, not the in-process generator one.
    Records mirror :func:`repro.launch.serve_async.open_loop`; a client
    that disconnects records reason="cancelled" (it never sees the
    terminal event)."""
    import asyncio
    import time as _time

    from repro.serving import AsyncFrontend
    from repro.serving.http import HttpServer, stream_generate
    frontend = AsyncFrontend(engine)
    server = await HttpServer(frontend, port=0).start()
    records: list[dict] = []

    async def client(i: int, payload: dict, cls: str) -> None:
        cancel_at = None
        if cancel_every > 0 and i % cancel_every == cancel_every - 1:
            cancel_at = cancel_after
        t_submit = _time.perf_counter()
        t_tokens: list[float] = []
        reason = None
        gen = stream_generate(server.host, server.port, payload,
                              tenant=f"bench-{i % 4}")
        try:
            async for kind, data in gen:
                if kind == "token":
                    t_tokens.append(_time.perf_counter())
                    if cancel_at is not None \
                            and len(t_tokens) >= cancel_at:
                        break          # socket close = disconnect
                elif kind == "done":
                    reason = data["reason"]
                else:
                    reason = f"http-{data['status']}"
        finally:
            await gen.aclose()
        ttft = t_tokens[0] - t_submit if t_tokens else None
        tpot = (t_tokens[-1] - t_tokens[0]) / (len(t_tokens) - 1) \
            if len(t_tokens) > 1 else None
        records.append({"rid": i, "cls": cls, "ttft": ttft,
                        "tpot": tpot, "tokens": len(t_tokens),
                        "reason": reason or "cancelled"})

    tasks = []
    for i, (gap, payload, cls) in enumerate(arrivals):
        if gap:
            await asyncio.sleep(gap)
        tasks.append(asyncio.ensure_future(client(i, payload, cls)))
    await asyncio.gather(*tasks)
    await frontend.drain()        # disconnect cancels settle
    await server.stop()
    await frontend.close()
    return sorted(records, key=lambda r: r["rid"])


def _run_open_loop(model, params, args):
    """SLA scoreboard: Poisson open-loop traffic through the asyncio
    streaming front-end, mixed across latency classes, with optional
    mid-stream abandonment.  Client-side p50/p99 TTFT and TPOT per
    class are the committed-baseline metrics (BENCH_serving.json).
    ``--transport http`` routes the identical workload through the
    HTTP/SSE server over real sockets instead of in-process
    generators.

    Runs the identical workload twice on the same model (jit compile
    cache is shared across engines), timing only the second run, so the
    reported latencies measure serving - not tracing.

    ``--smoke`` is the CI gate: every request resolves, the expected
    abandonments come back ``reason="cancelled"``, the pool is
    invariant-clean after all of it, and the adaptive prefill budget
    stayed inside its [floor, ceiling] clamp.
    """
    import asyncio

    from repro.launch.serve_async import open_loop, summarize
    from repro.serving import (LATENCY_CLASSES, AsyncFrontend, Request,
                               SamplingParams, ServingEngine)
    cfg = model.cfg
    n = args.n
    prompts, budgets = make_shared_prefix_workload(
        n, args.sys_len, args.prompt_len, args.long_len, cfg.vocab_size,
        seed=args.seed)
    if args.decode_len:
        budgets = np.full(n, args.decode_len, int)
    rng = np.random.default_rng(args.seed)
    names = sorted(args.class_mix)
    picks = rng.choice(len(names), size=n,
                       p=[args.class_mix[c] for c in names])
    gaps = poisson_gaps(rng, n, args.rate)

    def build_arrivals():
        return [(gaps[i], Request(
            rid=i, prompt=list(prompts[i]),
            max_new_tokens=int(budgets[i]),
            sampling=SamplingParams(temperature=args.temperature,
                                    top_k=args.top_k, top_p=args.top_p,
                                    seed=args.seed + i)
            if args.temperature > 0 else None,
            latency_class=LATENCY_CLASSES[names[int(picks[i])]]))
            for i in range(n)]

    def build_http_arrivals():
        # Same workload as build_arrivals(), expressed as wire payloads
        # (the server assigns rids; records are keyed by client index).
        arrivals = []
        for i in range(n):
            cls = names[int(picks[i])]
            payload = {"prompt": [int(t) for t in prompts[i]],
                       "max_new_tokens": int(budgets[i]),
                       "latency_class": cls, "id": i}
            if args.temperature > 0:
                payload.update(temperature=args.temperature,
                               top_k=args.top_k, top_p=args.top_p,
                               seed=args.seed + i)
            arrivals.append((gaps[i], payload, cls))
        return arrivals

    def run_once():
        engine = ServingEngine(
            model, params, max_batch=args.batch, page_size=args.page_size,
            max_seq=args.max_seq, prefill_budget="adaptive",
            spec_k=args.spec_k, kv_codec=args.kv_codec)
        t0 = time.perf_counter()
        if args.transport == "http":
            records = asyncio.run(_http_open_loop(
                engine, build_http_arrivals(),
                cancel_every=args.cancel_every,
                cancel_after=args.cancel_after))
        else:
            records = asyncio.run(open_loop(
                AsyncFrontend(engine), build_arrivals(),
                cancel_every=args.cancel_every,
                cancel_after=args.cancel_after))
        dt = time.perf_counter() - t0
        engine.cache.check_invariants()
        return records, dt, engine

    run_once()                                    # warm the jit shapes
    records, dt, engine = run_once()
    summary = summarize(records)
    st = engine.stats

    print(f"open-loop[{args.transport}]: {n} requests at {args.rate}/s "
          f"over {dt:.2f}s "
          f"({st['steps']} steps, {st['cancelled']} cancelled, "
          f"{st['preemptions']} preemptions, adaptive budget last "
          f"{st['adaptive_budget_last']} in [{engine.adaptive_floor}, "
          f"{engine.adaptive_ceiling}])")
    metrics = {"workload": "open-loop", "transport": args.transport,
               "requests": n,
               "cancelled": st["cancelled"],
               "steps": st["steps"],
               "adaptive_budget_last": st["adaptive_budget_last"],
               "kv_codec": engine.kv_codec,
               "bytes_per_token": engine.bytes_per_token()}
    for cls, ent in summary.items():
        tgt = LATENCY_CLASSES[cls]
        fmt = lambda v: "-" if v is None else f"{1e3 * v:.0f}ms"  # noqa: E731
        print(f"  {cls:<12} n={ent['n']:<3} "
              f"ttft p50/p99 {fmt(ent['ttft_p50'])}/{fmt(ent['ttft_p99'])} "
              f"(target {1e3 * tgt.ttft_target:.0f}ms)  "
              f"tpot p50/p99 {fmt(ent['tpot_p50'])}/{fmt(ent['tpot_p99'])} "
              f"(target {1e3 * tgt.tpot_target:.0f}ms)  "
              f"cancelled={ent['cancelled']}")
        for k in ("ttft_p50", "ttft_p99", "tpot_p50", "tpot_p99"):
            metrics[f"{k}_{cls}"] = ent[k]
        metrics[f"n_{cls}"] = ent["n"]

    ok = True
    if args.smoke:
        if len(records) != n:
            print(f"SMOKE FAIL: {len(records)}/{n} requests resolved")
            ok = False
        want_cancel = n // args.cancel_every if args.cancel_every else 0
        got_cancel = sum(r["reason"] == "cancelled" for r in records)
        if got_cancel != want_cancel:
            print(f"SMOKE FAIL: {got_cancel} cancelled, expected "
                  f"{want_cancel}")
            ok = False
        if not (engine.adaptive_floor <= st["adaptive_budget_last"]
                <= engine.adaptive_ceiling):
            print("SMOKE FAIL: adaptive budget escaped its clamp")
            ok = False
        missing = [r["rid"] for r in records
                   if r["reason"] in ("eos", "length")
                   and r["tokens"] != int(budgets[r["rid"]])]
        # eos on a random-weight model is improbable but legal; only a
        # short stream WITHOUT eos is a lost-token bug.
        missing = [rid for rid in missing
                   if records[rid]["reason"] != "eos"]
        if missing:
            print(f"SMOKE FAIL: streams {missing} lost tokens")
            ok = False
        print("smoke:", "OK" if ok else "FAIL")
    metrics["smoke_ok"] = bool(ok)
    _write_json(args.json, metrics)
    return ok


def _run_tp(model, params, prompts, budgets, sampling, args):
    """Tensor/data-parallel scoreboard: single-shard vs mesh-sharded
    paged serving on the identical workload.  The sharded run must be
    *token-identical* (the ACC merge with the neutral triplet is an fp
    identity per head; data shards apply the full batch's KV scatter),
    with per-shard pool bytes cut by tp (the pool replicates over the
    data axis) and only the tiny (m, l, o~) triplets crossing the
    model axis."""
    from repro.launch.mesh import make_tp_dp_mesh
    mesh = make_tp_dp_mesh(args.tp, args.dp)
    common = (model, params, prompts, budgets, args.batch, args.max_seq,
              args.page_size, args.prefill_budget, args.spec_k, sampling)
    codec = dict(kv_codec=args.kv_codec)
    run_paged(*common, **codec)              # warm single-shard jits
    run_paged(*common, mesh=mesh, **codec)   # warm TP jits
    s_tok, s_dt, s_stats, s_stalls, s_fin, s_eng = run_paged(*common,
                                                             **codec)
    p_tok, p_dt, stats, stalls, p_fin, p_eng = run_paged(*common,
                                                         mesh=mesh,
                                                         **codec)
    s_out = {f.rid: f.tokens for f in s_fin}
    p_out = {f.rid: f.tokens for f in p_fin}
    identical = s_out == p_out
    mism = sum(1 for r in s_out if p_out.get(r) != s_out[r])
    print(f"single shard:  {s_tok} tok in {s_dt:.2f}s -> "
          f"{s_tok / s_dt:.1f} tok/s "
          f"(pool {s_eng.pool_bytes_per_shard()} B/shard)")
    print(f"tp={args.tp} dp={args.dp} sharded: "
          f"{p_tok} tok in {p_dt:.2f}s -> "
          f"{p_tok / p_dt:.1f} tok/s "
          f"(pool {p_eng.pool_bytes_per_shard()} B/shard, "
          f"{stats['steps']} steps)")
    print(f"token parity:  {'IDENTICAL' if identical else 'MISMATCH'} "
          f"({len(s_out) - mism}/{len(s_out)} requests match)")
    print(f"ACC-merge triplet traffic: {stats['triplet_bytes']} B "
          f"({stats['triplet_bytes'] / max(p_tok, 1):.0f} B/token) vs "
          f"pool {p_eng.pool_bytes()} B")
    ok = identical
    if s_eng.pool_bytes_per_shard() != \
            p_eng.pool_bytes_per_shard() * args.tp:
        print(f"TP FAIL: per-shard pool not cut by tp "
              f"({s_eng.pool_bytes_per_shard()} -> "
              f"{p_eng.pool_bytes_per_shard()})")
        ok = False
    if args.tp > 1 and stats["triplet_bytes"] == 0:
        print("TP FAIL: no triplet traffic accounted")
        ok = False
    if not identical:
        print("TP FAIL: sharded output diverged from single shard")
    if args.smoke:
        if stalls != 0:
            print("SMOKE FAIL: decode stalled during chunked prefill")
            ok = False
        print("smoke:", "OK" if ok else "FAIL")
    return ok


def _run_disagg(model, params, prompts, budgets, sampling, args):
    """Disaggregated-serving scoreboard: the identical paged workload
    on a single engine vs a :class:`repro.serving.disagg.DisaggPair`
    (prompts prefilled on worker A, generation on worker B, the prompt
    KV pages device-copied across pools through the chain-hash
    manifest).  The two token streams must be identical per request;
    both pools must come back invariant-clean and leak-free.

    ``--smoke`` is the CI gate: full token parity, every request
    handed off (no silent fallback on this workload), at least one
    page shipped, zero refcount violations (``check_invariants``
    raises on any), both pools fully available afterwards."""
    from repro.serving import (DisaggPair, Request, SamplingParams,
                               ServingEngine)

    def samp(i):
        if sampling is None:
            return None
        return SamplingParams(temperature=sampling["temperature"],
                              top_k=sampling["top_k"],
                              top_p=sampling["top_p"],
                              seed=sampling["seed"] + i)

    def arrivals():
        return [(i, Request(rid=i, prompt=list(prompts[i]),
                            max_new_tokens=int(budgets[i]),
                            sampling=samp(i)))
                for i in range(len(prompts))]

    def engine():
        return ServingEngine(model, params, max_batch=args.batch,
                             page_size=args.page_size,
                             max_seq=args.max_seq,
                             prefill_budget=args.prefill_budget,
                             spec_k=args.spec_k, kv_codec=args.kv_codec)

    # warm the jit shapes on both paths (shared compile cache)
    engine().run(arrivals())
    DisaggPair(engine(), engine()).run(arrivals())

    single = engine()
    t0 = time.perf_counter()
    s_fin = single.run(arrivals())
    s_dt = time.perf_counter() - t0
    single.cache.check_invariants()

    pair = DisaggPair(engine(), engine())
    t0 = time.perf_counter()
    d_fin = pair.run(arrivals())
    d_dt = time.perf_counter() - t0
    pair.check_invariants()

    s_out = {f.rid: f.tokens for f in s_fin}
    d_out = {f.rid: f.tokens for f in d_fin}
    identical = s_out == d_out
    mism = sum(1 for r in s_out if d_out.get(r) != s_out[r])
    hs = pair.stats
    d_tok = pair.decode.stats["generated_tokens"]
    leaks = sum(1 for c in (pair.prefill.cache, pair.decode.cache)
                if c.available_page_count != c.num_pages)
    print(f"single engine: {single.stats['generated_tokens']} tok in "
          f"{s_dt:.2f}s -> "
          f"{single.stats['generated_tokens'] / s_dt:.1f} tok/s")
    print(f"disaggregated: {d_tok} tok in {d_dt:.2f}s -> "
          f"{d_tok / d_dt:.1f} tok/s "
          f"(prefill worker {pair.prefill.stats['steps']} steps, "
          f"decode worker {pair.decode.stats['steps']} steps)")
    print(f"token parity:  {'IDENTICAL' if identical else 'MISMATCH'} "
          f"({len(s_out) - mism}/{len(s_out)} requests match)")
    print(f"handoffs:      {hs['handoffs']} committed, "
          f"{hs['handoff_pages']} pages shipped, "
          f"{hs['handoff_dupes']} dupes shared in place, "
          f"{hs['handoff_fallbacks']} fallbacks, "
          f"{hs['handoff_aborts']} aborts")
    print(f"decode-worker prefill: "
          f"{pair.decode.stats['prefill_tokens']} tokens computed "
          f"({pair.decode.stats['cached_prefill_tokens']} claimed from "
          f"imported pages)")

    ok = identical and leaks == 0
    if not identical:
        print("DISAGG FAIL: streams diverged from the single engine")
    if leaks:
        print("DISAGG FAIL: a worker pool leaked pages")
    if args.smoke:
        if hs["handoffs"] != len(prompts):
            print(f"SMOKE FAIL: {hs['handoffs']}/{len(prompts)} "
                  f"requests handed off")
            ok = False
        if hs["handoff_pages"] == 0:
            print("SMOKE FAIL: no page ever shipped")
            ok = False
        if pair.decode.stats["cached_prefill_tokens"] == 0:
            print("SMOKE FAIL: decode worker never claimed an "
                  "imported page")
            ok = False
        print("smoke:", "OK" if ok else "FAIL")
    _write_json(args.json, {
        "workload": "disagg",
        "handoffs": hs["handoffs"],
        "handoff_pages": hs["handoff_pages"],
        "handoff_dupes": hs["handoff_dupes"],
        "handoff_fallbacks": hs["handoff_fallbacks"],
        "token_parity": bool(identical),
        "cached_prefill_tokens": pair.decode.stats[
            "cached_prefill_tokens"],
        "paged_tok_s": d_tok / d_dt,
        "steps": pair.decode.stats["steps"],
        "smoke_ok": bool(ok),
    })
    return ok


if __name__ == "__main__":
    import sys
    sys.exit(0 if main() else 1)
