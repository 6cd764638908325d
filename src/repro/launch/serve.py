"""Production serving entry point: continuous batching over paged KV.

Default path: the paged serving engine (block-pool KV cache + scheduler,
src/repro/serving/) with requests arriving every step - they join and
leave the batch mid-flight.  ``--dense`` falls back to the legacy
fixed-batch greedy loop over a contiguous cache.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --batch 2 --steps 4

``--tp N`` serves tensor-parallel: the paged KV pools are KV-head-sharded
over a ("data", "model") mesh and decode/prefill/verify attention runs
the cascaded ACC merge (only (m, l, o~) triplets cross shards).  On CPU
the mesh is simulated - jax must see N devices *before* it initializes,
which this entry point arranges by setting
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (hence jax is
imported only after argument parsing).
"""
import argparse
import os
import time

import numpy as np


def parse_prefill_budget(value: str | None) -> "int | str | None":
    """CLI form of the engine's ``prefill_budget``: "none"/"" -> None
    (unbounded), "adaptive" -> SLA-headroom-derived per-step budget
    (see repro.serving.scheduler.Scheduler.adaptive_prefill_budget),
    else an int token budget.  Lives here (not in the engine) so
    argparse can use it before jax is imported."""
    if value is None or value.lower() in ("", "none"):
        return None
    if value.lower() == "adaptive":
        return "adaptive"
    try:
        return int(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected an int, 'none' or 'adaptive', got {value!r}")


_DEV_COUNT_FLAG = "--xla_force_host_platform_device_count"


def merge_xla_flags(flags: str, n: int) -> str:
    """Merge ``--xla_force_host_platform_device_count=n`` into an
    existing ``XLA_FLAGS`` string, preserving every other flag.

    A pre-existing device-count flag is *raised* to ``n`` when it is
    lower (a CI env block pinning count=2 must not silently break a
    --tp 4 run) and kept verbatim when it already covers ``n`` (the
    user asked for more simulated devices than we need - fine)."""
    parts = flags.split()
    for i, part in enumerate(parts):
        if part.startswith(_DEV_COUNT_FLAG + "="):
            try:
                have = int(part.split("=", 1)[1])
            except ValueError:
                have = 0
            if have < n:
                parts[i] = f"{_DEV_COUNT_FLAG}={n}"
            return " ".join(parts)
    parts.append(f"{_DEV_COUNT_FLAG}={n}")
    return " ".join(parts)


def ensure_host_devices(tp: int) -> None:
    """Force at least ``tp`` simulated host devices for --tp runs.

    Must run before jax initializes, which is why this module (and
    benchmarks/serving.py, which imports this helper) defers ``import
    jax`` past argument parsing.  Other pre-existing ``XLA_FLAGS`` are
    preserved; a pre-existing device-count flag is raised to ``tp`` if
    too low and respected otherwise (see :func:`merge_xla_flags`).
    """
    import sys
    if tp <= 1 or "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = merge_xla_flags(flags, tp)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="concurrent decode slots")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=32,
                    help="decode tokens per request")
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests (paged mode; default 2x batch)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-budget", type=parse_prefill_budget,
                    default=None,
                    help="prefill token budget per engine step (chunked "
                         "prefill, Sarathi-style): an int, 'none' "
                         "(unbounded, the default) or 'adaptive' "
                         "(derived from the decode batch's SLA headroom)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared-prefix page reuse")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="max prompt-lookup draft tokens verified per "
                         "decode step (0 = no speculation)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus truncation (1.0 = disabled)")
    ap.add_argument("--repetition-penalty", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed (request i uses seed + i)")
    ap.add_argument("--n", type=int, default=1,
                    help="parallel samples per request (a sequence group "
                         "fans n branches out of one prefill over COW "
                         "forks; branch b samples under "
                         "branch_seed(seed, b))")
    ap.add_argument("--best-of", type=int, default=None,
                    help="branches sampled per request (>= n); the n "
                         "best by length-normalized cumulative logprob "
                         "are returned")
    ap.add_argument("--beam-width", type=int, default=0,
                    help="> 0: length-normalized beam search with this "
                         "many beams (deterministic; temperature must "
                         "stay 0; returns the n best hypotheses)")
    ap.add_argument("--length-penalty", type=float, default=1.0,
                    help="score = cum_logprob / len**length_penalty "
                         "(1.0 = mean logprob, 0 = raw sum)")
    ap.add_argument("--kv-codec", choices=("fp", "int8", "log16"),
                    default="fp",
                    help="paged KV page codec: 'fp' stores raw "
                         "compute-dtype rows, 'int8' per-row absmax "
                         "quantization with an f32 scale sidecar (~4x "
                         "fewer pool bytes/token), 'log16' 16-bit "
                         "log-domain rows on the HFA rail (2x)")
    ap.add_argument("--logprobs", action="store_true",
                    help="return per-token logprobs: prompt positions "
                         "(full-position LM head during prefill) and "
                         "every generated token")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards: KV-head-shard the paged "
                         "pools over a 'model' mesh axis (CPU simulates "
                         "the mesh via XLA_FLAGS="
                         "--xla_force_host_platform_device_count)")
    ap.add_argument("--dense", action="store_true",
                    help="legacy fixed-batch loop over a contiguous cache")
    args = ap.parse_args()
    if args.tp < 1:
        ap.error("--tp must be >= 1")
    if args.n < 1:
        ap.error("--n must be >= 1")
    if args.beam_width > 0 and args.temperature > 0:
        ap.error("beam search is deterministic: --temperature must be 0")
    if args.beam_width > 0 and args.best_of is not None:
        ap.error("--best-of is a parallel-sampling knob, incompatible "
                 "with --beam-width")
    width = args.beam_width or (args.best_of or args.n)
    if width > args.batch:
        ap.error(f"group width {width} exceeds --batch {args.batch}")
    ensure_host_devices(args.tp)

    import jax

    from repro.configs import get_config
    from repro.data import DataPipeline
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import build_model

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pipe = DataPipeline.for_config(cfg, args.prompt_len, args.batch)
    batch = pipe.batch(0)

    if args.dense or not _paged_supported(cfg):
        if args.tp > 1:
            raise SystemExit("--tp requires the paged serving path")
        if not args.dense:
            print(f"note: {cfg.name} (family={cfg.family}, "
                  f"pos_emb={cfg.pos_emb}) is not paged-servable yet; "
                  "falling back to the dense fixed-batch loop")
        _serve_dense(model, params, cfg, batch, args)
        return

    from repro.serving import Request, SamplingParams, ServingEngine

    mesh = None
    if args.tp > 1:
        from repro.launch.mesh import make_tp_mesh
        mesh = make_tp_mesh(args.tp)

    n_req = args.requests or 2 * args.batch
    prompts = np.concatenate(
        [pipe.batch(s)["tokens"] for s in range((n_req + args.batch - 1)
                                                // args.batch)])[:n_req]
    engine = ServingEngine(model, params, max_batch=args.batch,
                           page_size=args.page_size, max_seq=args.max_seq,
                           prefill_budget=args.prefill_budget,
                           prefix_caching=not args.no_prefix_cache,
                           spec_k=args.spec_k, mesh=mesh,
                           kv_codec=args.kv_codec)
    # one new arrival per step: requests join and leave mid-flight
    arrivals = [(i, Request(rid=i, prompt=prompts[i].tolist(),
                            max_new_tokens=args.steps,
                            sampling=SamplingParams(
                                temperature=args.temperature,
                                top_k=args.top_k, top_p=args.top_p,
                                repetition_penalty=args.repetition_penalty,
                                seed=args.seed + i),
                            n=args.n, best_of=args.best_of,
                            beam_width=args.beam_width,
                            length_penalty=args.length_penalty,
                            logprobs=args.logprobs))
                for i in range(n_req)]
    t0 = time.perf_counter()
    finished = engine.run(arrivals)
    dt = time.perf_counter() - t0
    engine.cache.check_invariants()
    st = engine.stats
    print(f"served {len(finished)} requests in {st['steps']} steps "
          f"({st['prefill_chunks']} prefill chunks, "
          f"{st['preemptions']} preemptions, page_size={args.page_size})")
    print(f"prefill: {st['prefill_tokens']} tokens computed, "
          f"{st['cached_prefill_tokens']} reused from prefix cache")
    print(f"generated {st['generated_tokens']} tokens in {dt:.2f} s "
          f"-> {st['generated_tokens']/dt:.1f} tok/s")
    print(f"kv codec {engine.kv_codec}: pool {engine.pool_bytes()} B, "
          f"{engine.bytes_per_token()} B/token")
    if args.logprobs:
        fr = finished[0]
        plp = [f"{x:+.2f}" if x is not None else "None"
               for x in (fr.prompt_logprobs or [])[:6]]
        tlp = [f"{x:+.2f}" for x in (fr.token_logprobs or [])[:6]]
        print(f"logprobs rid {fr.rid}: prompt {plp} tokens {tlp}")
    if args.tp > 1:
        print(f"tp={args.tp}: pool {engine.pool_bytes()} B total, "
              f"{engine.pool_bytes_per_shard()} B/shard; "
              f"ACC-merge triplet traffic {st['triplet_bytes']} B")
    if args.spec_k:
        rate = st["draft_accepted"] / max(st["draft_tokens"], 1)
        tps = st["decode_tokens"] / max(st["decode_slot_steps"], 1)
        print(f"speculation: {st['draft_accepted']}/{st['draft_tokens']} "
              f"drafts accepted ({rate:.0%}), "
              f"{tps:.2f} accepted tokens/step, "
              f"{st['rollbacks']} rollbacks")
    if st["groups"]:
        kind = f"beam-{args.beam_width}" if args.beam_width \
            else f"n={args.n}" + (f"/best-of-{args.best_of}"
                                  if args.best_of else "")
        print(f"sequence groups ({kind}): {st['groups']} groups, "
              f"{st['forks']} COW forks (zero KV copied at fork)")
        best = finished[0]
        if best.completions:
            for c in best.completions[:4]:
                print(f"  rid {best.rid} branch {c.branch} "
                      f"score {c.score:+.3f}: {c.tokens[:10]}")
    print("sample:", finished[0].tokens[:12])


def _paged_supported(cfg) -> bool:
    """Archs the paged engine can serve today: rope-positioned,
    attention-only stacks with token-only prompts (no Mamba per-slot
    state, no encoder cross caches, no patch/frame prefixes)."""
    return (cfg.pos_emb == "rope"
            and all(k == "attn" for k in cfg.layer_kinds())
            and cfg.family not in ("encdec", "vlm"))


def _serve_dense(model, params, cfg, batch, args):
    """Legacy path: one fixed batch, dense contiguous KV cache."""
    import jax
    import jax.numpy as jnp
    prompts = jnp.asarray(batch["tokens"])

    enc_out = None
    if cfg.family == "encdec":
        frames = jnp.asarray(batch["frames"])
        enc_out = model._encode(params, frames, jnp.float32)

    cache = model.init_cache(params, args.batch, args.max_seq,
                             enc_out=enc_out)
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)

    t0 = time.perf_counter()
    logits, cache = prefill(params, cache, prompts)
    jax.block_until_ready(logits)
    print(f"prefill: {1e3*(time.perf_counter()-t0):.1f} ms "
          f"({args.batch}x{args.prompt_len})")

    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    t0 = time.perf_counter()
    toks = []
    for _ in range(args.steps):
        toks.append(np.asarray(tok))
        logits, cache = decode(params, cache, tok)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    jax.block_until_ready(logits)
    dt = (time.perf_counter() - t0) / args.steps
    print(f"decode: {dt*1e3:.2f} ms/token; "
          f"throughput {args.batch/dt:.1f} tok/s")
    print("sample:", np.concatenate(toks, 1)[0][:12])


if __name__ == "__main__":
    main()
