"""Chip smoke: serve qwen3-1.7b at full width on a TPU, end to end.

  python chip_smoke.py              # one chip: the fa2 rail, then hfa
  python chip_smoke.py --chips 4    # four chips: tp 4 vs tp 1, and
                                    # four router replicas, one per chip

One process drives everything.  The model is the registered qwen3-1.7b
(28 layers, d_model 2048, 16/8 heads, d_head 128, vocab 151936, bf16)
with random weights from ``--seed``; the server is the stack
``repro.launch.serve_http`` builds (ServingEngine -> AsyncFrontend ->
HttpServer), driven over loopback HTTP on an ephemeral port.

One chip, for each attention rail (``fa2`` as registered, then the
paper's ``hfa``):

  * the engine's last-prompt-position logits (its own jitted prefill)
    against a dense float32 forward of the same weights
    (``model.apply`` with ``attn_impl="exact"``), relative RMS error at
    most ``LOGIT_TOL``;
  * a greedy, a seeded-sampled and a disconnected stream, then the
    greedy prompt again on a ``spec_k`` 2 server, which must repeat the
    greedy stream token for token (exact acceptance is lossless);
  * the compiled decode step holds the paged kernel's custom call;
  * no page leaked, and the page cache's invariants hold.

Four chips: ``--tp 4`` greedy and spec_k 2 streams equal to tp 1 on
chip 0, and four replicas behind the ``Router``, each with its params
and pools on its own chip (read from the arrays' ``.devices()``).

Shapes are fixed (one prompt length, an integer prefill budget, fixed
batch and max_seq) to keep the number of compiles small.  The last line
of output is the JSON result; without a TPU the script exits non-zero
and prints no result.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-1.7b"
PROMPT_LEN = 128            # every prompt: 8 pages of 16
NEW_TOKENS = 24
PAGE = 16
BATCH = 8
MAX_SEQ = 2048              # 16k-token pool, ~1.8 GiB at qwen3-1.7b widths
PREFILL_BUDGET = 512        # fixed: a prompt is always one chunk
# Logit error bound: relative RMS error ||engine - ref|| / ||ref|| over
# the real vocab, against a dense float32 forward with exact softmax.
# fa2 computes in bf16 (activations rounded to 8 mantissa bits at every
# layer boundary); hfa adds the paper's approximations - the FIX16 PWL
# exponent and the LogDiv finalize - whose per-layer bias compounds over
# depth.  A wrong kernel or layout scores about 1.4 (uncorrelated).
LOGIT_TOL = {"fa2": 0.05, "hfa": 0.35}
KERNEL = {1: "paged_flash_decode_partial", 3: "paged_verify_partial"}


class CompileLog:
    """Counts the executables JAX builds (compiles and persistent-cache
    loads alike) and the seconds they take, from jax.monitoring."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return self.count, self.seconds, self.cache_hits

    def since(self, mark) -> str:
        c, s, h = mark
        return (f"{self.count - c} executables in "
                f"{self.seconds - s:.1f} s ({self.cache_hits - h} from the "
                f"persistent cache)")


def prompts(cfg, seed: int, n: int) -> list[list[int]]:
    """n distinct random prompts of PROMPT_LEN real token ids."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, PROMPT_LEN).tolist()
            for _ in range(n)]


def reference_logits(cfg, params, prompt) -> "np.ndarray":
    """Last-position logits of a plain dense forward in float32 with
    exact softmax attention.  The bf16 weights stay in HBM; the layer
    scan upcasts one layer's weights at a time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import build_model
    ref = build_model(dataclasses.replace(
        cfg, attn_impl="exact", compute_dtype="float32"))
    logits, _ = jax.jit(ref.apply, static_argnames="train")(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, train=False)
    return np.asarray(logits[0, -1], np.float32)


def logit_error(got, ref, vocab: int) -> tuple[float, float]:
    """(relative RMS error, max |error| over max |ref|) on the real
    vocab; the first is the one held to ``LOGIT_TOL``."""
    import numpy as np
    d = got[:vocab] - ref[:vocab]
    ref = ref[:vocab]
    return (float(np.linalg.norm(d) / np.linalg.norm(ref)),
            float(np.max(np.abs(d)) / np.max(np.abs(ref))))


async def collect(server, payload) -> tuple[list[int], dict | None]:
    """Stream one request; returns (tokens, done event data)."""
    from repro.serving.http import stream_generate
    toks, done = [], None
    async for kind, data in stream_generate(server.host, server.port,
                                            payload):
        if kind == "token":
            toks.append(data["token"])
        elif kind == "done":
            done = data
        else:
            raise RuntimeError(f"stream error: {data}")
    return toks, done


def pool_check(engine, tag: str, fails: list) -> int:
    """Page-cache invariants and leaked pages after a drained server."""
    engine.cache.check_invariants()
    leaked = engine.cache.num_pages - engine.cache.available_page_count
    if leaked:
        fails.append(f"{tag}: {leaked} pages leaked")
    return leaked


def kernel_check(engine, tag: str, fails: list) -> None:
    """The compiled decode/verify step must run the paged kernel."""
    kw = engine.spec_k + 1
    text = engine.compiled_decode_text(PROMPT_LEN + NEW_TOKENS)
    ok = "tpu_custom_call" in text and KERNEL[kw] in text
    print(f"{tag}: compiled {'verify' if kw > 1 else 'decode'} step runs "
          f"{KERNEL[kw]} (tpu_custom_call): {ok}")
    if not ok:
        fails.append(f"{tag}: {KERNEL[kw]} missing from the compiled step")


async def serve_rail(model, params, ps, ref, log, tag: str) -> list[str]:
    """One rail on one chip: logits, HTTP streams, kernel, pool."""
    from repro.launch.serve_http import build_server
    from repro.serving.http import http_json, stream_generate
    fails: list[str] = []
    cfg = model.cfg
    mark = log.mark()
    server, frontends = build_server(
        model, params, batch=BATCH, page_size=PAGE, max_seq=MAX_SEQ,
        prefill_budget=PREFILL_BUDGET)
    engine = frontends[0].engine
    got = engine.prompt_logits(ps[0])
    err, err_max = logit_error(got, ref, cfg.vocab_size)
    print(f"{tag}: logit error vs dense f32 reference: relative RMS "
          f"{err:.6g} (tolerance {LOGIT_TOL[tag]}), max-abs relative "
          f"{err_max:.6g}")
    if not err <= LOGIT_TOL[tag]:
        fails.append(f"{tag}: logit error {err} > {LOGIT_TOL[tag]}")

    await server.start()
    try:
        greedy, done = await collect(server, {
            "prompt": ps[0], "max_new_tokens": NEW_TOKENS})
        if done is None or done["tokens"] != greedy or \
                len(greedy) != NEW_TOKENS:
            fails.append(f"{tag}: greedy stream {greedy} done={done}")
        sampled, done = await collect(server, {
            "prompt": ps[1], "max_new_tokens": NEW_TOKENS,
            "temperature": 0.8, "top_k": 50, "seed": 7})
        if done is None or len(sampled) != NEW_TOKENS:
            fails.append(f"{tag}: sampled stream {sampled} done={done}")
        gen = stream_generate(server.host, server.port,
                              {"prompt": ps[2], "max_new_tokens": 64})
        n_cut = 0
        async for kind, _ in gen:
            if kind == "token":
                n_cut += 1
                if n_cut >= 2:
                    break
        await gen.aclose()
        for _ in range(1000):
            if engine.stats["cancelled"] >= 1:
                break
            await asyncio.sleep(0.01)
        await frontends[0].drain()
        if engine.stats["cancelled"] < 1:
            fails.append(f"{tag}: the disconnect did not cancel")
        status, _ = await http_json(server.host, server.port, "GET",
                                    "/stats")
        if status != 200:
            fails.append(f"{tag}: /stats answered {status}")
    finally:
        await server.stop()
        for fe in frontends:
            await fe.close()
    leaked = pool_check(engine, tag, fails)
    kernel_check(engine, tag, fails)
    served = engine.stats["generated_tokens"]
    del engine, frontends, server

    # spec_k 2 server: the greedy prompt again, speculatively.
    server, frontends = build_server(
        model, params, batch=BATCH, page_size=PAGE, max_seq=MAX_SEQ,
        prefill_budget=PREFILL_BUDGET, spec_k=2)
    engine = frontends[0].engine
    await server.start()
    try:
        spec, _ = await collect(server, {"prompt": ps[0],
                                         "max_new_tokens": NEW_TOKENS})
        await frontends[0].drain()
    finally:
        await server.stop()
        for fe in frontends:
            await fe.close()
    st = engine.stats
    print(f"{tag}: spec_k 2 stream equals the greedy stream: "
          f"{spec == greedy} ({st['draft_accepted']}/{st['draft_tokens']} "
          f"drafts accepted)")
    if spec != greedy:
        fails.append(f"{tag}: spec_k 2 stream {spec} != greedy {greedy}")
    leaked += pool_check(engine, f"{tag} spec_k 2", fails)
    kernel_check(engine, f"{tag} spec_k 2", fails)
    served += st["generated_tokens"]
    print(f"{tag}: tokens served {served} over HTTP (greedy {len(greedy)}, "
          f"sampled {len(sampled)}, disconnected after {n_cut}, spec_k 2 "
          f"{len(spec)}); pages leaked {leaked}; check_invariants ok")
    print(f"{tag}: compile {log.since(mark)}")
    return fails


async def stream_tokens(model, params, prompts_, concurrent=False, **kw):
    """Greedy token streams of ``prompts_`` from a fresh server stack,
    one after another or all at once; returns (streams, engines)."""
    from repro.launch.serve_http import build_server
    server, frontends = build_server(model, params, batch=BATCH,
                                     page_size=PAGE, max_seq=512,
                                     prefill_budget=PREFILL_BUDGET, **kw)
    await server.start()
    try:
        reqs = [{"prompt": p, "max_new_tokens": NEW_TOKENS}
                for p in prompts_]
        if concurrent:
            out = await asyncio.gather(*(collect(server, r) for r in reqs))
        else:
            out = [await collect(server, r) for r in reqs]
        for fe in frontends:
            await fe.drain()
    finally:
        await server.stop()
        for fe in frontends:
            await fe.close()
    return [t for t, _ in out], [fe.engine for fe in frontends]


async def four_chips(model, params, ps, log) -> list[str]:
    """tp 4 vs tp 1 on chip 0 (greedy and spec_k 2), then four router
    replicas with their arrays on four different chips."""
    import jax
    fails: list[str] = []
    devs = jax.devices()
    for spec_k in (0, 2):
        mark = log.mark()
        one, engines = await stream_tokens(model, params, ps[:2],
                                           spec_k=spec_k)
        on = {d for leaf in jax.tree.leaves(engines[0].layers)
              for d in leaf.devices()}
        del engines
        four, engines = await stream_tokens(model, params, ps[:2],
                                            spec_k=spec_k, tp=4)
        eng = engines[0]
        pool_dev = {d for leaf in jax.tree.leaves(eng.layers)
                    for d in leaf.devices()}
        print(f"spec_k {spec_k}: tp 1 pools on {sorted(d.id for d in on)}, "
              f"tp 4 pools on {sorted(d.id for d in pool_dev)} "
              f"({eng.pool_bytes_per_shard()} of {eng.pool_bytes()} B per "
              f"chip); streams identical: {one == four}; "
              f"compile {log.since(mark)}")
        if one != four:
            fails.append(f"tp 4 spec_k {spec_k} streams {four} != tp 1 "
                         f"{one}")
        if len(pool_dev) != 4:
            fails.append(f"tp 4 pools on {pool_dev}")
        for e in engines:
            pool_check(e, f"tp 4 spec_k {spec_k}", fails)
        del engines, eng

    mark = log.mark()
    streams, engines = await stream_tokens(model, params, ps[:4],
                                           concurrent=True, replicas=4)
    for i, e in enumerate(engines):
        p_dev = {d for leaf in jax.tree.leaves(e.params)
                 for d in leaf.devices()}
        l_dev = {d for leaf in jax.tree.leaves(e.layers)
                 for d in leaf.devices()}
        print(f"replica {i}: params on {sorted(d.id for d in p_dev)}, "
              f"pools on {sorted(d.id for d in l_dev)}, "
              f"{e.stats['generated_tokens']} tokens, "
              f"{e.stats['steps']} steps")
        if e.stats["steps"] == 0:
            fails.append(f"replica {i} served nothing")
        if p_dev != {devs[i]} or l_dev != {devs[i]}:
            fails.append(f"replica {i} not on device {devs[i].id}: "
                         f"params {p_dev}, pools {l_dev}")
        pool_check(e, f"replica {i}", fails)
    if any(len(s) != NEW_TOKENS for s in streams):
        fails.append(f"router streams: {[len(s) for s in streams]}")
    print(f"router: {len(streams)} streams, "
          f"{sum(len(s) for s in streams)} tokens; "
          f"compile {log.since(mark)}")
    return fails


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} devices", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import build_model

    print(f"device: {devs[0].device_kind} ({devs[0].platform}) x "
          f"{len(devs)}; jax {jax.__version__}; compile cache "
          f"{enable_compile_cache()}")
    log = CompileLog()
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(args.seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, heads "
          f"{cfg.n_heads}/{cfg.n_kv_heads}, {n_params} params "
          f"({cfg.param_dtype}); init {time.perf_counter() - t0:.1f} s "
          f"(set-up)")
    ps = prompts(cfg, args.seed, 4)

    fails: list[str] = []
    if args.chips == 1:
        mark = log.mark()
        ref = reference_logits(cfg, params, ps[0])
        print(f"reference: dense f32 forward, compile {log.since(mark)}")
        for rail in ("fa2", "hfa"):
            t0 = time.perf_counter()
            model = build_model(dataclasses.replace(cfg, attn_impl=rail))
            fails += asyncio.run(serve_rail(model, params, ps, ref, log,
                                            rail))
            print(f"{rail}: phase {time.perf_counter() - t0:.1f} s "
                  f"(set-up and compile included)")
    else:
        fails += asyncio.run(four_chips(build_model(cfg), params, ps, log))

    for f in fails:
        print("FAIL:", f)
    if fails:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
