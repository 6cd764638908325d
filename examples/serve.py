"""Serving driver: batched prefill + decode with the H-FA decode path.

Loads the checkpoint written by examples/train_lm.py (or initializes fresh
weights) and serves a batch of prompts: one prefill, then greedy decode,
reporting per-token latency.  With --kv-split N it also demonstrates the
paper's multi-KV-block decode: the cache is split into N spans, partial
FAU triplets are merged with the log-domain ACC rule (Eq. 16).

Run:  PYTHONPATH=src python examples/serve.py [--tokens 32] [--kv-split 4]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import ModelConfig
from repro.data import DataPipeline
from repro.kernels import decode as dk
from repro.models.model import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--kv-split", type=int, default=4)
    args = ap.parse_args()

    cfg = ModelConfig(
        name="train-lm-100m", n_layers=12, d_model=768, n_heads=12,
        n_kv_heads=4, d_head=64, d_ff=2048, vocab_size=16384,
        vocab_pad_multiple=128, attn_impl="hfa_pallas", max_seq=256)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    try:
        mgr = CheckpointManager(args.ckpt)
        carry = {"params": params}
        restored, step = mgr.restore_latest(
            {"params": params}, None)
        params = restored["params"]
        print(f"restored checkpoint at step {step}")
    except Exception:
        print("no checkpoint found - serving random weights")

    pipe = DataPipeline.for_config(cfg, 64, args.batch, seed=123)
    prompts = jnp.asarray(pipe.batch(0)["tokens"][:, :48])

    decode_step = jax.jit(model.decode_step)
    cache = model.init_cache(params, args.batch, max_seq=128)
    t0 = time.perf_counter()
    logits, cache = jax.jit(model.prefill)(params, cache, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]

    out_tokens = []
    t0 = time.perf_counter()
    for _ in range(args.tokens):
        out_tokens.append(np.asarray(tok))
        logits, cache = decode_step(params, cache, tok)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    jax.block_until_ready(logits)
    dt = (time.perf_counter() - t0) / args.tokens
    gen = np.concatenate(out_tokens, axis=1)
    print(f"prefill({prompts.shape[1]} toks x {args.batch} seqs): "
          f"{t_prefill*1e3:.1f} ms")
    print(f"decode: {dt*1e3:.2f} ms/token (batch {args.batch})")
    print("generated ids (first seq):", gen[0][:16], "...")

    # --- paper Fig. 2 demo: KV split + log-domain ACC merge -------------
    rng = np.random.default_rng(0)
    g, s, d = 8, 1024, 64
    q = jnp.asarray(rng.standard_normal((2, g, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((2, s, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((2, s, d)), jnp.bfloat16)
    span = s // args.kv_split
    parts = [dk.decode_partial_pallas(q, k[:, i*span:(i+1)*span],
                                      v[:, i*span:(i+1)*span], use_hfa=True,
                                      interpret=True)
             for i in range(args.kv_split)]
    om, mm, lm = dk.merge_partials(
        jnp.stack([p[0] for p in parts]), jnp.stack([p[1] for p in parts]),
        jnp.stack([p[2] for p in parts]), use_hfa=True)
    merged = dk.finalize_decode(om, lm, use_hfa=True)
    from repro.core import reference
    gold = reference.exact_attention(q, k, v)
    print(f"KV split x{args.kv_split} + H-FA ACC merge vs exact: "
          f"max|err| = {float(jnp.abs(merged - gold).max()):.4f}")


if __name__ == "__main__":
    main()
