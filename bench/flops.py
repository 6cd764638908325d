"""Operations and bytes that the work needs, computed from shapes and live
context lengths - not from what a kernel happens to read.  A kernel that
walks padded pages does the same work by these counts, so its roofline
share shows the waste; one that skips them shows a higher share.

``arch`` is the configuration's architecture module (``bench/archs/``):
it gives each layer's attention window (``layer_windows``) and the
matrix parameters a token passes through (``layer_params``).  A layer
with window ``W`` attends at most the newest ``W`` rows of a query's
context, its own included; a layer without one attends all of it.
``m`` is the configuration's model section (published keys, see
``bench/configs``); KV and activations are counted in the served dtype.
"""
from __future__ import annotations

from collections import Counter

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(m: dict):
    h = m["num_attention_heads"]
    dh = m.get("head_dim") or m["hidden_size"] // h
    return h, m["num_key_value_heads"], dh, DTYPE_BYTES[m["torch_dtype"]]


def _windows(arch, m: dict) -> list:
    """(window, number of layers with it), in the order first seen."""
    return list(Counter(arch.layer_windows(m)).items())


def decode_attention(arch, m: dict, ctx_lens) -> tuple[float, float]:
    """(flops, bytes) of one decode step's paged attention, all layers:
    each slot's one query row attends its ``ctx`` rows (the new token
    included), or the window's where that is fewer.  FLOPs: q.k and
    p.v, 2 * d_head each, per query head and row.  Bytes: every attended
    K and V row once per KV head, plus q and the output."""
    h, hkv, dh, b = _dims(m)
    flops = byts = 0.0
    for window, layers in _windows(arch, m):
        rows = float(sum(ctx_lens) if window is None else
                     sum(min(n, window) for n in ctx_lens))
        flops += 4.0 * h * dh * rows * layers
        byts += layers * (2.0 * rows * hkv * dh * b
                          + len(ctx_lens) * 2.0 * h * dh * b)
    return flops, byts


def prefill_attention(arch, m: dict, chunks) -> tuple[float, float]:
    """(flops, bytes) of paged prefill attention for ``chunks`` of
    (start, length, final): query i of a chunk attends start + i + 1 rows
    (causal over the cached prefix and the chunk), or the window's where
    that is fewer; the K and V rows read are those some query of the
    chunk attends."""
    h, hkv, dh, b = _dims(m)
    flops = byts = 0.0
    for window, layers in _windows(arch, m):
        att = kv = q = 0.0
        for start, c, _ in chunks:
            if window is None:
                att += c * start + c * (c + 1) / 2.0
                kv += start + c
            else:
                att += sum(min(start + i + 1, window) for i in range(c))
                kv += min(start + c, window + c - 1)
            q += c
        flops += 4.0 * h * dh * att * layers
        byts += layers * (2.0 * kv * hkv * dh * b + 2.0 * q * h * dh * b)
    return flops, byts


def head_params(m: dict) -> float:
    return float(m["hidden_size"] * m["vocab_size"])


def step_flops(arch, m: dict, decode_ctx, prefill_chunks) -> float:
    """Model FLOPs of one engine step: 2 per matrix parameter per token
    processed, the LM head only where a next token is drawn (every decode
    row and the last position of a prompt's final chunk), plus
    attention.  ``prefill_chunks`` holds (start, length, final)."""
    n_dec = len(decode_ctx)
    n_pre = sum(c for _, c, _ in prefill_chunks)
    n_head = n_dec + sum(1 for _, _, final in prefill_chunks if final)
    f = 2.0 * arch.layer_params(m) * (n_dec + n_pre)
    f += 2.0 * head_params(m) * n_head
    f += decode_attention(arch, m, decode_ctx)[0]
    f += prefill_attention(arch, m, prefill_chunks)[0]
    return f


def roofline_share(flops: float, byts: float, seconds: float,
                   peak: dict) -> tuple[float, str]:
    """Least time the chip could take (the larger of flops over peak
    FLOP/s and bytes over peak bytes/s) over the measured time, in %, and
    which bound it."""
    tf = flops / peak["bf16_flops"]
    tb = byts / peak["hbm_bytes_per_s"]
    return 100.0 * max(tf, tb) / seconds, "compute" if tf >= tb else "memory"
