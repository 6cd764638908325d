"""Shared neural-net layers (pure JAX, no framework deps).

Every ``*_init`` returns ``(params, logical)`` - two parallel pytrees, the
second holding tuples of logical axis names consumed by
:mod:`repro.parallel.sharding`.  ``*_apply`` functions are pure.

Attention dispatches to the H-FA / FA-2 kernel stack via
:mod:`repro.kernels.ops` - the paper's contribution is a first-class layer
here, selected per-config with ``attn_impl``.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops


def _init_dense(key, shape, scale=None, dtype=jnp.float32):
    scale = 1.0 / math.sqrt(shape[0]) if scale is None else scale
    return jax.random.normal(key, shape, dtype) * scale


# ---------------------------------------------------------------- norms
def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}, {"scale": ("embed",)}


def rmsnorm_apply(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.float32):
    return ({"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
            {"scale": ("embed",), "bias": ("embed",)})


def layernorm_apply(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------- embeddings
def embedding_init(key, vocab: int, d: int, dtype=jnp.float32):
    w = jax.random.normal(key, (vocab, d), dtype) * 0.02
    return {"emb": w}, {"emb": ("vocab", "fsdp")}


def embedding_lookup(p, ids):
    return jnp.take(p["emb"], ids, axis=0)


def sinusoidal_pos(seq: int, d: int, offset=0) -> jax.Array:
    """Sinusoidal position embeddings; ``offset`` may be traced (decode)."""
    pos = (jnp.arange(seq, dtype=jnp.float32) + offset)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d)
    half = jnp.stack([jnp.sin(angle), jnp.cos(angle)], axis=-1)
    return half.reshape(seq, d)


# ---------------------------------------------------------------- RoPE
def yarn_inv_freq(dh: int, theta: float, yarn) -> tuple[np.ndarray, float]:
    """(inverse frequencies (dh/2,) f32, cos/sin scale) of YaRN, as
    transformers' ``_compute_yarn_parameters`` computes them: correction
    dims floored/ceiled, a linear ramp from ``inv_freq / factor`` (dims
    below the low one) to ``inv_freq`` (above the high one)."""
    def corr_dim(rot):
        return (dh * math.log(yarn.original / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), dh - 1)
    if low == high:
        high += 0.001
    pos_freqs = np.float32(theta) ** (np.arange(0, dh, 2, dtype=np.float32)
                                      / np.float32(dh))
    extra = np.float32(1.0) / pos_freqs
    inter = np.float32(1.0) / (np.float32(yarn.factor) * pos_freqs)
    ramp = np.clip((np.arange(dh // 2, dtype=np.float32) - low)
                   / np.float32(high - low), 0, 1).astype(np.float32)
    keep = np.float32(1.0) - ramp
    inv = inter * (np.float32(1.0) - keep) + extra * keep
    return inv.astype(np.float32), float(yarn.attention_factor)


def rope_apply(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
               yarn=None):
    """Rotary embedding. x: (B, S, H, dh), positions: (B, S) or (S,).
    ``yarn`` (a :class:`repro.configs.base.Yarn`) rescales the
    frequencies and scales cos and sin by its attention factor."""
    dh = x.shape[-1]
    half = dh // 2
    if yarn is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                 / half))
    else:
        inv, mscale = yarn_inv_freq(dh, theta, yarn)
        freqs = jnp.asarray(inv)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn is not None:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention
def attention_init(key, cfg, dtype=jnp.float32, cross: bool = False):
    """GQA attention params. cfg needs d_model, n_heads, n_kv_heads, d_head,
    qkv_bias, qk_norm."""
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init_dense(ks[0], (d, h, dh), 1.0 / math.sqrt(d), dtype),
        "wk": _init_dense(ks[1], (d, hkv, dh), 1.0 / math.sqrt(d), dtype),
        "wv": _init_dense(ks[2], (d, hkv, dh), 1.0 / math.sqrt(d), dtype),
        "wo": _init_dense(ks[3], (h, dh, d), 1.0 / math.sqrt(h * dh), dtype),
    }
    l = {
        "wq": ("fsdp", "heads", "head_dim"),
        "wk": ("fsdp", "kv_heads", "head_dim"),
        "wv": ("fsdp", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "fsdp"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, dh), dtype)
        p["bk"] = jnp.zeros((hkv, dh), dtype)
        p["bv"] = jnp.zeros((hkv, dh), dtype)
        l["bq"] = ("heads", "head_dim")
        l["bk"] = ("kv_heads", "head_dim")
        l["bv"] = ("kv_heads", "head_dim")
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
        l["q_norm"] = ("head_dim",)
        l["k_norm"] = ("head_dim",)
    return p, l


def _head_rmsnorm(scale, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def attention_apply(
    p,
    x: jax.Array,                    # (B, S, d_model)
    cfg,
    *,
    positions: jax.Array | None = None,
    kv_input: jax.Array | None = None,   # cross-attention source
    cache: dict[str, jax.Array] | None = None,
    cache_pos: jax.Array | int | None = None,
    causal: bool = True,
    attn_impl: str | None = None,
    page_state: dict[str, jax.Array] | None = None,
    attn=None,
):
    """Returns (out (B,S,d_model), new_cache).

    ``cache`` is either a dense ring {"k", "v"} or a paged block pool
    {"k_pages", "v_pages"}; the paged form additionally needs
    ``page_state`` = {"page_table" (B, J), "seq_lens" (B,)} from the
    serving engine (seq_lens[b] == 0 marks a free slot).

    ``attn`` (a :class:`repro.configs.base.AttnKind`, None: whole
    context, the config's RoPE) sets this layer's window and rotary
    embedding; a window runs on the paged path only.
    """
    window = None if attn is None else attn.window
    theta = cfg.rope_theta if attn is None or attn.rope_theta is None \
        else attn.rope_theta
    yarn = None if attn is None else attn.yarn
    paged = cache is not None and "k_pages" in cache and kv_input is None
    if window is not None and not paged:
        raise NotImplementedError(
            "windowed attention runs on the paged serving path only")
    b, s, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    impl = attn_impl or cfg.attn_impl
    src = x if kv_input is None else kv_input

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = _head_rmsnorm(p["q_norm"], q)
        k = _head_rmsnorm(p["k_norm"], k)
    if cfg.pos_emb == "rope" and kv_input is None:
        if positions is None:
            base = 0 if cache_pos is None else cache_pos
            positions = base + jnp.arange(s)
            if positions.ndim == 1:
                positions = jnp.broadcast_to(positions[None], (b, s))
        q = rope_apply(q, positions, theta, yarn)
        k = rope_apply(k, positions, theta, yarn)

    if paged:
        return _paged_attention(p, q, k, v, cfg, cache, page_state,
                                impl=impl, causal=causal, x_dtype=x.dtype,
                                window=window)

    new_cache = cache
    if cache is not None and kv_input is None:
        # Decode / incremental: write into the ring at cache_pos.
        pos = cache_pos if cache_pos is not None else 0
        if s == 1 and cfg.serve_attn == "shardmap_merge":
            # Paper's multi-KV-block ACC merge across the "model" axis:
            # local ring write + partial FAU + log-domain merge.
            from repro.parallel import collectives, sharding
            mesh = sharding._ACTIVE["mesh"]
            if mesh is not None and "model" in mesh.shape and \
                    cache["k"].shape[1] % mesh.shape["model"] == 0:
                out, ck, cv = collectives.shardmap_decode_attention(
                    q, k, v, cache["k"], cache["v"],
                    jnp.asarray(pos, jnp.int32), mesh=mesh,
                    use_hfa=impl.startswith("hfa"))
                out = jnp.einsum("bshk,hkd->bsd", out,
                                 p["wo"].astype(x.dtype))
                return out, {"k": ck, "v": cv}
        if s == 1:
            # Select-based write: elementwise, so it PRESERVES the cache's
            # sequence sharding (a dynamic-update-slice at a traced position
            # on a sharded dim makes the SPMD partitioner all-gather the
            # whole ring).  Costs a full cache rewrite in HBM bytes -
            # addressed by the shard_map local-write path in §Perf.
            hit = (jnp.arange(cache["k"].shape[1]) == pos)[None, :, None, None]
            ck = jnp.where(hit, k.astype(cache["k"].dtype), cache["k"])
            cv = jnp.where(hit, v.astype(cache["v"].dtype), cache["v"])
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
        new_cache = {"k": ck, "v": cv}
        if s == 1:
            out = kops.decode_attention(q, ck, cv, impl=_decode_impl(impl),
                                        kv_len=pos + 1)
            out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
            return out, new_cache
        # Fresh prefill (pos == 0): attend causally within the chunk itself;
        # the cache is storage only.  Continued chunked prefill (pos > 0)
        # must go through decode steps (documented limitation).

    out = kops.multihead_attention(q, k, v, impl=impl, causal=causal,
                                   block_q=cfg.attn_block,
                                   block_kv=cfg.attn_block)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, new_cache


def _paged_attention(p, q, k, v, cfg, cache, page_state, *, impl, causal,
                     x_dtype, window=None):
    """Attention against a paged block-pool KV cache (serving path).

    Decode: append the new token's K/V at seq_lens[b] through the page
    table, then run the paged decode kernel / jnp gather path over each
    slot's pages.  Chunked prefill (page_state carries "start_pos"):
    scatter the chunk's K/V at positions start_pos[b].. (padding rows
    dropped, so shared copy-on-write pages stay intact), then attend the
    chunk causally against everything materialized for its sequence -
    shared prefix pages, earlier chunks, and the chunk itself.  Legacy
    fresh prefill (no "start_pos": whole prompt at position 0 - a
    1-token prompt is still a prefill): the chunk attends causally to
    itself - the pages are storage only - and K/V land at positions
    0..S-1 of each row's page table; padded prefill tails are later
    masked by seq_lens, and are overwritten in place by later appends.

    Tensor parallel (page_state carries a "mesh" with a "model" axis of
    size > 1): the pools are KV-head-sharded over the mesh and every
    branch routes through the shard_map cascaded-ACC-merge path
    (:func:`repro.parallel.collectives.shardmap_paged_attention`) -
    each shard scatters/attends its local heads and only the tiny
    (m, l, o~) triplets cross the interconnect.

    ``window`` (static) limits every query to the newest ``window`` rows
    of its context; the kernels then walk only the pages in it.
    """
    from repro.kernels import page_codec
    from repro.kernels import paged_decode as paged_k
    from repro.kernels import paged_prefill as paged_pf_k
    assert page_state is not None, "paged cache requires page_state"
    pt = page_state["page_table"]
    mesh = page_state.get("mesh")
    codec = page_codec.get_codec(page_state.get("codec"))
    # The fp codec's read path is kept on codec=None so the raw-pool
    # kernels/fallbacks run byte-for-byte unchanged (fp stays bit-exact
    # to the pre-codec pool); encode_write is already the identity.
    rcodec = None if codec.name == "fp" else codec
    if mesh is not None and (mesh.shape.get("model", 1) > 1
                             or mesh.shape.get("data", 1) > 1):
        from repro.parallel import collectives
        if page_state.get("verify", False):
            mode, la, lb = ("verify", page_state["seq_lens"],
                            page_state["chunk_lens"])
        elif not page_state.get("prefill", False):
            sl = page_state["seq_lens"]
            mode, la, lb = "decode", sl, jnp.zeros_like(sl)
        elif "start_pos" in page_state:
            mode, la, lb = ("prefill", page_state["start_pos"],
                            page_state["chunk_lens"])
        else:
            # Legacy whole-prompt fresh prefill: positions 0..L-1, all
            # rows written in full (padded tails masked by seq_lens).
            b_, l_ = q.shape[0], q.shape[1]
            mode = "prefill"
            la = jnp.zeros((b_,), jnp.int32)
            lb = jnp.full((b_,), l_, jnp.int32)
        out, new_pools = collectives.shardmap_paged_attention(
            q, k, v, cache, pt, la, lb,
            mesh=mesh, mode=mode, impl=_decode_impl(impl), codec=codec,
            window=window)
    elif page_state.get("verify", False):
        # Speculative multi-token verify: scatter the K step tokens at
        # positions seq_lens[b].. (rows past chunk_lens are dropped, so
        # shared pages stay intact), then score all K positions in one
        # page-table walk.  K == 1 degenerates to the decode path.
        sl = page_state["seq_lens"]
        cl = page_state["chunk_lens"]
        new_pools = page_codec.encode_write(
            paged_pf_k.write_chunk_kv, codec, cache, k, v, pt, sl, cl)
        out = kops.paged_verify_attention(
            q, new_pools["k_pages"], new_pools["v_pages"], pt, sl, cl,
            impl=_decode_impl(impl), codec=rcodec,
            k_scales=new_pools.get("k_scale"),
            v_scales=new_pools.get("v_scale"), window=window)
    elif not page_state.get("prefill", False):
        sl = page_state["seq_lens"]
        new_pools = page_codec.encode_write(
            paged_k.append_kv, codec, cache, k, v, pt, sl)
        kv_lens = jnp.where(sl > 0, sl + 1, 0)
        out = kops.paged_decode_attention(
            q, new_pools["k_pages"], new_pools["v_pages"], pt, kv_lens,
            impl=_decode_impl(impl), codec=rcodec,
            k_scales=new_pools.get("k_scale"),
            v_scales=new_pools.get("v_scale"), window=window)
    elif "start_pos" in page_state or window is not None:
        # A whole-prompt fresh prefill with a window is the chunk at
        # position 0 of every row's full length.
        b_, l_ = q.shape[0], q.shape[1]
        sp = page_state.get("start_pos", jnp.zeros((b_,), jnp.int32))
        cl = page_state.get("chunk_lens", jnp.full((b_,), l_, jnp.int32))
        new_pools = page_codec.encode_write(
            paged_pf_k.write_chunk_kv, codec, cache, k, v, pt, sp, cl)
        out = kops.paged_prefill_attention(
            q, new_pools["k_pages"], new_pools["v_pages"], pt, sp, cl,
            impl=_decode_impl(impl), codec=rcodec,
            k_scales=new_pools.get("k_scale"),
            v_scales=new_pools.get("v_scale"), window=window)
    else:
        # Legacy fresh prefill: pages are storage only - attention runs
        # on the raw chunk, so the codec only affects later reads.
        new_pools = page_codec.encode_write(
            paged_k.write_prefill_kv, codec, cache, k, v, pt)
        out = kops.multihead_attention(q, k, v, impl=impl, causal=causal,
                                       block_q=cfg.attn_block,
                                       block_kv=cfg.attn_block)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x_dtype))
    return out, new_pools


def _decode_impl(impl: str) -> str:
    # Pallas prefill kernels pair with their decode counterparts.
    return {"fa2": "fa2", "exact": "fa2", "hfa": "hfa_pallas",
            "fa2_pallas": "fa2_pallas", "hfa_pallas": "hfa_pallas",
            "hfa_datapath": "hfa_pallas"}.get(impl, "fa2")


# ---------------------------------------------------------------- MLPs
def swiglu_init(key, d: int, ff: int, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {
        "wg": _init_dense(ks[0], (d, ff), dtype=dtype),
        "wu": _init_dense(ks[1], (d, ff), dtype=dtype),
        "wd": _init_dense(ks[2], (ff, d), dtype=dtype),
    }
    l = {"wg": ("fsdp", "mlp"), "wu": ("fsdp", "mlp"), "wd": ("mlp", "fsdp")}
    return p, l


def swiglu_apply(p, x):
    g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["wu"].astype(x.dtype))
    y = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("bsf,fd->bsd", y, p["wd"].astype(x.dtype))


def gelu_mlp_init(key, d: int, ff: int, dtype=jnp.float32):
    ks = jax.random.split(key, 2)
    p = {"wi": _init_dense(ks[0], (d, ff), dtype=dtype),
         "bi": jnp.zeros((ff,), dtype),
         "wo": _init_dense(ks[1], (ff, d), dtype=dtype),
         "bo": jnp.zeros((d,), dtype)}
    l = {"wi": ("fsdp", "mlp"), "bi": ("mlp",),
         "wo": ("mlp", "fsdp"), "bo": ("embed",)}
    return p, l


def gelu_mlp_apply(p, x):
    y = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype)) + p["bi"].astype(x.dtype)
    y = jax.nn.gelu(y.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", y, p["wo"].astype(x.dtype)) + p["bo"].astype(x.dtype)
