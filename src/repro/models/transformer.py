"""Block assembly + scan-over-layers for every assigned family.

A *block* = mixer (attention | mamba) + FFN (dense | moe | none), pre-norm
residual.  Layers are stacked along a leading "layers" axis and executed
with ``lax.scan`` over *periods*: the repeating pattern unit (1 layer for
homogeneous stacks, 8 for Jamba's 1:7 hybrid period).  Scanning keeps the
compiled HLO O(period) instead of O(depth) - essential for the 512-device
dry-run compiles - and is the standard PP-ready layout.

Caches: attention layers carry (k, v) rings; mamba layers carry
(ssm, conv_*) states; whisper decoder layers add precomputed cross (k, v).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import layers as L
from repro.models import mamba2, moe
from repro.parallel.sharding import constrain


def _norm_init(cfg, dtype):
    if cfg.norm_type == "layernorm":
        return L.layernorm_init(cfg.d_model, dtype)
    return L.rmsnorm_init(cfg.d_model, dtype)


def _norm_apply(cfg, p, x):
    if cfg.norm_type == "layernorm":
        return L.layernorm_apply(p, x, cfg.norm_eps)
    return L.rmsnorm_apply(p, x, cfg.norm_eps)


def _ffn_init(key, cfg, kind, dtype):
    if kind == "moe":
        return moe.moe_init(key, cfg, dtype)
    if kind == "dense":
        if cfg.mlp_type == "gelu":
            return L.gelu_mlp_init(key, cfg.d_model, cfg.d_ff, dtype)
        return L.swiglu_init(key, cfg.d_model, cfg.d_ff, dtype)
    return {}, {}


def _ffn_apply(p, x, cfg, kind, page_state=None, expert_group=None):
    if kind == "moe" and page_state is not None:
        return moe.moe_serve(p, x, cfg, page_state.get("token_valid"),
                             group=expert_group)
    if kind == "moe":
        return moe.moe_apply(p, x, cfg)
    if kind == "dense":
        if cfg.mlp_type == "gelu":
            return L.gelu_mlp_apply(p, x), {}
        return L.swiglu_apply(p, x), {}
    return jnp.zeros_like(x), {}


# ------------------------------------------------------------------ block
def block_init(key, cfg, kind: str, ffn_kind: str, dtype, cross: bool = False):
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {}
    l: dict[str, Any] = {}
    p["norm1"], l["norm1"] = _norm_init(cfg, dtype)
    if kind == "attn":
        p["mixer"], l["mixer"] = L.attention_init(ks[0], cfg, dtype)
    else:
        p["mixer"], l["mixer"] = mamba2.mamba_init(ks[0], cfg, dtype)
    if cross:
        p["norm_x"], l["norm_x"] = _norm_init(cfg, dtype)
        p["cross"], l["cross"] = L.attention_init(ks[1], cfg, dtype, cross=True)
    if ffn_kind != "none":
        p["norm2"], l["norm2"] = _norm_init(cfg, dtype)
        p["ffn"], l["ffn"] = _ffn_init(ks[2], cfg, ffn_kind, dtype)
    return p, l


def block_apply(p, x, cfg, *, kind: str, ffn_kind: str,
                positions=None, cache=None, cache_pos=None,
                enc_cache=None, causal: bool = True, page_state=None,
                attn=None, expert_group=None):
    """Returns (x, new_cache, aux_losses).  ``attn`` is the layer's
    :class:`repro.configs.base.AttnKind` (None: the config's);
    ``expert_group``: the expert weights are the whole stack, this
    layer at that index (``moe.moe_serve``)."""
    aux: dict[str, jax.Array] = {}
    h = _norm_apply(cfg, p["norm1"], x)
    if kind == "attn":
        if cache is None:
            attn_cache = None
        elif "k_pages" in cache:
            # Codec pools carry per-page scale sidecars next to the data
            # pools; they ride the same per-layer cache dict.
            attn_cache = {key: cache[key]
                          for key in ("k_pages", "v_pages",
                                      "k_scale", "v_scale")
                          if key in cache}
        else:
            attn_cache = {"k": cache["k"], "v": cache["v"]}
        y, new_attn_cache = L.attention_apply(
            p["mixer"], h, cfg, positions=positions, cache=attn_cache,
            cache_pos=cache_pos, causal=causal, page_state=page_state,
            attn=attn)
        new_cache = dict(cache) if cache is not None else None
        if new_attn_cache is not None and new_cache is not None:
            new_cache.update(new_attn_cache)
    else:
        y, new_state = mamba2.mamba_apply(
            p["mixer"], h, cfg, state=cache)
        new_cache = new_state if cache is not None else None
    x = x + y

    if "cross" in p and enc_cache is not None:
        hx = _norm_apply(cfg, p["norm_x"], x)
        y = _cross_attention(p["cross"], hx, cfg, enc_cache)
        x = x + y

    if ffn_kind != "none":
        h = _norm_apply(cfg, p["norm2"], x)
        y, aux = _ffn_apply(p["ffn"], h, cfg, ffn_kind, page_state,
                            expert_group)
        x = x + y
    x = constrain(x, ("batch", "seq", "embed"))
    return x, new_cache, aux


def _cross_attention(p, x, cfg, enc_cache):
    """Cross-attention against precomputed encoder (k, v)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
    out = kops.multihead_attention(q, enc_cache["ck"].astype(x.dtype),
                                   enc_cache["cv"].astype(x.dtype),
                                   impl=cfg.attn_impl, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def cross_kv(p, enc_out, cfg):
    """Precompute cross-attention (k, v) from encoder output (serve path)."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(enc_out.dtype))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(enc_out.dtype)
        v = v + p["bv"].astype(enc_out.dtype)
    return {"ck": k, "cv": v}


# ------------------------------------------------------------------ stack
def period_pattern(cfg) -> tuple[list[str], list[str], int]:
    """(mixer kinds, ffn kinds, period length).  The period also repeats
    the layers' attention kinds (``cfg.layer_attn``), so each position
    of it has one static window and RoPE."""
    kinds = cfg.layer_kinds()
    ffns = cfg.ffn_kinds()
    attns = [cfg.layer_attn(i) for i in range(cfg.n_layers)]
    n = cfg.n_layers
    for p in range(1, n + 1):
        if n % p:
            continue
        if (kinds == kinds[:p] * (n // p)) and (ffns == ffns[:p] * (n // p)) \
                and attns == attns[:p] * (n // p):
            return kinds[:p], ffns[:p], p
    return kinds, ffns, n


def stack_init(key, cfg, dtype, cross: bool = False):
    """Init all layers stacked by period: params[f'l{i}'] has leading
    (n_groups,) axis."""
    kinds, ffns, period = period_pattern(cfg)
    groups = cfg.n_layers // period

    def one_group(k):
        ks = jax.random.split(k, period)
        p, l = {}, {}
        for i in range(period):
            p[f"l{i}"], l[f"l{i}"] = block_init(
                ks[i], cfg, kinds[i], ffns[i], dtype, cross=cross)
        return p, l

    keys = jax.random.split(key, groups)
    p0, l0 = one_group(keys[0])
    if groups == 1:
        stacked = jax.tree.map(lambda a: a[None], p0)
    else:
        stacked = jax.vmap(lambda k: one_group(k)[0])(keys)
    logical = jax.tree.map(
        lambda axes: ("layers",) + axes,
        l0, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    return stacked, logical


def stack_apply(params, x, cfg, *, positions=None, caches=None,
                cache_pos=None, enc_caches=None, causal=True,
                dropout_rng=None, page_state=None):
    """Scan over layer groups. caches/enc_caches are stacked (groups, ...).

    ``page_state`` ({"page_table", "seq_lens"}, shared by every layer) is
    closed over rather than scanned - all layers of one step read the
    same tables.

    Returns (x, new_caches, aux_sum).  On the paged path an expert
    layer's aux is its counters (``moe.moe_serve``), summed over layers.
    """
    kinds, ffns, period = period_pattern(cfg)
    experts = None
    if page_state is not None and "moe" in ffns and cfg.scan_layers:
        # The expert kernels read each group's weights in place from
        # the whole stack: a scanned slice would be copied out for them.
        experts, params = _split_experts(params, ffns)

    def body(carry, scanned):
        x, aux_acc = carry
        gp, gcache, genc = scanned[:3]
        group = scanned[3] if experts is not None else None
        new_gcache = {} if gcache is not None else None
        for i in range(period):
            cache_i = gcache[f"l{i}"] if gcache is not None else None
            enc_i = genc[f"l{i}"] if genc is not None else None
            gp_i = gp[f"l{i}"]
            if experts is not None and f"l{i}" in experts:
                gp_i = dict(gp_i, ffn=dict(gp_i["ffn"], **experts[f"l{i}"]))
            x, nc, aux = block_apply(
                gp_i, x, cfg, kind=kinds[i], ffn_kind=ffns[i],
                positions=positions, cache=cache_i, cache_pos=cache_pos,
                enc_cache=enc_i, causal=causal, page_state=page_state,
                attn=cfg.layer_attn(i), expert_group=group)
            if new_gcache is not None:
                new_gcache[f"l{i}"] = nc
            for k, v in aux.items():
                aux_acc[k] = aux_acc.get(k, 0.0) + v
        return (x, aux_acc), new_gcache

    if cfg.remat != "none":
        body = jax.checkpoint(body, prevent_cse=False)

    init_aux = {"load_balance": jnp.float32(0.0), "router_z": jnp.float32(0.0)}
    if page_state is not None and "moe" in ffns:
        init_aux.update(moe_rows_local=jnp.int32(0),
                        moe_experts_active=jnp.int32(0))
    if cfg.scan_layers:
        xs = (params, caches, enc_caches)
        if experts is not None:
            xs += (jnp.arange(cfg.n_layers // period, dtype=jnp.int32),)
        (x, aux), new_caches = jax.lax.scan(body, (x, init_aux), xs)
        return x, new_caches, aux

    # Unrolled execution (used by the dry-run cost probes: while-loop bodies
    # are counted once by HLO cost analysis, so probes unroll instead).
    groups = jax.tree.leaves(params)[0].shape[0]
    carry = (x, init_aux)
    outs = []
    for g in range(groups):
        take = lambda t: (None if t is None
                          else jax.tree.map(lambda a: a[g], t))
        carry, yc = body(carry, (take(params), take(caches),
                                 take(enc_caches)))
        outs.append(yc)
    x, aux = carry
    new_caches = None
    if outs and outs[0] is not None:
        new_caches = jax.tree.map(lambda *a: jnp.stack(a), *outs)
    return x, new_caches, aux


def _split_experts(params, ffns):
    """(each expert layer's stacked wg/wu/wd by period position, the
    params without them)."""
    experts, rest = {}, dict(params)
    for i, kind in enumerate(ffns):
        if kind == "moe":
            ffn = dict(rest[f"l{i}"]["ffn"])
            experts[f"l{i}"] = {k: ffn.pop(k) for k in ("wg", "wu", "wd")}
            rest[f"l{i}"] = dict(rest[f"l{i}"], ffn=ffn)
    return experts, rest


def stack_init_paged_cache(cfg, num_pages: int, page_size: int, dtype,
                           codec: str = "fp"):
    """Paged block-pool caches, stacked (groups, P, Hkv, page, dh).

    One shared pool per layer; sequences address it through the
    engine-owned page table, so no per-slot ``max_seq`` is reserved.
    Attention-only stacks for now (Mamba/hybrid state is per-slot and
    dense; cross caches are tied to a fixed batch).

    ``codec`` selects the page codec (:mod:`repro.kernels.page_codec`):
    the data pools take the codec's storage dtype, and codecs with
    scales get f32 sidecar pools "k_scale"/"v_scale" of the same rank
    with trailing dim 1 - rank-matched so every page-table mechanism
    (scatter writers, COW copies, gathers, head sharding) treats scale
    leaves exactly like data leaves.
    """
    from repro.kernels import page_codec
    kinds, _, period = period_pattern(cfg)
    groups = cfg.n_layers // period
    assert all(k == "attn" for k in kinds), (
        "paged KV cache supports attention-only stacks, got %r" % (kinds,))
    c = page_codec.get_codec(codec)
    sdt = c.storage_dtype(dtype)

    def one_layer():
        shape = (groups, num_pages, cfg.n_kv_heads, page_size, cfg.d_head)
        leaves = {"k_pages": jnp.zeros(shape, sdt),
                  "v_pages": jnp.zeros(shape, sdt)}
        if c.has_scales:
            sshape = shape[:-1] + (1,)
            leaves["k_scale"] = jnp.zeros(sshape, jnp.float32)
            leaves["v_scale"] = jnp.zeros(sshape, jnp.float32)
        return leaves

    return {f"l{i}": one_layer() for i in range(period)}


def stack_init_cache(cfg, batch: int, max_seq: int, dtype, cross: bool = False,
                     enc_out=None, params=None):
    """Build stacked caches (groups-leading axis) for decode."""
    kinds, ffns, period = period_pattern(cfg)
    groups = cfg.n_layers // period

    def one_layer_cache(i):
        if kinds[i] == "attn":
            c = {
                "k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.d_head),
                               dtype),
                "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.d_head),
                               dtype),
            }
        else:
            c = mamba2.mamba_init_state(cfg, batch, dtype)
        return c

    def stack_leaf(i):
        c = one_layer_cache(i)
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (groups,) + a.shape), c)

    caches = {f"l{i}": stack_leaf(i) for i in range(period)}

    enc_caches = None
    if cross and enc_out is not None and params is not None:
        def group_cross(gp):
            return {f"l{i}": cross_kv(gp[f"l{i}"]["cross"], enc_out, cfg)
                    for i in range(period)}
        enc_caches = jax.vmap(group_cross, in_axes=0)(params) if groups > 1 \
            else jax.tree.map(lambda a: a[None], group_cross(
                jax.tree.map(lambda a: a[0], params)))
    return caches, enc_caches
