"""The Mellum 2 decoder (JetBrains Mellum2-12B-A2.5B): GQA attention whose
layers come in kinds - ``sliding_attention`` layers attend the newest
``sliding_window`` rows with plain RoPE, ``full_attention`` layers the
whole context with YaRN - and a sparse expert FFN in every layer.

  x = embed[tokens]
  per layer:  h = rms(x) * g1
              q, k, v = h Wq, h Wk, h Wv                       per head
              q, k = rope_kind(q), rope_kind(k)                (half split)
              x += softmax(q k^T / sqrt(d_head), causal [, window]) v Wo
              h = rms(x) * g2
              p = softmax(h Wr)         float32, over every router output
              top-k of p, renormalised over the k (norm_topk_prob)
              x += sum over the chosen experts e this chip holds of
                   p_e * (silu(h Wg_e) * (h Wu_e)) Wd_e
  logits = (rms(x) * gf) W_head                               (untied)

A window layer's query at position i attends rows j with
i - window < j <= i (transformers' sliding-window mask).  YaRN follows
transformers' ``_compute_yarn_parameters``: correction dims floored and
ceiled, a linear ramp between ``inv_freq / factor`` and ``inv_freq``,
cos and sin scaled by ``attention_factor``.

The chip's share (the configuration's ``deployment``): the router keeps
its published width (``router_experts``) and top-k over every output;
the layer adds only the part its held experts give, experts
``first_expert`` .. ``first_expert + num_experts - 1``.

The reference is float32 at the highest matmul precision.  Attention is
computed in blocks of query rows; a window layer's block reads only the
band of keys its window reaches.  Each held expert runs on its routed
rows alone, a chunk of rows at a time, with no capacity.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from archs.common import HIGHEST, _mm, _q8, _rms, attention

BLOCK = 512         # query rows per attention block
ROWS = 256          # routed rows per expert matmul in the reference


def dims(m: dict) -> dict:
    return {"d": m["hidden_size"], "h": m["num_attention_heads"],
            "hkv": m["num_key_value_heads"], "dh": m["head_dim"],
            "ff": m["moe_intermediate_size"], "E": m["num_experts"],
            "k": m["num_experts_per_tok"], "L": m["num_hidden_layers"],
            "V": m["vocab_size"]}


def period(m: dict) -> list:
    """The layer kinds of one period of ``layer_types``."""
    kinds = m["layer_types"]
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return kinds[:p]
    return kinds


# ---------------------------------------------------------------- program
def program_config(config: dict):
    """The program's ModelConfig: the registered architecture with every
    number, layer kind and the expert share set from the file."""
    from repro.configs import get_config
    from repro.configs.base import AttnKind, Yarn
    m, prog, eng = config["model"], config["program"], config["engine"]
    assert set(m["mlp_layer_types"]) == {"sparse"}, m["mlp_layer_types"]
    assert m["norm_topk_prob"], "the program renormalises the top-k gates"
    rope = m["rope_parameters"]
    sw, fa = rope["sliding_attention"], rope["full_attention"]
    assert sw["rope_type"] == "default" and fa["rope_type"] == "yarn"
    kinds = {
        "sliding_attention": AttnKind(window=m["sliding_window"],
                                      rope_theta=float(sw["rope_theta"])),
        "full_attention": AttnKind(rope_theta=float(fa["rope_theta"]),
                                   yarn=Yarn(
            factor=float(fa["factor"]),
            original=int(fa["original_max_position_embeddings"]),
            beta_fast=float(fa["beta_fast"]),
            beta_slow=float(fa["beta_slow"]),
            attention_factor=float(fa["attention_factor"])))}
    return dataclasses.replace(
        get_config(prog["arch"]), n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_head=m["head_dim"],
        d_ff=m["moe_intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=float(sw["rope_theta"]),
        attn_pattern=tuple(kinds[t] for t in period(m)),
        n_experts=m["num_experts"], n_router_experts=router_width(m),
        expert_offset=m["first_expert"],
        moe_top_k=m["num_experts_per_tok"],
        norm_eps=m["rms_norm_eps"], tie_embeddings=m["tie_word_embeddings"],
        qk_norm=False, qkv_bias=m["attention_bias"],
        attn_impl=prog["attn_impl"], param_dtype=m["torch_dtype"],
        compute_dtype=m["torch_dtype"], max_seq=eng["max_seq"])


# ---------------------------------------------------------------- weights
def shapes(m: dict, padded_vocab: int) -> dict:
    """{path: shape} of the tree: one stack per position of the period,
    its leading axis the periods."""
    x = dims(m)
    d, h, hkv, dh, ff, e = x["d"], x["h"], x["hkv"], x["dh"], x["ff"], x["E"]
    per = period(m)
    g = x["L"] // len(per)
    s = {"embed/emb": (padded_vocab, d), "final_norm/scale": (d,),
         "lm_head": (d, padded_vocab)}
    for i in range(len(per)):
        p = f"layers/l{i}/"
        s.update({p + "norm1/scale": (g, d),
                  p + "mixer/wq": (g, d, h, dh),
                  p + "mixer/wk": (g, d, hkv, dh),
                  p + "mixer/wv": (g, d, hkv, dh),
                  p + "mixer/wo": (g, h, dh, d),
                  p + "norm2/scale": (g, d),
                  p + "ffn/router": (g, d, router_width(m)),
                  p + "ffn/wg": (g, e, d, ff),
                  p + "ffn/wu": (g, e, d, ff),
                  p + "ffn/wd": (g, e, ff, d)})
    return s


def router_width(m: dict) -> int:
    """The router's outputs: every published expert (``num_experts``
    counts those held here)."""
    return m["router_experts"]


def leaf(path: str, shape, m: dict) -> tuple:
    """(kind, fan-in, dtype): the router in float32 (the program keeps
    it so), every other leaf in the configuration's dtype; a matrix's
    fan-in is the inputs summed into each output."""
    name, dtype = path.rsplit("/", 1)[-1], m["torch_dtype"]
    if name == "scale":
        return "norm", None, dtype
    if path in ("embed/emb", "lm_head"):
        return ("embed" if path == "embed/emb" else "head"), None, dtype
    if name == "router":
        return "matrix", shape[1], "float32"
    if name in ("wg", "wu", "wd"):
        return "matrix", shape[2], dtype
    return "matrix", (shape[1] * shape[2] if name == "wo" else shape[1]), \
        dtype


# -------------------------------------------------------------- reference
def _inv_freq(dh: int, rope):
    """Rotary inverse frequencies (dh/2,) and the cos/sin scale of a
    ``rope_parameters`` section (a dict or its items)."""
    rope = dict(rope)
    theta = float(rope["rope_theta"])
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    if rope["rope_type"] == "default":
        return inv, 1.0
    factor, orig = float(rope["factor"]), rope[
        "original_max_position_embeddings"]

    def corr(rot):
        return dh * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), dh - 1)
    high = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(dh // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    # Below the low dim the frequencies are kept, above the high one
    # divided by the factor.
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    return inv, float(rope["attention_factor"])


def _rope(x, rope: dict):
    """Rotary embedding, rotate-half form, positions 0..T-1."""
    t, _, dh = x.shape
    inv, scale = _inv_freq(dh, rope)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = (jnp.cos(ang) * scale)[:, None], (jnp.sin(ang) * scale)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _band(q, k, v, window: int, control: bool):
    """Windowed causal attention in blocks of ``BLOCK`` query rows, each
    reading only the keys its window reaches: rows i*B - P .. (i+1)*B - 1
    with P the window less one, rounded up to a block.  Under
    ``control`` q and the probabilities are scaled per tensor, as
    ``common.attention`` does."""
    t, _, dh = q.shape
    n = t // BLOCK
    pad = -(-(window - 1) // BLOCK) * BLOCK
    if control:
        # Keys and values in float8 under their whole tensor's scale,
        # before the bands are cut from them.
        k, v = _q8(k), _q8(v)
    kp = jnp.pad(k, ((pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((pad, 0), (0, 0), (0, 0)))

    def mm(spec, a, b, a_max):
        a = _q8(a, a_max) if control else a
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    def probs(i, qb, q_max):
        kb = jax.lax.dynamic_slice_in_dim(kp, i * BLOCK, pad + BLOCK)
        s = mm("qhk,shk->hqs", qb, kb, q_max) / jnp.sqrt(jnp.float32(dh))
        q_pos = i * BLOCK + jnp.arange(BLOCK)
        k_pos = i * BLOCK - pad + jnp.arange(pad + BLOCK)
        ok = (k_pos[None] >= 0) & (k_pos[None] <= q_pos[:, None]) & (
            q_pos[:, None] - k_pos[None] < window)
        return jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)

    blocks = (jnp.arange(n), q.reshape(n, BLOCK, *q.shape[1:]))
    q_max = p_max = None
    if control:
        q_max = jnp.max(jnp.abs(q))
        p_max = jnp.max(jax.lax.map(
            lambda a: jnp.max(probs(a[0], a[1], q_max)), blocks))

    def out(a):
        i, qb = a
        vb = jax.lax.dynamic_slice_in_dim(vp, i * BLOCK, pad + BLOCK)
        return mm("hqs,shk->qhk", probs(i, qb, q_max), vb, p_max)
    return jax.lax.map(out, blocks).reshape(q.shape)


def _experts(h, f, k: int, first: int, control: bool):
    """The held experts' part of the expert layer for rows ``h`` (T, d):
    route over every router output, top-k, renormalise; each held
    expert then runs on its routed rows alone, ``ROWS`` at a time."""
    t, d = h.shape
    e = f["wg"].shape[0]
    logits = _mm("td,de->te", h, f["router"].astype(jnp.float32), control)
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    routed = idx
    local = (idx - first).reshape(-1)
    held = (local >= 0) & (local < e)
    eid = jnp.where(held, local, e)
    order = jnp.argsort(eid, stable=True)
    tok = (jnp.arange(t * k) // k)[order]
    gate = gates.reshape(-1)[order]
    counts = jnp.zeros((e + 1,), jnp.int32).at[eid].add(1)
    starts = jnp.cumsum(counts) - counts
    h_max = jnp.max(jnp.abs(h)) if control else None

    def expert(j, out):
        wg, wu, wd = (f[n][j].astype(jnp.float32) for n in ("wg", "wu", "wd"))

        def chunk(c, out):
            r = c * ROWS + jnp.arange(ROWS)
            at = jnp.minimum(starts[j] + r, t * k - 1)
            x = h[tok[at]]
            g = _mm("rd,df->rf", x, wg, control, h_max)
            u = _mm("rd,df->rf", x, wu, control, h_max)
            y = _mm("rf,fd->rd", jax.nn.silu(g) * u, wd, control)
            w = jnp.where(r < counts[j], gate[at], 0.0)
            return out.at[tok[at]].add(w[:, None] * y)
        return jax.lax.fori_loop(0, (counts[j] + ROWS - 1) // ROWS, chunk,
                                 out)
    return jax.lax.fori_loop(0, e, expert, jnp.zeros_like(h)), routed


@functools.partial(jax.jit, static_argnames=("arch", "control"))
def _hidden(w, tokens, arch, control):
    """Final-norm hidden states (T, d) of one padded token row, and each
    layer's router choices (L, T, k)."""
    kinds, windows, ropes, eps, k, first = arch
    f32 = jnp.float32
    x = jnp.take(w["embed"]["emb"], tokens, axis=0).astype(f32)

    def layer(x, lw, i):
        a = jax.tree.map(lambda t: t.astype(f32), lw["mixer"])
        h = _rms(x, lw["norm1"]["scale"].astype(f32), eps)
        q = _rope(_mm("td,dhk->thk", h, a["wq"], control), ropes[i])
        kk = _rope(_mm("td,dhk->thk", h, a["wk"], control), ropes[i])
        v = _mm("td,dhk->thk", h, a["wv"], control)
        group = q.shape[1] // kk.shape[1]
        kk, v = jnp.repeat(kk, group, axis=1), jnp.repeat(v, group, axis=1)
        if windows[i] is None:
            o = attention(q, kk, v, control, block=BLOCK)
        else:
            o = _band(q, kk, v, windows[i], control)
        x = x + _mm("thk,hkd->td", o, a["wo"], control)
        h = _rms(x, lw["norm2"]["scale"].astype(f32), eps)
        y, routed = _experts(h, lw["ffn"], k, first, control)
        return x + y, routed

    def group(x, gw):
        routes = []
        for i in range(len(kinds)):
            x, r = layer(x, gw[f"l{i}"], i)
            routes.append(r)
        return x, jnp.stack(routes)

    x, routes = jax.lax.scan(group, x, w["layers"])
    return (_rms(x, w["final_norm"]["scale"].astype(f32), eps),
            routes.reshape(-1, *routes.shape[2:]))


def hidden(w, tokens, m: dict, control: bool):
    """Final-norm hidden states (T, d) of one padded token row."""
    return _forward(w, tokens, m, control)[0]


def routes(w, tokens, m: dict):
    """Each layer's router choices (L, T, k) for one padded token row:
    the experts, of every router output, each row chose."""
    return _forward(w, tokens, m, False)[1]


def _forward(w, tokens, m: dict, control: bool):
    assert len(tokens) % BLOCK == 0, len(tokens)
    kinds = tuple(period(m))
    rope = m["rope_parameters"]
    arch = (kinds,
            tuple(m["sliding_window"] if t == "sliding_attention" else None
                  for t in kinds),
            tuple(tuple(sorted(rope[t].items())) for t in kinds),
            float(m["rms_norm_eps"]), int(m["num_experts_per_tok"]),
            int(m["first_expert"]))
    return _hidden(w, tokens, arch, control)


# ------------------------------------------------------------------ work
def layer_windows(m: dict) -> list:
    """Per layer, ``sliding_window`` for a sliding layer, None for a full
    one."""
    return [m["sliding_window"] if t == "sliding_attention" else None
            for t in m["layer_types"]]


def held_per_token(m: dict) -> float:
    """Experts here a token is expected to reach: top-k times the held
    share of the router (an expectation, not a count)."""
    return m["num_experts_per_tok"] * m["num_experts"] / router_width(m)


def layer_params(m: dict) -> float:
    """Matrix parameters a token passes through: attention, the router,
    and the expected ``held_per_token`` experts."""
    x = dims(m)
    d, h, hkv, dh, ff = x["d"], x["h"], x["hkv"], x["dh"], x["ff"]
    attn = d * (h + 2 * hkv) * dh + h * dh * d
    return float(x["L"] * (attn + d * router_width(m)
                           + held_per_token(m) * 3 * d * ff))


def expert_work(m: dict, rows: float, pairs: float) -> tuple[float, float]:
    """(flops, bytes) of the held experts' work: ``rows`` (token, choice)
    rows computed and ``pairs`` (layer, expert) pairs with a row.  FLOPs
    2 * 3 * d * ff a row; bytes each active expert's three matrices once
    in the served dtype, and each row's input and output of d."""
    x = dims(m)
    d, ff = x["d"], x["ff"]
    b = 2 if m["torch_dtype"] in ("bfloat16", "float16") else 4
    return 6.0 * d * ff * rows, 3.0 * d * ff * b * pairs + 2.0 * d * b * rows
