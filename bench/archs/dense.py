"""The dense Qwen-style decoder: the architecture module a configuration
gets when its ``program`` section names no ``bench_arch``.

  x = embed[tokens]
  per layer:  h = rms(x) * g1
              q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)    per head
              q, k = rms(q) * gq, rms(k) * gk                 (qk_norm)
              q, k = rope(q), rope(k)                         (half split)
              x += softmax(q k^T / sqrt(d_head), causal) v Wo
              h = rms(x) * g2
              x += (silu(h Wg) * (h Wu)) Wd
  logits = (rms(x) * gf) W_head        (W_head = embed^T when tied)

Every layer attends its whole context (no window).  The reference's
layers run in one ``lax.scan``, each upcasting only its own weights,
and attend through the whole (h, T, T) score matrix
(``common.attention`` in its full form).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from archs.common import _mm, _rms, _rope, attention


def dims(m: dict) -> dict:
    h = m["num_attention_heads"]
    return {"d": m["hidden_size"], "h": h, "hkv": m["num_key_value_heads"],
            "dh": m.get("head_dim") or m["hidden_size"] // h,
            "ff": m["intermediate_size"], "L": m["num_hidden_layers"],
            "V": m["vocab_size"]}


# ---------------------------------------------------------------- program
def program_config(config: dict):
    """The program's ModelConfig for a configuration file: the registered
    architecture with every number set from the file's model keys."""
    from repro.configs import get_config
    m, prog, eng = config["model"], config["program"], config["engine"]
    h = m["num_attention_heads"]
    return dataclasses.replace(
        get_config(prog["arch"]), n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=h,
        n_kv_heads=m["num_key_value_heads"],
        d_head=m.get("head_dim") or m["hidden_size"] // h,
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=float(m["rope_theta"]), norm_eps=m["rms_norm_eps"],
        tie_embeddings=m["tie_word_embeddings"], qk_norm=m["qk_norm"],
        qkv_bias=m["qkv_bias"], attn_impl=prog["attn_impl"],
        param_dtype=m["torch_dtype"], compute_dtype=m["torch_dtype"],
        max_seq=eng["max_seq"])


# ---------------------------------------------------------------- weights
def shapes(m: dict, padded_vocab: int) -> dict:
    """{path: shape} of the tree (paths joined by '/')."""
    x = dims(m)
    d, h, hkv, dh, ff, L = x["d"], x["h"], x["hkv"], x["dh"], x["ff"], x["L"]
    s = {"embed/emb": (padded_vocab, d),
         "layers/l0/norm1/scale": (L, d),
         "layers/l0/mixer/wq": (L, d, h, dh),
         "layers/l0/mixer/wk": (L, d, hkv, dh),
         "layers/l0/mixer/wv": (L, d, hkv, dh),
         "layers/l0/mixer/wo": (L, h, dh, d),
         "layers/l0/norm2/scale": (L, d),
         "layers/l0/ffn/wg": (L, d, ff),
         "layers/l0/ffn/wu": (L, d, ff),
         "layers/l0/ffn/wd": (L, ff, d),
         "final_norm/scale": (d,)}
    if m["qkv_bias"]:
        s.update({"layers/l0/mixer/bq": (L, h, dh),
                  "layers/l0/mixer/bk": (L, hkv, dh),
                  "layers/l0/mixer/bv": (L, hkv, dh)})
    if m["qk_norm"]:
        s.update({"layers/l0/mixer/q_norm": (L, dh),
                  "layers/l0/mixer/k_norm": (L, dh)})
    if not m["tie_word_embeddings"]:
        s["lm_head"] = (d, padded_vocab)
    return s


def leaf(path: str, shape, m: dict) -> tuple:
    """(kind, fan-in, dtype) of a leaf (``bench/weights.py``): every leaf
    in the configuration's dtype; a matrix's fan-in is the inputs summed
    into each output of a stacked (L, in..., out...) matrix - the output
    projection sums heads and head rows."""
    name, dtype = path.rsplit("/", 1)[-1], m["torch_dtype"]
    if name == "scale" or name.endswith("_norm"):
        return "norm", None, dtype
    if name.startswith("b"):
        return "bias", None, dtype
    if path in ("embed/emb", "lm_head"):
        return ("embed" if path == "embed/emb" else "head"), None, dtype
    return "matrix", (shape[1] * shape[2] if path.endswith("/wo")
                      else shape[1]), dtype


# -------------------------------------------------------------- reference
@functools.partial(jax.jit, static_argnames=("arch", "control"))
def _hidden(w, tokens, arch, control):
    """Final-norm hidden states (T, d) of one padded token row."""
    qk_norm, qkv_bias, theta, eps = arch
    f32 = jnp.float32
    x = jnp.take(w["embed"]["emb"], tokens, axis=0).astype(f32)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(f32), lw)
        a = lw["mixer"]
        h = _rms(x, lw["norm1"]["scale"], eps)
        q = _mm("td,dhk->thk", h, a["wq"], control)
        k = _mm("td,dhk->thk", h, a["wk"], control)
        v = _mm("td,dhk->thk", h, a["wv"], control)
        if qkv_bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        if qk_norm:
            q, k = _rms(q, a["q_norm"], 1e-6), _rms(k, a["k_norm"], 1e-6)
        q, k = _rope(q, theta), _rope(k, theta)
        group = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        o = attention(q, k, v, control)
        x = x + _mm("thk,hkd->td", o, a["wo"], control)
        f = lw["ffn"]
        h = _rms(x, lw["norm2"]["scale"], eps)
        g = _mm("td,df->tf", h, f["wg"], control)
        u = _mm("td,df->tf", h, f["wu"], control)
        x = x + _mm("tf,fd->td", jax.nn.silu(g) * u, f["wd"], control)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"]["l0"])
    return _rms(x, w["final_norm"]["scale"].astype(f32), eps)


def hidden(w, tokens, m: dict, control: bool):
    """Final-norm hidden states (T, d) of one padded token row."""
    arch = (bool(m["qk_norm"]), bool(m["qkv_bias"]), float(m["rope_theta"]),
            float(m["rms_norm_eps"]))
    return _hidden(w, tokens, arch, control)


# ------------------------------------------------------------------ work
def layer_windows(m: dict) -> list:
    """Every layer attends its whole context."""
    return [None] * m["num_hidden_layers"]


def layer_params(m: dict) -> float:
    """Matrix parameters of the transformer layers (norms and biases are
    not matrix work)."""
    x = dims(m)
    d, h, hkv, dh, ff = x["d"], x["h"], x["hkv"], x["dh"], x["ff"]
    attn = d * (h + 2 * hkv) * dh + h * dh * d
    return float(x["L"] * (attn + 3 * d * ff))
