"""The weights of a run, made by the benchmark from ``--seed``: on the
device, in one jitted call, in the dtype they are served in.

The tree has the layout the program's model takes (``embed``, the
layer stack ``layers/l0/...`` with a leading layer axis, ``final_norm``,
and ``lm_head`` where the embeddings are untied); the harness checks it
against the program's own parameter shapes before serving.  The
reference reads the same tree.  Rows of the padded vocabulary are zero,
so no padded id can be drawn.

Scales: matrices N(0, 1/fan_in), embeddings and head N(0, 0.02^2), norm
scales 1 + N(0, 0.1^2), biases N(0, 0.1^2) - every parameter is random,
so a path that ignores one shows in the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def dims(m: dict) -> dict:
    h = m["num_attention_heads"]
    return {"d": m["hidden_size"], "h": h, "hkv": m["num_key_value_heads"],
            "dh": m.get("head_dim") or m["hidden_size"] // h,
            "ff": m["intermediate_size"], "L": m["num_hidden_layers"],
            "V": m["vocab_size"]}


def key(seed: int):
    """A PRNG key for any whole seed, including ones past 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


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


def _fill(k, path: str, shape, m: dict, dtype):
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "scale" or leaf.endswith("_norm"):
        return (1.0 + 0.1 * jax.random.normal(k, shape)).astype(dtype)
    if leaf.startswith("b"):
        return (0.1 * jax.random.normal(k, shape)).astype(dtype)
    if path in ("embed/emb", "lm_head"):
        w = 0.02 * jax.random.normal(k, shape, dtype)
        vocab_axis = 0 if path == "embed/emb" else 1
        real = jnp.arange(shape[vocab_axis]) < m["vocab_size"]
        real = real[:, None] if vocab_axis == 0 else real[None, :]
        return jnp.where(real, w, 0).astype(dtype)
    fan_in = shape[1] if leaf != "wo" else shape[1] * shape[2]
    return (jax.random.normal(k, shape, dtype) * fan_in ** -0.5).astype(dtype)


def make(m: dict, padded_vocab: int, seed: int, device=None) -> dict:
    """The nested weight tree, made on the device in one jitted call."""
    s = shapes(m, padded_vocab)
    dtype = DTYPES[m["torch_dtype"]]

    def build(k):
        ks = jax.random.split(k, len(s))
        tree: dict = {}
        for kk, (path, shape) in zip(ks, sorted(s.items())):
            node = tree
            parts = path.split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = _fill(kk, path, shape, m, dtype)
        return tree

    with jax.default_device(device or jax.devices()[0]):
        return jax.jit(build)(key(seed))


def flat(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flat(v, p))
        else:
            out[p] = v
    return out
