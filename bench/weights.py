"""The weights of a run, made by the benchmark from ``--seed``: on the
device, in one jitted call, in the dtype they are served in.

The tree has the layout the program's model takes, with the paths and
shapes the configuration's architecture module (``bench/archs/``)
gives: ``embed/emb``, the layer stacks with a leading layer axis,
``final_norm``, and ``lm_head`` where the embeddings are untied.  The
harness checks it against the program's own parameter shapes and dtypes
before serving, and the reference reads the same tree.  Rows of the
padded vocabulary are zero, so no padded id can be drawn.

The module's ``leaf(path, shape, m)`` gives each leaf's kind, fan-in and
dtype name (a key of ``DTYPES``), so a module whose program keeps a leaf
in another dtype than the rest (a router in float32) says so itself.
Fills by kind: ``matrix`` N(0, 1/fan_in); ``embed`` and ``head``
N(0, 0.02^2), the padded vocabulary's rows (axis 0 of ``embed``, axis 1
of ``head``) zero; ``norm`` 1 + N(0, 0.1^2); ``bias`` N(0, 0.1^2) -
every parameter is random, so a path that ignores one shows in the
comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def key(seed: int):
    """A PRNG key for any whole seed, including ones past 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _fill(k, kind: str, shape, dtype, fan_in, vocab: int):
    if kind == "norm":
        return (1.0 + 0.1 * jax.random.normal(k, shape)).astype(dtype)
    if kind == "bias":
        return (0.1 * jax.random.normal(k, shape)).astype(dtype)
    if kind in ("embed", "head"):
        w = 0.02 * jax.random.normal(k, shape, dtype)
        vocab_axis = 0 if kind == "embed" else 1
        real = jnp.arange(shape[vocab_axis]) < vocab
        real = real[:, None] if vocab_axis == 0 else real[None, :]
        return jnp.where(real, w, 0).astype(dtype)
    if kind == "matrix":
        return (jax.random.normal(k, shape, dtype)
                * fan_in ** -0.5).astype(dtype)
    raise ValueError(f"unknown leaf kind {kind!r}")


def make(arch, m: dict, padded_vocab: int, seed: int, device=None) -> dict:
    """The nested weight tree of architecture module ``arch``, made on
    the device in one jitted call."""
    s = arch.shapes(m, padded_vocab)

    def build(k):
        ks = jax.random.split(k, len(s))
        tree: dict = {}
        for kk, (path, shape) in zip(ks, sorted(s.items())):
            node = tree
            parts = path.split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            kind, fan_in, dtype = arch.leaf(path, shape, m)
            node[parts[-1]] = _fill(kk, kind, shape, DTYPES[dtype], fan_in,
                                    m["vocab_size"])
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
