"""The plain reference: the forward pass of a Qwen-style decoder written
from the published architecture in straightforward ``jax.numpy``, in
float32 at the highest matmul precision, with exact softmax attention.
It imports nothing of the program and reads only the benchmark's own
weight tree (``bench/weights.py``).

  x = embed[tokens]
  per layer:  h = rms(x) * g1
              q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)    per head
              q, k = rms(q) * gq, rms(k) * gk                 (qk_norm)
              q, k = rope(q), rope(k)                         (half split)
              x += softmax(q k^T / sqrt(d_head), causal) v Wo
              h = rms(x) * g2
              x += (silu(h Wg) * (h Wu)) Wd
  logits = (rms(x) * gf) W_head        (W_head = embed^T when tied)

``control=True`` computes the same in float8 (e4m3): every matmul
operand - weights and activations, attention's included - is rounded to
e4m3 under a per-tensor absmax scale, with float32 accumulation.  That
is the step below the bfloat16 the configurations state, the one a
later change could be tempted to take; the comparison that decides
``correct`` must fail it.

The layers run in one ``lax.scan``, each upcasting only its own weights;
the sequence is padded to a multiple of ``PAD`` (causal attention
leaves the real positions untouched) and the logit rows to a multiple of
``ROWS``, so a few compiled programs cover every request.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 1024      # sequence rows padded to a multiple of this
ROWS = 256      # logit rows computed, a multiple of this
E4M3_MAX = 448.0


def _q8(x):
    """Round to float8 e4m3 under a per-tensor absmax scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, control):
    if control:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * g


def _rope(x, theta):
    """Rotary embedding, rotate-half form, positions 0..T-1."""
    t, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("arch", "control"))
def _hidden(w, tokens, arch, control):
    """Final-norm hidden states (T, d) of one padded token row."""
    qk_norm, qkv_bias, theta, eps = arch
    f32 = jnp.float32
    x = jnp.take(w["embed"]["emb"], tokens, axis=0).astype(f32)
    t = tokens.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))

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
        s = _mm("qhk,shk->hqs", q, k, control) / jnp.sqrt(f32(q.shape[-1]))
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = _mm("hqs,shk->qhk", p, v, control)
        x = x + _mm("thk,hkd->td", o, a["wo"], control)
        f = lw["ffn"]
        h = _rms(x, lw["norm2"]["scale"], eps)
        g = _mm("td,df->tf", h, f["wg"], control)
        u = _mm("td,df->tf", h, f["wu"], control)
        x = x + _mm("tf,fd->td", jax.nn.silu(g) * u, f["wd"], control)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"]["l0"])
    return _rms(x, w["final_norm"]["scale"].astype(f32), eps)


@functools.partial(jax.jit, static_argnames=("rows", "vocab", "control"))
def _head(w, hid, first, rows, vocab, control):
    """Logits of hidden rows first .. first + rows - 1, real vocab only."""
    h = jax.lax.dynamic_slice_in_dim(hid, first, rows)
    head = w["lm_head"] if "lm_head" in w else w["embed"]["emb"].T
    return _mm("td,dv->tv", h, head[:, :vocab].astype(jnp.float32), control)


@jax.jit
def _served_rows(ref, ids):
    got = jnp.take_along_axis(ref, ids[:, None], axis=1)[:, 0]
    return jnp.max(ref, axis=1) - got


@jax.jit
def _top_rows(ref, ctl):
    top = jnp.argmax(ctl, axis=1)
    got = jnp.take_along_axis(ref, top[:, None], axis=1)[:, 0]
    return jnp.max(ref, axis=1) - got


def gaps(w: dict, m: dict, prompt: list[int], served: list[int],
         control: bool = False) -> tuple[np.ndarray, np.ndarray | None]:
    """Per served token, the gap by which its reference logit lies below
    the reference's best at its position (0 where it is the reference's
    argmax, inf where it is no token of the vocabulary); with
    ``control``, the same gaps for the tokens the control puts first at
    those positions.  The reference reads ``prompt`` and the served
    tokens, teacher-forced."""
    toks = prompt + served[:-1]
    first, n, vocab = len(prompt) - 1, len(served), m["vocab_size"]
    rows = -(-n // ROWS) * ROWS
    row = np.zeros(-(-(first + rows) // PAD) * PAD, np.int32)
    row[:len(toks)] = toks
    arch = (bool(m["qk_norm"]), bool(m["qkv_bias"]), float(m["rope_theta"]),
            float(m["rms_norm_eps"]))
    at = jnp.int32(first)
    ref = _head(w, _hidden(w, row, arch, False), at, rows, vocab, False)
    ids = np.zeros(rows, np.int32)
    ids[:n] = np.minimum(served, vocab - 1)
    gap = np.asarray(_served_rows(ref, ids))[:n].astype(np.float64)
    gap[np.asarray(served) >= vocab] = np.inf
    if not control:
        return gap, None
    ctl = _head(w, _hidden(w, row, arch, True), at, rows, vocab, True)
    return gap, np.asarray(_top_rows(ref, ctl))[:n].astype(np.float64)
