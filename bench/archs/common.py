"""Pieces the architecture modules' plain references share, in float32
at the highest matmul precision: the matmul with the float8 control,
RMS norm, rotary embedding, and causal softmax attention with an
optional window, in full or in blocks of query rows.

``control=True`` rounds every matmul operand - weights and activations,
attention's included - to float8 (e4m3) under a per-tensor absmax
scale, with float32 accumulation: the step below the bfloat16 the
configurations state, which the comparison that decides ``correct``
must fail.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _q8(x, amax=None):
    """Round to float8 e4m3 under a per-tensor absmax scale; ``amax`` is
    the whole tensor's absmax where ``x`` is one part of it."""
    amax = jnp.max(jnp.abs(x)) if amax is None else amax
    s = jnp.maximum(amax, 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, control, a_max=None):
    """``einsum``; under ``control`` both operands in float8, ``a``
    scaled by ``a_max`` where it is given."""
    if control:
        a, b = _q8(a, a_max), _q8(b)
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


def _probs(q, k, q_pos, window, control, q_max):
    """Softmax weights (h, q, s) of queries at positions ``q_pos`` over
    every key row: causal, and with ``window`` only the newest ``window``
    rows (the query's own included)."""
    s = _mm("qhk,shk->hqs", q, k, control, q_max) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    k_pos = jnp.arange(k.shape[0])
    ok = k_pos[None] <= q_pos[:, None]
    if window is not None:
        ok = ok & (q_pos[:, None] - k_pos[None] < window)
    return jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)


def _scores_to_out(q, k, v, q_pos, window, control, q_max=None, p_max=None):
    p = _probs(q, k, q_pos, window, control, q_max)
    return _mm("hqs,shk->qhk", p, v, control, p_max)


def attention(q, k, v, control, window=None, block=None):
    """Causal attention of ``q`` (T, h, d) over ``k``, ``v`` (T, h, d)
    (KV heads already repeated to the query heads).  ``block=None``
    materialises the whole (h, T, T) score matrix; a ``block`` that
    divides T computes the same in blocks of that many query rows, so
    only (h, block, T) scores live at once.  Under ``control`` the
    blocked form scales q and the probabilities per tensor, as the full
    form does: a first pass over the blocks finds the probabilities'
    largest."""
    t = q.shape[0]
    if block is None:
        return _scores_to_out(q, k, v, jnp.arange(t), window, control)
    n = t // block
    blocks = (jnp.arange(n), q.reshape(n, block, *q.shape[1:]))

    def pos(i):
        return i * block + jnp.arange(block)

    q_max = p_max = None
    if control:
        q_max = jnp.max(jnp.abs(q))
        p_max = jnp.max(jax.lax.map(
            lambda a: jnp.max(_probs(a[1], k, pos(a[0]), window, control,
                                     q_max)), blocks))
    out = jax.lax.map(
        lambda a: _scores_to_out(a[1], k, v, pos(a[0]), window, control,
                                 q_max, p_max), blocks)
    return out.reshape(q.shape)
