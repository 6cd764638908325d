"""Flash-decode Pallas kernel: one new token vs a long KV cache.

Maps the paper's multi-KV-block FAU architecture (Fig. 2) onto decode:

  * GQA grouping: the G query heads that share one KV head become the MXU
    rows, so the score matmul is (G x d) @ (d x block_kv) instead of a
    degenerate (1 x d) vector op.
  * The kernel streams KV blocks with the Alg. 2 online update and returns
    the *partial triplet* (m, l, o~) - unnormalized - exactly like a block
    FAU.  The caller (a single host, or shard_map across devices holding a
    sequence-sharded cache) merges triplets with the log-domain ACC rule
    (Eq. 16) and applies LogDiv.  The cross-device merge is the paper's
    cascaded ACC pipeline promoted to the cluster interconnect.
  * ``use_hfa`` switches the exponential terms to the FIX16-quantized
    PWL/bit-pack datapath (no transcendental exp).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import bitmath

NEG_INF = -1e30
LANES = 128


def _decode_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, block_kv: int, kv_len: int, use_hfa: bool):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # (G, d)
    k = k_ref[0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0].astype(jnp.float32)            # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kv_ids = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kv_ids < kv_len
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    if use_hfa:
        alpha = bitmath.exp2_hfa_rail(
            bitmath.quant_rail(jnp.minimum(m_prev - m_new, 0.0)))
        p = bitmath.exp2_hfa_rail(bitmath.quant_rail(s - m_new[:, None]))
    else:
        alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask & (m_new != NEG_INF)[:, None], p, 0.0)

    l_scr[:, 0] = l_scr[:, 0] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[:, 0] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)
        m_ref[0, :, 0] = m_scr[:, 0]
        l_ref[0, :, 0] = l_scr[:, 0]


def decode_partial_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    interpret: bool,
    scale: float | None = None,
    block_kv: int = 128,
    kv_len: int | None = None,
    use_hfa: bool = False,
):
    """Partial decode attention.

    Args:
      q: (BHkv, G, d) - grouped queries (G = q_heads per kv_head).
      k, v: (BHkv, S, d) local KV shard.
    Returns:
      (o~, m, l): o~ (BHkv, G, d) unnormalized f32 output accumulator,
      m/l (BHkv, G) running max / sum-of-exps - a block-FAU triplet.
    """
    bh, g, d = q.shape
    _, s_len, _ = k.shape
    assert s_len % block_kv == 0, (s_len, block_kv)
    scale_v = (1.0 / d ** 0.5) if scale is None else scale
    kv_len = s_len if kv_len is None else kv_len

    grid = (bh, s_len // block_kv)
    kernel = functools.partial(_decode_kernel, scale=scale_v,
                               block_kv=block_kv, kv_len=kv_len,
                               use_hfa=use_hfa)
    o, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, d), lambda b, ik: (b, 0, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, ik: (b, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, g, d), lambda b, ik: (b, 0, 0)),
            pl.BlockSpec((1, g, 1), lambda b, ik: (b, 0, 0)),
            pl.BlockSpec((1, g, 1), lambda b, ik: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, g, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, g, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode_partial",
    )(q, k, v)
    return o, m[..., 0], l[..., 0]


def merge_partials(
    o_parts: jax.Array,   # (P, ..., d)
    m_parts: jax.Array,   # (P, ...)
    l_parts: jax.Array,   # (P, ...)
    *,
    use_hfa: bool = False,
):
    """Eq. (1)/(16): merge P block-FAU triplets (ACC cascade, vectorized).

    With ``use_hfa`` the rescale factors go through the FIX16 quantized
    log-domain path (the ACC unit of Fig. 4); the adds stay in float (on
    TPU the cross-block adds ride the VPU; the LNS adder is an ASIC win).
    """
    m_n = jnp.max(m_parts, axis=0)
    dm = jnp.minimum(m_parts - m_n[None], 0.0)
    if use_hfa:
        w = bitmath.exp2_hfa_rail(bitmath.quant_rail(dm))
    else:
        w = jnp.exp(dm)
    l_n = jnp.sum(l_parts * w, axis=0)
    o_n = jnp.sum(o_parts * w[..., None], axis=0)
    return o_n, m_n, l_n


def finalize_decode(o_acc: jax.Array, l: jax.Array, *, use_hfa: bool = False):
    """Final normalization: float divide (FA-2) or LogDiv (H-FA)."""
    safe = jnp.where(l <= 0.0, 1.0, l)
    if use_hfa:
        recip = bitmath.recip_logdiv(safe)
        recip = jnp.where(l <= 0.0, 0.0, recip)
        return o_acc * recip[..., None]
    return jnp.where((l <= 0.0)[..., None], 0.0, o_acc / safe[..., None])
