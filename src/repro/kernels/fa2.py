"""Baseline FlashAttention-2 Pallas TPU kernel (paper's 'FA-2' datapath).

Tiled per Alg. 2: grid (batch*heads, q_blocks, kv_blocks) with the KV axis
innermost/sequential; the running (m, l, acc) state lives in VMEM scratch
and is rescaled online (lines 4-6).  Block shapes are MXU-aligned
(multiples of 128 on the KV/lane dims; head_dim padded by the wrapper).

This kernel is the float reference datapath that H-FA is compared against,
matching the paper's hardware evaluation setup.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
LANES = 128


def _fa2_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, block_q: int, block_kv: int,
                kv_len: int, q_offset: int):
    """One (q_block, kv_block) step of Alg. 2."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q + q_offset          # global row of first query
    k_start = ik * block_kv                    # global col of first key

    def _visit():
        q = q_ref[0].astype(jnp.float32)       # (bq, d)
        k = k_ref[0].astype(jnp.float32)       # (bk, d)
        v = v_ref[0].astype(jnp.float32)       # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)

        # Mask: KV padding + (optionally) the causal triangle.
        kv_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kv_ids < kv_len
        if causal:
            q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (kv_ids <= q_ids)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask & (m_new != NEG_INF)[:, None], p, 0.0)

        l_scr[:, 0] = l_scr[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, 0] = m_new

    if causal:
        # Skip blocks strictly above the diagonal.
        pl.when(k_start <= q_start + block_q - 1)(_visit)
    else:
        _visit()

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)
        # logsumexp residual for the backward kernels
        lse_ref[0, :, 0] = m_scr[:, 0] + jnp.log(safe)


def fa2_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    interpret: bool,
    causal: bool = False,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    kv_len: int | None = None,
    q_offset: int | None = None,
    out_dtype=jnp.float32,
    return_lse: bool = False,
):
    """Tiled FA-2 over (BH, Lq, d) x (BH, Lkv, d) -> (BH, Lq, d).

    Lq/Lkv must be multiples of the block sizes (the ops.py wrapper pads).
    ``kv_len`` masks KV padding; ``q_offset`` is the global index of query
    row 0 (causal offset, = Lkv - Lq for suffix queries).  With
    ``return_lse`` also returns the per-row logsumexp (backward residual).
    """
    bh, lq, d = q.shape
    _, lkv, _ = k.shape
    assert lq % block_q == 0 and lkv % block_kv == 0, (lq, lkv)
    scale_v = (1.0 / d ** 0.5) if scale is None else scale
    kv_len = lkv if kv_len is None else kv_len
    q_offset = (lkv - lq) if q_offset is None else q_offset

    grid = (bh, lq // block_q, lkv // block_kv)
    kernel = functools.partial(
        _fa2_kernel, scale=scale_v, causal=causal, block_q=block_q,
        block_kv=block_kv, kv_len=kv_len, q_offset=q_offset)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, iq, ik: (b, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, iq, ik: (b, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, d), out_dtype),
            jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),   # m
            pltpu.VMEM((block_q, LANES), jnp.float32),   # l
            pltpu.VMEM((block_q, d), jnp.float32),       # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="fa2_fwd",
    )(q, k, v)
    if return_lse:
        return out, lse[..., 0]
    return out
