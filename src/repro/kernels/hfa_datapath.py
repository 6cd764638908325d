"""Datapath-faithful H-FA Pallas kernel: per-element FIX16 LNS accumulation.

This kernel is the direct transcription of the paper's FAU (Fig. 3): it
streams keys one-by-one inside the kernel and keeps the fused accumulator
O = [l, o] as (sign, raw) LNS state in VMEM, using exactly the
:mod:`repro.core.lns` operations (quant -> Blinn -> Mitchell add -> LogDiv).
It exists to prove the hardware spec is implementable as a kernel and to
pin the semantics: tests assert *exact* rail equality against the
``core.hfa`` emulation.

It is validated in interpret mode; on a real TPU it would be VPU-bound and
slower than ``hfa.py`` (the MXU-compatible kernel) - that trade-off is the
central hardware-adaptation point discussed in DESIGN.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lns
from repro.core.numerics import LOG_ZERO

NEG_INF = -1e30


def _datapath_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
                     causal: bool, kv_len: int, q_offset: int):
    """Whole-row FAU: streams every key for one (batch*head) slice."""
    lq, d = q_ref.shape[1], q_ref.shape[2]
    lkv = k_ref.shape[1]

    q = q_ref[0].astype(jnp.float32)
    # Scores for the full row in BF16 (the FP half of the datapath).
    s_all = jax.lax.dot_general(
        q, k_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    s_all = s_all.astype(jnp.bfloat16).astype(jnp.float32)   # (lq, lkv)

    kv_ids = jax.lax.broadcasted_iota(jnp.int32, (lq, lkv), 1)
    valid = kv_ids < kv_len
    if causal:
        q_ids = q_offset + jax.lax.broadcasted_iota(jnp.int32, (lq, lkv), 0)
        valid = valid & (kv_ids <= q_ids)

    def step(i, carry):
        m_prev, sgn_prev, raw_prev = carry
        s_i = jax.lax.dynamic_slice(s_all, (0, i), (lq, 1))[:, 0]
        valid_i = jax.lax.dynamic_slice(valid, (0, i), (lq, 1))[:, 0]
        v_i = jax.lax.dynamic_slice(v_ref[0], (i, 0), (1, d))[0]
        v_i = v_i.astype(jnp.bfloat16)

        m_new = jnp.maximum(m_prev, s_i)
        live = valid_i & (m_new > NEG_INF / 2)

        q_dm = lns.quant_scorediff(m_prev - m_new)
        q_ds = lns.quant_scorediff(s_i - m_new)

        a_raw = lns.clamp_rail(raw_prev + q_dm[:, None])
        a_raw = jnp.where(raw_prev <= LOG_ZERO, float(LOG_ZERO), a_raw)

        ones = jnp.ones((1,), jnp.bfloat16)
        v_ext = jnp.concatenate([ones, v_i], axis=0)          # (d+1,)
        sgn_v, raw_v = lns.blinn_log2(v_ext)
        b_raw = lns.clamp_rail(raw_v[None, :] + q_ds[:, None])
        b_raw = jnp.where(raw_v[None, :] <= LOG_ZERO, float(LOG_ZERO), b_raw)
        sgn_b = jnp.broadcast_to(sgn_v[None, :], sgn_prev.shape)
        b_raw = jnp.broadcast_to(b_raw, raw_prev.shape)

        sgn_new, raw_new = lns.lns_add(sgn_prev, a_raw, sgn_b, b_raw)

        keep = ~live
        m_out = jnp.where(keep, m_prev, m_new)
        sgn_out = jnp.where(keep[:, None], sgn_prev, sgn_new)
        raw_out = jnp.where(keep[:, None], raw_prev, raw_new)
        return m_out, sgn_out, raw_out

    init = (
        jnp.full((lq,), NEG_INF, jnp.float32),
        jnp.zeros((lq, d + 1), jnp.int32),
        jnp.full((lq, d + 1), float(LOG_ZERO), jnp.float32),
    )
    m, sgn, raw = jax.lax.fori_loop(0, lkv, step, init)

    # LogDiv (Eq. 15) + inverse Blinn (Eq. 22).
    raw_l = raw[:, :1]
    sgn_l = sgn[:, :1]
    raw_attn = lns.clamp_rail(raw[:, 1:] - raw_l)
    sgn_attn = jnp.bitwise_xor(sgn[:, 1:], sgn_l)
    empty = (raw_l <= LOG_ZERO) | (raw[:, 1:] <= LOG_ZERO)
    raw_attn = jnp.where(empty, float(LOG_ZERO), raw_attn)
    o_ref[0] = lns.lns_to_bf16(sgn_attn, raw_attn).astype(o_ref.dtype)


def hfa_datapath_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    interpret: bool,
    causal: bool = False,
    scale: float | None = None,
    kv_len: int | None = None,
) -> jax.Array:
    """Per-element LNS H-FA over (BH, Lq, d); returns BF16 attention."""
    bh, lq, d = q.shape
    _, lkv, _ = k.shape
    scale_v = (1.0 / d ** 0.5) if scale is None else scale
    kv_len = lkv if kv_len is None else kv_len
    q_offset = lkv - lq

    kernel = functools.partial(_datapath_kernel, scale=scale_v,
                               causal=causal, kv_len=kv_len,
                               q_offset=q_offset)
    return pl.pallas_call(
        kernel,
        grid=(bh,),
        in_specs=[
            pl.BlockSpec((1, lq, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, lkv, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, lkv, d), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, lq, d), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="hfa_datapath",
    )(q, k, v)
