"""FlashAttention-2 backward Pallas kernels.

Standard two-kernel FA-2 backward (Dao 2023), TPU-tiled:

  * forward saves the per-row logsumexp L = m + ln(l)  (``return_lse``);
  * ``delta = rowsum(do * o)`` is computed outside (one fused elementwise);
  * dq kernel: grid (bh, q_blocks, kv_blocks), accumulates
      ds = p * (do . v^T - delta),   dq += ds . k * scale
  * dkv kernel: grid (bh, kv_blocks, q_blocks), accumulates
      dv += p^T . do,   dk += ds^T . q * scale

Both recompute p = exp(s - L) on the fly (no (Lq x Lkv) residuals), with
the same iota-based causal/padding masks as the forward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
LANES = 128


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc, *, scale, causal, block_q, block_kv, kv_len, q_offset):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    q_start = iq * block_q + q_offset
    k_start = ik * block_kv

    def _visit():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kv_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kv_ids < kv_len
        if causal:
            q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (kv_ids <= q_ids)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, :, 0][:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, 0][:, None])
        acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_visit)
    else:
        _visit()

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0] = acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, acck, accv, *,
                scale, causal, block_q, block_kv, kv_len, q_offset):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        acck[...] = jnp.zeros_like(acck)
        accv[...] = jnp.zeros_like(accv)

    q_start = iq * block_q + q_offset
    k_start = ik * block_kv

    def _visit():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kv_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kv_ids < kv_len
        if causal:
            q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (kv_ids <= q_ids)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, :, 0][:, None]), 0.0)
        accv[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, 0][:, None])
        acck[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        # This kv block only sees q rows at or below the diagonal.
        pl.when(q_start + block_q - 1 >= k_start)(_visit)
    else:
        _visit()

    @pl.when(iq == nq - 1)
    def _done():
        dk_ref[0] = acck[...].astype(dk_ref.dtype)
        dv_ref[0] = accv[...].astype(dv_ref.dtype)


def fa2_backward(q, k, v, o, do, lse, *, interpret, causal=False,
                 scale=None, block_q=128, block_kv=128, kv_len=None,
                 q_offset=None):
    """Returns (dq, dk, dv) for the padded (bh, lq, d)/(bh, lkv, d) tiles."""
    bh, lq, d = q.shape
    _, lkv, _ = k.shape
    scale_v = (1.0 / d ** 0.5) if scale is None else scale
    kv_len = lkv if kv_len is None else kv_len
    q_offset = (lkv - lq) if q_offset is None else q_offset

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)              # (bh, lq, 1)
    lse3 = lse[..., None]                                 # (bh, lq, 1)

    common = dict(scale=scale_v, causal=causal, block_q=block_q,
                  block_kv=block_kv, kv_len=kv_len, q_offset=q_offset)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(bh, lq // block_q, lkv // block_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, iq, ik: (b, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="fa2_bwd_dq",
    )(q, k, v, do, lse3, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(bh, lkv // block_kv, lq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, ik, iq: (b, iq, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, ik, iq: (b, ik, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, ik, iq: (b, ik, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, ik, iq: (b, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, ik, iq: (b, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, ik, iq: (b, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, d), lambda b, ik, iq: (b, ik, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, ik, iq: (b, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lkv, d), k.dtype),
            jax.ShapeDtypeStruct((bh, lkv, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="fa2_bwd_dkv",
    )(q, k, v, do, lse3, delta)
    return dq, dk, dv
