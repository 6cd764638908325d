"""H-FA Pallas TPU kernel: hybrid float/log FlashAttention (paper Sec. IV-V).

TPU-native adaptation of the H-FA datapath (see DESIGN.md):

  * scores ``s = qk^T`` stay in floating point on the MXU, rounded to BF16
    (the paper's dot-product unit is BF16);
  * the exponential terms 2^{quant[(m_prev-m)log2e]} and
    2^{quant[(s-m)log2e]} use the paper's FIX16 (9.7) quantization and the
    8-segment PWL + exponent-bit-packing - no transcendental exp anywhere;
  * the final softmax division is replaced by the LogDiv unit: Blinn
    forward log2 on l, rail negation, inverse Mitchell bit-pack - a
    division-free reciprocal;
  * ``P~ . V`` remains an MXU matmul: on TPU the per-element LNS adder of
    the ASIC cannot beat the systolic array, so the *accumulation* is kept
    in linear float while every exp/div is from the paper's log datapath.
    The per-element LNS datapath itself is validated separately in
    ``hfa_datapath.py``.

Error sources (quantization, Mitchell, PWL) are therefore the same three
as the paper's Table III, at tile granularity.
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


def _hfa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, block_q: int, block_kv: int,
                kv_len: int, q_offset: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q + q_offset
    k_start = ik * block_kv

    def _visit():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = s.astype(jnp.bfloat16).astype(jnp.float32)  # BF16 score datapath

        kv_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kv_ids < kv_len
        if causal:
            q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (kv_ids <= q_ids)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))

        # --- log-domain exponential terms (Eq. 14b/c): FIX16 quantization,
        # PWL 2^{-f}, exponent packing. No exp(), no exp2() calls.
        dm_rail = bitmath.quant_rail(jnp.minimum(m_prev - m_new, 0.0))
        alpha = bitmath.exp2_hfa_rail(dm_rail)               # (bq,)
        ds_rail = bitmath.quant_rail(s - m_new[:, None])
        p = bitmath.exp2_hfa_rail(ds_rail)                   # (bq, bk)
        p = jnp.where(mask & (m_new != NEG_INF)[:, None], p, 0.0)

        l_scr[:, 0] = l_scr[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, 0] = m_new

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_visit)
    else:
        _visit()

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:, 0]
        safe = jnp.where(l <= 0.0, 1.0, l)
        # LogDiv: division-free normalization via the log-domain reciprocal.
        recip = bitmath.recip_logdiv(safe)
        recip = jnp.where(l <= 0.0, 0.0, recip)
        o_ref[0] = (acc_scr[...] * recip[:, None]).astype(o_ref.dtype)


def hfa_pallas(
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
) -> jax.Array:
    """Tiled H-FA over (BH, Lq, d) x (BH, Lkv, d) -> (BH, Lq, d)."""
    bh, lq, d = q.shape
    _, lkv, _ = k.shape
    assert lq % block_q == 0 and lkv % block_kv == 0, (lq, lkv)
    scale_v = (1.0 / d ** 0.5) if scale is None else scale
    kv_len = lkv if kv_len is None else kv_len
    q_offset = (lkv - lq) if q_offset is None else q_offset

    grid = (bh, lq // block_q, lkv // block_kv)
    kernel = functools.partial(
        _hfa_kernel, scale=scale_v, causal=causal, block_q=block_q,
        block_kv=block_kv, kv_len=kv_len, q_offset=q_offset)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, iq, ik: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="hfa_fwd",
    )(q, k, v)
