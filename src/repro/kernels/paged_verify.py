"""Paged multi-query verify Pallas kernel for self-speculative decode.

One verify step scores K = 1 + spec_k input tokens per sequence (the
carry token plus up to spec_k drafted continuations) against the paged
KV pools in a *single* page-table walk - FlashAttention-2's
work-partitioning argument applied to speculation: the page gather and
the log-domain ACC merge that H-FA makes cheap are amortized over all K
positions instead of being paid once per generated token.

Contract (the decode-shaped sibling of :mod:`paged_prefill`):

  * The step's K tokens sit at absolute positions
    ``seq_lens[b] + i`` for i in [0, chunk_lens[b]); their K/V must
    already be scattered into the pools (``paged_prefill.write_chunk_kv``
    with ``start_pos = seq_lens``).  Query row i attends causally to KV
    positions ``<= seq_lens[b] + i`` and ``< seq_lens[b] +
    chunk_lens[b]``.
  * ``chunk_lens[b] == 0`` marks a free / mid-prefill slot riding along
    masked: it emits an all-zero triplet.  Rows at ``i >=
    chunk_lens[b]`` read only valid KV but produce garbage the caller
    ignores.
  * The kernel emits the same partial triplet (m, l, o~) as
    ``paged_decode.py`` / ``paged_prefill.py`` - with K = 1 it computes
    exactly the paged decode attention - so the Eq. 16 merge and the
    LogDiv finalize are reused unchanged, and ``use_hfa`` swaps the
    exponentials for the FIX16 PWL/bit-pack datapath.

``paged_verify_partial_ref`` is the op-order-free jnp triplet oracle
(dense gather + full softmax pieces) used by the golden-parity matrix in
``tests/test_kernels.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import bitmath
from repro.kernels.decode import LANES, NEG_INF
from repro.kernels.paged_decode import _load_tile, gather_pages


def _paged_verify_kernel(pt_ref, sl_ref, cl_ref, q_ref, k_ref, v_ref,
                         *rest, page_size: int, spec_width: int,
                         scale: float, use_hfa: bool, codec=None):
    if codec is not None and codec.has_scales:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)           # (G * K, d)
    k = _load_tile(codec, k_ref, ks_ref)          # (page, d)
    v = _load_tile(codec, v_ref, vs_ref)          # (page, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kv_ids = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # Row r of the flattened (G, K) query block is verify position
    # r % K, i.e. absolute position seq_lens[b] + r % K.
    q_pos = sl_ref[b] + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0) % spec_width
    mask = (kv_ids <= q_pos) & (kv_ids < sl_ref[b] + cl_ref[b])
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

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)
        m_ref[0, 0, :, 0] = m_scr[:, 0]
        l_ref[0, 0, :, 0] = l_scr[:, 0]


def paged_verify_partial_pallas(
    q: jax.Array,           # (B, Hkv, G, K, d) grouped verify queries
    k_pages: jax.Array,     # (P, Hkv, page, d) shared block pool
    v_pages: jax.Array,     # (P, Hkv, page, d)
    page_table: jax.Array,  # (B, pages_per_seq) int32 page ids
    seq_lens: jax.Array,    # (B,) int32 pre-step KV length per sequence
    chunk_lens: jax.Array,  # (B,) int32 real input count this step (0=free)
    *,
    interpret: bool,
    scale: float | None = None,
    use_hfa: bool = False,
    codec=None,
    k_scales: jax.Array | None = None,  # (P, Hkv, page, 1) f32 sidecar
    v_scales: jax.Array | None = None,
):
    """Partial paged verify attention: one block-FAU triplet per
    (sequence, kv head, verify position).

    Returns:
      (o~, m, l): o~ (B, Hkv, G, K, d) unnormalized f32 accumulator,
      m/l (B, Hkv, G, K) running max / sum-of-exps - the same triplet
      contract as ``paged_decode_partial_pallas`` (K = 1 is exactly the
      paged decode), mergeable/finalizable via
      :mod:`repro.kernels.decode`.
    """
    b, hkv, g, spec_width, d = q.shape
    _, hkv_p, page_size, _ = k_pages.shape
    assert hkv_p == hkv, (hkv_p, hkv)
    pages_per_seq = page_table.shape[1]
    scale_v = (1.0 / d ** 0.5) if scale is None else scale
    rows = g * spec_width
    q3 = q.reshape(b, hkv, rows, d)
    has_scales = codec is not None and codec.has_scales

    kernel = functools.partial(_paged_verify_kernel, page_size=page_size,
                               spec_width=spec_width, scale=scale_v,
                               use_hfa=use_hfa, codec=codec)
    in_specs = [
        pl.BlockSpec((1, 1, rows, d),
                     lambda b, h, j, pt, sl, cl: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d),
                     lambda b, h, j, pt, sl, cl: (pt[b, j], h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d),
                     lambda b, h, j, pt, sl, cl: (pt[b, j], h, 0, 0)),
    ]
    operands = [q3, k_pages, v_pages]
    if has_scales:
        in_specs += [
            pl.BlockSpec((1, 1, page_size, 1),
                         lambda b, h, j, pt, sl, cl: (pt[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, 1),
                         lambda b, h, j, pt, sl, cl: (pt[b, j], h, 0, 0)),
        ]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, pages_per_seq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda b, h, j, pt, sl, cl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, rows, 1),
                         lambda b, h, j, pt, sl, cl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, rows, 1),
                         lambda b, h, j, pt, sl, cl: (b, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, rows, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, rows, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_verify_partial",
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      chunk_lens.astype(jnp.int32), *operands)
    return (o.reshape(b, hkv, g, spec_width, d),
            m[..., 0].reshape(b, hkv, g, spec_width),
            l[..., 0].reshape(b, hkv, g, spec_width))


def paged_verify_partial_ref(q, k_pages, v_pages, page_table, seq_lens,
                             chunk_lens, *, scale=None, use_hfa=False,
                             codec=None, k_scales=None, v_scales=None):
    """jnp triplet oracle: dense gather + one-shot softmax pieces.

    Same signature/returns as :func:`paged_verify_partial_pallas`.  The
    running max equals the global max, so ``m`` differs from the
    kernel's only by the order in which the score dot sums its d
    products; ``l``/``o~`` differ by f32 summation order.  With a
    ``codec`` the gathered pages (and sidecar scales) are decoded before
    the dense softmax - the same decode the kernel applies per tile.
    """
    b, hkv, g, spec_width, d = q.shape
    scale_v = (1.0 / d ** 0.5) if scale is None else scale
    kc = gather_pages(k_pages, page_table)        # (B, S, Hkv, d)
    vc = gather_pages(v_pages, page_table)
    if codec is not None:
        ks = None if k_scales is None else gather_pages(k_scales, page_table)
        vs = None if v_scales is None else gather_pages(v_scales, page_table)
        kc = codec.decode(kc, ks)
        vc = codec.decode(vc, vs)
    s = jnp.einsum("bhgld,bshd->bhgls", q.astype(jnp.float32),
                   kc.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale_v
    kv_ids = jnp.arange(kc.shape[1], dtype=jnp.int32)
    sl = seq_lens.astype(jnp.int32)[:, None, None]
    q_pos = sl + jnp.arange(spec_width, dtype=jnp.int32)[None, :, None]
    mask = (kv_ids[None, None, :] <= q_pos) & \
        (kv_ids[None, None, :] < sl + chunk_lens.astype(jnp.int32)[:, None,
                                                                   None])
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    if use_hfa:
        p = bitmath.exp2_hfa_rail(bitmath.quant_rail(s - m[..., None]))
    else:
        p = jnp.exp(s - m[..., None])
    live = (m != NEG_INF)
    p = jnp.where(mask[:, None, None, :, :] & live[..., None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgls,bshd->bhgld", p, vc.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    m = jnp.where(live, m, NEG_INF)
    return o, m, l
