"""Paged chunked-prefill Pallas kernel + chunk-write / page-copy ops.

Chunked prefill is the serving-side dual of :mod:`paged_decode`: instead
of one query token per sequence, a bounded *chunk* of L prompt tokens
(starting at an arbitrary per-slot offset ``start_pos``) attends
causally against everything already materialized in the paged KV pools
- the shared-prefix pages claimed at admission, earlier chunks, and the
chunk itself, which is scattered into the pools before attention runs.

The kernel walks the sequence's page table with scalar prefetch (page id
feeds the BlockSpec index map, so non-contiguous pages DMA straight from
HBM) and streams each page through the Alg. 2 online update, exactly
like ``paged_decode.py`` but with G*L query rows per (sequence, kv head)
instead of G.  It emits the same partial triplet (m, l, o~), so the
log-domain ACC merge and LogDiv finalize are reused unchanged, and
``use_hfa`` swaps the exponentials for the FIX16 PWL/bit-pack datapath.

Also here: ``write_chunk_kv`` (position-exact scatter of a chunk's K/V
through the page table - padded tail rows are dropped, never written, so
shared copy-on-write pages stay intact) and ``copy_pages`` (the device
side of copy-on-write: duplicate page contents inside a pool).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import bitmath
from repro.kernels.decode import LANES, NEG_INF
from repro.kernels.paged_decode import _load_tile, _page_coords, _write_rows


def _paged_prefill_kernel(pt_ref, sp_ref, kl_ref, q_ref, k_ref, v_ref,
                          *rest, page_size: int, chunk: int, scale: float,
                          use_hfa: bool, codec=None):
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

    q = q_ref[0, 0].astype(jnp.float32)           # (G * chunk, d)
    k = _load_tile(codec, k_ref, ks_ref)          # (page, d)
    v = _load_tile(codec, v_ref, vs_ref)          # (page, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kv_ids = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # Row r of the flattened (G, chunk) query block is local chunk
    # position r % chunk, i.e. absolute position start + r % chunk.
    q_pos = sp_ref[b] + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0) % chunk
    mask = (kv_ids <= q_pos) & (kv_ids < kl_ref[b])
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


def paged_prefill_partial_pallas(
    q: jax.Array,           # (B, Hkv, G, L, d) grouped chunk queries
    k_pages: jax.Array,     # (P, Hkv, page, d) shared block pool
    v_pages: jax.Array,     # (P, Hkv, page, d)
    page_table: jax.Array,  # (B, pages_per_seq) int32 page ids
    start_pos: jax.Array,   # (B,) int32 chunk start position per sequence
    kv_lens: jax.Array,     # (B,) int32 valid KV length (start + chunk len)
    *,
    interpret: bool,
    scale: float | None = None,
    use_hfa: bool = False,
    codec=None,
    k_scales: jax.Array | None = None,  # (P, Hkv, page, 1) f32 sidecar
    v_scales: jax.Array | None = None,
):
    """Partial paged chunked-prefill attention.

    Query row (g, l) of sequence b sits at absolute position
    ``start_pos[b] + l`` and attends causally to KV positions
    ``<= start_pos[b] + l`` (and ``< kv_lens[b]``).  Rows at ``l >=``
    the real chunk length read valid KV but produce garbage the caller
    ignores.  Page-table entries past ``ceil(kv_lens[b] / page)`` may be
    any valid page id (masked out).

    Returns:
      (o~, m, l): o~ (B, Hkv, G, L, d) unnormalized f32 accumulator,
      m/l (B, Hkv, G, L) running max / sum-of-exps - the same block-FAU
      triplet contract as :func:`repro.kernels.paged_decode.
      paged_decode_partial_pallas`, mergeable/finalizable with
      :mod:`repro.kernels.decode`.
    """
    b, hkv, g, chunk, d = q.shape
    _, hkv_p, page_size, _ = k_pages.shape
    assert hkv_p == hkv, (hkv_p, hkv)
    pages_per_seq = page_table.shape[1]
    scale_v = (1.0 / d ** 0.5) if scale is None else scale
    rows = g * chunk
    q3 = q.reshape(b, hkv, rows, d)
    has_scales = codec is not None and codec.has_scales

    kernel = functools.partial(_paged_prefill_kernel, page_size=page_size,
                               chunk=chunk, scale=scale_v, use_hfa=use_hfa,
                               codec=codec)
    in_specs = [
        pl.BlockSpec((1, 1, rows, d),
                     lambda b, h, j, pt, sp, kl: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d),
                     lambda b, h, j, pt, sp, kl: (pt[b, j], h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d),
                     lambda b, h, j, pt, sp, kl: (pt[b, j], h, 0, 0)),
    ]
    operands = [q3, k_pages, v_pages]
    if has_scales:
        in_specs += [
            pl.BlockSpec((1, 1, page_size, 1),
                         lambda b, h, j, pt, sp, kl: (pt[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, 1),
                         lambda b, h, j, pt, sp, kl: (pt[b, j], h, 0, 0)),
        ]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, pages_per_seq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda b, h, j, pt, sp, kl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, rows, 1),
                         lambda b, h, j, pt, sp, kl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, rows, 1),
                         lambda b, h, j, pt, sp, kl: (b, h, 0, 0)),
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
        name="paged_prefill_partial",
    )(page_table.astype(jnp.int32), start_pos.astype(jnp.int32),
      kv_lens.astype(jnp.int32), *operands)
    return (o.reshape(b, hkv, g, chunk, d),
            m[..., 0].reshape(b, hkv, g, chunk),
            l[..., 0].reshape(b, hkv, g, chunk))


# ------------------------------------------------------- page cache ops
def write_chunk_kv(k_pages, v_pages, k_new, v_new, page_table, start_pos,
                   chunk_lens):
    """Position-exact scatter of a prefill chunk's K/V into the pools.

    k_new/v_new: (B, L, Hkv, d); row b's token i lands at position
    ``start_pos[b] + i``.  Rows with ``i >= chunk_lens[b]`` (padding)
    are DROPPED, not written - unlike the fresh-prefill scatter this
    never touches positions outside the chunk, so shared prefix pages
    below ``start_pos`` and pages beyond the chunk stay intact.
    """
    p, _, page_size, _ = k_pages.shape
    b, l, hkv, d = k_new.shape
    offs = jnp.arange(l, dtype=jnp.int32)[None]                # (1, L)
    pos = start_pos.astype(jnp.int32)[:, None] + offs          # (B, L)
    page, off = _page_coords(page_table.astype(jnp.int32), pos, page_size)
    valid = offs < chunk_lens.astype(jnp.int32)[:, None]
    page = jnp.where(valid, page, p)                           # OOB => drop
    return _write_rows(k_pages, v_pages, k_new.reshape(b * l, hkv, d),
                       v_new.reshape(b * l, hkv, d), page.reshape(-1),
                       off.reshape(-1))


def copy_pages(pages: jax.Array, src: jax.Array, dst: jax.Array,
               axis: int = 0) -> jax.Array:
    """Device side of copy-on-write: ``pages[dst[i]] = pages[src[i]]``
    along ``axis``.  Padding entries use an out-of-range ``dst`` (the
    write is dropped); ``src`` is clipped so the dead gather stays in
    bounds.  ``axis`` selects the page dimension (1 for the stacked
    (groups, P, Hkv, page, d) layer pools)."""
    vals = jnp.take(pages, src.astype(jnp.int32), axis=axis, mode="clip")
    idx = (slice(None),) * axis + (dst.astype(jnp.int32),)
    return pages.at[idx].set(vals, mode="drop")
