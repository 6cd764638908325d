"""Paged flash-decode Pallas kernel + device-side page-table cache ops.

The serving engine stores each layer's KV cache as a shared *block pool*
of fixed-size pages, ``(num_pages, Hkv, page_size, d)``, addressed by a
per-sequence page table ``(B, pages_per_seq)`` - vLLM's PagedAttention
layout mapped onto the paper's multi-KV-block FAU architecture (Fig. 2):

  * Pools are head-major: the kernel's K/V block ``(1, 1, page, d)`` is
    one (page, d) tile per (page, kv head), which satisfies Mosaic's
    block rule (the last two block dims divisible by 8 and 128) for the
    page sizes in :data:`TPU_PAGE_SIZES`.  A bf16 page of 16 is exactly
    one native (16, 128) tile.

  * Every page is one KV block.  The kernel walks a sequence's page
    table with scalar prefetch (the page id feeds the BlockSpec index
    map, so the DMA engine gathers non-contiguous pages directly from
    HBM) and streams them through the Alg. 2 online update.
  * The kernel emits the same *partial triplet* (m, l, o~) as the dense
    ``decode.py`` kernel, so the log-domain ACC merge (Eq. 16) and the
    LogDiv finalize are reused unchanged.
  * ``use_hfa`` switches the exponentials to the FIX16-quantized
    PWL/bit-pack datapath, exactly as in the dense kernel.

Also here (they pair with the kernel, not with host bookkeeping):
``append_kv`` / ``write_prefill_kv`` scatter new K/V into the pools at
page-table-resolved positions, and ``gather_pages`` reconstructs a dense
view for the jnp fallback path and the test oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import bitmath
from repro.kernels.decode import LANES, NEG_INF

# Page sizes the paged kernels compile for on the TPU (checked against a
# described v5e by tests/test_tpu_compile.py).  The (page, d) K/V tile
# needs a page that is a multiple of 8 sublanes; 8 and 16 are the sizes
# the serving stack uses.
TPU_PAGE_SIZES = (8, 16)


def _load_tile(codec, ref, s_ref):
    """Decode one (page, d) KV tile to f32 right after its DMA.

    ``codec is None`` is the raw fp pool (astype only - bit-exact to the
    pre-codec kernel); otherwise the codec's decode runs inside the tile
    loop, with the per-page scale tile (page, 1) from the sidecar pool.
    """
    tile = ref[0, 0]
    if codec is None:
        return tile.astype(jnp.float32)
    s = None if s_ref is None else s_ref[0, 0].astype(jnp.float32)
    return codec.decode(tile, s).astype(jnp.float32)


def _paged_decode_kernel(pt_ref, sl_ref, q_ref, k_ref, v_ref, *rest,
                         page_size: int, scale: float, use_hfa: bool,
                         codec=None):
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

    q = q_ref[0, 0].astype(jnp.float32)           # (G, d)
    k = _load_tile(codec, k_ref, ks_ref)          # (page, d)
    v = _load_tile(codec, v_ref, vs_ref)          # (page, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kv_ids = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kv_ids < sl_ref[b]
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


def paged_decode_partial_pallas(
    q: jax.Array,           # (B, Hkv, G, d) grouped queries
    k_pages: jax.Array,     # (P, Hkv, page, d) shared block pool
    v_pages: jax.Array,     # (P, Hkv, page, d)
    page_table: jax.Array,  # (B, pages_per_seq) int32 page ids
    kv_lens: jax.Array,     # (B,) int32 valid KV length per sequence
    *,
    interpret: bool,
    scale: float | None = None,
    use_hfa: bool = False,
    codec=None,
    k_scales: jax.Array | None = None,  # (P, Hkv, page, 1) f32 sidecar
    v_scales: jax.Array | None = None,
):
    """Partial paged decode attention: one block-FAU triplet per (b, hkv).

    Page-table entries past ``ceil(kv_lens[b] / page)`` may be any valid
    page id (their contribution is masked out); ``kv_lens[b] == 0`` marks
    a free slot and yields an all-zero triplet.

    ``codec`` (a :class:`repro.kernels.page_codec.PageCodec`, or None for
    the raw fp pool) decodes each page tile inside the loop; codecs with
    scales stream the matching sidecar page through the same
    scalar-prefetch index map as the KV pages.

    Returns:
      (o~, m, l): o~ (B, Hkv, G, d) unnormalized f32 accumulator, m/l
      (B, Hkv, G) running max / sum-of-exps - mergeable with the dense
      triplets via :func:`repro.kernels.decode.merge_partials`.
    """
    b, hkv, g, d = q.shape
    _, hkv_p, page_size, _ = k_pages.shape
    assert hkv_p == hkv, (hkv_p, hkv)
    pages_per_seq = page_table.shape[1]
    scale_v = (1.0 / d ** 0.5) if scale is None else scale
    has_scales = codec is not None and codec.has_scales

    kernel = functools.partial(_paged_decode_kernel, page_size=page_size,
                               scale=scale_v, use_hfa=use_hfa, codec=codec)
    in_specs = [
        pl.BlockSpec((1, 1, g, d), lambda b, h, j, pt, sl: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d),
                     lambda b, h, j, pt, sl: (pt[b, j], h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d),
                     lambda b, h, j, pt, sl: (pt[b, j], h, 0, 0)),
    ]
    operands = [q, k_pages, v_pages]
    if has_scales:
        in_specs += [
            pl.BlockSpec((1, 1, page_size, 1),
                         lambda b, h, j, pt, sl: (pt[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, 1),
                         lambda b, h, j, pt, sl: (pt[b, j], h, 0, 0)),
        ]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, pages_per_seq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b, h, j, pt, sl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda b, h, j, pt, sl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda b, h, j, pt, sl: (b, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_flash_decode_partial",
    )(page_table.astype(jnp.int32), kv_lens.astype(jnp.int32), *operands)
    return o, m[..., 0], l[..., 0]


# ------------------------------------------------------- page cache ops
def _write_rows(k_pages, v_pages, k_rows, v_rows, page, off):
    """Scatter token rows into head-major pools: row i (Hkv, d) lands
    at ``pages[page[i], :, off[i]]``.  Rows whose ``page`` is out of
    range (the pool size) are dropped."""
    kp = k_pages.at[page, :, off].set(k_rows.astype(k_pages.dtype),
                                      mode="drop")
    vp = v_pages.at[page, :, off].set(v_rows.astype(v_pages.dtype),
                                      mode="drop")
    return kp, vp


def _page_coords(page_table, positions, page_size):
    """(page id, in-page offset) of each (b, position).  positions: (B,)
    or (B, L).  Page indices are clamped to the table width so padded
    positions past the allocation resolve to a (wrong but in-bounds)
    page - callers that can produce them (write_chunk_kv) drop those
    writes explicitly."""
    pidx = jnp.minimum(positions // page_size, page_table.shape[1] - 1)
    page = jnp.take_along_axis(page_table, pidx, axis=1)
    return page, positions % page_size


def append_kv(k_pages, v_pages, k_new, v_new, page_table, seq_lens):
    """Scatter one new token's K/V per *active* sequence into the pools.

    k_new/v_new: (B, 1, Hkv, d); the token for sequence b lands at
    position ``seq_lens[b]``.  Slots with ``seq_lens[b] == 0`` are free
    (nothing has been prefilled) and their write is dropped.
    """
    p, _, page_size, _ = k_pages.shape
    pos = seq_lens.astype(jnp.int32)
    page, off = _page_coords(page_table, pos[:, None], page_size)
    page = jnp.where(pos > 0, page[:, 0], p)            # OOB => dropped
    return _write_rows(k_pages, v_pages, k_new[:, 0], v_new[:, 0], page,
                       off[:, 0])


def write_prefill_kv(k_pages, v_pages, k_new, v_new, page_table):
    """Write a fresh prompt's K/V (positions 0..L-1) through the page table.

    k_new/v_new: (B, L, Hkv, d); row b uses page_table row b.  All rows
    are written in full - the engine prefills per request (or per group
    of equal-length requests), padding to a page multiple; padded tail
    positions are masked later by ``kv_lens``.
    """
    page_size = k_pages.shape[2]
    b, l, hkv, d = k_new.shape
    pos = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None], (b, l))
    page, off = _page_coords(page_table, pos, page_size)
    return _write_rows(k_pages, v_pages, k_new.reshape(b * l, hkv, d),
                       v_new.reshape(b * l, hkv, d), page.reshape(-1),
                       off.reshape(-1))


def gather_pages(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """Dense (B, pages_per_seq * page, Hkv, d) view of each sequence's KV
    (sequence-major, the layout of the dense jnp paths and oracles)."""
    b, j = page_table.shape
    _, hkv, page_size, d = pages.shape
    out = jnp.take(pages, page_table.reshape(-1), axis=0)
    out = jnp.swapaxes(out.reshape(b, j, hkv, page_size, d), 2, 3)
    return out.reshape(b, j * page_size, hkv, d)
