"""Paged flash-decode Pallas kernel + device-side page-table cache ops.

The serving engine stores each layer's KV cache as a shared *block pool*
of fixed-size pages, ``(num_pages, Hkv, page_size, d)``, addressed by a
per-sequence page table ``(B, pages_per_seq)`` - vLLM's PagedAttention
layout mapped onto the paper's multi-KV-block FAU architecture (Fig. 2):

  * Pools are head-major, so one page's ``(Hkv, page, d)`` slab over
    all KV heads is contiguous, and one (page, d) tile per (page, kv
    head) satisfies Mosaic's tiling for the page sizes in
    :data:`TPU_PAGE_SIZES` (a bf16 page of 16 is one native (16, 128)
    tile).
  * A grid step is one block of a slot's pages across every KV head:
    :func:`pages_per_block` pages, the smallest power of two whose K+V
    slabs reach :data:`BLOCK_BYTES`.  The page ids come from the
    scalar-prefetched page table; each live page's slab arrives in one
    DMA into double-buffered VMEM, the next live block's (this slot's,
    else the next live slot's first) in flight while the current one is
    computed.  Blocks past ``kv_lens[b]`` start no copy and do no work,
    so a step costs the live context, not the table width.
  * Inside a block the pages go through the Alg. 2 online update one at
    a time, in order, each KV head on its own (page, d) tile: the
    arithmetic of a walk of one page per grid step, bit for bit.
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


def _column(row):
    """(1, n) -> (n, 1), exactly: the diagonal of the row broadcast to
    (n, n), summed along lanes (every other term is 0)."""
    n = row.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


# VMEM a block's K+V slab over all KV heads should reach: the block is
# the smallest power of two of pages that fills it (capped by the table
# width), so a slot's pages arrive in a few large DMAs whatever the head
# count.  Double-buffered, the slabs take twice this.
BLOCK_BYTES = 512 * 1024


def pages_per_block(k_pages, pages_per_seq: int) -> int:
    """Pages per grid step for head-major pools ``(P, Hkv, page, d)``."""
    _, hkv, page_size, d = k_pages.shape
    page_bytes = 2 * hkv * page_size * d * k_pages.dtype.itemsize
    n = 1
    while n * page_bytes < BLOCK_BYTES and n < pages_per_seq:
        n *= 2
    return min(n, pages_per_seq)


def window_pages(window: int, rows: int, page_size: int) -> int:
    """Most pages the keys of ``rows`` consecutive queries under a
    ``window`` can touch: window - 1 + rows keys, starting anywhere in a
    page."""
    return -(-(window - 1 + rows) // page_size) + 1


def first_window_page(kv_len, window: int, page_size: int):
    """Page holding the first key row a query at ``kv_len - 1`` attends
    under ``window``."""
    return jnp.maximum(kv_len - window, 0) // page_size


def _paged_decode_kernel(pt_ref, sl_ref, q_ref, k_hbm, v_hbm, *rest,
                         page_size: int, pages_per_block: int, scale: float,
                         use_hfa: bool, codec=None, window=None):
    if codec is not None and codec.has_scales:
        (ks_ref, vs_ref, o_ref, m_ref, l_ref, k_buf, v_buf, sem, state,
         m_scr, l_scr, acc_scr) = rest
    else:
        ks_ref = vs_ref = None
        (o_ref, m_ref, l_ref, k_buf, v_buf, sem, state, m_scr, l_scr,
         acc_scr) = rest
    nslots, pages_per_seq = pt_ref.shape
    hkv = q_ref.shape[1]
    ppb = pages_per_block
    b = pl.program_id(0)
    i = pl.program_id(1)

    def first(b, i):
        """Table index of block i's first page of slot b: blocks start
        at page 0, or with a window at the page holding the window's
        first row."""
        if window is None:
            return i * ppb
        return first_window_page(sl_ref[b], window, page_size) + i * ppb

    def block_pages(b, i):
        """Live pages of block i of slot b: 0 past ``kv_lens[b]``."""
        live = jnp.minimum((sl_ref[b] + page_size - 1) // page_size,
                           pages_per_seq)
        return jnp.clip(live - first(b, i), 0, ppb)

    def dma(b, i, into, n, wait):
        """Start (or wait for) the copies of the n live pages of block i
        of slot b into VMEM buffer ``into``: one (Hkv, page, d) slab per
        page, K and V."""
        def page(p, _):
            pid = pt_ref[b, first(b, i) + p]
            for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                c = pltpu.make_async_copy(hbm.at[pid], buf.at[into, p],
                                          sem.at[into])
                if wait:
                    c.wait()
                else:
                    c.start()
        jax.lax.fori_loop(0, n, page, None)

    def next_block(b, i):
        """(slot, block) of the live block after (b, i) in grid order;
        slot == nslots when there is none."""
        def next_slot():
            return jax.lax.fori_loop(
                b + 1, nslots,
                lambda c, f: jnp.where((f == nslots) & (sl_ref[c] > 0), c,
                                       f),
                nslots)
        more = block_pages(b, i + 1) > 0
        return (jax.lax.cond(more, lambda: b, next_slot),
                jnp.where(more, i + 1, 0))

    @pl.when((b == 0) & (i == 0))
    def _start_kernel():
        state[0] = 0        # buffer holding (or about to hold) this block
        state[1] = 0        # 1 once the first live block's copies started

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    n = block_pages(b, i)

    @pl.when(n > 0)
    def _block():
        cur = state[0]

        @pl.when(state[1] == 0)
        def _first():
            dma(b, i, cur, n, wait=False)
            state[1] = 1

        nxt_b, nxt_i = next_block(b, i)

        @pl.when(nxt_b < nslots)
        def _prefetch():
            dma(nxt_b, nxt_i, 1 - cur, block_pages(nxt_b, nxt_i),
                wait=False)

        dma(b, i, cur, n, wait=True)

        def load(buf, s_ref, p, h):
            """Page p, head h of the block as f32 (page, d)."""
            tile = buf[cur, p, h]
            if codec is None:
                return tile.astype(jnp.float32)
            s = None if s_ref is None else _column(s_ref[0, p, pl.ds(h, 1)])
            return codec.decode(tile, s).astype(jnp.float32)

        def page(p, _):
            # One page's online update per KV head: the arithmetic of a
            # (slot, head, page) tile, in page order.  Pages past the
            # live length are never visited - on them the update is the
            # identity (alpha = 1, p = 0 on both rails).
            kv_ids = (first(b, i) + p) * page_size
            for h in range(hkv):
                q = q_ref[0, h].astype(jnp.float32)       # (G, d)
                k = load(k_buf, ks_ref, p, h)             # (page, d)
                v = load(v_buf, vs_ref, p, h)             # (page, d)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                rows = kv_ids + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                mask = rows < sl_ref[b]
                if window is not None:
                    mask = mask & (rows >= sl_ref[b] - window)
                s = jnp.where(mask, s, NEG_INF)

                m_prev = m_scr[h, :, 0]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
                if use_hfa:
                    alpha = bitmath.exp2_hfa_rail(bitmath.quant_rail(
                        jnp.minimum(m_prev - m_new, 0.0)))
                    pr = bitmath.exp2_hfa_rail(
                        bitmath.quant_rail(s - m_new[:, None]))
                else:
                    alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
                    pr = jnp.exp(s - m_new[:, None])
                pr = jnp.where(mask & (m_new != NEG_INF)[:, None], pr, 0.0)

                l_scr[h, :, 0] = l_scr[h, :, 0] * alpha + jnp.sum(pr, axis=1)
                acc_scr[h] = acc_scr[h] * alpha[:, None] + jax.lax.dot_general(
                    pr, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_scr[h, :, 0] = m_new

        jax.lax.fori_loop(0, n, page, None)
        state[0] = 1 - cur

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)
        m_ref[0, :, :, 0] = m_scr[:, :, 0]
        l_ref[0, :, :, 0] = l_scr[:, :, 0]


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
    window: int | None = None,
):
    """Partial paged decode attention: one block-FAU triplet per (b, hkv).

    Only a slot's live pages, the first ``ceil(kv_lens[b] / page)`` of
    its table row, are used; entries past them may hold anything.
    ``kv_lens[b] == 0`` marks a free slot and yields an all-zero
    triplet.

    With a ``window`` the query attends only rows ``>= kv_lens[b] -
    window``: each slot's walk starts at the page holding the first of
    them (rows before it in that page are masked), the grid spans only
    the blocks a window can fill, and the call is named
    ``paged_flash_decode_window``.

    ``codec`` (a :class:`repro.kernels.page_codec.PageCodec`, or None for
    the raw fp pool) decodes each page tile inside the loop; codecs with
    scales read the table's sidecar pages, gathered here as (Hkv, page)
    rows per page.

    Returns:
      (o~, m, l): o~ (B, Hkv, G, d) unnormalized f32 accumulator, m/l
      (B, Hkv, G) running max / sum-of-exps - mergeable with the dense
      triplets via :func:`repro.kernels.decode.merge_partials`.
    """
    b, hkv, g, d = q.shape
    _, hkv_p, page_size, _ = k_pages.shape
    assert hkv_p == hkv, (hkv_p, hkv)
    pages_per_seq = page_table.shape[1]
    span = pages_per_seq if window is None else \
        min(pages_per_seq, window_pages(window, 1, page_size))
    ppb = pages_per_block(k_pages, span)
    nblocks = -(-span // ppb)
    scale_v = (1.0 / d ** 0.5) if scale is None else scale
    page_table = page_table.astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)

    kernel = functools.partial(_paged_decode_kernel, page_size=page_size,
                               pages_per_block=ppb, scale=scale_v,
                               use_hfa=use_hfa, codec=codec, window=window)
    per_slot = lambda b, i, pt, sl: (b, 0, 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, hkv, g, d), per_slot),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [q, k_pages, v_pages]
    if codec is not None and codec.has_scales:
        # The sidecars' (page, 1) minor dims are no DMA slab on the TPU,
        # so the table's scales are gathered into (B, pages, Hkv, page)
        # and streamed a block at a time.  Past the last live block the
        # index stays put, so dead blocks fetch nothing.
        if window is None:
            pad = ((0, 0), (0, nblocks * ppb - pages_per_seq))
            rows = jnp.pad(page_table, pad)
            bk = ppb * page_size

            def last_live(b, i, pt, sl):
                return (b, jnp.minimum(
                    i, jnp.maximum((sl[b] + bk - 1) // bk - 1, 0)), 0, 0)
        else:
            # The blocks start at each slot's window: gather its pages
            # from there, the last live page repeated past the end.
            first = first_window_page(kv_lens, window, page_size)
            last = jnp.maximum((kv_lens + page_size - 1) // page_size - 1,
                               0)
            idx = jnp.minimum(first[:, None] + jnp.arange(nblocks * ppb),
                              last[:, None])
            rows = jnp.take_along_axis(page_table, idx, axis=1)

            def last_live(b, i, pt, sl):
                n = jnp.maximum(sl[b] - window, 0) // page_size
                live = (sl[b] + page_size - 1) // page_size - n
                return (b, jnp.minimum(
                    i, jnp.maximum((live + ppb - 1) // ppb - 1, 0)), 0, 0)
        in_specs += [pl.BlockSpec((1, ppb, hkv, page_size), last_live)] * 2
        operands += [jnp.take(k_scales[..., 0], rows, axis=0),
                     jnp.take(v_scales[..., 0], rows, axis=0)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nblocks),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, hkv, g, d), per_slot),
            pl.BlockSpec((1, hkv, g, 1), per_slot),
            pl.BlockSpec((1, hkv, g, 1), per_slot),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, ppb) + k_pages.shape[1:], k_pages.dtype),
            pltpu.VMEM((2, ppb) + v_pages.shape[1:], v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((hkv, g, LANES), jnp.float32),
            pltpu.VMEM((hkv, g, LANES), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
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
        # Both axes in order: each live block prefetches the next one,
        # which may belong to a later slot.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=("paged_flash_decode_partial" if window is None
              else "paged_flash_decode_window"),
    )(page_table, kv_lens, *operands)
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
