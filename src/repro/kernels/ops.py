"""Public jit'd attention ops: impl dispatch, GQA plumbing, padding.

``multihead_attention`` is what the model layer calls.  It accepts
(B, Lq, H, d) queries and (B, Lkv, Hkv, d) keys/values (Hkv | H), handles
GQA head grouping, pads sequence lengths up to block multiples, dispatches
to the chosen implementation and unpads.

Implementations:
  exact          dense softmax reference (f32)
  fa2            blocked jnp FlashAttention-2 (Alg. 2)
  hfa            bit-accurate H-FA emulation (slow; tests/small models)
  fa2_pallas     baseline Pallas TPU kernel
  hfa_pallas     hybrid float/log Pallas TPU kernel (the paper's H-FA)
  hfa_datapath   per-element LNS Pallas kernel (validation only)

On CPU the Pallas kernels run in interpret mode automatically.

The paged serving ops below follow one rule instead: on the TPU every
paged decode, prefill and verify call runs its Pallas kernel, whatever
the impl; the impl only picks the numerics (``use_hfa`` for the H-FA
rail, i.e. any impl starting with "hfa").  Off the TPU they run the jnp
gather path, unless ``force_pallas`` pins the kernel in interpret mode
(the parity tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hfa as core_hfa
from repro.core import reference
from repro.kernels import decode as decode_k
from repro.kernels import fa2 as fa2_k
from repro.kernels import hfa as hfa_k
from repro.kernels import hfa_datapath as dp_k
from repro.kernels import paged_decode as paged_k
from repro.kernels import paged_prefill as paged_pf_k
from repro.kernels import paged_verify as paged_v_k

IMPLS = ("exact", "fa2", "hfa", "fa2_pallas", "hfa_pallas", "hfa_datapath")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _paged_pallas(force_pallas: bool) -> bool:
    """Whether a paged op runs its Pallas kernel: always on the TPU,
    elsewhere only when a parity test pins it (interpret mode)."""
    return force_pallas or _on_tpu()


def _pad_to(x: jax.Array, axis: int, mult: int) -> tuple[jax.Array, int]:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def _gqa_expand(k: jax.Array, hq: int) -> jax.Array:
    """Repeat KV heads to match H query heads: (B, L, Hkv, d) -> (B, L, H, d)."""
    hkv = k.shape[2]
    if hkv == hq:
        return k
    assert hq % hkv == 0, (hq, hkv)
    return jnp.repeat(k, hq // hkv, axis=2)


# ---- differentiable Pallas attention ------------------------------------
# The forward runs the Pallas kernel.  For fa2 the backward is the
# handwritten Pallas FA-2 backward (kernels/fa2_bwd.py, using the saved
# logsumexp residual).  For hfa the backward differentiates the
# op-matched jnp oracle (ref.py) - the cotangent then follows the same
# quantized numerics the kernel computed (STE, see bitmath).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _pallas_attention(q3, k3, v3, impl, causal, block_q, block_kv,
                      kv_len, q_offset):
    from repro.kernels import fa2 as fa2_k
    from repro.kernels import hfa as hfa_k
    interpret = not _on_tpu()
    fn = fa2_k.fa2_pallas if impl == "fa2_pallas" else hfa_k.hfa_pallas
    return fn(q3, k3, v3, causal=causal, block_q=block_q, block_kv=block_kv,
              kv_len=kv_len, q_offset=q_offset, interpret=interpret)


def _oracle(q3, k3, v3, impl, causal, block_kv, kv_len, q_offset):
    from repro.core import reference
    from repro.kernels import ref as kref
    km = k3[:, :kv_len]
    vm = v3[:, :kv_len]
    if impl == "fa2_pallas":
        part = reference.fa2_partial(q3, km, vm, causal=causal,
                                     q_offset=q_offset if causal else None,
                                     block=block_kv)
        return part.o / part.l[..., None]
    return kref.ref_hfa_mxu_padded(q3, km, vm, causal=causal,
                                   block_kv=block_kv, q_offset=q_offset)


def _pallas_attention_fwd(q3, k3, v3, impl, causal, block_q, block_kv,
                          kv_len, q_offset):
    from repro.kernels import fa2 as fa2_k
    interpret = not _on_tpu()
    if impl == "fa2_pallas":
        out, lse = fa2_k.fa2_pallas(
            q3, k3, v3, causal=causal, block_q=block_q, block_kv=block_kv,
            kv_len=kv_len, q_offset=q_offset, interpret=interpret,
            return_lse=True)
        return out, (q3, k3, v3, out, lse)
    out = _pallas_attention(q3, k3, v3, impl, causal, block_q, block_kv,
                            kv_len, q_offset)
    return out, (q3, k3, v3, None, None)


def _pallas_attention_bwd(impl, causal, block_q, block_kv, kv_len, q_offset,
                          res, g):
    q3, k3, v3, o3, lse = res
    if impl == "fa2_pallas":
        from repro.kernels import fa2_bwd
        dq, dk, dv = fa2_bwd.fa2_backward(
            q3, k3, v3, o3, g, lse, causal=causal,
            block_q=block_q, block_kv=block_kv, kv_len=kv_len,
            q_offset=q_offset, interpret=not _on_tpu())
        return dq, dk, dv
    _, vjp = jax.vjp(
        lambda q, k, v: _oracle(q, k, v, impl, causal, block_kv, kv_len,
                                q_offset), q3, k3, v3)
    dq, dk, dv = vjp(g.astype(jnp.float32))
    return (dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype))


_pallas_attention.defvjp(_pallas_attention_fwd, _pallas_attention_bwd)


def multihead_attention(
    q: jax.Array,   # (B, Lq, H, d)
    k: jax.Array,   # (B, Lkv, Hkv, d)
    v: jax.Array,   # (B, Lkv, Hkv, d)
    *,
    impl: str = "fa2",
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
) -> jax.Array:
    """Multi-head attention returning (B, Lq, H, d) in q.dtype."""
    assert impl in IMPLS, impl
    b, lq, h, d = q.shape
    _, lkv, hkv, _ = k.shape

    k = _gqa_expand(k, h)
    v = _gqa_expand(v, h)

    # (B, H, L, d) layout for the core/batched refs.
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)

    if impl == "exact":
        out = reference.exact_attention(qh, kh, vh, causal=causal, scale=scale)
    elif impl == "fa2":
        out = reference.fa2_attention(qh, kh, vh, causal=causal, scale=scale,
                                      block=min(block_kv, lkv))
    elif impl == "hfa":
        out = core_hfa.hfa_attention(qh, kh, vh, causal=causal, scale=scale)
    else:
        interpret = not _on_tpu()
        q3 = qh.reshape(b * h, lq, d)
        k3 = kh.reshape(b * h, lkv, d)
        v3 = vh.reshape(b * h, lkv, d)
        if impl == "hfa_datapath":
            out = dp_k.hfa_datapath_pallas(q3, k3, v3, causal=causal,
                                           scale=scale, interpret=interpret)
        else:
            assert scale is None, "pallas impls use the default 1/sqrt(d)"
            q3, lq0 = _pad_to(q3, 1, block_q)
            k3, lkv0 = _pad_to(k3, 1, block_kv)
            v3, _ = _pad_to(v3, 1, block_kv)
            out = _pallas_attention(q3, k3, v3, impl, causal,
                                    block_q, block_kv, lkv0, lkv0 - lq0)
            out = out[:, :lq0]
        out = out.reshape(b, h, lq, d)

    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def decode_attention(
    q: jax.Array,        # (B, 1, H, d) single new token
    k_cache: jax.Array,  # (B, S, Hkv, d)
    v_cache: jax.Array,  # (B, S, Hkv, d)
    *,
    impl: str = "fa2",
    scale: float | None = None,
    kv_len: jax.Array | int | None = None,
    block_kv: int = 128,
) -> jax.Array:
    """Single-token decode attention against a KV cache.

    Uses the grouped-GQA partial kernel + merge/LogDiv for Pallas impls;
    jnp streaming otherwise.  ``kv_len`` masks unwritten cache slots (may
    be a traced scalar for the jnp paths).
    """
    b, one, h, d = q.shape
    assert one == 1
    _, s_len, hkv, _ = k_cache.shape
    g = h // hkv
    use_hfa = impl.startswith("hfa")

    if impl in ("fa2_pallas", "hfa_pallas") and isinstance(kv_len, (int, type(None))):
        interpret = not _on_tpu()
        kvl = s_len if kv_len is None else int(kv_len)
        qg = q.reshape(b, h, d).reshape(b, hkv, g, d).reshape(b * hkv, g, d)
        k3 = jnp.swapaxes(k_cache, 1, 2).reshape(b * hkv, s_len, d)
        v3 = jnp.swapaxes(v_cache, 1, 2).reshape(b * hkv, s_len, d)
        k3, _ = _pad_to(k3, 1, block_kv)
        v3, _ = _pad_to(v3, 1, block_kv)
        o, m, l = decode_k.decode_partial_pallas(
            qg, k3, v3, scale=scale, block_kv=block_kv, kv_len=kvl,
            use_hfa=use_hfa, interpret=interpret)
        out = decode_k.finalize_decode(o, l, use_hfa=use_hfa)
        return out.reshape(b, hkv, g, d).reshape(b, 1, h, d).astype(q.dtype)

    # jnp path (supports traced kv_len): grouped-GQA masked attention.
    qg = q.reshape(b, hkv, g, d)                        # (B, Hkv, G, d)
    out = _decode_jnp_grouped(qg, k_cache, v_cache, kv_len,
                              scale=scale, use_hfa=use_hfa,
                              acc_dtype=q.dtype)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def _decode_jnp_grouped(qg, k_cache, v_cache, kv_len, *, scale, use_hfa,
                        acc_dtype):
    """Grouped-GQA single-token decode: the L == 1 case of
    :func:`_prefill_jnp_grouped` (the single query sits at position
    ``kv_len - 1``, so the causal mask degenerates to ``< kv_len``).

    No head repeat and no f32 cache copy: the score/PV einsums read the
    bf16 ring directly with f32 accumulation - essential for the
    32k/500k sequence-sharded caches.  ``kv_len`` masks unwritten cache
    slots; it may be None, a (traced) scalar, or a per-sequence (B,)
    vector (the paged/continuous-batching case, where a 0 entry marks a
    free slot and yields a zero row).

    qg: (B, Hkv, G, d); k_cache/v_cache: (B, S, Hkv, d).
    Returns (B, Hkv, G, d) float32.
    """
    b = qg.shape[0]
    s_len = k_cache.shape[1]
    if kv_len is None:
        kvl = jnp.full((b,), s_len, jnp.int32)
    else:
        kvl = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    out = _prefill_jnp_grouped(qg[:, :, :, None, :], k_cache, v_cache,
                               kvl[:, None] - 1, kvl, scale=scale,
                               use_hfa=use_hfa, acc_dtype=acc_dtype)
    return out[:, :, :, 0, :]


def _prefill_jnp_partial(qg, k_cache, v_cache, q_pos, kv_lens, *, scale,
                         use_hfa, acc_dtype, window=None):
    """Grouped-GQA chunked-prefill *partial* attention (block-FAU form).

    Same math as the Pallas kernels' triplet contract: per query row,
    ``m`` is the running max, ``p = exp(s - m)`` (or the FIX16 PWL rail
    under ``use_hfa``), ``l = sum(p)``, ``o~ = p @ V`` unnormalized.
    Returning the triplet instead of the normalized output is what lets
    a tensor-parallel shard contribute its local heads/pages to the
    log-domain ACC merge (Eq. 16) - and the single-shard path finalizes
    the *same* triplet, so sharded and unsharded decode are bit-equal
    per head.

    Fully-masked rows (free slots / padding) come back as the merge's
    *neutral* triplet (o~=0, m=NEG_INF, l=0): their pages may hold junk
    (donated buffers), and even with p == 0 the PV einsum turns NaN/Inf
    into 0 * NaN = NaN, so dead rows are forced to zero explicitly.

    qg: (B, Hkv, G, L, d); k_cache/v_cache: (B, S, Hkv, d);
    q_pos: (B, L) absolute position per chunk row; kv_lens: (B,) valid
    KV length; ``window``: a row at position p attends only ``> p -
    window`` (every row is still read and masked here).
    Returns (o~ (B, Hkv, G, L, d) f32, m (B, Hkv, G, L), l (B, Hkv, G, L)).
    """
    b, _, _, _, d = qg.shape
    s_len = k_cache.shape[1]
    scale_v = (1.0 / d ** 0.5) if scale is None else scale
    s = jnp.einsum("bhgld,bshd->bhgls", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale_v
    kv_ids = jnp.arange(s_len, dtype=jnp.int32)
    mask = (kv_ids[None, None, :] <= q_pos[:, :, None]) & \
        (kv_ids[None, None, :] < kv_lens.astype(jnp.int32)[:, None, None])
    if window is not None:
        mask = mask & (kv_ids[None, None, :] > q_pos[:, :, None] - window)
    s = jnp.where(mask[:, None, None, :, :], s, decode_k.NEG_INF)
    live = jnp.any(mask, axis=-1)                              # (B, L)
    m = jnp.max(s, axis=-1)
    if use_hfa:
        from repro.kernels import bitmath
        p = bitmath.exp2_hfa_rail(bitmath.quant_rail(s - m[..., None]))
    else:
        p = jnp.exp(s - m[..., None])
    # Masked positions: exp underflows to 0 for live rows, but a dead
    # row has s == m == NEG_INF, so exp(0) == 1 - zero them explicitly.
    p = jnp.where(mask[:, None, None, :, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgls,bshd->bhgld", p.astype(acc_dtype), v_cache,
                   preferred_element_type=jnp.float32)
    o = jnp.where(live[:, None, None, :, None], o, 0.0)
    return o, m, l


def _prefill_jnp_grouped(qg, k_cache, v_cache, q_pos, kv_lens, *, scale,
                         use_hfa, acc_dtype):
    """Grouped-GQA chunked-prefill attention over a gathered dense view:
    the partial block-FAU triplet (:func:`_prefill_jnp_partial`)
    finalized with LogDiv / float divide.  Full-width softmax per query
    row in f32 - the result is independent of how the prompt was cut
    into chunks, which is what makes chunked prefill token-exact.

    Returns (B, Hkv, G, L, d) float32.
    """
    o, m, l = _prefill_jnp_partial(qg, k_cache, v_cache, q_pos, kv_lens,
                                   scale=scale, use_hfa=use_hfa,
                                   acc_dtype=acc_dtype)
    return decode_k.finalize_decode(o, l, use_hfa=use_hfa)


# ---- paged attention: partial triplets ----------------------------------
# Each function returns the block-FAU triplet (o~, m, l) over whatever KV
# heads the pools it was handed contain.  The public ops below finalize
# the triplet directly; the tensor-parallel shard_map path
# (:mod:`repro.parallel.collectives`) calls the same partials on each
# shard's local heads and merges the gathered triplets with the
# log-domain ACC rule instead - so sharded and unsharded serving share
# one set of numerics.
#
# ``codec`` / ``k_scales`` / ``v_scales`` select a page codec
# (:mod:`repro.kernels.page_codec`): the Pallas kernels decode each page
# tile inside the loop (scales streamed via the same scalar-prefetch
# index map), and the jnp gather paths decode the gathered dense view
# with the *same* codec.decode - codec=None is the raw fp pool, bit-exact
# to the pre-codec path.
#
# Pools are head-major, (P, Hkv, page, d) (scale sidecars (P, Hkv, page,
# 1)); see :mod:`repro.kernels.paged_decode`.

def _gathered_kv(k_pages, v_pages, page_table, codec, k_scales, v_scales):
    """Dense per-sequence KV view for the jnp gather path, codec-decoded."""
    k_cache = paged_k.gather_pages(k_pages, page_table)
    v_cache = paged_k.gather_pages(v_pages, page_table)
    if codec is not None:
        ks = None if k_scales is None else \
            paged_k.gather_pages(k_scales, page_table)
        vs = None if v_scales is None else \
            paged_k.gather_pages(v_scales, page_table)
        k_cache = codec.decode(k_cache, ks)
        v_cache = codec.decode(v_cache, vs)
    return k_cache, v_cache


def paged_decode_partials(
    qg: jax.Array,          # (B, Hkv, G, d) grouped queries
    k_pages: jax.Array,     # (P, Hkv, page, d)
    v_pages: jax.Array,     # (P, Hkv, page, d)
    page_table: jax.Array,  # (B, pages_per_seq) int32
    kv_lens: jax.Array,     # (B,) int32; 0 marks a free slot
    *,
    impl: str = "fa2",
    scale: float | None = None,
    force_pallas: bool = False,
    codec=None,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
    window: int | None = None,
):
    """Paged decode partial triplet: (o~ (B,Hkv,G,d), m/l (B,Hkv,G))."""
    b = qg.shape[0]
    use_hfa = impl.startswith("hfa")
    if _paged_pallas(force_pallas):
        return paged_k.paged_decode_partial_pallas(
            qg, k_pages, v_pages, page_table, kv_lens, scale=scale,
            use_hfa=use_hfa, interpret=not _on_tpu(), codec=codec,
            k_scales=k_scales, v_scales=v_scales, window=window)
    k_cache, v_cache = _gathered_kv(k_pages, v_pages, page_table, codec,
                                    k_scales, v_scales)
    kvl = jnp.broadcast_to(jnp.asarray(kv_lens, jnp.int32), (b,))
    o, m, l = _prefill_jnp_partial(qg[:, :, :, None, :], k_cache, v_cache,
                                   kvl[:, None] - 1, kvl, scale=scale,
                                   use_hfa=use_hfa, acc_dtype=qg.dtype,
                                   window=window)
    return o[:, :, :, 0, :], m[..., 0], l[..., 0]


def paged_prefill_partials(
    qg: jax.Array,          # (B, Hkv, G, L, d) grouped chunk queries
    k_pages: jax.Array,     # (P, Hkv, page, d)
    v_pages: jax.Array,     # (P, Hkv, page, d)
    page_table: jax.Array,  # (B, pages_per_seq) int32
    start_pos: jax.Array,   # (B,) int32 chunk start position
    kv_lens: jax.Array,     # (B,) int32 valid KV length (start + chunk)
    *,
    impl: str = "fa2",
    scale: float | None = None,
    force_pallas: bool = False,
    codec=None,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
    window: int | None = None,
):
    """Paged chunked-prefill partial triplet: shapes (B,Hkv,G,L,[d])."""
    use_hfa = impl.startswith("hfa")
    if _paged_pallas(force_pallas):
        return paged_pf_k.paged_prefill_partial_pallas(
            qg, k_pages, v_pages, page_table, start_pos, kv_lens,
            scale=scale, use_hfa=use_hfa, interpret=not _on_tpu(),
            codec=codec, k_scales=k_scales, v_scales=v_scales, window=window)
    k_cache, v_cache = _gathered_kv(k_pages, v_pages, page_table, codec,
                                    k_scales, v_scales)
    l = qg.shape[3]
    q_pos = start_pos.astype(jnp.int32)[:, None] + \
        jnp.arange(l, dtype=jnp.int32)[None]
    return _prefill_jnp_partial(qg, k_cache, v_cache, q_pos, kv_lens,
                                scale=scale, use_hfa=use_hfa,
                                acc_dtype=qg.dtype, window=window)


def paged_verify_partials(
    qg: jax.Array,          # (B, Hkv, G, K, d) grouped verify queries
    k_pages: jax.Array,     # (P, Hkv, page, d)
    v_pages: jax.Array,     # (P, Hkv, page, d)
    page_table: jax.Array,  # (B, pages_per_seq) int32
    seq_lens: jax.Array,    # (B,) int32 pre-step KV length; 0 = free slot
    chunk_lens: jax.Array,  # (B,) int32 real input count this step
    *,
    impl: str = "fa2",
    scale: float | None = None,
    force_pallas: bool = False,
    codec=None,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
    window: int | None = None,
):
    """Paged speculative-verify partial triplet: shapes (B,Hkv,G,K,[d])."""
    use_hfa = impl.startswith("hfa")
    if _paged_pallas(force_pallas):
        return paged_v_k.paged_verify_partial_pallas(
            qg, k_pages, v_pages, page_table, seq_lens, chunk_lens,
            scale=scale, use_hfa=use_hfa, interpret=not _on_tpu(),
            codec=codec, k_scales=k_scales, v_scales=v_scales, window=window)
    k_cache, v_cache = _gathered_kv(k_pages, v_pages, page_table, codec,
                                    k_scales, v_scales)
    kw = qg.shape[3]
    sl = seq_lens.astype(jnp.int32)
    q_pos = sl[:, None] + jnp.arange(kw, dtype=jnp.int32)[None]
    kv_lens = sl + chunk_lens.astype(jnp.int32)
    return _prefill_jnp_partial(qg, k_cache, v_cache, q_pos, kv_lens,
                                scale=scale, use_hfa=use_hfa,
                                acc_dtype=qg.dtype, window=window)


def paged_prefill_attention(
    q: jax.Array,           # (B, L, H, d) one prefill chunk per sequence
    k_pages: jax.Array,     # (P, Hkv, page, d) shared block pool
    v_pages: jax.Array,     # (P, Hkv, page, d)
    page_table: jax.Array,  # (B, pages_per_seq) int32
    start_pos: jax.Array,   # (B,) int32 chunk start position
    chunk_lens: jax.Array,  # (B,) int32 real (unpadded) chunk length
    *,
    impl: str = "fa2",
    scale: float | None = None,
    force_pallas: bool = False,
    codec=None,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """Chunked-prefill attention against a paged KV cache.

    The chunk's K/V must already be scattered into the pools
    (:func:`repro.kernels.paged_prefill.write_chunk_kv`); queries then
    attend causally to KV positions ``<= start_pos[b] + i``.  On the TPU
    the paged-prefill Pallas kernel walks the page table with scalar
    prefetch, on both rails; the result is finalized with LogDiv on the
    H-FA rail.  Elsewhere a jnp path gathers the pages into a dense view
    (the CPU path).  ``force_pallas`` pins the kernel (interpret mode
    off the TPU) for parity tests.
    """
    b, l, h, d = q.shape
    hkv = k_pages.shape[1]
    g = h // hkv
    use_hfa = impl.startswith("hfa")
    kv_lens = (start_pos + chunk_lens).astype(jnp.int32)
    # (B, L, H, d) -> (B, Hkv, G, L, d): heads are kv-major (GQA repeat).
    qg = jnp.swapaxes(q, 1, 2).reshape(b, hkv, g, l, d)
    o, m, ell = paged_prefill_partials(
        qg, k_pages, v_pages, page_table, start_pos, kv_lens, impl=impl,
        scale=scale, force_pallas=force_pallas, codec=codec,
        k_scales=k_scales, v_scales=v_scales, window=window)
    out = decode_k.finalize_decode(o, ell, use_hfa=use_hfa)
    # (B, Hkv, G, L, d) -> (B, L, H, d)
    return jnp.swapaxes(out.reshape(b, h, l, d), 1, 2).astype(q.dtype)


def paged_decode_attention(
    q: jax.Array,           # (B, 1, H, d) single new token per slot
    k_pages: jax.Array,     # (P, Hkv, page, d) shared block pool
    v_pages: jax.Array,     # (P, Hkv, page, d)
    page_table: jax.Array,  # (B, pages_per_seq) int32
    kv_lens: jax.Array,     # (B,) int32; 0 marks a free slot
    *,
    impl: str = "fa2",
    scale: float | None = None,
    force_pallas: bool = False,
    codec=None,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """Continuous-batching decode attention against a paged KV cache.

    On the TPU the paged Pallas kernel streams pages straight from HBM
    via the page table (scalar prefetch), on both rails; the H-FA rail
    finalizes with LogDiv.  Elsewhere a jnp path gathers the sequence's
    pages into a dense view and reuses the grouped decode math (the CPU
    path the test suite exercises end to end).  ``force_pallas`` pins
    the kernel (interpret mode off the TPU) for parity tests.
    """
    b, one, h, d = q.shape
    assert one == 1
    hkv = k_pages.shape[1]
    g = h // hkv
    use_hfa = impl.startswith("hfa")
    qg = q.reshape(b, h, d).reshape(b, hkv, g, d)
    o, m, l = paged_decode_partials(qg, k_pages, v_pages, page_table,
                                    kv_lens, impl=impl, scale=scale,
                                    force_pallas=force_pallas, codec=codec,
                                    k_scales=k_scales, v_scales=v_scales,
                                    window=window)
    out = decode_k.finalize_decode(o, l, use_hfa=use_hfa)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def paged_verify_attention(
    q: jax.Array,           # (B, K, H, d) K verify tokens per slot
    k_pages: jax.Array,     # (P, Hkv, page, d) shared block pool
    v_pages: jax.Array,     # (P, Hkv, page, d)
    page_table: jax.Array,  # (B, pages_per_seq) int32
    seq_lens: jax.Array,    # (B,) int32 pre-step KV length; 0 = free slot
    chunk_lens: jax.Array,  # (B,) int32 real input count this step
    *,
    impl: str = "fa2",
    scale: float | None = None,
    force_pallas: bool = False,
    codec=None,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """Multi-query speculative-verify attention against a paged KV cache.

    The step's K tokens (carry + drafts) must already be scattered into
    the pools at positions ``seq_lens[b]..``; query row i attends
    causally to KV ``<= seq_lens[b] + i`` (and ``< seq_lens[b] +
    chunk_lens[b]``), so all K positions are scored in one page-table
    walk.  With K == 1 this computes exactly
    :func:`paged_decode_attention` on the post-append cache.  On the TPU
    the dedicated verify kernel walks the table with scalar prefetch, on
    both rails; elsewhere the jnp gather path reuses the grouped chunk
    math (same numerics as the decode path, which is what makes k-step
    spec decode token-exact).  Rows at ``i >= chunk_lens[b]`` are
    garbage the caller ignores; ``chunk_lens[b] == 0`` rows come back
    zero.
    """
    b, kw, h, d = q.shape
    hkv = k_pages.shape[1]
    g = h // hkv
    use_hfa = impl.startswith("hfa")
    qg = jnp.swapaxes(q, 1, 2).reshape(b, hkv, g, kw, d)
    o, m, l = paged_verify_partials(
        qg, k_pages, v_pages, page_table, seq_lens, chunk_lens, impl=impl,
        scale=scale, force_pallas=force_pallas, codec=codec,
        k_scales=k_scales, v_scales=v_scales, window=window)
    out = decode_k.finalize_decode(o, l, use_hfa=use_hfa)
    # (B, Hkv, G, K, d) -> (B, K, H, d)
    return jnp.swapaxes(out.reshape(b, h, kw, d), 1, 2).astype(q.dtype)
