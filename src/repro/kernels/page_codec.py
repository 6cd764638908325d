"""Pluggable page codecs for the paged KV block pool.

The serving engine's KV pools were "one array per layer in the compute
dtype".  A :class:`PageCodec` generalizes that layout into encode-on-
write / decode-in-kernel: the pools hold *encoded* pages in the codec's
storage dtype, an optional per-page **scale sidecar** rides next to them
(same (P, Hkv, page, ·) rank, trailing dim 1, so every page-table
mechanism - scatter writers, COW ``copy_pages``, ``gather_pages``, the
TP ``NamedSharding`` placement - applies to scale leaves unchanged),
and the paged kernels dequantize inside the tile loop right after the
page DMA.

Codecs:

  fp     identity - pages stored in the compute dtype, no sidecar.
         Bit-exact to the pre-codec pool; the default.
  int8   per-page absmax int8.  One f32 scale per token row per KV head
         (a row-granular refinement of per-page absmax: appending one
         token never re-encodes the page's other rows, so decode-append
         stays a pure scatter).  decode = data * scale.
  log16  FIX16 log-domain pages on the H-FA rail (paper Sec. IV-V).
         ``lns.blinn_log2`` quantizes each element to the (sign, rail)
         pair and the two are bit-packed as ``sign<<15 | (rail +
         bias<<7)`` - which is exactly the BFloat16 bit layout (Eq. 18
         and Eq. 22 are inverses), so dequant on the hfa rail is a
         bitcast: the page IS the log-domain operand.  No sidecar;
         bytes halve vs an fp32 pool and drift is bounded by bf16
         rounding of the source values.

Byte accounting lives here too (:meth:`PageCodec.bytes_per_row` /
:func:`bytes_per_token`), so ``serving.engine`` and the benchmark
scoreboard derive slots-at-equal-pool-bytes from one source of truth.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import lns
from repro.core.numerics import BF16_BIAS, FRAC_BITS, LOG_ZERO

CODECS = ("fp", "int8", "log16")

_SCALE_DTYPE = jnp.float32


class PageCodec:
    """Encode-on-write / decode-in-kernel page transform.

    ``encode(x)`` maps compute-dtype values ``(..., d)`` to
    ``(data, scales)`` where ``data`` has :meth:`storage_dtype` and
    ``scales`` is ``(..., 1)`` f32 (or None when :attr:`has_scales` is
    False).  ``decode(data, scales)`` is the f32 inverse; it must be
    cheap enough to run inside a Pallas tile loop (the jnp fallback
    paths call the identical function on gathered pages, so kernel and
    fallback agree by construction).
    """

    name: str = "?"
    has_scales: bool = False

    def storage_dtype(self, ref_dtype):
        raise NotImplementedError

    def encode(self, x: jax.Array):
        raise NotImplementedError

    def decode(self, data: jax.Array, scales: jax.Array | None):
        raise NotImplementedError

    def bytes_per_row(self, d: int, ref_dtype) -> int:
        """Stored bytes for one token row of one KV head (d elements
        plus this codec's share of the scale sidecar)."""
        raise NotImplementedError


class FpCodec(PageCodec):
    """Identity codec: today's pool, bit-exact."""

    name = "fp"
    has_scales = False

    def storage_dtype(self, ref_dtype):
        return ref_dtype

    def encode(self, x):
        return x, None

    def decode(self, data, scales):
        return data.astype(jnp.float32)

    def bytes_per_row(self, d, ref_dtype):
        return d * jnp.dtype(ref_dtype).itemsize


class Int8Codec(PageCodec):
    """Per-page absmax int8 with a per-row f32 scale sidecar."""

    name = "int8"
    has_scales = True

    def storage_dtype(self, ref_dtype):
        return jnp.int8

    def encode(self, x):
        xf = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
        scale = amax / 127.0
        safe = jnp.where(scale > 0, scale, 1.0)
        data = jnp.clip(jnp.round(xf / safe), -127, 127).astype(jnp.int8)
        return data, scale.astype(_SCALE_DTYPE)

    def decode(self, data, scales):
        return data.astype(jnp.float32) * scales.astype(jnp.float32)

    def bytes_per_row(self, d, ref_dtype):
        return d + jnp.dtype(_SCALE_DTYPE).itemsize


class Log16Codec(PageCodec):
    """FIX16 log-domain pages: Blinn-quantized (sign, rail) bit-packs.

    Encode runs the paper's Eq. 18 (``lns.blinn_log2``) and packs the
    pair as ``sign << 15 | (rail + BF16_BIAS << FRAC_BITS)`` in uint16.
    That packing coincides with the BFloat16 bit pattern (the Eq. 22
    inverse is exact for integer rail values), so decode is a bitcast -
    on the hfa rail the stored page is already the log-domain operand
    and dequantization costs one type reinterpretation.
    """

    name = "log16"
    has_scales = False

    def storage_dtype(self, ref_dtype):
        return jnp.uint16

    def encode(self, x):
        sign, raw = lns.blinn_log2(x)
        mag = raw + (BF16_BIAS << FRAC_BITS)
        mag = jnp.clip(mag, 0, 0x7FFF)
        mag = jnp.where(raw <= LOG_ZERO, 0, mag.astype(jnp.int32))
        bits = jnp.left_shift(sign, 15) | mag
        return bits.astype(jnp.uint16), None

    def decode(self, data, scales):
        return jax.lax.bitcast_convert_type(
            data, jnp.bfloat16).astype(jnp.float32)

    def bytes_per_row(self, d, ref_dtype):
        return d * 2


_REGISTRY: dict[str, PageCodec] = {
    c.name: c for c in (FpCodec(), Int8Codec(), Log16Codec())
}


def get_codec(name: str | PageCodec | None) -> PageCodec:
    """Resolve a codec by name (None -> fp); PageCodec passes through."""
    if name is None:
        return _REGISTRY["fp"]
    if isinstance(name, PageCodec):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown page codec {name!r}; have {sorted(_REGISTRY)}")


def bytes_per_token(codec, hkv: int, d: int, ref_dtype) -> int:
    """Stored KV bytes per token position per layer: K + V rows across
    all KV heads, scale sidecar included."""
    return 2 * hkv * get_codec(codec).bytes_per_row(d, ref_dtype)


def decode_pages(codec, pages: jax.Array,
                 scales: jax.Array | None) -> jax.Array:
    """Decode a whole (or gathered) pool view to f32 (jnp fallback /
    oracle path - the Pallas kernels call ``codec.decode`` per tile)."""
    return get_codec(codec).decode(pages, scales)


def encode_write(writer, codec, pools: dict, k_new: jax.Array,
                 v_new: jax.Array, *args) -> dict:
    """Encode-on-write: run ``codec.encode`` on this step's K/V and push
    data (and scale sidecars) through ``writer(kp, vp, k, v, *args)``.

    ``writer`` is any of the page scatter ops (``append_kv``,
    ``write_chunk_kv``, ``write_prefill_kv``) - they are dtype- and
    trailing-dim-agnostic, so the (B, L, Hkv, 1) scale rows ride through
    the *same* page-table-resolved scatter (same drop semantics) as the
    (B, L, Hkv, d) data rows.  ``pools`` holds "k_pages"/"v_pages" and,
    for codecs with scales, "k_scale"/"v_scale"; the returned dict has
    the same keys.  The fp codec's encode is the identity, so its write
    is bit-exact to the pre-codec path.
    """
    c = get_codec(codec)
    kd, ks = c.encode(k_new)
    vd, vs = c.encode(v_new)
    kp, vp = writer(pools["k_pages"], pools["v_pages"], kd, vd, *args)
    out = {"k_pages": kp, "v_pages": vp}
    if c.has_scales:
        ksp, vsp = writer(pools["k_scale"], pools["v_scale"], ks, vs, *args)
        out["k_scale"] = ksp
        out["v_scale"] = vsp
    return out
