"""Distributed attention collectives: the paper's multi-KV-block merge
(Fig. 2 / Eq. 16) promoted to the mesh.

``shardmap_decode_attention`` serves one new token against a KV ring whose
*sequence* dim is sharded over the "model" axis:

  * each shard writes the new (k, v) row with a LOCAL dynamic-update-slice
    (a traced-index DUS on a sharded dim would force the SPMD partitioner
    to all-gather and rewrite the whole ring - the baseline's memory
    bottleneck, see EXPERIMENTS.md §Perf);
  * each shard computes a partial FAU triplet (o~, m, l) over its local
    window, exactly like one of the paper's block-FAUs;
  * the triplets (tiny: one d-vector per head) are all-gathered over the
    shard axis and merged with the log-domain ACC rule, optionally through
    the FIX16 quantized path (use_hfa).

Collective volume per token: P * (d+2) floats per head instead of the
full ring - this is the paper's cascaded-ACC architecture as an ICI
pattern.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels import decode as dk


def shardmap_decode_attention(
    q: jax.Array,        # (B, 1, H, dh)
    k_new: jax.Array,    # (B, 1, Hkv, dh)
    v_new: jax.Array,    # (B, 1, Hkv, dh)
    cache_k: jax.Array,  # (B, S, Hkv, dh), S sharded over `axis`
    cache_v: jax.Array,
    pos: jax.Array,      # scalar int32: global write index
    *,
    mesh,
    axis: str = "model",
    batch_axes=("pod", "data"),
    use_hfa: bool = True,
    scale: float | None = None,
):
    """Returns (out (B,1,H,dh), new_cache_k, new_cache_v)."""
    b, _, h, dh = q.shape
    hkv = cache_k.shape[2]
    g = h // hkv
    n_shards = mesh.shape[axis]
    s_local = cache_k.shape[1] // n_shards
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    bspec = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)

    def local(q, k_new, v_new, ck, cv, pos):
        bl = q.shape[0]  # local (batch-sharded) size
        idx = jax.lax.axis_index(axis)
        offset = idx * s_local
        local_pos = jnp.clip(pos - offset, 0, s_local - 1)
        hit = (pos >= offset) & (pos < offset + s_local)
        # Local write: plain DUS on the unsharded local ring.
        ck_w = jax.lax.dynamic_update_slice(
            ck, k_new.astype(ck.dtype), (0, local_pos, 0, 0))
        cv_w = jax.lax.dynamic_update_slice(
            cv, v_new.astype(cv.dtype), (0, local_pos, 0, 0))
        ck = jnp.where(hit, ck_w, ck)
        cv = jnp.where(hit, cv_w, cv)

        # Partial FAU over the local window [offset, offset + s_local).
        kv_len_local = jnp.clip(pos + 1 - offset, 0, s_local)
        qg = q.reshape(bl, hkv, g, dh)
        scale_v = (1.0 / dh ** 0.5) if scale is None else scale
        s = jnp.einsum("bhgd,bshd->bhgs", qg, ck,
                       preferred_element_type=jnp.float32) * scale_v
        mask = jnp.arange(s_local)[None, None, None, :] < kv_len_local
        s = jnp.where(mask, s, -1e30)
        m = jnp.max(s, axis=-1)
        if use_hfa:
            from repro.kernels import bitmath
            p = bitmath.exp2_hfa_rail(bitmath.quant_rail(
                jnp.minimum(s - m[..., None], 0.0)))
        else:
            p = jnp.exp(s - m[..., None])
        p = jnp.where(mask & (m != -1e30)[..., None], p, 0.0)
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhgs,bshd->bhgd", p.astype(q.dtype), cv,
                       preferred_element_type=jnp.float32)

        # ACC merge across shards (Eq. 16): gather the tiny triplets.
        og = jax.lax.all_gather(o, axis)
        mg = jax.lax.all_gather(m, axis)
        lg = jax.lax.all_gather(l, axis)
        om, mm, lm = dk.merge_partials(og, mg, lg, use_hfa=use_hfa)
        out = dk.finalize_decode(om, lm, use_hfa=use_hfa)
        return out.reshape(bl, 1, h, dh).astype(q.dtype), ck, cv

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec),
                  P(bspec, axis), P(bspec, axis), P()),
        out_specs=(P(bspec), P(bspec, axis), P(bspec, axis)),
        check_vma=False)
    return fn(q, k_new, v_new, cache_k, cache_v, pos)


# ---------------------------------------------------------------- paged TP
def tp_shards(mesh, axis: str = "model") -> int:
    """Size of the tensor-parallel axis on ``mesh`` (1 = no TP)."""
    if mesh is None:
        return 1
    return int(mesh.shape.get(axis, 1))


def shardmap_paged_attention(
    q: jax.Array,        # (B, L, H, dh) decode L=1 / verify L=K / chunk L
    k_new: jax.Array,    # (B, L, Hkv, dh) this step's K/V to scatter
    v_new: jax.Array,    # (B, L, Hkv, dh)
    pools: dict,         # {"k_pages", "v_pages"[, "k_scale", "v_scale"]}
                         # each (P, Hkv, page, ·), Hkv sharded over `axis`
    page_table: jax.Array,  # (B, pages_per_seq) int32, replicated
    lens_a: jax.Array,   # (B,) int32: decode/verify seq_lens; prefill start
    lens_b: jax.Array,   # (B,) int32: verify/prefill chunk_lens; decode 0s
    *,
    mesh,
    mode: str,           # "decode" | "verify" | "prefill"
    impl: str = "fa2",
    axis: str = "model",
    data_axis: str = "data",
    scale: float | None = None,
    codec=None,          # page codec (name or PageCodec); None/"fp" = raw
    window: int | None = None,  # newest rows each query attends
):
    """Tensor-parallel paged attention: the cascaded ACC merge over a
    KV-head-sharded page pool.

    The paper's multi-KV-block merge (Fig. 2 / Eq. 16), already an ICI
    pattern for the dense ring (:func:`shardmap_decode_attention`),
    applied to the production paged pool:

      * the pools keep the *full* page layout on every shard but carry
        only ``Hkv / tp`` KV heads (page tables stay replicated, so host
        paging logic - refcounts, COW, prefix cache, rollback - is
        untouched);
      * each shard scatters its local heads' K/V (a LOCAL page-table
        write: no cross-shard traffic) and computes the partial block-FAU
        triplet (o~, m, l) over its local heads via the same
        :mod:`repro.kernels.ops` partials the single-shard path
        finalizes;
      * local triplets are padded to full head width with the merge's
        *neutral* element (o~=0, m=NEG_INF, l=0), all-gathered over the
        shard axis (tiny: tp * B * L * H * (dh + 2) floats vs the full
        KV pool), and merged with the log-domain ACC rule
        (:func:`repro.kernels.decode.merge_partials`; ``use_hfa``
        selects the FIX16/PWL rail) before one LogDiv finalize.

    Because a head's triplet is computed by exactly one shard and the
    ACC merge with the neutral element is an fp identity (the owning
    shard's rescale weight is exp(0) == 1, the neutral's l/o~ are
    exactly 0), the merged output is bit-equal to the single-shard
    finalize per head - which is what makes TP serving token-exact.

    With a page ``codec``, each shard encodes its local heads' K/V
    before the scatter (encode is elementwise per head, so shard-local
    encode == global encode) and the scale sidecar pools ride the same
    head-sharded spec as the data pools; decode-in-kernel happens inside
    the shard-local partials, so the sharded rail quantizes exactly like
    the single-shard one.

    Data parallelism (a ``data_axis`` of size dp > 1 on the mesh): the
    *slot* (batch) dim of q is additionally sharded over the data axis
    whenever ``B % dp == 0``, so a step's attention compute splits dp
    ways with ZERO new collectives.  The trick that keeps it bit-exact:
    every data shard applies the FULL batch's K/V scatter (k_new /
    page_table / lens stay replicated over "data"), so the pool
    replicas on each data shard evolve bit-identically - only the
    partials + merge run on the local batch slice (selected with
    ``axis_index(data_axis)``), and the outputs are reassembled on the
    batch dim.  A batch that dp does not divide (an odd chunked-prefill
    group) falls back to fully replicated compute for that call, which
    is the same arithmetic on every shard - still bit-exact, just not
    parallel.

    Returns (out (B, L, H, dh), new_pools) with the pools (and any scale
    sidecars) still KV-head-sharded (and replicated over the data axis).
    """
    from repro.kernels import ops as kops
    from repro.kernels import page_codec
    from repro.kernels import paged_decode as paged_k
    from repro.kernels import paged_prefill as paged_pf_k

    assert mode in ("decode", "verify", "prefill"), mode
    b, l_q, h, dh = q.shape
    hkv = pools["k_pages"].shape[1]
    g = h // hkv
    n = tp_shards(mesh, axis)
    assert hkv % n == 0, (
        f"paged TP needs kv_heads % tp == 0, got {hkv} % {n}")
    hkv_l = hkv // n
    use_hfa = impl.startswith("hfa")
    cod = page_codec.get_codec(codec)
    rcodec = None if cod.name == "fp" else cod
    dp = tp_shards(mesh, data_axis)
    # Batch-shard q over the data axis when it divides evenly; otherwise
    # every data shard runs the full batch (identical arithmetic - the
    # bit-exact fallback for odd prefill group sizes).
    shard_b = dp > 1 and b % dp == 0

    def local(q, k_new, v_new, pools, pt, la, lb):
        # q arrives head-sharded (and, with shard_b, batch-sharded):
        # (B/dp, L, H/n, dh) - heads are kv-major, so the head slice is
        # exactly this shard's hkv_l KV-head groups.
        idx = jax.lax.axis_index(axis)
        bl = q.shape[0]
        if shard_b:
            # Every data shard scatters the FULL batch (pool replicas
            # stay bit-identical - no collective needed to reconcile
            # them), but attends only its own batch slice.
            didx = jax.lax.axis_index(data_axis)
            pt_l = jax.lax.dynamic_slice_in_dim(pt, didx * bl, bl, 0)
            la_l = jax.lax.dynamic_slice_in_dim(la, didx * bl, bl, 0)
            lb_l = jax.lax.dynamic_slice_in_dim(lb, didx * bl, bl, 0)
        else:
            pt_l, la_l, lb_l = pt, la, lb
        if mode == "decode":
            pools = page_codec.encode_write(
                paged_k.append_kv, cod, pools, k_new, v_new, pt, la)
            kv_lens = jnp.where(la_l > 0, la_l + 1, 0)
            qg = q.reshape(bl, hkv_l, g, dh)
            o, m, l = kops.paged_decode_partials(
                qg, pools["k_pages"], pools["v_pages"], pt_l, kv_lens,
                impl=impl, scale=scale, codec=rcodec,
                k_scales=pools.get("k_scale"),
                v_scales=pools.get("v_scale"), window=window)
        elif mode == "verify":
            pools = page_codec.encode_write(
                paged_pf_k.write_chunk_kv, cod, pools, k_new, v_new, pt,
                la, lb)
            qg = jnp.swapaxes(q, 1, 2).reshape(bl, hkv_l, g, l_q, dh)
            o, m, l = kops.paged_verify_partials(
                qg, pools["k_pages"], pools["v_pages"], pt_l, la_l, lb_l,
                impl=impl, scale=scale, codec=rcodec,
                k_scales=pools.get("k_scale"),
                v_scales=pools.get("v_scale"), window=window)
        else:
            pools = page_codec.encode_write(
                paged_pf_k.write_chunk_kv, cod, pools, k_new, v_new, pt,
                la, lb)
            kv_lens = (la_l + lb_l).astype(jnp.int32)
            qg = jnp.swapaxes(q, 1, 2).reshape(bl, hkv_l, g, l_q, dh)
            o, m, l = kops.paged_prefill_partials(
                qg, pools["k_pages"], pools["v_pages"], pt_l, la_l,
                kv_lens,
                impl=impl, scale=scale, codec=rcodec,
                k_scales=pools.get("k_scale"),
                v_scales=pools.get("v_scale"), window=window)

        # Pad the local triplet to full head width with the neutral
        # element, so the gathered merge reconstitutes every head.
        o_f = jnp.zeros((bl, hkv) + o.shape[2:], o.dtype)
        m_f = jnp.full((bl, hkv) + m.shape[2:], dk.NEG_INF, m.dtype)
        l_f = jnp.zeros((bl, hkv) + l.shape[2:], l.dtype)
        off = idx * hkv_l
        o_f = jax.lax.dynamic_update_slice_in_dim(o_f, o, off, axis=1)
        m_f = jax.lax.dynamic_update_slice_in_dim(m_f, m, off, axis=1)
        l_f = jax.lax.dynamic_update_slice_in_dim(l_f, l, off, axis=1)

        # ACC merge across shards (Eq. 16): gather only the triplets
        # (over the model axis alone - data shards own disjoint batch
        # rows, so nothing crosses the data axis here).
        og = jax.lax.all_gather(o_f, axis)
        mg = jax.lax.all_gather(m_f, axis)
        lg = jax.lax.all_gather(l_f, axis)
        om, mm, lm = dk.merge_partials(og, mg, lg, use_hfa=use_hfa)
        out = dk.finalize_decode(om, lm, use_hfa=use_hfa)
        if mode == "decode":
            out = out.reshape(bl, 1, h, dh)
        else:
            # (B, Hkv, G, L, dh) -> (B, L, H, dh)
            out = jnp.swapaxes(out.reshape(bl, h, l_q, dh), 1, 2)
        return out.astype(q.dtype), pools

    # hspec is a pytree *prefix* for the pools dict: every pool leaf
    # (data or scale sidecar) is (P, Hkv, page, ·) with Hkv at axis 1.
    # Nothing names the data axis except q/out's batch dim: the pools
    # and the scatter operands stay replicated over "data" so every
    # data shard's pool replica evolves identically.
    hspec = P(None, axis, None, None)
    kvspec = P(None, None, axis, None)          # (B, L, Hkv, dh) new K/V
    dspec = data_axis if shard_b else None
    qspec = P(dspec, None, axis, None)
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, hspec, P(), P(), P()),
        out_specs=(P(dspec), hspec),
        check_vma=False)
    return fn(q, k_new, v_new, dict(pools), page_table,
              lens_a.astype(jnp.int32), lens_b.astype(jnp.int32))
