"""Per-kernel Pallas validation: shape/dtype sweeps vs the ref.py oracles
(interpret mode on CPU), as required by the task spec."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import reference as cref
from repro.kernels import decode, fa2, hfa, hfa_datapath, ops, ref


def _rand(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


SHAPES = [
    # (bh, lq, lkv, d, block_q, block_kv)
    (1, 128, 128, 64, 128, 128),
    (2, 128, 256, 64, 128, 128),
    (2, 256, 256, 128, 128, 128),
    (3, 128, 384, 32, 128, 128),
    (1, 256, 512, 64, 128, 256),
]
DTYPES = [jnp.bfloat16, jnp.float32]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("causal", [False, True])
def test_fa2_kernel_vs_oracle(shape, dtype, causal):
    bh, lq, lkv, d, bq, bk = shape
    q = _rand((bh, lq, d), dtype, 1)
    k = _rand((bh, lkv, d), dtype, 2)
    v = _rand((bh, lkv, d), dtype, 3)
    out = np.asarray(fa2.fa2_pallas(q, k, v, causal=causal,
                                    block_q=bq, block_kv=bk,
                                    interpret=True))
    gold = np.asarray(ref.ref_fa2(q, k, v, causal=causal))
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out, gold, atol=tol)


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("causal", [False, True])
def test_hfa_kernel_matches_tile_oracle(shape, causal):
    """hfa.py must match its op-order-identical jnp oracle ~bit-exactly."""
    bh, lq, lkv, d, bq, bk = shape
    q = _rand((bh, lq, d), jnp.bfloat16, 4)
    k = _rand((bh, lkv, d), jnp.bfloat16, 5)
    v = _rand((bh, lkv, d), jnp.bfloat16, 6)
    out = np.asarray(hfa.hfa_pallas(q, k, v, causal=causal,
                                    block_q=bq, block_kv=bk,
                                    interpret=True))
    gold = np.asarray(ref.ref_hfa_mxu(q, k, v, causal=causal, block_kv=bk))
    np.testing.assert_allclose(out, gold, atol=1e-6)


def test_hfa_kernel_accuracy_vs_exact():
    q = _rand((2, 128, 64), jnp.bfloat16, 7)
    k = _rand((2, 256, 64), jnp.bfloat16, 8)
    v = _rand((2, 256, 64), jnp.bfloat16, 9)
    out = np.asarray(hfa.hfa_pallas(q, k, v, causal=True, interpret=True))
    gold = np.asarray(ref.ref_fa2(q, k, v, causal=True))
    assert np.isfinite(out).all()
    assert np.abs(out - gold).mean() < 0.02  # quantized-exp regime


def test_hfa_datapath_kernel_bit_exact_vs_emulation():
    """The per-element LNS kernel == core.hfa emulation EXACTLY."""
    q = _rand((2, 8, 32), jnp.bfloat16, 10)
    k = _rand((2, 32, 32), jnp.bfloat16, 11)
    v = _rand((2, 32, 32), jnp.bfloat16, 12)
    for causal in (False, True):
        out = np.asarray(hfa_datapath.hfa_datapath_pallas(
            q, k, v, causal=causal, interpret=True).astype(jnp.float32))
        gold = np.asarray(ref.ref_hfa_datapath(q, k, v, causal=causal)
                          .astype(jnp.float32))
        assert np.array_equal(out, gold)


@pytest.mark.parametrize("use_hfa", [False, True])
@pytest.mark.parametrize("g,s,d", [(4, 256, 64), (8, 384, 128), (2, 512, 32)])
def test_decode_partial_vs_oracle(use_hfa, g, s, d):
    q = _rand((3, g, d), jnp.bfloat16, 13)
    k = _rand((3, s, d), jnp.bfloat16, 14)
    v = _rand((3, s, d), jnp.bfloat16, 15)
    o, m, l = decode.decode_partial_pallas(q, k, v, use_hfa=use_hfa,
                                           interpret=True)
    og, mg, lg = ref.ref_decode_partial(q, k, v, use_hfa=use_hfa)
    np.testing.assert_allclose(np.asarray(o), np.asarray(og), atol=2e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mg), atol=0)
    np.testing.assert_allclose(np.asarray(l), np.asarray(lg), atol=2e-5)


@pytest.mark.parametrize("parts", [2, 4])
@pytest.mark.parametrize("use_hfa", [False, True])
def test_decode_split_merge_equals_full(parts, use_hfa):
    """Paper Fig. 2 at decode: split KV + ACC merge == single span."""
    g, s, d = 4, 512, 64
    q = _rand((2, g, d), jnp.bfloat16, 16)
    k = _rand((2, s, d), jnp.bfloat16, 17)
    v = _rand((2, s, d), jnp.bfloat16, 18)
    span = s // parts
    triplets = [decode.decode_partial_pallas(
        q, k[:, i * span:(i + 1) * span], v[:, i * span:(i + 1) * span],
        use_hfa=use_hfa, interpret=True) for i in range(parts)]
    om, mm, lm = decode.merge_partials(
        jnp.stack([t[0] for t in triplets]),
        jnp.stack([t[1] for t in triplets]),
        jnp.stack([t[2] for t in triplets]), use_hfa=use_hfa)
    merged = np.asarray(decode.finalize_decode(om, lm, use_hfa=use_hfa))
    gold = np.asarray(cref.exact_attention(q, k, v))
    tol = 5e-2 if use_hfa else 1e-5
    np.testing.assert_allclose(merged, gold, atol=tol)


def test_decode_kv_len_masking():
    g, s, d = 4, 256, 64
    q = _rand((2, g, d), jnp.bfloat16, 19)
    k = _rand((2, s, d), jnp.bfloat16, 20)
    v = _rand((2, s, d), jnp.bfloat16, 21)
    o, m, l = decode.decode_partial_pallas(q, k, v, kv_len=100,
                                           interpret=True)
    got = np.asarray(decode.finalize_decode(jnp.asarray(o), jnp.asarray(l)))
    gold = np.asarray(cref.exact_attention(q, k[:, :100], v[:, :100]))
    np.testing.assert_allclose(got, gold, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 128, 256, 64), (1, 128, 128, 32),
                                   (2, 256, 384, 128)])
def test_fa2_backward_kernel_vs_autodiff(causal, shape):
    """Pallas FA-2 backward (dq/dkv kernels) vs jax.grad of the oracle."""
    bh, lq, lkv, d = shape
    q = _rand((bh, lq, d), jnp.float32, 30)
    k = _rand((bh, lkv, d), jnp.float32, 31)
    v = _rand((bh, lkv, d), jnp.float32, 32)

    def loss_pallas(q, k, v):
        from repro.kernels.ops import _pallas_attention
        out = _pallas_attention(q, k, v, "fa2_pallas", causal, 128, 128,
                                lkv, lkv - lq)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        out = cref.exact_attention(q, k, v, causal=causal)
        return jnp.sum(out ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, err_msg=f"d{name}")


def test_fa2_forward_lse_residual():
    q = _rand((2, 128, 64), jnp.bfloat16, 33)
    k = _rand((2, 256, 64), jnp.bfloat16, 34)
    v = _rand((2, 256, 64), jnp.bfloat16, 35)
    out, lse = fa2.fa2_pallas(q, k, v, causal=True, return_lse=True,
                              interpret=True)
    s = np.einsum("bqd,bkd->bqk", np.asarray(q, np.float32),
                  np.asarray(k, np.float32)) / 8.0
    mask = np.tril(np.ones((128, 256), bool), k=128)
    s = np.where(mask, s, -1e30)
    want = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) \
        + s.max(-1)
    np.testing.assert_allclose(np.asarray(lse), want, atol=1e-3)


@pytest.mark.parametrize("impl", ["fa2_pallas", "hfa_pallas"])
def test_ops_gqa_and_padding(impl):
    """Wrapper: GQA expansion + non-multiple seq lengths."""
    q = _rand((2, 100, 8, 64), jnp.bfloat16, 22)
    k = _rand((2, 100, 2, 64), jnp.bfloat16, 23)
    v = _rand((2, 100, 2, 64), jnp.bfloat16, 24)
    out = np.asarray(ops.multihead_attention(q, k, v, impl=impl)
                     .astype(jnp.float32))
    gold = np.asarray(ops.multihead_attention(q, k, v, impl="exact")
                      .astype(jnp.float32))
    tol = 0.35 if impl == "hfa_pallas" else 5e-3
    assert np.abs(out - gold).max() < tol


def test_ops_decode_wrapper_consistency():
    q = _rand((2, 1, 8, 64), jnp.bfloat16, 25)
    kc = _rand((2, 200, 2, 64), jnp.bfloat16, 26)
    vc = _rand((2, 200, 2, 64), jnp.bfloat16, 27)
    a = np.asarray(ops.decode_attention(q, kc, vc, impl="fa2_pallas",
                                        kv_len=150).astype(jnp.float32))
    b = np.asarray(ops.decode_attention(q, kc, vc, impl="fa2",
                                        kv_len=150).astype(jnp.float32))
    np.testing.assert_allclose(a, b, atol=5e-3)


# ----------------------------------------- paged_verify golden parity
def _verify_setup(seed, *, b=2, hkv=2, g=4, d=64, page=8, pages_each=3,
                  kw=1):
    """Random pools + shuffled page table + ragged seq_lens with room
    for a kw-token verify step, whose K/V is already written."""
    from repro.kernels import paged_prefill as paged_pf
    rng = np.random.default_rng(seed)
    num_pages = b * pages_each + 2
    kp = _rand((num_pages, hkv, page, d), jnp.float32, seed + 1)
    vp = _rand((num_pages, hkv, page, d), jnp.float32, seed + 2)
    pt = jnp.asarray(rng.permutation(num_pages)[:b * pages_each]
                     .reshape(b, pages_each).astype(np.int32))
    sl = jnp.asarray(rng.integers(1, pages_each * page - kw + 1, b)
                     .astype(np.int32))
    cl = jnp.full((b,), kw, jnp.int32)
    q = _rand((b, hkv, g, kw, d), jnp.float32, seed + 3)
    return q, kp, vp, pt, sl, cl


def _score_order_bound(q, k_dense, scale):
    """Largest difference two f32 evaluations of the scores ``scale * q
    . k`` may show when they sum the d products in different orders:
    Higham's bound gamma_d * sum_i |q_i k_i| (gamma_d ~ d * eps32),
    taken over every (query row, key).  The running max ``m`` is a
    score, so two kernels that tile the dot differently may disagree on
    ``m`` by up to this much - and no more."""
    d = q.shape[-1]
    gamma = d * np.finfo(np.float32).eps / (1 - d * np.finfo(np.float32)
                                            .eps)
    qa = np.abs(np.asarray(q, np.float32))        # (B, Hkv, G, K, d)
    ka = np.abs(np.asarray(k_dense, np.float32))  # (B, S, Hkv, d)
    return float(gamma * scale * np.einsum("bhgld,bshd->bhgls", qa,
                                           ka).max())


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("g", [1, 4])
def test_paged_verify_k1_triplet_parity_matrix(d, g):
    """Golden-parity matrix over head_dim and GQA group size, on ragged
    seq_lens.  With one verify column the paged_verify kernel must be
    the paged_decode kernel bit for bit - speculative decode is token-
    exact because a K = 1 verify step computes what a decode step
    does.  The dense decode kernel and the jnp triplet oracle sum the
    score dot in another order, so their running max may differ by the
    f32 dot-order bound (:func:`_score_order_bound`) and their l / o~
    by the fp32 tolerances below."""
    from repro.kernels import paged_decode as paged
    from repro.kernels import paged_verify as paged_ver
    q, kp, vp, pt, sl, cl = _verify_setup(50 + d + g, d=d, g=g, kw=1)
    kvl = sl + 1
    ov, mv, lv = paged_ver.paged_verify_partial_pallas(
        q, kp, vp, pt, sl, cl, interpret=True)
    od, md, ld = paged.paged_decode_partial_pallas(
        q[:, :, :, 0, :], kp, vp, pt, kvl, interpret=True)
    np.testing.assert_array_equal(np.asarray(mv[..., 0]), np.asarray(md))
    np.testing.assert_array_equal(np.asarray(lv[..., 0]), np.asarray(ld))
    np.testing.assert_array_equal(np.asarray(ov[:, :, :, 0]),
                                  np.asarray(od))
    k_dense = paged.gather_pages(kp, pt)
    v_dense = paged.gather_pages(vp, pt)
    m_tol = _score_order_bound(q, k_dense, 1.0 / d ** 0.5)
    # jnp triplet oracle (order-free softmax pieces)
    orf, mrf, lrf = paged_ver.paged_verify_partial_ref(q, kp, vp, pt, sl,
                                                       cl)
    np.testing.assert_allclose(np.asarray(mv), np.asarray(mrf), rtol=0,
                               atol=m_tol)
    np.testing.assert_allclose(np.asarray(lv), np.asarray(lrf), atol=1e-4)
    np.testing.assert_allclose(np.asarray(ov), np.asarray(orf), atol=1e-3)
    # dense decode kernel on the gathered contiguous view, row by row
    for i in range(q.shape[0]):
        o3, m3, l3 = decode.decode_partial_pallas(
            q[i, :, :, 0, :], jnp.swapaxes(k_dense[i], 0, 1),
            jnp.swapaxes(v_dense[i], 0, 1), block_kv=8,
            kv_len=int(kvl[i]), interpret=True)
        np.testing.assert_allclose(np.asarray(mv[i, :, :, 0]),
                                   np.asarray(m3), rtol=0, atol=m_tol)
        np.testing.assert_allclose(np.asarray(lv[i, :, :, 0]),
                                   np.asarray(l3), atol=1e-5)
        np.testing.assert_allclose(np.asarray(ov[i, :, :, 0]),
                                   np.asarray(o3), atol=1e-4)


@pytest.mark.parametrize("use_hfa", [False, True])
def test_paged_verify_rows_match_paged_decode_positions(use_hfa):
    """Each verify column i scores position seq_lens + i: its triplet
    must equal a paged_decode call with kv_len = seq_lens + i + 1 -
    including through the FIX16 H-FA datapath (identical page walk,
    identical quantized numerics)."""
    from repro.kernels import paged_decode as paged
    from repro.kernels import paged_verify as paged_ver
    kw = 4
    q, kp, vp, pt, sl, cl = _verify_setup(77, kw=kw)
    ov, mv, lv = paged_ver.paged_verify_partial_pallas(
        q, kp, vp, pt, sl, cl, use_hfa=use_hfa, interpret=True)
    for i in range(kw):
        od, md, ld = paged.paged_decode_partial_pallas(
            q[:, :, :, i, :], kp, vp, pt, sl + i + 1, use_hfa=use_hfa,
            interpret=True)
        np.testing.assert_allclose(np.asarray(mv[..., i]), np.asarray(md),
                                   atol=0)
        np.testing.assert_allclose(np.asarray(lv[..., i]), np.asarray(ld),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(ov[:, :, :, i]),
                                   np.asarray(od), atol=1e-4)


def test_paged_verify_ragged_chunks_and_free_slot():
    """Ragged chunk_lens: a free slot (cl == 0) emits a zero triplet,
    short rows only attend KV below seq_lens + chunk_lens, and live
    rows are untouched by junk in other slots' pages."""
    from repro.kernels import paged_verify as paged_ver
    q, kp, vp, pt, sl, cl = _verify_setup(91, b=3, kw=4)
    sl = sl.at[1].set(0)
    cl = jnp.asarray(np.array([4, 0, 2], np.int32))
    ov, mv, lv = paged_ver.paged_verify_partial_pallas(
        q, kp, vp, pt, sl, cl, interpret=True)
    assert np.all(np.asarray(ov)[1] == 0.0)
    assert np.all(np.asarray(lv)[1] == 0.0)
    orf, mrf, lrf = paged_ver.paged_verify_partial_ref(q, kp, vp, pt, sl,
                                                       cl)
    # live columns agree with the oracle (garbage columns excluded)
    for b, k_real in ((0, 4), (2, 2)):
        np.testing.assert_allclose(np.asarray(ov)[b, :, :, :k_real],
                                   np.asarray(orf)[b, :, :, :k_real],
                                   atol=1e-3)
        np.testing.assert_allclose(np.asarray(lv)[b, :, :, :k_real],
                                   np.asarray(lrf)[b, :, :, :k_real],
                                   atol=1e-4)


def test_ops_paged_verify_jnp_matches_pallas_and_decode():
    """ops.paged_verify_attention: the jnp gather path (CPU serving) ==
    the Pallas kernel path, and K = 1 == ops.paged_decode_attention."""
    from repro.kernels import paged_verify as paged_ver  # noqa: F401
    q, kp, vp, pt, sl, cl = _verify_setup(93, kw=4)
    b, hkv, g, kw, d = q.shape
    q4 = jnp.swapaxes(q.reshape(b, hkv * g, kw, d), 1, 2)   # (B, K, H, d)
    for impl, tol in (("fa2_pallas", 1e-5), ("hfa_pallas", 2e-2)):
        a = np.asarray(ops.paged_verify_attention(
            q4, kp, vp, pt, sl, cl, impl=impl, force_pallas=True))
        jj = np.asarray(ops.paged_verify_attention(
            q4, kp, vp, pt, sl, cl, impl=impl))
        np.testing.assert_allclose(a, jj, atol=tol)
    one = np.asarray(ops.paged_verify_attention(
        q4[:, :1], kp, vp, pt, sl, jnp.ones_like(cl), impl="fa2"))
    dec = np.asarray(ops.paged_decode_attention(
        q4[:, :1], kp, vp, pt, sl + 1, impl="fa2"))
    np.testing.assert_allclose(one, dec, atol=1e-5)


# ------------------------- paged decode blocks vs the page-at-a-time walk
# (Hkv, G, table width): qwen3-1.7b, qwen1.5-4b (its width no multiple of
# the block) and a TP-4 shard of qwen3-1.7b; d 128, page 16.  The fp pool
# is f32: off the TPU, XLA may sum a bf16 tile's (1, 16) x (16, 128) dot
# in another order inside each kernel (paged_verify against the parent
# decode kernel too), while both kernels agree bit for bit on the chip.
# Every rail x codec at qwen3-1.7b's heads; both rails and all three
# codecs at the other two.
DECODE_BLOCK_CASES = (
    [(hfa, name, 8, 2, 64) for hfa in (False, True)
     for name in ("fp", "int8", "log16")]
    + [(False, "fp", 20, 1, 21), (True, "int8", 20, 1, 21),
       (False, "log16", 20, 1, 21)]
    + [(True, "fp", 2, 2, 128), (False, "int8", 2, 2, 128),
       (True, "log16", 2, 2, 128)])


@pytest.mark.parametrize("use_hfa,name,hkv,g,width", DECODE_BLOCK_CASES)
def test_paged_decode_blocks_bit_equal_page_walk(use_hfa, name, hkv, g,
                                                 width):
    """The decode kernel walks a slot's live pages a block at a time,
    every KV head per grid step; its triplets must equal paged_verify at
    K = 1, which walks one (slot, head, page) tile per grid step, bit for
    bit.  Slots of one page, of exactly one block, of one block + 1
    token and of the full width, and a free slot; the table shuffled,
    with pages shared between slots.  The oracle walks each slot's live
    pages only: a masked page is the identity on both rails."""
    from repro.kernels import page_codec
    from repro.kernels import paged_decode as paged
    from repro.kernels import paged_verify as paged_ver
    page, d = 16, 128
    rng = np.random.default_rng(hkv * 100 + width)
    num_pages = 5 * width + 2
    c = page_codec.get_codec(name)
    shape = (num_pages, hkv, page, d)
    kd, ks = c.encode(_rand(shape, jnp.float32, 601))
    vd, vs = c.encode(_rand(shape, jnp.float32, 602))
    ck = dict(codec=None if name == "fp" else c, k_scales=ks, v_scales=vs)
    ppb = paged.pages_per_block(kd, width)
    assert ppb < width and (hkv != 20 or width % ppb)
    lens = np.array([page, ppb * page, ppb * page + 1, width * page, 0],
                    np.int32)
    pt = rng.permutation(num_pages)[:5 * width].reshape(5, width)
    pt[2, :ppb] = pt[1, :ppb]                  # shared with slot 1
    pt[3, ::7] = pt[0, 0]                      # one page, several times
    pt = jnp.asarray(pt.astype(np.int32))
    q = _rand((5, hkv, g, d), jnp.float32, 603)
    o, m, l = paged.paged_decode_partial_pallas(
        q, kd, vd, pt, jnp.asarray(lens), use_hfa=use_hfa, interpret=True,
        **ck)
    for b, n in enumerate(lens):
        if n == 0:
            np.testing.assert_array_equal(np.asarray(o[b]), 0.0)
            np.testing.assert_array_equal(np.asarray(m[b]),
                                          np.float32(decode.NEG_INF))
            np.testing.assert_array_equal(np.asarray(l[b]), 0.0)
            continue
        live = -(-int(n) // page)
        ov, mv, lv = paged_ver.paged_verify_partial_pallas(
            q[b:b + 1, :, :, None], kd, vd, pt[b:b + 1, :live],
            jnp.asarray([n - 1], jnp.int32), jnp.ones((1,), jnp.int32),
            use_hfa=use_hfa, interpret=True, **ck)
        np.testing.assert_array_equal(np.asarray(o[b]),
                                      np.asarray(ov[0, :, :, 0]))
        np.testing.assert_array_equal(np.asarray(m[b]),
                                      np.asarray(mv[0, ..., 0]))
        np.testing.assert_array_equal(np.asarray(l[b]),
                                      np.asarray(lv[0, ..., 0]))


# ------------------------------------ COW fork golden parity (groups)
def _cow_tables(seed, *, b=2, hkv=2, g=2, d=64, page=8, pages_each=3,
                kw=1):
    """Two page-table views of identical KV: ``shared`` aliases one
    physical page set across both slots (a COW fork before any
    divergence), ``mat`` backs slot 1 with a materialized byte-for-byte
    copy into fresh pages (what a non-COW engine would allocate)."""
    from repro.kernels import paged_prefill as paged_pf
    rng = np.random.default_rng(seed)
    num_pages = 2 * pages_each + 2               # room for the copies
    kp = _rand((num_pages, hkv, page, d), jnp.float32, seed + 1)
    vp = _rand((num_pages, hkv, page, d), jnp.float32, seed + 2)
    src = rng.permutation(pages_each).astype(np.int32)       # slot 0 pages
    dst = (pages_each + rng.permutation(pages_each)).astype(np.int32)
    kp = paged_pf.copy_pages(kp, jnp.asarray(src), jnp.asarray(dst))
    vp = paged_pf.copy_pages(vp, jnp.asarray(src), jnp.asarray(dst))
    shared = jnp.asarray(np.stack([src, src]))
    mat = jnp.asarray(np.stack([src, dst]))
    sl = jnp.asarray(
        rng.integers(1, pages_each * page - kw + 1, b).astype(np.int32))
    q = _rand((b, hkv, g, kw, d), jnp.float32, seed + 3)
    return q, kp, vp, shared, mat, sl


@pytest.mark.parametrize("use_hfa", [False, True])
def test_paged_decode_forked_table_bit_equal_materialized(use_hfa):
    """A decode step over a COW-shared page table (fork: two slots, one
    physical page set) must be BIT-equal to the same step over a
    materialized copy - page aliasing is invisible to the kernel, on
    the fp and FIX16 H-FA rails alike.  This is what makes sequence
    groups free: a fork costs refcounts, never numerics."""
    from repro.kernels import paged_decode as paged
    q, kp, vp, shared, mat, sl = _cow_tables(201)
    q1 = q[:, :, :, 0, :]
    o_s, m_s, l_s = paged.paged_decode_partial_pallas(
        q1, kp, vp, shared, sl, use_hfa=use_hfa, interpret=True)
    o_m, m_m, l_m = paged.paged_decode_partial_pallas(
        q1, kp, vp, mat, sl, use_hfa=use_hfa, interpret=True)
    np.testing.assert_array_equal(np.asarray(o_s), np.asarray(o_m))
    np.testing.assert_array_equal(np.asarray(m_s), np.asarray(m_m))
    np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_m))


@pytest.mark.parametrize("use_hfa", [False, True])
def test_paged_verify_forked_table_bit_equal_materialized(use_hfa):
    """Same contract for the K-token verify walk: a speculative step
    over a forked (COW-shared) table == the materialized copy, bit for
    bit, fa2 + hfa."""
    from repro.kernels import paged_verify as paged_ver
    kw = 3
    q, kp, vp, shared, mat, sl = _cow_tables(203, kw=kw)
    cl = jnp.full((2,), kw, jnp.int32)
    # KV for the verify columns is pre-written in the pools; aliasing
    # covers it identically by construction of _cow_tables.
    o_s, m_s, l_s = paged_ver.paged_verify_partial_pallas(
        q, kp, vp, shared, sl, cl, use_hfa=use_hfa, interpret=True)
    o_m, m_m, l_m = paged_ver.paged_verify_partial_pallas(
        q, kp, vp, mat, sl, cl, use_hfa=use_hfa, interpret=True)
    np.testing.assert_array_equal(np.asarray(o_s), np.asarray(o_m))
    np.testing.assert_array_equal(np.asarray(m_s), np.asarray(m_m))
    np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_m))


@pytest.mark.parametrize("impl", ["fa2", "hfa_pallas"])
def test_ops_paged_jnp_forked_table_bit_equal_materialized(impl):
    """The jnp gather paths (CPU serving) honor the same aliasing
    contract end to end through ops.paged_{decode,verify}_attention."""
    q, kp, vp, shared, mat, sl = _cow_tables(207, kw=2)
    b, hkv, g, kw, d = q.shape
    q4 = jnp.swapaxes(q.reshape(b, hkv * g, kw, d), 1, 2)
    cl = jnp.full((b,), kw, jnp.int32)
    v_s = np.asarray(ops.paged_verify_attention(q4, kp, vp, shared, sl, cl,
                                                impl=impl))
    v_m = np.asarray(ops.paged_verify_attention(q4, kp, vp, mat, sl, cl,
                                                impl=impl))
    np.testing.assert_array_equal(v_s, v_m)
    d_s = np.asarray(ops.paged_decode_attention(q4[:, :1], kp, vp, shared,
                                                sl, impl=impl))
    d_m = np.asarray(ops.paged_decode_attention(q4[:, :1], kp, vp, mat,
                                                sl, impl=impl))
    np.testing.assert_array_equal(d_s, d_m)


# ------------------------------------------- page codecs (quantized KV)
PAGE_CODECS = ["fp", "int8", "log16"]


@pytest.mark.parametrize("name", PAGE_CODECS)
def test_page_codec_roundtrip(name):
    """Per-codec encode/decode contract: fp is the identity (bit-exact);
    int8 per-row absmax error is bounded by half a quantization step
    and all-zero rows survive exactly; log16's stored uint16 IS the
    BFloat16 bit pattern, so its roundtrip equals a bf16 cast exactly."""
    from repro.kernels import page_codec
    c = page_codec.get_codec(name)
    x = _rand((3, 8, 2, 64), jnp.float32, 301)
    x = x.at[1, 2].set(0.0)                       # an all-zero token row
    data, scales = c.encode(x)
    y = np.asarray(c.decode(data, scales))
    if name == "fp":
        assert scales is None
        np.testing.assert_array_equal(y, np.asarray(x))
    elif name == "int8":
        assert data.dtype == jnp.int8
        assert scales.shape == x.shape[:-1] + (1,)
        err = np.abs(y - np.asarray(x))
        bound = 0.5 * np.asarray(scales) * (1 + 1e-5) + 1e-7
        assert (err <= bound).all(), float((err - bound).max())
        np.testing.assert_array_equal(y[1, 2], 0.0)
    else:
        assert data.dtype == jnp.uint16 and scales is None
        ref = np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32))
        np.testing.assert_array_equal(y, ref)


@pytest.mark.parametrize("name", PAGE_CODECS)
def test_page_codec_pool_byte_accounting(name):
    """The pool arrays `stack_init_paged_cache` actually allocates (data
    + scale sidecars) match the codec's declared `bytes_per_token`, the
    single source of truth the engine and the benchmark scoreboard use
    for slots-at-equal-pool-bytes."""
    from repro.configs import get_config
    from repro.kernels import page_codec
    from repro.models import transformer
    cfg = get_config("qwen3-1.7b").reduced()
    num_pages, page = 6, 8
    layers = transformer.stack_init_paged_cache(
        cfg, num_pages, page, jnp.float32, codec=name)
    total = sum(int(a.nbytes) for d in layers.values()
                for a in d.values())
    expect = (cfg.n_layers * num_pages * page *
              page_codec.bytes_per_token(name, cfg.n_kv_heads,
                                         cfg.d_head, jnp.float32))
    assert total == expect
    keys = set(next(iter(layers.values())))
    want = {"k_pages", "v_pages"} | (
        {"k_scale", "v_scale"} if name == "int8" else set())
    assert keys == want


def _codec_pools(name, kp, vp):
    """Encode raw f32 pools; read rule: fp stays on codec=None (the
    byte-identical pre-codec path), quantized codecs pass themselves."""
    from repro.kernels import page_codec
    c = page_codec.get_codec(name)
    kd, ks = c.encode(kp)
    vd, vs = c.encode(vp)
    rc = None if c.name == "fp" else c
    return kd, vd, dict(codec=rc, k_scales=ks, v_scales=vs)


# Output drift vs the raw fp pool, both rails (fp must be bit-exact;
# int8/log16 bounds are ~4x the drift measured on N(0,1) pools).
PAGE_CODEC_ATOL = {"fp": 0.0, "int8": 5e-2, "log16": 5e-2}


@pytest.mark.parametrize("impl,pal_atol", [("fa2_pallas", 1e-4),
                                           ("hfa_pallas", 2e-2)])
@pytest.mark.parametrize("name", PAGE_CODECS)
def test_paged_codec_parity_matrix(name, impl, pal_atol):
    """codec x rail x op parity matrix through the ops wrappers: for
    each of paged decode/prefill/verify, (1) the codec path tracks the
    raw fp pool within the documented atol (fp: bit-exact), and (2) the
    Pallas kernel (dequant in the tile loop) matches the jnp gather
    fallback (dequant on the gathered view) within rail tolerance."""
    kw = 4
    q, kp, vp, pt, sl, cl = _verify_setup(400, kw=kw)
    b, hkv, g, _, d = q.shape
    q4 = jnp.swapaxes(q.reshape(b, hkv * g, kw, d), 1, 2)  # (B,kw,H,d)
    kd, vd, ck = _codec_pools(name, kp, vp)

    def runs(tag, call):
        ref = np.asarray(call(kp, vp, {}))               # raw fp pool
        y_jnp = np.asarray(call(kd, vd, ck))
        y_pal = np.asarray(call(kd, vd, {**ck, "force_pallas": True}))
        if name == "fp":
            np.testing.assert_array_equal(y_jnp, ref, err_msg=tag)
        else:
            np.testing.assert_allclose(y_jnp, ref, err_msg=tag,
                                       atol=PAGE_CODEC_ATOL[name])
        np.testing.assert_allclose(y_pal, y_jnp, atol=pal_atol,
                                   err_msg=tag)

    runs("decode", lambda k, v, e: ops.paged_decode_attention(
        q4[:, :1], k, v, pt, sl + 1, impl=impl, **e))
    runs("verify", lambda k, v, e: ops.paged_verify_attention(
        q4, k, v, pt, sl, cl, impl=impl, **e))
    runs("prefill", lambda k, v, e: ops.paged_prefill_attention(
        q4, k, v, pt, sl, cl, impl=impl, **e))


@pytest.mark.parametrize("impl", ["fa2_pallas", "hfa_pallas"])
@pytest.mark.parametrize("name", ["int8", "log16"])
def test_paged_codec_cow_fork_and_rollback(name, impl):
    """Encoded pools honor the COW contracts: (1) a forked (page-
    aliased) table is BIT-equal to a materialized copy when the scale
    sidecars ride the same `copy_pages`; (2) rows past seq_len - the
    stale encodings (and stale scales) a speculative rollback leaves
    behind - never reach the output, so rollback stays a pure seq_len
    decrement for every codec."""
    from repro.kernels import page_codec
    from repro.kernels import paged_prefill as paged_pf
    rng = np.random.default_rng(501)
    b, hkv, h, d, page, pages_each, kw = 2, 2, 4, 64, 8, 3, 2
    num_pages = 2 * pages_each + 2
    kp = _rand((num_pages, hkv, page, d), jnp.float32, 502)
    vp = _rand((num_pages, hkv, page, d), jnp.float32, 503)
    c = page_codec.get_codec(name)
    kd, ks = c.encode(kp)
    vd, vs = c.encode(vp)
    src = rng.permutation(pages_each).astype(np.int32)
    dst = (pages_each + rng.permutation(pages_each)).astype(np.int32)
    sj, dj = jnp.asarray(src), jnp.asarray(dst)
    kd = paged_pf.copy_pages(kd, sj, dj)
    vd = paged_pf.copy_pages(vd, sj, dj)
    if ks is not None:
        ks = paged_pf.copy_pages(ks, sj, dj)
        vs = paged_pf.copy_pages(vs, sj, dj)
    shared = jnp.asarray(np.stack([src, src]))
    mat = jnp.asarray(np.stack([src, dst]))
    sl = jnp.asarray(rng.integers(1, pages_each * page - kw + 1,
                                  b).astype(np.int32))
    cl = jnp.full((b,), kw, jnp.int32)
    q = _rand((b, kw, h, d), jnp.float32, 504)
    ck = dict(impl=impl, codec=c, k_scales=ks, v_scales=vs,
              force_pallas=True)
    v_s = np.asarray(ops.paged_verify_attention(q, kd, vd, shared, sl,
                                                cl, **ck))
    v_m = np.asarray(ops.paged_verify_attention(q, kd, vd, mat, sl, cl,
                                                **ck))
    np.testing.assert_array_equal(v_s, v_m)
    d_s = np.asarray(ops.paged_decode_attention(q[:, :1], kd, vd, shared,
                                                sl, **ck))
    d_m = np.asarray(ops.paged_decode_attention(q[:, :1], kd, vd, mat,
                                                sl, **ck))
    np.testing.assert_array_equal(d_s, d_m)
    # Rollback half: trash every encoded row (and scale) at positions
    # >= sl + kw; the reads above are bounded by seq/chunk lens, so the
    # outputs must not move by a single bit.
    keep = np.zeros((num_pages, page), bool)      # (P, row) rows read
    mat_np = np.asarray(mat)
    for i in range(b):
        for pos in range(int(sl[i]) + kw):
            keep[mat_np[i, pos // page], pos % page] = True
    kp_i, row_i = np.nonzero(keep)                # pools: (P, Hkv, row, .)
    jr = np.random.default_rng(505)
    pools = {"k": np.array(kd), "v": np.array(vd)}
    for key, orig in (("k", kd), ("v", vd)):
        a = pools[key]
        a[...] = jr.integers(1, 120, a.shape).astype(a.dtype)
        a[kp_i, :, row_i] = np.asarray(orig)[kp_i, :, row_i]
    ksx, vsx = ks, vs
    if ks is not None:
        ksx, vsx = np.array(ks), np.array(vs)
        for a, orig in ((ksx, ks), (vsx, vs)):
            a[...] = jr.standard_normal(a.shape).astype(a.dtype)
            a[kp_i, :, row_i] = np.asarray(orig)[kp_i, :, row_i]
    ck2 = dict(impl=impl, codec=c, k_scales=None if ksx is None
               else jnp.asarray(ksx),
               v_scales=None if vsx is None else jnp.asarray(vsx),
               force_pallas=True)
    kdx, vdx = jnp.asarray(pools["k"]), jnp.asarray(pools["v"])
    v_j = np.asarray(ops.paged_verify_attention(q, kdx, vdx, mat, sl, cl,
                                                **ck2))
    np.testing.assert_array_equal(v_j, v_m)
    d_j = np.asarray(ops.paged_decode_attention(q[:, :1], kdx, vdx, mat,
                                                sl, **ck2))
    np.testing.assert_array_equal(d_j, d_m)
