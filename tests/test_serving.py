"""Paged KV-cache + continuous-batching serving subsystem tests.

Covers, per the subsystem spec:
  * paged_decode Pallas kernel (interpret mode) vs the dense decode
    kernel / exact reference, float and HFA datapaths;
  * page scatter/gather ops;
  * PagedKVCache alloc/free/reuse invariants (randomized trace);
  * Scheduler admission/preemption/retirement (randomized trace, no jax);
  * model-level paged vs dense logits parity and engine-level greedy
    token parity under churn + preemption.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import decode, ops
from repro.kernels import paged_decode as paged
from repro.kernels import paged_prefill as paged_pf
from repro.serving import PagedKVCache, Request, Scheduler, ServingEngine


def _rand(shape, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _paged_setup(seed, *, b=3, hkv=2, g=4, d=32, page=16, pages_each=4,
                 extra_pages=3, dtype=jnp.float32):
    """Random pools + a shuffled page table + ragged per-seq lengths."""
    rng = np.random.default_rng(seed)
    num_pages = b * pages_each + extra_pages
    q = _rand((b, hkv, g, d), seed + 1, dtype)
    k_pages = _rand((num_pages, hkv, page, d), seed + 2, dtype)
    v_pages = _rand((num_pages, hkv, page, d), seed + 3, dtype)
    perm = rng.permutation(num_pages)[:b * pages_each]
    page_table = jnp.asarray(perm.reshape(b, pages_each).astype(np.int32))
    kv_lens = jnp.asarray(
        rng.integers(1, pages_each * page + 1, b).astype(np.int32))
    return q, k_pages, v_pages, page_table, kv_lens


def _dense_view(k_pages, page_table):
    return np.asarray(paged.gather_pages(k_pages, page_table))


# ------------------------------------------------------------- kernel
@pytest.mark.parametrize("seed", [0, 1])
def test_paged_kernel_matches_dense_kernel_float(seed):
    """Same KV through the paged kernel (page gather) and the dense
    kernel (contiguous) must agree to float roundoff."""
    q, kp, vp, pt, kvl = _paged_setup(seed)
    o, m, l = paged.paged_decode_partial_pallas(q, kp, vp, pt, kvl,
                                                interpret=True)
    out = np.asarray(decode.finalize_decode(o, l))
    k_dense = paged.gather_pages(kp, pt)
    v_dense = paged.gather_pages(vp, pt)
    for i in range(q.shape[0]):     # dense kernel takes one kv_len at a time
        od, md, ld = decode.decode_partial_pallas(
            q[i], jnp.swapaxes(k_dense[i], 0, 1),
            jnp.swapaxes(v_dense[i], 0, 1),
            block_kv=16, kv_len=int(kvl[i]), interpret=True)
        gold = np.asarray(decode.finalize_decode(od, ld))
        np.testing.assert_allclose(out[i], gold, atol=1e-5)


@pytest.mark.parametrize("seed", [2, 3])
def test_paged_kernel_hfa_error_envelope(seed):
    """HFA paged decode carries the same quantization-error envelope as
    the dense HFA decode kernel (vs the exact float reference)."""
    q, kp, vp, pt, kvl = _paged_setup(seed)
    o, m, l = paged.paged_decode_partial_pallas(q, kp, vp, pt, kvl,
                                                use_hfa=True,
                                                interpret=True)
    out = np.asarray(decode.finalize_decode(o, l, use_hfa=True))
    k_dense = paged.gather_pages(kp, pt)
    v_dense = paged.gather_pages(vp, pt)
    for i in range(q.shape[0]):
        kvl_i = int(kvl[i])
        ki = k_dense[i, :kvl_i]
        vi = v_dense[i, :kvl_i]
        s = np.asarray(jnp.einsum("hgd,shd->hgs", q[i], ki)) / np.sqrt(
            q.shape[-1])
        p = np.exp(s - s.max(-1, keepdims=True))
        gold = np.einsum("hgs,shd->hgd", p / p.sum(-1, keepdims=True),
                         np.asarray(vi))
        od, md, ld = decode.decode_partial_pallas(
            q[i], jnp.swapaxes(k_dense[i], 0, 1),
            jnp.swapaxes(v_dense[i], 0, 1),
            block_kv=16, kv_len=kvl_i, use_hfa=True, interpret=True)
        dense_hfa = np.asarray(decode.finalize_decode(od, ld, use_hfa=True))
        err_paged = np.abs(out[i] - gold).max()
        err_dense = np.abs(dense_hfa - gold).max()
        # same envelope as the dense HFA decode kernel: the paged walk
        # must not amplify the PWL/FIX16 quantization error
        assert err_paged <= max(2.0 * err_dense, 1e-3), \
            (err_paged, err_dense)
        assert err_paged < 2e-1     # absolute sanity cap


def test_paged_kernel_free_slot_zero():
    q, kp, vp, pt, kvl = _paged_setup(7)
    kvl = kvl.at[1].set(0)
    o, m, l = paged.paged_decode_partial_pallas(q, kp, vp, pt, kvl,
                                                interpret=True)
    out = np.asarray(decode.finalize_decode(o, l))
    assert np.all(out[1] == 0.0)
    assert np.all(np.asarray(l)[1] == 0.0)


@pytest.mark.parametrize("use_hfa", [False, True])
def test_ops_paged_jnp_matches_pallas(use_hfa):
    """The jnp gather path (CPU serving) == the Pallas kernel path."""
    q, kp, vp, pt, kvl = _paged_setup(11)
    b, hkv, g, d = q.shape
    q4 = q.reshape(b, 1, hkv * g, d)
    impl = "hfa_pallas" if use_hfa else "fa2_pallas"
    a = np.asarray(ops.paged_decode_attention(q4, kp, vp, pt, kvl,
                                              impl=impl, force_pallas=True))
    jj = np.asarray(ops.paged_decode_attention(q4, kp, vp, pt, kvl,
                                               impl=impl))
    tol = 2e-2 if use_hfa else 1e-5
    np.testing.assert_allclose(a, jj, atol=tol)


def test_ops_paged_matches_dense_decode():
    """ops.paged_decode_attention == ops.decode_attention on the same KV."""
    q, kp, vp, pt, kvl = _paged_setup(13)
    b, hkv, g, d = q.shape
    q4 = q.reshape(b, 1, hkv * g, d)
    out = np.asarray(ops.paged_decode_attention(q4, kp, vp, pt, kvl,
                                                impl="fa2"))
    k_dense = paged.gather_pages(kp, pt)
    v_dense = paged.gather_pages(vp, pt)
    for i in range(b):
        gold = np.asarray(ops.decode_attention(
            q4[i:i + 1], k_dense[i:i + 1], v_dense[i:i + 1], impl="fa2",
            kv_len=int(kvl[i])))
        np.testing.assert_allclose(out[i], gold[0], atol=1e-5)


# ------------------------------------------------------ page cache ops
def test_append_and_prefill_write_roundtrip():
    page, hkv, d = 8, 2, 16
    kp = jnp.zeros((6, hkv, page, d))
    vp = jnp.zeros((6, hkv, page, d))
    pt = jnp.asarray(np.array([[4, 1, 3], [5, 0, 2]], np.int32))
    k_new = _rand((2, 11, hkv, d), 21)
    v_new = _rand((2, 11, hkv, d), 22)
    kp, vp = paged.write_prefill_kv(kp, vp, k_new, v_new, pt)
    got = _dense_view(kp, pt)
    np.testing.assert_allclose(got[:, :11], np.asarray(k_new))
    assert np.all(got[:, 11:] == 0.0)

    # append one token per row at position 11
    k1 = _rand((2, 1, hkv, d), 23)
    v1 = _rand((2, 1, hkv, d), 24)
    sl = jnp.asarray(np.array([11, 11], np.int32))
    kp2, vp2 = paged.append_kv(kp, vp, k1, v1, pt, sl)
    got = _dense_view(kp2, pt)
    np.testing.assert_allclose(got[:, 11], np.asarray(k1[:, 0]))
    np.testing.assert_allclose(got[:, :11], np.asarray(k_new))

    # free slot (seq_len 0): write must be dropped entirely
    sl0 = jnp.asarray(np.array([0, 12], np.int32))
    kp3, _ = paged.append_kv(kp2, vp2, k1, v1, pt, sl0)
    np.testing.assert_allclose(_dense_view(kp3, pt)[0],
                               _dense_view(kp2, pt)[0])


# ------------------------------------------------- host page bookkeeping
def test_paged_cache_alloc_free_reuse():
    c = PagedKVCache(num_pages=8, page_size=4, max_batch=3, pages_per_seq=4)
    s0 = c.alloc_slot(5)            # 2 pages
    s1 = c.alloc_slot(9)            # 3 pages
    c.check_invariants()
    assert c.free_page_count == 3
    assert not c.can_admit(16)      # would need 5 pages > pages_per_seq
    assert c.can_admit(11)
    # 12 tokens exactly fill 3 pages: admission reserves the decode
    # append's page too, so with only 3 free this must be refused.
    assert not c.can_admit(12)
    with pytest.raises(RuntimeError):
        c.alloc_slot(16)
    # growth across a page boundary
    assert c.ensure_append_capacity(s0)     # pos 5 fits page 2
    c.advance(s0)
    for _ in range(2):
        assert c.ensure_append_capacity(s0)
        c.advance(s0)
    assert int(c.seq_lens[s0]) == 8
    assert c.ensure_append_capacity(s0)     # pos 8 -> needs page 3
    c.check_invariants()
    # exhaustion: grow s1 until the pool dries up
    grown = 0
    while c.ensure_append_capacity(s1):
        c.advance(s1)
        grown += 1
        if grown > 64:
            raise AssertionError("never exhausted")
    c.check_invariants()
    # free recycles everything
    c.free_slot(s0)
    c.free_slot(s1)
    c.check_invariants()
    assert c.free_page_count == 8 and c.free_slot_count == 3
    assert np.all(c.page_table == 0) and np.all(c.seq_lens == 0)


def test_paged_cache_randomized_trace():
    rng = np.random.default_rng(0)
    c = PagedKVCache(num_pages=24, page_size=4, max_batch=6,
                     pages_per_seq=6)
    live: list[int] = []
    for _ in range(400):
        op = rng.random()
        if op < 0.35 and c.free_slot_count:
            plen = int(rng.integers(1, 17))
            if c.can_admit(plen):
                live.append(c.alloc_slot(plen))
        elif op < 0.75 and live:
            slot = live[rng.integers(len(live))]
            if c.ensure_append_capacity(slot):
                c.advance(slot)
        elif live:
            live.remove(slot := live[rng.integers(len(live))])
            c.free_slot(slot)
        c.check_invariants()


def test_scheduler_randomized_trace():
    """Admission/preemption/retirement over a random request stream,
    driven without any model - pure host logic."""
    rng = np.random.default_rng(1)
    cache = PagedKVCache(num_pages=10, page_size=4, max_batch=3,
                         pages_per_seq=5)
    sched = Scheduler(cache)
    n_req = 25
    for i in range(n_req):
        sched.submit(Request(rid=i, prompt=[1] * int(rng.integers(1, 9)),
                             max_new_tokens=int(rng.integers(1, 8)),
                             eos_id=7))
    finished = []
    for step in range(500):
        if not sched.has_work:
            break
        for slot, tokens in sched.admit():
            st = sched.record_token(slot, int(rng.integers(0, 9)))
            if st != "running":
                finished.append(sched.retire(slot, st))
        for slot in sorted(sched.running):
            if not cache.ensure_append_capacity(slot):
                sched.preempt(slot)
        for slot in sorted(sched.running):
            cache.advance(slot)
            st = sched.record_token(slot, int(rng.integers(0, 9)))
            if st != "running":
                finished.append(sched.retire(slot, st))
        cache.check_invariants()
    assert sorted(f.rid for f in finished) == list(range(n_req))
    for f in finished:
        assert f.reason in ("eos", "length")
        if f.reason == "eos":
            assert f.tokens[-1] == 7
        else:
            assert len(f.tokens) >= 1
    cache.check_invariants()


# ------------------------------------------------------- model + engine
@pytest.fixture(scope="module")
def qwen_smoke():
    from repro.configs import get_config
    from repro.models.model import build_model
    cfg = get_config("qwen3-1.7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.mark.parametrize("attn_impl", ["fa2", "hfa"])
def test_model_paged_matches_dense_logits(qwen_smoke, attn_impl):
    """paged prefill+decode logits == dense prefill+decode logits."""
    import dataclasses
    cfg, model, params = qwen_smoke
    if attn_impl != cfg.attn_impl:
        from repro.models.model import build_model
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
        model = build_model(cfg)
    rng = np.random.default_rng(3)
    b, l = 2, 7
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, l)), jnp.int32)
    nxt = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, 1)), jnp.int32)

    cache = model.init_cache(params, b, 32)
    lg_d, cache = model.prefill(params, cache, toks)
    lg_d2, _ = model.decode_step(params, cache, nxt)

    layers = model.init_paged_cache(num_pages=8, page_size=4)
    pt = jnp.asarray(np.array([[3, 5, 1], [2, 6, 0]], np.int32))
    lg_p, layers = model.paged_prefill(params, layers, toks, pt)
    sl = jnp.full((b,), l, jnp.int32)
    lg_p2, _ = model.paged_decode_step(params, layers, nxt, pt, sl)

    tol = 1e-4 if attn_impl == "hfa" else 1e-5
    np.testing.assert_allclose(np.asarray(lg_p[:, -1:]), np.asarray(lg_d),
                               atol=tol)
    np.testing.assert_allclose(np.asarray(lg_p2), np.asarray(lg_d2),
                               atol=tol)


def test_engine_matches_dense_generation_under_churn(qwen_smoke):
    """Greedy tokens from the continuous-batching engine == a dense
    fixed-cache loop per request, across churn and preemptions."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(5)
    engine = ServingEngine(model, params, max_batch=3, page_size=4,
                           num_pages=9, max_seq=40)
    reqs = []
    for i in range(6):
        plen = int(rng.integers(2, 9))
        reqs.append(Request(rid=i,
                            prompt=rng.integers(
                                1, cfg.vocab_size, plen).tolist(),
                            max_new_tokens=int(rng.integers(3, 9))))
    finished = engine.run([(i, r) for i, r in enumerate(reqs)])
    engine.cache.check_invariants()
    # Retired sequences' published prefix pages park in the cached LRU
    # (claimable by identical prompts); nothing is leaked outright.
    assert engine.cache.available_page_count == engine.cache.num_pages
    assert sorted(f.rid for f in finished) == list(range(6))

    dec = jax.jit(model.decode_step)
    pre = jax.jit(model.prefill)
    for f in finished:
        req = reqs[f.rid]
        cache = model.init_cache(params, 1, 40)
        lg, cache = pre(params, cache,
                        jnp.asarray([req.prompt], jnp.int32))
        want = [int(jnp.argmax(lg[0, -1]))]
        for _ in range(req.max_new_tokens - 1):
            lg, cache = dec(params, cache,
                            jnp.asarray([[want[-1]]], jnp.int32))
            want.append(int(jnp.argmax(lg[0, -1])))
        assert f.tokens == want, (f.rid, f.preemptions)


def test_engine_counts_decode_kv_pages(qwen_smoke):
    """decode_kv_pages sums each decoding slot's live pages, the pages
    the decode kernel walks per layer; decode_kv_table_pages the slots x
    page-table width a page-by-page walk covers.  Page 4, prompts of 5
    and 9 tokens, 4 and 6 tokens out: the first token comes from the
    prefill, decode j of a request attends prompt + j tokens."""
    _, model, params = qwen_smoke
    engine = ServingEngine(model, params, max_batch=2, page_size=4,
                           max_seq=40)
    engine.run([(0, Request(rid=0, prompt=list(range(1, 6)),
                            max_new_tokens=4)),
                (0, Request(rid=1, prompt=list(range(1, 10)),
                            max_new_tokens=6))])
    # rid 0: ceil(6/4) + ceil(7/4) + ceil(8/4) = 2 + 2 + 2
    # rid 1: ceil(10/4) + ... + ceil(14/4) = 3 + 3 + 3 + 4 + 4
    assert engine.stats["decode_kv_pages"] == 6 + 17
    # five decode steps (rid 1's), each 2 slots x a width of 4 pages
    assert engine.stats["decode_steps"] == 5
    assert engine.stats["decode_kv_table_pages"] == 5 * 2 * 4


def test_paged_prefill_single_token_prompt(qwen_smoke):
    """A 1-token prompt is a PREFILL (even though S == 1): its KV must
    land in the pages and its logits must match the dense path."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(9)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, 1)), jnp.int32)

    cache = model.init_cache(params, 1, 8)
    lg_d, cache = model.prefill(params, cache, toks)
    nxt = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, 1)), jnp.int32)
    lg_d2, _ = model.decode_step(params, cache, nxt)

    layers = model.init_paged_cache(num_pages=4, page_size=1)
    pt = jnp.asarray(np.array([[2, 1, 3]], np.int32))
    lg_p, layers = model.paged_prefill(params, layers, toks, pt)
    assert float(jnp.abs(layers["l0"]["k_pages"]).sum()) > 0.0, \
        "prefill KV never written to the pages"
    lg_p2, _ = model.paged_decode_step(params, layers, nxt, pt,
                                       jnp.asarray([1], jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_p[:, -1:]), np.asarray(lg_d),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(lg_p2), np.asarray(lg_d2),
                               atol=1e-5)


def test_engine_page_boundary_prompt(qwen_smoke):
    """Prompt length == a page multiple: the first decode append needs a
    fresh page; generation must still match the dense loop."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(11)
    engine = ServingEngine(model, params, max_batch=2, page_size=4,
                           max_seq=24)
    prompt = rng.integers(1, cfg.vocab_size, 8).tolist()   # 2 full pages
    [fin] = engine.run([(0, Request(rid=0, prompt=prompt,
                                    max_new_tokens=5))])
    cache = model.init_cache(params, 1, 24)
    lg, cache = model.prefill(params, cache,
                              jnp.asarray([prompt], jnp.int32))
    want = [int(jnp.argmax(lg[0, -1]))]
    for _ in range(4):
        lg, cache = model.decode_step(
            params, cache, jnp.asarray([[want[-1]]], jnp.int32))
        want.append(int(jnp.argmax(lg[0, -1])))
    assert fin.tokens == want


def test_engine_rejects_oversized_request(qwen_smoke):
    _, model, params = qwen_smoke
    engine = ServingEngine(model, params, max_batch=2, page_size=4,
                           max_seq=16)
    with pytest.raises(ValueError):
        engine.submit(Request(rid=0, prompt=[1] * 10, max_new_tokens=10))


def test_engine_run_survives_oversized_request(qwen_smoke):
    """An oversized request arriving mid-trace is finished as
    reason="rejected" instead of killing the serving loop."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(17)
    engine = ServingEngine(model, params, max_batch=2, page_size=4,
                           max_seq=16)
    good = lambda rid: Request(
        rid=rid, prompt=rng.integers(1, cfg.vocab_size, 4).tolist(),
        max_new_tokens=4)
    arrivals = [(0, good(0)), (1, Request(rid=1, prompt=[1] * 10,
                                          max_new_tokens=10)),
                (2, good(2))]
    finished = engine.run(arrivals)
    assert sorted(f.rid for f in finished) == [0, 1, 2]
    by_rid = {f.rid: f for f in finished}
    assert by_rid[1].reason == "rejected" and by_rid[1].tokens == []
    for rid in (0, 2):
        assert by_rid[rid].reason in ("eos", "length")
        assert len(by_rid[rid].tokens) == 4
    assert engine.stats["rejected"] == 1


# --------------------------------------------------- chunked prefill
def _golden_greedy(model, params, req, max_seq):
    """Dense fixed-cache greedy loop: the token-exactness oracle."""
    cache = model.init_cache(params, 1, max_seq)
    lg, cache = model.prefill(params, cache,
                              jnp.asarray([req.prompt], jnp.int32))
    want = [int(jnp.argmax(lg[0, -1]))]
    for _ in range(req.max_new_tokens - 1):
        lg, cache = model.decode_step(params, cache,
                                      jnp.asarray([[want[-1]]], jnp.int32))
        want.append(int(jnp.argmax(lg[0, -1])))
    return want


def _chunk_setup(seed, *, b=2, hkv=2, g=2, d=16, page=4, pages_each=6,
                 hist=(8, 5), chunk=(7, 4)):
    """Pools holding per-seq history of ``hist`` tokens, plus a written
    chunk of ``chunk`` tokens starting right after; returns the dense
    full K/V for the oracle."""
    rng = np.random.default_rng(seed)
    num_pages = b * pages_each + 2
    kp = jnp.zeros((num_pages, hkv, page, d), jnp.float32)
    vp = jnp.zeros((num_pages, hkv, page, d), jnp.float32)
    pt = jnp.asarray(rng.permutation(num_pages)[:b * pages_each]
                     .reshape(b, pages_each).astype(np.int32))
    start = np.asarray(hist, np.int32)
    cl = np.asarray(chunk, np.int32)
    total = start + cl
    lmax = int(cl.max())
    k_full = rng.standard_normal((b, int(total.max()), hkv, d)) \
        .astype(np.float32)
    v_full = rng.standard_normal((b, int(total.max()), hkv, d)) \
        .astype(np.float32)
    kp, vp = paged_pf.write_chunk_kv(
        kp, vp, jnp.asarray(k_full[:, :int(start.max())]),
        jnp.asarray(v_full[:, :int(start.max())]), pt,
        jnp.zeros((b,), jnp.int32), jnp.asarray(start))
    k_ch = np.zeros((b, lmax, hkv, d), np.float32)
    v_ch = np.zeros_like(k_ch)
    for i in range(b):
        k_ch[i, :cl[i]] = k_full[i, start[i]:total[i]]
        v_ch[i, :cl[i]] = v_full[i, start[i]:total[i]]
    kp, vp = paged_pf.write_chunk_kv(kp, vp, jnp.asarray(k_ch),
                                     jnp.asarray(v_ch), pt,
                                     jnp.asarray(start), jnp.asarray(cl))
    q = _rand((b, lmax, hkv * g, d), seed + 1)
    return q, kp, vp, pt, start, cl, k_full, v_full


def test_write_chunk_kv_is_position_exact():
    """Chunk writes land at start_pos.. and padding rows are DROPPED -
    pages outside the chunk (shared prefixes, later pages) are never
    touched, unlike the fresh-prefill padded scatter."""
    q, kp, vp, pt, start, cl, k_full, v_full = _chunk_setup(31)
    got = _dense_view(kp, pt)
    for b in range(q.shape[0]):
        total = int(start[b] + cl[b])
        np.testing.assert_allclose(got[b, :total], k_full[b, :total])
        assert np.all(got[b, total:] == 0.0), "padding row was written"


@pytest.mark.parametrize("seed", [41, 42])
def test_paged_prefill_kernel_matches_oracle(seed):
    """Chunk queries at pos start..start+L-1 attending causally over the
    paged history: Pallas kernel (interpret) == jnp gather path == per-row
    dense softmax oracle."""
    q, kp, vp, pt, start, cl, k_full, v_full = _chunk_setup(seed)
    b, lmax, h, d = q.shape
    hkv = kp.shape[1]
    g = h // hkv
    out_jnp = np.asarray(ops.paged_prefill_attention(
        q, kp, vp, pt, jnp.asarray(start), jnp.asarray(cl), impl="fa2"))
    out_pl = np.asarray(ops.paged_prefill_attention(
        q, kp, vp, pt, jnp.asarray(start), jnp.asarray(cl),
        impl="fa2_pallas", force_pallas=True))
    qn = np.asarray(q)
    for i in range(b):
        for li in range(int(cl[i])):
            pos = int(start[i]) + li
            for hh in range(h):
                hk = hh // g
                s = (qn[i, li, hh] @ k_full[i, :pos + 1, hk].T) / np.sqrt(d)
                p = np.exp(s - s.max())
                gold = (p / p.sum()) @ v_full[i, :pos + 1, hk]
                np.testing.assert_allclose(out_jnp[i, li, hh], gold,
                                           atol=1e-5)
                np.testing.assert_allclose(out_pl[i, li, hh], gold,
                                           atol=1e-5)


def test_paged_prefill_kernel_hfa_rowwise_matches_decode_kernel():
    """Each chunk row through the H-FA paged-prefill kernel is
    bit-identical to the same query through the H-FA paged-decode
    kernel (same page walk, same FIX16 datapath) - the chunk dimension
    must not perturb the quantized numerics."""
    q, kp, vp, pt, start, cl, _, _ = _chunk_setup(43)
    b, lmax, h, d = q.shape
    hkv = kp.shape[1]
    g = h // hkv
    qg = jnp.swapaxes(q, 1, 2).reshape(b, hkv, g, lmax, d)
    o, m, l = paged_pf.paged_prefill_partial_pallas(
        qg, kp, vp, pt, jnp.asarray(start), jnp.asarray(start + cl),
        use_hfa=True, interpret=True)
    out = np.asarray(decode.finalize_decode(o, l, use_hfa=True))
    for i in range(b):
        for li in range(int(cl[i])):
            pos = int(start[i]) + li
            od, md, ld = paged.paged_decode_partial_pallas(
                qg[i:i + 1, :, :, li, :], kp, vp, pt[i:i + 1],
                jnp.asarray([pos + 1], jnp.int32), use_hfa=True,
                interpret=True)
            gold = np.asarray(decode.finalize_decode(od, ld, use_hfa=True))
            np.testing.assert_array_equal(out[i, :, :, li], gold[0])


@pytest.mark.parametrize("attn_impl", ["fa2", "hfa"])
def test_model_chunked_prefill_matches_dense(qwen_smoke, attn_impl):
    """paged_prefill in two chunks (the second at pos > 0) must agree
    with a whole-prompt prefill: same last logits and same subsequent
    decode logits.

    The reference must quantize in the same order as the rail under
    test.  On fa2 that is the dense contiguous-cache model (exact
    softmax math).  On hfa the dense model's prefill runs the
    bit-accurate ``core_hfa`` emulation, a different H-FA algorithm, so
    the reference is the paged rail itself run as one whole-prompt
    chunk over contiguous pages: full-row FIX16 softmax per query, the
    order the chunked path must reproduce."""
    import dataclasses
    cfg, model, params = qwen_smoke
    if attn_impl != cfg.attn_impl:
        from repro.models.model import build_model
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
        model = build_model(cfg)
    rng = np.random.default_rng(23)
    b, l, cut = 2, 7, 4
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, l)), jnp.int32)
    nxt = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, 1)), jnp.int32)
    zeros = jnp.zeros((b,), jnp.int32)
    sl = jnp.full((b,), l, jnp.int32)

    if attn_impl == "hfa":
        layers = model.init_paged_cache(num_pages=8, page_size=4)
        pt_ref = jnp.asarray(np.array([[0, 1, 2], [3, 4, 5]], np.int32))
        lg_d, layers = model.paged_prefill(
            params, layers, toks, pt_ref,
            last_pos=jnp.full((b,), l - 1, jnp.int32), start_pos=zeros)
        lg_d2, _ = model.paged_decode_step(params, layers, nxt, pt_ref, sl)
    else:
        cache = model.init_cache(params, b, 32)
        lg_d, cache = model.prefill(params, cache, toks)
        lg_d2, _ = model.decode_step(params, cache, nxt)

    layers = model.init_paged_cache(num_pages=8, page_size=4)
    pt = jnp.asarray(np.array([[3, 5, 1], [2, 6, 0]], np.int32))
    _, layers = model.paged_prefill(
        params, layers, toks[:, :cut], pt,
        last_pos=jnp.full((b,), cut - 1, jnp.int32), start_pos=zeros)
    lg_p, layers = model.paged_prefill(
        params, layers, toks[:, cut:], pt,
        last_pos=jnp.full((b,), l - cut - 1, jnp.int32),
        start_pos=jnp.full((b,), cut, jnp.int32))
    lg_p2, _ = model.paged_decode_step(params, layers, nxt, pt, sl)

    np.testing.assert_allclose(np.asarray(lg_p[:, -1:]), np.asarray(lg_d),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(lg_p2), np.asarray(lg_d2),
                               atol=1e-4)
    assert np.array_equal(np.argmax(np.asarray(lg_p[:, -1:]), -1),
                          np.argmax(np.asarray(lg_d), -1))
    assert np.array_equal(np.argmax(np.asarray(lg_p2), -1),
                          np.argmax(np.asarray(lg_d2), -1))


def test_engine_prompt_logits_match_dense_reference(qwen_smoke):
    """ServingEngine.prompt_logits (the engine's own jitted prefill on a
    scratch slot) equals a dense exact-softmax forward of the same
    weights, and hands the slot and its pages back."""
    import dataclasses
    from repro.models.model import build_model
    cfg, model, params = qwen_smoke
    eng = ServingEngine(model, params, max_batch=2, page_size=8,
                        max_seq=64, prefill_budget=32)
    rng = np.random.default_rng(31)
    prompt = rng.integers(1, cfg.vocab_size, 13).tolist()
    got = eng.prompt_logits(prompt)
    exact = build_model(dataclasses.replace(cfg, attn_impl="exact"))
    ref, _ = exact.apply(params, {"tokens": jnp.asarray([prompt])},
                         train=False)
    np.testing.assert_allclose(got, np.asarray(ref[0, -1], np.float32),
                               atol=1e-4)
    eng.cache.check_invariants()
    assert eng.cache.available_page_count == eng.cache.num_pages
    assert eng.cache.free_slot_count == 2


def test_engine_refuses_unsupported_page_size_on_tpu(qwen_smoke,
                                                     monkeypatch):
    """On the TPU the paged kernels compile only for the page sizes in
    TPU_PAGE_SIZES; any other is refused at construction, not in the
    compiler at the first step.  Off the TPU every size is served."""
    from repro.kernels import paged_decode as paged_k
    from repro.serving import engine as engine_mod
    _, model, params = qwen_smoke
    ServingEngine(model, params, max_batch=1, page_size=4, max_seq=16)
    monkeypatch.setattr(engine_mod.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="page_size 4"):
        ServingEngine(model, params, max_batch=1, page_size=4, max_seq=16)
    for page in paged_k.TPU_PAGE_SIZES:
        ServingEngine(model, params, max_batch=1, page_size=page,
                      max_seq=32)


def test_engine_chunked_prefill_token_exact(qwen_smoke):
    """For one arrival trace, every prefill chunk budget produces
    greedy outputs identical to the unchunked engine and to the dense
    per-request loop."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(29)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        int(rng.integers(2, 11))).tolist(),
                    max_new_tokens=int(rng.integers(2, 7)))
            for i in range(4)]
    gold = {r.rid: _golden_greedy(model, params, r, 48) for r in reqs}
    for budget in (None, 3, 8):
        engine = ServingEngine(model, params, max_batch=3, page_size=4,
                               max_seq=48, prefill_budget=budget)
        finished = engine.run([(i, r) for i, r in enumerate(reqs)])
        engine.cache.check_invariants()
        assert sorted(f.rid for f in finished) == list(range(4))
        for f in finished:
            assert f.tokens == gold[f.rid], (budget, f.rid, f.preemptions)


def test_decode_keeps_running_while_long_prompt_prefills(qwen_smoke):
    """A long prompt streaming in under a small chunk budget must not
    stall the running decode: every step during the multi-step prefill
    still yields a decode token."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(37)
    engine = ServingEngine(model, params, max_batch=2, page_size=4,
                           max_seq=64, prefill_budget=4)
    engine.submit(Request(rid=0,
                          prompt=rng.integers(1, cfg.vocab_size, 4).tolist(),
                          max_new_tokens=20))
    engine.step()                        # rid 0 prefilled + decoding
    assert engine.sched.decoding_slots()
    engine.submit(Request(rid=1,
                          prompt=rng.integers(1, cfg.vocab_size,
                                              20).tolist(),
                          max_new_tokens=5))

    def rid1_prefilling():
        return any(st.req.rid == 1 and not st.decoding
                   for st in engine.sched.running.values()) or \
            any(st.req.rid == 1 for st in engine.sched.waiting)

    prefill_steps = 0
    engine.step()                        # rid 1 admitted, first chunk
    prefill_steps += 1
    while rid1_prefilling():
        before = engine.stats["generated_tokens"]
        engine.step()
        assert engine.stats["generated_tokens"] > before, \
            "decode stalled during chunked prefill"
        prefill_steps += 1
        assert prefill_steps < 20
    # 20 prompt tokens at 4 tokens/step: the prefill really was chunked
    # across multiple steps while rid 0 kept decoding.
    assert prefill_steps >= 5


def test_admission_reserves_decode_page_no_livelock(qwen_smoke):
    """Regression: a prompt that exactly fills the free pages used to be
    admitted, prefilled (wasted work), preempted on its first decode
    append, and re-admitted next step - quadratic replay thrash.  Now
    admission reserves the decode-append page: the infeasible request is
    never admitted (zero wasted prefills) and the stall is reported."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(43)
    prompt = rng.integers(1, cfg.vocab_size, 8).tolist()   # 2 full pages
    engine = ServingEngine(model, params, max_batch=1, page_size=4,
                           num_pages=2, max_seq=16)
    with pytest.raises(RuntimeError, match="stalled"):
        engine.run([(0, Request(rid=0, prompt=prompt, max_new_tokens=4))],
                   max_steps=50)
    assert engine.stats["prefills"] == 0, "wasted prefill before preempt"
    assert engine.stats["preemptions"] == 0

    # One page of headroom makes it feasible - and it must then complete
    # without a single preemption (the old code thrashed even here when
    # the pool later ran dry).
    engine = ServingEngine(model, params, max_batch=1, page_size=4,
                           num_pages=3, max_seq=16)
    [fin] = engine.run([(0, Request(rid=0, prompt=prompt,
                                    max_new_tokens=4))])
    assert fin.reason in ("eos", "length") and len(fin.tokens) == 4
    assert engine.stats["preemptions"] == 0


def test_preemption_evicts_least_work_victim(qwen_smoke):
    """Pool pressure must evict the sequence with the least accumulated
    work (cheapest replay), not the lowest slot id: here slot 0 holds the
    long-running sequence, so the old sorted()-first policy would evict
    it at maximal replay cost."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(47)
    long_req = Request(rid=0, prompt=rng.integers(1, cfg.vocab_size,
                                                  8).tolist(),
                       max_new_tokens=9)
    short_req = Request(rid=1, prompt=rng.integers(1, cfg.vocab_size,
                                                   4).tolist(),
                        max_new_tokens=9)
    engine = ServingEngine(model, params, max_batch=2, page_size=4,
                           num_pages=5, max_seq=20, prefix_caching=False)
    finished = engine.run([(0, long_req), (1, short_req)])
    by_rid = {f.rid: f for f in finished}
    assert engine.stats["preemptions"] >= 1
    assert by_rid[0].preemptions == 0, \
        "evicted the longest-running sequence (maximal replay cost)"
    assert by_rid[1].preemptions >= 1
    gold = {r.rid: _golden_greedy(model, params, r, 20)
            for r in (long_req, short_req)}
    for f in finished:
        assert f.tokens == gold[f.rid]


def test_scheduler_choose_victim_least_work():
    """Host-level: choose_victim picks the fewest materialized KV tokens,
    breaking ties toward the newest admission."""
    cache = PagedKVCache(num_pages=16, page_size=4, max_batch=4,
                         pages_per_seq=4)
    sched = Scheduler(cache)
    for rid, plen in ((0, 9), (1, 3), (2, 5)):
        sched.submit(Request(rid=rid, prompt=[1] * plen, max_new_tokens=4))
    admitted = sched.admit()
    assert len(admitted) == 3
    slots = {sched.running[s].req.rid: s for s, _ in admitted}
    assert sched.choose_victim() == slots[1]          # 3 tokens: least work
    # equal work: the newer admission loses
    cache.seq_lens[slots[1]] = 5
    assert sched.choose_victim() == slots[2]


def test_engine_prefix_reuse_shared_system_prompt(qwen_smoke):
    """Requests sharing a system prompt must reuse its full pages (fewer
    prefill tokens computed) and still generate token-exact outputs."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(53)
    sysp = rng.integers(1, cfg.vocab_size, 12).tolist()    # 3 full pages
    reqs = [Request(rid=i,
                    prompt=sysp + rng.integers(1, cfg.vocab_size,
                                               3).tolist(),
                    max_new_tokens=4)
            for i in range(3)]
    engine = ServingEngine(model, params, max_batch=2, page_size=4,
                           max_seq=48)
    finished = engine.run([(2 * i, r) for i, r in enumerate(reqs)])
    engine.cache.check_invariants()
    # first request prefills the system prompt; later ones claim it
    assert engine.stats["cached_prefill_tokens"] >= 2 * len(sysp)
    total_prompt = sum(len(r.prompt) for r in reqs)
    assert engine.stats["prefill_tokens"] <= total_prompt - 2 * len(sysp)
    for f in finished:
        assert f.tokens == _golden_greedy(model, params, reqs[f.rid], 48)


def test_cached_lru_cap_bounds_dead_prefix_pages():
    """Regression (ROADMAP follow-up): long-running multi-tenant churn
    used to park every retired prefix in the cached LRU until the
    entire free pool was dead single-use prefixes - each later
    allocation then paid an eviction + hash retraction instead of a
    free-list pop.  With ``max_cached_pages`` the LRU is bounded and
    ages out oldest-first, so strictly-free pages stay available."""
    def churn(cache, tenants):
        for i in range(tenants):
            toks = [1000 * i + t for t in range(13)]     # distinct prefix
            slot = cache.alloc_slot(len(toks))           # 4 pages
            cache.register_pages(slot, toks)             # 3 full pages
            cache.free_slot(slot)
            cache.check_invariants()

    uncapped = PagedKVCache(16, 4, 2, 4)
    churn(uncapped, 8)
    assert uncapped.free_page_count == 16 - len(uncapped._cached)
    assert len(uncapped._cached) > 8, "churn never built up dead prefixes"

    capped = PagedKVCache(16, 4, 2, 4, max_cached_pages=4)
    churn(capped, 8)
    assert len(capped._cached) <= 4
    assert capped.free_page_count >= 12, \
        "dead prefix pages still crowd out the free pool"
    # aging is LRU: the most recent tenant's prefix is still claimable,
    # the oldest ones are gone
    last = [1000 * 7 + t for t in range(13)]
    assert len(capped.lookup_prefix(last)) > 0
    assert len(capped.lookup_prefix([0, 1, 2, 3, 4, 5])) == 0
    capped.check_invariants()


def test_engine_cached_frac_plumbs_to_cache(qwen_smoke):
    cfg, model, params = qwen_smoke
    engine = ServingEngine(model, params, max_batch=2, page_size=4,
                           num_pages=12, max_seq=32, cached_frac=0.25)
    assert engine.cache.max_cached_pages == 3
    engine = ServingEngine(model, params, max_batch=2, page_size=4,
                           num_pages=12, max_seq=32, cached_frac=1.0)
    assert engine.cache.max_cached_pages is None


def test_paged_cache_fork_cow():
    """fork shares every page by refcount; the first append into the
    shared tail page copies it (pending device copy) and leaves the full
    prefix pages shared."""
    c = PagedKVCache(num_pages=8, page_size=4, max_batch=4, pages_per_seq=4)
    s0 = c.alloc_slot(6)                     # 2 pages, partial tail
    c.check_invariants()
    s1 = c.fork(s0)
    c.check_invariants()
    assert int(c.seq_lens[s1]) == 6
    assert c.refcount(int(c.page_table[s0, 0])) == 2
    assert c.refcount(int(c.page_table[s0, 1])) == 2
    assert not c.take_pending_copies()
    assert c.ensure_append_capacity(s1)      # append into shared tail
    copies = c.take_pending_copies()
    assert len(copies) == 1
    src, dst = copies[0]
    assert src == int(c.page_table[s0, 1]) and dst == int(
        c.page_table[s1, 1])
    assert c.page_table[s1, 0] == c.page_table[s0, 0], \
        "full prefix page must stay shared"
    assert c.refcount(src) == 1 and c.refcount(dst) == 1
    c.advance(s1)
    c.check_invariants()
    # the original owner's tail is now exclusive: no further copy
    assert c.ensure_append_capacity(s0)
    assert not c.take_pending_copies()
    c.free_slot(s0)
    c.free_slot(s1)
    c.check_invariants()
    assert c.free_page_count == 8


def test_cow_failure_never_exposes_shared_page_for_writing():
    """When copy-on-write cannot allocate (pool dry), the shrunk-chunk
    capacity must exclude the still-shared page: writing it would
    corrupt the forked sibling's KV."""
    c = PagedKVCache(num_pages=2, page_size=4, max_batch=3, pages_per_seq=2)
    s0 = c.alloc_slot(6)                     # both pages, partial tail
    s1 = c.fork(s0)                          # tail page shared, pool dry
    assert not c.ensure_append_capacity(s1), "COW without a free page?"
    assert not c.take_pending_copies()
    # allocation capacity still counts the shared page, but the
    # *writable* capacity (what a shrunk prefill chunk may use) must
    # stop before it - and stay below seq_lens, i.e. nothing writable.
    assert c.token_capacity(s1) == 8
    assert c.writable_token_capacity(s1) == 4
    with pytest.raises(AssertionError):
        c.mark_prefilled(s1, 7)              # would write the shared page
    c.check_invariants()
    c.free_slot(s0)
    # sole owner again: append now succeeds without any copy
    assert c.ensure_append_capacity(s1)
    assert not c.take_pending_copies()
    c.advance(s1)
    c.check_invariants()


def test_copy_pages_device_semantics():
    """copy_pages duplicates page contents along the chosen axis, drops
    padding entries (out-of-range dst), and leaves every other page
    untouched - including on the stacked (groups, P, page, ...) layer
    layout the engine uses (axis=1)."""
    rng = np.random.default_rng(67)
    pool = jnp.asarray(rng.standard_normal((6, 4, 2, 8)), jnp.float32)
    out = np.asarray(paged_pf.copy_pages(
        pool, jnp.asarray([2, 0], jnp.int32),
        jnp.asarray([5, 6], jnp.int32)))          # dst 6 is padding
    np.testing.assert_allclose(out[5], np.asarray(pool)[2])
    np.testing.assert_allclose(out[:5], np.asarray(pool)[:5])

    stacked = jnp.asarray(rng.standard_normal((2, 6, 4, 2, 8)), jnp.float32)
    out = np.asarray(paged_pf.copy_pages(
        stacked, jnp.asarray([1], jnp.int32), jnp.asarray([4], jnp.int32),
        axis=1))
    np.testing.assert_allclose(out[:, 4], np.asarray(stacked)[:, 1])
    np.testing.assert_allclose(out[:, :4], np.asarray(stacked)[:, :4])


def test_engine_applies_cow_copies_to_device_pools(qwen_smoke):
    """fork + divergent append end-to-end at the engine layer: the
    pending COW copy must be applied to every layer's device pools, so
    the fork's pages read back identical KV to the original."""
    cfg, model, params = qwen_smoke
    rng = np.random.default_rng(71)
    engine = ServingEngine(model, params, max_batch=3, page_size=4,
                           max_seq=32)
    engine.submit(Request(rid=0,
                          prompt=rng.integers(1, cfg.vocab_size, 6).tolist(),
                          max_new_tokens=8))
    engine.step()                              # 6-token KV + partial tail
    [slot] = engine.sched.decoding_slots()

    def dense_kv(s):
        pt = jnp.asarray(engine.cache.page_table[s:s + 1, :2])
        return np.asarray(paged.gather_pages(
            engine.layers["l0"]["k_pages"][0], pt))[0]

    before = dense_kv(slot)
    fork = engine.cache.fork(slot)             # tail page now shared
    assert engine.cache.ensure_append_capacity(fork)
    assert engine.cache._pending_copies       # COW queued, not yet applied
    engine._apply_pending_copies()
    assert engine.stats["cow_copies"] == 1
    n = int(engine.cache.seq_lens[slot])
    np.testing.assert_allclose(dense_kv(fork)[:n], before[:n])
    np.testing.assert_allclose(dense_kv(slot)[:n], before[:n])
    engine.cache.advance(fork)
    engine.cache.check_invariants()


def test_engine_hfa_free_slot_no_nan():
    """H-FA jnp decode over a mixed free/active batch: junk (NaN/Inf) in
    a free slot's pages must not leak NaN into any row (0 * NaN guard)."""
    rng = np.random.default_rng(61)
    b, hkv, g, d, page, J = 3, 2, 2, 16, 4, 3
    num_pages = 10
    kp = rng.standard_normal((num_pages, hkv, page, d)).astype(np.float32)
    vp = rng.standard_normal((num_pages, hkv, page, d)).astype(np.float32)
    pt = np.array([[1, 2, 3], [0, 0, 0], [4, 5, 6]], np.int32)
    kvl = np.array([5, 0, 7], np.int32)          # slot 1 is free
    q = _rand((b, 1, hkv * g, d), 62)
    clean = {impl: np.asarray(ops.paged_decode_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt),
        jnp.asarray(kvl), impl=impl)) for impl in ("fa2", "hfa_pallas")}
    kp[0] = np.nan                                # free slot's pages rot
    vp[0] = np.inf
    for impl in ("fa2", "hfa_pallas"):
        out = np.asarray(ops.paged_decode_attention(
            q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt),
            jnp.asarray(kvl), impl=impl))
        assert np.isfinite(out).all(), impl
        assert np.all(out[1] == 0.0), "free slot row must be zero"
        np.testing.assert_allclose(out[[0, 2]], clean[impl][[0, 2]],
                                   atol=1e-6)


def test_engine_logprobs_match_dense_recompute(qwen_smoke):
    """`Request(logprobs=True)`: prompt logprobs (position 0 None, the
    rest log p(prompt[t] | prompt[:t])) and per-generated-token
    logprobs from the paged engine == log_softmax over one dense
    `model.apply` forward of the full stream (teacher-forced)."""
    cfg, model, params = qwen_smoke
    engine = ServingEngine(model, params, max_batch=2, page_size=4,
                           num_pages=12, max_seq=40)
    rng = np.random.default_rng(11)
    prompt = rng.integers(1, cfg.vocab_size, 7).tolist()
    (f,) = engine.run([(0, Request(rid=0, prompt=prompt,
                                   max_new_tokens=5, logprobs=True))])
    assert f.prompt_logprobs is not None
    assert len(f.prompt_logprobs) == len(prompt)
    assert f.prompt_logprobs[0] is None
    assert all(lp is not None and lp <= 0.0
               for lp in f.prompt_logprobs[1:])
    assert len(f.token_logprobs) == len(f.tokens)

    stream = prompt + f.tokens
    lg, _ = model.apply(params, {"tokens": jnp.asarray([stream],
                                                       jnp.int32)},
                        train=False)
    lsm = np.asarray(jax.nn.log_softmax(lg[0].astype(jnp.float32), -1))
    for t in range(1, len(prompt)):
        np.testing.assert_allclose(f.prompt_logprobs[t],
                                   lsm[t - 1, prompt[t]], atol=5e-4)
    for i, tok in enumerate(f.tokens):
        np.testing.assert_allclose(f.token_logprobs[i],
                                   lsm[len(prompt) - 1 + i, tok],
                                   atol=5e-4)
