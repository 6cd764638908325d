"""Compile-only checks of the paged serving kernels for a TPU v5e.

Nothing runs: each test lowers a kernel at qwen3-1.7b widths (8 KV
heads of d_head 128, GQA group 2, bf16 pools, page 16) against a
*described* v5e:2x2 topology and compiles it with the TPU's compiler,
which is installed here even without a chip.  What Mosaic refuses here
(block shapes off the (8, 128) tiling, too much VMEM) it would refuse on
the chip at the first serving step.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports
this file.  Where it cannot be described the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import page_codec
from repro.kernels import paged_decode
from repro.kernels import paged_prefill
from repro.kernels import paged_verify

# qwen3-1.7b widths: batch 8, 8 KV heads, GQA group 2, d_head 128.
B, HKV, G, D = 8, 8, 2, 128
PAGE = 16
PAGES_PER_SEQ = 16             # 256-token context per slot
NUM_PAGES = B * PAGES_PER_SEQ


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _pools(sh, codec, hkv, page=PAGE, num_pages=NUM_PAGES):
    """Shape stand-ins of one layer's K/V pools (and scale sidecars)."""
    c = page_codec.get_codec(codec)
    shape = (num_pages, hkv, page, D)
    data = jax.ShapeDtypeStruct(shape, c.storage_dtype(jnp.bfloat16),
                                sharding=sh)
    extra = {}
    if c.has_scales:
        s = jax.ShapeDtypeStruct(shape[:-1] + (1,), jnp.float32,
                                 sharding=sh)
        extra = {"k_scales": s, "v_scales": s}
    return data, extra, (None if c.name == "fp" else c)


def _i32(sh, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sh)


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# The benchmark's decode shapes: (slots, GQA group, page-table width,
# pool pages) of qwen3-1.7b (docqa) and qwen1.5-4b (20 KV heads, MHA).
QWEN3_DOCQA = (16, 2, 256, 3268)
QWEN15_MIXED = (4, 1, 152, 644)


@pytest.mark.parametrize("codec,use_hfa,hkv,shape", [
    pytest.param("fp", False, HKV, None, id="fp-False-8"),
    pytest.param("fp", True, HKV, None, id="fp-True-8"),
    pytest.param("int8", True, HKV, None, id="int8-True-8"),
    pytest.param("log16", True, HKV, None, id="log16-True-8"),
    pytest.param("fp", True, HKV // 4, None, id="fp-True-2"),
    pytest.param("fp", True, HKV, QWEN3_DOCQA, id="qwen3-docqa"),
    pytest.param("fp", False, 20, QWEN15_MIXED, id="qwen15-mixed-long"),
])
def test_paged_decode_compiles(one_chip, codec, use_hfa, hkv, shape):
    """Decode on both rails, the int8 / log16 codecs, the 2 local heads
    of a TP-4 shard, and the benchmark's two models at their serving
    shapes (Mosaic checks each block's VMEM slabs and DMAs)."""
    b, g, width, num_pages = shape or (B, G, PAGES_PER_SEQ, NUM_PAGES)
    pool, extra, c = _pools(one_chip, codec, hkv, num_pages=num_pages)
    q = jax.ShapeDtypeStruct((b, hkv, g, D), jnp.bfloat16, sharding=one_chip)

    def f(q, k, v, pt, kl, **sc):
        return paged_decode.paged_decode_partial_pallas(
            q, k, v, pt, kl, interpret=False, use_hfa=use_hfa, codec=c,
            **sc)

    text = _compile_text(lambda *a: f(*a[:5], **dict(zip(extra, a[5:]))),
                         q, pool, pool, _i32(one_chip, b, width),
                         _i32(one_chip, b), *extra.values())
    assert "tpu_custom_call" in text
    assert "paged_flash_decode_partial" in text


@pytest.mark.parametrize("use_hfa", [False, True])
def test_paged_prefill_compiles(one_chip, use_hfa):
    """A 128-token prefill chunk per slot, on both rails."""
    pool, _, _ = _pools(one_chip, "fp", HKV)
    q = jax.ShapeDtypeStruct((B, HKV, G, 128, D), jnp.bfloat16,
                             sharding=one_chip)
    text = _compile_text(
        lambda q, k, v, pt, sp, kl: paged_prefill.paged_prefill_partial_pallas(
            q, k, v, pt, sp, kl, interpret=False, use_hfa=use_hfa),
        q, pool, pool, _i32(one_chip, B, PAGES_PER_SEQ), _i32(one_chip, B),
        _i32(one_chip, B))
    assert "paged_prefill_partial" in text


@pytest.mark.parametrize("use_hfa", [False, True])
def test_paged_verify_compiles(one_chip, use_hfa):
    """A speculative verify step of K = 5 positions, on both rails."""
    pool, _, _ = _pools(one_chip, "fp", HKV)
    q = jax.ShapeDtypeStruct((B, HKV, G, 5, D), jnp.bfloat16,
                             sharding=one_chip)
    text = _compile_text(
        lambda q, k, v, pt, sl, cl: paged_verify.paged_verify_partial_pallas(
            q, k, v, pt, sl, cl, interpret=False, use_hfa=use_hfa),
        q, pool, pool, _i32(one_chip, B, PAGES_PER_SEQ), _i32(one_chip, B),
        _i32(one_chip, B))
    assert "paged_verify_partial" in text


@pytest.mark.parametrize("page", paged_decode.TPU_PAGE_SIZES)
def test_supported_page_sizes_compile(one_chip, page):
    """Every page size the engine admits on the TPU compiles (decode,
    H-FA rail, bf16 pool)."""
    pool = jax.ShapeDtypeStruct((NUM_PAGES, HKV, page, D), jnp.bfloat16,
                                sharding=one_chip)
    q = jax.ShapeDtypeStruct((B, HKV, G, D), jnp.bfloat16, sharding=one_chip)
    text = _compile_text(
        lambda q, k, v, pt, kl: paged_decode.paged_decode_partial_pallas(
            q, k, v, pt, kl, interpret=False, use_hfa=True),
        q, pool, pool, _i32(one_chip, B, 256 // page), _i32(one_chip, B))
    assert "tpu_custom_call" in text


# Mellum2-12B-A2.5B at published widths: 4 KV heads of 128, GQA group 8,
# windowed layers of 1024 rows, 32 decode slots, its benchmark pool; the
# held experts' SwiGLU matrices (d 2304, expert width 896).
MELLUM = dict(b=32, hkv=4, g=8, width=375, num_pages=4100, window=1024)


@pytest.mark.parametrize("use_hfa", [False, True])
def test_paged_decode_window_compiles(one_chip, use_hfa):
    """The windowed decode kernel is its own call,
    paged_flash_decode_window."""
    c = MELLUM
    pool, _, _ = _pools(one_chip, "fp", c["hkv"], num_pages=c["num_pages"])
    q = jax.ShapeDtypeStruct((c["b"], c["hkv"], c["g"], D), jnp.bfloat16,
                             sharding=one_chip)
    text = _compile_text(
        lambda q, k, v, pt, kl: paged_decode.paged_decode_partial_pallas(
            q, k, v, pt, kl, interpret=False, use_hfa=use_hfa,
            window=c["window"]),
        q, pool, pool, _i32(one_chip, c["b"], c["width"]),
        _i32(one_chip, c["b"]))
    assert "paged_flash_decode_window" in text
    assert "paged_flash_decode_partial" not in text


@pytest.mark.parametrize("chunk", [128, 512])
def test_paged_prefill_and_verify_window_compile(one_chip, chunk):
    """Windowed prefill at group 8: a 512-token chunk (4096 query rows)
    runs as query blocks that fit VMEM; verify of K = 5."""
    c = MELLUM
    pool, _, _ = _pools(one_chip, "fp", c["hkv"], num_pages=c["num_pages"])
    q = jax.ShapeDtypeStruct((1, c["hkv"], c["g"], chunk, D), jnp.bfloat16,
                             sharding=one_chip)
    text = _compile_text(
        lambda q, k, v, pt, sp, kl: paged_prefill.paged_prefill_partial_pallas(
            q, k, v, pt, sp, kl, interpret=False, use_hfa=True,
            window=c["window"]),
        q, pool, pool, _i32(one_chip, 1, c["width"]), _i32(one_chip, 1),
        _i32(one_chip, 1))
    assert "paged_prefill_partial" in text
    qv = jax.ShapeDtypeStruct((c["b"], c["hkv"], c["g"], 5, D),
                              jnp.bfloat16, sharding=one_chip)
    text = _compile_text(
        lambda q, k, v, pt, sl, cl: paged_verify.paged_verify_partial_pallas(
            q, k, v, pt, sl, cl, interpret=False, use_hfa=True,
            window=c["window"]),
        qv, pool, pool, _i32(one_chip, c["b"], c["width"]),
        _i32(one_chip, c["b"]), _i32(one_chip, c["b"]))
    assert "paged_verify_partial" in text


@pytest.mark.parametrize("tokens", [32, 512])
def test_moe_gmm_compiles(one_chip, tokens):
    """The held experts' grouped matmuls, named moe_gmm: a decode step's
    32 tokens and a 512-token prefill chunk, top-8 of 64 router outputs
    onto 16 held experts, the weights read in place from the stack of 7
    layer groups."""
    from repro.kernels import grouped_matmul as gm
    d, ff, held = 2304, 896, 16
    tm = gm.tile_rows(tokens * 8, 64)
    m = gm.num_tiles(tokens * 8, held, tm) * tm
    te = _i32(one_chip, m // tm)
    nl = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    for k, n, out in ((d, ff, jnp.bfloat16), (ff, d, jnp.float32)):
        text = _compile_text(
            lambda x, w, te, nl, g, out=out: gm.gmm_pallas(
                x, w, te, nl, tm=tm, out_dtype=out, interpret=False,
                group=g),
            bf16(m, k), bf16(7, held, k, n), te, nl, nl)
        assert "moe_gmm" in text
