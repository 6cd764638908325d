"""Tensor-parallel paged serving: ACC-merge algebra + engine parity.

Two layers of coverage:

  * single-process merge algebra - ``merge_partials`` (Eq. 16) over
    arbitrary splits of the paged decode triplets (2/4-way page splits,
    head splits padded with the neutral element, fp and ``use_hfa``)
    must reproduce the unsplit paged decode;
  * subprocess tests on a simulated 2-device mesh (the device count must
    be fixed before jax initializes, so these shell out like
    ``test_distributed.py``) - the shard_map op path and the full
    ``ServingEngine`` must be token-exact against single-shard serving,
    with the per-shard pool cut in half.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels import decode as dk  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels import paged_decode as paged_k  # noqa: E402

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 2) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO_SRC
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def _pool_setup(seed=0, b=3, hkv=2, g=2, d=64, page=8, pages_per_seq=4):
    """Random pools + page tables with ragged lengths (slot 0 free)."""
    rng = np.random.default_rng(seed)
    num_pages = b * pages_per_seq
    kp = jnp.asarray(rng.standard_normal((num_pages, hkv, page, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((num_pages, hkv, page, d)),
                     jnp.float32)
    pt = jnp.asarray(rng.permutation(num_pages).reshape(b, pages_per_seq)
                     .astype(np.int32))
    kvl = jnp.asarray([0, 27, page * pages_per_seq], jnp.int32)[:b]
    q = jnp.asarray(rng.standard_normal((b, 1, hkv * g, d)), jnp.float32)
    qg = q.reshape(b, hkv, g, d)
    return q, qg, kp, vp, pt, kvl, page, pages_per_seq


@pytest.mark.parametrize("use_hfa", [False, True])
@pytest.mark.parametrize("parts", [2, 4])
def test_merge_partials_page_splits_match_unsplit(use_hfa, parts):
    """Triplets computed over disjoint page ranges, merged with the
    log-domain ACC rule, must match the unsplit paged decode."""
    impl = "hfa" if use_hfa else "fa2"
    q, qg, kp, vp, pt, kvl, page, pps = _pool_setup()
    ref = ops.paged_decode_attention(q, kp, vp, pt, kvl, impl=impl)

    assert pps % parts == 0
    pp = pps // parts
    span = pp * page
    trips = []
    for j in range(parts):
        kvl_j = jnp.clip(kvl - j * span, 0, span)
        trips.append(ops.paged_decode_partials(
            qg, kp, vp, pt[:, j * pp:(j + 1) * pp], kvl_j, impl=impl))
    o = jnp.stack([t[0] for t in trips])
    m = jnp.stack([t[1] for t in trips])
    l = jnp.stack([t[2] for t in trips])
    om, mm, lm = dk.merge_partials(o, m, l, use_hfa=use_hfa)
    got = dk.finalize_decode(om, lm, use_hfa=use_hfa)
    got = got.reshape(ref.shape)
    tol = 0.05 if use_hfa else 2e-5
    err = float(jnp.abs(got - ref).max())
    assert err < tol, (parts, use_hfa, err)


@pytest.mark.parametrize("use_hfa", [False, True])
def test_merge_neutral_head_padding_is_exact(use_hfa):
    """The TP identity: per-head triplets padded with the neutral
    element (o~=0, m=NEG_INF, l=0) and ACC-merged across "shards" must
    be *bit-equal* to the unsplit triplet - this is what makes
    KV-head-sharded serving token-exact, not just close."""
    impl = "hfa" if use_hfa else "fa2"
    q, qg, kp, vp, pt, kvl, _, _ = _pool_setup()
    o, m, l = ops.paged_decode_partials(qg, kp, vp, pt, kvl, impl=impl)
    hkv = o.shape[1]
    o_p, m_p, l_p = [], [], []
    for h in range(hkv):          # one "shard" per kv head
        sel = (jnp.arange(hkv) == h)[None, :, None]
        o_p.append(jnp.where(sel[..., None], o, 0.0))
        m_p.append(jnp.where(sel, m, dk.NEG_INF))
        l_p.append(jnp.where(sel, l, 0.0))
    om, mm, lm = dk.merge_partials(
        jnp.stack(o_p), jnp.stack(m_p), jnp.stack(l_p), use_hfa=use_hfa)
    assert bool(jnp.all(om == o)), "o~ not bit-equal after neutral merge"
    assert bool(jnp.all(lm == l)), "l not bit-equal after neutral merge"
    assert bool(jnp.all(mm == m)), "m not bit-equal after neutral merge"
    got = dk.finalize_decode(om, lm, use_hfa=use_hfa)
    ref = dk.finalize_decode(o, l, use_hfa=use_hfa)
    assert bool(jnp.all(got == ref))


@pytest.mark.parametrize("use_hfa", [False, True])
def test_merge_partials_verify_page_splits(use_hfa):
    """Same split-merge algebra for the K-column verify triplets."""
    impl = "hfa" if use_hfa else "fa2"
    rng = np.random.default_rng(1)
    b, hkv, g, d, page, pps, kw = 2, 2, 2, 64, 8, 4, 3
    num_pages = b * pps
    kp = jnp.asarray(rng.standard_normal((num_pages, hkv, page, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((num_pages, hkv, page, d)),
                     jnp.float32)
    pt = jnp.asarray(rng.permutation(num_pages).reshape(b, pps)
                     .astype(np.int32))
    sl = jnp.asarray([9, 20], jnp.int32)
    cl = jnp.asarray([kw, kw], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, kw, hkv * g, d)), jnp.float32)
    qg = jnp.swapaxes(q, 1, 2).reshape(b, hkv, g, kw, d)
    ref = ops.paged_verify_attention(q, kp, vp, pt, sl, cl, impl=impl)

    # Page-range split expressed through the verify positions: part j
    # sees positions [j*span, (j+1)*span) as its local window.
    span = (pps // 2) * page
    trips = []
    for j in range(2):
        sl_j = jnp.clip(sl - j * span, 0, span)
        cl_j = jnp.clip(sl + cl - j * span, 0, span) - sl_j
        trips.append(ops.paged_verify_partials(
            qg, kp, vp, pt[:, j * (pps // 2):(j + 1) * (pps // 2)],
            sl_j, cl_j, impl=impl))
    om, mm, lm = dk.merge_partials(
        jnp.stack([t[0] for t in trips]),
        jnp.stack([t[1] for t in trips]),
        jnp.stack([t[2] for t in trips]), use_hfa=use_hfa)
    got = dk.finalize_decode(om, lm, use_hfa=use_hfa)
    got = jnp.swapaxes(got.reshape(b, hkv * g, kw, d), 1, 2)
    tol = 0.05 if use_hfa else 2e-5
    err = float(jnp.abs(got - ref).max())
    assert err < tol, (use_hfa, err)


def test_shardmap_paged_decode_matches_single_shard():
    """collectives.shardmap_paged_attention (decode mode) on a 2-device
    mesh == append + paged decode on one device, bit-exact per head."""
    out = _run("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels import ops
from repro.kernels import paged_decode as paged_k
from repro.parallel import collectives
from repro.launch.mesh import make_tp_mesh

mesh = make_tp_mesh(2)
rng = np.random.default_rng(0)
b, hkv, g, d, page, pps = 3, 2, 2, 64, 8, 4
num_pages = b * pps
kp = jnp.asarray(rng.standard_normal((num_pages, hkv, page, d)), jnp.float32)
vp = jnp.asarray(rng.standard_normal((num_pages, hkv, page, d)), jnp.float32)
pt = jnp.asarray(rng.permutation(num_pages).reshape(b, pps).astype(np.int32))
sl = jnp.asarray([0, 13, 31], jnp.int32)
q = jnp.asarray(rng.standard_normal((b, 1, hkv * g, d)), jnp.float32)
kn = jnp.asarray(rng.standard_normal((b, 1, hkv, d)), jnp.float32)
vn = jnp.asarray(rng.standard_normal((b, 1, hkv, d)), jnp.float32)

# single-shard reference: append then attend
kp1, vp1 = paged_k.append_kv(kp, vp, kn, vn, pt, sl)
kv_lens = jnp.where(sl > 0, sl + 1, 0)
ref = ops.paged_decode_attention(q, kp1, vp1, pt, kv_lens, impl="fa2")

sh = NamedSharding(mesh, P(None, "model", None, None))
pools = {"k_pages": jax.device_put(kp, sh), "v_pages": jax.device_put(vp, sh)}
out, pools2 = jax.jit(lambda *a: collectives.shardmap_paged_attention(
    *a, mesh=mesh, mode="decode", impl="fa2"))(
    q, kn, vn, pools, pt, sl, jnp.zeros_like(sl))
err = float(jnp.abs(out - ref).max())
print("ERR", err)
assert err < 1e-6, err
assert bool(jnp.all(jnp.asarray(pools2["k_pages"]) == kp1))
assert bool(jnp.all(jnp.asarray(pools2["v_pages"]) == vp1))
print("OK")
""")
    assert "OK" in out


def test_tp_engine_token_exact_vs_single_shard():
    """Full ServingEngine on a simulated 2-device mesh: greedy, spec-k,
    and seeded-sampling token streams must be identical to the
    single-shard engine, with per-shard pool bytes halved."""
    out = _run("""
import jax, numpy as np
from repro.configs import get_config
from repro.models.model import build_model
from repro.serving import Request, SamplingParams, ServingEngine
from repro.launch.mesh import make_tp_mesh

cfg = get_config("qwen3-1.7b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
prompts = [rng.integers(1, cfg.vocab_size, 12).tolist() for _ in range(5)]

def run(mesh, spec_k, sampling):
    eng = ServingEngine(model, params, max_batch=3, page_size=8,
                        max_seq=64, prefill_budget=16, spec_k=spec_k,
                        mesh=mesh)
    arrivals = [(i, Request(rid=i, prompt=list(p), max_new_tokens=8,
                            sampling=sampling)) for i, p in
                enumerate(prompts)]
    fin = eng.run(arrivals)
    eng.cache.check_invariants()
    return {f.rid: tuple(f.tokens) for f in fin}, eng

mesh = make_tp_mesh(2)
sp = SamplingParams(temperature=0.8, top_k=4, seed=7)
for spec_k, sampling in ((0, None), (2, None), (0, sp)):
    t1, e1 = run(None, spec_k, sampling)
    t2, e2 = run(mesh, spec_k, sampling)
    assert t1 == t2, (spec_k, sampling, t1, t2)
    assert e2.tp == 2
    assert e2.pool_bytes_per_shard() * 2 == e1.pool_bytes_per_shard()
    assert e2.stats["triplet_bytes"] > 0
    for leaf in jax.tree.leaves(e2.layers):
        shards = leaf.addressable_shards
        assert len(shards) == 2
        assert all(s.data.nbytes == leaf.nbytes // 2 for s in shards)
    print("case", spec_k, sampling is not None, "OK")
print("OK")
""")
    assert "OK" in out


def test_tp_engine_rejects_bad_head_split():
    """tp must divide the KV heads - reduced qwen3 has 2, so tp=3 is an
    early, explicit error rather than a wrong-answer shard."""
    out = _run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.configs import get_config
from repro.models.model import build_model
from repro.serving import ServingEngine

cfg = get_config("qwen3-1.7b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2)[:, :1],
            ("data", "model"))   # model axis size 1: fine (no TP)
eng = ServingEngine(model, params, max_batch=2, page_size=8, max_seq=32,
                    mesh=mesh)
assert eng.tp == 1
bad = Mesh(np.asarray(jax.devices()[:2]).reshape(2, 1), ("data", "model"))
# model axis 1 again - craft a real bad case via monkeypatched heads
import dataclasses
cfg3 = dataclasses.replace(cfg, n_kv_heads=3, n_heads=6)
model3 = build_model(cfg3)
from jax.sharding import Mesh as M
mesh2 = M(np.asarray(jax.devices()[:2]).reshape(1, 2), ("data", "model"))
try:
    ServingEngine(model3, params, max_batch=2, page_size=8, max_seq=32,
                  mesh=mesh2)
    raise SystemExit("expected ValueError")
except ValueError as e:
    assert "divide" in str(e), e
print("OK")
""")
    assert "OK" in out


def test_tp_engine_codec_token_parity():
    """Quantized page codecs under 2-way TP: int8/log16 engines on a
    simulated mesh emit the same greedy streams as their single-shard
    counterparts, the scale sidecars shard with the pages (per-shard
    pool bytes halve, every leaf split in two), and bytes_per_token is
    a property of the codec, not of the mesh."""
    out = _run("""
import jax, numpy as np
from repro.configs import get_config
from repro.models.model import build_model
from repro.serving import Request, ServingEngine
from repro.launch.mesh import make_tp_mesh

cfg = get_config("qwen3-1.7b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(3)
prompts = [rng.integers(1, cfg.vocab_size, 10).tolist()
           for _ in range(4)]

def run(mesh, codec):
    eng = ServingEngine(model, params, max_batch=3, page_size=8,
                        max_seq=64, mesh=mesh, kv_codec=codec)
    fin = eng.run([(i, Request(rid=i, prompt=list(p),
                               max_new_tokens=6))
                   for i, p in enumerate(prompts)])
    eng.cache.check_invariants()
    return {f.rid: tuple(f.tokens) for f in fin}, eng

mesh = make_tp_mesh(2)
for codec in ("int8", "log16"):
    t1, e1 = run(None, codec)
    t2, e2 = run(mesh, codec)
    assert t1 == t2, (codec, t1, t2)
    assert e2.bytes_per_token() == e1.bytes_per_token()
    assert e2.pool_bytes_per_shard() * 2 == e1.pool_bytes_per_shard()
    for leaf in jax.tree.leaves(e2.layers):
        assert len(leaf.addressable_shards) == 2
        assert all(s.data.nbytes == leaf.nbytes // 2
                   for s in leaf.addressable_shards)
    print(codec, "OK")
print("OK")
""")
    assert "OK" in out


def test_tp_dp_engine_token_exact_vs_tp_only():
    """Composed tp x dp mesh (4 simulated devices, batch sharded over
    the "data" axis) must be *token-identical* to the tp-only engine on
    the same batch - across plain greedy decode, chunked prefill, and
    speculative verify - because every data shard applies the full
    batch's KV scatter and its local partials merge through the same
    neutral-element ACC algebra.  The pool replicates over "data": each
    of the 4 shards holds total/tp bytes."""
    out = _run("""
import jax, numpy as np
from repro.configs import get_config
from repro.models.model import build_model
from repro.serving import Request, SamplingParams, ServingEngine
from repro.launch.mesh import make_tp_dp_mesh, make_tp_mesh

cfg = get_config("qwen3-1.7b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
prompts = [rng.integers(1, cfg.vocab_size, 12).tolist() for _ in range(6)]

def run(mesh, spec_k, sampling, budget=16):
    eng = ServingEngine(model, params, max_batch=4, page_size=8,
                        max_seq=64, prefill_budget=budget, spec_k=spec_k,
                        mesh=mesh)
    arrivals = [(i, Request(rid=i, prompt=list(p), max_new_tokens=8,
                            sampling=sampling)) for i, p in
                enumerate(prompts)]
    fin = eng.run(arrivals)
    eng.cache.check_invariants()
    return {f.rid: tuple(f.tokens) for f in fin}, eng

tp = make_tp_mesh(2)
tpdp = make_tp_dp_mesh(2, 2)
sp = SamplingParams(temperature=0.8, top_k=4, seed=7)
for spec_k, sampling in ((0, None), (2, None), (0, sp)):
    t1, e1 = run(tp, spec_k, sampling)
    t2, e2 = run(tpdp, spec_k, sampling)
    assert t1 == t2, (spec_k, sampling, t1, t2)
    assert e2.tp == 2 and e2.dp == 2
    # pool bytes: sharded over tp, REPLICATED over dp
    assert e2.pool_bytes_per_shard() == e1.pool_bytes_per_shard()
    for leaf in jax.tree.leaves(e2.layers):
        shards = leaf.addressable_shards
        assert len(shards) == 4
        assert all(s.data.nbytes == leaf.nbytes // 2 for s in shards)
    print("case", spec_k, sampling is not None, "OK")
print("OK")
""", devices=4)
    assert "OK" in out


def test_dp_engine_rejects_indivisible_batch():
    """dp must divide max_batch (the slot dim is sharded evenly);
    anything else is an early explicit error, not a silent wrong
    shard - and a non-divisible *runtime* batch falls back to the
    replicated compute path rather than failing."""
    out = _run("""
import jax, numpy as np
from repro.configs import get_config
from repro.models.model import build_model
from repro.serving import Request, ServingEngine
from repro.launch.mesh import make_tp_dp_mesh

cfg = get_config("qwen3-1.7b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
mesh = make_tp_dp_mesh(1, 2)
try:
    ServingEngine(model, params, max_batch=3, page_size=8, max_seq=32,
                  mesh=mesh)
    raise SystemExit("expected ValueError")
except ValueError as e:
    assert "dp=2" in str(e) and "divide" in str(e), e
# divisible batch works end to end on a dp-only mesh
eng = ServingEngine(model, params, max_batch=2, page_size=8, max_seq=32,
                    mesh=mesh)
assert eng.tp == 1 and eng.dp == 2
rng = np.random.default_rng(5)
fin = eng.run([(i, Request(rid=i,
                           prompt=rng.integers(1, cfg.vocab_size,
                                               9).tolist(),
                           max_new_tokens=5)) for i in range(3)])
assert sorted(f.rid for f in fin) == [0, 1, 2]
eng.cache.check_invariants()
print("OK")
""")
    assert "OK" in out


def test_make_tp_dp_mesh_validation():
    """Mesh construction errors early and by name when the simulated
    device pool cannot cover dp * tp."""
    out = _run("""
from repro.launch.mesh import make_tp_dp_mesh
mesh = make_tp_dp_mesh(2, 1)
assert dict(mesh.shape) == {"data": 1, "model": 2}, dict(mesh.shape)
try:
    make_tp_dp_mesh(2, 2)           # needs 4, only 2 simulated
    raise SystemExit("expected RuntimeError")
except RuntimeError as e:
    assert "xla_force_host_platform_device_count" in str(e), e
for bad in ((0, 1), (1, 0)):
    try:
        make_tp_dp_mesh(*bad)
        raise SystemExit("expected ValueError")
    except ValueError:
        pass
print("OK")
""")
    assert "OK" in out


def test_router_replicas_each_on_own_device():
    """serve_http's stack with --replicas 2 on a 2-device host: replica
    i's params and pools live on device i (read from the arrays), both
    replicas serve over HTTP, and concurrent greedy streams equal the
    single-engine streams."""
    out = _run("""
import asyncio, jax, numpy as np
from repro.configs import get_config
from repro.launch.serve_http import build_server
from repro.models.model import build_model
from repro.serving.http import stream_generate

cfg = get_config("qwen3-1.7b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(3)
prompts = [rng.integers(1, cfg.vocab_size, 12).tolist() for _ in range(2)]

async def serve(replicas):
    server, fes = build_server(model, params, batch=2, page_size=8,
                               max_seq=64, prefill_budget=16,
                               replicas=replicas)
    await server.start()

    async def one(p):
        toks = []
        async for kind, data in stream_generate(
                server.host, server.port,
                {"prompt": p, "max_new_tokens": 6}):
            if kind == "token":
                toks.append(data["token"])
        return toks
    try:
        got = await asyncio.gather(*(one(p) for p in prompts))
        for fe in fes:
            await fe.drain()
    finally:
        await server.stop()
        for fe in fes:
            await fe.close()
    return got, [fe.engine for fe in fes]

one, _ = asyncio.run(serve(1))
two, engines = asyncio.run(serve(2))
assert one == two, (one, two)
devs = jax.devices()
for i, e in enumerate(engines):
    for tree in (e.params, e.layers):
        assert {d for x in jax.tree.leaves(tree) for d in x.devices()} \\
            == {devs[i]}, i
    assert e.stats["steps"] > 0, i
    e.cache.check_invariants()
print("OK")
""")
    assert "OK" in out
