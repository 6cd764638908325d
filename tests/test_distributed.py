"""Distributed semantics on 8 fake CPU devices (subprocess: the device
count must be fixed before jax initializes, so these tests shell out)."""
import os
import subprocess
import sys

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO_SRC
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_sharded_train_step_matches_single_device():
    out = _run("""
from repro.launch.mesh import make_mesh
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models.model import build_model
from repro.optim import build_optimizer
from repro.optim.schedule import constant
from repro.runtime.trainer import make_train_step
from repro.parallel import sharding as sh

cfg = get_config("qwen3-1.7b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
opt = build_optimizer(cfg, constant(1e-2))
step = make_train_step(model, opt)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)))}
carry = {"params": params, "opt_state": opt.init(params)}

# single device
_, m1 = jax.jit(step)(carry, batch)

# 4x2 mesh, sharded
mesh = make_mesh((4, 2), ("data", "model"))
sh.set_context(mesh, sh.TRAIN_RULES)
shapes, logical = model.shape_and_logical()
pspec = sh.tree_specs(logical, shapes, sh.TRAIN_RULES, mesh)
psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                   is_leaf=lambda x: isinstance(x, P))
with mesh:
    params_s = jax.device_put(params, psh)
    carry_s = {"params": params_s, "opt_state": opt.init(params_s)}
    batch_s = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    _, m2 = jax.jit(step)(carry_s, batch_s)
print("LOSS", float(m1["loss"]), float(m2["loss"]))
assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-3, (m1, m2)
print("OK")
""")
    assert "OK" in out


def test_kv_sharded_decode_matches_replicated():
    """Sequence-sharded KV cache decode == replicated decode (the paper's
    multi-KV-block parallelism at mesh level, XLA-merged)."""
    out = _run("""
from repro.launch.mesh import make_mesh
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels import ops
mesh = make_mesh((1, 8), ("data", "model"))
rng = np.random.default_rng(0)
q = jnp.asarray(rng.standard_normal((2, 1, 8, 64)), jnp.bfloat16)
kc = jnp.asarray(rng.standard_normal((2, 512, 2, 64)), jnp.bfloat16)
vc = jnp.asarray(rng.standard_normal((2, 512, 2, 64)), jnp.bfloat16)
ref = np.asarray(ops.decode_attention(q, kc, vc, impl="fa2", kv_len=400).astype(jnp.float32))
with mesh:
    f = jax.jit(lambda q, k, v: ops.decode_attention(q, k, v, impl="fa2", kv_len=400),
        in_shardings=(NamedSharding(mesh, P()),
                      NamedSharding(mesh, P(None, "model", None, None)),
                      NamedSharding(mesh, P(None, "model", None, None))),
        out_shardings=NamedSharding(mesh, P()))
    got = np.asarray(f(q, kc, vc).astype(jnp.float32))
print("ERR", np.abs(got - ref).max())
assert np.abs(got - ref).max() < 2e-3
print("OK")
""")
    assert "OK" in out


def test_shardmap_decode_merge_matches_reference():
    """shard_map KV-split decode + explicit log-domain ACC merge (Eq. 16):
    the paper's cascaded merge as a cluster collective."""
    out = _run("""
from repro.launch.mesh import make_mesh
import jax, numpy as np, jax.numpy as jnp, functools
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.parallel.collectives import shard_map
from repro.kernels import decode as dk
from repro.core import reference as cref

mesh = make_mesh((8,), ("kv",))
rng = np.random.default_rng(0)
BH, G, S, D = 4, 4, 1024, 64
q = jnp.asarray(rng.standard_normal((BH, G, D)), jnp.bfloat16)
k = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.bfloat16)
v = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.bfloat16)

def local_partial(q, k, v):
    # pure-jnp partial per shard (kernel path equivalently validated in
    # test_kernels); here we exercise the collective merge itself.
    from repro.kernels import ref as kref
    o, m, l = kref.ref_decode_partial(q, k, v)
    og = jax.lax.all_gather(o, "kv")            # (P, BH, G, D)
    mg = jax.lax.all_gather(m, "kv")
    lg = jax.lax.all_gather(l, "kv")
    om, mm, lm = dk.merge_partials(og, mg, lg, use_hfa=True)
    return dk.finalize_decode(om, lm, use_hfa=True)

f = shard_map(local_partial, mesh=mesh,
              in_specs=(P(), P(None, "kv", None), P(None, "kv", None)),
              out_specs=P(), check_vma=False)
got = np.asarray(jax.jit(f)(q, k, v))
ref = np.asarray(cref.exact_attention(q, k, v))
print("ERR", np.abs(got - ref).max())
assert np.abs(got - ref).max() < 0.05
print("OK")
""")
    assert "OK" in out


def test_shardmap_local_write_decode_attention():
    """parallel/collectives.py: local ring write + partial FAU + ACC merge
    must equal write-then-attend on one device (the §Perf mechanism)."""
    out = _run("""
from repro.launch.mesh import make_mesh
import jax, numpy as np, jax.numpy as jnp
from repro.parallel.collectives import shardmap_decode_attention
from repro.kernels import ops
mesh = make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
B, S, H, HKV, D = 4, 512, 8, 2, 64
q = jnp.asarray(rng.standard_normal((B,1,H,D)), jnp.bfloat16)
kn = jnp.asarray(rng.standard_normal((B,1,HKV,D)), jnp.bfloat16)
vn = jnp.asarray(rng.standard_normal((B,1,HKV,D)), jnp.bfloat16)
ck = jnp.asarray(rng.standard_normal((B,S,HKV,D)), jnp.bfloat16)
cv = jnp.asarray(rng.standard_normal((B,S,HKV,D)), jnp.bfloat16)
for pos in (0, 300, 511):
    with mesh:
        out, nk, nv = jax.jit(lambda *a: shardmap_decode_attention(
            *a, mesh=mesh, batch_axes=("data",), use_hfa=False))(
            q, kn, vn, ck, cv, jnp.int32(pos))
    ck2 = ck.at[:, pos].set(kn[:, 0]); cv2 = cv.at[:, pos].set(vn[:, 0])
    ref = ops.decode_attention(q, ck2, cv2, impl="fa2", kv_len=pos+1)
    err = float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max())
    assert err < 2e-3, (pos, err)
    assert bool(jnp.all(nk[:, pos] == kn[:, 0]))
print("OK")
""")
    assert "OK" in out


def test_checkpoint_elastic_reshard():
    """Save under one sharding, restore under another mesh (elastic)."""
    out = _run("""
from repro.launch.mesh import make_mesh
import tempfile, jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import save, restore
tree = {"w": jnp.arange(64.0).reshape(8, 8)}
d = tempfile.mkdtemp()
mesh1 = make_mesh((8,), ("data",))
t1 = jax.device_put(tree, NamedSharding(mesh1, P("data", None)))
save(d, 1, t1)
mesh2 = make_mesh((2, 4), ("data", "model"))
sh2 = {"w": NamedSharding(mesh2, P("model", "data"))}
got, step = restore(d, None, tree, sh2)
assert got["w"].sharding == sh2["w"]
np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
print("OK")
""")
    assert "OK" in out


def test_small_dryrun_cell_on_8_devices():
    """The dry-run machinery works end-to-end on a small mesh."""
    out = _run("""
from repro.launch.mesh import make_mesh
import jax, json
from repro.configs import get_config
from repro.launch.specs import build_cell
cfg = get_config("qwen3-1.7b").reduced()
mesh = make_mesh((4, 2), ("data", "model"))
for shape in ("train_4k", "decode_32k"):
    import dataclasses
    from repro.launch import specs
    mode, seq, batch = specs.SHAPES[shape]
    specs.SHAPES[shape] = (mode, 256, 8)   # shrink for the test
    fn, args, in_sh, out_sh, meta = build_cell(cfg, shape, mesh)
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    specs.SHAPES[shape] = (mode, seq, batch)
print("OK")
""")
    assert "OK" in out
