"""Mellum2-12B-A2.5B's mechanisms in the program, on the CPU at small
sizes: per-layer attention kinds (windows, YaRN), the dropless expert
layer over the experts a chip holds, and its grouped matmul.

The comparison of the served model with the benchmark's plain reference
is in ``tests/bench/test_mellum_arch.py``.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import AttnKind, Yarn
from repro.kernels import grouped_matmul as gm
from repro.models import layers as L
from repro.models import moe
from repro.models.model import build_model
from repro.models.transformer import period_pattern

WINDOW = AttnKind(window=24, rope_theta=1e4)
FULL = AttnKind(rope_theta=1e4, yarn=Yarn(factor=4.0, original=512,
                                          beta_fast=32.0, beta_slow=1.0,
                                          attention_factor=1.1))


def small(**kw):
    """Mellum's layout at toy widths: one whole period (3 windowed + 1
    full), GQA group 4, a window no page multiple, YaRN whose ramp
    covers interior dims, 16 router experts of which 4 are held, top-4;
    float32."""
    base = dict(n_layers=4, d_model=64, n_heads=8, n_kv_heads=2, d_head=32,
                d_ff=48, vocab_size=256, vocab_pad_multiple=64,
                n_experts=4, n_router_experts=16, expert_offset=4,
                moe_top_k=4, attn_pattern=(WINDOW, WINDOW, WINDOW, FULL),
                param_dtype="float32", compute_dtype="float32",
                max_seq=192, attn_impl="fa2")
    base.update(kw)
    return dataclasses.replace(get_config("mellum2-12b-a2.5b"), **base)


def test_registered_config_is_one_period_of_four():
    cfg = get_config("mellum2-12b-a2.5b")
    kinds, ffns, period = period_pattern(cfg)
    assert period == 4 and ffns == ["moe"] * 4
    assert [cfg.layer_attn(i).window for i in range(8)] == \
        [1024, 1024, 1024, None] * 2
    assert cfg.layer_attn(3).yarn.factor == 16.0
    r = cfg.reduced()
    assert r.n_layers == 4 and period_pattern(r)[2] == 4
    assert r.router_experts == r.n_experts


def test_yarn_frequencies():
    """Mellum's full layers: correction dims 18 and 35 of 64; below the
    low one the plain frequencies, above the high one divided by 16,
    linear between; cos and sin scaled by 0.1 ln 16 + 1."""
    yarn = get_config("mellum2-12b-a2.5b").layer_attn(3).yarn
    inv, scale = L.yarn_inv_freq(128, 5e5, yarn)
    plain = 1.0 / 5e5 ** (np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    ramp = (np.arange(19, 35) - 18) / 17
    np.testing.assert_allclose(
        inv[19:35], plain[19:35] / 16 * ramp + plain[19:35] * (1 - ramp),
        rtol=1e-5)
    assert scale == pytest.approx(0.1 * math.log(16) + 1)
    x = jnp.ones((1, 3, 1, 128))
    pos = jnp.arange(3)[None]
    y = L.rope_apply(x, pos, 5e5, yarn)
    np.testing.assert_allclose(np.asarray(y[0, 0, 0]), scale, rtol=1e-6)


def test_window_refused_off_the_paged_path():
    cfg = small()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    with pytest.raises(NotImplementedError):
        model.apply(params, {"tokens": jnp.ones((1, 8), jnp.int32)})


def _expert_params(key, cfg, n):
    ks = jax.random.split(key, 4)
    d, ff = cfg.d_model, cfg.d_ff
    return {"router": jax.random.normal(ks[0], (d, cfg.router_experts)),
            "wg": jax.random.normal(ks[1], (n, d, ff)) / d ** 0.5,
            "wu": jax.random.normal(ks[2], (n, d, ff)) / d ** 0.5,
            "wd": jax.random.normal(ks[3], (n, ff, d)) / ff ** 0.5}


def test_expert_shares_sum_to_the_uncut_layer():
    """16 experts over 4 chips: the four shares' outputs, each routing
    over all 16 router outputs and computing its own 4 experts, add up
    to the uncut layer's output; the shares' row counts add up to every
    row routed."""
    whole = small(n_experts=16, n_router_experts=16, expert_offset=0)
    p = _expert_params(jax.random.key(1), whole, 16)
    x = jax.random.normal(jax.random.key(2), (3, 10, whole.d_model))
    full, stats = moe.moe_serve(p, x, whole)
    assert int(stats["moe_rows_local"]) == 3 * 10 * whole.moe_top_k
    parts, rows = 0.0, 0
    for chip in range(4):
        cfg = small(n_experts=4, n_router_experts=16, expert_offset=4 * chip)
        share = dict(p, **{k: p[k][4 * chip:4 * chip + 4]
                           for k in ("wg", "wu", "wd")})
        y, st = moe.moe_serve(share, x, cfg)
        parts = parts + y
        rows += int(st["moe_rows_local"])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(full),
                               rtol=1e-5, atol=1e-5)
    assert rows == 3 * 10 * whole.moe_top_k


def test_expert_layer_is_the_dense_sum_over_held_experts():
    """The dropless layer equals the plain sum over each token's chosen
    held experts of gate x SwiGLU, and padding rows are routed nowhere."""
    cfg = small()
    p = _expert_params(jax.random.key(3), cfg, cfg.n_experts)
    x = jax.random.normal(jax.random.key(4), (2, 7, cfg.d_model))
    valid = jnp.arange(7)[None] < jnp.asarray([[7], [4]])
    y, st = moe.moe_serve(p, x, cfg, valid)
    gates, idx, _ = moe.route(p, x.reshape(-1, cfg.d_model), cfg.moe_top_k)
    xt = np.asarray(x.reshape(-1, cfg.d_model), np.float64)
    want = np.zeros_like(xt)
    rows, active = 0, set()
    for t in range(14):
        if not bool(valid.reshape(-1)[t]):
            continue
        for c in range(cfg.moe_top_k):
            e = int(idx[t, c]) - cfg.expert_offset
            if not 0 <= e < cfg.n_experts:
                continue
            rows += 1
            active.add(e)
            g = xt[t] @ np.asarray(p["wg"][e], np.float64)
            u = xt[t] @ np.asarray(p["wu"][e], np.float64)
            h = g / (1 + np.exp(-g)) * u
            want[t] += float(gates[t, c]) * (h @ np.asarray(p["wd"][e],
                                                            np.float64))
    np.testing.assert_allclose(np.asarray(y).reshape(14, -1), want,
                               rtol=1e-4, atol=1e-5)
    assert int(st["moe_rows_local"]) == rows
    assert int(st["moe_experts_active"]) == len(active)


@pytest.mark.parametrize("tokens", [5, 40])
def test_grouped_matmul_kernel_matches_jnp(tokens):
    """The moe_gmm kernel (interpret mode) equals the jnp path on the
    layer's tiles, dead tiles zero, in bf16 with f32 accumulation."""
    cfg = small(compute_dtype="bfloat16")
    p = _expert_params(jax.random.key(5), cfg, cfg.n_experts)
    x = jax.random.normal(jax.random.key(6), (1, tokens, cfg.d_model))
    x = x.astype(jnp.bfloat16)
    y_ref, st = moe.moe_serve(p, x, cfg)
    y_pal, st2 = moe.moe_serve(p, x, cfg, force_pallas=True)
    np.testing.assert_array_equal(np.asarray(y_pal, np.float32),
                                  np.asarray(y_ref, np.float32))
    assert int(st["moe_rows_local"]) == int(st2["moe_rows_local"])


def test_grouped_matmul_reads_a_stack_in_place():
    """Given the whole stack of layer groups and an index, the kernel and
    the jnp path compute that group's experts, as on the slice."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 4, 32, 24)), jnp.float32)
    te = jnp.asarray([0, 2, 3, 3], jnp.int32)
    want = gm.gmm_ref(x, w[1], te, 3, tm=16, out_dtype=jnp.float32)
    for force in (False, True):
        got = gm.gmm(x, w, te, jnp.int32(3), tm=16, out_dtype=jnp.float32,
                     group=jnp.int32(1), force_pallas=force)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-5)
    assert not np.asarray(want[48:]).any()


def test_tile_rows():
    assert gm.tile_rows(32 * 8, 64) == 16
    assert gm.tile_rows(512 * 8, 64) == 64
    assert gm.tile_rows(10 ** 6, 64) == 128


def _pools(model, num_pages=64, page=8):
    return model.init_paged_cache(num_pages, page)


def test_dropless_decode_is_batch_invariant():
    """A request's decode logits are the same alone and beside three
    other requests: no capacity, so no token is dropped for its
    neighbours."""
    cfg = small()
    model = build_model(cfg)
    params = model.init(jax.random.key(7))
    rng = np.random.default_rng(8)
    lens = np.array([37, 50, 29, 61], np.int32)
    pt = jnp.asarray(np.arange(32, dtype=np.int32).reshape(4, 8))
    toks = jnp.asarray(rng.integers(1, 256, (4, 64)), jnp.int32)
    layers = _pools(model)
    _, layers = model.paged_prefill(
        params, layers, toks, pt, last_pos=jnp.asarray(lens - 1),
        start_pos=jnp.zeros((4,), jnp.int32))
    nxt = jnp.asarray(rng.integers(1, 256, (4, 1)), jnp.int32)

    def decode(live):
        sl = jnp.where(jnp.asarray(live), jnp.asarray(lens), 0)
        lg, _, st = model.paged_decode_step(
            params, jax.tree.map(jnp.copy, layers), nxt, pt, sl, stats=True)
        return np.asarray(lg[0, 0]), np.asarray(st)
    alone, st1 = decode([True, False, False, False])
    full, st4 = decode([True, True, True, True])
    np.testing.assert_array_equal(alone, full)
    assert st4[0] > st1[0] > 0


def test_dropless_prefill_is_batch_invariant():
    """A prompt's last logits are the same prefilled alone and in one
    chunk group with three others (f32: up to reassociation across
    the different tile heights)."""
    cfg = small()
    model = build_model(cfg)
    params = model.init(jax.random.key(9))
    rng = np.random.default_rng(10)
    toks = jnp.asarray(rng.integers(1, 256, (4, 40)), jnp.int32)
    last = jnp.asarray([39, 31, 20, 39], jnp.int32)
    pt = jnp.asarray(np.arange(24, dtype=np.int32).reshape(4, 6))
    zero = jnp.zeros((4,), jnp.int32)
    group, _ = model.paged_prefill(params, _pools(model), toks, pt,
                                   last_pos=last, start_pos=zero)
    alone, _ = model.paged_prefill(params, _pools(model), toks[:1], pt[:1],
                                   last_pos=last[:1], start_pos=zero[:1])
    np.testing.assert_allclose(np.asarray(alone[0]), np.asarray(group[0]),
                               rtol=0, atol=1e-5)
