"""The architecture modules (``bench/archs/``) on the CPU.

The dense module is the benchmark's dense code moved, not rewritten: on
a small copy of each configuration file (2 layers, a vocabulary of
1000 padded to 1024) it makes the same weights and the same reference
gaps, the control's too, to the bit - the digests below were recorded
from the benchmark before the architecture became a module - and the
same work counts at the published sizes.  The shared causal attention
computed in blocks of query rows equals the full form, with and without
a window, and so does its float8 control.

  JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_archs.py
"""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import spec  # noqa: E402

SEED = 2 ** 31 + 7
VOCAB, PADDED = 1000, 1024
GOLDEN = {
    "qwen3-1.7b-hfa": {
        "weights": "a15e64f01c723ab5da73f52cb1f61267"
                   "b798ba3b85551c7bc6045dc278f36c7d",
        "gaps": "29ba92ade060327e7e7c205f1eef9bc8"
                "1b6785d20bfb584d8fc17ec1c59591b8",
        "decode": (1548288000.0, 775061504.0),
        "prefill": (129024688128.0, 538460160.0),
        "step": 1907942588416.0},
    "qwen1.5-4b-fa2": {
        "weights": "37ec22ea98254377d613f96e20926290"
                   "34009a03dfcf761c0af6a870c0474501",
        "gaps": "22e1aef590bd7a27fe92d0cb99698856"
                "ecee302f31229488f11bc1ef5ae9a6c9",
        "decode": (2764800000.0, 2766438400.0),
        "prefill": (230401228800.0, 1488486400.0),
        "step": 4248499732480.0}}


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _digest(arrays):
    h = hashlib.sha256()
    for k, a in arrays:
        a = np.asarray(a)
        h.update(f"{k}|{a.dtype}|{a.shape}|".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _tokens():
    """A prompt of 60 and 20 served tokens, one of them past the
    vocabulary (its gap is inf)."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, VOCAB, 60).tolist()
    served = rng.integers(1, VOCAB, 20).tolist()
    served[5] = VOCAB + 3
    return prompt, served


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_dense_weights_and_reference_are_unchanged(name):
    import reference
    import weights
    config = _config(name)
    arch = spec.arch(ROOT, config["program"].get("bench_arch", "dense"))
    m = dict(config["model"], num_hidden_layers=2, vocab_size=VOCAB)
    w = weights.make(arch, m, PADDED, SEED)
    assert _digest(sorted(weights.flat(w).items())) == \
        GOLDEN[name]["weights"]
    prompt, served = _tokens()
    g, c = reference.gaps(arch, w, m, prompt, served, control=True)
    assert np.isinf(g[5]) and np.isfinite(np.delete(g, 5)).all()
    assert _digest([("program", g), ("control", c)]) == GOLDEN[name]["gaps"]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_dense_work_counts_are_unchanged(name):
    config = _config(name)
    arch = spec.arch(ROOT, config["program"].get("bench_arch", "dense"))
    m, want = config["model"], GOLDEN[name]
    assert flops.decode_attention(arch, m, [1, 700, 2049, 4000]) == \
        want["decode"]
    assert flops.prefill_attention(
        arch, m, [(0, 512, False), (512, 512, True), (1000, 37, True)]) == \
        want["prefill"]
    assert flops.step_flops(arch, m, [5, 3000, 1200],
                            [(0, 512, False), (2048, 128, True)]) == \
        want["step"]


def _attention_by_rows(q, k, v, window):
    """Each query row's softmax over its causal (windowed) rows, in
    float64, one row at a time."""
    t, h, d = q.shape
    out = np.zeros((t, h, d))
    for i in range(t):
        lo = 0 if window is None else max(0, i + 1 - window)
        for j in range(h):
            s = k[lo:i + 1, j] @ q[i, j] / np.sqrt(d)
            p = np.exp(s - s.max())
            out[i, j] = (p / p.sum()) @ v[lo:i + 1, j]
    return out


@pytest.mark.parametrize("window", [None, 10])
def test_blocked_attention_equals_the_full_form(window):
    import jax
    from archs import common
    rng = np.random.default_rng(5)
    q, k, v = (rng.standard_normal((64, 4, 16)).astype(np.float32)
               for _ in range(3))
    attend = jax.jit(common.attention,
                     static_argnames=("control", "window", "block"))
    full = np.asarray(attend(q, k, v, False, window=window))
    blocked = np.asarray(attend(q, k, v, False, window=window, block=16))
    np.testing.assert_allclose(blocked, full, rtol=0, atol=1e-6)
    np.testing.assert_allclose(full, _attention_by_rows(q, k, v, window),
                               rtol=0, atol=1e-5)
    # The control is float8 under per-tensor scales in both forms: the
    # blocked one equals the full one, and both lie far from the exact
    # rows.
    ctl = np.asarray(attend(q, k, v, True, window=window))
    np.testing.assert_allclose(
        np.asarray(attend(q, k, v, True, window=window, block=16)), ctl,
        rtol=0, atol=1e-6)
    assert np.abs(ctl - full).max() > 1e-2
