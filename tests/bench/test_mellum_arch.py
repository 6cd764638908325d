"""The Mellum2 architecture module (``bench/archs/mellum.py``) and the
served model against it, on the CPU.

A small copy of the configuration keeps Mellum's layout at toy widths:
one whole period (3 windowed layers, 1 full), GQA group 4, a window of
24 rows on pages of 8 (no page multiple) with contexts several pages
past it, YaRN over an original length of 512 so that its ramp covers
interior dims, and 16 router experts of which this chip holds 4 (from
expert 4), top-4.  Float32, so the comparison sees the mechanisms and
not the dtype.

  JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_mellum_arch.py
"""
import json
import os
import shutil
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

import flops  # noqa: E402
import spec  # noqa: E402

CONFIG = "mellum2-12b-a2.5b-ep4"
CELL = "mellum2-ep4.repo-qa"
SEED = 2 ** 31 + 5
PAGE = 8


def _config(rail="fa2"):
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    m = cfg["model"]
    m.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
             head_dim=32, moe_intermediate_size=48, num_experts=4,
             router_experts=16, first_expert=4, num_experts_per_tok=4,
             num_hidden_layers=4, layer_types=m["layer_types"][:4],
             mlp_layer_types=m["mlp_layer_types"][:4], sliding_window=24,
             vocab_size=256, torch_dtype="float32")
    m["rope_parameters"] = {
        "sliding_attention": {"rope_type": "default", "rope_theta": 1e4},
        "full_attention": {"rope_type": "yarn", "rope_theta": 1e4,
                           "factor": 4.0,
                           "original_max_position_embeddings": 512,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.1}}
    cfg["engine"].update(max_batch=4, num_pages=96, max_seq=192,
                         prefill_budget=32, page_size=PAGE)
    cfg["program"]["attn_impl"] = rail
    return cfg


def _model(cfg):
    import harness
    import weights
    from repro.models.model import build_model
    arch = spec.arch(ROOT, "mellum")
    model = build_model(arch.program_config(cfg))
    w = weights.make(arch, cfg["model"], model.cfg.padded_vocab, SEED)
    harness.check_layout(model, w)
    return arch, model, w


def _engine(model, w, cfg):
    from repro.serving import ServingEngine
    e = cfg["engine"]
    return ServingEngine(model, w, max_batch=e["max_batch"],
                         page_size=e["page_size"], num_pages=e["num_pages"],
                         max_seq=e["max_seq"],
                         prefill_budget=e["prefill_budget"])


def _row(tokens):
    import archs.mellum as mellum
    row = np.zeros(-(-len(tokens) // (2 * mellum.BLOCK)) * 2 * mellum.BLOCK,
                   np.int32)
    row[:len(tokens)] = tokens
    return row


def _ref_logprobs(arch, w, m, tokens, control):
    """log p(tokens[i + 1] | tokens[:i + 1]) of the reference, over the
    padded vocabulary as the program's head gives it (padded ids have
    zero weights: logit 0)."""
    import jax
    import jax.numpy as jnp
    hid = arch.hidden(w, _row(tokens[:-1]), m, control)
    lg = jnp.einsum("td,dv->tv", hid, w["lm_head"].astype(jnp.float32),
                    precision="highest")
    lsm = np.asarray(jax.nn.log_softmax(lg, axis=-1))
    return lsm[np.arange(len(tokens) - 1), tokens[1:]]


# |program - reference| of the log-probability of each prompt token
# (chunked prefill) and each served token (decode through the cache).
# fa2: both float32, so only the order of sums differs (1e-6 measured);
# 1e-4 leaves 100x of room, and float8 operands read 0.04-0.07 at most.
FA2_MAX = 1e-4
# hfa: the rail's PWL exponentials and LogDiv (15% relative RMS on the
# attention weights) move logprobs by 0.0057-0.0094 on average
# and 0.012-0.019 at the 90th percentile over eight seeded prompts; the
# float8 reference reads 0.012-0.020 and 0.026-0.037.
HFA_MEAN = 0.011
HFA_P90 = 0.024


@pytest.mark.parametrize("rail", ["fa2", "hfa"])
def test_served_logprobs_match_the_reference(rail):
    """Through ServingEngine: two prompts of 90 and 61 tokens prefilled
    in 32-token chunks (so past the window from the second chunk on),
    then 20 tokens decoded through the cache, each with its logprob;
    every one against the reference, teacher-forced.  The reference in
    float8 fails the same limits."""
    from repro.serving import Request
    cfg = _config(rail)
    arch, model, w = _model(cfg)
    eng = _engine(model, w, cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, n).tolist() for n in (90, 61)]
    out = eng.run([(0, Request(rid=i, prompt=p, max_new_tokens=20,
                               logprobs=True))
                   for i, p in enumerate(prompts)])
    got, ctl = [], []
    for fr in out:
        toks = fr.prompt + fr.tokens
        prog = np.array(fr.prompt_logprobs[1:] + fr.token_logprobs)
        got.append(np.abs(prog - _ref_logprobs(arch, w, cfg["model"], toks,
                                               False)))
        ctl.append(np.abs(_ref_logprobs(arch, w, cfg["model"], toks, True)
                          - _ref_logprobs(arch, w, cfg["model"], toks,
                                          False)))
    got, ctl = np.concatenate(got), np.concatenate(ctl)
    assert got.size == 89 + 60 + 40
    if rail == "fa2":
        assert got.max() <= FA2_MAX
        assert ctl.max() > FA2_MAX
    else:
        assert got.mean() <= HFA_MEAN and np.percentile(got, 90) <= HFA_P90
        assert ctl.mean() > HFA_MEAN or np.percentile(ctl, 90) > HFA_P90


def test_expert_counters_match_the_reference_routing():
    """``moe_rows_local``, ``moe_rows_routed`` and ``moe_experts_active``
    after serving one request at a time equal a recount from the
    reference's routing of the same tokens: prompt chunks of 32, then
    one position a decode step."""
    from repro.serving import Request
    cfg = _config()
    arch, model, w = _model(cfg)
    m = cfg["model"]
    eng = _engine(model, w, cfg)
    rng = np.random.default_rng(2)
    first, held, k = m["first_expert"], m["num_experts"], \
        m["num_experts_per_tok"]
    keys = ("moe_rows_local", "moe_rows_routed", "moe_experts_active")
    for rid, n in enumerate((75, 40)):
        before = {key: eng.stats[key] for key in keys}
        fr, = eng.run([(0, Request(rid=rid,
                                   prompt=rng.integers(1, 256, n).tolist(),
                                   max_new_tokens=9))])
        toks = fr.prompt + fr.tokens[:-1]
        routes = np.asarray(arch.routes(w, _row(toks), m))[:, :len(toks)]
        calls = [range(s, min(s + 32, n)) for s in range(0, n, 32)] + \
            [range(p, p + 1) for p in range(n, len(toks))]
        want = {key: 0 for key in keys}
        for call in calls:
            for layer in routes:
                local = layer[list(call)].reshape(-1) - first
                mine = local[(local >= 0) & (local < held)]
                want["moe_rows_local"] += mine.size
                want["moe_experts_active"] += len(set(mine.tolist()))
            want["moe_rows_routed"] += len(call) * k * len(routes)
        assert {key: eng.stats[key] - before[key] for key in keys} == want


@pytest.mark.parametrize("control", [False, True])
def test_band_attention_equals_the_full_form(control):
    """A window layer's blocked reference, reading only its band of keys,
    equals the shared attention over every key with the window mask -
    the float8 control too (both scale per tensor)."""
    import jax
    from archs import common
    import archs.mellum as mellum
    rng = np.random.default_rng(4)
    q, k, v = (rng.standard_normal((1024, 2, 8)).astype(np.float32)
               for _ in range(3))
    for window in (24, 700):
        band = np.asarray(jax.jit(mellum._band, static_argnums=(3, 4))(
            q, k, v, window, control))
        full = np.asarray(jax.jit(common.attention, static_argnames=(
            "control", "window"))(q, k, v, control, window=window))
        np.testing.assert_allclose(band, full, rtol=0, atol=2e-6)


def test_work_counts():
    """At the published sizes: 21 windowed and 7 full layers; a token
    passes through attention, the router and the expected 8 x 16/64 = 2
    held experts; the expert work of rows and active pairs; a windowed
    decode row attends at most 1024 rows."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        m = json.load(f)["model"]
    arch = spec.arch(ROOT, "mellum")
    wins = arch.layer_windows(m)
    assert wins.count(1024) == 21 and wins.count(None) == 7
    d, ff = 2304, 896
    attn = d * (32 + 8) * 128 + 32 * 128 * d
    assert arch.layer_params(m) == 28 * (attn + d * 64 + 2 * 3 * d * ff)
    assert arch.expert_work(m, 10, 3) == (6.0 * d * ff * 10,
                                          3.0 * d * ff * 2 * 3
                                          + 2.0 * d * 2 * 10)
    f_full, _ = flops.decode_attention(arch, m, [5000])
    assert f_full == 4.0 * 32 * 128 * (7 * 5000 + 21 * 1024)


def _run(stats0, stats1, trace=None, arch=None, model=None):
    """A harness ``Run`` stand-in with one step in the window."""
    return types.SimpleNamespace(
        steps=[{"stats": stats1, "dec": [3000, 500], "pre": []}],
        before={"stats": stats0}, trace=trace, arch=arch, model=model,
        peak={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})


def test_metric_readers():
    """The four readers on a stand-in run, and nothing on a run whose
    engine has no expert counters or whose trace lacks the kernels (a
    program without this architecture)."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        m = json.load(f)["model"]
    arch = spec.arch(ROOT, "mellum")
    per = spec.reader(ROOT, "moe_rows_per_expert")
    gmm = spec.reader(ROOT, "moe_gmm_roofline")
    win = spec.reader(ROOT, "window_decode_roofline")
    full = spec.reader(ROOT, "full_decode_roofline")
    s0 = {"moe_rows_local": 100, "moe_experts_active": 10}
    s1 = {"moe_rows_local": 500, "moe_experts_active": 110}
    trace = {"ops": [["fusion.1", "moe_gmm tpu_custom_call", 0.01],
                     ["fusion.2", "paged_flash_decode_window", 0.002],
                     ["fusion.3", "paged_flash_decode_partial", 0.004]],
             "window_s": 1.0}
    assert per(_run(s0, s1)) == 4.0
    f, b = arch.expert_work(m, 400, 100)
    want = 100 * max(f / 197e12, b / 819e9) / 0.01
    assert gmm(_run(s0, s1, trace, arch, m)) == pytest.approx(want)
    wf, wb = flops.decode_attention(
        types.SimpleNamespace(layer_windows=lambda m: [1024] * 21), m,
        [3000, 500])
    assert win(_run(s0, s1, trace, arch, m)) == pytest.approx(
        100 * max(wf / 197e12, wb / 819e9) / 0.002)
    ff, fb = flops.decode_attention(
        types.SimpleNamespace(layer_windows=lambda m: [None] * 7), m,
        [3000, 500])
    assert full(_run(s0, s1, trace, arch, m)) == pytest.approx(
        100 * max(ff / 197e12, fb / 819e9) / 0.004)
    bare = _run({}, {}, {"ops": [], "window_s": 1.0}, arch, m)
    assert per(bare) is None and gmm(bare) is None and win(bare) is None
    assert full(bare) is None
    dense = spec.arch(ROOT, "dense")
    assert win(_run(s0, s1, trace, dense, m)) is None


def test_cell_is_declared():
    """The cell and its configuration as the benchmark names them: the
    four metrics listed for this cell alone."""
    bench = spec.load(ROOT)
    c = spec.cell(bench, CELL, ROOT)
    assert c["config"]["program"]["bench_arch"] == "mellum"
    assert [x["name"] for x in c["end_to_end"]] == ["out_tok_s", "setup_s"]
    mine = {x["name"] for x in bench["per_layer"]
            if x.get("workloads") == [CELL]}
    assert mine == {"window_decode_roofline", "full_decode_roofline",
                    "moe_gmm_roofline", "moe_rows_per_expert"}


def test_published_keys_are_the_ones_run():
    """The file states the published keys at its top level and again
    under ``model``, which the harness runs; the two never differ, and
    the only key cut from the published config is the one ``reduced``
    names."""
    with open(os.path.join(ROOT, "bench", "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    own = {"source", "model", "program", "engine", "correct", "reduced",
           "deployment", "assumed"}
    top = {k: v for k, v in cfg.items() if k not in own}
    assert len(top) == 23
    assert top == {k: cfg["model"][k] for k in top}
    assert cfg["reduced"] == ["num_experts"]
    assert top["num_experts"] * 4 == cfg["model"]["router_experts"] == 64


@pytest.fixture(scope="module")
def tiny_mellum_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("mellum_checkout")
    shutil.copytree(BENCH, root / "bench")
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    cfg = _config("hfa")
    cfg["model"]["torch_dtype"] = "bfloat16"
    cfg["engine"].update(num_pages=96, max_seq=256, prefill_budget=64)
    cfg["correct"]["tokens_compared_min"] = 20
    (root / "bench" / "configs" / "tiny-mellum.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny-qa.json").write_text(json.dumps({
        "loop": "closed", "streams": 4,
        "shared": {"count": 2, "length": {"dist": "loguniform", "min": 48,
                                          "max": 112, "round": 16}},
        "unique": {"dist": "fixed", "value": 16},
        "output": {"dist": "loguniform", "min": 8, "max": 24},
        "stagger_first": True, "rounds": 64}))
    bench = spec.load(ROOT)
    bench["configs"].append({"name": "tiny-mellum", "source": "test",
                             "file": "bench/configs/tiny-mellum.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.mellum",
                               "config": "tiny-mellum",
                               "traffic": "tiny-qa", "chips": 1,
                               "why": "test"})
    for x in bench["per_layer"]:
        if x.get("workloads") == [CELL]:
            x["workloads"].append("tiny.mellum")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_tiny_cell_runs_correct(tiny_mellum_root):
    """A whole run of a bf16 hfa Mellum cell at toy widths through the
    harness: every served token checked against the reference, and the
    expert counters read."""
    import harness
    result, checks, _ = harness.run_cell(
        tiny_mellum_root, "tiny.mellum", SEED, 1.5, False, time.monotonic(),
        require_tpu=False, log=lambda msg: None)
    assert result["correct"], checks
    assert result["failed"] == 0
    assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
