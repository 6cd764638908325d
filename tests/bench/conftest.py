"""A tiny cell for the benchmark's end-to-end tests on the CPU: the
qwen3-1.7b configuration cut to toy widths, on the fa2 rail, under a
small closed-loop mix, added to a copy of the benchmark as files and
entries only.  Its limits are set like a cell's, from readings at this
size (2-s windows, every served token compared, eleven seeds): the
program's widest gap 0.0036-0.0086, mean gap 1.6e-5-6.9e-5 and share
of gaps over 0.02 always 0; the control's (the reference in float8 in
the program's place) 0.081-0.252, 0.0038-0.0092 and 0.078-0.167 (four
seeds).

Four cells share the one configuration and differ only in the numbers
``correct`` compares: "tiny.both" all three, "tiny.max" the widest gap
alone, "tiny.mean" the mean gap alone, "tiny.share" the share alone.
"""
import json
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

TINY_MAX = 0.03
TINY_MEAN = 0.0005
TINY_OVER = 0.02
TINY_SHARE = 0.01
LIMITS = {"both": (TINY_MAX, TINY_MEAN, TINY_SHARE),
          "max": (TINY_MAX, None, None), "mean": (None, TINY_MEAN, None),
          "share": (None, None, TINY_SHARE)}


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench")
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "qwen3-1.7b-hfa.json")))
    cfg["model"].update(hidden_size=64, intermediate_size=128,
                        num_hidden_layers=2, num_attention_heads=4,
                        num_key_value_heads=2, head_dim=16, vocab_size=512)
    cfg["engine"].update(max_batch=4, num_pages=64, max_seq=256,
                         prefill_budget=64)
    cfg["program"]["attn_impl"] = "fa2"
    (root / "bench" / "traffic" / "tiny-mix.json").write_text(json.dumps({
        "loop": "closed", "streams": 4,
        "shared": {"count": 2, "length": {"dist": "loguniform", "min": 32,
                                          "max": 96, "round": 16}},
        "unique": {"dist": "fixed", "value": 16},
        "output": {"dist": "loguniform", "min": 8, "max": 24},
        "stagger_first": True, "rounds": 64}))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for name, (gap_max, gap_mean, share) in LIMITS.items():
        cfg["correct"] = {"logit_gap_max": gap_max,
                          "logit_gap_mean": gap_mean,
                          "logit_gap_share_over": TINY_OVER,
                          "logit_gap_share": share,
                          "tokens_compared_min": 20}
        (root / "bench" / "configs" / f"tiny-{name}.json").write_text(
            json.dumps(cfg))
        bench["configs"].append({"name": f"tiny-{name}", "source": "test",
                                 "file": f"bench/configs/tiny-{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": f"tiny.{name}",
                                   "config": f"tiny-{name}",
                                   "traffic": "tiny-mix", "chips": 1,
                                   "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def run_tiny(root, cell, seed, **kw):
    """One whole run of a tiny cell, skipping only the look for a chip."""
    import harness
    return harness.run_cell(root, cell, seed, 2.0, False, time.monotonic(),
                            require_tpu=False, log=lambda m: None, **kw)
