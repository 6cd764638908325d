"""Unit tests of the benchmark's yardstick, on the CPU and without a chip:
the trace reduction, the FLOP and byte counts, the traffic generator, the
end-to-end metric definitions, finding a cell's pieces (an architecture
module among them) by name, the step log's counters, and the result
line's schema.

  JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_bench.py
"""
import json
import os
import shutil
import subprocess
import sys

import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, BENCH)

import device_trace  # noqa: E402
import e2e  # noqa: E402
import flops  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402

QWEN3 = json.load(open(os.path.join(BENCH, "configs",
                                    "qwen3-1.7b-hfa.json")))["model"]
DENSE = spec.arch(ROOT, "dense")

# ------------------------------------------------------------ trace reduction
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000
             stats { metadata_id: 1 str_value: "paged_flash_decode_partial" } }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 1000000
             stats { metadata_id: 1 str_value: "paged_flash_decode_partial" } }
    events { metadata_id: 3 offset_ps: 13000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 0 duration_ps: 20000000 } }
  event_metadata { key: 1 value { id: 1 name: "custom-call.7" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.3" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.9" } }
  stat_metadata { key: 1 value { id: 1 name: "long_name" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 6500000
             stats { metadata_id: 1 int64_value: 4 } }
    events { metadata_id: 2 offset_ps: 5500000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 3000000
             stats { metadata_id: 1 int64_value: 5 } }
    events { metadata_id: 1 offset_ps: 12000000 duration_ps: 3000000
             stats { metadata_id: 1 int64_value: 6 } } }
  event_metadata { key: 1 value { id: 1 name: "bench_step" } }
  event_metadata { key: 2 value { id: 2 name: "bench_plan_decode" } }
  stat_metadata { key: 1 value { id: 1 name: "i" } }
}
"""


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData
    return device_trace.read_profile(ProfileData.from_text_proto(SYNTHETIC))


def test_trace_reduction_busy_idle_and_kernels(synthetic):
    # Steps 4 and 5 span [0.5, 10) us; ops busy [1, 5) and [8, 9).
    red = device_trace.reduce(synthetic, {4, 5})
    assert red["window_s"] == pytest.approx(9.5e-6)
    assert red["busy_s"] == pytest.approx(5e-6)
    assert device_trace.kernel_seconds(
        red, "paged_flash_decode_partial") == pytest.approx(4e-6)
    # Gaps [0.5, 1) and [5, 8) and [9, 10): the host planned the decode
    # in [5.5, 6.5) and was elsewhere in its steps the rest of the time.
    gaps = dict(red["idle_gaps"])
    assert gaps["bench_plan_decode"] == pytest.approx(1e-6)
    assert gaps["bench_step"] == pytest.approx(3.5e-6)
    assert sum(gaps.values()) == pytest.approx(red["window_s"]
                                               - red["busy_s"])
    assert red["device_ops"][0] == ["custom-call.7", pytest.approx(4e-6)]
    # The module-level event nests nothing on the ops line here; a
    # loop op's own time excludes its body.
    assert device_trace.self_ns([("loop", 0, 10, ""), ("a", 1, 3, ""),
                                 ("b", 4, 5, ""), ("c", 12, 13, "")]) == \
        [7, 2, 1, 1]
    # Only the step spans chosen count: the op at 13 us is outside.
    assert "fusion.9" not in dict(red["device_ops"])


def test_trace_reduction_without_steps_or_devices(synthetic):
    assert device_trace.reduce(synthetic, {99}) is None
    assert device_trace.reduce({"devices": {}, "host": synthetic["host"]},
                               {4}) is None


def test_union_and_gaps():
    ivs = [(0, 2), (1, 3), (5, 6), (10, 12)]
    assert device_trace.union_ns(ivs, 0, 11) == 3 + 1 + 1
    assert device_trace.gaps_ns(ivs, 0, 11) == [(3, 5), (6, 10)]


# -------------------------------------------------------- flops and bytes
def test_decode_attention_hand_count():
    # qwen3-1.7b: 16 query heads, 8 KV heads, d_head 128, 28 layers, bf16.
    f, b = flops.decode_attention(DENSE, QWEN3, [1000, 3000])
    assert f == 4 * 16 * 128 * 4000 * 28
    assert b == 28 * (2 * 4000 * 8 * 128 * 2 + 2 * 2 * 16 * 128 * 2)


def test_prefill_attention_hand_count():
    # One chunk of 4 at start 10: queries attend 11, 12, 13, 14 rows.
    f, b = flops.prefill_attention(DENSE, QWEN3, [(10, 4, True)])
    assert f == 4 * 16 * 128 * (11 + 12 + 13 + 14) * 28
    assert b == 28 * (2 * 14 * 8 * 128 * 2 + 2 * 4 * 16 * 128 * 2)


def test_step_flops_hand_count():
    layer = 28 * (2048 * (16 + 16) * 128 + 16 * 128 * 2048 + 3 * 2048 * 6144)
    assert DENSE.layer_params(QWEN3) == layer
    head = 2048 * 151936
    got = flops.step_flops(DENSE, QWEN3, [100], [(0, 8, False),
                                                 (8, 8, True)])
    want = (2 * layer * 17 + 2 * head * 2
            + flops.decode_attention(DENSE, QWEN3, [100])[0]
            + flops.prefill_attention(DENSE, QWEN3,
                                      [(0, 8, 0), (8, 8, 0)])[0])
    assert got == want


def test_roofline_share_names_its_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_share(50, 1, 1.0, peak) == (50.0, "compute")
    assert flops.roofline_share(1, 5, 2.0, peak) == (25.0, "memory")


# ------------------------------------------------------------------ traffic
@pytest.mark.parametrize("name", ["docqa-decode", "mixed-long"])
def test_generator_is_deterministic_and_in_range(name):
    mix = traffic.load(os.path.join(BENCH, "traffic", name + ".json"))
    a = traffic.build(mix, 2 ** 31 + 5, 151936, 4736)
    b = traffic.build(mix, 2 ** 31 + 5, 151936, 4736)
    c = traffic.build(mix, 7, 151936, 4736)
    assert a == b and a != c
    for plan in (a, c):
        reqs = [r for s in plan["streams"] for r in s]
        assert len(plan["streams"]) == mix["streams"]
        o = mix["output"]
        for s in plan["streams"]:
            assert 1 <= s[0]["max_new_tokens"] <= o["max"]   # staggered
            assert all(o["min"] <= r["max_new_tokens"] <= o["max"]
                       for r in s[1:])
        u = mix["unique"]
        lo = u.get("min", u.get("value"))
        hi = u.get("max", u.get("value"))
        assert all(lo <= len(r["unique"]) <= hi for r in reqs)
        assert all(0 < t < 151936 for r in reqs for t in r["unique"])
        for p in plan["shared"]:
            s = mix["shared"]["length"]
            assert s["min"] <= len(p) <= s["max"] and len(p) % 16 == 0
    # Every seed gets the same prompt and output sizes, in another order.
    for size in (lambda p, r: traffic.prompt_len(p, r),
                 lambda p, r: r["max_new_tokens"]):
        assert sorted(size(a, r) for s in a["streams"] for r in s[1:]) == \
            sorted(size(c, r) for s in c["streams"] for r in s[1:])


@pytest.mark.parametrize("change", [{"loop": "open"}, {"streams": 0},
                                    {"arrivals": {"rate": 1.0}},
                                    {"output": {"dist": "zipf"}}])
def test_generator_refuses_a_mix_it_cannot_honour(change):
    mix = traffic.load(os.path.join(BENCH, "traffic", "mixed-long.json"))
    with pytest.raises(ValueError):
        traffic.check({**mix, **change})


def test_generator_refuses_requests_over_max_seq():
    mix = traffic.load(os.path.join(BENCH, "traffic", "docqa-decode.json"))
    with pytest.raises(ValueError, match="max_seq"):
        traffic.build(mix, 1, 151936, 2048)


# ---------------------------------------------------------- end-to-end
def _rec(sent=0.0, first=None, times=(), status="done", end=None):
    return {"stream": 0, "idx": 0, "sent": sent, "first": first,
            "end": end, "times": list(times), "tokens": [1] * len(times),
            "status": status, "error": None}


def test_failed_request_is_attempted_and_failed():
    recs = [_rec(sent=1.0, first=1.5, times=[1.5, 2.0], end=2.0),
            _rec(sent=2.0, status="failed", end=2.1),
            _rec(sent=11.0, status="failed", end=11.1)]
    # The third was sent after the window closed at 10.
    assert e2e.attempted_failed(recs, 0.0, 10.0) == (2, 1)


def test_window_counts_only_tokens_inside():
    recs = [_rec(sent=0.0, times=[1.0, 2.0, 3.0, 4.0, 11.0], end=11.5),
            _rec(sent=9.0, times=[9.5], status="cut", end=10.0),
            _rec(sent=-5.0, times=[-4.0], end=-3.0)]
    assert e2e.out_tok_s(recs, 2.0, 10.0) == pytest.approx(4 / 8.0)
    # Mean in-window gap of the first request: (4 - 2) / 2 s.
    assert e2e.tpot_p90_ms(recs, 2.0, 10.0) == pytest.approx(1000.0)
    # The request that ended before the window is not attempted; the
    # cut one is attempted and not failed.
    assert e2e.attempted_failed(recs, 2.0, 10.0) == (2, 0)


def test_percentile_is_linear_between_ranks():
    assert e2e.percentile([1, 2, 3, 4, 5], 90) == pytest.approx(4.6)
    assert e2e.percentile([7], 90) == 7


# --------------------------------------------------------- finding by name
TOY_MODEL = {"num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 8, "hidden_size": 32, "vocab_size": 64,
             "torch_dtype": "float32"}
TOY_ARCH = '''"""A toy architecture: a period of four layers, the first three
attending a window of 8 rows, the fourth its whole context; 4 experts a
layer behind a router the program keeps in float32."""


def shapes(m, padded_vocab):
    d = m["hidden_size"]
    return {"embed/emb": (padded_vocab, d),
            "layers/l0/router": (4, d, 4),
            "layers/l0/wg": (4, 4, d, 16),
            "final_norm/scale": (d,)}


def leaf(path, shape, m):
    """The router in float32 whatever the configuration's dtype; an
    expert matrix (L, E, d, ff) sums its d inputs, not its experts."""
    if path == "embed/emb":
        return "embed", None, m["torch_dtype"]
    if path == "final_norm/scale":
        return "norm", None, m["torch_dtype"]
    if path.endswith("/router"):
        return "matrix", shape[1], "float32"
    return "matrix", shape[2], m["torch_dtype"]


def layer_windows(m):
    return [8, 8, 8, None]


def layer_params(m):
    return 1000.0
'''


@pytest.fixture
def scratch_root(tmp_path):
    """A checkout holding this benchmark plus a throwaway configuration,
    architecture, mix and metric, added as files and entries only."""
    shutil.copytree(BENCH, tmp_path / "bench")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (tmp_path / "bench" / "configs" / "toy.json").write_text(
        json.dumps({"model": TOY_MODEL, "engine": {},
                    "program": {"bench_arch": "toy_windowed"}}))
    (tmp_path / "bench" / "archs" / "toy_windowed.py").write_text(TOY_ARCH)
    (tmp_path / "bench" / "traffic" / "toy-mix.json").write_text(
        json.dumps({"loop": "closed", "streams": 1,
                    "unique": {"dist": "fixed", "value": 4},
                    "output": {"dist": "fixed", "value": 2}}))
    (tmp_path / "bench" / "metrics" / "toy_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "toy", "source": "x",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.mix", "config": "toy",
                               "traffic": "toy-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "toy_metric", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "out_tok_s",
                               "workloads": ["toy.mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_cell_pieces_are_found_by_name(scratch_root):
    c = spec.cell(spec.load(scratch_root), "toy.mix", scratch_root)
    assert c["config"]["model"]["vocab_size"] == 64
    assert c["arch"].layer_windows(TOY_MODEL) == [8, 8, 8, None]
    assert c["traffic"].endswith("toy-mix.json")
    assert [m["name"] for m in c["per_layer"]] == ["toy_metric"]
    assert spec.reader(scratch_root, "toy_metric")(None) == 42.0
    # The metrics without a workloads list apply to every cell.
    assert {m["name"] for m in c["end_to_end"]} >= {"setup_s"}


def test_new_architecture_counts_rows_of_its_windows(scratch_root):
    arch = spec.cell(spec.load(scratch_root), "toy.mix", scratch_root)["arch"]
    h, hkv, dh, b = 4, 2, 8, 4
    # Decode, contexts 5 and 20: a windowed layer attends 5 + 8 rows,
    # the full one 5 + 20.
    f, by = flops.decode_attention(arch, TOY_MODEL, [5, 20])
    assert f == 4 * h * dh * (3 * 13 + 25)
    assert by == 3 * (2 * 13 * hkv * dh * b + 2 * 2 * h * dh * b) + \
        (2 * 25 * hkv * dh * b + 2 * 2 * h * dh * b)
    # Prefill: a chunk of 4 at 6 (queries attend 7, 8, 9, 10 rows;
    # windowed 7, 8, 8, 8 of the same 10 K/V rows) and one of 4 at 20
    # (21..24 rows; windowed 8 each, of the 11 rows 13..23).
    f, by = flops.prefill_attention(arch, TOY_MODEL, [(6, 4, False),
                                                      (20, 4, True)])
    assert f == 4 * h * dh * (3 * (31 + 32) + (34 + 90))
    assert by == 3 * (2 * (10 + 11) * hkv * dh * b + 2 * 8 * h * dh * b) + \
        (2 * (10 + 24) * hkv * dh * b + 2 * 8 * h * dh * b)
    assert flops.step_flops(arch, TOY_MODEL, [5], []) == \
        2 * 1000.0 + 2 * 32 * 64 + flops.decode_attention(
            arch, TOY_MODEL, [5])[0]


def test_new_architecture_owns_its_leaves_dtypes_and_fills(scratch_root):
    import jax.numpy as jnp
    import numpy as np

    import harness
    import weights
    arch = spec.cell(spec.load(scratch_root), "toy.mix", scratch_root)["arch"]
    m = dict(TOY_MODEL, torch_dtype="bfloat16")
    w = weights.make(arch, m, 128, 2 ** 40 + 3)
    leaves = weights.flat(w)
    assert {k: v.dtype for k, v in leaves.items()} == {
        "embed/emb": jnp.bfloat16, "layers/l0/router": jnp.float32,
        "layers/l0/wg": jnp.bfloat16, "final_norm/scale": jnp.bfloat16}
    # The fan-in is the module's: d = 32 inputs, not the 4 experts.
    wg = np.asarray(leaves["layers/l0/wg"], np.float32)
    assert abs(wg.std() - 32 ** -0.5) < 0.01
    emb = np.asarray(leaves["embed/emb"], np.float32)
    assert not emb[64:].any() and np.abs(emb[:64]).min(axis=1).all()

    class Model:
        """The program's tree, its router in ``router_dtype``."""

        def __init__(self, router_dtype):
            self.router_dtype = router_dtype

        def init(self, key):
            bf = jnp.bfloat16
            return {"embed": {"emb": jnp.zeros((128, 32), bf)},
                    "layers": {"l0": {
                        "router": jnp.zeros((4, 32, 4), self.router_dtype),
                        "wg": jnp.zeros((4, 4, 32, 16), bf)}},
                    "final_norm": {"scale": jnp.zeros((32,), bf)}}

    harness.check_layout(Model(jnp.float32), w)
    with pytest.raises(harness.HarnessError, match="router"):
        harness.check_layout(Model(jnp.bfloat16), w)


def test_step_log_carries_every_counter_and_the_phases():
    import harness

    class Engine:
        """Counters the harness never names, a text entry, and one
        phase; a step moves them."""
        stats = {"steps": 0, "toy_pages": 0, "note": "text"}
        phases = {"engine.plan": (0.0, 0.0, 0)}
        sched = types.SimpleNamespace(schedule_prefill=None,
                                      schedule_decode=None)

        def step(self):
            self.stats["steps"] += 1
            self.stats["toy_pages"] += 7
            w, c, n = self.phases["engine.plan"]
            self.phases["engine.plan"] = (w + 0.5, c + 0.25, n + 1)

    eng = Engine()
    log = harness.StepLog(eng)
    for _ in range(3):
        eng.step()
    steps = log.steps
    assert steps[-1]["stats"] == {"steps": 3, "toy_pages": 21}
    assert steps[-1]["phases"] == {"engine.plan": (1.5, 0.75, 3)}
    assert steps[0]["phases"] == {"engine.plan": (0.5, 0.25, 1)}
    inside, before = log.window(steps[1]["t0"], steps[2]["t1"])
    assert [s["i"] for s in inside] == [1, 2]
    assert before == {"stats": {"steps": 1, "toy_pages": 7},
                      "phases": {"engine.plan": (0.5, 0.25, 1)}}
    inside, before = log.window(steps[0]["t0"], steps[0]["t1"])
    assert before == {"stats": {"steps": 0, "toy_pages": 0},
                      "phases": {"engine.plan": (0.0, 0.0, 0)}}


def test_every_metric_and_cell_of_the_benchmark_resolves():
    bench = spec.load(ROOT)
    for w in bench["workloads"]:
        c = spec.cell(bench, w["name"], ROOT)
        traffic.load(c["traffic"])
        for m in c["end_to_end"] + c["per_layer"]:
            assert callable(spec.reader(ROOT, m["name"]))


@pytest.mark.parametrize("kind,name", [("workload", "no.such.cell"),
                                       ("reader", "no_such_metric"),
                                       ("arch", "no_such_arch"),
                                       ("peak", "TPU v99")])
def test_unknown_names_are_refused(kind, name):
    with pytest.raises(spec.UnknownName):
        if kind == "workload":
            spec.cell(spec.load(ROOT), name, ROOT)
        elif kind == "reader":
            spec.reader(ROOT, name)
        elif kind == "arch":
            spec.arch(ROOT, name)
        else:
            spec.peak(ROOT, name)


# --------------------------------------------------------------- the command
def test_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "qwen3-hfa.docqa-decode", "--seed",
                        "1", "--seconds", "1", "--trace", "0"], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
