"""Phase spans and counters of the serving loop (repro.serving.tracing).

  * the engine phases tile ``ServingEngine.step``: their summed wall
    time covers the step's, and every counter only grows;
  * under ``jax.profiler`` the engine's phase spans land in the host
    plane, nested inside the step that ran them, with its step number;
  * an ``AsyncFrontend`` run advances the ``frontend.*`` phases;
  * ``GET /stats`` serves the phase mapping;
  * the adaptive prefill budget's rate comes from the prefill phases.
"""
import asyncio
import glob
import time

import numpy as np
import pytest

import jax

from repro.serving import (AsyncFrontend, HttpServer, Request,
                           SamplingParams, ServingEngine, tracing)
from repro.serving.http import http_json, stream_generate


@pytest.fixture(scope="module")
def qwen_smoke():
    from repro.configs import get_config
    from repro.models.model import build_model
    cfg = get_config("qwen3-1.7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(model, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_seq", 64)
    return ServingEngine(model, params, **kw)


def _prompt(cfg, seed, n):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, n).tolist()


def _mixed_requests(cfg):
    """Greedy, sampled, logprobs and a parallel group: every engine
    phase runs within a few steps."""
    return [Request(rid=0, prompt=_prompt(cfg, 1, 11), max_new_tokens=6),
            Request(rid=1, prompt=_prompt(cfg, 2, 7), max_new_tokens=5,
                    sampling=SamplingParams(temperature=0.8, seed=3)),
            Request(rid=2, prompt=_prompt(cfg, 3, 9), max_new_tokens=4,
                    logprobs=True),
            Request(rid=3, prompt=_prompt(cfg, 4, 6), max_new_tokens=4, n=2,
                    sampling=SamplingParams(temperature=0.7, seed=5))]


def _engine_wall(phases) -> float:
    return tracing.total_wall_s(phases, tracing.ENGINE_PHASES)


def test_engine_phases_tile_the_step(qwen_smoke):
    cfg, model, params = qwen_smoke
    eng = _engine(model, params, max_batch=6, prefill_budget=8)
    assert set(eng.phases) == set(tracing.ENGINE_PHASES
                                  + tracing.FRONTEND_PHASES)
    for r in _mixed_requests(cfg):
        eng.submit(r)
    prev = dict(eng.phases)
    covered = []
    while eng.sched.has_work:
        t0 = time.perf_counter()
        eng.step()
        wall = time.perf_counter() - t0
        now = dict(eng.phases)
        covered.append((_engine_wall(now) - _engine_wall(prev)) / wall)
        for name in now:
            for a, b in zip(prev[name], now[name]):
                assert b >= a, (name, prev[name], now[name])
        prev = now
    assert len(covered) > 3
    assert min(covered) <= 1.0 + 1e-9
    assert np.median(covered) >= 0.98
    ran = {n for n in tracing.ENGINE_PHASES if eng.phases[n].count}
    assert ran == set(tracing.ENGINE_PHASES)
    assert eng.phases["engine.decode.dispatch"].count == \
        eng.stats["decode_steps"]
    assert all(eng.phases[n].count == 0 for n in tracing.FRONTEND_PHASES)
    # Two capacity passes and at least one planning pass per step.
    assert eng.phases["engine.capacity"].count == 2 * eng.stats["steps"]
    assert eng.phases["engine.plan"].count >= eng.stats["steps"]


def test_profiler_trace_nests_the_phase_spans_in_the_step(qwen_smoke,
                                                          tmp_path):
    cfg, model, params = qwen_smoke
    eng = _engine(model, params)
    eng.submit(Request(rid=0, prompt=_prompt(cfg, 7, 9), max_new_tokens=4))
    eng.step()                      # compiles outside the trace
    first = eng.stats["steps"]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for i in range(2):
            with jax.profiler.TraceAnnotation("test_step", i=i):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    steps, spans = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                if e.name == "test_step":
                    steps.append((int(dict(e.stats)["i"]), *iv))
                elif e.name.startswith("engine."):
                    spans.append((e.name, int(dict(e.stats)["step"]), *iv))
    assert sorted(i for i, _, _ in steps) == [0, 1]
    names = {n for n, *_ in spans}
    assert {"engine.capacity", "engine.plan", "engine.decode.inputs",
            "engine.decode.dispatch", "engine.decode.sync",
            "engine.decode.commit"} <= names
    for i, lo, hi in steps:
        inside = [s for s in spans if s[1] == first + i]
        assert inside
        assert all(lo <= s <= e <= hi for _, _, s, e in inside)


def test_frontend_run_advances_frontend_phases(qwen_smoke):
    cfg, model, params = qwen_smoke

    async def main():
        fe = AsyncFrontend(_engine(model, params))
        toks = [t async for t in fe.submit(
            Request(rid=0, prompt=_prompt(cfg, 8, 5), max_new_tokens=4))]
        await fe.close()
        return fe.engine, toks

    eng, toks = asyncio.run(main())
    assert len(toks) == 4
    ph = eng.phases
    steps = eng.stats["steps"]
    assert ph["frontend.handoff"].count == steps
    assert ph["frontend.publish"].count == steps
    assert ph["frontend.intake"].count >= steps
    assert ph["frontend.handoff"].cpu_s == 0.0
    for name in tracing.FRONTEND_PHASES:
        assert ph[name].wall_s > 0.0, name


def test_stats_serves_the_phase_mapping(qwen_smoke):
    cfg, model, params = qwen_smoke

    async def main():
        fe = AsyncFrontend(_engine(model, params))
        server = await HttpServer(fe).start()
        async for _ in stream_generate(server.host, server.port,
                                       {"prompt": _prompt(cfg, 9, 3),
                                        "max_new_tokens": 3}):
            pass
        status, st = await http_json(server.host, server.port, "GET",
                                     "/stats")
        await server.stop()
        await fe.close()
        return status, st

    status, st = asyncio.run(main())
    assert status == 200
    phases = st["phases"]
    assert set(phases) == set(tracing.ENGINE_PHASES
                              + tracing.FRONTEND_PHASES)
    for name, t in phases.items():
        assert set(t) == {"wall_s", "cpu_s", "count"}, name
    assert phases["engine.decode.sync"]["count"] == \
        st["engine"]["decode_steps"]
    assert phases["frontend.handoff"]["count"] == st["engine"]["steps"]


def test_adaptive_budget_rate_comes_from_the_prefill_phases(qwen_smoke):
    cfg, model, params = qwen_smoke
    eng = _engine(model, params, prefill_budget="adaptive")
    eng.submit(Request(rid=0, prompt=_prompt(cfg, 10, 13),
                       max_new_tokens=3))
    rates = []
    while eng.sched.has_work:
        wall = tracing.total_wall_s(eng.phases, tracing.PREFILL_PHASES)
        tok = eng.stats["prefill_tokens"]
        eng.step()
        wall = tracing.total_wall_s(eng.phases, tracing.PREFILL_PHASES) \
            - wall
        tok = eng.stats["prefill_tokens"] - tok
        if tok:
            rate = tok / wall
            rates.append(rate if not rates else 0.8 * rates[-1] + 0.2 * rate)
            assert eng._prefill_rate == pytest.approx(rates[-1], rel=1e-12)
    assert rates
