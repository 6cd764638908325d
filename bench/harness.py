"""One run of one cell: the serving stack on the chip, driven over HTTP by
the load generator's child process, measured, and checked against the
reference.  ``bench/run.py`` is the command; this module is its body.

Set-up (all of it counts in ``setup_s``):

  1. the weights, made on the device from the seed (``weights.make``)
     in the layout the configuration's architecture module gives
     (``bench/archs/``), checked against the program model's own
     parameter shapes;
  2. the stack the HTTP entry point serves - ``ServingEngine`` ->
     ``AsyncFrontend`` -> ``HttpServer`` on an ephemeral loopback port, as
     the program's ``build_server`` builds it, but with the
     configuration's ``num_pages`` (``build_server`` takes none);
  3. the mix's shared prefixes prefilled, so the prefix cache holds them;
  4. every program shape the cell's traffic can reach compiled, by
     serving requests of those shapes (``warm``), so nothing compiles in
     the window;
  5. the ramp: the load generator starts, and the window opens when it
     says (see ``loadgen``).

The engine's ``step`` and its scheduler's two planning calls are wrapped
on the instance: the wrapper logs each step's host times, every numeric
counter of ``engine.stats``, the program's phase totals, and the live
context of every attention call, and writes the host spans the trace
reduction names idle gaps by.  Nothing of the program is changed.

After the window: the device's peak memory is read, the program's state
freed, and every served request compared with the plain reference
(``reference``).
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import glob
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
import device_trace  # noqa: E402
import e2e  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402

WARM_NEW_TOKENS = 2             # first token from prefill, one decode


class HarnessError(RuntimeError):
    pass


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take it."""
    model: dict                 # the configuration's model keys
    arch: object                # its architecture module
    peak: dict                  # the chip's peaks
    setup_s: float
    t0: float                   # window, client clock (time.monotonic)
    t1: float
    records: list               # load generator records
    steps: list                 # step log entries inside the window
    before: dict                # {"stats", "phases"} before the first
    trace: dict | None = None   # device_trace.reduce() of the window


# ------------------------------------------------------------------ model
def check_layout(model, w) -> None:
    """The benchmark's weight tree must be exactly the program's."""
    import jax
    import weights
    want = weights.flat(jax.eval_shape(model.init, jax.random.key(0)))
    got = weights.flat(w)
    bad = sorted(set(want) ^ set(got)) + sorted(
        k for k in set(want) & set(got)
        if (want[k].shape, want[k].dtype) != (got[k].shape, got[k].dtype))
    if bad:
        raise HarnessError(f"weight tree differs from the program's at {bad}")


# ---------------------------------------------------------------- warm-up
def _pow2_width(pages: int, cap: int) -> int:
    w = 1
    while w < pages:
        w *= 2
    return min(w, cap)


def _pages(n: int, page: int) -> int:
    return -(-n // page)


def warm_plan(plan: dict, eng: dict) -> tuple[list, list]:
    """The warm-up: (template request, group size) pairs whose prefill
    chunks, first-token sampling and first decode hit every program
    shape the plan's requests can reach, and the decode widths no
    template reaches alone (served by filler prompts).

    Shapes: a prefill chunk is (group size, chunk rounded up to a page,
    page-table width rounded up to a power of two); a decode step is
    (max_batch, width over its slots).  Requests that arrive together
    are prefilled as one group, up to ``prefill_budget // chunk``."""
    page, budget = eng["page_size"], eng["prefill_budget"]
    pps = _pages(eng["max_seq"], page)
    groups, seen, covered = [], set(), set()
    lo, hi = None, 0
    for req in (r for s in plan["streams"] for r in s):
        plen = traffic.prompt_len(plan, req)
        lo = plen if lo is None else min(lo, plen)
        hi = max(hi, plen + req["max_new_tokens"] - 1)
        cached = 0
        if req["prefix"] is not None:
            cached = min(len(plan["shared"][req["prefix"]]) // page,
                         (plen - 1) // page) * page
        chunks, pos = [], cached
        while pos < plen:
            c = min(budget, plen - pos)
            chunks.append((_pages(c, page), _pow2_width(_pages(pos + c, page),
                                                        pps)))
            pos += c
        dec = _pow2_width(_pages(plen + 1, page), pps)
        sig = (tuple(chunks), dec)
        if sig in seen:
            continue
        seen.add(sig)
        covered.add(dec)
        gmax = 1 if plen - cached > budget else \
            max(1, min(eng["max_batch"], budget // (plen - cached)))
        groups += [(req, g) for g in range(1, gmax + 1)]
    fillers = sorted({_pow2_width(_pages(n + 1, page), pps)
                      for n in range(lo, hi + 1)} - covered)
    return groups, fillers


def _filler_len(width: int, page: int, pps: int) -> int:
    """A prompt length whose first decode needs a page table of ``width``."""
    p = 1
    while _pow2_width(p, pps) < width:
        p += 1
    return (p - 1) * page + 1


def warm(engine, plan: dict, eng: dict, seed: int) -> int:
    """Serve the warm-up requests straight through the engine (before the
    HTTP front end takes it over); returns how many were served."""
    from repro.serving import Request
    rng = np.random.default_rng([seed, 2])
    vocab = engine.model.cfg.vocab_size
    groups, fillers = warm_plan(plan, eng)
    rid = 1 << 40

    def req(prompt):
        nonlocal rid
        rid += 1
        return Request(rid=rid, prompt=prompt, max_new_tokens=WARM_NEW_TOKENS)

    n = 0
    for template, g in groups:
        pre = [] if template["prefix"] is None else \
            plan["shared"][template["prefix"]]
        batch = [req(pre + rng.integers(1, vocab, len(template["unique"]))
                     .tolist()) for _ in range(g)]
        engine.run([(0, r) for r in batch])
        n += g
    pps = _pages(eng["max_seq"], eng["page_size"])
    for width in fillers:
        length = _filler_len(width, eng["page_size"], pps)
        engine.run([(0, req(rng.integers(1, vocab, length).tolist()))])
        n += 1
    return n


# --------------------------------------------------------------- step log
def counters(engine) -> dict:
    """The engine's counters as they stand: every numeric entry of
    ``engine.stats`` under ``"stats"``, a copy of ``engine.phases`` (the
    phase totals; none where the program keeps none) under
    ``"phases"``."""
    return {"stats": {k: v for k, v in engine.stats.items()
                      if isinstance(v, (int, float))},
            "phases": dict(getattr(engine, "phases", {}))}


class StepLog:
    """Wraps ``engine.step`` and the scheduler's planning calls on the
    instance: per step, its host start and end, the counters after it
    (``counters``), the context length of every decode row and the
    (start, length, final) of every prefill chunk, and a host span for
    the trace."""

    def __init__(self, engine):
        import jax
        span = jax.profiler.TraceAnnotation
        self.steps: list[dict] = []
        cur: dict = {}
        sched = engine.sched
        step, plan_pre, plan_dec = (engine.step, sched.schedule_prefill,
                                    sched.schedule_decode)

        def schedule_prefill(budget):
            with span("bench_plan_prefill"):
                chunks, reused = plan_pre(budget)
            cur["pre"] += [(ck.start, len(ck.tokens), ck.is_final)
                           for ck in chunks]
            return chunks, reused

        def schedule_decode(*args, **kw):
            with span("bench_plan_decode"):
                plans = plan_dec(*args, **kw)
            cur["dec"] += [int(engine.cache.seq_lens[p.slot]) + len(p.tokens)
                           for p in plans]
            return plans

        def logged_step():
            cur.clear()
            cur.update(i=len(self.steps), pre=[], dec=[])
            t0 = time.monotonic()
            with span("bench_step", i=cur["i"]):
                out = step()
            rec = dict(cur, t0=t0, t1=time.monotonic(), **counters(engine))
            self.steps.append(rec)
            return out

        engine.step = logged_step
        sched.schedule_prefill = schedule_prefill
        sched.schedule_decode = schedule_decode

    def window(self, t0: float, t1: float) -> tuple[list, dict]:
        """Steps that ran wholly inside [t0, t1), and the counters before
        the first of them (``{"stats", "phases"}``, zero before the
        first step logged)."""
        inside = [s for s in self.steps if s["t0"] >= t0 and s["t1"] <= t1]
        if not inside:
            return [], {}
        i = inside[0]["i"]
        if i > 0:
            prev = self.steps[i - 1]
            return inside, {"stats": prev["stats"], "phases": prev["phases"]}
        first = inside[0]
        return inside, {"stats": {k: 0 for k in first["stats"]},
                        "phases": {k: (0.0, 0.0, 0)
                                   for k in first["phases"]}}


class CompileLog:
    """Host times at which JAX built an executable (a compile or a load
    from the persistent cache) and at which it traced a function anew."""

    def __init__(self):
        import jax
        self.times: list[float] = []
        self.traces: list[float] = []

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.times.append(time.monotonic())
            elif name == "/jax/core/compile/jaxpr_trace_duration":
                self.traces.append(time.monotonic())

        self._listener = on_duration
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listener)

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.times)

    def traced_between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.traces)


class GcLog:
    """Host times of the cyclic garbage collector's passes, each as
    (start, end, generation), whichever thread ran them."""

    def __init__(self):
        self.passes: list[tuple[float, float, int]] = []
        started = {}

        def on_gc(phase, info):
            if phase == "start":
                started["t"] = time.monotonic()
            elif "t" in started:
                self.passes.append((started.pop("t"), time.monotonic(),
                                    info["generation"]))

        self._callback = on_gc
        gc.callbacks.append(on_gc)

    def close(self) -> None:
        gc.callbacks.remove(self._callback)

    def between(self, t0: float, t1: float) -> list:
        return [p for p in self.passes if t0 <= p[0] < t1]


# ----------------------------------------------------------------- window
async def serve(model, w, eng: dict, plan: dict, seed: int, args: list[str],
                trace_dir: str | None, mark, engine_hook=None):
    """Build the stack, set it up, and serve over HTTP while the load
    generator runs.  Returns the load generator's output, whether the
    front end failed, and the step log (which holds the engine)."""
    import jax

    from repro.serving import (AsyncFrontend, HttpServer, Request,
                               ServingEngine)
    engine = ServingEngine(
        model, w, max_batch=eng["max_batch"], page_size=eng["page_size"],
        num_pages=eng["num_pages"], max_seq=eng["max_seq"],
        prefill_budget=eng["prefill_budget"], spec_k=eng["spec_k"],
        kv_codec=eng["kv_codec"])
    fe = AsyncFrontend(engine)
    server = HttpServer(fe, host="127.0.0.1", port=0)
    if plan["shared"]:
        engine.run([(0, Request(rid=(1 << 41) + i, prompt=p,
                                max_new_tokens=1))
                    for i, p in enumerate(plan["shared"])])
    mark("stack built, shared prefixes prefilled")
    n_warm = warm(engine, plan, eng, seed)
    mark(f"warm-up of {n_warm} requests done")
    if engine_hook is not None:
        engine_hook(engine)
    steplog = StepLog(engine)
    await server.start()
    traced = False
    try:
        if trace_dir is not None:
            # Host spans (TraceMe) stay; the Python function tracer, which
            # records every Python call of the server, goes.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced = True
        proc = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(BENCH_DIR, "loadgen.py"),
            "--port", str(server.port), *args,
            stdout=asyncio.subprocess.PIPE, stderr=asyncio.subprocess.PIPE)
        out, err = await proc.communicate()
    finally:
        if traced:
            jax.profiler.stop_trace()
        await server.stop()
        await fe.close(drain=False)
    if proc.returncode != 0:
        raise HarnessError(f"load generator exited {proc.returncode}: "
                           f"{err.decode()[-4000:]}")
    return json.loads(out.decode().strip().splitlines()[-1]), fe.failed, \
        steplog


# ------------------------------------------------------------ correctness
def check(arch, w: dict, m: dict, plan: dict, recs: list, control: bool,
          share_over: float | None = None) -> dict:
    """Every served token of every request, against the reference: the
    gap of its reference logit below the reference's best at its
    position.  Returns the readings - ``tokens_compared``,
    ``logit_gap_max``, ``logit_gap_mean`` and, given ``share_over``,
    ``logit_gap_share``, the share of tokens whose gap exceeds it; the
    control's too, prefixed ``control_``, when asked - and, under
    ``gaps``, the arrays."""
    import reference
    got, ctl = [], []
    for r in recs:
        if not r["tokens"]:
            continue
        req = traffic.request_at(plan, r["stream"], r["idx"])
        g, c = reference.gaps(arch, w, m, traffic.prompt(plan, req),
                              r["tokens"], control)
        got.append(g)
        ctl.append(c)
    got = np.concatenate(got) if got else np.zeros(0)
    out = {"tokens_compared": int(got.size)}
    arrays = {"program": got}
    if control:
        arrays["control"] = np.concatenate(ctl) if ctl else np.zeros(0)
    for name, v in arrays.items():
        pre = "" if name == "program" else "control_"
        out[pre + "logit_gap_max"] = float(v.max()) if v.size else 0.0
        out[pre + "logit_gap_mean"] = float(v.mean()) if v.size else 0.0
        if share_over is not None:
            out[pre + "logit_gap_share"] = \
                float((v > share_over).mean()) if v.size else 0.0
    out["gaps"] = arrays
    return out


# -------------------------------------------------------------------- run
def device_info(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise HarnessError(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise HarnessError(f"the cell needs {chips} chips; JAX found "
                           f"{len(devs)}")
    return devs


def _log_window(log, inside, compiles, gcs, t0, t1) -> None:
    log(f"window: {t1 - t0:.3f} s, {len(inside)} engine steps, "
        f"{compiles.between(t0, t1)} executables built inside it "
        f"(compiles_in_window), {compiles.traced_between(t0, t1)} "
        f"functions traced")
    passes = gcs.between(t0, t1)
    longest = max(passes, key=lambda p: p[1] - p[0], default=None)
    log(f"gc: {len(passes)} collector passes in the window, "
        f"{1e3 * sum(e - s for s, e, _ in passes):.1f} ms in all"
        + ("" if longest is None else
           f", longest {1e3 * (longest[1] - longest[0]):.1f} ms "
           f"(generation {longest[2]})"))
    if inside:
        med = float(np.median([st["t1"] - st["t0"] for st in inside]))
        slow = [st for st in inside if st["t1"] - st["t0"] > 2 * med]
        hit = sum(any(s < st["t1"] and e > st["t0"] for s, e, _ in passes)
                  for st in slow)
        wall = time.time() - time.monotonic()
        log(f"stalls: {len(slow)} steps over twice the median step "
            f"({1e3 * med:.1f} ms), {hit} of them overlapping a collector "
            f"pass" + "".join(
                time.strftime(", %H:%M:%S UTC", time.gmtime(wall + st["t0"]))
                + f" {1e3 * (st['t1'] - st['t0']):.0f} ms" for st in slow))
        busy = sum(st["t1"] - st["t0"] for st in inside)
        slow = sorted(inside, key=lambda st: st["t0"] - st["t1"])[:5]
        log(f"steps: {1e3 * busy / len(inside):.1f} ms on average, "
            f"{1e3 * (inside[-1]['t1'] - inside[0]['t0'] - busy):.0f} ms "
            f"between them; slowest (ms, prefill chunks, decode rows): "
            + ", ".join(f"({1e3 * (st['t1'] - st['t0']):.0f}, "
                        f"{len(st['pre'])}, {len(st['dec'])})"
                        for st in slow))


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, require_tpu: bool = True,
             control: bool = False, engine_hook=None, log=print):
    """One run; returns the result line, the numbers compared with their
    limits, and every correctness reading (the control's too, with
    ``control``).  ``log`` takes the progress lines (standard error in
    the command)."""
    c = spec.cell(spec.load(root), workload, root)
    config, eng, arch = c["config"], c["config"]["engine"], c["arch"]
    m = dict(config["model"])
    devs = device_info(c["workload"]["chips"], require_tpu)
    import weights
    from repro.models.model import build_model
    peak = spec.peak(root, devs[0].device_kind) if require_tpu else \
        {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}
    compiles, gcs = CompileLog(), GcLog()
    mix = traffic.load(c["traffic"])
    plan = traffic.build(mix, seed, m["vocab_size"], eng["max_seq"])

    def mark(what):
        log(f"set-up: {what} at {time.monotonic() - t_start:.1f} s, "
            f"{len(compiles.times)} executables built so far")

    model = build_model(arch.program_config(config))
    w = weights.make(arch, m, model.cfg.padded_vocab, seed, devs[0])
    check_layout(model, w)
    mark("weights made")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        args = ["--traffic", c["traffic"], "--seed", str(seed),
                "--seconds", str(seconds), "--vocab", str(m["vocab_size"]),
                "--max-seq", str(eng["max_seq"])]
        out, fe_failed, steplog = asyncio.run(serve(
            model, w, eng, plan, seed, args, trace_dir, mark, engine_hook))
        t0, t1, recs = out["t0"], out["t1"], out["requests"]
        inside, before = steplog.window(t0, t1)
        run = Run(model=m, arch=arch, peak=peak, setup_s=t0 - t_start,
                  t0=t0, t1=t1, records=recs, steps=inside, before=before)
        if trace:
            files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                              recursive=True)
            if files:
                run.trace = device_trace.reduce(
                    device_trace.read_xplane(files[0]),
                    {s["i"] for s in inside})
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        compiles.close()
        gcs.close()
    _log_window(log, inside, compiles, gcs, t0, t1)

    mem = devs[0].memory_stats() or {}
    attempted, failed = e2e.attempted_failed(recs, t0, t1)
    names = [x["name"] for x in (c["per_layer"] if trace
                                 else c["end_to_end"])]
    units = {x["name"]: x["unit"] for x in c["per_layer"] + c["end_to_end"]}
    metrics = {}
    for name in names:
        v = spec.reader(root, name)(run)
        if v is not None:
            metrics[name] = {"value": v, "unit": units[name]}

    # The program's state goes before the reference runs.
    del steplog
    gc.collect()
    t = time.monotonic()
    limits = config["correct"]
    got = check(arch, w, m, plan, recs, control,
                limits.get("logit_gap_share_over"))
    log(f"reference: {time.monotonic() - t:.1f} s")
    checks = {name: {"value": got[name], "limit": limits[name]}
              for name in ("logit_gap_max", "logit_gap_mean",
                           "logit_gap_share")
              if limits.get(name) is not None}
    checks["tokens_compared"] = {"value": got["tokens_compared"],
                                 "limit": limits["tokens_compared_min"]}
    correct = not fe_failed and all(
        chk["value"] <= chk["limit"] for name, chk in checks.items()
        if name != "tokens_compared") and \
        got["tokens_compared"] >= limits["tokens_compared_min"]
    log("readings: " + json.dumps({k: v for k, v in got.items()
                                   if k != "gaps"}))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": mem.get("peak_bytes_in_use")}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = checks
    return result, checks, got
