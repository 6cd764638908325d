"""Per-phase host readings of a run, from the program's phase spans and
counters (``repro.serving.tracing``: ``engine.phases`` holds, per phase,
the wall seconds, the thread's CPU seconds and the passes so far).

  python3 bench/phases.py --workload <name> --seed <n> --seconds <s> \\
      --trace <0|1>

runs one cell exactly as ``bench/run.py`` does (its result line is the
second-to-last line of standard output) and reads the copy of
``engine.phases`` that the harness's step log keeps after each step:
the window's readings are deltas of those totals over its steps, as
``decode_batch_mean`` reads its counters.  With one addition the
benchmark's own reduction does not make yet:

  * with ``--trace 1`` the trace reduction also keeps the host spans
    whose names start with ``engine.`` or ``frontend.``, so an idle gap
    of the device inside a step is filed under the phase that held the
    host (the reduction picks the innermost span) instead of
    ``bench_step``.  Busy and window seconds, the operations and the
    idle total read what they read without them.

The last line is {"phases": readings} (see ``readings``).  The program
of the parent commit has no phase counters: there the readings are
empty and nothing raises.
"""
from __future__ import annotations

import json
import os
import statistics
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
import device_trace  # noqa: E402
import harness  # noqa: E402

PREFIXES = ("engine.", "frontend.")
_read_profile = device_trace.read_profile


# ------------------------------------------------------------------ trace
def phase_events(pd) -> list:
    """The program's phase spans in the host planes of a
    ``jax.profiler.ProfileData``, as ``device_trace`` host tuples."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, None))
    return out


def read_profile(pd) -> dict:
    """``device_trace.read_profile``, plus the program's phase spans."""
    tr = _read_profile(pd)
    tr["host"] = tr["host"] + phase_events(pd)
    return tr


# --------------------------------------------------------------- counters
def deltas(steps: list, before: dict) -> list[dict]:
    """Per step, each phase's (wall_s, cpu_s, count) spent in it: the
    change of the snapshot ``step["phases"]`` from the one before."""
    out, prev = [], before
    for st in steps:
        now = st["phases"]
        out.append({k: tuple(b - a for a, b in zip(prev[k], v))
                    for k, v in now.items()})
        prev = now
    return out


def _change(steps: list, before: dict, keep) -> tuple[float, float]:
    """Wall and CPU seconds of the phases ``keep`` selects, over the
    window."""
    last = steps[-1]["phases"]
    wall = sum(last[k][0] - before[k][0] for k in last if keep(k))
    cpu = sum(last[k][1] - before[k][1] for k in last if keep(k))
    return wall, cpu


def _host_work(name: str) -> bool:
    return name.startswith("engine.") and not name.endswith(".sync")


def step_host_ms(steps: list, before: dict) -> float | None:
    """Wall ms per step in the engine phases other than ``*.sync``: the
    host work the device waits for."""
    if not steps or not before:
        return None
    return 1e3 * _change(steps, before, _host_work)[0] / len(steps)


def step_starved_ms(steps: list, before: dict) -> float | None:
    """Over the same phases, wall minus thread CPU ms per step: time the
    engine thread was ready to run but did not (interpreter lock or a
    busy CPU)."""
    if not steps or not before:
        return None
    wall, cpu = _change(steps, before, _host_work)
    return 1e3 * (wall - cpu) / len(steps)


def frontend_turn_ms(steps: list, before: dict) -> float | None:
    """Wall ms per step of ``frontend.intake``, ``.publish`` and
    ``.handoff``."""
    if not steps or not before:
        return None
    wall = _change(steps, before, lambda k: k.startswith("frontend."))[0]
    return 1e3 * wall / len(steps)


def step_cover(steps: list, before: dict) -> float | None:
    """Median over the steps of the engine phases' summed wall time over
    the step's span on the harness's clock."""
    if not steps or not before:
        return None
    return statistics.median(
        sum(v[0] for k, v in d.items() if k.startswith("engine."))
        / (st["t1"] - st["t0"])
        for st, d in zip(steps, deltas(steps, before)))


def stalls(steps: list, before: dict) -> list[dict]:
    """Each step over twice the median step: its ms, and the engine
    phase whose wall time exceeded that phase's median most, with the
    phase's wall and CPU ms in the step."""
    if not steps or not before:
        return []
    per = deltas(steps, before)
    med = statistics.median(st["t1"] - st["t0"] for st in steps)
    names = [k for k in per[0] if k.startswith("engine.")]
    typical = {k: statistics.median(d[k][0] for d in per) for k in names}
    out = []
    for st, d in zip(steps, per):
        if st["t1"] - st["t0"] <= 2 * med:
            continue
        k = max(names, key=lambda n: d[n][0] - typical[n])
        out.append({"step_ms": 1e3 * (st["t1"] - st["t0"]), "phase": k,
                    "wall_ms": 1e3 * d[k][0], "cpu_ms": 1e3 * d[k][1]})
    return out


def readings(steps: list, before: dict) -> dict:
    """Everything this module reads of a window: the three per-step
    numbers, the engine phases' cover of the step, the stalls, and each
    phase's wall and CPU ms per step."""
    if not steps or not before:
        return {}
    n = len(steps)
    last = steps[-1]["phases"]
    return {"steps": n,
            "step_host_ms": step_host_ms(steps, before),
            "step_starved_ms": step_starved_ms(steps, before),
            "frontend_turn_ms": frontend_turn_ms(steps, before),
            "step_cover": step_cover(steps, before),
            "stalls": stalls(steps, before),
            "per_step_ms": {k: [1e3 * (v[0] - before[k][0]) / n,
                                1e3 * (v[1] - before[k][1]) / n]
                            for k, v in last.items()}}


# -------------------------------------------------------------------- run
class PhaseLog(harness.StepLog):
    """The harness's step log, keeping the window it was asked for: its
    steps and the phase totals before the first of them."""

    window_steps: tuple[list, dict] = ([], {})

    def window(self, t0: float, t1: float) -> tuple[list, dict]:
        inside, before = super().window(t0, t1)
        self.window_steps = (inside, before.get("phases", {}))
        return inside, before


def main(argv=None) -> int:
    import run
    logs: list[PhaseLog] = []

    def step_log(engine):
        logs.append(PhaseLog(engine))
        return logs[-1]

    harness.StepLog = step_log
    device_trace.read_profile = read_profile
    rc = run.main(argv)
    if rc == 0 and logs:
        print(json.dumps({"phases": readings(*logs[-1].window_steps)}),
              flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
