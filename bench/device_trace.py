"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.  One reading pass turns the file into plain
tuples; the rest are pure functions of those, which the tests check on a
synthetic trace.

Device time: the events of the TPU planes (``/device:TPU:<n>``) on their
``XLA Ops`` line - one event per operation run, named by its HLO text; a
loop op's event holds the events of its body.  Busy time is the union
of those intervals in the window, averaged over the chips used; a
kernel's time is the sum of the durations of the operations that carry
its name (in the HLO text or in one of its stats).  The breakdown ranks
operations by self time (less their nested operations).

Window: the harness writes one host span ``bench_step`` per engine step
(with the step's number as the stat ``i``), and two inner spans around
the scheduler's planning (``bench_plan_prefill``, ``bench_plan_decode``).
The traced window runs from the start of the first step that lies in
the measured window to the end of the last one, on the trace's own
clock.  Each stretch of an idle gap of the device in it is named after
the innermost harness span that covers it on the host, or
``between_steps``.
"""
from __future__ import annotations

import collections

STEP_SPAN = "bench_step"
HOST_SPANS = ("bench_step", "bench_plan_prefill", "bench_plan_decode")
OPS_LINE = "XLA Ops"


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    return read_profile(ProfileData.from_file(path))


def read_profile(pd) -> dict:
    """{"devices": {plane: [(name, start_ns, end_ns, text)]},
    "host": [(name, start_ns, end_ns, step_i)]} from a
    ``jax.profiler.ProfileData``; ``text`` joins the event's string
    stats, where a custom call's kernel name may sit."""
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    text = " ".join(str(v) for _, v in e.stats
                                    if isinstance(v, str))
                    evs.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, text))
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        i = dict(e.stats).get("i")
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns,
                                     None if i is None else int(i)))
    return {"devices": devices, "host": host}


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of [start, end) intervals clipped to [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle stretches of [lo, hi) that no interval covers."""
    out, t = [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def window_ns(host, steps: set[int]) -> tuple[float, float] | None:
    spans = [(s, e) for n, s, e, i in host if n == STEP_SPAN and i in steps]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(tr: dict, steps: set[int]) -> dict | None:
    """Busy and window seconds, device seconds per operation and the
    breakdown of the traced window spanned by the engine steps
    ``steps``; None when the trace holds none of them or no device op.
    Device seconds are averaged over the chips the trace holds."""
    win = window_ns(tr["host"], steps)
    if win is None or not tr["devices"]:
        return None
    lo, hi = win
    n = len(tr["devices"])
    busy, merged = 0.0, []
    ops: dict[str, list] = {}
    for evs in tr["devices"].values():
        ivs = [(s, e) for _, s, e, _ in evs]
        busy += union_ns(ivs, lo, hi)
        merged += ivs
        inside = [ev for ev in evs if ev[1] >= lo and ev[2] <= hi]
        for (name, s, e, text), own in zip(inside, self_ns(inside)):
            row = ops.setdefault(op_name(name), [f"{name} {text}", 0.0, 0.0])
            row[1] += (e - s) / n / 1e9
            row[2] += own / n / 1e9
    if busy <= 0:
        return None
    idle = collections.Counter()
    for s, e in gaps_ns(merged, lo, hi):
        cuts = sorted({s, e} | {t for _, a, b, _ in tr["host"]
                                for t in (a, b) if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            idle[host_activity(tr["host"], (a + b) / 2)] += (b - a) / 1e9
    top = sorted(ops.items(), key=lambda kv: -kv[1][2])[:10]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / n / 1e9,
            "ops": [[k, t, v] for k, (t, v, _) in ops.items()],
            "device_ops": [[k, own] for k, (_, _, own) in top],
            "idle_gaps": [[k, v] for k, v in idle.most_common(10)]}


def op_name(name: str) -> str:
    """An XLA op event's instruction name (its HLO text up to ' = ')."""
    return name.split(" = ", 1)[0]


def self_ns(events) -> list[float]:
    """Each event's duration less that of the events nested directly in
    it on the same line (a loop op holds its body's ops)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] - e[1] for e in events]
    stack: list[int] = []
    for i in order:
        s, e = events[i][1], events[i][2]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def kernel_seconds(summary: dict, kernel: str) -> float:
    """Device seconds of the operations that carry ``kernel``'s name."""
    return sum(v for name, text, v in summary["ops"]
               if kernel in name or kernel in text)


def host_activity(host, t: float) -> str:
    """The innermost harness span covering time t, else between_steps."""
    best = None
    for name, s, e, _ in host:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "between_steps"
