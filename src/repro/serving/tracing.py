"""Phase spans and counters of the serving loop.

Every phase of :meth:`ServingEngine.step` and of the async frontend's
drive loop runs inside one :class:`Phase`, which does two things:

  * it opens a ``jax.profiler.TraceAnnotation`` of the phase's name, so
    under a profiler the program's spans land in the host plane on the
    device trace's clock (engine phases carry the engine's step number
    as the stat ``step``); with no profiler running the span is only
    its inactive check;
  * it adds the phase's wall seconds (``time.perf_counter``), the
    calling thread's CPU seconds (``time.thread_time``) and one to the
    count of its :class:`PhaseTotal` in a mapping of phase name to
    totals - ``ServingEngine.phases``, served by ``GET /stats`` under
    ``"phases"``.

Wall minus CPU is time the thread was ready to run but did not: it
waited for the interpreter lock or for a CPU core.  The totals are
immutable tuples and every name is in the mapping from the start, so a
shallow copy of the mapping is a consistent snapshot by value.

The engine phases tile the step - the step's work lies in exactly one
of them, in order:

  ``engine.capacity``   both capacity passes (append pages, preemption)
  ``engine.plan``       prefill budget, ``schedule_prefill`` with the
                        gridlock victim, draft count, ``schedule_decode``
                        and the decode sizing loop
  ``engine.cow``        both copy-on-write flushes
  ``engine.prefill.inputs`` / ``.dispatch`` / ``.sync`` / ``.commit``
  ``engine.decode.inputs``  / ``.dispatch`` / ``.sync`` / ``.commit``

``inputs`` builds the host arrays and moves them to the device,
``dispatch`` enqueues the jitted call, ``sync`` waits for its results
on the host (the device work shows here), and ``commit`` applies them to
the scheduler and the page cache.  A step enters a prefill or decode
phase once per jitted call it makes, so counts per step vary.

The frontend's phases: ``frontend.intake`` (cancels and submissions),
``frontend.publish`` (tokens to the streams) and ``frontend.handoff``,
the two hops between the event loop and the executor thread that runs
the step.  The handoff crosses threads, so it is a counter with no span
and its CPU seconds stay 0.
"""
from __future__ import annotations

import time
from typing import NamedTuple

import jax

ENGINE_PHASES = ("engine.capacity", "engine.plan", "engine.cow",
                 "engine.prefill.inputs", "engine.prefill.dispatch",
                 "engine.prefill.sync", "engine.prefill.commit",
                 "engine.decode.inputs", "engine.decode.dispatch",
                 "engine.decode.sync", "engine.decode.commit")
PREFILL_PHASES = tuple(p for p in ENGINE_PHASES
                       if p.startswith("engine.prefill."))
FRONTEND_PHASES = ("frontend.intake", "frontend.publish",
                   "frontend.handoff")


class PhaseTotal(NamedTuple):
    wall_s: float = 0.0
    cpu_s: float = 0.0
    count: int = 0


def new_totals() -> dict[str, PhaseTotal]:
    """A mapping holding every phase of the engine and the frontend at
    zero."""
    return {name: PhaseTotal() for name in ENGINE_PHASES + FRONTEND_PHASES}


def add(totals: dict, name: str, wall_s: float, cpu_s: float = 0.0) -> None:
    """Count one pass of phase ``name``."""
    t = totals[name]
    totals[name] = PhaseTotal(t.wall_s + wall_s, t.cpu_s + cpu_s,
                              t.count + 1)


def total_wall_s(totals: dict, names) -> float:
    """Summed wall seconds of the phases ``names`` so far."""
    return sum(totals[n].wall_s for n in names)


class Phase:
    """``with Phase(totals, name, **stats):`` - a profiler span of
    ``name`` (with ``stats``) around the block, and the block's wall and
    thread CPU seconds added to ``totals[name]``."""

    __slots__ = ("_totals", "_name", "_span", "_wall", "_cpu")

    def __init__(self, totals: dict, name: str, **stats):
        self._totals = totals
        self._name = name
        self._span = jax.profiler.TraceAnnotation(name, **stats)

    def __enter__(self):
        self._span.__enter__()
        self._wall = time.perf_counter()
        self._cpu = time.thread_time()
        return self

    def __exit__(self, *exc):
        cpu = time.thread_time() - self._cpu
        add(self._totals, self._name, time.perf_counter() - self._wall, cpu)
        return self._span.__exit__(*exc)
