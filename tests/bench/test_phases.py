"""The per-phase readings of ``bench/phases.py``: idle gaps filed under
the program's phase spans on a synthetic trace, with every other number
of the reduction unchanged; the three per-step readings, the step cover
and the stalls against hand-made step snapshots; and a whole run of the
tiny cell (``conftest.py``) with the phase log in the harness.

  JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_phases.py
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench"))

import device_trace  # noqa: E402
import harness  # noqa: E402
import phases  # noqa: E402
from conftest import run_tiny  # noqa: E402

# Two engine steps (4: [0.5, 7) us, 5: [7, 10) us) over device ops busy
# in [1, 5) and [8, 9) us; the harness planned a decode in [5.5, 6.5).
# The program's spans: engine.plan [5.2, 6.8) around that planning,
# engine.decode.commit [9, 9.8), and frontend.publish [10.5, 11.5)
# after the window.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000
             stats { metadata_id: 1 str_value: "paged_flash_decode_partial" } }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 1000000
             stats { metadata_id: 1 str_value: "paged_flash_decode_partial" } }
    events { metadata_id: 3 offset_ps: 13000000 duration_ps: 1000000 } }
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
  lines { id: 2 name: "executor" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 5200000 duration_ps: 1600000
             stats { metadata_id: 2 int64_value: 17 } }
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 800000
             stats { metadata_id: 2 int64_value: 18 } }
    events { metadata_id: 5 offset_ps: 10500000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench_step" } }
  event_metadata { key: 2 value { id: 2 name: "bench_plan_decode" } }
  event_metadata { key: 3 value { id: 3 name: "engine.plan" } }
  event_metadata { key: 4 value { id: 4 name: "engine.decode.commit" } }
  event_metadata { key: 5 value { id: 5 name: "frontend.publish" } }
  stat_metadata { key: 1 value { id: 1 name: "i" } }
  stat_metadata { key: 2 value { id: 2 name: "step" } }
}
"""


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(TRACE)


def test_idle_inside_a_step_is_filed_under_its_phase(profile):
    plain = device_trace.reduce(device_trace.read_profile(profile), {4, 5})
    named = device_trace.reduce(phases.read_profile(profile), {4, 5})
    gaps = dict(named["idle_gaps"])
    assert gaps["engine.decode.commit"] == pytest.approx(0.8e-6)
    assert gaps["engine.plan"] == pytest.approx(0.6e-6)
    # The harness's planning span is the innermost over its stretch.
    assert gaps["bench_plan_decode"] == pytest.approx(1e-6)
    assert gaps["bench_step"] == pytest.approx(2.1e-6)
    assert dict(plain["idle_gaps"])["bench_step"] == pytest.approx(3.5e-6)
    assert "frontend.publish" not in gaps
    for key in ("window_s", "busy_s", "ops", "device_ops"):
        assert named[key] == plain[key], key
    assert sum(gaps.values()) == pytest.approx(
        sum(v for _, v in plain["idle_gaps"]))


def test_the_plain_reduction_ignores_the_phase_spans(profile):
    names = {h[0] for h in device_trace.read_profile(profile)["host"]}
    assert names == {"bench_step", "bench_plan_decode"}
    assert sorted(h[0] for h in phases.phase_events(profile)) == \
        ["engine.decode.commit", "engine.plan", "frontend.publish"]


def _snap(**phases_):
    """A phases snapshot: every phase at zero unless given."""
    names = ("engine.plan", "engine.decode.dispatch", "engine.decode.sync",
             "engine.decode.commit", "frontend.intake", "frontend.handoff")
    return {n: phases_.get(n.replace(".", "_"), (0.0, 0.0, 0))
            for n in names}


def _steps():
    """Three 100-ms steps and one of 400 ms whose commit phase held the
    host 300 ms while its thread ran 10 ms of it; sync is device work."""
    before = _snap(engine_plan=(1.0, 1.0, 10), frontend_intake=(0.5, 0.5, 9))
    walls = [(0.004, 0.080, 0.010), (0.004, 0.080, 0.010),
             (0.004, 0.080, 0.310), (0.004, 0.080, 0.010)]
    steps, snap, t = [], before, 100.0
    for i, (plan, sync, commit) in enumerate(walls):
        cpu_commit = 0.010 if commit > 0.1 else commit
        snap = dict(snap)
        add = {"engine.plan": (plan, plan, 1),
               "engine.decode.dispatch": (0.002, 0.002, 1),
               "engine.decode.sync": (sync, 0.001, 1),
               "engine.decode.commit": (commit, cpu_commit, 1),
               "frontend.intake": (0.0005, 0.0005, 1),
               "frontend.handoff": (0.0003, 0.0, 1)}
        snap = {k: tuple(a + b for a, b in zip(snap[k], add[k]))
                for k in snap}
        span = plan + 0.002 + sync + commit
        steps.append({"i": i, "t0": t, "t1": t + span, "phases": snap})
        t += span + 0.001
    return steps, before


def test_per_step_readings_from_step_snapshots():
    steps, before = _steps()
    # Host work: plan 4 + dispatch 2 + commit (10, 10, 310, 10)/4 ms.
    assert phases.step_host_ms(steps, before) == pytest.approx(
        4 + 2 + 85)
    assert phases.step_starved_ms(steps, before) == pytest.approx(75)
    assert phases.frontend_turn_ms(steps, before) == pytest.approx(0.8)
    assert phases.step_cover(steps, before) == pytest.approx(1.0)
    stall, = phases.stalls(steps, before)
    assert stall["phase"] == "engine.decode.commit"
    assert stall["step_ms"] == pytest.approx(396)
    assert stall["wall_ms"] == pytest.approx(310)
    assert stall["cpu_ms"] == pytest.approx(10)
    got = phases.readings(steps, before)
    assert got["steps"] == 4
    assert got["per_step_ms"]["engine.decode.sync"] == \
        pytest.approx([80, 1])


def test_readings_of_a_program_without_phases_are_empty():
    steps = [{"i": 0, "t0": 0.0, "t1": 1.0, "phases": {}}]
    assert phases.readings(steps, {}) == {}
    assert phases.step_host_ms(steps, {}) is None
    assert phases.stalls([], {}) == []


def test_tiny_run_with_the_phase_log(tiny_root, monkeypatch):
    logs = []

    def step_log(engine):
        logs.append(phases.PhaseLog(engine))
        return logs[-1]

    monkeypatch.setattr(harness, "StepLog", step_log)
    result, _, _ = run_tiny(tiny_root, "tiny.both", 11)
    assert result["correct"] is True
    got = phases.readings(*logs[0].window_steps)
    assert got["steps"] > 2
    assert got["step_host_ms"] > 0 and got["frontend_turn_ms"] > 0
    assert 0.9 < got["step_cover"] <= 1.0
    assert set(got["per_step_ms"]) >= {"engine.plan", "engine.decode.sync",
                                       "frontend.handoff"}
