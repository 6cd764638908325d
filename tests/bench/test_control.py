"""The comparison that decides ``correct`` must pass sound runs and fail
the control.  The test drives a whole run of the tiny cell on the CPU
(``conftest.py``) - set-up, the HTTP server, the load generator's child
process, the window and the reference - skipping only the harness's look
for a chip: a sound run is correct, its result line has the keys and
types that ``bench/run.py`` documents, and the control - the reference
computed in float8 in place of the program - reads above every limit
that the sound run stays under.

  JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_control.py
"""
import json

from conftest import TINY_MAX, TINY_MEAN, TINY_SHARE, run_tiny


def test_sound_run_is_correct_and_well_formed(tiny_root):
    result, checks, got = run_tiny(tiny_root, "tiny.both", 2 ** 31 + 9,
                                   control=True)
    assert result["correct"] is True
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0
    # The end-to-end metrics without a workloads list: every cell's.
    assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(checks) == {"logit_gap_max", "logit_gap_mean",
                           "logit_gap_share", "tokens_compared"}
    assert checks["logit_gap_max"]["value"] <= TINY_MAX
    assert checks["logit_gap_mean"]["value"] <= TINY_MEAN
    assert checks["logit_gap_share"]["value"] <= TINY_SHARE
    assert checks["tokens_compared"]["value"] >= 20
    # The control, in the program's place, fails every limit.
    assert got["control_logit_gap_max"] > TINY_MAX
    assert got["control_logit_gap_mean"] > TINY_MEAN
    assert got["control_logit_gap_share"] > TINY_SHARE
    json.dumps(result)
