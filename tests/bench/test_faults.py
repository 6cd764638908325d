"""A fault planted in the timed path must make a run not correct, under
each number a cell's ``correct`` may compare on its own: a token altered
where it is produced (the jitted decode step's sampled tokens) fails the
widest gap alone ("tiny.max"), the mean gap alone ("tiny.mean") and the
share of gaps over a threshold alone ("tiny.share"), the only number
the hfa cell compares.  Whole runs of the tiny cell on the
CPU (``conftest.py``).

  JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_faults.py
"""
import numpy as np
import pytest

from conftest import TINY_MAX, TINY_MEAN, TINY_SHARE, run_tiny


def _alter_every(n):
    """An engine hook: every n-th decode step's sampled tokens are moved
    to the next id of the vocabulary."""
    def hook(engine):
        decode = engine._decode
        calls = [0]

        def broken(*args):
            toks, *rest = decode(*args)
            calls[0] += 1
            if calls[0] % n == 0:
                toks = (np.asarray(toks) + 1) % engine.model.cfg.vocab_size
            return toks, *rest
        engine._decode = broken
    return hook


@pytest.mark.parametrize("cell,number,limit", [
    ("tiny.max", "logit_gap_max", TINY_MAX),
    ("tiny.mean", "logit_gap_mean", TINY_MEAN),
    ("tiny.share", "logit_gap_share", TINY_SHARE)])
def test_altered_token_is_not_correct(tiny_root, cell, number, limit):
    result, checks, _ = run_tiny(tiny_root, cell, 5,
                                 engine_hook=_alter_every(8))
    assert set(checks) == {number, "tokens_compared"}
    assert result["correct"] is False
    assert checks[number]["value"] > limit
