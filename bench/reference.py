"""The plain reference: the forward pass written from the published
architecture in straightforward ``jax.numpy``, in float32 at the highest
matmul precision, with exact softmax attention.  It imports nothing of
the program and reads only the benchmark's own weight tree
(``bench/weights.py``).

The configuration's architecture module (``bench/archs/<name>.py``)
gives the final-norm hidden states of a token row (``hidden``); this
module pads the row, applies the LM head and reads the gaps.

``control=True`` computes the same in float8 (e4m3), as
``bench/archs/common.py`` rounds every matmul operand: the step below
the bfloat16 the configurations state, the one a later change could be
tempted to take; the comparison that decides ``correct`` must fail it.

The sequence is padded to a multiple of ``PAD`` (causal attention
leaves the real positions untouched) and the logit rows to a multiple of
``ROWS``, so a few compiled programs cover every request.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from archs.common import _mm

PAD = 1024      # sequence rows padded to a multiple of this
ROWS = 256      # logit rows computed, a multiple of this


@functools.partial(jax.jit, static_argnames=("rows", "vocab", "control"))
def _head(w, hid, first, rows, vocab, control):
    """Logits of hidden rows first .. first + rows - 1, real vocab only."""
    h = jax.lax.dynamic_slice_in_dim(hid, first, rows)
    head = w["lm_head"] if "lm_head" in w else w["embed"]["emb"].T
    return _mm("td,dv->tv", h, head[:, :vocab].astype(jnp.float32), control)


@jax.jit
def _served_rows(ref, ids):
    got = jnp.take_along_axis(ref, ids[:, None], axis=1)[:, 0]
    return jnp.max(ref, axis=1) - got


@jax.jit
def _top_rows(ref, ctl):
    top = jnp.argmax(ctl, axis=1)
    got = jnp.take_along_axis(ref, top[:, None], axis=1)[:, 0]
    return jnp.max(ref, axis=1) - got


def gaps(arch, w: dict, m: dict, prompt: list[int], served: list[int],
         control: bool = False) -> tuple[np.ndarray, np.ndarray | None]:
    """Per served token, the gap by which its reference logit lies below
    the reference's best at its position (0 where it is the reference's
    argmax, inf where it is no token of the vocabulary); with
    ``control``, the same gaps for the tokens the control puts first at
    those positions.  The reference - architecture module ``arch`` -
    reads ``prompt`` and the served tokens, teacher-forced."""
    toks = prompt + served[:-1]
    first, n, vocab = len(prompt) - 1, len(served), m["vocab_size"]
    rows = -(-n // ROWS) * ROWS
    row = np.zeros(-(-(first + rows) // PAD) * PAD, np.int32)
    row[:len(toks)] = toks
    at = jnp.int32(first)
    ref = _head(w, arch.hidden(w, row, m, False), at, rows, vocab, False)
    ids = np.zeros(rows, np.int32)
    ids[:n] = np.minimum(served, vocab - 1)
    gap = np.asarray(_served_rows(ref, ids))[:n].astype(np.float64)
    gap[np.asarray(served) >= vocab] = np.inf
    if not control:
        return gap, None
    ctl = _head(w, arch.hidden(w, row, m, True), at, rows, vocab, True)
    return gap, np.asarray(_top_rows(ref, ctl))[:n].astype(np.float64)
