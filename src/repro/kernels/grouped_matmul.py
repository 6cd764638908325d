"""Grouped matmul over the experts a layer holds: the dropless expert
layer's three SwiGLU matrices, each one Pallas call named ``moe_gmm``.

Rows arrive sorted by expert, each expert's rows padded to whole tiles
of ``tm`` rows, so no tile spans two experts: ``tile_expert[t]`` names
tile t's expert, and the first ``n_live`` tiles hold rows.  A grid step
is one (column block, tile): the tile's (tm, K) rows times its expert's
(K, tn) block.  K is never split, so consecutive tiles of one expert
keep their weight block and it is fetched once per column block; tiles
past ``n_live`` keep the last live tile's blocks (nothing is fetched)
and write zeros.

On the TPU :func:`gmm` runs the kernel; elsewhere a jnp path does the
same tiles' work (``force_pallas`` pins the kernel in interpret mode,
for the parity tests).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Most bytes of one expert's (K, tn) weight block in VMEM (twice that
# double-buffered).
W_BLOCK_BYTES = 2 * 1024 * 1024


def tile_rows(rows: int, n_router: int) -> int:
    """Tile height for ``rows`` routed rows over ``n_router`` experts:
    the power of two at or above the mean rows an expert gets, 16 to
    128 (16 is a bf16 sublane tile)."""
    tm = 16
    while tm < 128 and tm * n_router < rows:
        tm *= 2
    return tm


def num_tiles(rows: int, n_held: int, tm: int) -> int:
    """Tiles that hold any routing of ``rows`` rows to ``n_held``
    experts: each expert's rows round up to a whole tile."""
    return -(-rows // tm) + n_held


def _col_block(k: int, n: int, itemsize: int) -> int:
    """Output columns per grid step: the widest multiple of 128 that
    divides ``n`` with a (k, tn) block within ``W_BLOCK_BYTES``; all of
    ``n`` where it is no multiple of 128."""
    if n % 128:
        return n
    best = 128
    for tn in range(128, n + 1, 128):
        if n % tn == 0 and k * tn * itemsize <= W_BLOCK_BYTES:
            best = tn
    return best


def _gmm_kernel(te_ref, nl_ref, g_ref, x_ref, w_ref, o_ref):
    t = pl.program_id(1)

    @pl.when(t < nl_ref[0])
    def _live():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(t >= nl_ref[0])
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)


def _stacked(w, group):
    """(layers, E, K, N) weights and the layer index: a lone layer's
    (E, K, N) is a stack of one."""
    if w.ndim == 3:
        return w[None], jnp.zeros((), jnp.int32)
    return w, jnp.asarray(group, jnp.int32)


def gmm_pallas(x, w, tile_expert, n_live, *, tm: int, out_dtype,
               interpret: bool, group=None):
    """x (M, K) rows in tiles of ``tm``; w (E, K, N), or a stack of
    layers (G, E, K, N) read in place at layer ``group``; tile_expert
    (M / tm,) int32; n_live () int32.  Returns (M, N) in ``out_dtype``,
    accumulated in f32."""
    m, k = x.shape
    w, group = _stacked(w, group)
    n = w.shape[-1]
    tn = _col_block(k, n, w.dtype.itemsize)

    def live(t, nl):
        return jnp.minimum(t, jnp.maximum(nl[0] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, m // tm),
        in_specs=[
            pl.BlockSpec((tm, k),
                         lambda c, t, te, nl, g: (live(t, nl), 0)),
            pl.BlockSpec((1, 1, k, tn),
                         lambda c, t, te, nl, g: (g[0], te[live(t, nl)], 0,
                                                  c)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda c, t, te, nl, g: (t, c)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="moe_gmm",
    )(tile_expert.astype(jnp.int32),
      jnp.reshape(n_live, (1,)).astype(jnp.int32), jnp.reshape(group, (1,)),
      x, w)


def gmm_ref(x, w, tile_expert, n_live, *, tm: int, out_dtype, group=None):
    """The same tiles' work in jnp: each tile times its expert's whole
    (K, N) matrix, dead tiles zero."""
    m, k = x.shape
    tiles = m // tm
    w, group = _stacked(w, group)
    y = jnp.einsum("tmk,tkn->tmn", x.reshape(tiles, tm, k),
                   jnp.take(w[group], tile_expert, axis=0).astype(x.dtype),
                   preferred_element_type=jnp.float32)
    y = jnp.where((jnp.arange(tiles) < n_live)[:, None, None], y, 0.0)
    return y.reshape(m, -1).astype(out_dtype)


def gmm(x, w, tile_expert, n_live, *, tm: int, out_dtype, group=None,
        force_pallas: bool = False):
    """Grouped matmul of expert-sorted, tile-padded rows: the Pallas
    kernel on the TPU (or pinned), the jnp path elsewhere."""
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or force_pallas:
        return gmm_pallas(x, w, tile_expert, n_live, tm=tm,
                          out_dtype=out_dtype, interpret=not on_tpu,
                          group=group)
    return gmm_ref(x, w, tile_expert, n_live, tm=tm, out_dtype=out_dtype,
                   group=group)
