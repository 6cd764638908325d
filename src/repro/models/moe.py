"""Mixture-of-Experts FFN: top-k router + capacity dispatch/combine.

GShard/Switch-style formulation generalized to top-k: tokens are routed to
their top-k experts, each expert processes at most C = ceil(T/E * cf * k)
tokens (overflow dropped, standard at scale), and outputs are combined with
the router weights.  The dispatch/combine einsums lower to all-to-all
resharding when experts are sharded over the "model" mesh axis (EP).

Aux losses: load-balancing (Switch) + router z-loss, returned for the
trainer to add.

Serving takes :func:`moe_serve` instead: dropless, so a token's output
never depends on the rest of the batch, and computed only by the experts
the layer holds (``cfg.n_experts`` of the router's
``cfg.router_experts``, from ``cfg.expert_offset``: one chip's share
under expert parallelism).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import grouped_matmul as gm
from repro.models.layers import _init_dense


def moe_init(key, cfg, dtype=jnp.float32):
    e, d, ff = cfg.n_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    p = {
        "router": _init_dense(ks[0], (d, cfg.router_experts),
                              dtype=jnp.float32),
        "wg": _init_dense(ks[1], (e, d, ff), 1.0 / math.sqrt(d), dtype),
        "wu": _init_dense(ks[2], (e, d, ff), 1.0 / math.sqrt(d), dtype),
        "wd": _init_dense(ks[3], (e, ff, d), 1.0 / math.sqrt(ff), dtype),
    }
    l = {
        "router": ("fsdp", None),
        "wg": ("experts", "fsdp", "expert_mlp"),
        "wu": ("experts", "fsdp", "expert_mlp"),
        "wd": ("experts", "expert_mlp", "fsdp"),
    }
    return p, l


GROUP_SIZE = 4096  # tokens per dispatch group (GShard 'group' dimension)


def moe_apply(p, x: jax.Array, cfg) -> tuple[jax.Array, dict[str, jax.Array]]:
    """x: (B, S, d) -> (out, aux) with aux = {load_balance, router_z}.

    Tokens are split into groups of <= GROUP_SIZE with *per-group* capacity
    (GShard semantics): dispatch memory is O(G * g * E * C_g) with
    C_g = g/E * cf * k, instead of the quadratic-in-T naive form.  Groups
    map onto the data-parallel token sharding, experts onto "model" (EP);
    the dispatch/combine einsums then lower to all-to-alls.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    t = b * s
    # Bound the dense dispatch-einsum cost relative to the expert FFN cost:
    # dispatch ~ cf*g*d flops/token vs FFN ~ 6*k*d*ff, so keep g <~ 4*ff.
    auto = cfg.moe_group or min(GROUP_SIZE, 4 * max(cfg.d_ff, 128))
    g = min(auto, t)
    while t % g:
        g //= 2
    ng = t // g
    cap = max(int(math.ceil(g / e * cfg.capacity_factor * k)), k)

    xt = x.reshape(ng, g, d)
    gate_vals, gate_idx, logits = route(p, xt, k)            # (N, g, k)
    probs = jax.nn.softmax(logits, axis=-1)

    # Slot of each (token, choice) inside its expert's capacity buffer.
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)    # (N, g, k, e)
    flatoh = onehot.reshape(ng, g * k, e)
    pos = jnp.cumsum(flatoh, axis=1) * flatoh - 1
    pos = jnp.max(pos, axis=-1).reshape(ng, g, k)
    keep = pos < cap

    slot_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                             dtype=xt.dtype)[..., :cap]       # (N, g, k, C)
    disp = jnp.einsum("Ntke,Ntkc->Ntec", onehot.astype(xt.dtype), slot_oh)
    comb = jnp.einsum("Ntk,Ntke,Ntkc->Ntec",
                      gate_vals.astype(xt.dtype) * keep.astype(xt.dtype),
                      onehot.astype(xt.dtype), slot_oh)

    expert_in = jnp.einsum("Ntec,Ntd->Necd", disp, xt)        # a2a under EP
    gact = jnp.einsum("Necd,edf->Necf", expert_in, p["wg"].astype(xt.dtype))
    uact = jnp.einsum("Necd,edf->Necf", expert_in, p["wu"].astype(xt.dtype))
    act = jax.nn.silu(gact.astype(jnp.float32)).astype(xt.dtype) * uact
    expert_out = jnp.einsum("Necf,efd->Necd", act, p["wd"].astype(xt.dtype))
    out = jnp.einsum("Ntec,Necd->Ntd", comb, expert_out)      # a2a back

    # Switch load-balance loss + router z-loss (per group, averaged).
    me = jnp.mean(probs, axis=1)                              # (N, e)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[..., 0], e, dtype=jnp.float32),
                  axis=1)
    aux = {
        "load_balance": e * jnp.mean(jnp.sum(me * ce, axis=-1)),
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    return out.reshape(b, s, d), aux


def route(p, x, k: int):
    """The router, for training and serving alike: x (..., d) ->
    (gates (..., k) f32, expert indices (..., k), logits (..., E) f32),
    the float32 softmax over every router output, top-k, renormalised
    over the k."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return gates / jnp.sum(gates, axis=-1, keepdims=True), idx, logits


def moe_serve(p, x: jax.Array, cfg, valid: jax.Array | None = None,
              force_pallas: bool = False, group=None):
    """Dropless expert layer over the experts this layer holds.

    x: (B, S, d); valid: optional (B, S) bool, False for padding and free
    slots (routed nowhere, not counted).  Every (token, choice) row that
    falls on a held expert is computed - no capacity, so a token's
    output is the same whatever the batch - as grouped matmuls over the
    held experts (:mod:`repro.kernels.grouped_matmul`), and each token
    gets the gated sum of its held experts' outputs, in choice order.
    The absent experts' part is left out.

    With ``group`` the expert weights are the whole stack of layer
    groups, (G, E, d, ff) / (G, E, ff, d), read in place at that index.

    Returns (out (B, S, d), counts) with counts
    {"moe_rows_local": rows computed, "moe_experts_active": held experts
    with a row} as int32 scalars.
    """
    b, s, d = x.shape
    t, k, n = b * s, cfg.moe_top_k, cfg.n_experts
    xt = x.reshape(t, d)
    gates, idx, _ = route(p, xt, k)
    local = idx - cfg.expert_offset
    held = (local >= 0) & (local < n)
    if valid is not None:
        held = held & valid.reshape(t, 1)
    eid = jnp.where(held, local, n).reshape(-1)              # (T k,)
    rows = t * k
    tm = gm.tile_rows(rows, cfg.router_experts)
    tiles = gm.num_tiles(rows, n, tm)
    # Each held expert's rows, in (token, choice) order, from the start
    # of its first tile.
    onehot = (eid[:, None] == jnp.arange(n)[None]).astype(jnp.int32)
    counts = jnp.sum(onehot, axis=0)                          # (n,)
    tile_end = jnp.cumsum((counts + tm - 1) // tm)
    tile_start = tile_end - (counts + tm - 1) // tm
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    dest = jnp.where(eid < n,
                     tile_start[jnp.minimum(eid, n - 1)] * tm + rank,
                     tiles * tm)                              # OOB: none
    xs = jnp.zeros((tiles * tm, d), x.dtype).at[dest].set(
        jnp.repeat(xt, k, axis=0), mode="drop")
    tile_expert = jnp.minimum(jnp.searchsorted(
        tile_end, jnp.arange(tiles, dtype=tile_end.dtype), side="right"),
        n - 1).astype(jnp.int32)
    n_live = tile_end[-1]

    def mm(a, w, out_dtype):
        return gm.gmm(a, w.astype(a.dtype), tile_expert, n_live, tm=tm,
                      out_dtype=out_dtype, group=group,
                      force_pallas=force_pallas)

    g = mm(xs, p["wg"], x.dtype)
    u = mm(xs, p["wu"], x.dtype)
    act = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = mm(act, p["wd"], jnp.float32)                         # (M, d)
    yk = jnp.take(y, jnp.minimum(dest, tiles * tm - 1), axis=0)
    yk = jnp.where(held.reshape(-1, 1), yk, 0.0).reshape(t, k, d)
    out = jnp.sum(yk * gates[..., None], axis=1)
    stats = {"moe_rows_local": jnp.sum(counts).astype(jnp.int32),
             "moe_experts_active": jnp.sum(counts > 0).astype(jnp.int32)}
    return out.astype(x.dtype).reshape(b, s, d), stats
