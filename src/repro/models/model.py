"""Model facade: init/apply/caches/loss for every assigned family.

``build_model(cfg)`` returns an ``LM`` whose methods are pure functions
suitable for jit/pjit:

  init(key) -> params
  shape_and_logical() -> (ShapeDtypeStruct tree, logical-axes tree)
  apply(params, batch, train=True) -> (logits, aux)
  loss(params, batch) -> (scalar, metrics)
  init_cache(params_or_shapes, batch, max_seq, enc_out=None) -> cache
  decode_step(params, cache, tokens) -> (logits, new_cache)

Batch dicts per family:
  dense/moe/ssm/hybrid: {"tokens": (B,S) int32}
  vlm:    {"tokens": (B,S), "patches": (B,P,d_model)}   (stub frontend)
  encdec: {"tokens": (B,S), "frames": (B,T,d_model)}    (stub frontend)
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.parallel.sharding import constrain

AUX_COEF = {"load_balance": 0.01, "router_z": 0.001}


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------- init
    def _init(self, key):
        cfg = self.cfg
        pdt = _dtype(cfg.param_dtype)
        ks = jax.random.split(key, 8)
        p: dict[str, Any] = {}
        l: dict[str, Any] = {}
        p["embed"], l["embed"] = L.embedding_init(ks[0], cfg.padded_vocab,
                                                  cfg.d_model, pdt)
        cross = cfg.family == "encdec"
        p["layers"], l["layers"] = T.stack_init(ks[1], cfg, pdt, cross=cross)
        p["final_norm"], l["final_norm"] = T._norm_init(cfg, pdt)
        if not cfg.tie_embeddings:
            p["lm_head"] = jax.random.normal(
                ks[2], (cfg.d_model, cfg.padded_vocab), pdt) * 0.02
            l["lm_head"] = ("fsdp", "vocab")
        if cfg.pos_emb == "learned":
            p["pos_emb"] = jax.random.normal(
                ks[3], (cfg.max_seq, cfg.d_model), pdt) * 0.02
            l["pos_emb"] = ("seq", "embed")
        if cross:
            enc_cfg = self._enc_cfg()
            p["enc_layers"], l["enc_layers"] = T.stack_init(
                ks[4], enc_cfg, pdt, cross=False)
            p["enc_norm"], l["enc_norm"] = T._norm_init(enc_cfg, pdt)
        return p, l

    def _enc_cfg(self):
        import dataclasses
        return dataclasses.replace(
            self.cfg, family="dense", n_layers=self.cfg.n_enc_layers,
            pos_emb="sinusoidal", n_experts=0, attn_every=0)

    def init(self, key):
        return self._init(key)[0]

    def shape_and_logical(self):
        captured = {}

        def f(key):
            p, l = self._init(key)
            captured["l"] = l
            return p

        shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
        return shapes, captured["l"]

    # ------------------------------------------------------------ forward
    def _embed_in(self, params, tokens, cdt, pos0=0):
        cfg = self.cfg
        x = L.embedding_lookup(params["embed"], tokens).astype(cdt)
        if cfg.pos_emb == "learned":
            pe = jax.lax.dynamic_slice_in_dim(
                params["pos_emb"], pos0, tokens.shape[1], axis=0)
            x = x + pe.astype(cdt)[None]
        elif cfg.pos_emb == "sinusoidal":
            x = x + L.sinusoidal_pos(tokens.shape[1], cfg.d_model,
                                     pos0).astype(cdt)[None]
        return x

    def _encode(self, params, frames, cdt):
        """Whisper encoder over precomputed (stub) frame embeddings."""
        cfg = self._enc_cfg()
        x = frames.astype(cdt)
        x = x + L.sinusoidal_pos(x.shape[1], cfg.d_model).astype(cdt)[None]
        x, _, _ = T.stack_apply(params["enc_layers"], x, cfg, causal=False)
        return T._norm_apply(cfg, params["enc_norm"], x)

    def apply(self, params, batch, train: bool = True):
        cfg = self.cfg
        cdt = _dtype(cfg.compute_dtype)
        tokens = batch["tokens"]
        enc_caches = None

        if cfg.family == "encdec":
            enc_out = self._encode(params, batch["frames"], cdt)
            _, enc_caches = T.stack_init_cache(
                cfg, tokens.shape[0], 0, cdt, cross=True, enc_out=enc_out,
                params=params["layers"])
            x = self._embed_in(params, tokens, cdt)
        elif cfg.family == "vlm":
            patches = batch["patches"].astype(cdt)     # (B, P, d) stub
            tok = self._embed_in(params, tokens, cdt)
            x = jnp.concatenate([patches, tok], axis=1)
        else:
            x = self._embed_in(params, tokens, cdt)

        x = constrain(x, ("batch", "seq", "embed"))
        x, _, aux = T.stack_apply(params["layers"], x, cfg,
                                  enc_caches=enc_caches, causal=True)
        x = T._norm_apply(cfg, params["final_norm"], x)
        if cfg.family == "vlm":
            x = x[:, batch["patches"].shape[1]:]       # logits on text only
        logits = self._head(params, x)
        return logits, aux

    def _head(self, params, x):
        cfg = self.cfg
        w = (params["embed"]["emb"].T if cfg.tie_embeddings
             else params["lm_head"])
        logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
        # vocab sharding takes priority over SP on the seq dim here: the
        # f32 loss intermediates are V/16-sharded instead.
        return constrain(logits, ("batch", None, "vocab"))

    # ------------------------------------------------------------- loss
    def loss(self, params, batch):
        logits, aux = self.apply(params, batch)
        tokens = batch["tokens"]
        lg = logits[:, :-1].astype(jnp.float32)
        tg = tokens[:, 1:]
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tg[..., None], axis=-1)[..., 0]
        mask = (tg >= 0).astype(jnp.float32)
        nll = jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        total = nll
        metrics = {"nll": nll}
        for k, v in aux.items():
            coef = AUX_COEF.get(k, 0.0)
            total = total + coef * v
            metrics[k] = v
        metrics["loss"] = total
        return total, metrics

    # ------------------------------------------------------------- decode
    def init_cache(self, params, batch: int, max_seq: int, enc_out=None):
        cfg = self.cfg
        cdt = _dtype(cfg.compute_dtype)
        cross = cfg.family == "encdec"
        caches, enc_caches = T.stack_init_cache(
            cfg, batch, max_seq, cdt, cross=cross, enc_out=enc_out,
            params=params["layers"] if cross else None)
        cache = {"layers": caches, "pos": jnp.int32(0)}
        if enc_caches is not None:
            cache["enc"] = enc_caches
        return cache

    def prefill(self, params, cache, tokens, prefix_embeds=None):
        """Write a prompt into the cache; logits for its last position.

        Must be called at cache position 0 (fresh prefill).  For VLM,
        ``prefix_embeds`` (B, P, d) are concatenated before the tokens.
        """
        cfg = self.cfg
        cdt = _dtype(cfg.compute_dtype)
        x = self._embed_in(params, tokens, cdt, pos0=0)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(cdt), x], axis=1)
        x = constrain(x, ("batch", "seq", "embed"))
        x, new_caches, _ = T.stack_apply(
            params["layers"], x, cfg, caches=cache["layers"],
            cache_pos=0, enc_caches=cache.get("enc"), causal=True)
        x = T._norm_apply(cfg, params["final_norm"], x[:, -1:])
        logits = self._head(params, x)
        out = dict(cache)
        out["layers"] = new_caches
        n_prefix = 0 if prefix_embeds is None else prefix_embeds.shape[1]
        out["pos"] = cache["pos"] + tokens.shape[1] + n_prefix
        return logits, out

    def decode_step(self, params, cache, tokens):
        """One token: tokens (B, 1) -> (logits (B,1,V), new cache)."""
        cfg = self.cfg
        cdt = _dtype(cfg.compute_dtype)
        pos = cache["pos"]
        x = self._embed_in(params, tokens, cdt, pos0=pos)
        x = constrain(x, ("batch", None, "embed"))
        x, new_caches, _ = T.stack_apply(
            params["layers"], x, cfg, caches=cache["layers"], cache_pos=pos,
            enc_caches=cache.get("enc"), causal=True)
        x = T._norm_apply(cfg, params["final_norm"], x)
        logits = self._head(params, x)
        out = dict(cache)
        out["layers"] = new_caches
        out["pos"] = pos + 1
        return logits, out


    # ------------------------------------------------ paged decode (serving)
    def init_paged_cache(self, num_pages: int, page_size: int, mesh=None,
                         codec: str = "fp"):
        """Shared block-pool KV caches for continuous-batching decode.

        Unlike :meth:`init_cache` there is no per-slot ``max_seq``
        reservation: all slots draw pages from one pool via the
        engine-owned page table.  RoPE-positioned attention-only stacks
        (the positions come from per-slot seq_lens, not a global
        cache_pos; learned/sinusoidal embeddings would need per-slot
        embed offsets).

        With ``mesh`` (a "model" axis of size tp > 1) the pools are
        placed KV-head-sharded over the mesh: every shard keeps the full
        page layout but only ``n_kv_heads / tp`` heads, so per-shard
        pool HBM shrinks by tp while the host page tables (and all the
        refcount/COW/prefix-cache bookkeeping) stay replicated.

        ``codec`` selects the page codec ("fp" | "int8" | "log16", see
        :mod:`repro.kernels.page_codec`): the pools take the codec's
        storage dtype and quantized codecs add f32 scale sidecar pools;
        the same NamedSharding placement covers every leaf (scale
        sidecars share the data pools' rank and Hkv axis).
        """
        cfg = self.cfg
        assert cfg.pos_emb == "rope", (
            "paged serving requires rope positions, got %r" % cfg.pos_emb)
        cdt = _dtype(cfg.compute_dtype)
        layers = T.stack_init_paged_cache(cfg, num_pages, page_size, cdt,
                                          codec=codec)
        tp = 1 if mesh is None else int(mesh.shape.get("model", 1))
        if tp > 1:
            if cfg.n_kv_heads % tp or cfg.n_heads % tp:
                raise ValueError(
                    f"paged TP requires heads divisible by tp: "
                    f"n_kv_heads={cfg.n_kv_heads}, n_heads={cfg.n_heads}, "
                    f"tp={tp}")
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            # Stacked pools are (groups, P, Hkv, page, dh): head axis 2.
            sh = NamedSharding(mesh, P(None, None, "model", None, None))
            layers = jax.device_put(layers, sh)
        return layers

    def paged_prefill(self, params, layers, tokens, page_table,
                      last_pos=None, start_pos=None, mesh=None,
                      codec: str = "fp", return_all_logits: bool = False):
        """Prefill sequences into paged KV storage.

        tokens: (B, L) token rows padded to a common length L.
        page_table: (B, J) rows with pages allocated for the positions
        being written.
        last_pos: optional (B,) int32 - each row's last *real* position
        within ``tokens``; when given, the LM head runs only there and
        logits are (B, 1, V) (the padded-vocab projection over every
        padded position is the dominant prefill cost at full scale).
        Without it, logits cover all positions: (B, L, V).
        start_pos: optional (B,) int32 - *chunked* prefill: row b is a
        chunk of ``last_pos[b] + 1`` real tokens starting at absolute
        position ``start_pos[b]`` (pos > 0 resumes a paused or
        budget-bounded prefill).  The chunk attends causally against all
        KV already written for its sequence (shared prefix pages +
        earlier chunks + itself); padding rows are never written.
        Requires ``last_pos``.  Without it, the legacy whole-prompt
        fresh prefill at position 0 runs (padded tail KV is masked by
        seq_lens and overwritten by later appends).
        mesh: optional tensor-parallel mesh (a "model" axis > 1 routes
        attention through the KV-head-sharded cascaded-ACC-merge path).
        return_all_logits: keep logits at every position even when
        ``last_pos`` is given - the prompt-logprobs path pays the full
        (B, L, V) projection to score each prompt token.
        Returns (logits, new layer caches).
        """
        cfg = self.cfg
        cdt = _dtype(cfg.compute_dtype)
        x = self._embed_in(params, tokens, cdt, pos0=0)
        x = constrain(x, ("batch", "seq", "embed"))
        if start_pos is None:
            positions = None
            ps = {"page_table": page_table, "prefill": True, "mesh": mesh,
                  "codec": codec,
                  "seq_lens": jnp.zeros((tokens.shape[0],), jnp.int32)}
        else:
            assert last_pos is not None, "chunked prefill needs last_pos"
            # Positions reach attention via `positions`, which only RoPE
            # consumes; learned/sinusoidal embeds would need a per-row
            # embedding offset (pos0 is scalar) and silently misplace
            # any chunk at start_pos > 0.
            assert cfg.pos_emb == "rope", (
                "chunked paged prefill requires rope positions, got %r"
                % cfg.pos_emb)
            start_pos = start_pos.astype(jnp.int32)
            positions = start_pos[:, None] + jnp.arange(
                tokens.shape[1], dtype=jnp.int32)[None]
            ps = {"page_table": page_table, "prefill": True, "mesh": mesh,
                  "codec": codec, "start_pos": start_pos,
                  "chunk_lens": last_pos.astype(jnp.int32) + 1}
        x, new_layers, _ = T.stack_apply(
            params["layers"], x, cfg, positions=positions, caches=layers,
            cache_pos=0, page_state=ps, causal=True)
        if last_pos is not None and not return_all_logits:
            x = jnp.take_along_axis(x, last_pos[:, None, None].astype(
                jnp.int32), axis=1)
        x = T._norm_apply(cfg, params["final_norm"], x)
        return self._head(params, x), new_layers

    def paged_verify_step(self, params, layers, tokens, page_table,
                          seq_lens, chunk_lens, mesh=None,
                          codec: str = "fp"):
        """K-token speculative verify step across every slot.

        tokens: (B, K) input tokens per slot - the carry token followed
        by up to K-1 drafted continuations, landing at positions
        ``seq_lens[b] + i``.  chunk_lens: (B,) int32 real input count
        per slot (0 = free / mid-prefill slot: nothing is written and
        its logits are garbage to be ignored; rows at i >= chunk_lens
        are likewise garbage).  Writes KV for the real inputs and
        returns (logits (B, K, V), new layer caches) - the logits at
        every verify position, scored in one paged-attention call.
        With K == 1 this is exactly :meth:`paged_decode_step`.
        """
        cfg = self.cfg
        cdt = _dtype(cfg.compute_dtype)
        x = self._embed_in(params, tokens, cdt, pos0=0)
        x = constrain(x, ("batch", None, "embed"))
        seq_lens = seq_lens.astype(jnp.int32)
        positions = seq_lens[:, None] + jnp.arange(
            tokens.shape[1], dtype=jnp.int32)[None]
        ps = {"page_table": page_table, "seq_lens": seq_lens, "mesh": mesh,
              "codec": codec,
              "chunk_lens": chunk_lens.astype(jnp.int32), "verify": True}
        x, new_layers, _ = T.stack_apply(
            params["layers"], x, cfg, positions=positions, caches=layers,
            page_state=ps, causal=True)
        x = T._norm_apply(cfg, params["final_norm"], x)
        return self._head(params, x), new_layers

    def paged_decode_step(self, params, layers, tokens, page_table,
                          seq_lens, mesh=None, codec: str = "fp"):
        """One continuous-batching decode step across every slot.

        tokens: (B, 1) next input token per slot; seq_lens: (B,) int32
        current length per slot (0 = free slot: its write is dropped and
        its logits are garbage to be ignored).  Appends each active
        token's KV at position seq_lens[b] and returns
        (logits (B, 1, V), new layer caches).
        """
        cfg = self.cfg
        cdt = _dtype(cfg.compute_dtype)
        x = self._embed_in(params, tokens, cdt, pos0=0)
        x = constrain(x, ("batch", None, "embed"))
        ps = {"page_table": page_table, "seq_lens": seq_lens, "mesh": mesh,
              "codec": codec}
        x, new_layers, _ = T.stack_apply(
            params["layers"], x, cfg, positions=seq_lens[:, None],
            caches=layers, page_state=ps, causal=True)
        x = T._norm_apply(cfg, params["final_norm"], x)
        return self._head(params, x), new_layers


def build_model(cfg: ModelConfig) -> LM:
    return LM(cfg)
