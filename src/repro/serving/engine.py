"""ServingEngine: continuous-batching sampled + speculative decode over
paged KV.

One engine step = one token-budget step that *mixes* prefill chunks with
the batched decode (Sarathi-style):

  * prefill work is bounded by ``prefill_budget`` tokens per step and
    handed out as chunks, so a long prompt streams in across steps
    while every running decode keeps producing tokens per step (no
    prefill stall);
  * admission claims the longest cached prompt prefix (full pages, via
    the cache's chain-hash table) instead of recomputing it -
    shared-system-prompt workloads prefill only their unique tail;
  * decode is one jitted *verify* call over all ``max_batch`` slots and
    ``spec_k + 1`` token columns: the carry token plus up to ``spec_k``
    prompt-lookup drafts per slot are scored in a single page-table
    walk (free and mid-prefill slots ride along masked), so the trace
    is unique and requests join/leave without recompilation;
  * sampling (temperature / top-k / top-p / repetition penalty) runs
    *inside* the jitted step, seeded per request and keyed by stream
    position (``jax.random.fold_in``), so a request's tokens are
    identical whether it shares the step with 0 or 7 neighbors - and a
    draft is accepted iff it equals the token the sampler would have
    produced, which makes speculative decode lossless under both greedy
    and stochastic sampling;
  * rejected draft columns are rolled back on the host: ``seq_lens``
    drops to the accepted prefix and now-empty tail pages return to the
    pool (COW refcounts respected);
  * sequence groups (``n > 1`` / ``best_of`` / ``beam_width``): one
    prefill fans out into width branch slots over ``PagedKVCache.fork``
    (prompt pages shared by refcount, zero KV copied).  Parallel
    branches sample under ``branch_seed(seed, branch)`` and decode
    exactly like independent requests - token-identical, asserted by
    tests/test_parallel_sampling.py; beam branches take their tokens
    from a per-group top-2k reorder (fork the parents keeping several
    children, free the childless), with speculation auto-disabled.
    Preemption evicts whole groups; deterministic keys re-derive the
    same completions on re-admission;

  * under page pressure, mid-prefill sequences pause in place (keep
    pages, resume at pos > 0) and decode-append pressure preempts the
    *least-advanced* sequence (cheapest replay);
  * copy-on-write page copies (fork / shared-page divergence) are
    drained from the cache and applied to the device pools before any
    write.

  * tensor parallelism (``mesh`` with a "model" axis of size tp > 1):
    the layer KV pools are KV-head-sharded across the mesh and every
    attention call routes through the cascaded-ACC-merge shard_map
    path - per-shard pool HBM drops by tp, only (m, l, o~) triplets
    cross the interconnect, and the token stream is bit-identical to
    single-shard serving.  All host-side state in this file (page
    tables, scheduler, sampling vectors) stays replicated.

Only the (max_batch, spec_k + 1) sampled-token matrix crosses to the
host per step.

Invariant (rollback x refcounts): the verify step in :meth:`_run_decode`
commits KV for all K+1 columns *before* acceptance is known and then
rolls back - the constraints that make that safe (rollback drops only
this slot's refs, re-trims the hash chain, keeps rejected-column COW
copies, junk KV above seq_lens is never attended) are documented at
length in :mod:`repro.serving.paged_cache` and must hold for every
ordering of mark_prefilled / rollback / register_pages below.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import page_codec
from repro.kernels import paged_decode as paged_k
from repro.kernels import paged_prefill as paged_pf_k
from repro.serving import sampler, tracing
from repro.serving.paged_cache import PagedKVCache
from repro.serving.scheduler import (FinishedRequest, InvalidRequestError,
                                     PrefillChunk, Request, Scheduler)

# Placeholder for the presence bitmask on greedy (static-flag) traces:
# the argmax branch never reads it, and shipping the real
# (max_batch, padded_vocab) bool matrix to the device every step would
# make the fast path pay for sampling it is not doing.
_NO_PRESENCE = np.zeros((1, 1), bool)


def _serving_jits(model, mesh=None, codec="fp"):
    """Jitted prefill/verify/sample/copy steps, cached on the model so
    every engine over the same model shares one compile cache
    (benchmarks and tests spin up several engines).  The cache is keyed
    by the tensor-parallel mesh (None = single shard) and the page
    codec - a TP engine and a single-shard engine over the same model
    trace different attention paths, and each codec bakes a different
    encode/decode into the trace.  The layer pools are donated on every
    backend, so each step updates them in place and the CPU suites
    exercise the same buffer aliasing as the chip."""
    cache = getattr(model, "_serving_jits_v5", None)
    if cache is None:
        cache = model._serving_jits_v5 = {}
    jits = cache.get((mesh, codec))
    if jits is not None:
        return jits

    # Prefill returns the last-position logits instead of a sampled
    # token: first tokens are drawn by the shared ``sample_fn`` below,
    # so a sequence group can fan one prefill out into n first tokens
    # (n rows replicating the same logits under per-branch seeds) while
    # a plain request samples through the *identical* code path - the
    # bit-identity the parallel-sampling conformance suite pins down.
    def prefill_fn(params, layers, tokens, page_table, start_pos, last_pos):
        logits, layers = model.paged_prefill(params, layers, tokens,
                                             page_table, last_pos=last_pos,
                                             start_pos=start_pos, mesh=mesh,
                                             codec=codec)
        return logits[:, 0], layers

    # Prompt-logprobs prefill: same KV writes, but the LM head runs at
    # every chunk position (the cost ``Request.logprobs`` opts into)
    # and each position's log p(next prompt token) comes back alongside
    # the last-position logits.  ``targets[b, j]`` is the stream token
    # at position start + j + 1 (0 where out of range; the host slices
    # the valid prefix).
    def prefill_lp_fn(params, layers, tokens, page_table, start_pos,
                      last_pos, targets):
        logits, layers = model.paged_prefill(params, layers, tokens,
                                             page_table, last_pos=last_pos,
                                             start_pos=start_pos, mesh=mesh,
                                             codec=codec,
                                             return_all_logits=True)
        lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        plp = jnp.take_along_axis(
            lsm, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
        last = jnp.take_along_axis(
            logits, last_pos[:, None, None].astype(jnp.int32), axis=1)
        return last[:, 0], plp, layers

    # ``greedy`` is a static (trace-time) flag: when every row this call
    # serves is argmax (temperature 0, no penalty), the whole sampling
    # pipeline (sorts, nucleus scan, categorical) compiles away.
    # ``want_lp`` (static) additionally returns the chosen token's
    # logprob - the best_of ranking signal - and stays off the greedy
    # hot path when no ranking group is live.
    def sample_fn(logits, presence, seeds, positions, temp, top_k, top_p,
                  rep_pen, greedy, want_lp):
        if greedy:
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            toks = sampler.sample_tokens(logits, presence, seeds,
                                         positions, temp, top_k, top_p,
                                         rep_pen)
        return toks, _chosen_lp(logits, toks, want_lp)

    def topk_fn(logits, k):
        """Top-k (logprob, token) per row - the beam expansion feed."""
        lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        vals, idx = jax.lax.top_k(lsm, k)
        return vals, idx.astype(jnp.int32)

    def _chosen_lp(logits, toks, want_lp):
        if not want_lp:
            return jnp.zeros(toks.shape, jnp.float32)
        lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.take_along_axis(
            lsm, toks[..., None].astype(jnp.int32), axis=-1)[..., 0]

    def _extras(logits, toks, beam_k, want_lp):
        """Side outputs of a decode/verify call: top-``beam_k``
        (logprob, token) rows for live beam groups and the chosen
        token's logprob for best_of ranking.  Both statically gated -
        zeros (and no log_softmax) when off."""
        if beam_k:
            lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            tkv, tki = jax.lax.top_k(lsm, beam_k)
            tki = tki.astype(jnp.int32)
        else:
            b, kw = toks.shape
            tkv = jnp.zeros((b, kw, 1), jnp.float32)
            tki = jnp.zeros((b, kw, 1), jnp.int32)
        return tkv, tki, _chosen_lp(logits, toks, want_lp)

    def decode_fn(params, layers, tokens, page_table, seq_lens, chunk_lens,
                  seeds, temp, top_k, top_p, rep_pen, presence, greedy,
                  beam_k, want_lp):
        # spec_k == 0 fast path: the single-token decode attention
        # (append + grouped decode) instead of the chunk-write verify.
        logits, layers = model.paged_decode_step(
            params, layers, tokens, page_table, seq_lens, mesh=mesh,
            codec=codec)
        if greedy:
            toks = jnp.argmax(logits[:, :1], axis=-1).astype(jnp.int32)
        else:
            pos = seq_lens.astype(jnp.int32) + 1
            toks = sampler.sample_tokens(
                logits[:, 0], presence, seeds, pos, temp, top_k, top_p,
                rep_pen)[:, None]
        tkv, tki, lp = _extras(logits, toks, beam_k, want_lp)
        return toks, tkv, tki, lp, layers

    def verify_fn(params, layers, tokens, page_table, seq_lens, chunk_lens,
                  seeds, temp, top_k, top_p, rep_pen, presence, greedy,
                  beam_k, want_lp):
        logits, layers = model.paged_verify_step(
            params, layers, tokens, page_table, seq_lens, chunk_lens,
            mesh=mesh, codec=codec)
        b, kw, v = logits.shape
        if greedy:
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            pres = sampler.step_presence(presence, tokens)
            # Sampled token i lands at stream index seq_lens + 1 + i.
            pos = seq_lens.astype(jnp.int32)[:, None] + 1 + \
                jnp.arange(kw, dtype=jnp.int32)[None]
            rep = lambda x: jnp.repeat(x, kw, axis=0)  # noqa: E731
            toks = sampler.sample_tokens(
                logits.reshape(b * kw, v), pres.reshape(b * kw, -1),
                rep(seeds), pos.reshape(-1), rep(temp), rep(top_k),
                rep(top_p), rep(rep_pen)).reshape(b, kw)
        tkv, tki, lp = _extras(logits, toks, beam_k, want_lp)
        return toks, tkv, tki, lp, layers

    def copy_fn(layers, src, dst):
        # Layer pools are stacked (groups, P, Hkv, page, d): page axis 1.
        return jax.tree.map(
            lambda pool: paged_pf_k.copy_pages(pool, src, dst, axis=1),
            layers)

    jits = (jax.jit(prefill_fn, donate_argnums=(1,)),
            jax.jit(decode_fn, donate_argnums=(1,),
                    static_argnums=(12, 13, 14)),
            jax.jit(verify_fn, donate_argnums=(1,),
                    static_argnums=(12, 13, 14)),
            jax.jit(copy_fn, donate_argnums=(0,)),
            jax.jit(sample_fn, static_argnums=(8, 9)),
            jax.jit(topk_fn, static_argnums=(1,)),
            jax.jit(prefill_lp_fn, donate_argnums=(1,)))
    cache[(mesh, codec)] = jits
    return jits


class ServingEngine:
    # spec_k="auto" draft ceiling: bounds both the per-step draft count
    # and the number of distinct (kw) trace shapes jit ever sees.
    AUTO_SPEC_KMAX = 4

    def __init__(self, model, params, *, max_batch: int = 8,
                 page_size: int = 16, num_pages: int | None = None,
                 max_seq: int | None = None,
                 prefill_budget: int | str | None = None,
                 prefix_caching: bool = True,
                 spec_k: int | str = 0,
                 cached_frac: float = 0.5,
                 adaptive_floor: int | None = None,
                 adaptive_ceiling: int | None = None,
                 mesh=None, kv_codec: str = "fp", device=None):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if jax.default_backend() == "tpu" and \
                page_size not in paged_k.TPU_PAGE_SIZES:
            raise ValueError(
                f"page_size {page_size} is not supported on the TPU: the "
                f"paged kernels compile for page sizes "
                f"{paged_k.TPU_PAGE_SIZES}")
        if mesh is not None and device is not None:
            raise ValueError("pass a mesh or a device, not both")
        # Page codec: how KV rows are stored in the device pools ("fp"
        # = raw compute dtype, "int8" = per-row absmax quant + f32 scale
        # sidecar, "log16" = 16-bit log-domain).  Validated here so a
        # typo'd codec fails at engine construction, not first step.
        self.kv_codec = page_codec.get_codec(kv_codec).name
        # prefill_budget: None = unbounded, int = fixed token budget per
        # step, "adaptive" = derived each step from the decode batch's
        # SLA headroom (see Scheduler.adaptive_prefill_budget), clamped
        # to [adaptive_floor, adaptive_ceiling].
        self.adaptive_prefill = prefill_budget == "adaptive"
        if isinstance(prefill_budget, str) and not self.adaptive_prefill:
            raise ValueError(
                f"prefill_budget must be an int, None or 'adaptive', "
                f"got {prefill_budget!r}")
        if not self.adaptive_prefill and prefill_budget is not None \
                and prefill_budget < 1:
            raise ValueError(
                f"prefill_budget must be >= 1, got {prefill_budget}")
        # spec_k = "auto": speculate up to AUTO_SPEC_KMAX drafts and let
        # the measured accept-rate EMA choose each step's draft count
        # (exact acceptance is lossless at any k, so the token stream is
        # identical to every fixed spec_k - only the step count moves).
        self.auto_spec = spec_k == "auto"
        if self.auto_spec:
            spec_k = self.AUTO_SPEC_KMAX
        elif isinstance(spec_k, str):
            raise ValueError(
                f"spec_k must be an int or 'auto', got {spec_k!r}")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if not 0.0 <= cached_frac <= 1.0:
            raise ValueError(
                f"cached_frac must be in [0, 1], got {cached_frac}")
        # Tensor parallelism: a mesh with a "model" axis of size tp > 1
        # shards the KV pools by head; everything host-side (page
        # tables, refcounts, scheduler) is oblivious to it.  A "data"
        # axis of size dp > 1 additionally batch-shards every paged
        # attention call on the slot dim (pools and host state stay
        # replicated across data shards - see
        # repro.parallel.collectives).
        # Placement: a mesh replicates the params over its devices (the
        # pools are head-sharded in init_paged_cache); a device commits
        # params and pools to that one device, so the jitted steps run
        # there (one replica per chip behind a Router).  With neither,
        # everything stays on the default device.
        self.mesh = mesh
        if mesh is not None:
            params = jax.device_put(params, NamedSharding(mesh, P()))
        elif device is not None:
            params = jax.device_put(params, device)
        self.tp = 1 if mesh is None else int(mesh.shape.get("model", 1))
        self.dp = 1 if mesh is None else int(mesh.shape.get("data", 1))
        if self.tp > 1 or self.dp > 1:
            if len(mesh.devices.flat) > len(jax.devices()):
                raise ValueError(
                    f"mesh needs {len(mesh.devices.flat)} devices, have "
                    f"{len(jax.devices())}")
        if self.tp > 1:
            if model.cfg.n_kv_heads % self.tp or \
                    model.cfg.n_heads % self.tp:
                raise ValueError(
                    f"tp={self.tp} must divide n_kv_heads="
                    f"{model.cfg.n_kv_heads} and n_heads="
                    f"{model.cfg.n_heads}")
        if self.dp > 1 and max_batch % self.dp:
            # Decode/verify steps are always max_batch-shaped, so the
            # slot dim must divide evenly for the data axis to shard it
            # (odd prefill groups fall back to replicated compute).
            raise ValueError(
                f"data-parallel degree dp={self.dp} must divide "
                f"max_batch={max_batch}")
        self.model = model
        self.params = params
        self.page_size = page_size
        self.max_batch = max_batch
        self.prefill_budget = prefill_budget
        self.adaptive_floor = adaptive_floor if adaptive_floor is not None \
            else page_size
        self.adaptive_ceiling = adaptive_ceiling \
            if adaptive_ceiling is not None \
            else max(8 * page_size, self.adaptive_floor)
        if not 1 <= self.adaptive_floor <= self.adaptive_ceiling:
            raise ValueError(
                f"need 1 <= adaptive_floor <= adaptive_ceiling, got "
                f"{self.adaptive_floor}..{self.adaptive_ceiling}")
        # EMA of measured prefill throughput (tokens/sec of wall time in
        # the engine.prefill.* phases) - the rate adaptive_prefill_budget
        # converts SLA headroom seconds into a token budget with.
        self._prefill_rate = 0.0
        self.prefix_caching = prefix_caching
        self.spec_k = spec_k
        max_seq = max_seq if max_seq is not None else model.cfg.max_seq
        self.pages_per_seq = -(-max_seq // page_size)
        if num_pages is None:
            num_pages = max_batch * self.pages_per_seq
        # Bound the dead-prefix LRU to a fraction of the pool so
        # long-running multi-tenant churn cannot turn the whole free
        # pool into single-use cached prefixes (1.0 = uncapped).
        max_cached = None if cached_frac >= 1.0 \
            else int(cached_frac * num_pages)
        self.cache = PagedKVCache(num_pages, page_size, max_batch,
                                  self.pages_per_seq,
                                  max_cached_pages=max_cached)
        self.sched = Scheduler(self.cache)
        with jax.default_device(device):
            self.layers = model.init_paged_cache(num_pages, page_size,
                                                 mesh=mesh,
                                                 codec=self.kv_codec)
        if device is not None:
            self.layers = jax.device_put(self.layers, device)
        # Per-slot sampling state (greedy defaults), mirrored to device
        # every step; presence is the repetition-penalty context bitmask.
        self._temp = np.zeros((max_batch,), np.float32)
        self._top_k = np.zeros((max_batch,), np.int32)
        self._top_p = np.ones((max_batch,), np.float32)
        self._rep_pen = np.ones((max_batch,), np.float32)
        self._seed = np.zeros((max_batch,), np.int32)
        self._presence = np.zeros((max_batch, model.cfg.padded_vocab), bool)
        self.stats = {"steps": 0, "prefills": 0, "prefill_chunks": 0,
                      "prefill_tokens": 0, "cached_prefill_tokens": 0,
                      "generated_tokens": 0, "preemptions": 0,
                      "cow_copies": 0, "rejected": 0, "decode_steps": 0,
                      "decode_slot_steps": 0, "decode_tokens": 0,
                      # Pages a layer's paged decode kernel walks (each
                      # slot's live pages) against the slots x page-table
                      # width it would walk page by page, summed over
                      # decode steps without drafts:
                      "decode_kv_pages": 0, "decode_kv_table_pages": 0,
                      "draft_tokens": 0, "draft_accepted": 0,
                      # Draft-quality EMA (alpha 0.2 over verify steps
                      # that proposed >= 1 draft) and the per-step draft
                      # count it chose when spec_k="auto":
                      "accept_rate_ema": 0.0, "spec_k_last": 0,
                      "rollbacks": 0, "triplet_bytes": 0,
                      "groups": 0, "forks": 0, "beam_steps": 0,
                      "beam_early_stops": 0,
                      "cancelled": 0, "adaptive_budget_last": 0,
                      # AsyncFrontend bookkeeping (kept here so every
                      # serving counter surfaces through one dict, e.g.
                      # the HTTP transport's GET /stats):
                      "results_evicted": 0,    # unclaimed finished
                      #                          results aged out of the
                      #                          bounded LRU
                      "stream_overflows": 0}   # bounded per-stream
        #                                        queues hitting capacity
        #                                        (stalled readers)
        # Wall, thread-CPU seconds and passes of every step and frontend
        # phase (repro.serving.tracing), beside the counters above.
        self.phases = tracing.new_totals()
        (self._prefill, self._decode, self._verify, self._copy,
         self._sample, self._topk,
         self._prefill_lp) = _serving_jits(model, mesh, self.kv_codec)

    # ------------------------------------------------------------- TP info
    def pool_bytes(self) -> int:
        """Total logical KV pool bytes (across all shards)."""
        return sum(x.nbytes for x in jax.tree.leaves(self.layers))

    def bytes_per_token(self) -> int:
        """Pool bytes consumed per stored KV token-row (all layers, data
        + scale sidecars).  Derived from the actual pool leaves, so it
        is the number the equal-pool-bytes slot math in the benchmark
        uses: at a fixed byte budget a codec admits
        ``fp_bytes_per_token / codec_bytes_per_token`` times the
        sequences."""
        num_pages = self.cache.num_pages
        return self.pool_bytes() // (num_pages * self.page_size)

    def pool_bytes_per_shard(self) -> int:
        """KV pool bytes actually resident on the fullest device,
        *measured* from the arrays' addressable shards - not derived
        from ``tp`` - so a silently dropped pool sharding (replicated
        pools) shows up as full-size here and fails the ``--tp``
        benchmark gate instead of hiding behind arithmetic."""
        per_dev: dict = {}
        for leaf in jax.tree.leaves(self.layers):
            for s in leaf.addressable_shards:
                per_dev[s.device] = per_dev.get(s.device, 0) + \
                    s.data.nbytes
        return max(per_dev.values())

    def prompt_logits(self, prompt: list[int]) -> np.ndarray:
        """Last-position logits (f32, padded vocab) of ``prompt`` from
        this engine's own jitted prefill step - same params, pools,
        codec and chunk shapes as serving a request with this prompt in
        one chunk - computed in a scratch slot that is freed again.
        Call between steps, with a slot and the prompt's pages free."""
        n = len(prompt)
        slot = self.cache.alloc_slot(n)
        try:
            lpad = -(-n // self.page_size) * self.page_size
            width = self._pow2_width(self.cache.pages_for(n))
            toks = np.zeros((1, lpad), np.int32)
            toks[0, :n] = prompt
            logits, self.layers = self._prefill(
                self.params, self.layers, jnp.asarray(toks),
                jnp.asarray(self.cache.page_table[slot:slot + 1, :width]),
                jnp.zeros((1,), jnp.int32), jnp.asarray([n - 1], jnp.int32))
            # Read the result before free_slot rewrites the table row: on
            # the CPU the step may still be reading it in place.
            return np.asarray(logits[0], np.float32)
        finally:
            self.cache.free_slot(slot)

    def compiled_decode_text(self, max_tokens: int | None = None) -> str:
        """HLO text of the compiled greedy decode step (spec_k == 0) or
        verify step (spec_k > 0), at the page-table width serving uses
        for sequences of up to ``max_tokens`` tokens (default: the
        per-sequence ceiling) - for checking which kernels the step
        served really runs."""
        width = self.pages_per_seq if max_tokens is None else \
            self._pow2_width(self.cache.pages_for(max_tokens))
        kw = self.spec_k + 1
        b = self.max_batch
        zi = jnp.zeros((b,), jnp.int32)
        zf = jnp.zeros((b,), jnp.float32)
        step_fn = self._decode if kw == 1 else self._verify
        return step_fn.lower(
            self.params, self.layers, jnp.zeros((b, kw), jnp.int32),
            jnp.asarray(self.cache.page_table[:, :width]), zi, zi, zi, zf,
            zi, zf, zf,
            jnp.asarray(_NO_PRESENCE), True, 0, False).compile().as_text()

    def _count_triplets(self, batch: int, rows: int) -> None:
        """Account the ACC-merge collective volume of one jitted call:
        each of the tp shards gathers tp padded (o~, m, l) triplets -
        (d_head + 2) f32 per (slot, query row, head, layer)."""
        if self.tp <= 1:
            return
        cfg = self.model.cfg
        per_shard = self.tp * batch * rows * cfg.n_heads * \
            (cfg.d_head + 2) * 4 * cfg.n_layers
        self.stats["triplet_bytes"] += self.tp * per_shard

    # ------------------------------------------------------------- intake
    def submit(self, req: Request) -> None:
        limit = self.pages_per_seq * self.page_size
        need = len(req.prompt) + req.max_new_tokens
        if need > limit:
            raise ValueError(
                f"request {req.rid}: prompt+budget {need} exceeds the "
                f"per-sequence ceiling {limit} (pages_per_seq * page_size)")
        width = req.beam_width if req.beam_width > 0 \
            else (req.best_of if req.best_of is not None else req.n)
        if width > self.max_batch:
            raise ValueError(
                f"request {req.rid}: group width {width} exceeds "
                f"max_batch {self.max_batch}")
        self.sched.submit(req)

    def cancel(self, rid: int) -> bool:
        """Drop request ``rid`` wherever it is (waiting / mid-prefill /
        mid-decode / fanned-out group), freeing its slots and pages
        refcount-clean.  Pending COW copies are flushed *first*: a
        queued device copy whose destination page gets freed here and
        reallocated next step would clobber the new owner's KV.
        Returns True if the request was found.  Must be called between
        engine steps (the async frontend serializes this)."""
        self._apply_pending_copies()
        hit = self.sched.cancel(rid)
        if hit:
            self.stats["cancelled"] += 1
        return hit

    # -------------------------------------------------------------- step
    def _step_budget(self) -> int | None:
        """This step's prefill token budget (None = unbounded)."""
        if not self.adaptive_prefill:
            return self.prefill_budget
        budget = self.sched.adaptive_prefill_budget(
            self._prefill_rate, self.adaptive_floor, self.adaptive_ceiling)
        self.stats["adaptive_budget_last"] = budget
        return budget

    def _phase(self, name: str) -> tracing.Phase:
        """A phase of the step in progress (see repro.serving.tracing)."""
        return tracing.Phase(self.phases, name, step=self.stats["steps"])

    def step(self) -> list[FinishedRequest]:
        """One token-budget step: continue/admit prefill chunks, run one
        batched (speculative) decode over every decoding slot; returns
        the requests that finished during this step.  Its work is tiled
        by the ``engine.*`` phases of :mod:`repro.serving.tracing`."""
        finished: list[FinishedRequest] = []
        # Decoding slots claim their next page BEFORE prefill work is
        # scheduled - otherwise a prompt chunk can grab the last free
        # pages and evict an in-flight decode into a costly replay.
        with self._phase("engine.capacity"):
            self._capacity_pass()

        with self._phase("engine.plan"):
            chunks = self._plan_prefill()
        with self._phase("engine.cow"):
            self._apply_pending_copies()
        if chunks:
            wall = tracing.total_wall_s(self.phases, tracing.PREFILL_PHASES)
            self._run_chunks(chunks, finished)
            wall = tracing.total_wall_s(self.phases,
                                        tracing.PREFILL_PHASES) - wall
            n_tok = sum(len(ck.tokens) for ck in chunks)
            if wall > 0.0 and n_tok:
                rate = n_tok / wall
                self._prefill_rate = rate if self._prefill_rate == 0.0 \
                    else 0.8 * self._prefill_rate + 0.2 * rate
        # Second (idempotent) capacity pass: slots that finished their
        # prefill this step also append a token below, and a prompt
        # ending exactly on a page boundary needs its next page before
        # the decode scatter.
        with self._phase("engine.capacity"):
            self._capacity_pass()
        self._run_decode(finished)
        self.stats["steps"] += 1
        return finished

    def _plan_prefill(self) -> list[PrefillChunk]:
        """This step's prefill chunks under its token budget."""
        budget = self._step_budget()
        chunks, reused = self.sched.schedule_prefill(budget)
        if not chunks and not self.sched.decoding_slots() \
                and self.sched.running:
            # Gridlock: every running slot is a paused prefill and the
            # pool is dry.  Free the least-advanced one (cheapest
            # replay; its published pages stay claimable) so the rest
            # can finish, then re-plan.
            victim = self.sched.choose_victim()
            if victim is not None:
                self.sched.preempt(victim)
                self.stats["preemptions"] += 1
                chunks, r2 = self.sched.schedule_prefill(budget)
                reused += r2
        self.stats["cached_prefill_tokens"] += reused
        return chunks

    # ---------------------------------------------------------- capacity
    def _capacity_pass(self) -> None:
        """Guarantee every decoding slot can append one token, preempting
        the least-advanced running sequence under pool pressure."""
        for slot in self.sched.decoding_slots():
            if slot not in self.sched.running:
                continue                    # already evicted as a victim
            while slot in self.sched.running and \
                    not self.cache.ensure_append_capacity(slot):
                at_ceiling = self.cache.pages_for(
                    int(self.cache.seq_lens[slot]) + 1) \
                    > self.cache.pages_per_seq
                victim = slot if at_ceiling else self.sched.choose_victim()
                self.sched.preempt(victim)
                self.stats["preemptions"] += 1
                # A group victim evicts every branch of its group - the
                # probed slot itself may be gone (membership re-checked
                # by the loop condition).

    def _apply_pending_copies(self) -> None:
        """Apply queued copy-on-write page copies to the device pools.

        Padded to a power-of-two batch (dropped out-of-range writes) so
        jit sees a handful of shapes.
        """
        copies = self.cache.take_pending_copies()
        if not copies:
            return
        self.stats["cow_copies"] += len(copies)
        n = 1
        while n < len(copies):
            n *= 2
        src = np.zeros((n,), np.int32)
        dst = np.full((n,), self.cache.num_pages, np.int32)   # dropped
        for i, (s, d) in enumerate(copies):
            src[i], dst[i] = s, d
        self.layers = self._copy(self.layers, jnp.asarray(src),
                                 jnp.asarray(dst))

    # ----------------------------------------------------------- sampling
    def _all_greedy(self, slots) -> bool:
        """True when every listed slot is pure argmax (temperature 0, no
        repetition penalty) - the static fast-path flag for the jits."""
        idx = np.asarray(list(slots), np.int64)
        return bool(np.all(self._temp[idx] == 0.0)
                    and np.all(self._rep_pen[idx] == 1.0))

    def _set_sampling(self, slot: int) -> None:
        """Mirror a slot's request sampling params into the batched
        per-slot vectors the jitted steps consume."""
        sp = self.sched.running[slot].req.sampling or sampler.GREEDY
        self._temp[slot] = sp.temperature
        self._top_k[slot] = sp.top_k
        self._top_p[slot] = sp.top_p
        self._rep_pen[slot] = sp.repetition_penalty
        self._seed[slot] = sp.seed

    def _rebuild_presence(self, slot: int) -> None:
        """Recompute a slot's repetition-penalty context from its full
        token stream (admission / replay after preemption)."""
        self._presence[slot] = False
        toks = np.asarray(self.sched.running[slot].tokens(), np.int64)
        self._presence[slot, toks] = True

    # ----------------------------------------------------------- prefill
    def _run_chunks(self, chunks: list[PrefillChunk], finished: list):
        """Run this step's prefill chunks, batched by padded length (one
        jit trace per (group size, padded length) pair).  Final chunks
        yield the sequence's first new token(s): the prefill jit returns
        the last-position logits, sequence groups fan out their width
        branches over ``fork`` (sharing every prompt page), and all
        first tokens - one per plain request, one per branch - are drawn
        in a single shared sampling call."""
        with self._phase("engine.prefill.inputs"):
            for ck in chunks:
                self._set_sampling(ck.slot)
            groups: dict[int, list[PrefillChunk]] = {}
            for ck in chunks:
                lpad = -(-len(ck.tokens) // self.page_size) * self.page_size
                groups.setdefault(lpad, []).append(ck)
        for lpad, grp in sorted(groups.items()):
            with self._phase("engine.prefill.inputs"):
                bsz = len(grp)
                width = self._pow2_width(max(
                    self.cache.pages_for(ck.start + len(ck.tokens))
                    for ck in grp))
                toks = np.zeros((bsz, lpad), np.int32)
                rows = np.zeros((bsz, width), np.int32)
                start = np.zeros((bsz,), np.int32)
                last = np.zeros((bsz,), np.int32)
                for i, ck in enumerate(grp):
                    toks[i, :len(ck.tokens)] = ck.tokens
                    rows[i] = self.cache.page_table[ck.slot, :width]
                    start[i] = ck.start
                    last[i] = len(ck.tokens) - 1
                args = (jnp.asarray(toks), jnp.asarray(rows),
                        jnp.asarray(start), jnp.asarray(last))
                # Any logprobs request in the batch routes the whole
                # group through the prompt-logprobs prefill
                # (full-position LM head); groups without one stay on
                # the gathered fast path.
                want_plp = any(self.sched.running[ck.slot].req.logprobs
                               for ck in grp)
                if want_plp:
                    args += (jnp.asarray(self._prompt_targets(grp, lpad)),)
            with self._phase("engine.prefill.dispatch"):
                if want_plp:
                    logits, plp, self.layers = self._prefill_lp(
                        self.params, self.layers, *args)
                else:
                    logits, self.layers = self._prefill(
                        self.params, self.layers, *args)
            if want_plp:
                with self._phase("engine.prefill.sync"):
                    plp = np.asarray(plp)
            with self._phase("engine.prefill.commit"):
                if want_plp:
                    self._record_prompt_lps(grp, plp)
                self.stats["prefills"] += 1
                self._count_triplets(bsz, lpad)
                finals = []
                for i, ck in enumerate(grp):
                    self.stats["prefill_chunks"] += 1
                    self.stats["prefill_tokens"] += len(ck.tokens)
                    self.sched.complete_chunk(ck)
                    if self.prefix_caching:
                        self.cache.register_pages(
                            ck.slot, self.sched.running[ck.slot].tokens())
                    if ck.is_final:
                        finals.append((i, ck.slot))
            if finals:
                self._finish_prefills(logits, finals, finished)

    def _prompt_targets(self, grp, lpad: int) -> np.ndarray:
        """Next-token target per chunk position: ``targets[i, j]`` is
        chunk i's stream token at absolute position start + j + 1 (what
        the logit at position j predicts), 0 where out of range."""
        targets = np.zeros((len(grp), lpad), np.int32)
        for i, ck in enumerate(grp):
            stream = self.sched.running[ck.slot].tokens()
            hi = min(len(ck.tokens), len(stream) - ck.start - 1)
            if hi > 0:
                targets[i, :hi] = stream[ck.start + 1:ck.start + 1 + hi]
        return targets

    def _record_prompt_lps(self, grp, plp: np.ndarray) -> None:
        """Fill each logprobs request's prompt_lps from this group's
        per-position logprobs: position j of a chunk scores the prompt
        token at stream index start + j + 1.  Indices past the prompt
        (replayed generated tokens) and the final chunk's last position
        (it predicts the first *generated* token - the sampler's lp
        path owns that) are skipped."""
        for i, ck in enumerate(grp):
            st = self.sched.running[ck.slot]
            if not st.req.logprobs:
                continue
            plen = len(st.req.prompt)
            n = len(ck.tokens)
            valid = n - 1 if ck.is_final else n
            for j in range(valid):
                t = ck.start + j + 1
                if 1 <= t < plen:
                    st.prompt_lps[t] = float(plp[i, j])

    def _finish_prefills(self, logits, finals: list, finished: list):
        """First tokens for every sequence whose prefill just completed:
        fan sequence groups out into their branches, then draw one token
        per (plain request | parallel branch) in a single sampling call
        over replicated logits rows, and hand beam roots their top-2k
        expansion."""
        rows: list[tuple[int, int]] = []     # (logits row, slot)
        beams: list[tuple[int, int]] = []
        with self._phase("engine.prefill.commit"):
            for i, slot in finals:
                st = self.sched.running[slot]
                if st.group is None:
                    self._rebuild_presence(slot)
                    rows.append((i, slot))
                elif st.group.beam:
                    self.stats["groups"] += 1
                    beams.append((i, slot))
                else:
                    self.stats["groups"] += 1
                    base = st.req.sampling or sampler.GREEDY
                    branches = self.sched.fan_out(slot)
                    self.stats["forks"] += len(branches) - 1
                    for bslot, b in branches:
                        self._set_branch_sampling(bslot, base, b)
                        self._rebuild_presence(bslot)
                        rows.append((i, bslot))
        if rows:
            self._sample_first_tokens(logits, rows, finished)
        for i, slot in beams:
            self._expand_beam_root(logits, i, slot, finished)

    def _sample_first_tokens(self, logits, rows, finished):
        """One sampling call covering every first token: row j draws for
        ``rows[j] = (logits row, slot)`` at the slot's stream position
        under the slot's (branch) seed - the same code path a decode
        step's sampler uses, padded to a power-of-two row count."""
        with self._phase("engine.prefill.inputs"):
            n = 1
            while n < len(rows):
                n *= 2
            src = np.zeros((n,), np.int32)
            slots = np.zeros((n,), np.int64)
            pos = np.zeros((n,), np.int32)
            for j, (i, slot) in enumerate(rows):
                src[j] = i
                slots[j] = slot
                # The sampled token's stream index is the prompt length
                # plus any generated tokens replayed after a preemption
                # - i.e. the stream length itself.
                pos[j] = self.sched.running[slot].target
            greedy = self._all_greedy(slot for _, slot in rows)
            want_lp = self._want_logprobs()
            pres = _NO_PRESENCE if greedy else self._presence[slots]
            args = (jnp.asarray(src), jnp.asarray(pres),
                    jnp.asarray(self._seed[slots]), jnp.asarray(pos),
                    jnp.asarray(self._temp[slots]),
                    jnp.asarray(self._top_k[slots]),
                    jnp.asarray(self._top_p[slots]),
                    jnp.asarray(self._rep_pen[slots]))
        with self._phase("engine.prefill.dispatch"):
            lrows = jnp.take(logits, args[0], axis=0)
            toks, lps = self._sample(lrows, *args[1:], greedy, want_lp)
        with self._phase("engine.prefill.sync"):
            toks = np.asarray(toks)
            lps = np.asarray(lps)
        with self._phase("engine.prefill.commit"):
            for j, (i, slot) in enumerate(rows):
                tok = int(toks[j])
                self.stats["generated_tokens"] += 1
                st = self.sched.running[slot]
                status = self.sched.record_token(slot, tok)
                st.cum_logprob += float(lps[j])
                if st.req.logprobs:
                    st.token_logprobs.append(float(lps[j]))
                self._presence[slot, tok] = True
                if status != "running":
                    fr = self.sched.finish(slot, status)
                    if fr is not None:
                        finished.append(fr)

    def _expand_beam_root(self, logits, i, slot, finished):
        """First beam expansion: top-2*width (logprob, token) candidates
        from the prompt's last-position logits seed the beam."""
        group = self.sched.running[slot].group
        with self._phase("engine.prefill.dispatch"):
            vals, idx = self._topk(logits[i:i + 1], 2 * group.width)
        with self._phase("engine.prefill.sync"):
            cands = list(zip(np.asarray(idx)[0].tolist(),
                             np.asarray(vals)[0].tolist()))
        with self._phase("engine.prefill.commit"):
            before_tok = self.sched.tokens_emitted
            before_forks = self.sched.forks
            fr = self.sched.fan_out_beam(slot, cands)
            self.stats["generated_tokens"] += \
                self.sched.tokens_emitted - before_tok
            self.stats["forks"] += self.sched.forks - before_forks
            if fr is not None:
                finished.append(fr)
            else:
                self._reset_beam_slots(group)

    def _set_branch_sampling(self, slot: int, sp, branch: int) -> None:
        """Branch ``branch`` samples under ``branch_seed(seed, branch)``
        - otherwise the request's own sampling params."""
        self._temp[slot] = sp.temperature
        self._top_k[slot] = sp.top_k
        self._top_p[slot] = sp.top_p
        self._rep_pen[slot] = sp.repetition_penalty
        self._seed[slot] = sampler.branch_seed(sp.seed, branch)

    def _reset_beam_slots(self, group) -> None:
        """Pin every live beam slot's sampling vectors to greedy: a
        reorder forks branches into slots whose vectors may still hold
        a previous occupant's sampled params, and one stale
        temperature would silently knock the whole batch off the
        greedy (sampling-free) fast path."""
        for slot in group.slots:
            self._set_branch_sampling(slot, sampler.GREEDY, 0)

    def _want_logprobs(self) -> bool:
        """True when any parallel-sampling group is live (branches
        accumulate the chosen-token logprob so completions come back
        scored, and best_of > n can rank on it) or any live request
        asked for per-token logprobs.  Plain serving never pays for the
        extra log_softmax."""
        return any((st.group is not None and not st.group.beam)
                   or st.req.logprobs
                   for st in self.sched.running.values())

    # ------------------------------------------------------------ decode
    def _run_decode(self, finished: list) -> None:
        """One batched verify step: feed each decoding slot its carry
        token plus up to ``spec_k`` prompt-lookup drafts, sample the
        target token at every position on device, and keep the longest
        prefix whose drafts the sampler confirmed.  Rejected columns
        roll the paged KV back to the accepted prefix.  Beam branches
        ride along with a single carry column: their next tokens come
        from the per-group top-2k reorder after the call, never from
        the sampler."""
        with self._phase("engine.plan"):
            plan = self._plan_decode()
        if plan is None:
            return
        steps, toks, dl, cl, width, beam_groups = plan
        with self._phase("engine.cow"):
            self._apply_pending_copies()
        with self._phase("engine.decode.inputs"):
            kw = toks.shape[1]
            step_fn = self._decode if kw == 1 else self._verify
            greedy = self._all_greedy(s.slot for s in steps)
            beam_k = 2 * max((g.width for g in beam_groups.values()),
                             default=0)
            want_lp = self._want_logprobs()
            args = (jnp.asarray(toks),
                    jnp.asarray(self.cache.page_table[:, :width]),
                    jnp.asarray(dl), jnp.asarray(cl),
                    jnp.asarray(self._seed), jnp.asarray(self._temp),
                    jnp.asarray(self._top_k), jnp.asarray(self._top_p),
                    jnp.asarray(self._rep_pen),
                    jnp.asarray(_NO_PRESENCE if greedy else self._presence))
        with self._phase("engine.decode.dispatch"):
            sampled, tkv, tki, lps, self.layers = step_fn(
                self.params, self.layers, *args, greedy, beam_k, want_lp)
        with self._phase("engine.decode.sync"):
            sampled = np.asarray(sampled)
            lps = np.asarray(lps)
        with self._phase("engine.decode.commit"):
            self.stats["decode_steps"] += 1
            self.stats["decode_slot_steps"] += len(steps)
            if kw == 1:
                self.stats["decode_kv_pages"] += sum(
                    self.cache.pages_for(int(dl[s.slot] + cl[s.slot]))
                    for s in steps)
                self.stats["decode_kv_table_pages"] += self.max_batch * width
            self._count_triplets(self.max_batch, kw)
            self._commit_decode(steps, sampled, lps, finished)
            if beam_groups:
                self._reorder_beams(beam_groups, np.asarray(tkv),
                                    np.asarray(tki), finished)

    def _plan_decode(self):
        """This step's decode work: the scheduler's decode steps (at
        this step's draft count), each shrunk to the pages it can write,
        as the (max_batch, k + 1) token matrix, the per-slot seq and
        chunk lengths, the page-table width, and the live beam groups;
        None when no slot decodes."""
        k = self.spec_k
        if self.auto_spec:
            # Draft-count auto-tune: spend draft compute proportional to
            # the measured accept rate (floor 1 keeps measuring after a
            # cold start or a workload shift kills the EMA).
            ema = self.stats["accept_rate_ema"]
            k = max(1, min(self.spec_k, round(ema * (self.spec_k + 1))))
        self.stats["spec_k_last"] = k
        steps = self.sched.schedule_decode(k)
        if not steps:
            return None
        toks = np.zeros((self.max_batch, k + 1), np.int32)
        dl = np.zeros((self.max_batch,), np.int32)
        cl = np.zeros((self.max_batch,), np.int32)
        beam_groups: dict[int, object] = {}
        for step in steps:
            slot = step.slot
            st = self.sched.running[slot]
            if st.group is not None and st.group.beam:
                beam_groups.setdefault(id(st.group), st.group)
            sl = int(self.cache.seq_lens[slot])
            c = len(step.tokens)
            if c > 1 and not self.cache.ensure_capacity(slot, sl + c):
                # Pool pressure / per-seq ceiling: shrink the step to
                # the writable pages (the capacity pass guaranteed at
                # least the one-token append).
                c = max(1, min(
                    c, self.cache.writable_token_capacity(slot) - sl))
                del step.tokens[c:]
                del step.drafts[max(0, c - 1):]
            dl[slot] = sl
            cl[slot] = c
            toks[slot, :c] = step.tokens
        width = self._pow2_width(max(
            self.cache.pages_for(int(dl[s.slot] + cl[s.slot]))
            for s in steps))
        return steps, toks, dl, cl, width, beam_groups

    def _commit_decode(self, steps, sampled, lps, finished) -> None:
        """Accept each slot's confirmed prefix of the step's sampled
        tokens, roll back the rest, and publish the written pages."""
        step_drafted = step_accepted = 0
        for step in steps:
            slot = step.slot
            st = self.sched.running[slot]
            c = len(step.tokens)
            t = sampled[slot]
            sl = int(self.cache.seq_lens[slot])
            # KV for all c inputs is on device; commit it, then roll
            # back past the accepted prefix below.  Sharp edge: between
            # this mark_prefilled and the rollback, seq_lens over-counts
            # by the rejected columns - nothing in this window may
            # register pages, and a fork must truncate at the accepted
            # length (see the rollback x refcount contract in
            # repro.serving.paged_cache).
            self.cache.mark_prefilled(slot, sl + c)
            if st.group is not None and st.group.beam:
                # Carry KV committed (c == 1, speculation disabled);
                # the group's reorder below picks the next tokens.
                if self.prefix_caching:
                    self.cache.register_pages(slot, st.tokens())
                continue
            # Accept drafts while they equal the sampled target token at
            # their position - exact (lossless) acceptance: t[j-1] is
            # the token the no-spec loop would have emitted where the
            # step fed draft step.tokens[j].
            a = 1
            while a < c and int(t[a - 1]) == step.tokens[a]:
                a += 1
            self.stats["draft_tokens"] += c - 1
            self.stats["draft_accepted"] += a - 1
            st.drafted += c - 1
            st.accepted += a - 1
            step_drafted += c - 1
            step_accepted += a - 1
            status, used = "running", 0
            for j in range(a):
                tok = int(t[j])
                used += 1
                self.stats["generated_tokens"] += 1
                self.stats["decode_tokens"] += 1
                status = self.sched.record_token(slot, tok)
                st.cum_logprob += float(lps[slot, j])
                if st.req.logprobs:
                    st.token_logprobs.append(float(lps[slot, j]))
                self._presence[slot, tok] = True
                if status != "running":
                    break
            if status != "running":
                fr = self.sched.finish(slot, status)
                if fr is not None:
                    finished.append(fr)
                continue
            if used < c:
                # Paged rollback: decrement seq_len to the accepted
                # prefix and free now-empty tail pages (refcounts
                # respected - a forked sibling only loses this slot's
                # reference).
                self.cache.rollback(slot, sl + used)
                self.stats["rollbacks"] += 1
            if self.prefix_caching:
                self.cache.register_pages(
                    slot, self.sched.running[slot].tokens())
        if step_drafted:
            rate = step_accepted / step_drafted
            ema = self.stats["accept_rate_ema"]
            self.stats["accept_rate_ema"] = rate if ema == 0.0 \
                else 0.8 * ema + 0.2 * rate

    def _reorder_beams(self, beam_groups, tkv, tki, finished) -> None:
        """Each live beam group's next tokens from its top-2*width
        (logprob, token) rows of the step."""
        for group in beam_groups.values():
            if not group.slots:
                continue
            # Each group sees exactly its own top-2*width slice, so its
            # expansion is independent of what other live beam groups
            # made the call compute.
            k = 2 * group.width
            per_slot = {
                s: list(zip(tki[s, 0, :k].tolist(), tkv[s, 0, :k].tolist()))
                for s in group.slots}
            before_tok = self.sched.tokens_emitted
            before_forks = self.sched.forks
            before_stops = self.sched.beam_early_stops
            fr = self.sched.beam_reorder(group, per_slot)
            self.stats["generated_tokens"] += \
                self.sched.tokens_emitted - before_tok
            self.stats["forks"] += self.sched.forks - before_forks
            self.stats["beam_early_stops"] += \
                self.sched.beam_early_stops - before_stops
            self.stats["beam_steps"] += 1
            if fr is not None:
                finished.append(fr)
            else:
                self._reset_beam_slots(group)

    def _pow2_width(self, need: int) -> int:
        """Page-table width covering ``need`` pages, rounded up to a
        power of two so jit sees a handful of shapes.

        This is where paging pays on the compute side too: decode and
        prefill-chunk attention cover only the KV that exists, not the
        max_seq reservation the dense cache burns every step.
        """
        width = 1
        while width < need:
            width *= 2
        return min(width, self.pages_per_seq)

    # --------------------------------------------------------------- run
    def run(self, arrivals: list[tuple[int, Request]],
            max_steps: int | None = None) -> list[FinishedRequest]:
        """Drive to completion. arrivals: [(arrival_step, request)].

        A request whose prompt + budget cannot ever fit a sequence's
        page allowance is rejected (``reason="rejected"``) instead of
        killing the serving loop.
        """
        pending = sorted(arrivals, key=lambda a: a[0])
        finished: list[FinishedRequest] = []
        step = 0
        while pending or self.sched.has_work:
            while pending and pending[0][0] <= step:
                _, req = pending.pop(0)
                try:
                    self.submit(req)
                except InvalidRequestError:
                    raise        # contradictory knobs: client misuse
                except ValueError:
                    # resource rejection (prompt/width over capacity)
                    self.stats["rejected"] += 1
                    finished.append(FinishedRequest(
                        rid=req.rid, prompt=req.prompt, tokens=[],
                        reason="rejected"))
            before = self.stats["generated_tokens"]
            finished.extend(self.step())
            step += 1
            if max_steps is not None and step >= max_steps:
                break
            if (self.stats["generated_tokens"] == before
                    and not self.sched.running and not pending
                    and self.sched.waiting):
                raise RuntimeError(
                    "serving stalled: page pool too small for the "
                    "smallest waiting request")
        return finished
