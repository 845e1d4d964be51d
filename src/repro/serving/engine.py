"""Batched serving engine: sequence-parallel prefill (ASTRA) + cached decode.

The paper's serving story (§3.1, §5): prefill is distributed across devices
with ASTRA's compressed exchange (time-to-first-token acceleration); decode
is autoregressive.  This engine supports:
  * static-batch generate() with per-request lengths,
  * every ``serving.cache_backend`` layout: fp or vq (Appendix G) slab
    caches, their paged page-pool variants ("paged" / "paged_vq", per-group
    block tables via serving.kv_cache.PagedKVCache), and the seq-sharded
    shard cache when a mesh with a sequence axis is given,
  * two prefill pipelines: "chunked" (default — the bucketed chunk grid of
    ``serving.steps``, prefill cost scales with the prompt and compiles
    O(buckets)) and "padded" (legacy one-shot; also the automatic fallback
    for the seq-sharded shard cache and astra-sim prefill).

Decode runs through the shared jitted multi-token loop in
``repro.serving.steps``: the host dispatches one chunk of ``decode_chunk``
steps at a time and syncs once per chunk (``host_syncs`` counts the
device->host transfers so tests can pin the O(max_new_tokens / chunk)
behaviour).  The chunk size comes from the persisted autotune winner when
one exists (``serving.autotune``); cache buffers are donated into the
jitted steps so updates are in-place on platforms that alias (no-op on
CPU).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.sequence_parallel import LOCAL, MeshContext
from repro.models import transformer as tlm
from repro.models.context import StepCtx
from repro.serving import autotune as serving_autotune
from repro.serving import cache_backend as cbe
from repro.serving import steps as serving_steps

DEFAULT_DECODE_CHUNK = 8


@dataclasses.dataclass
class GenerationResult:
    tokens: List[List[int]]
    prefill_logits: Optional[np.ndarray] = None


class ServingEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_len: int = 512,
        mesh_ctx: MeshContext = LOCAL,
        astra_mode: str = "sim",
        cache_mode: str = "fp",
        cache_dtype=jnp.float32,
        decode_chunk: Optional[int] = None,
        page_size: int = 16,
        donate: Optional[bool] = None,
        prefill_mode: Optional[str] = None,
        prefill_chunk: Optional[int] = None,
        use_pallas: bool = False,
        speculative: int = 0,
        draft=None,
    ):
        """``speculative=k`` (> 0) turns on draft/verify decoding: each round
        drafts k tokens and scores all k+1 positions in one jitted verify
        step (``serving.steps.verify_chunk``), committing the longest
        matching prefix plus the bonus token — greedy emissions stay bitwise
        identical to the sequential decode loop.  k snaps onto
        ``steps.SPEC_K_LADDER`` so the verify step compiles O(ladder).

        ``draft`` picks the proposer: ``None``/``"ngram"`` self-drafts from
        each row's own history (``serving.drafter.NGramDrafter``), or a
        ``(cfg, params)`` pair runs a small same-vocabulary model (see
        ``repro.configs.DRAFT_PAIRS``) for k greedy steps per round on its
        own fp-slab cache — all-global attention only, so rejected drafts
        self-heal without rollback."""
        seq_sharded = (mesh_ctx.seq_axis is not None
                       and mesh_ctx.mesh is not None)
        # resolves the layout (and rejects unknown modes)
        self.backend = cbe.get_backend(cache_mode, seq_sharded=seq_sharded)
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.cache_dtype = cache_dtype
        if decode_chunk is None:
            decode_chunk = (serving_autotune.load_decode_chunk(cfg.name)
                            or DEFAULT_DECODE_CHUNK)
        self.decode_chunk = max(int(decode_chunk), 1)
        self.page_size = page_size
        # use_pallas routes the attention hot loops (decode_attend +
        # chunk_attend, every layout) through the Pallas kernels — compiled
        # on TPU, interpret-mode elsewhere; greedy tokens match the jnp
        # path either way (tests/test_pallas_serving.py)
        self.use_pallas = bool(use_pallas)
        self.prefill_ctx = StepCtx(cfg=cfg, mesh=mesh_ctx, mode="prefill",
                                   astra_mode=astra_mode, cache_mode=cache_mode,
                                   use_pallas=self.use_pallas)
        self.decode_ctx = StepCtx(cfg=cfg, mesh=mesh_ctx, mode="decode",
                                  astra_mode=astra_mode, cache_mode=cache_mode,
                                  use_pallas=self.use_pallas)
        if prefill_mode not in (None, "chunked", "padded"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        # every cache layout chunks (the seq-sharded shard cache scatters
        # shard-locally and merges per-shard partials); only an
        # astra-simulated prefill still needs the one-shot padded path —
        # it attends through quantized K/V sim that the chunk step (exact
        # cached attention) does not reproduce.  An explicit request the
        # engine cannot honor is an error, never a silent downgrade.
        if prefill_mode == "chunked" and self.prefill_ctx.astra_on:
            raise ValueError(
                "prefill_mode='chunked' cannot run under astra simulation: "
                "the simulated prefill attends through quantized K/V that "
                "the exact chunked step does not reproduce; pass "
                "prefill_mode='padded' or leave it unset")
        self.prefill_mode = prefill_mode or (
            "padded" if self.prefill_ctx.astra_on else "chunked")
        if prefill_chunk is None:
            prefill_chunk = (
                serving_autotune.load_prefill_chunk(cfg.name)
                or serving_steps.DEFAULT_PREFILL_CHUNK)
        self.prefill_chunk = max(int(prefill_chunk), 1)
        self.prefill_buckets = serving_steps.prefill_buckets(
            self.prefill_chunk)
        # prefill donates the incoming cache pytree (the paged pools are
        # rewritten in place; slab modes pass None and donation is a no-op)
        prefill_donate = (self.backend.donate_argnums((3,)) if donate is None
                          else ((3,) if donate else ()))
        self._prefill = serving_steps.CountingJit(
            self._prefill_impl, donate_argnums=prefill_donate)
        self._prefill_chunk = serving_steps.make_prefill_chunk(
            self.prefill_ctx, donate=donate)
        self._decode_chunk = serving_steps.make_decode_chunk(self.decode_ctx,
                                                             donate=donate)
        self.spec_k = 0
        self.drafter = None
        self._draft_engine = None
        self._verify_chunk = None
        if speculative:
            self.spec_k = serving_steps.spec_bucket(int(speculative))
            bound = serving_steps.max_spec_width(cfg, max_len)
            if bound is not None and self.spec_k + 1 > bound:
                raise ValueError(
                    f"speculative width {self.spec_k + 1} exceeds the "
                    f"smallest SWA ring ({bound} slots) — rollback would "
                    f"lap the ring")
            self._verify_chunk = serving_steps.make_verify_chunk(
                self.decode_ctx, donate=donate)
            if draft is None or draft == "ngram":
                from repro.serving.drafter import NGramDrafter

                self.drafter = NGramDrafter(self.spec_k)
            else:
                dcfg, dparams = draft
                if dcfg.vocab_size != cfg.vocab_size:
                    raise ValueError(
                        f"draft vocab {dcfg.vocab_size} != target vocab "
                        f"{cfg.vocab_size}; pair models via "
                        f"repro.configs.DRAFT_PAIRS")
                if serving_steps.max_spec_width(dcfg, max_len) is not None:
                    raise ValueError(
                        "draft model must be all-global attention (its "
                        "rejected drafts heal by overwrite; SWA rings "
                        "would need their own rollback)")
                # oversized by k so drafting past the target's last
                # position never clamp-writes over the draft's own history
                self._draft_engine = ServingEngine(
                    dcfg, dparams, max_len=max_len + self.spec_k,
                    mesh_ctx=mesh_ctx, astra_mode="off", cache_mode="fp",
                    cache_dtype=cache_dtype, decode_chunk=self.spec_k + 1,
                    donate=donate, prefill_mode=prefill_mode,
                    use_pallas=use_pallas)
        # speculative telemetry (benchmarks read these): per-generate round
        # count, rows active per round, tokens committed
        self.spec_rounds = 0
        self.spec_active_rows = 0
        self.spec_tokens = 0
        # device->host transfer counter (one increment per blocking fetch)
        self.host_syncs = 0

    # -- steps ---------------------------------------------------------------
    def _prefill_impl(self, params, tokens, lengths, caches, block_tables):
        """caches/block_tables are None for slab modes (the slab is created
        here); paged modes pass the page pools + block tables in and prefill
        scatters prompt K/V into pages directly — no (B, max_len) slab."""
        if caches is None:
            caches = tlm.init_lm_cache(self.cfg, tokens.shape[0], self.max_len,
                                       self.prefill_ctx, self.cache_dtype)
        logits, _, _, caches = tlm.lm_forward(
            params, {"tokens": tokens}, ctx=self.prefill_ctx, caches=caches,
            lengths=lengths, block_tables=block_tables)
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None].clip(0), axis=1)[:, 0]
        return last, caches

    def _run_prefill(self, toks: np.ndarray, lens: np.ndarray,
                     max_new_tokens: int):
        """Prefill every row's cache; returns (last_logits, caches,
        block_tables).

        "chunked" walks the prompts through the bucketed chunk grid — cost
        scales with ceil(len/chunk)*chunk tokens, and the jitted chunk
        compiles once per bucket *width* (chunk_start is traced).  "padded"
        is the legacy one-shot full-width prefill, kept for the seq-sharded
        / astra-sim paths and as the benchmark baseline."""
        b = toks.shape[0]
        block_tables = caches0 = None
        kv = None
        if self.backend.paged:
            # one per-generate cache state: each request gets exactly the
            # pages its prompt + budget needs, all layers share the tables.
            kv = self.backend.make_state(
                self.cfg, slots=b, max_len=self.max_len, ctx=self.decode_ctx,
                page_size=self.page_size, dtype=self.cache_dtype)
            for i in range(b):
                ok = self.backend.advance(
                    kv, i, min(int(lens[i]) + max_new_tokens, self.max_len))
                assert ok, "pool sized for slots*span can't run dry"
            block_tables = kv.tables()
        if self.prefill_mode == "padded":
            if kv is not None:
                caches0 = kv.init_cache(b)
            last_logits, caches = self._prefill(
                self.params, jnp.asarray(toks), jnp.asarray(lens), caches0,
                block_tables)
            return last_logits, caches, block_tables
        if kv is not None:
            caches = kv.init_cache(b, prefill_scratch=True)
        else:
            caches = self.backend.commit_caches(
                tlm.init_lm_cache(self.cfg, b, self.max_len,
                                  self.prefill_ctx, self.cache_dtype,
                                  prefill_scratch=True), self.prefill_ctx)
        rows = functools.partial(self.backend.commit_rows,
                                 ctx=self.prefill_ctx)
        lengths = rows(jnp.asarray(lens))
        last_logits = rows(jnp.zeros((b, self.cfg.vocab_size), jnp.float32))
        for s0, w in serving_steps.plan_chunks(int(lens.max()),
                                               self.prefill_buckets):
            chunk = np.zeros((b, w), np.int32)
            seg = toks[:, s0:s0 + w]
            chunk[:, :seg.shape[1]] = seg
            last_logits, caches = self._prefill_chunk(
                self.params, jnp.asarray(chunk), jnp.asarray(s0, jnp.int32),
                caches, lengths, last_logits, block_tables,
                history_len=serving_steps.view_bucket(s0 + w, self.max_len))
        return last_logits, cbe.strip_prefill_scratch(caches), block_tables

    # -- API -----------------------------------------------------------------
    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        *,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: int = 0,
        eos_id: Optional[int] = None,
        seed: int = 0,
    ) -> GenerationResult:
        b = len(prompts)
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens <= 0:
            # fail fast: the decode loop's budget is max_new_tokens - 1
            # *after* the unconditional first token, so a non-positive
            # budget would still emit one token and then underflow the
            # remaining-counter into a full-max_len decode.
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        lens = np.array([len(p) for p in prompts], np.int32)
        if int(lens.max()) + max_new_tokens > self.max_len:
            # fail fast: the dense slab would silently clamp writes at the
            # last position and the paged path would cycle offsets through
            # its last page — both corrupt the row's own KV history.
            raise ValueError(
                f"prompt length {int(lens.max())} + max_new_tokens "
                f"{max_new_tokens} exceeds max_len={self.max_len}")
        t_pad = int(max(lens.max(), 1))
        toks = np.zeros((b, t_pad), np.int32)
        for i, p in enumerate(prompts):
            toks[i, : len(p)] = p

        last_logits, caches, block_tables = self._run_prefill(
            toks, lens, max_new_tokens)
        rng = jax.random.PRNGKey(seed)
        rng, sub = jax.random.split(rng)
        eos_arr = serving_steps.as_eos_array(eos_id, b)
        cur, done = serving_steps.first_token(sub, last_logits, eos_arr,
                                              temperature=temperature,
                                              top_k=top_k)
        first, done_h, prefill_logits = jax.device_get(
            (cur, done, last_logits))
        self.host_syncs += 1
        out = [[int(first[i])] for i in range(b)]

        rows = functools.partial(self.backend.commit_rows,
                                 ctx=self.decode_ctx)
        lengths = rows(jnp.asarray(lens))
        budget = max_new_tokens - 1
        # num_steps stays pinned to decode_chunk (ONE compiled scan) even for
        # short budgets — the per-row `remaining` mask truncates the tail, so
        # varying max_new_tokens never re-specializes the decode graph.
        chunk = self.decode_chunk
        remaining = rows(jnp.full((b,), budget, jnp.int32))
        emitted = 0
        if self.spec_k:
            k = self.spec_k
            d_caches = d_bt = d_lengths = None
            if self._draft_engine is not None:
                _, d_caches, d_bt = self._draft_engine._run_prefill(
                    toks, lens, max_new_tokens + k)
                d_lengths = jnp.asarray(lens)
            # k+1 draft steps, not k: a full accept advances the target to
            # start + k + 1, and the draft must have written KV for every
            # position below its next start — the k-th draft step covers
            # the bonus-token position (its proposal is discarded).
            d_rem = jnp.full((b,), k + 1, jnp.int32)
            d_eos = jnp.full((b,), -1, jnp.int32)
            d_done = jnp.zeros((b,), bool)
            # rows advance unevenly (1..k+1 per round), so an emitted-count
            # bound would cut slow rows off early; every active row commits
            # at least one token per round, so `done` alone terminates.
            while not done_h.all():
                rng, sub = jax.random.split(rng)
                if self._draft_engine is not None:
                    rng, dsub = jax.random.split(rng)
                    de = self._draft_engine
                    d_toks, _, _, d_caches, _, _, _ = de._decode_chunk(
                        de.params, cur, d_caches, d_lengths, d_rem, d_eos,
                        d_done, dsub, d_bt, num_steps=k + 1,
                        temperature=0.0, top_k=0)
                    draft_toks = d_toks[:, :k]
                else:
                    draft_toks = jnp.asarray(self.drafter.propose_batch(
                        [list(prompts[i]) + out[i] for i in range(b)]))
                toks_d, valid_d, cur, caches, lengths, remaining, done = \
                    self._verify_chunk(self.params, cur, draft_toks, caches,
                                       lengths, remaining, eos_arr, done,
                                       sub, block_tables, num_drafted=k,
                                       temperature=temperature, top_k=top_k)
                if self._draft_engine is not None:
                    # drafted past the accept point is garbage in the draft
                    # cache too — all-global, so resetting its lengths to
                    # the target's retreats and later writes heal in order
                    d_lengths = lengths
                toks_h, valid_h, done_h = jax.device_get(
                    (toks_d, valid_d, done))
                self.host_syncs += 1
                for i in range(b):
                    for j in range(k + 1):
                        if valid_h[i, j]:
                            out[i].append(int(toks_h[i, j]))
                self.spec_rounds += 1
                self.spec_active_rows += int(valid_h[:, 0].sum())
                self.spec_tokens += int(valid_h.sum())
            self.host_syncs += 1  # prefill_logits fetch above
            return GenerationResult(tokens=out,
                                    prefill_logits=np.asarray(prefill_logits))
        while emitted < budget and not done_h.all():
            rng, sub = jax.random.split(rng)
            toks_d, valid_d, cur, caches, lengths, remaining, done = \
                self._decode_chunk(self.params, cur, caches, lengths,
                                   remaining, eos_arr, done, sub,
                                   block_tables, num_steps=chunk,
                                   temperature=temperature, top_k=top_k)
            toks_h, valid_h, done_h = jax.device_get((toks_d, valid_d, done))
            self.host_syncs += 1
            for i in range(b):
                for j in range(chunk):
                    if valid_h[i, j]:
                        out[i].append(int(toks_h[i, j]))
            emitted += chunk
        self.host_syncs += 1  # prefill_logits fetch above rides this budget
        return GenerationResult(tokens=out,
                                prefill_logits=np.asarray(prefill_logits))

    # -- metrics ---------------------------------------------------------
    def prefill_comm_bits_per_device(self, seq_len: int,
                                     num_devices: int) -> float:
        """ASTRA wire bits for one prefill (per device), paper §3.2."""
        from repro.core.comm_model import bits_astra, CommEnv

        env = CommEnv(bandwidth_mbps=1.0, num_devices=num_devices,
                      seq_len=seq_len, d_model=self.cfg.d_model,
                      num_layers=self.cfg.num_layers)
        c = 2 if self.cfg.astra.quantize_mode == "kv" else 1
        return bits_astra(env, self.cfg.astra.groups,
                          self.cfg.astra.codebook_size, c)
