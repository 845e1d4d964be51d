"""Continuous batching: slot-based scheduler over the decode step.

The static-batch ``ServingEngine`` serves one fixed batch start-to-finish;
real serving workloads trickle in.  This scheduler keeps a fixed number of
SLOTS (the compiled decode batch), admits queued requests into free slots as
they open (per-slot prefill written into the shared cache), decodes all
active slots together, and retires slots on EOS/max-new — vLLM-style
iteration-level scheduling, with ASTRA's sequence-parallel prefill supplying
the time-to-first-token acceleration.

The cache layout is whatever ``serving.cache_backend`` resolves for the
engine's ``cache_mode``.  For the paged layouts the cache is a
block-granular page pool (``serving.kv_cache.PagedKVCache``): admission
additionally blocks until the allocator can cover the request's prompt +
budget (``backend.advance``), prefill writes pages directly (no per-slot
slab copy), and retirement returns the pages.  "paged_vq" stores uint8/16
VQ codes per page — the Appendix-G codes-only cache under per-group block
tables (windowed layers ride the capped "window" table).

With ``prefix_cache=True`` (paged + chunked + all-global attention only)
admission first consults the radix prefix index
(``serving.kv_cache.PrefixIndex``): the longest cached prefix's pages are
shared into the slot's block-table row (refcounted — see ``PageAllocator``),
a partially matching last page forks copy-on-write, and the chunked prefill
plan starts at the first uncached token.  Retirement inserts the prompt's
full pages into the index instead of freeing them; the index LRU-evicts
leaves under allocator pressure.

Admission runs the *chunked prefill pipeline* by default
(``prefill_mode="chunked"``): the prompt walks the bucketed chunk grid
(``serving.steps.plan_chunks`` over ``PREFILL_BUCKETS``) one chunk per
scheduler tick, interleaved with decode — admitting a long prompt never
stalls running decodes, and prefill cost scales with
ceil(len/chunk)*chunk tokens instead of ``max_len`` (Sarathi/DeepSpeed-FastGen
style).  The request owns its slot (and pages) for the whole in-flight
prefill; the decode step sees its block-table rows pointed at scratch until
activation, and the batch-1 chunk cache is merged into the live batched
cache on device when the last chunk lands.  ``prefill_mode="padded"`` keeps
the legacy one-shot full-width prefill (also the fallback under a
seq-sharded mesh or an astra-sim prefill).

All steps are fixed-shape (slot count and max_len are static), so the jitted
steps compile O(1)/O(buckets) times — the admitted slot index and the chunk
start are traced scalars: the prefill merges its batch-1 result into the
engine cache on device, letting the whole cache pytree be donated (in-place
on platforms that alias; no-op on CPU).  Decoding goes through the same
jitted multi-token chunk as ``ServingEngine`` (``repro.serving.steps``):
each ``step()`` advances every active slot by up to ``decode_chunk`` tokens
on device and syncs with the host once, so admission/retirement happen at
chunk boundaries instead of after every token.

**Priority, deadlines and preemption** (the SLA layer):

* ``submit(..., priority=, deadline=)`` — ``priority`` is a class number,
  *lower = more urgent* (default 1, so a ``priority=0`` request outranks
  every default submission); ``deadline`` is an optional per-request TTFT
  SLO in *scheduler steps* (deterministic under replay, unlike wall-clock).
  Admission picks the queued request with the smallest ``(priority,
  deadline, uid)`` — strict priority classes, earliest-deadline-first
  within a class, FIFO within a deadline.  The selected request is
  head-blocking: if its pages aren't grantable (and nothing may be
  preempted for it) admission waits rather than letting smaller requests
  starve it.

* **Preemption** is the release valve for that wait: when the selected
  request has no free slot or can't get pages, the scheduler preempts the
  *lowest-priority* active decode whose class is strictly below the
  candidate's (highest priority number; youngest uid among ties — it has
  done the least work).  Equal-priority decodes are never preempted.

* Under ``preempt_mode="swap"`` (default) the victim's exact cache bytes
  move to a host-side arena (``kv_cache.SwapArena``): its block-table
  rows' pages per pool leaf (``paged_vq`` swaps *code* pages, ~16x smaller
  than fp — the Appendix-G ratio applied to the memory hierarchy), its
  per-slot rows of every dense leaf, its decode cursor, and the per-page
  fp prefill scratch the prefix index would need at retirement.  The
  slot's page references are then dropped through ``backend.release`` —
  refcount-aware, so prefix-shared pages survive via their other owners.
  Re-admission re-grants the same token high-water and scatters the saved
  payload into the fresh pages in one fixed-shape jit
  (``kv_cache.restore_slot``); decode resumes from the saved cursor, so a
  restored request's greedy output is *bitwise identical* to one that was
  never preempted.  ``preempt_mode="recompute"`` drops the cache instead
  and re-admits through the ordinary prefill pipeline over
  ``prompt + output[:-1]`` (the ``CacheBackend.rollback``/prefix-grant
  machinery), resuming from the last emitted token — cheaper in host
  memory, but a prefill-vs-decode numeric path difference means it only
  promises completion, not bitwise parity.  Preemption is refused under a
  sequence-sharded mesh (``backend.preemptible``).

**Spans and stamps.**  Each ``step()`` is a ``jax.profiler.TraceAnnotation``
span ``engine.step`` holding ``engine.admit`` (with ``engine.prefill_chunk``
-- one chunk's dispatch plus the slot merge -- and ``engine.first_token``,
the sampling and its blocking fetch), ``engine.decode_dispatch``,
``engine.sync`` (the ``device_get`` of the chunk's tokens) and
``engine.emit`` (token bookkeeping, retirement, table rebuild).
``engine.submit``, ``engine.preempt`` and ``engine.restore`` are spans of
their own; spans of one request carry its ``uid``.  They record only
while a profiler trace is active.  ``Request.t_submit`` / ``t_admit`` /
``t_first`` / ``t_done`` are always stamped, on ``time.perf_counter()``;
``run_until_drained`` reports TTFT and end-to-end latency in ms from them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.sequence_parallel import LOCAL, MeshContext
from repro.models import transformer as tlm
from repro.models.context import StepCtx
from repro.serving import autotune as serving_autotune
from repro.serving import cache_backend as cbe
from repro.serving import kv_cache as kvc
from repro.serving import steps as serving_steps

DEFAULT_DECODE_CHUNK = 4


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    # SLA knobs: lower priority number = more urgent (default class 1, so
    # priority 0 outranks every default submission); deadline is a TTFT
    # SLO in scheduler steps (None = best-effort), used for EDF ordering
    # within a class and for goodput accounting — missing it never cancels
    priority: int = 1
    deadline: Optional[float] = None
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    submitted_step: int = -1
    first_token_step: int = -1
    done_step: int = -1
    preemptions: int = 0
    # host stamps on time.perf_counter(): submitted, first left the queue
    # (a preempted request keeps its first admission), first token
    # sampled, finished
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None


@dataclasses.dataclass
class _PendingPrefill:
    """An admission in flight under chunked prefill: the request holds its
    slot (pages already granted) while its prompt walks the chunk grid one
    chunk per scheduler tick, so running decodes never stall behind a long
    prompt.  The batch-1 cache carries recurrent state / slab rows across
    ticks; for paged layouts its pool leaves are re-adopted from the live
    cache before each chunk (decode ticks produce fresh pool arrays)."""

    req: Request
    slot: int
    n: int  # length of ``tokens`` (prompt, or prompt + output[:-1])
    tokens: List[int]  # the sequence being prefilled: the prompt for a
    # fresh admission; prompt + already-emitted output minus the resume
    # token for a ``preempt_mode="recompute"`` re-admission
    plan: List  # [(chunk_start, width)] from serving_steps.plan_chunks
    next_chunk: int
    caches: Any
    last_logits: Any  # (1, V) running last-position logits


class ContinuousBatchingEngine:
    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 256, mesh_ctx: MeshContext = LOCAL,
                 astra_mode: str = "off", cache_mode: str = "fp",
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 decode_chunk: Optional[int] = None, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 donate: Optional[bool] = None,
                 prefill_mode: Optional[str] = None,
                 prefill_chunk: Optional[int] = None,
                 use_pallas: bool = False,
                 prefix_cache: Optional[bool] = None,
                 speculative: int = 0,
                 draft=None,
                 preempt_mode: str = "swap"):
        if cfg.arch_type in ("vit",):
            raise ValueError("classification models are not generative")
        seq_sharded = (mesh_ctx.seq_axis is not None
                       and mesh_ctx.mesh is not None)
        self.backend = cbe.get_backend(cache_mode, seq_sharded=seq_sharded)
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.temperature = temperature
        self.top_k = top_k
        if decode_chunk is None:
            decode_chunk = (
                serving_autotune.load_decode_chunk(cfg.name, batch=slots)
                or DEFAULT_DECODE_CHUNK)
        self.decode_chunk = max(int(decode_chunk), 1)
        # use_pallas: Pallas-kernel attention hot loops (see ServingEngine)
        self.use_pallas = bool(use_pallas)
        self.prefill_ctx = StepCtx(cfg=cfg, mesh=mesh_ctx, mode="prefill",
                                   astra_mode=astra_mode,
                                   cache_mode=cache_mode,
                                   use_pallas=self.use_pallas)
        self.decode_ctx = StepCtx(cfg=cfg, mesh=mesh_ctx, mode="decode",
                                  astra_mode=astra_mode,
                                  cache_mode=cache_mode,
                                  use_pallas=self.use_pallas)
        if prefill_mode not in (None, "chunked", "padded"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        # an explicit chunked request the engine cannot honor (astra-sim
        # prefill attends through quantized K/V sim the exact chunk step
        # does not reproduce) raises; unset picks the best supported mode
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
                serving_autotune.load_prefill_chunk(cfg.name, batch=slots)
                or serving_steps.DEFAULT_PREFILL_CHUNK)
        self.prefill_chunk = max(int(prefill_chunk), 1)
        self.prefill_buckets = serving_steps.prefill_buckets(
            self.prefill_chunk)
        # one cache state for the engine's whole life: page allocators +
        # per-group block tables for the paged layouts, a trivial slab
        # handle otherwise (undersized num_pages => admission waits for
        # pages, not slots)
        self.kv = self.backend.make_state(
            cfg, slots=slots, max_len=max_len, ctx=self.decode_ctx,
            page_size=page_size, num_pages=num_pages, dtype=jnp.float32)
        self.caches = self.kv.init_cache()
        self._bt = self.kv.tables()
        self.admission_stalls = 0  # deferral *episodes* (see _note_stall)
        self._stalled_uid: Optional[int] = None
        if preempt_mode not in ("swap", "recompute"):
            raise ValueError(f"unknown preempt_mode {preempt_mode!r} "
                             f"(choose 'swap' or 'recompute')")
        self.preempt_mode = preempt_mode
        self.preemptions = 0  # preemption events (a request may repeat)
        self.preempt_log: List = []  # (step, uid) per event
        self.lengths = self.backend.commit_rows(
            jnp.zeros((slots,), jnp.int32), self.decode_ctx)
        self.cur_token = self.backend.commit_rows(
            jnp.zeros((slots,), jnp.int32), self.decode_ctx)
        self.active: List[Optional[Request]] = [None] * slots
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.step_count = 0
        self.host_syncs = 0
        self._rng = jax.random.PRNGKey(seed)
        # the whole live cache pytree is donated through prefill (the merge
        # happens on device) and through the decode chunk
        prefill_donate = (self.backend.donate_argnums((4,)) if donate is None
                          else ((4,) if donate else ()))
        self._prefill = serving_steps.CountingJit(
            self._prefill_impl, donate_argnums=prefill_donate)
        self._prefill_chunk = serving_steps.make_prefill_chunk(
            self.prefill_ctx, donate=donate)
        # slot-merge for the chunked path: the live cache is donated, the
        # batch-1 prefill result is inserted at the (traced) slot on device
        merge_donate = (self.backend.donate_argnums((0,)) if donate is None
                        else ((0,) if donate else ()))
        self._merge = serving_steps.CountingJit(
            kvc.merge_slot, donate_argnums=merge_donate)
        self._decode_chunk = serving_steps.make_decode_chunk(self.decode_ctx,
                                                             donate=donate)
        # swap-restore for preempted requests: span-shaped payloads and
        # (R, 1, ...) dense rows scatter back at the (traced) slot — one
        # compile covers every restore (kvc.restore_slot)
        restore_donate = (self.backend.donate_argnums((0,)) if donate is None
                          else ((0,) if donate else ()))
        self._restore_jit = serving_steps.CountingJit(
            kvc.restore_slot, donate_argnums=restore_donate)
        # speculative decoding: each tick drafts k tokens per slot by n-gram
        # lookup over the slot's own prompt + output and verifies all k+1
        # positions in one jitted step — variable tokens per slot per tick,
        # committed through the same valid-mask loop as the decode chunk.
        # Paired draft *models* stay with ServingEngine: a second model
        # would need its own slot admission/prefill pipeline here.
        self.spec_k = 0
        self.drafter = None
        self._verify_chunk = None
        if speculative:
            self.spec_k = serving_steps.spec_bucket(int(speculative))
            bound = serving_steps.max_spec_width(cfg, max_len)
            if bound is not None and self.spec_k + 1 > bound:
                raise ValueError(
                    f"speculative width {self.spec_k + 1} exceeds the "
                    f"smallest SWA ring ({bound} slots) — rollback would "
                    f"lap the ring")
            if draft not in (None, "ngram"):
                raise ValueError(
                    "the continuous scheduler drafts by n-gram lookup only; "
                    "paired draft models ride ServingEngine")
            from repro.serving.drafter import NGramDrafter

            self.drafter = NGramDrafter(self.spec_k)
            self._verify_chunk = serving_steps.make_verify_chunk(
                self.decode_ctx, donate=donate)
        self.spec_rounds = 0
        self.spec_active_rows = 0
        self.spec_tokens = 0
        self._pending: Optional[_PendingPrefill] = None
        self.prefill_chunk_ticks = 0  # chunk dispatches (chunked mode)
        self._uid = 0
        # cross-request prefix caching (paged + chunked + all-global only:
        # a shared page id indexes every layer's pool, so reuse is exact
        # only when each layer's KV is a pure function of the token prefix)
        supported = (self.backend.paged and self.prefill_mode == "chunked"
                     and getattr(self.kv, "prefix_shareable", False))
        if prefix_cache and not supported:
            raise ValueError(
                f"prefix_cache=True needs a paged backend with chunked "
                f"prefill and an all-global-attention model "
                f"(cache_mode={self.backend.name!r}, "
                f"prefill_mode={self.prefill_mode!r}, cfg={cfg.name!r})")
        self.prefix_cache = bool(prefix_cache)
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        if self.prefix_cache:
            self.kv.enable_prefix_cache()
            # copy-on-write page fork: src/dst ride as traced scalars, the
            # live cache is donated like every other cache round-trip
            cow_donate = (self.backend.donate_argnums((0,))
                          if donate is None else ((0,) if donate else ()))
            self._cow = serving_steps.CountingJit(
                kvc.copy_page, donate_argnums=cow_donate)
        # per-slot fp scratch snapshots awaiting retirement-time insertion
        # into the prefix index (paged_vq only)
        self._slot_fp: Dict[int, Any] = {}

    # -- jitted steps --------------------------------------------------------
    def _prefill_impl(self, params, tokens, length, slot, live_caches,
                      block_tables):
        """tokens: (1, max_len) padded prompt -> (last_logits, merged caches).

        Slab modes build a throwaway (1, max_len) cache; paged modes adopt
        the engine's live page pools instead and prefill scatters prompt K/V
        straight into the slot's allocated pages.  Either way the batch-1
        result is merged into the live batched cache *on device* at the
        (traced) ``slot`` — one compile covers every admission, and the
        donated ``live_caches`` buffers are updated in place where the
        platform allows."""
        caches = tlm.init_lm_cache(
            self.cfg, 1, self.max_len, self.prefill_ctx, jnp.float32,
            page_size=self.kv.page_size if self.backend.paged else 0,
            num_pages=(self.kv.num_pages_by_group if self.backend.paged
                       else 0))
        if self.backend.paged:
            caches = kvc.adopt_pools(caches, live_caches)
        logits, _, _, caches = tlm.lm_forward(
            params, {"tokens": tokens}, ctx=self.prefill_ctx, caches=caches,
            lengths=jnp.reshape(length, (1,)), block_tables=block_tables)
        last = jnp.take_along_axis(
            logits, (length - 1)[None, None, None].clip(0), axis=1)[:, 0]
        return last, kvc.merge_slot(live_caches, caches, slot)

    # -- slot management -----------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               eos_id: Optional[int] = None, *, priority: int = 1,
               deadline: Optional[float] = None) -> int:
        """Queue a request.  Invalid requests are rejected HERE, not during
        ``step()``: a bad request discovered mid-drain used to either wedge
        the engine (``can_ever_fit`` raising from the queue head) or
        silently truncate the prompt to ``max_len - max_new_tokens - 1`` —
        admitting a garbage all-zeros chunk once ``max_new_tokens`` got
        within 1 of ``max_len``.  Likewise ``max_new_tokens <= 0`` (a
        request that could never emit would pin its slot forever: the
        budget check ``len(output) >= max_new_tokens`` only runs after a
        token lands) and non-positive/NaN deadlines (NaN compares False
        against every TTFT, silently exempting the request from its own
        SLO and poisoning the EDF sort).

        ``priority``: class number, lower = more urgent (default 1).
        ``deadline``: optional TTFT SLO in scheduler steps; orders
        admission within a class (EDF) and feeds goodput accounting."""
        with TraceAnnotation("engine.submit", uid=self._uid + 1):
            prompt = list(prompt)
            if not prompt:
                raise ValueError("empty prompt")
            max_new_tokens = int(max_new_tokens)
            if max_new_tokens <= 0:
                raise ValueError(
                    f"max_new_tokens must be >= 1, got {max_new_tokens} — "
                    f"the request could never emit and would pin its slot "
                    f"forever")
            if int(priority) < 0:
                raise ValueError(f"priority must be >= 0, got {priority}")
            if deadline is not None:
                deadline = float(deadline)
                if not deadline > 0:  # rejects <= 0 and NaN in one comparison
                    raise ValueError(
                        f"deadline must be a positive number of scheduler "
                        f"steps, got {deadline}")
            if len(prompt) + max_new_tokens > self.max_len:
                raise ValueError(
                    f"prompt length {len(prompt)} + max_new_tokens "
                    f"{max_new_tokens} exceeds max_len={self.max_len}")
            tokens_needed = len(prompt) + max_new_tokens
            if not self.kv.can_ever_fit(tokens_needed):
                raise ValueError(
                    f"request needs pages for {tokens_needed} tokens but "
                    f"the pool can never hold them")
            self._uid += 1
            self.queue.append(Request(self._uid, prompt, max_new_tokens,
                                      eos_id, priority=int(priority),
                                      deadline=deadline,
                                      submitted_step=self.step_count,
                                      t_submit=time.perf_counter()))
            return self._uid

    def _slot_tables(self, slot: int):
        if self._bt is None:
            return None
        return {name: t[slot:slot + 1] for name, t in self._bt.items()}

    def _resume_seq(self, req: Request) -> List[int]:
        """The token sequence a (re-)admission must prefill: the prompt for
        a fresh request; prompt + emitted output minus the resume token for
        a ``preempt_mode="recompute"`` re-admission (the last emitted token
        becomes ``cur_token`` and is fed back to decode, not prefilled)."""
        return req.prompt + req.output[:-1] if req.output else req.prompt

    def _select_index(self) -> int:
        """Index of the next admission candidate: strict priority classes
        (lower number first), earliest deadline within a class, FIFO (uid)
        within a deadline.  Deadline-less requests sort after any deadline
        in their class."""
        return min(range(len(self.queue)), key=lambda i: (
            self.queue[i].priority,
            self.queue[i].deadline if self.queue[i].deadline is not None
            else float("inf"),
            self.queue[i].uid))

    def _note_stall(self, req: Request) -> None:
        """Count one admission-stall *episode*: the same request deferred
        again on consecutive ticks is one stall, not one per tick (the
        counter is a how-often-did-pressure-bite signal, monotone but not
        tick-inflated).  Cleared when the stalled request admits."""
        if self._stalled_uid != req.uid:
            self.admission_stalls += 1
            self._stalled_uid = req.uid

    def _pick_victim(self, req: Request) -> Optional[int]:
        """Slot of the active decode to preempt for ``req``: the one whose
        priority class is strictly below ``req``'s (largest priority
        number), youngest uid among ties — it has done the least work.
        None when nothing is preemptible: no strictly-lower-priority
        active decode, or a sequence-sharded layout
        (``backend.preemptible``)."""
        if not self.backend.preemptible:
            return None
        best = None
        for slot, r in enumerate(self.active):
            if r is None or r.priority <= req.priority:
                continue
            if best is None or (r.priority, r.uid) > \
                    (self.active[best].priority, self.active[best].uid):
                best = slot
        return best

    def preempt(self, slot: int) -> Request:
        """Preempt the active decode in ``slot`` and requeue it.

        ``preempt_mode="swap"``: snapshot the exact bytes the slot owns
        (pages per pool leaf — code pages under ``paged_vq`` —, dense rows,
        decode cursor, pending fp prefill-scratch snapshots) into the host
        arena, keyed by uid; re-admission restores them bitwise
        (``_restore``).  ``"recompute"``: drop the cache and re-prefill at
        re-admission (``_resume_seq``).  Either way the slot's page
        references are released refcount-aware — pages the prefix index or
        another slot still co-owns survive — and the slot's block-table
        rows point back at scratch."""
        req = self.active[slot]
        if req is None:
            raise ValueError(f"slot {slot} has no active request")
        with TraceAnnotation("engine.preempt", uid=req.uid):
            if self.preempt_mode == "swap":
                entry = self.backend.swap_out(self.kv, slot, self.caches)
                entry.uid = req.uid
                ln, ct = jax.device_get((self.lengths[slot],
                                         self.cur_token[slot]))
                self.host_syncs += 1
                entry.length = int(ln)
                entry.cur_token = int(ct)
                entry.fp_pages = self._slot_fp.pop(slot, None)
                self.kv.arena.stash(entry)
            else:
                self._slot_fp.pop(slot, None)
            self.active[slot] = None
            self.backend.release(self.kv, slot)
            self._bt = self.kv.tables()
        req.preemptions += 1
        self.preemptions += 1
        self.preempt_log.append((self.step_count, req.uid))
        self.queue.append(req)
        return req

    def _restore(self, req: Request, slot: int) -> bool:
        """Re-admit a swapped-out request into ``slot``: re-grant its token
        high-water (preempting lower-priority decodes under pressure, like
        any admission), scatter the arena payload into the fresh page ids
        and merge the dense rows back in one fixed-shape jit, then resume
        decode from the saved cursor — no prefill, no resampling, so the
        greedy continuation is bitwise what the victim would have emitted.
        False (arena entry kept) when pages stay unavailable."""
        entry = self.kv.arena.peek(req.uid)
        while not self.backend.advance(self.kv, slot, entry.granted):
            victim = self._pick_victim(req)
            if victim is None:
                self._note_stall(req)
                return False
            self.preempt(victim)
        with TraceAnnotation("engine.restore", uid=req.uid):
            entry = self.kv.arena.pop(req.uid)
            self._bt = self.kv.tables()
            dests = self.backend.swap_dests(self.kv, slot, entry)
            self.caches = self._restore_jit(
                self.caches, entry.pages, dests, entry.dense,
                jnp.asarray(slot, jnp.int32))
            if entry.fp_pages is not None:
                self._slot_fp[slot] = entry.fp_pages
            self.active[slot] = req
            self.lengths = self.lengths.at[slot].set(entry.length)
            self.cur_token = self.cur_token.at[slot].set(entry.cur_token)
        if self._stalled_uid == req.uid:
            self._stalled_uid = None
        return True

    def _grant_slot(self, slot: int, req: Request):
        """Page-grant ``req`` into ``slot``; returns
        ``(seq_len, reuse_tokens, fp_pages)``, or None on allocator
        pressure (slot untouched; the prefix index may have LRU-evicted —
        callers route pressure through ``_grant_or_preempt``, which counts
        the stall episode and may preempt instead).  ``submit`` already
        validated the request, so the full prompt is admitted — no
        truncation, no mid-drain raise.  With the prefix cache on, the
        grant routes through ``kv.prefix_grant``: shared pages attach to
        the slot's block-table row first, a partial-page match forks
        copy-on-write, and only the remainder allocates.  A recompute
        re-admission grants (and prefix-matches) over ``_resume_seq`` —
        same total footprint, the emitted output rides along."""
        seq = self._resume_seq(req)
        n = len(seq)
        # admission blocks on allocator pressure, not slot count: the
        # request needs pages for its prompt + full budget (slab
        # backends always have room — advance is a bound check there).
        tokens_needed = min(len(req.prompt) + req.max_new_tokens,
                            self.max_len)
        if self.prefix_cache:
            granted = self.kv.prefix_grant(slot, seq, tokens_needed)
            if granted is None:
                return None  # wait for a retirement to free pages
            reuse, cow, fp_pages = granted
            if cow is not None:
                src, dst = cow
                self.caches = self._cow(self.caches,
                                        jnp.asarray(src, jnp.int32),
                                        jnp.asarray(dst, jnp.int32))
            if reuse:
                self.prefix_hits += 1
                self.prefix_hit_tokens += reuse
        else:
            if not self.backend.advance(self.kv, slot, tokens_needed):
                return None  # wait for a retirement to free pages
            reuse, fp_pages = 0, None
        self._bt = self.kv.tables()
        return n, reuse, fp_pages

    def _grant_or_preempt(self, slot: int, req: Request):
        """``_grant_slot`` with the preemption release valve: on allocator
        pressure, evict the lowest-priority active decode strictly below
        ``req``'s class and retry; once no victim remains, count one stall
        episode and defer."""
        while True:
            granted = self._grant_slot(slot, req)
            if granted is not None:
                if self._stalled_uid == req.uid:
                    self._stalled_uid = None
                return granted
            victim = self._pick_victim(req)
            if victim is None:
                self._note_stall(req)
                return None
            self.preempt(victim)

    def _finish_admission(self, req: Request, slot: int, n: int,
                          last_logits) -> None:
        """Sample the prefill continuation and activate the slot.  A
        recompute re-admission (non-empty ``req.output``) resumes from its
        already-emitted last token instead of sampling a fresh one — the
        prefill covered ``_resume_seq``, and decode picks up exactly where
        the victim stopped."""
        resumed = bool(req.output)
        if resumed:
            tok = req.output[-1]
        else:
            with TraceAnnotation("engine.first_token", uid=req.uid):
                self._rng, sub = jax.random.split(self._rng)
                eos_arr = serving_steps.as_eos_array(req.eos_id, 1)
                first, _ = serving_steps.first_token(
                    sub, last_logits, eos_arr, temperature=self.temperature,
                    top_k=self.top_k)
                tok = int(first[0])
            req.t_first = time.perf_counter()
            self.host_syncs += 1
            req.output.append(tok)
            req.first_token_step = self.step_count
        self.active[slot] = req
        self.lengths = self.lengths.at[slot].set(n)
        self.cur_token = self.cur_token.at[slot].set(tok)
        if not resumed:
            self._maybe_finish(slot, tok)

    def _leave_queue(self, req: Request) -> None:
        """Take an admitted (or restored) request off the queue, stamping
        its first admission."""
        self.queue.remove(req)
        if req.t_admit is None:
            req.t_admit = time.perf_counter()

    def _admit(self) -> None:
        if self.prefill_mode == "padded":
            self._admit_padded()
            return
        self._start_pending()
        self._advance_pending()

    def _free_slot_for(self, req: Request) -> Optional[int]:
        """A slot for ``req``: the first free one, else the slot freed by
        preempting a strictly-lower-priority decode (None when neither
        exists)."""
        slot = next((s for s in range(self.slots)
                     if self.active[s] is None), None)
        if slot is not None:
            return slot
        victim = self._pick_victim(req)
        if victim is None:
            return None
        self.preempt(victim)
        return victim

    def _admit_padded(self) -> None:
        """Legacy one-shot admission: the whole (max_len-padded) prompt
        prefills in a single jitted step, stalling this tick's decode.
        Candidates come in priority/EDF order; the selected request is
        head-blocking (pressure it can't preempt away defers admission
        entirely)."""
        while self.queue:
            req = self.queue[self._select_index()]
            slot = self._free_slot_for(req)
            if slot is None:
                return
            if self.preempt_mode == "swap" and self.kv.arena.holds(req.uid):
                if not self._restore(req, slot):
                    return
                self._leave_queue(req)
                continue
            granted = self._grant_or_preempt(slot, req)
            if granted is None:
                return
            n, _, _ = granted  # padded mode never prefix-caches
            self._leave_queue(req)
            seq = self._resume_seq(req)
            toks = np.zeros((1, self.max_len), np.int32)
            toks[0, :n] = seq[:n]
            with TraceAnnotation("engine.prefill_chunk", uid=req.uid):
                last_logits, self.caches = self._prefill(
                    self.params, jnp.asarray(toks),
                    jnp.asarray(n, jnp.int32), jnp.asarray(slot, jnp.int32),
                    self.caches, self._slot_tables(slot))
            self._finish_admission(req, slot, n, last_logits)

    def _start_pending(self) -> None:
        """Begin a chunked admission when a slot (and its pages) are free —
        preempting a lower-priority decode for either when the candidate
        outranks one (see ``_pick_victim``).  A swapped-out candidate
        restores in place of prefilling.  One admission is in flight at a
        time; its request already owns its pages, so a retirement can't
        steal them mid-prefill."""
        if self._pending is not None or not self.queue:
            return
        req = self.queue[self._select_index()]
        slot = self._free_slot_for(req)
        if slot is None:
            return
        if self.preempt_mode == "swap" and self.kv.arena.holds(req.uid):
            if self._restore(req, slot):
                self._leave_queue(req)
            return
        granted = self._grant_or_preempt(slot, req)
        if granted is None:
            return
        n, reuse, fp_pages = granted
        self._leave_queue(req)
        seq = self._resume_seq(req)
        caches = self.kv.init_cache(1, prefill_scratch=True)
        if self.backend.paged:
            caches = kvc.adopt_pools(caches, self.caches)
        if reuse and self.backend.vq_codes:
            # re-seed the fp prefill-view scratch with the prefix nodes'
            # exact snapshots: the tail chunks attend against the original
            # values, keeping reuse bitwise identical to a cold prefill
            caches = kvc.hydrate_prefill_scratch(
                caches, fp_pages, reuse, self.kv.page_size)
        self._pending = _PendingPrefill(
            req=req, slot=slot, n=n, tokens=seq,
            plan=serving_steps.plan_chunks(n, self.prefill_buckets,
                                           start=reuse),
            next_chunk=0, caches=caches,
            last_logits=self.backend.commit_rows(
                jnp.zeros((1, self.cfg.vocab_size), jnp.float32),
                self.prefill_ctx))

    def _advance_pending(self) -> None:
        """Run at most ONE prefill chunk — the scheduler's
        prefill/decode interleave: a long prompt admits over several ticks
        while every active slot keeps decoding."""
        pend = self._pending
        if pend is None:
            return
        with TraceAnnotation("engine.prefill_chunk", uid=pend.req.uid):
            landed = self._run_chunk(pend)
        if landed:
            self._pending = None
            self._finish_admission(pend.req, pend.slot, pend.n,
                                   pend.last_logits)

    def _run_chunk(self, pend: _PendingPrefill) -> bool:
        """Dispatch ``pend``'s next chunk; after the last one, merge the
        batch-1 cache into the live one.  True once the prompt has landed."""
        if self.backend.paged:
            # decode ticks between chunks produced fresh pool arrays
            pend.caches = kvc.adopt_pools(pend.caches, self.caches)
        s0, w = pend.plan[pend.next_chunk]
        chunk = np.zeros((1, w), np.int32)
        seg = pend.tokens[s0:min(s0 + w, pend.n)]
        chunk[0, :len(seg)] = seg
        pend.last_logits, pend.caches = self._prefill_chunk(
            self.params, jnp.asarray(chunk), jnp.asarray(s0, jnp.int32),
            pend.caches, jnp.asarray([pend.n], jnp.int32),
            pend.last_logits, self._slot_tables(pend.slot),
            history_len=serving_steps.view_bucket(s0 + w, self.max_len))
        self.prefill_chunk_ticks += 1
        pend.next_chunk += 1
        if self.backend.paged:
            self.caches = kvc.adopt_pools(self.caches, pend.caches)
        if pend.next_chunk < len(pend.plan):
            return False
        if self.prefix_cache and self.backend.vq_codes:
            # capture the exact fp scratch per prompt page before it is
            # stripped — retirement hands these to the prefix index
            self._slot_fp[pend.slot] = kvc.snapshot_prefill_scratch(
                pend.caches, pend.n, self.kv.page_size)
        fresh = cbe.strip_prefill_scratch(pend.caches)
        if self.backend.paged:
            # the pool leaves inside ``fresh`` are the very arrays
            # ``self.caches`` holds (adopted above): donating self.caches
            # into the merge while fresh still referenced them would hand
            # XLA the same buffer as both donated and non-donated input.
            # The live pools already carry every prefill write, so the
            # merge only needs the dense (batched) leaves.
            fresh = kvc.strip_pool_leaves(fresh)
        self.caches = self._merge(self.caches, fresh,
                                  jnp.asarray(pend.slot, jnp.int32))
        return True

    def _maybe_finish(self, slot: int, tok: int) -> bool:
        req = self.active[slot]
        if req is None:
            return False
        if (req.eos_id is not None and tok == req.eos_id) or \
                len(req.output) >= req.max_new_tokens:
            req.done_step = self.step_count
            req.t_done = time.perf_counter()
            self.finished.append(req)
            self.active[slot] = None
            if self.prefix_cache:
                # the prompt's full pages move into the prefix index (each
                # node takes its own reference) instead of dying with the
                # slot; release below only drops the slot's references.
                self.kv.prefix_insert(slot, req.prompt,
                                      self._slot_fp.pop(slot, None))
            # the request's remaining page references go back to the free
            # lists; the slot's table rows point at scratch so the
            # fixed-shape decode step keeps writing harmlessly until
            # re-admission (no-op for slab backends).
            self.backend.release(self.kv, slot)
            self._bt = self.kv.tables()
            return True
        return False

    # -- main loop -----------------------------------------------------------
    def step(self) -> int:
        """One scheduler iteration: admit + one on-device decode chunk (up
        to ``decode_chunk`` tokens) for all active slots.  Returns the
        number of tokens emitted this iteration."""
        with TraceAnnotation("engine.step"):
            with TraceAnnotation("engine.admit"):
                self._admit()
            if all(r is None for r in self.active):
                self.step_count += 1
                return 0
            with TraceAnnotation("engine.decode_dispatch"):
                width, toks_d, valid_d = self._dispatch_decode()
            with TraceAnnotation("engine.sync"):
                toks_h, valid_h = jax.device_get((toks_d, valid_d))
            self.host_syncs += 1
            self.step_count += 1
            with TraceAnnotation("engine.emit"):
                return self._emit(width, toks_h, valid_h)

    def _dispatch_decode(self):
        """Build the per-row inputs and dispatch one decode (or verify)
        chunk; returns ``(width, tokens, valid)`` still on device."""
        remaining = jnp.asarray(
            [(r.max_new_tokens - len(r.output)) if r is not None else 0
             for r in self.active], jnp.int32)
        eos_ids = jnp.asarray(
            [r.eos_id if r is not None and r.eos_id is not None else -1
             for r in self.active], jnp.int32)
        done = jnp.asarray([r is None for r in self.active])
        self._rng, sub = jax.random.split(self._rng)
        bt = self._bt
        if bt is not None and self._pending is not None:
            # a mid-prefill slot already owns pages the decode step must not
            # scribble on (inactive rows re-feed their last token and write
            # it at their stale position): point its rows at scratch until
            # the admission completes.
            bt = {name: t.at[self._pending.slot].set(0)
                  for name, t in bt.items()}
        if self.spec_k:
            # inactive slots get a dummy history (their verify row accepts
            # nothing anyway — done masks every position)
            draft_toks = jnp.asarray(self.drafter.propose_batch(
                [(r.prompt + r.output) if r is not None else [0]
                 for r in self.active]))
            width = self.spec_k + 1
            toks_d, valid_d, cur, self.caches, self.lengths, _, _ = \
                self._verify_chunk(self.params, self.cur_token, draft_toks,
                                   self.caches, self.lengths, remaining,
                                   eos_ids, done, sub, bt,
                                   num_drafted=self.spec_k,
                                   temperature=self.temperature,
                                   top_k=self.top_k)
        else:
            width = self.decode_chunk
            toks_d, valid_d, cur, self.caches, self.lengths, _, _ = \
                self._decode_chunk(self.params, self.cur_token, self.caches,
                                   self.lengths, remaining, eos_ids, done,
                                   sub, bt, num_steps=self.decode_chunk,
                                   temperature=self.temperature,
                                   top_k=self.top_k)
        self.cur_token = cur
        return width, toks_d, valid_d

    def _emit(self, width: int, toks_h, valid_h) -> int:
        """Append the chunk's valid tokens to their requests and retire the
        finished ones; returns the number of tokens emitted."""
        if self.spec_k:
            self.spec_rounds += 1
            self.spec_active_rows += int(valid_h[:, 0].sum())
            self.spec_tokens += int(valid_h.sum())
        emitted = 0
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            for j in range(width):
                if valid_h[slot, j]:
                    req.output.append(int(toks_h[slot, j]))
                    emitted += 1
            if valid_h[slot].any():
                # only this chunk's tokens can retire the slot; a chunk that
                # emitted nothing must not re-check a stale earlier token
                # against EOS (it was already checked when it was emitted).
                self._maybe_finish(slot, req.output[-1])
        return emitted

    def slo_report(self) -> Dict[str, Any]:
        """Deadline bookkeeping over finished requests: a request meets its
        SLO when its TTFT (in scheduler steps) is within its deadline;
        deadline-less requests always count as met.  ``goodput_tokens`` is
        the DeepSpeed-style goodput-under-SLO numerator — tokens emitted by
        SLO-met requests only."""
        met = goodput = with_deadline = 0
        for r in self.finished:
            ttft = r.first_token_step - r.submitted_step
            with_deadline += r.deadline is not None
            if r.deadline is None or ttft <= r.deadline:
                met += 1
                goodput += len(r.output)
        return {"requests": len(self.finished),
                "with_deadline": with_deadline, "met": met,
                "goodput_tokens": goodput}

    @property
    def idle(self) -> bool:
        """No work left: nothing queued (which covers swapped-out requests
        — preemption requeues them), no prefill in flight, no active
        decode."""
        return (not self.queue and self._pending is None
                and all(r is None for r in self.active))

    def run_until_drained(self, max_steps: int = 10_000) -> Dict[str, Any]:
        t0 = time.perf_counter()
        decoded = 0
        while not self.idle and self.step_count < max_steps:
            decoded += self.step()
        dt = max(time.perf_counter() - t0, 1e-9)
        ttfts = [r.first_token_step - r.submitted_step
                 for r in self.finished]
        ttft_ms = [1e3 * (r.t_first - r.t_submit) for r in self.finished]
        e2e_ms = [1e3 * (r.t_done - r.t_submit) for r in self.finished]
        return {
            "requests": len(self.finished),
            "tokens": sum(len(r.output) for r in self.finished),
            "steps": self.step_count,
            "wall_s": dt,
            "tok_per_s": decoded / dt,
            "mean_ttft_steps": float(np.mean(ttfts)) if ttfts else 0.0,
            "p50_ttft_steps": float(np.percentile(ttfts, 50)) if ttfts
            else 0.0,
            "p99_ttft_steps": float(np.percentile(ttfts, 99)) if ttfts
            else 0.0,
            "ttft_ms_p50": float(np.percentile(ttft_ms, 50)) if ttft_ms
            else 0.0,
            "ttft_ms_p90": float(np.percentile(ttft_ms, 90)) if ttft_ms
            else 0.0,
            "e2e_ms_p50": float(np.percentile(e2e_ms, 50)) if e2e_ms
            else 0.0,
            "e2e_ms_p90": float(np.percentile(e2e_ms, 90)) if e2e_ms
            else 0.0,
            "admission_stalls": self.admission_stalls,
            "preemptions": self.preemptions,
            "preempted_requests": len({u for _, u in self.preempt_log}),
            "swap": self.kv.arena.stats(),
            "slo": self.slo_report(),
            "prefill_chunk_ticks": self.prefill_chunk_ticks,
            "spec_rounds": self.spec_rounds,
            "spec_tokens": self.spec_tokens,
            "spec_tokens_per_round": (self.spec_tokens
                                      / max(self.spec_active_rows, 1)
                                      if self.spec_k else None),
            "pages_in_use": self.kv.pages_in_use,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_index": (self.kv.prefix.stats()
                             if self.prefix_cache else None),
        }
