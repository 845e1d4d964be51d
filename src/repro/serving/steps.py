"""Shared jitted serving steps: chunked prefill + the on-device decode loop.

Chunked prefill
---------------
Both engines used to pad every prompt to one full-width buffer and run a
single monolithic prefill — a 64-token prompt under ``max_len=4096`` paid
~4096^2 attention FLOPs.  ``make_prefill_chunk`` builds the jitted
``prefill_chunk`` step instead: a fixed-width chunk (widths drawn from the
small bucket ladder ``PREFILL_BUCKETS`` so the compile count is O(buckets),
not O(distinct prompt lengths)) that attends causally over the cache
written so far, appends through ``ctx.backend.chunk_attend``, and carries
recurrent state (RG-LRU, mamba2 SSD) across chunks via each row's *real*
boundary state — right-padding can no longer fold into any carried state by
construction.  ``plan_chunks`` decomposes a prompt into the bucketed chunk
grid (greedy largest-fit, smallest-covering tail), so prefill cost scales
with ceil(len/chunk)*chunk tokens instead of ``max_len``.  This is the
DeepSpeed-Inference/Sarathi-style chunked-prefill move; the continuous
scheduler additionally interleaves at most one prefill chunk per decode
tick so admitting a long prompt never stalls running decodes.

Both serving engines (static-batch ``ServingEngine`` and the slot-based
``ContinuousBatchingEngine``) used to drive decoding with a host Python loop
— one jitted dispatch, one device->host sync and one host-side EOS check
*per generated token per request*.  This module replaces that with a single
``lax.scan`` over a decode chunk: sampling, EOS detection, per-row length
and token-budget tracking all run on device, and the host syncs once per
chunk (O(max_new_tokens / chunk) transfers instead of O(max_new_tokens)).

This is the iteration-level-scheduling move of DeepSpeed-Inference/vLLM-
style servers: the accelerator stays busy across decode iterations, and the
scheduler (admission, retirement) interposes only at chunk boundaries.

Per-row state is carried as arrays so rows are independent:
  * ``remaining``  — tokens this row may still emit (0 => frozen),
  * ``eos_ids``    — per-row EOS token id, or -1 for "no EOS",
  * ``done``       — row already emitted its EOS (or was never active).
Frozen rows keep re-feeding their last token with ``lengths`` unchanged.
CAUTION: that keeps their *emitted tokens* exact but dirties their slice of
the returned caches — KV writes land on the next unconsumed position, and
recurrent-state layers (SSD / RG-LRU) keep folding the re-fed token into
their position-less hidden state.  Callers must treat a finished row's
cache as dead: both engines do (ServingEngine discards caches after
generate; the scheduler re-prefills a slot on admission).  Any future
continue-from-cache feature needs per-row state freezing first.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import transformer as tlm
from repro.serving.sampler import sample_tokens

# chunk-width ladder for the bucketed prefill: every chunk's width is drawn
# from this set, so the jitted prefill step compiles at most once per bucket
PREFILL_BUCKETS = (32, 128, 512)
DEFAULT_PREFILL_CHUNK = 128


def prefill_buckets(prefill_chunk: int = DEFAULT_PREFILL_CHUNK,
                    ladder=PREFILL_BUCKETS) -> Tuple[int, ...]:
    """The bucket widths the engines may use: ladder entries up to the
    (autotuned) ``prefill_chunk`` cap, never empty."""
    out = tuple(b for b in sorted(set(ladder)) if b <= prefill_chunk)
    return out or (min(ladder),)


VIEW_FLOOR = 128


def view_bucket(chunk_end: int, max_len: int,
                floor: int = VIEW_FLOOR) -> int:
    """Static attention-view length for one prefill chunk: the smallest
    power-of-two ladder value >= ``chunk_end`` (capped at ``max_len``).

    The chunk step attends over only the first ``history_len`` cache
    positions — a 64-token prompt under ``max_len=4096`` scores 64x128
    entries, not 64x4096 — while keeping the view length off the ladder of
    distinct compiled shapes O(log(max_len / floor)), not O(prompt
    lengths)."""
    v = floor
    while v < chunk_end:
        v *= 2
    return min(v, max_len)


def plan_chunks(total_len: int, buckets,
                start: int = 0) -> List[Tuple[int, int]]:
    """Decompose a prompt of ``total_len`` tokens into ``(start, width)``
    chunks with widths drawn from ``buckets``: greedy largest-fit, and a
    smallest-covering bucket for the tail (its padding is masked/dropped by
    the chunk step, so a bucket overhanging ``max_len`` is harmless).

    A nonzero ``start`` begins the plan at the first *uncached* token — the
    prefix-cache tail plan: chunks cover ``[start, total_len)`` only, and
    at least the final token's chunk always runs (``start`` is clamped to
    ``total_len - 1``) so prefill still produces ``last_logits``."""
    buckets = sorted(set(int(b) for b in buckets))
    if not buckets or buckets[0] <= 0:
        raise ValueError(f"invalid prefill buckets {buckets}")
    plan: List[Tuple[int, int]] = []
    total = max(int(total_len), 1)
    start = min(max(int(start), 0), total - 1)
    while start < total:
        rem = total - start
        fit = [b for b in buckets if b <= rem]
        w = max(fit) if fit else min(b for b in buckets if b >= rem)
        plan.append((start, w))
        start += w
    return plan


class CountingJit:
    """``jax.jit`` wrapper that counts retraces.

    The wrapped python function only runs when jit (re)traces, so
    ``trace_count`` exposes compilation behaviour to tests: the serving
    engines assert the decode chunk stays at one trace across a whole
    workload (fixed shapes + static chunk size => compile once), including
    with donated cache buffers and per-layer block tables.

    ``donate_argnums`` is forwarded to ``jax.jit``: donated cache pytrees
    let XLA alias the input and output buffers so the functional cache
    round-trip becomes an in-place update on platforms that support it
    (see ``serving.cache_backend.donation_supported``)."""

    def __init__(self, fn, *, static_argnames=(), donate_argnums=()):
        self.trace_count = 0
        self.donate_argnums = tuple(donate_argnums)

        def counted(*args, **kwargs):
            self.trace_count += 1
            return fn(*args, **kwargs)

        self._jit = jax.jit(counted, static_argnames=static_argnames,
                            donate_argnums=self.donate_argnums)

    def __call__(self, *args, **kwargs):
        return self._jit(*args, **kwargs)

    def lower(self, *args, **kwargs):
        """AOT lowering passthrough — the compiled-artifact auditor
        (``repro.analysis.trace_audit``) lints the optimized HLO of the
        real jitted step without executing it.  Lowering traces, so
        ``trace_count`` still advances."""
        return self._jit.lower(*args, **kwargs)


def make_prefill_chunk(ctx, *, donate: Optional[bool] = None) -> CountingJit:
    """Jitted ``prefill_chunk(params, tokens, chunk_start, caches, lengths,
    last_logits, block_tables)`` specialized to one StepCtx.

    ``chunk_start`` is a *traced* scalar, so walking a prompt through the
    chunk grid never re-specializes the graph — only a new chunk *width*
    (bucket) does, and ``trace_count`` stays O(buckets).  The caches and the
    running ``last_logits`` are donated where the platform aliases (both are
    dead after each call by construction)."""
    if donate is None:
        argnums = ctx.backend.donate_argnums((3, 5))
    else:
        argnums = (3, 5) if donate else ()
    return CountingJit(functools.partial(prefill_chunk, ctx=ctx),
                       static_argnames=("history_len",),
                       donate_argnums=argnums)


def prefill_chunk(params, tokens, chunk_start, caches, lengths, last_logits,
                  block_tables=None, *, ctx, history_len: int = 0):
    """One chunked-prefill step (see ``tlm.lm_prefill_chunk``).
    ``history_len`` (static) bounds the attention view — see
    ``view_bucket``; 0 means the full cache span."""
    return tlm.lm_prefill_chunk(params, tokens, chunk_start, caches,
                                lengths, last_logits, ctx=ctx,
                                block_tables=block_tables,
                                history_len=history_len)


def make_decode_chunk(ctx, *, donate: Optional[bool] = None):
    """Jitted ``decode_chunk`` specialized to one StepCtx — the single
    compiled decode entry point both serving engines share.

    ``donate=None`` (default) donates the caches argument whenever the
    platform can alias donated buffers (no-op on CPU); True/False force it.
    Every call site passes the previous chunk's returned caches, so the
    donated input is always dead by construction.
    """
    if donate is None:
        argnums = ctx.backend.donate_argnums((2,))
    else:
        argnums = (2,) if donate else ()
    return CountingJit(functools.partial(decode_chunk, ctx=ctx),
                       static_argnames=("num_steps", "temperature", "top_k"),
                       donate_argnums=argnums)


def decode_chunk(
    params,
    cur: jax.Array,        # (B,) int32 — last sampled token per row
    caches: List[Dict],
    lengths: jax.Array,    # (B,) int32 — tokens already in the cache
    remaining: jax.Array,  # (B,) int32 — emission budget left per row
    eos_ids: jax.Array,    # (B,) int32 — per-row EOS id, -1 = none
    done: jax.Array,       # (B,) bool — row finished (EOS seen / inactive)
    rng: jax.Array,
    block_tables=None,  # {group: (B, span) int32} for paged modes
    *,
    ctx,                   # StepCtx (decode mode) — closed over via partial
    num_steps: int,
    temperature: float = 0.0,
    top_k: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array, List[Dict], jax.Array,
           jax.Array, jax.Array]:
    """Advance every row by up to ``num_steps`` tokens, entirely on device.

    Returns ``(tokens, valid, cur, caches, lengths, remaining, done)`` where
    ``tokens``/``valid`` are (B, num_steps): ``valid[b, j]`` marks whether
    ``tokens[b, j]`` was actually emitted by row ``b`` (False once the row
    hit EOS, exhausted its budget, or was inactive on entry).  The returned
    ``done`` includes budget exhaustion, so callers can stop polling.

    ``block_tables`` (paged cache modes) is a per-page-group dict of
    fixed-shape tables riding through the whole scan as constants: page
    allocation changes between chunks never re-specialize the compiled
    graph, only the table *values* change.
    """

    def one(carry, step_rng):
        cur, caches, lengths, remaining, done = carry
        logits, caches = tlm.lm_decode_step(params, cur[:, None], caches,
                                            lengths, ctx=ctx,
                                            block_tables=block_tables)
        with jax.named_scope("sample"):
            nxt = sample_tokens(step_rng, logits[:, 0],
                                temperature=temperature, top_k=top_k)
        active = jnp.logical_and(~done, remaining > 0)
        nxt = jnp.where(active, nxt, cur)
        lengths = lengths + active.astype(lengths.dtype)
        remaining = remaining - active.astype(remaining.dtype)
        done = done | (active & (eos_ids >= 0) & (nxt == eos_ids))
        return (nxt, caches, lengths, remaining, done), (nxt, active)

    carry = (cur, caches, lengths, remaining, done)
    (cur, caches, lengths, remaining, done), (toks, valid) = jax.lax.scan(
        one, carry, jax.random.split(rng, num_steps))
    return (toks.T, valid.T, cur, caches, lengths, remaining,
            done | (remaining <= 0))


# draft-length ladder for speculative decoding: engines snap a requested k
# up to the nearest rung, so the jitted verify step compiles at most once
# per rung (CountingJit-asserted) instead of once per distinct k
SPEC_K_LADDER = (2, 4, 8)


def spec_bucket(k: int, ladder=SPEC_K_LADDER) -> int:
    """Snap a requested draft length ``k`` onto the compile ladder: the
    smallest rung >= k, or the largest rung when k overshoots.  The verify
    width (k+1) is a static jit argument, so an un-laddered k would compile
    a fresh program per value."""
    if k <= 0:
        raise ValueError(f"speculative draft length must be positive, got {k}")
    for b in sorted(ladder):
        if b >= k:
            return b
    return max(ladder)


def max_spec_width(cfg, max_len: int) -> Optional[int]:
    """Largest verify width W = k+1 the cache layouts support, or None when
    unbounded (no windowed layers).  SWA ring rollback restores clobbered
    slots from the pre-verify ring, which only works while one verify step
    cannot lap the ring: W <= ring slots = min(window, max_len).  Raises for
    recurrent/SSM stacks — their per-token state folds are irreversible, so
    no rollback (and no speculative decoding) is possible."""
    bound: Optional[int] = None
    for kinds, _ in tlm.stages(cfg):
        for kind in kinds:
            if kind not in tlm.ATTN_KINDS:
                raise ValueError(
                    f"speculative decoding needs attention-only stacks; "
                    f"{cfg.name!r} has irreversible {kind!r} layers")
            w = attn.kind_window(kind, cfg)
            if w:
                s = min(w, max_len)
                bound = s if bound is None else min(bound, s)
    return bound


def make_verify_chunk(ctx, *, donate: Optional[bool] = None) -> CountingJit:
    """Jitted ``verify_chunk`` specialized to one StepCtx — the speculative
    counterpart of ``make_decode_chunk``.

    ``num_drafted`` (and the sampling knobs) are static: engines draw k from
    ``SPEC_K_LADDER`` via ``spec_bucket`` so the compile count stays
    O(ladder).  The caches are donated where the platform aliases; the
    pre-verify ring snapshot the rollback needs is read inside the same jit,
    which XLA resolves with copy-insertion, so donation stays safe."""
    if donate is None:
        argnums = ctx.backend.donate_argnums((3,))
    else:
        argnums = (3,) if donate else ()
    return CountingJit(functools.partial(verify_chunk, ctx=ctx),
                       static_argnames=("num_drafted", "temperature",
                                        "top_k"),
                       donate_argnums=argnums)


def verify_chunk(
    params,
    cur: jax.Array,        # (B,) int32 — last sampled token per row
    draft: jax.Array,      # (B, k) int32 — drafted continuations
    caches: List[Dict],
    lengths: jax.Array,    # (B,) int32 — tokens already in the cache
    remaining: jax.Array,  # (B,) int32 — emission budget left per row
    eos_ids: jax.Array,    # (B,) int32 — per-row EOS id, -1 = none
    done: jax.Array,       # (B,) bool — row finished (EOS seen / inactive)
    rng: jax.Array,
    block_tables=None,
    *,
    ctx,                   # StepCtx (decode mode) — closed over via partial
    num_drafted: int,
    temperature: float = 0.0,
    top_k: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array, List[Dict], jax.Array,
           jax.Array, jax.Array]:
    """One speculative draft/verify step: advance every row by 1..k+1 tokens
    for the price of a single target forward.

    The target scores all W = k+1 positions ``[cur, draft]`` in one
    chunk-shaped pass (``tlm.lm_verify_chunk``), then an unrolled W-step
    acceptance loop replays exactly the masks of ``decode_chunk``'s scan
    body: position j's target token is emitted only while the row is still
    *reachable* — every earlier target token matched its drafted proposal —
    and still active (not done, budget left).  The first mismatching
    position still emits the target's token (the standard bonus token), so
    a row always advances by at least one token while active, and a full
    match advances by k+1.  Greedy emissions are bitwise identical to the
    sequential decode loop for *any* proposals — wrong drafts cost only
    wasted compute, never wrong tokens.

    Cache writes for rejected positions are healed before returning:
    global layers mask stale keys past the retreated length by validity,
    SWA rings are restored from the pre-verify snapshot
    (``tlm.lm_rollback_caches``).  Returns the same
    ``(tokens, valid, cur, caches, lengths, remaining, done)`` tuple as
    ``decode_chunk`` with W-wide token/valid planes, so engine commit loops
    are shared between the two paths.
    """
    w = num_drafted + 1
    tokens_in = jnp.concatenate([cur[:, None], draft.astype(cur.dtype)],
                                axis=1)
    starts = lengths
    old_caches = caches
    logits, caches = tlm.lm_verify_chunk(params, tokens_in, caches, lengths,
                                         ctx=ctx, block_tables=block_tables)
    step_rngs = jax.random.split(rng, w)
    toks, valids = [], []
    reach = jnp.ones_like(done)
    for j in range(w):
        with jax.named_scope("sample"):
            t_j = sample_tokens(step_rngs[j], logits[:, j],
                                temperature=temperature, top_k=top_k)
        active = reach & ~done & (remaining > 0)
        nxt = jnp.where(active, t_j, cur)
        lengths = lengths + active.astype(lengths.dtype)
        remaining = remaining - active.astype(remaining.dtype)
        done = done | (active & (eos_ids >= 0) & (nxt == eos_ids))
        toks.append(nxt)
        valids.append(active)
        cur = nxt
        if j < num_drafted:
            reach = reach & active & (t_j == draft[:, j])
    accepted = lengths - starts
    caches = tlm.lm_rollback_caches(caches, old_caches, starts, accepted, w,
                                    ctx=ctx, block_tables=block_tables)
    return (jnp.stack(toks, axis=1), jnp.stack(valids, axis=1), cur, caches,
            lengths, remaining, done | (remaining <= 0))


def first_token(rng: jax.Array, last_logits: jax.Array, eos_ids: jax.Array,
                *, temperature: float = 0.0,
                top_k: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Sample the prefill continuation and check it against EOS on device.

    The first sampled token goes through exactly the same EOS gate as every
    scan step above — the historical "first token never checked against
    eos_id" bug is impossible by construction.
    """
    with jax.named_scope("sample"):
        cur = sample_tokens(rng, last_logits, temperature=temperature,
                            top_k=top_k)
        return cur, (eos_ids >= 0) & (cur == eos_ids)


def as_eos_array(eos_id, batch: int) -> jax.Array:
    """Normalize an Optional[int] (or per-row list) EOS id to a (B,) array."""
    if eos_id is None:
        return jnp.full((batch,), -1, jnp.int32)
    arr = jnp.asarray(eos_id, jnp.int32)
    if arr.ndim == 0:
        arr = jnp.full((batch,), int(eos_id), jnp.int32)
    return arr
