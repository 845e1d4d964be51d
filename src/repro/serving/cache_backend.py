"""CacheBackend: one interface in front of every KV-cache layout.

The four cache modes (fp / vq slabs, paged / paged_vq page pools) plus the
sequence-sharded shard cache used to be string-dispatched at five call
sites (attention init/prefill/decode, both engines, the scheduler, the
launcher).  This module is now the single owner of that dispatch: a
``CacheBackend`` implements

  * ``init_cache``     — per-layer cache pytree for one attention kind,
  * ``prefill_write``  — write prompt K/V into that cache (traced),
  * ``decode_attend``  — one decode step: write the new token, attend over
                         the cached history, return (y, new_cache) (traced),
  * ``make_state``     — host-side engine handle (page allocator + block
                         tables for paged layouts, a trivial slab handle
                         otherwise),
  * ``advance``        — host-side capacity bookkeeping between chunks
                         (page-grant growth; no-op for slabs),
  * ``bytes_report``   — analytic memory accounting for this layout,
  * ``donate_argnums`` — which jitted-step arguments may be donated so the
                         compiled update is in-place (vLLM/TensorRT-LLM
                         style); filtered to () on platforms where XLA
                         cannot alias (CPU) so donation stays a no-op there.

The paged layouts name their page pools in ``resident_keys``: the layer
scan then carries each pool whole, every layer's pages merged into one
array, and the traced methods write and gather one layer's pages of it in
place through ``page_base`` (``transformer.run_stages``).

Everything outside this file talks to ``ctx.backend`` (resolved from
``StepCtx.cache_mode``); a tokenize-based grep test forbids ``cache_mode``
string dispatch anywhere else, so adding a cache layout is one new class
here, not five call-site edits.

Pallas routing (``StepCtx.use_pallas``): every ``decode_attend`` /
``chunk_attend`` below forks between the dense jnp epilogues
(``attention._masked_{decode,chunk}_attn`` — the reference path) and their
Pallas twins (``attention._pallas_*``), which run the same online-softmax
in ``kernels/`` tiles: fp views (slabs, SWA rings, page-gathered tiles) go
through the flash kernels directly; coded layers keep their VQ codes
compressed in HBM when the group geometry splits per kv head
(``kernels.ops.vq_kernel_geometry_ok``) and otherwise dequantize in jnp
but still attend through the fp kernel.  Paged layouts gather their pages
into block-aligned contiguous tiles *before* kernel entry, so the kernels
never see a block table.  The differential conformance harness
(``tests/test_pallas_serving.py``) pins greedy-token parity between the
two forks for every layout on both engines.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core import vq
from repro.core.mixed_attention import (
    chunk_partial_stats,
    merge_partial_stats,
    partial_attention_stats,
)
from repro.models import attention as attn
from repro.serving import kv_cache as kvc

CACHE_MODES = ("fp", "vq", "paged", "paged_vq")

# fp prefill-view leaves carried by vq-coded layers during chunked prefill
# only: chunk attention must read exact fp K/V for earlier chunks (one-shot
# prefill attends full precision, so dequantized codes would break parity),
# while the *persistent* cache stays codes-only.  Stripped before decode.
SCRATCH_KEYS = kvc.PREFILL_SCRATCH_KEYS


def strip_prefill_scratch(caches):
    """Drop the fp prefill-view leaves from a cache tree (host-side,
    structural): after the last prefill chunk the decode step must see the
    exact decode-cache structure, codes-only for vq layouts."""
    return [{name: {k: v for k, v in sub.items() if k not in SCRATCH_KEYS}
             for name, sub in stage.items()} for stage in caches]


def donation_supported(platform: Optional[str] = None) -> bool:
    """True when XLA can alias donated buffers on this platform (TPU/GPU).
    CPU rejects donation (warns and copies), so we never request it there."""
    if platform is None:
        platform = jax.default_backend()
    return platform != "cpu"


# ---------------------------------------------------------------------------
# Shared traced helpers
# ---------------------------------------------------------------------------


def _ring_decode(params, q, k_new, v_new, cache, lengths, window, cap, ctx):
    """Dense ring cache decode (windowed layers): write slot ``l % S``,
    mask to the last ``window`` positions."""
    s = cache["k"].shape[1]
    slot = jnp.mod(lengths, s)
    ck = attn._write_at(cache["k"], k_new, slot)
    cv = attn._write_at(cache["v"], v_new, slot)
    if ctx.use_pallas:
        y = attn._pallas_decode_attn(params, q, ck, cv, lengths, window, cap)
        return y, {"k": ck, "v": cv}
    pos = attn.ring_positions(s, lengths)  # (B, S)
    valid = (pos >= 0) & (pos >= (lengths[:, None] - window + 1)) & (
        pos <= lengths[:, None])
    y = attn._masked_decode_attn(params, q, ck, cv, valid, cap)
    return y, {"k": ck, "v": cv}


def _slab_prefill_fp(cache, k, v, lengths=None):
    """Positions 0..T-1 into a dense slab.

    When the prompt buffer overflows a ring (SWA) slab, each ring slot j
    must hold the *real* position p ≡ j (mod S) closest below ``lengths``
    — naively keeping the last S buffer positions would fill the ring with
    right-padding junk whenever the per-row prompt is shorter than the
    padded buffer (the scheduler always pads to max_len).  Slots beyond a
    row's prompt end up with clipped junk that the decode validity mask
    (ring_positions) already rejects."""
    s = cache["k"].shape[1]
    t = k.shape[1]
    if t == s:
        return {"k": k.astype(cache["k"].dtype),
                "v": v.astype(cache["v"].dtype)}
    if t > s:  # ring overflow
        if lengths is None:  # no row lengths: buffer tail == prompt tail
            return {"k": k[:, t - s:].astype(cache["k"].dtype),
                    "v": v[:, t - s:].astype(cache["v"].dtype)}
        # ring slot j must hold the greatest real position ≡ j (mod S)
        # below `lengths` — exactly the decode-side slot->position map
        # evaluated at the last written position.
        p = jnp.clip(attn.ring_positions(s, lengths - 1), 0, t - 1)  # (B, S)
        idx = p[:, :, None, None]
        return {"k": jnp.take_along_axis(k, idx, axis=1).astype(
                    cache["k"].dtype),
                "v": jnp.take_along_axis(v, idx, axis=1).astype(
                    cache["v"].dtype)}
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), 0, 1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), 0, 1)
    return {"k": ck, "v": cv}


@jax.named_scope("kv_write")
def _chunk_slab_write(buf: jax.Array, vals: jax.Array,
                      chunk_start: jax.Array) -> jax.Array:
    """Write a chunk (B, W, ...) at positions ``chunk_start .. +W-1`` of a
    (B, S, ...) slab.  Bucketed chunk widths may overhang the slab end
    (the last chunk of a prompt is padded up to its bucket), so
    out-of-range positions are dropped rather than clamped — a clamping
    ``dynamic_update_slice`` would shift the write window back over live
    history."""
    w = vals.shape[1]
    pos = chunk_start + jnp.arange(w)
    return buf.at[:, pos].set(vals.astype(buf.dtype), mode="drop")


def _verify_positions(starts: jax.Array, w: int) -> jax.Array:
    """(B, W) global positions of one verify step's tokens."""
    return starts[:, None] + jnp.arange(w)[None, :]


@jax.named_scope("kv_write")
def _slab_verify_write(bk: jax.Array, bv: jax.Array, k_new: jax.Array,
                       v_new: jax.Array, starts: jax.Array):
    """Per-row scatter of W verify tokens into (B, S) slabs at positions
    ``starts[b] + j``.  Rows near their budget end may overhang the slab
    (those positions can never be accepted), so out-of-range writes are
    dropped — a clamping ``dynamic_update_slice`` would shift the window
    back over live history.  Returns (k_slab, v_slab, positions (B, W))."""
    b, w = k_new.shape[:2]
    pos = _verify_positions(starts, w)
    rows = jnp.arange(b)[:, None]
    return (bk.at[rows, pos].set(k_new.astype(bk.dtype), mode="drop"),
            bv.at[rows, pos].set(v_new.astype(bv.dtype), mode="drop"),
            pos)


def _fp_scratch(cfg, batch: int, max_len: int, dtype) -> Dict[str, jax.Array]:
    """The fp prefill-view slabs a vq-coded layer carries across chunks."""
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    return {"k_fp": jnp.zeros((batch, max_len, hkv, hd), dtype),
            "v_fp": jnp.zeros((batch, max_len, hkv, hd), dtype)}


def _view_len(full: int, history_len: int) -> int:
    """Static attention-view length for a chunk step: ``history_len`` (from
    ``serving.steps.view_bucket``) capped at the cache span; 0 = full."""
    return full if history_len <= 0 else min(int(history_len), full)


def _view_chunk_attn(params, q, k_view, v_view, chunk_start, hv, cap, ctx):
    """Global-layer chunk attention over the first ``hv`` cache positions
    (the written prefix / fp scratch / gathered pages): causal against
    ``k_pos = arange(hv)``, jnp or Pallas per ``ctx.use_pallas``."""
    k_pos = jnp.arange(hv)
    if ctx.use_pallas:
        return attn._pallas_chunk_attn(params, q, k_view, v_view,
                                       chunk_start, k_pos, 0, cap)
    return attn._masked_chunk_attn(params, q, k_view, v_view,
                                   chunk_start + jnp.arange(q.shape[1]),
                                   k_pos, 0, cap)


def _require_scratch(cache: Dict, name: str) -> None:
    if "k_fp" not in cache:
        raise ValueError(
            f"chunked prefill over the {name!r} layout needs the fp "
            "prefill-view scratch: build the cache with "
            "init_cache(..., prefill_scratch=True)")


def _ring_chunk_sources(s: int, chunk_start: jax.Array, lengths: jax.Array,
                        w: int) -> Tuple[jax.Array, jax.Array]:
    """Keep-latest map for writing one prefill chunk into a ring of length
    ``s``: ring slot ``j`` must end up holding the greatest *real* position
    ``p ≡ j (mod s)`` below ``min(lengths, chunk_start + w)``.  Returns
    ``(take, src)``: ``take`` (B, s) marks slots whose latest source lies in
    this chunk (others keep their current contents — earlier-chunk history
    or, beyond a row's prompt, junk the validity mask already rejects);
    ``src`` (B, s) is the chunk-local index to gather from."""
    e = jnp.minimum(lengths, chunk_start + w)
    p = attn.ring_positions(s, e - 1)  # (B, s), <0 during warmup
    take = p >= chunk_start
    src = jnp.clip(p - chunk_start, 0, w - 1)
    return take, src


@jax.named_scope("kv_write")
def _ring_chunk_write(cache: Dict, k: jax.Array, v: jax.Array,
                      chunk_start: jax.Array, lengths: jax.Array) -> Dict:
    """Masked keep-latest chunk write into a dense (B, S) ring slab."""
    s = cache["k"].shape[1]
    take, src = _ring_chunk_sources(s, chunk_start, lengths, k.shape[1])
    idx = src[..., None, None]
    t4 = take[..., None, None]
    kn = jnp.take_along_axis(k, idx, axis=1)
    vn = jnp.take_along_axis(v, idx, axis=1)
    return {"k": jnp.where(t4, kn.astype(cache["k"].dtype), cache["k"]),
            "v": jnp.where(t4, vn.astype(cache["v"].dtype), cache["v"])}


def _ring_k_pos(s: int, chunk_start: jax.Array, w: int) -> jax.Array:
    """Key positions of ``concat(ring-before-write, chunk)`` for one chunk
    step: ring slot j holds position ≡ j (mod S) just below ``chunk_start``
    (negative during warmup = invalid), the chunk holds its own."""
    rp = attn.ring_positions(s, jnp.reshape(chunk_start - 1, (1,)))[0]
    return jnp.concatenate([rp, chunk_start + jnp.arange(w)])


def _ring_chunk_attend(params, q, k_new, v_new, cache, chunk_start, lengths,
                       window, cap, ctx) -> Tuple[jax.Array, Dict]:
    """Windowed-layer chunk attention over ``concat(ring-before-write,
    chunk)``: the ring supplies the last ``S >= window`` positions before
    ``chunk_start`` and the chunk supplies its own K/V at exact positions —
    necessary because a chunk wider than the ring would overwrite history
    that *early* queries of the same chunk still need."""
    b, w = k_new.shape[:2]
    s = cache["k"].shape[1]
    k_pos = _ring_k_pos(s, chunk_start, w)
    k_all = jnp.concatenate(
        [cache["k"].astype(k_new.dtype), k_new], axis=1)
    v_all = jnp.concatenate(
        [cache["v"].astype(v_new.dtype), v_new], axis=1)
    if ctx.use_pallas:
        y = attn._pallas_chunk_attn(params, q, k_all, v_all, chunk_start,
                                    k_pos, window, cap)
    else:
        y = attn._masked_chunk_attn(params, q, k_all, v_all,
                                    chunk_start + jnp.arange(w), k_pos,
                                    window, cap)
    return y, _ring_chunk_write(cache, k_new, v_new, chunk_start, lengths)


def _unrolled_pallas_verify(params, q, k_all, v_all, starts, window, cap):
    """Pallas fork of the vectorized verify paths: the chunk kernel
    prefetches a *scalar* chunk start (per-row verify offsets are not
    expressible), so after the W-token write the W queries flash one at a
    time through the decode kernel — its length-derived validity mask hides
    the already-written future positions exactly like the dense mask."""
    ys = [attn._pallas_decode_attn(params, q[:, j:j + 1], k_all, v_all,
                                   starts + j, window, cap)
          for j in range(q.shape[1])]
    return jnp.concatenate(ys, axis=1)


def _coded_kernel_ok(cfg) -> bool:
    """Whether the Pallas coded-decode kernel can consume this config's
    codes directly (whole VQ groups per kv head); otherwise the use_pallas
    path dequantizes in jnp and attends through the fp flash kernel."""
    from repro.kernels.ops import vq_kernel_geometry_ok

    return vq_kernel_geometry_ok(cfg.num_kv_heads, cfg.astra.groups)


def _encode_pair(k, v, cfg, vq_params):
    spec = vq.VQSpec(cfg.d_kv, cfg.astra.groups, cfg.astra.codebook_size)
    b, t = k.shape[0], k.shape[1]
    kc = vq.encode(vq_params["k"], k.reshape(b, t, -1), spec)
    vc = vq.encode(vq_params["v"], v.reshape(b, t, -1), spec)
    return kc, vc, spec


def _decode_codes(codes, cfg, vq_params, which):
    spec = vq.VQSpec(cfg.d_kv, cfg.astra.groups, cfg.astra.codebook_size)
    b, s = codes.shape[:2]
    return vq.decode(vq_params[which], codes.astype(jnp.int32), spec
                     ).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)


def _table_for(block_tables, kind: str, cfg) -> jax.Array:
    if block_tables is None:
        raise ValueError("paged cache modes require block tables")
    if isinstance(block_tables, dict):
        return block_tables[kvc.page_group_for(kind, cfg)]
    return block_tables  # single pre-selected table


def _paged(pages: jax.Array, page_base) -> jax.Array:
    """Page ids into a pool: as they are for one layer's pool, or shifted
    to ``page_base`` (traced), where the layer's pages start in the
    layer-merged pool ``transformer.run_stages`` keeps resident."""
    return pages if page_base is None else pages + page_base


@jax.named_scope("kv_write")
def _pool_write(kp: jax.Array, vp: jax.Array, dest: jax.Array,
                offs: jax.Array, k: jax.Array, v: jax.Array,
                page_base=None):
    """Token-granular write into a K and a V page pool (N, ps, ...): token
    ``i`` of ``k``/``v`` (shaped ``dest.shape + (...)``) lands on page
    ``dest[i]`` at offset ``offs[i]`` (one scatter, in place into the
    merged pool when ``page_base`` is given)."""
    n = dest.ndim
    idx = (_paged(dest.reshape(-1), page_base), offs.reshape(-1))
    return (kp.at[idx].set(k.reshape((-1,) + k.shape[n:]).astype(kp.dtype)),
            vp.at[idx].set(v.reshape((-1,) + v.shape[n:]).astype(vp.dtype)))


@jax.named_scope("page_gather")
def _pool_view(kp: jax.Array, vp: jax.Array, table: jax.Array,
               page_base=None):
    """Gather each row's pages through ``table`` (B, n) into contiguous
    (B, n * ps, ...) K and V views, one gather each.  From the merged pool
    (``page_base`` given) gathered fp pages (B, n, ps, Hkv, hd) are held
    to the pool's own dim order: left free, the TPU compiler hands the
    attention kernels their (B, Hkv, S, hd) view by relaying out the whole
    merged pool, per layer."""
    b, n = table.shape
    shape = (b, n * kp.shape[1]) + kp.shape[2:]
    out = []
    for pool in (kp, vp):
        view = pool[_paged(table, page_base)]
        if page_base is not None and view.ndim == 5:
            view = with_layout_constraint(view, Layout(tuple(range(5))))
        out.append(view.reshape(shape))
    return tuple(out)


def _scatter_pages(pool: jax.Array, vals: jax.Array, table: jax.Array,
                   lengths: Optional[jax.Array],
                   page_base=None) -> jax.Array:
    """Write ``vals`` (B, T, ...) into ``pool`` (N, ps, ...) through a
    block table whose span may be a ring (capped window tables); pages are
    shifted to ``page_base`` as in ``_pool_write``.

    Fast path (prompt buffer fits the ring, the only case for full-span
    global tables): page ``i`` lands on table entry ``i`` wholesale; pages
    holding no real token (page start >= ``lengths``) are routed to the
    scratch page 0 so prompt-padding junk can never clobber a live slot.

    Ring-overflow path (T > span * ps): duplicate page destinations would
    make a page-wise scatter order-dependent, and a straddling page would
    mix old and new positions — so write token-granular instead: ring slot
    ``j`` gets the greatest real position ≡ j (mod ring) below ``lengths``,
    exactly the dense ring slab's semantics (slots with no real source go
    to scratch; the decode validity mask rejects them anyway)."""
    ps = pool.shape[1]
    b, t = vals.shape[:2]
    n_pages = -(-t // ps)
    span = table.shape[1]
    if n_pages > span:  # ring overflow: token-granular keep-latest
        s = span * ps
        lens = lengths if lengths is not None else jnp.full((b,), t)
        # slot->source-position map shared with the decode validity mask
        p = attn.ring_positions(s, lens - 1)  # (B, s), <0 = no real source
        real = p >= 0
        src = jnp.clip(p, 0, t - 1)[(...,) + (None,) * (vals.ndim - 2)]
        gathered = jnp.take_along_axis(vals, src, axis=1)  # (B, s, ...)
        dest = jnp.where(real, table[:, np.arange(s) // ps], 0)
        offs = jnp.broadcast_to(np.arange(s) % ps, (b, s))
        return pool.at[_paged(dest.reshape(-1), page_base),
                       offs.reshape(-1)].set(
            gathered.reshape((b * s,) + gathered.shape[2:]).astype(
                pool.dtype))
    pad = n_pages * ps - t
    if pad:
        vals = jnp.pad(vals, [(0, 0), (0, pad)] + [(0, 0)] * (vals.ndim - 2))
    vals = vals.reshape((b * n_pages, ps) + vals.shape[2:])
    dest = table[:, np.arange(n_pages)]  # (B, n_pages)
    if lengths is not None:
        real = (np.arange(n_pages) * ps)[None, :] < lengths[:, None]
        dest = jnp.where(real, dest, 0)
    return pool.at[_paged(dest.reshape(-1), page_base)].set(
        vals.astype(pool.dtype))


# ---------------------------------------------------------------------------
# Backend protocol + concrete layouts
# ---------------------------------------------------------------------------


class CacheBackend:
    """Base class: engine-level behaviour shared by every layout."""

    name = "?"
    paged = False      # block-table page pools (vs contiguous slabs)
    vq_codes = False   # global layers store VQ codes (Appendix G)
    sharded = False    # decode runs the seq-sharded shard_map path
    # cache leaves the layer scan keeps resident in its carry, every
    # layer's pages merged into one (reps * N, ps, ...) pool
    # (``transformer.run_stages``): the per-layer methods below then get
    # ``page_base``, the traced index of the layer's first page, and write
    # and gather that layer's pages of the merged pool in place.  Every
    # other leaf is sliced per layer, as the scan's xs/ys
    # (``page_base=None``).
    resident_keys: frozenset = frozenset()

    # -- layer level (jit-traced) -------------------------------------------
    def init_cache(self, cfg, kind: str, batch: int, max_len: int, dtype, *,
                   page_size: int = 0, num_pages=0,
                   prefill_scratch: bool = False) -> Dict[str, jax.Array]:
        raise NotImplementedError

    def prefill_write(self, cache, k, v, *, ctx, kind: str, vq_params=None,
                      block_tables=None, lengths=None, page_base=None) -> Dict:
        raise NotImplementedError

    def decode_attend(self, params, q, k_new, v_new, cache, lengths, *, ctx,
                      kind: str, vq_params=None, block_tables=None,
                      page_base=None) -> Tuple[jax.Array, Dict]:
        raise NotImplementedError

    def chunk_attend(self, params, q, k_new, v_new, cache, chunk_start,
                     lengths, *, ctx, kind: str, vq_params=None,
                     block_tables=None, history_len: int = 0,
                     page_base=None) -> Tuple[jax.Array, Dict]:
        """One chunked-prefill step: write the chunk's K/V (positions
        ``chunk_start .. chunk_start + W - 1``, length-masked where the
        layout needs it) and attend causally over everything cached so far
        plus the chunk itself.  ``history_len`` (static, >= the chunk end)
        bounds the global-layer attention view so a short prompt never
        scores against the whole ``max_len`` span.  Returns
        (y, new_cache)."""
        raise NotImplementedError(
            f"backend {self.name!r} does not support chunked prefill")

    def verify_attend(self, params, q, k_new, v_new, cache, starts, *, ctx,
                      kind: str, vq_params=None, block_tables=None,
                      page_base=None) -> Tuple[jax.Array, Dict]:
        """Speculative verify: W = k+1 tokens per row at per-row positions
        ``starts[b] .. starts[b] + W - 1`` in one call.  Returns
        (y (B, W, ...), new_cache) with all W keys/values written — exactly
        the cache W sequential ``decode_attend`` steps would leave behind.

        The base implementation *is* those W sequential steps, unrolled
        inside the caller's jit (W is static): bitwise parity with plain
        decode by construction, valid for every layout, Pallas fork and the
        sharded path alike.  Layouts where a single multi-query attention
        is expressible override this with a vectorized path (one chunk-
        shaped attention instead of W score rounds)."""
        w = q.shape[1]
        ys = []
        for j in range(w):
            y, cache = self.decode_attend(
                params, q[:, j:j + 1], k_new[:, j:j + 1], v_new[:, j:j + 1],
                cache, starts + j, ctx=ctx, kind=kind, vq_params=vq_params,
                block_tables=block_tables, page_base=page_base)
            ys.append(y)
        return jnp.concatenate(ys, axis=1), cache

    def verify_rollback(self, cache, old_cache, starts, accepted,
                        num_tokens, *, ctx, kind: str,
                        block_tables=None) -> Dict:
        """Undo a verify step's rejected writes in one layer's cache
        (traced — runs inside the verify jit, after acceptance is known).

        ``num_tokens`` (static) is the verify width W; ``accepted`` (B,)
        is how many of the W written positions the row actually kept.
        Global layers self-heal — a stale key at position >= the new length
        is masked invalid until a later step overwrites it in order — so
        they return ``cache`` untouched.  SWA rings cannot: writing position
        ``p`` clobbers slot ``p % S`` whose *old* position ``p - S`` is
        still inside the window once the length retreats, so every slot
        whose post-write position lands at/after ``starts + accepted`` is
        restored from the pre-verify cache.  Requires W <= S (the engines
        gate speculative width to the smallest ring)."""
        window = attn.kind_window(kind, ctx.cfg)
        if not window:
            return cache
        s = cache["k"].shape[1]
        # post-write slot -> position map; slots at/after the accept point
        # were written by rejected (or not-yet-reached) positions
        p = attn.ring_positions(s, starts + num_tokens - 1)  # (B, S)
        m = (p >= (starts + accepted)[:, None])[..., None, None]
        return {"k": jnp.where(m, old_cache["k"], cache["k"]),
                "v": jnp.where(m, old_cache["v"], cache["v"])}

    @property
    def chunkable(self) -> bool:
        """Whether the engines may drive this backend through the chunked
        prefill pipeline.  Every layout is chunkable — including the
        seq-sharded shard cache, whose chunk step scatters shard-locally and
        merges per-shard partial stats (``_chunk_sharded``); only astra-sim
        prefill (engine-level, not a layout property) still needs the
        one-shot padded path."""
        return True

    # -- engine level (host) ------------------------------------------------
    def commit_caches(self, caches, ctx):
        """Place a host-built cache tree where the compiled serving steps
        return it (identity off the mesh; see ``ShardedBackend``)."""
        return caches

    def commit_rows(self, x, ctx):
        """Place host-built per-row step state (lengths, budgets, running
        logits) where the compiled steps return it (identity off the
        mesh)."""
        return x

    def make_state(self, cfg, *, slots: int, max_len: int, ctx, dtype=None,
                   page_size: int = 16, num_pages: Optional[int] = None):
        return kvc.SlabCache(cfg, slots=slots, max_len=max_len, ctx=ctx,
                             dtype=dtype)

    def advance(self, state, slot, num_tokens: int) -> bool:
        """Grow ``slot``'s cache grant to cover ``num_tokens`` total tokens;
        False (state unchanged) on capacity pressure.  Slabs only check the
        static bound; paged layouts allocate pages in every group."""
        return state.advance(slot, num_tokens)

    def release(self, state, slot) -> int:
        """Retire a request's cache grant; returns the pages freed."""
        return state.free(slot)

    def rollback(self, state, slot, n: int) -> int:
        """Retreat ``slot``'s granted length by ``n`` tokens (host-side
        bookkeeping twin of ``verify_rollback``): slabs are a no-op, paged
        layouts drop the tail page references the retreat implies — never
        freeing a page the prefix index (or another slot) still co-owns.
        Returns the pages freed.  Request preemption's recompute path
        re-admits through the same primitive (scheduler ``preempt_mode=
        "recompute"`` re-prefills over prompt + emitted output)."""
        return state.rollback(slot, n)

    @property
    def preemptible(self) -> bool:
        """Whether the scheduler may preempt a decoding slot on this
        layout: ``swap_out`` snapshots the slot's exact cache bytes to the
        host arena and ``kvc.restore_slot`` scatters them back, so a
        restored decode is bitwise identical to a never-preempted one.
        Single-host layouts all support it; the sequence-sharded wrapper
        refuses (per-shard pools + replicated rings have no single-host
        payload to stash)."""
        return True

    def swap_out(self, state, slot, caches) -> "kvc.SwapEntry":
        """Preemption: host-snapshot everything ``slot`` owns (pages +
        dense rows; ``paged_vq`` swaps code pages, ~16x cheaper than fp).
        The caller still ``release``s the slot afterwards — prefix-shared
        pages survive through their other owners' refcounts."""
        return state.swap_out(slot, caches)

    def swap_dests(self, state, slot, entry) -> list:
        """Destination block-table rows for ``kvc.restore_slot`` after the
        slot has been re-granted ``entry.granted`` tokens."""
        return state.swap_dests(slot, entry.pages)

    def donate_argnums(self, argnums: Tuple[int, ...],
                       platform: Optional[str] = None) -> Tuple[int, ...]:
        """Filter a jitted step's cache argnums to what may be donated: all
        of them when the platform aliases donated buffers, none on CPU."""
        return tuple(argnums) if donation_supported(platform) else ()

    def bytes_report(self, cfg, *, max_len: int, slots: int = 1,
                     page_size: int = 16, num_pages: Optional[int] = None,
                     dtype_bytes: int = 4) -> Dict[str, Any]:
        return {
            "mode": self.name,
            "cache_bytes": kvc.slab_cache_bytes(
                cfg, max_len=max_len, slots=slots, vq_codes=self.vq_codes,
                dtype_bytes=dtype_bytes),
        }


class FPSlabBackend(CacheBackend):
    """Contiguous full-precision slab: (B, S, Hkv, hd) per layer; windowed
    layers keep a (B, min(W, S)) ring."""

    name = "fp"

    def init_cache(self, cfg, kind, batch, max_len, dtype, *, page_size=0,
                   num_pages=0, prefill_scratch=False):
        window = attn.kind_window(kind, cfg)
        s = min(window, max_len) if window else max_len
        hkv, hd = cfg.num_kv_heads, cfg.head_dim
        return {"k": jnp.zeros((batch, s, hkv, hd), dtype),
                "v": jnp.zeros((batch, s, hkv, hd), dtype)}

    def prefill_write(self, cache, k, v, *, ctx, kind, vq_params=None,
                      block_tables=None, lengths=None, page_base=None):
        return _slab_prefill_fp(cache, k, v, lengths)

    def decode_attend(self, params, q, k_new, v_new, cache, lengths, *, ctx,
                      kind, vq_params=None, block_tables=None, page_base=None):
        cfg = ctx.cfg
        cap = cfg.attn_logit_softcap
        window = attn.kind_window(kind, cfg)
        if window:
            return _ring_decode(params, q, k_new, v_new, cache, lengths,
                                window, cap, ctx)
        ck = attn._write_at(cache["k"], k_new, lengths)
        cv = attn._write_at(cache["v"], v_new, lengths)
        if ctx.use_pallas:
            y = attn._pallas_decode_attn(params, q, ck, cv, lengths, 0, cap)
            return y, {"k": ck, "v": cv}
        pos = jnp.arange(ck.shape[1])[None, :]
        valid = pos <= lengths[:, None]
        y = attn._masked_decode_attn(params, q, ck, cv, valid, cap)
        return y, {"k": ck, "v": cv}

    def chunk_attend(self, params, q, k_new, v_new, cache, chunk_start,
                     lengths, *, ctx, kind, vq_params=None,
                     block_tables=None, history_len=0, page_base=None):
        cfg = ctx.cfg
        cap = cfg.attn_logit_softcap
        window = attn.kind_window(kind, cfg)
        if window:
            return _ring_chunk_attend(params, q, k_new, v_new, cache,
                                      chunk_start, lengths, window, cap, ctx)
        # global slab: write the chunk, attend over the (masked) written
        # prefix.  Positions past a row's prompt end hold junk but are
        # causally unreachable from any valid query, and decode overwrites
        # them in order before they ever become valid.
        new = {"k": _chunk_slab_write(cache["k"], k_new, chunk_start),
               "v": _chunk_slab_write(cache["v"], v_new, chunk_start)}
        hv = _view_len(new["k"].shape[1], history_len)
        y = _view_chunk_attn(params, q, new["k"][:, :hv], new["v"][:, :hv],
                             chunk_start, hv, cap, ctx)
        return y, new

    def verify_attend(self, params, q, k_new, v_new, cache, starts, *, ctx,
                      kind, vq_params=None, block_tables=None, page_base=None):
        """Global layers: write all W verify tokens per-row (out-of-range
        positions dropped — a budget-exhausted row's tail can overhang the
        slab, and the unrolled path's clamping ``_write_at`` would shift
        those writes back over live history), then one chunk-shaped
        attention with per-row query positions.  Windowed rings keep the
        unrolled decode path (ring wrap is the correct overflow behaviour
        there, and ``verify_rollback`` restores the clobbered slots)."""
        cfg = ctx.cfg
        cap = cfg.attn_logit_softcap
        window = attn.kind_window(kind, cfg)
        if window:
            return CacheBackend.verify_attend(
                self, params, q, k_new, v_new, cache, starts, ctx=ctx,
                kind=kind, vq_params=vq_params, block_tables=block_tables)
        ck, cv, pos = _slab_verify_write(cache["k"], cache["v"], k_new,
                                         v_new, starts)
        if ctx.use_pallas:
            y = _unrolled_pallas_verify(params, q, ck, cv, starts, 0, cap)
        else:
            y = attn._masked_chunk_attn(params, q, ck, cv, pos,
                                        jnp.arange(ck.shape[1]), 0, cap)
        return y, {"k": ck, "v": cv}


class VQSlabBackend(CacheBackend):
    """Codes-only slab (Appendix G): global layers hold (B, S, G) VQ codes,
    dequantized on read; windowed layers stay full-precision rings exactly
    like the fp slab (their footprint is already bounded by W)."""

    name = "vq"
    vq_codes = True

    def init_cache(self, cfg, kind, batch, max_len, dtype, *, page_size=0,
                   num_pages=0, prefill_scratch=False):
        window = attn.kind_window(kind, cfg)
        if window:
            return FPSlabBackend.init_cache(self, cfg, kind, batch, max_len,
                                            dtype)
        cd = vq.code_dtype(cfg.astra.codebook_size)
        g = cfg.astra.groups
        cache = {"k_codes": jnp.zeros((batch, max_len, g), cd),
                 "v_codes": jnp.zeros((batch, max_len, g), cd)}
        if prefill_scratch:
            cache.update(_fp_scratch(cfg, batch, max_len, dtype))
        return cache

    def prefill_write(self, cache, k, v, *, ctx, kind, vq_params=None,
                      block_tables=None, lengths=None, page_base=None):
        if "k_codes" not in cache:  # windowed fp ring
            return _slab_prefill_fp(cache, k, v, lengths)
        kc, vc, _ = _encode_pair(k, v, ctx.cfg, vq_params)
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k_codes"], kc.astype(cache["k_codes"].dtype), 0, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v_codes"], vc.astype(cache["v_codes"].dtype), 0, 1)
        return {"k_codes": ck, "v_codes": cv}

    def decode_attend(self, params, q, k_new, v_new, cache, lengths, *, ctx,
                      kind, vq_params=None, block_tables=None, page_base=None):
        cfg = ctx.cfg
        cap = cfg.attn_logit_softcap
        window = attn.kind_window(kind, cfg)
        if window:
            return _ring_decode(params, q, k_new, v_new, cache, lengths,
                                window, cap, ctx)
        b = k_new.shape[0]
        kc, vc, _ = _encode_pair(k_new, v_new, cfg, vq_params)
        ck = attn._write_at(cache["k_codes"],
                            kc.astype(cache["k_codes"].dtype), lengths)
        cv = attn._write_at(cache["v_codes"],
                            vc.astype(cache["v_codes"].dtype), lengths)
        if ctx.use_pallas and _coded_kernel_ok(cfg):
            # codes stay compressed in HBM; dequant happens in VMEM tiles
            y = attn._pallas_coded_decode_attn(params, q, ck, cv, vq_params,
                                               lengths, cap)
            return y, {"k_codes": ck, "v_codes": cv}
        k_all = _decode_codes(ck, cfg, vq_params, "k")
        v_all = _decode_codes(cv, cfg, vq_params, "v")
        if ctx.use_pallas:  # geometry the coded kernel can't split
            y = attn._pallas_decode_attn(params, q, k_all, v_all, lengths,
                                         0, cap)
            return y, {"k_codes": ck, "v_codes": cv}
        pos = jnp.arange(k_all.shape[1])[None, :]
        valid = pos <= lengths[:, None]
        y = attn._masked_decode_attn(params, q, k_all, v_all, valid, cap)
        return y, {"k_codes": ck, "v_codes": cv}

    def chunk_attend(self, params, q, k_new, v_new, cache, chunk_start,
                     lengths, *, ctx, kind, vq_params=None,
                     block_tables=None, history_len=0, page_base=None):
        cfg = ctx.cfg
        cap = cfg.attn_logit_softcap
        window = attn.kind_window(kind, cfg)
        if window:  # fp ring, identical to the fp slab
            return _ring_chunk_attend(params, q, k_new, v_new, cache,
                                      chunk_start, lengths, window, cap, ctx)
        _require_scratch(cache, self.name)
        kc, vc, _ = _encode_pair(k_new, v_new, cfg, vq_params)
        # persistent cache: codes.  attention view: the fp scratch slab —
        # one-shot prefill attends full precision among prompt tokens, and
        # chunking must not change that (the codes are only ever *read* by
        # decode, exactly as in the one-shot path).
        new = {"k_codes": _chunk_slab_write(cache["k_codes"], kc,
                                            chunk_start),
               "v_codes": _chunk_slab_write(cache["v_codes"], vc,
                                            chunk_start),
               "k_fp": _chunk_slab_write(cache["k_fp"], k_new, chunk_start),
               "v_fp": _chunk_slab_write(cache["v_fp"], v_new, chunk_start)}
        hv = _view_len(new["k_fp"].shape[1], history_len)
        y = _view_chunk_attn(params, q, new["k_fp"][:, :hv],
                             new["v_fp"][:, :hv], chunk_start, hv, cap, ctx)
        return y, new

    def verify_attend(self, params, q, k_new, v_new, cache, starts, *, ctx,
                      kind, vq_params=None, block_tables=None, page_base=None):
        """Global coded layers: encode all W tokens at once (per-position
        encoding is order-independent), scatter the codes per-row with
        out-of-range drops, then attend over the dequantized slab — the
        coded Pallas kernel (or the fp kernel after a jnp dequant) runs
        once per query position, the dense path runs one chunk-shaped
        attention.  Windowed fp rings keep the unrolled decode path."""
        cfg = ctx.cfg
        cap = cfg.attn_logit_softcap
        if attn.kind_window(kind, cfg):
            return CacheBackend.verify_attend(
                self, params, q, k_new, v_new, cache, starts, ctx=ctx,
                kind=kind, vq_params=vq_params, block_tables=block_tables)
        kc, vc, _ = _encode_pair(k_new, v_new, cfg, vq_params)
        ck, cv, pos = _slab_verify_write(cache["k_codes"], cache["v_codes"],
                                         kc, vc, starts)
        new_cache = {"k_codes": ck, "v_codes": cv}
        if ctx.use_pallas and _coded_kernel_ok(cfg):
            ys = [attn._pallas_coded_decode_attn(
                      params, q[:, j:j + 1], ck, cv, vq_params, starts + j,
                      cap) for j in range(q.shape[1])]
            return jnp.concatenate(ys, axis=1), new_cache
        k_all = _decode_codes(ck, cfg, vq_params, "k")
        v_all = _decode_codes(cv, cfg, vq_params, "v")
        if ctx.use_pallas:
            y = _unrolled_pallas_verify(params, q, k_all, v_all, starts, 0,
                                        cap)
        else:
            y = attn._masked_chunk_attn(params, q, k_all, v_all, pos,
                                        jnp.arange(k_all.shape[1]), 0, cap)
        return y, new_cache


class PagedBackend(CacheBackend):
    """Block-table page pools, fp value pages.  Global layers address a
    full-span table; windowed layers address the capped "window" table as a
    page ring over the last ``span * page_size`` positions."""

    name = "paged"
    paged = True
    resident_keys = kvc.PAGED_LEAF_KEYS

    def _group_num_pages(self, num_pages, kind, cfg) -> int:
        if isinstance(num_pages, dict):
            return int(num_pages[kvc.page_group_for(kind, cfg)])
        return int(num_pages)

    def init_cache(self, cfg, kind, batch, max_len, dtype, *, page_size=0,
                   num_pages=0, prefill_scratch=False):
        n = self._group_num_pages(num_pages, kind, cfg) if num_pages else 0
        if page_size <= 0 or n <= 0:
            raise ValueError("paged cache modes need page_size/num_pages "
                             "(build caches via serving.kv_cache.PagedKVCache)")
        window = attn.kind_window(kind, cfg)
        if self.vq_codes and not window:
            g = cfg.astra.groups
            cd = vq.code_dtype(cfg.astra.codebook_size)
            cache = {"k_code_pages": jnp.zeros((n, page_size, g), cd),
                     "v_code_pages": jnp.zeros((n, page_size, g), cd)}
            if prefill_scratch:
                cache.update(_fp_scratch(cfg, batch, max_len, dtype))
            return cache
        hkv, hd = cfg.num_kv_heads, cfg.head_dim
        return {"k_pages": jnp.zeros((n, page_size, hkv, hd), dtype),
                "v_pages": jnp.zeros((n, page_size, hkv, hd), dtype)}

    def prefill_write(self, cache, k, v, *, ctx, kind, vq_params=None,
                      block_tables=None, lengths=None, page_base=None):
        """Prompt K/V (or codes) straight into the page pools — no
        (B, max_len) slab is ever materialized or copied."""
        cfg = ctx.cfg
        table = _table_for(block_tables, kind, cfg)
        if "k_code_pages" in cache:
            kc, vc, _ = _encode_pair(k, v, cfg, vq_params)
            return {
                "k_code_pages": _scatter_pages(cache["k_code_pages"], kc,
                                               table, lengths, page_base),
                "v_code_pages": _scatter_pages(cache["v_code_pages"], vc,
                                               table, lengths, page_base),
            }
        return {
            "k_pages": _scatter_pages(cache["k_pages"], k, table, lengths,
                                      page_base),
            "v_pages": _scatter_pages(cache["v_pages"], v, table, lengths,
                                      page_base),
        }

    def decode_attend(self, params, q, k_new, v_new, cache, lengths, *, ctx,
                      kind, vq_params=None, block_tables=None, page_base=None):
        """Scatter-write the token's page slot (ring over the table span),
        gather the request's pages through the block table, then run the
        same dense masked decode attention as every other layout."""
        cfg = ctx.cfg
        cap = cfg.attn_logit_softcap
        window = attn.kind_window(kind, cfg)
        table = _table_for(block_tables, kind, cfg)
        vq_pool = "k_code_pages" in cache
        kp = cache["k_code_pages" if vq_pool else "k_pages"]
        vp = cache["v_code_pages" if vq_pool else "v_pages"]
        ps = kp.shape[1]
        s = table.shape[1] * ps  # ring length (== max_len for global tables)
        flat = jnp.mod(lengths, s)
        page_ids = jnp.take_along_axis(table, (flat // ps)[:, None],
                                       axis=1)[:, 0]
        offs = jnp.mod(flat, ps)
        if vq_pool:
            kc, vc, _ = _encode_pair(k_new, v_new, cfg, vq_params)
            kp, vp = _pool_write(kp, vp, page_ids, offs, kc[:, 0], vc[:, 0],
                                 page_base)
            new_cache = {"k_code_pages": kp, "v_code_pages": vp}
            # gather code pages into one contiguous (B, s, G) tile — the
            # kernels never see a block table, only block-aligned tiles
            codes_k, codes_v = _pool_view(kp, vp, table, page_base)
            if ctx.use_pallas and not window and _coded_kernel_ok(cfg):
                y = attn._pallas_coded_decode_attn(params, q, codes_k,
                                                   codes_v, vq_params,
                                                   lengths, cap)
                return y, new_cache
            k_all = _decode_codes(codes_k, cfg, vq_params, "k")
            v_all = _decode_codes(codes_v, cfg, vq_params, "v")
        else:
            kp, vp = _pool_write(kp, vp, page_ids, offs, k_new[:, 0],
                                 v_new[:, 0], page_base)
            k_all, v_all = _pool_view(kp, vp, table, page_base)
            new_cache = {"k_pages": kp, "v_pages": vp}
        if ctx.use_pallas:
            # the gathered view is a ring over the table span; the kernel's
            # ring mask mirrors the dense validity mask below exactly
            y = attn._pallas_decode_attn(params, q, k_all, v_all, lengths,
                                         window, cap)
            return y, new_cache
        pos = attn.ring_positions(s, lengths)  # (B, s)
        valid = (pos >= 0) & (pos <= lengths[:, None])
        if window:
            valid &= pos >= lengths[:, None] - (window - 1)
        y = attn._masked_decode_attn(params, q, k_all, v_all, valid, cap)
        return y, new_cache

    def chunk_attend(self, params, q, k_new, v_new, cache, chunk_start,
                     lengths, *, ctx, kind, vq_params=None,
                     block_tables=None, history_len=0, page_base=None):
        """Token-granular chunk scatter through the block table (page-wise
        writes would need chunk/page alignment), then the same masked chunk
        attention as the slab layouts over the table-gathered view."""
        cfg = ctx.cfg
        cap = cfg.attn_logit_softcap
        window = attn.kind_window(kind, cfg)
        table = _table_for(block_tables, kind, cfg)
        vq_pool = "k_code_pages" in cache
        kp = cache["k_code_pages" if vq_pool else "k_pages"]
        vp = cache["v_code_pages" if vq_pool else "v_pages"]
        ps = kp.shape[1]
        b, w = k_new.shape[:2]
        s = table.shape[1] * ps  # ring length (== max_len for global tables)
        q_pos = chunk_start + jnp.arange(w)

        if window:  # fp page ring (windowed layers keep fp pages under vq)
            # read the ring before the write below, so the write can update
            # the pool in place
            ring_k, ring_v = _pool_view(kp, vp, table, page_base)
            k_pos = _ring_k_pos(s, chunk_start, w)
            k_all = jnp.concatenate([ring_k.astype(k_new.dtype), k_new], 1)
            v_all = jnp.concatenate([ring_v.astype(v_new.dtype), v_new], 1)
            if ctx.use_pallas:
                y = attn._pallas_chunk_attn(params, q, k_all, v_all,
                                            chunk_start, k_pos, window, cap)
            else:
                y = attn._masked_chunk_attn(params, q, k_all, v_all, q_pos,
                                            k_pos, window, cap)
            # keep-latest write through the page ring; slots whose latest
            # source is not in this chunk are routed to the scratch page
            take, src = _ring_chunk_sources(s, chunk_start, lengths, w)
            idx = src[..., None, None]
            gk = jnp.take_along_axis(k_new, idx, axis=1)  # (B, s, ...)
            gv = jnp.take_along_axis(v_new, idx, axis=1)
            dest = jnp.where(take, table[:, np.arange(s) // ps], 0)
            offs = jnp.broadcast_to(np.arange(s) % ps, (b, s))
            kp, vp = _pool_write(kp, vp, dest, offs, gk, gv, page_base)
            return y, {"k_pages": kp, "v_pages": vp}

        # global table: scatter the chunk token-granular (positions past the
        # table span — bucket overhang — go to scratch page 0)
        page_idx = jnp.clip(q_pos // ps, 0, table.shape[1] - 1)
        dest = jnp.where((q_pos < s)[None], table[:, page_idx], 0)  # (B, W)
        offs = jnp.broadcast_to(q_pos % ps, (b, w))
        if vq_pool:
            _require_scratch(cache, self.name)
            kc, vc, _ = _encode_pair(k_new, v_new, cfg, vq_params)
            kp, vp = _pool_write(kp, vp, dest, offs, kc, vc, page_base)
            k_view = _chunk_slab_write(cache["k_fp"], k_new, chunk_start)
            v_view = _chunk_slab_write(cache["v_fp"], v_new, chunk_start)
            hv = _view_len(k_view.shape[1], history_len)
            y = _view_chunk_attn(params, q, k_view[:, :hv], v_view[:, :hv],
                                 chunk_start, hv, cap, ctx)
            return y, {"k_code_pages": kp, "v_code_pages": vp,
                       "k_fp": k_view, "v_fp": v_view}
        kp, vp = _pool_write(kp, vp, dest, offs, k_new, v_new, page_base)
        # gather only the first ceil(hv/ps) pages per row — the view length
        # ladder keeps both the gather (a block-aligned contiguous tile the
        # kernel can consume) and the score matrix prompt-sized
        hv = _view_len(s, history_len)
        n_view = -(-hv // ps)
        sv = n_view * ps
        k_all, v_all = _pool_view(kp, vp, table[:, :n_view], page_base)
        y = _view_chunk_attn(params, q, k_all, v_all, chunk_start, sv, cap,
                             ctx)
        return y, {"k_pages": kp, "v_pages": vp}

    def verify_attend(self, params, q, k_new, v_new, cache, starts, *, ctx,
                      kind, vq_params=None, block_tables=None, page_base=None):
        """Global tables: token-granular per-row scatter of all W verify
        positions through the block table (out-of-span positions — a
        budget-exhausted row's overhang — route to the scratch page instead
        of mod-wrapping over the row's own early pages), then attention
        over the table-gathered contiguous view.  Windowed page rings keep
        the unrolled decode path (wrap + ``verify_rollback``)."""
        cfg = ctx.cfg
        cap = cfg.attn_logit_softcap
        if attn.kind_window(kind, cfg):
            return CacheBackend.verify_attend(
                self, params, q, k_new, v_new, cache, starts, ctx=ctx,
                kind=kind, vq_params=vq_params, block_tables=block_tables,
                page_base=page_base)
        table = _table_for(block_tables, kind, cfg)
        vq_pool = "k_code_pages" in cache
        kp = cache["k_code_pages" if vq_pool else "k_pages"]
        vp = cache["v_code_pages" if vq_pool else "v_pages"]
        ps = kp.shape[1]
        w = k_new.shape[1]
        s = table.shape[1] * ps  # == max_len for global tables
        pos = _verify_positions(starts, w)
        page_idx = jnp.clip(pos // ps, 0, table.shape[1] - 1)
        dest = jnp.where(pos < s,
                         jnp.take_along_axis(table, page_idx, axis=1), 0)
        offs = jnp.mod(pos, ps)
        if vq_pool:
            kc, vc, _ = _encode_pair(k_new, v_new, cfg, vq_params)
            kp, vp = _pool_write(kp, vp, dest, offs, kc, vc, page_base)
            new_cache = {"k_code_pages": kp, "v_code_pages": vp}
            codes_k, codes_v = _pool_view(kp, vp, table, page_base)
            if ctx.use_pallas and _coded_kernel_ok(cfg):
                ys = [attn._pallas_coded_decode_attn(
                          params, q[:, j:j + 1], codes_k, codes_v,
                          vq_params, starts + j, cap) for j in range(w)]
                return jnp.concatenate(ys, axis=1), new_cache
            k_all = _decode_codes(codes_k, cfg, vq_params, "k")
            v_all = _decode_codes(codes_v, cfg, vq_params, "v")
        else:
            kp, vp = _pool_write(kp, vp, dest, offs, k_new, v_new, page_base)
            new_cache = {"k_pages": kp, "v_pages": vp}
            k_all, v_all = _pool_view(kp, vp, table, page_base)
        if ctx.use_pallas:
            y = _unrolled_pallas_verify(params, q, k_all, v_all, starts, 0,
                                        cap)
        else:
            y = attn._masked_chunk_attn(params, q, k_all, v_all, pos,
                                        jnp.arange(s), 0, cap)
        return y, new_cache

    def verify_rollback(self, cache, old_cache, starts, accepted,
                        num_tokens, *, ctx, kind, block_tables=None):
        """Windowed page rings: gather the pre-verify ring contents through
        the block table and scatter them back over every slot whose
        post-write position lands at/after the accept point (non-restored
        slots route to the scratch page).  Global tables self-heal like the
        slabs and pass through untouched."""
        if not attn.kind_window(kind, ctx.cfg):
            return cache
        table = _table_for(block_tables, kind, ctx.cfg)
        kp, vp = cache["k_pages"], cache["v_pages"]
        ps = kp.shape[1]
        b = starts.shape[0]
        s = table.shape[1] * ps
        p = attn.ring_positions(s, starts + num_tokens - 1)  # (B, s)
        mask = p >= (starts + accepted)[:, None]
        old_k, old_v = _pool_view(old_cache["k_pages"], old_cache["v_pages"],
                                  table)
        dest = jnp.where(mask, table[:, np.arange(s) // ps], 0)
        offs = jnp.broadcast_to(np.arange(s) % ps, (b, s))
        kp, vp = _pool_write(kp, vp, dest, offs, old_k, old_v)
        return {"k_pages": kp, "v_pages": vp}

    def make_state(self, cfg, *, slots, max_len, ctx, dtype=None,
                   page_size=16, num_pages=None):
        return kvc.PagedKVCache(cfg, slots=slots, max_len=max_len, ctx=ctx,
                                page_size=page_size, num_pages=num_pages,
                                dtype=dtype)

    def bytes_report(self, cfg, *, max_len, slots=1, page_size=16,
                     num_pages=None, dtype_bytes=4):
        return {
            "mode": self.name,
            "cache_bytes": kvc.paged_pool_bytes(
                cfg, max_len=max_len, page_size=page_size,
                vq_codes=self.vq_codes, slots=slots, num_pages=num_pages,
                dtype_bytes=dtype_bytes),
            "page_group_spans": kvc.page_group_spans(cfg, max_len, page_size),
        }


class PagedVQBackend(PagedBackend):
    """Paged pools with uint8/16 VQ code pages on global layers (the
    Appendix-G codes-only cache under a block table); windowed layers keep
    fp pages, mirroring the dense "vq" slab."""

    name = "paged_vq"
    vq_codes = True


class ShardedBackend(CacheBackend):
    """Sequence-sharded shard cache: the inner layout (slab or paged) with
    the global-layer decode *and* chunked prefill running under shard_map
    over ``mesh.seq_axis`` — each device owns a disjoint sequence shard
    (for paged pools, a disjoint page-id range) and partial-softmax stats
    are merged flash-decoding style (windowed layers keep the replicated
    ring; one-shot prefill and init are the inner layout's)."""

    sharded = True

    def __init__(self, inner: CacheBackend):
        self.inner = inner
        self.name = f"sharded_{inner.name}"
        self.vq_codes = inner.vq_codes
        self.paged = inner.paged

    def init_cache(self, cfg, kind, batch, max_len, dtype, *, page_size=0,
                   num_pages=0, prefill_scratch=False):
        return self.inner.init_cache(cfg, kind, batch, max_len, dtype,
                                     page_size=page_size, num_pages=num_pages,
                                     prefill_scratch=prefill_scratch)

    def prefill_write(self, cache, k, v, *, ctx, kind, vq_params=None,
                      block_tables=None, lengths=None, page_base=None):
        return self.inner.prefill_write(cache, k, v, ctx=ctx, kind=kind,
                                        vq_params=vq_params,
                                        block_tables=block_tables,
                                        lengths=lengths)

    def decode_attend(self, params, q, k_new, v_new, cache, lengths, *, ctx,
                      kind, vq_params=None, block_tables=None, page_base=None):
        cfg = ctx.cfg
        window = attn.kind_window(kind, cfg)
        if window:  # ring cache / page ring, replicated over the seq axis
            return self.inner.decode_attend(
                params, q, k_new, v_new, cache, lengths, ctx=ctx, kind=kind,
                vq_params=vq_params, block_tables=block_tables)
        if self.paged:
            table = _table_for(block_tables, kind, cfg)
            return _paged_decode_sharded(params, q, k_new, v_new, cache,
                                         lengths, table, ctx, cfg,
                                         cfg.attn_logit_softcap, vq_params)
        return _decode_sharded(params, q, k_new, v_new, cache, lengths,
                               ctx, cfg, cfg.attn_logit_softcap, vq_params)

    def chunk_attend(self, params, q, k_new, v_new, cache, chunk_start,
                     lengths, *, ctx, kind, vq_params=None,
                     block_tables=None, history_len=0, page_base=None):
        cfg = ctx.cfg
        window = attn.kind_window(kind, cfg)
        if window:  # replicated ring / page ring: the inner layout's path
            return self.inner.chunk_attend(
                params, q, k_new, v_new, cache, chunk_start, lengths,
                ctx=ctx, kind=kind, vq_params=vq_params,
                block_tables=block_tables, history_len=history_len)
        if self.vq_codes:
            _require_scratch(cache, self.name)
        if self.paged:
            table = _table_for(block_tables, kind, cfg)
            return _paged_chunk_sharded(params, q, k_new, v_new, cache,
                                        chunk_start, table, ctx, cfg,
                                        cfg.attn_logit_softcap, vq_params)
        return _chunk_sharded(params, q, k_new, v_new, cache, chunk_start,
                              ctx, cfg, cfg.attn_logit_softcap, vq_params)

    def verify_rollback(self, cache, old_cache, starts, accepted,
                        num_tokens, *, ctx, kind, block_tables=None):
        # rollback only ever touches windowed rings, which stay replicated
        # under the mesh — the inner layout's restore applies verbatim
        return self.inner.verify_rollback(cache, old_cache, starts, accepted,
                                          num_tokens, ctx=ctx, kind=kind,
                                          block_tables=block_tables)

    def make_state(self, cfg, *, slots, max_len, ctx, dtype=None,
                   page_size=16, num_pages=None):
        return self.inner.make_state(cfg, slots=slots, max_len=max_len,
                                     ctx=ctx, dtype=dtype,
                                     page_size=page_size,
                                     num_pages=num_pages)

    # jit keys each compiled program on its arguments' shardings: a cache or
    # row vector built on the host enters the first step call uncommitted,
    # while every later call gets the step's own mesh-placed outputs — two
    # traces and two compiles of the same step.  Committing host-built state
    # to the shardings the steps return makes the first call the last
    # compile.
    def commit_caches(self, caches, ctx):
        """Global attention layers: slabs and the fp prefill-view scratch
        (reps, B, S, ...) split over the sequence axis, page pools
        (reps, N, ...) over their page-id axis — the shard_map out_specs of
        the sharded steps.  Everything else (rings, recurrent state) is
        replicated."""
        from repro.models import transformer as tlm

        mesh, axis = ctx.mesh.mesh, ctx.mesh.seq_axis
        bspec = ctx.mesh.batch_axes if ctx.mesh.batch_axes else None

        def place(kind, leaves):
            if kind not in tlm.ATTN_KINDS or attn.kind_window(kind, ctx.cfg):
                return jax.device_put(leaves, NamedSharding(mesh, P()))
            return {name: jax.device_put(x, NamedSharding(
                        mesh, P(None, axis) if name.endswith("_pages")
                        else P(None, bspec, axis)))
                    for name, x in leaves.items()}

        return [{sub: place(kinds[int(sub[len("sub"):])], leaves)
                 for sub, leaves in stage.items()}
                for (kinds, _), stage in zip(tlm.stages(ctx.cfg), caches)]

    def commit_rows(self, x, ctx):
        return jax.device_put(x, NamedSharding(ctx.mesh.mesh, P()))

    @property
    def preemptible(self) -> bool:
        """Preemption stays a single-host feature (like prefix caching):
        under the mesh the global pools are per-shard and the snapshot /
        restore pair would have to gather and re-scatter shard-local page
        ids — not worth it when the scheduler can simply defer instead."""
        return False

    def swap_out(self, state, slot, caches):
        raise ValueError(
            f"{self.name}: preemption swap is not supported under a "
            f"sequence-sharded mesh (check backend.preemptible first)")

    def bytes_report(self, cfg, *, max_len, slots=1, page_size=16,
                     num_pages=None, dtype_bytes=4):
        rep = self.inner.bytes_report(cfg, max_len=max_len, slots=slots,
                                      page_size=page_size,
                                      num_pages=num_pages,
                                      dtype_bytes=dtype_bytes)
        rep["mode"] = self.name
        rep["note"] = "sequence-sharded: divide cache_bytes by shard count"
        return rep


def _decode_sharded(params, q, k_new, v_new, cache, lengths, ctx, cfg, cap,
                    vq_params):
    """Distributed decode: cache sharded over mesh.seq_axis on the sequence
    dim; flash-decoding partial-softmax merge (beyond-paper, DESIGN.md §2)."""
    axis = ctx.mesh.seq_axis
    bspec = ctx.mesh.batch_axes if ctx.mesh.batch_axes else None
    b = q.shape[0]
    vq_cache = "k_codes" in cache
    pallas_on = ctx.use_pallas or ctx.use_pallas_decode
    # the Pallas coded-decode kernel needs whole groups per kv head; other
    # geometries dequantize in jnp but still flash through the fp kernel
    kernel_ok = pallas_on and vq_cache and _coded_kernel_ok(cfg)

    def body(q_l, k_n, v_n, ck, cv, lens, cb_k, cb_v):
        s_loc = ck.shape[1]
        off = jax.lax.axis_index(axis) * s_loc
        local_idx = jnp.clip(lens - off, 0, s_loc - 1)
        mine = (lens >= off) & (lens < off + s_loc)
        lens_local = lens - off  # negative => nothing valid on this shard
        if vq_cache:
            spec = vq.VQSpec(cfg.d_kv, cfg.astra.groups,
                             cfg.astra.codebook_size)
            bl = q_l.shape[0]
            kc_n = vq.encode({"codebook": cb_k}, k_n.reshape(bl, 1, -1), spec)
            vc_n = vq.encode({"codebook": cb_v}, v_n.reshape(bl, 1, -1), spec)
            ck2 = jnp.where(mine[:, None, None],
                            attn._write_at(ck, kc_n.astype(ck.dtype),
                                           local_idx), ck)
            cv2 = jnp.where(mine[:, None, None],
                            attn._write_at(cv, vc_n.astype(cv.dtype),
                                           local_idx), cv)
            if kernel_ok:
                # Pallas flash-decode over the coded cache: codes are never
                # dequantized in HBM (kernels/vq_decode_attn.py)
                from repro.kernels.ops import decode_attention_partials

                m_, l_, acc_ = decode_attention_partials(
                    q_l[:, 0], ck2, cv2, cb_k, cb_v, lens_local,
                    softcap=cap, use_pallas=True)
                out = merge_partial_stats(m_[..., None], l_[..., None],
                                          acc_[:, None], axis)
                return out, ck2, cv2
            k_shard = vq.decode({"codebook": cb_k}, ck2.astype(jnp.int32),
                                spec).reshape(bl, s_loc, cfg.num_kv_heads,
                                              cfg.head_dim)
            v_shard = vq.decode({"codebook": cb_v}, cv2.astype(jnp.int32),
                                spec).reshape(bl, s_loc, cfg.num_kv_heads,
                                              cfg.head_dim)
        else:
            ck2 = jnp.where(mine[:, None, None, None],
                            attn._write_at(ck, k_n, local_idx), ck)
            cv2 = jnp.where(mine[:, None, None, None],
                            attn._write_at(cv, v_n, local_idx), cv)
            k_shard, v_shard = ck2, cv2
        if pallas_on:
            # fp shard tiles (and de-coded tiles when the coded kernel
            # can't split the groups) flash through the fp decode kernel
            from repro.kernels.ops import fp_decode_partials

            m_, l_, acc_ = fp_decode_partials(q_l[:, 0], k_shard, v_shard,
                                              lens_local, softcap=cap,
                                              use_pallas=True)
            out = merge_partial_stats(m_[..., None], l_[..., None],
                                      acc_[:, None], axis)
            return out, ck2, cv2
        pos = off + jnp.arange(s_loc)[None, :]
        valid = pos <= lens[:, None]
        m, l, o = partial_attention_stats(q_l, k_shard, v_shard,
                                          k_valid=valid, softcap=cap)
        out = merge_partial_stats(m, l, o, axis)
        return out, ck2, cv2

    qspec = P(bspec, None, None, None)
    cspec4 = P(bspec, axis, None, None)
    cspec3 = P(bspec, axis, None)
    if vq_cache:
        in_specs = (qspec, qspec, qspec, cspec3, cspec3, P(bspec), P(), P())
        out_specs = (qspec, cspec3, cspec3)
        cb_k = vq_params["k"]["codebook"]
        cb_v = vq_params["v"]["codebook"]
        ck_in, cv_in = cache["k_codes"], cache["v_codes"]
    else:
        in_specs = (qspec, qspec, qspec, cspec4, cspec4, P(bspec), P(), P())
        out_specs = (qspec, cspec4, cspec4)
        cb_k = cb_v = jnp.zeros((1,), jnp.float32)
        ck_in, cv_in = cache["k"], cache["v"]

    with jax.named_scope("attn_kernel"):
        out, ck2, cv2 = shard_map(
            body, mesh=ctx.mesh.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)(q, k_new, v_new, ck_in, cv_in, lengths, cb_k,
                             cb_v)
    with jax.named_scope("attn_out"):
        y = out.reshape(b, 1, -1) @ params["wo"]
    new_cache = ({"k_codes": ck2, "v_codes": cv2} if vq_cache
                 else {"k": ck2, "v": cv2})
    return y, new_cache


@jax.named_scope("kv_write")
def _shard_chunk_write(buf: jax.Array, vals: jax.Array,
                       loc_pos: jax.Array) -> jax.Array:
    """Write a chunk (B, W, ...) into a shard-local (B, S_loc, ...) slab at
    shard-local positions ``loc_pos`` (W,).  Positions outside
    ``[0, S_loc)`` — the parts of the chunk other shards own, and bucket
    overhang — are routed to index ``S_loc`` and dropped: a negative traced
    index would wrap and a clamp would shift the write over live history."""
    s_loc = buf.shape[1]
    dest = jnp.where((loc_pos >= 0) & (loc_pos < s_loc), loc_pos, s_loc)
    return buf.at[:, dest].set(vals.astype(buf.dtype), mode="drop")


def _chunk_shard_merge(q_l, k_view, v_view, chunk_start, off, cap, axis,
                       pallas_on):
    """Score one chunk's W queries against one shard's local view (keys at
    global positions ``off .. off + S_loc - 1``) and merge the flash
    partials across the mesh axis — ``merge_partial_stats`` is
    width-agnostic, so the decode merge applies to W-wide stats verbatim."""
    b, w = q_l.shape[:2]
    s_loc = k_view.shape[1]
    k_pos = off + jnp.arange(s_loc)
    if pallas_on:
        from repro.kernels.ops import chunk_attention_partials

        m_, l_, acc_ = chunk_attention_partials(
            q_l, k_view, v_view, k_pos, chunk_start, softcap=cap,
            use_pallas=True)
    else:
        q_pos = chunk_start + jnp.arange(w)
        valid = jnp.broadcast_to(
            (k_pos[None, :] <= q_pos[:, None])[None], (b, w, s_loc))
        m_, l_, acc_ = chunk_partial_stats(q_l, k_view, v_view, valid=valid,
                                           softcap=cap)
    return merge_partial_stats(m_, l_, acc_, axis)


def _chunk_sharded(params, q, k_new, v_new, cache, chunk_start, ctx, cfg,
                   cap, vq_params):
    """Seq-sharded chunked prefill over slab caches (global layers): every
    shard scatters the chunk positions it owns into its slab shard
    (out-of-shard positions drop), scores the whole chunk against its local
    prefix, and the partial softmax stats merge across the mesh axis — the
    ``_decode_sharded`` flash-decoding merge widened to W queries with a
    per-query causal mask.  Junk beyond a row's prompt is causally
    unreachable from any valid query, exactly as in the single-host slab
    path, so no length mask is needed; the per-shard view is already
    ``max_len / n_shards`` so the static ``history_len`` crop is moot."""
    axis = ctx.mesh.seq_axis
    bspec = ctx.mesh.batch_axes if ctx.mesh.batch_axes else None
    b, w = q.shape[:2]
    vq_cache = "k_codes" in cache
    pallas_on = ctx.use_pallas
    cs = jnp.asarray(chunk_start, jnp.int32)

    def body(q_l, k_n, v_n, ck, cv, kf, vf, cs_l, cb_k, cb_v):
        s_loc = ck.shape[1]
        off = jax.lax.axis_index(axis) * s_loc
        loc_pos = cs_l + jnp.arange(w) - off
        if vq_cache:
            spec = vq.VQSpec(cfg.d_kv, cfg.astra.groups,
                             cfg.astra.codebook_size)
            bl = q_l.shape[0]
            kc = vq.encode({"codebook": cb_k}, k_n.reshape(bl, w, -1), spec)
            vc = vq.encode({"codebook": cb_v}, v_n.reshape(bl, w, -1), spec)
            ck2 = _shard_chunk_write(ck, kc, loc_pos)
            cv2 = _shard_chunk_write(cv, vc, loc_pos)
            kf2 = _shard_chunk_write(kf, k_n, loc_pos)
            vf2 = _shard_chunk_write(vf, v_n, loc_pos)
            k_view, v_view = kf2, vf2
        else:
            ck2 = _shard_chunk_write(ck, k_n, loc_pos)
            cv2 = _shard_chunk_write(cv, v_n, loc_pos)
            kf2, vf2 = kf, vf
            k_view, v_view = ck2, cv2
        out = _chunk_shard_merge(q_l, k_view, v_view, cs_l, off, cap, axis,
                                 pallas_on)
        return out, ck2, cv2, kf2, vf2

    qspec = P(bspec, None, None, None)
    cspec4 = P(bspec, axis, None, None)
    cspec3 = P(bspec, axis, None)
    if vq_cache:
        in_specs = (qspec, qspec, qspec, cspec3, cspec3, cspec4, cspec4,
                    P(), P(), P())
        out_specs = (qspec, cspec3, cspec3, cspec4, cspec4)
        cb_k = vq_params["k"]["codebook"]
        cb_v = vq_params["v"]["codebook"]
        ck_in, cv_in = cache["k_codes"], cache["v_codes"]
        kf_in, vf_in = cache["k_fp"], cache["v_fp"]
    else:
        in_specs = (qspec, qspec, qspec, cspec4, cspec4, P(), P(),
                    P(), P(), P())
        out_specs = (qspec, cspec4, cspec4, P(), P())
        cb_k = cb_v = jnp.zeros((1,), jnp.float32)
        ck_in, cv_in = cache["k"], cache["v"]
        kf_in = vf_in = jnp.zeros((1,), jnp.float32)

    with jax.named_scope("attn_kernel"):
        out, ck2, cv2, kf2, vf2 = shard_map(
            body, mesh=ctx.mesh.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)(q, k_new, v_new, ck_in, cv_in, kf_in, vf_in, cs,
                             cb_k, cb_v)
    with jax.named_scope("attn_out"):
        y = out.reshape(b, w, -1) @ params["wo"]
    new_cache = ({"k_codes": ck2, "v_codes": cv2, "k_fp": kf2, "v_fp": vf2}
                 if vq_cache else {"k": ck2, "v": cv2})
    return y, new_cache


def _paged_shard_geometry(cache, table, ctx):
    """Static geometry of a sharded page pool: shard i owns the page-id
    range ``[i * n_loc, (i+1) * n_loc)`` (``PagedKVCache`` allocates table
    entry j from shard ``j // span_loc``, so shard i's table columns hold
    only its own ids) and the sequence range ``[i * s_loc, (i+1) * s_loc)``
    of every request."""
    n_shards = ctx.mesh.num_seq_shards
    vq_pool = "k_code_pages" in cache
    kp = cache["k_code_pages" if vq_pool else "k_pages"]
    ps = kp.shape[1]
    span = table.shape[1]
    if span % n_shards or kp.shape[0] % n_shards:
        raise ValueError(
            f"sharded paged pools need the table span ({span}) and pool "
            f"size ({kp.shape[0]}) divisible by the {n_shards} sequence "
            f"shards")
    span_loc = span // n_shards
    return vq_pool, ps, span_loc, span_loc * ps


def _paged_decode_sharded(params, q, k_new, v_new, cache, lengths, table,
                          ctx, cfg, cap, vq_params):
    """Distributed decode over sharded page pools: the owning shard
    scatter-writes the token into its local page (everyone else hits its
    local scratch page 0), each shard gathers its own table slice into a
    contiguous local view, and the per-shard flash partials merge exactly
    as in ``_decode_sharded``."""
    axis = ctx.mesh.seq_axis
    bspec = ctx.mesh.batch_axes if ctx.mesh.batch_axes else None
    b = q.shape[0]
    vq_pool, ps, span_loc, s_loc = _paged_shard_geometry(cache, table, ctx)
    kp_in = cache["k_code_pages" if vq_pool else "k_pages"]
    vp_in = cache["v_code_pages" if vq_pool else "v_pages"]
    pallas_on = ctx.use_pallas or ctx.use_pallas_decode
    kernel_ok = pallas_on and vq_pool and _coded_kernel_ok(cfg)

    def body(q_l, k_n, v_n, kp, vp, tab, lens, cb_k, cb_v):
        n_loc = kp.shape[0]
        i = jax.lax.axis_index(axis)
        off = i * s_loc
        tab_loc = jax.lax.dynamic_slice_in_dim(tab, i * span_loc, span_loc,
                                               axis=1)
        # global -> shard-local page ids; ungranted entries (0) clip to the
        # local scratch page, whose junk the validity mask already rejects
        loc_ids = jnp.clip(tab_loc - i * n_loc, 0, n_loc - 1)
        mine = (lens >= off) & (lens < off + s_loc)
        lpos = jnp.clip(lens - off, 0, s_loc - 1)
        entry = jnp.take_along_axis(loc_ids, (lpos // ps)[:, None],
                                    axis=1)[:, 0]
        dest = jnp.where(mine, entry, 0)
        offs = jnp.mod(lpos, ps)
        bl = q_l.shape[0]
        lens_local = lens - off  # negative => nothing valid on this shard
        if vq_pool:
            spec = vq.VQSpec(cfg.d_kv, cfg.astra.groups,
                             cfg.astra.codebook_size)
            kc = vq.encode({"codebook": cb_k}, k_n.reshape(bl, 1, -1), spec)
            vc = vq.encode({"codebook": cb_v}, v_n.reshape(bl, 1, -1), spec)
            kp2, vp2 = _pool_write(kp, vp, dest, offs, kc[:, 0], vc[:, 0])
            codes_k, codes_v = _pool_view(kp2, vp2, loc_ids)
            if kernel_ok:
                from repro.kernels.ops import decode_attention_partials

                m_, l_, acc_ = decode_attention_partials(
                    q_l[:, 0], codes_k, codes_v, cb_k, cb_v, lens_local,
                    softcap=cap, use_pallas=True)
                out = merge_partial_stats(m_[..., None], l_[..., None],
                                          acc_[:, None], axis)
                return out, kp2, vp2
            k_shard = vq.decode({"codebook": cb_k},
                                codes_k.astype(jnp.int32), spec).reshape(
                bl, s_loc, cfg.num_kv_heads, cfg.head_dim)
            v_shard = vq.decode({"codebook": cb_v},
                                codes_v.astype(jnp.int32), spec).reshape(
                bl, s_loc, cfg.num_kv_heads, cfg.head_dim)
        else:
            kp2, vp2 = _pool_write(kp, vp, dest, offs, k_n[:, 0], v_n[:, 0])
            k_shard, v_shard = _pool_view(kp2, vp2, loc_ids)
        if pallas_on:
            from repro.kernels.ops import fp_decode_partials

            m_, l_, acc_ = fp_decode_partials(q_l[:, 0], k_shard, v_shard,
                                              lens_local, softcap=cap,
                                              use_pallas=True)
            out = merge_partial_stats(m_[..., None], l_[..., None],
                                      acc_[:, None], axis)
            return out, kp2, vp2
        pos = off + jnp.arange(s_loc)[None, :]
        valid = pos <= lens[:, None]
        m, l, o = partial_attention_stats(q_l, k_shard, v_shard,
                                          k_valid=valid, softcap=cap)
        out = merge_partial_stats(m, l, o, axis)
        return out, kp2, vp2

    qspec = P(bspec, None, None, None)
    pspec = P(*((axis,) + (None,) * (kp_in.ndim - 1)))
    in_specs = (qspec, qspec, qspec, pspec, pspec, P(bspec, None),
                P(bspec), P(), P())
    out_specs = (qspec, pspec, pspec)
    if vq_pool:
        cb_k = vq_params["k"]["codebook"]
        cb_v = vq_params["v"]["codebook"]
    else:
        cb_k = cb_v = jnp.zeros((1,), jnp.float32)
    with jax.named_scope("attn_kernel"):
        out, kp2, vp2 = shard_map(
            body, mesh=ctx.mesh.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)(q, k_new, v_new, kp_in, vp_in, table, lengths,
                             cb_k, cb_v)
    with jax.named_scope("attn_out"):
        y = out.reshape(b, 1, -1) @ params["wo"]
    new_cache = ({"k_code_pages": kp2, "v_code_pages": vp2} if vq_pool
                 else {"k_pages": kp2, "v_pages": vp2})
    return y, new_cache


def _paged_chunk_sharded(params, q, k_new, v_new, cache, chunk_start, table,
                         ctx, cfg, cap, vq_params):
    """Seq-sharded chunked prefill over sharded page pools: token-granular
    scatter of the chunk positions this shard owns through its table slice
    (everything else routes to the local scratch page), then the same
    local-view score + cross-shard partial merge as ``_chunk_sharded``.
    vq pools additionally carry the fp prefill-view scratch as sharded
    slabs, exactly mirroring the single-host paged_vq chunk step."""
    axis = ctx.mesh.seq_axis
    bspec = ctx.mesh.batch_axes if ctx.mesh.batch_axes else None
    b, w = q.shape[:2]
    vq_pool, ps, span_loc, s_loc = _paged_shard_geometry(cache, table, ctx)
    kp_in = cache["k_code_pages" if vq_pool else "k_pages"]
    vp_in = cache["v_code_pages" if vq_pool else "v_pages"]
    pallas_on = ctx.use_pallas
    cs = jnp.asarray(chunk_start, jnp.int32)

    def body(q_l, k_n, v_n, kp, vp, kf, vf, tab, cs_l, cb_k, cb_v):
        n_loc = kp.shape[0]
        i = jax.lax.axis_index(axis)
        off = i * s_loc
        tab_loc = jax.lax.dynamic_slice_in_dim(tab, i * span_loc, span_loc,
                                               axis=1)
        loc_ids = jnp.clip(tab_loc - i * n_loc, 0, n_loc - 1)
        bl = q_l.shape[0]
        loc_pos = cs_l + jnp.arange(w) - off  # (W,) shard-local positions
        inside = (loc_pos >= 0) & (loc_pos < s_loc)
        page_idx = jnp.clip(loc_pos // ps, 0, span_loc - 1)
        entry = loc_ids[:, page_idx]  # (B, W)
        dest = jnp.where(inside[None, :], entry, 0)
        offs = jnp.broadcast_to(jnp.where(inside, jnp.mod(loc_pos, ps), 0),
                                (bl, w))
        if vq_pool:
            spec = vq.VQSpec(cfg.d_kv, cfg.astra.groups,
                             cfg.astra.codebook_size)
            kc = vq.encode({"codebook": cb_k}, k_n.reshape(bl, w, -1), spec)
            vc = vq.encode({"codebook": cb_v}, v_n.reshape(bl, w, -1), spec)
            kp2, vp2 = _pool_write(kp, vp, dest, offs, kc, vc)
            kf2 = _shard_chunk_write(kf, k_n, loc_pos)
            vf2 = _shard_chunk_write(vf, v_n, loc_pos)
            k_view, v_view = kf2, vf2
        else:
            kp2, vp2 = _pool_write(kp, vp, dest, offs, k_n, v_n)
            kf2, vf2 = kf, vf
            k_view, v_view = _pool_view(kp2, vp2, loc_ids)
        out = _chunk_shard_merge(q_l, k_view, v_view, cs_l, off, cap, axis,
                                 pallas_on)
        return out, kp2, vp2, kf2, vf2

    qspec = P(bspec, None, None, None)
    cspec4 = P(bspec, axis, None, None)
    pspec = P(*((axis,) + (None,) * (kp_in.ndim - 1)))
    tspec = P(bspec, None)
    if vq_pool:
        in_specs = (qspec, qspec, qspec, pspec, pspec, cspec4, cspec4,
                    tspec, P(), P(), P())
        out_specs = (qspec, pspec, pspec, cspec4, cspec4)
        cb_k = vq_params["k"]["codebook"]
        cb_v = vq_params["v"]["codebook"]
        kf_in, vf_in = cache["k_fp"], cache["v_fp"]
    else:
        in_specs = (qspec, qspec, qspec, pspec, pspec, P(), P(),
                    tspec, P(), P(), P())
        out_specs = (qspec, pspec, pspec, P(), P())
        cb_k = cb_v = jnp.zeros((1,), jnp.float32)
        kf_in = vf_in = jnp.zeros((1,), jnp.float32)

    with jax.named_scope("attn_kernel"):
        out, kp2, vp2, kf2, vf2 = shard_map(
            body, mesh=ctx.mesh.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)(q, k_new, v_new, kp_in, vp_in, kf_in, vf_in,
                             table, cs, cb_k, cb_v)
    with jax.named_scope("attn_out"):
        y = out.reshape(b, w, -1) @ params["wo"]
    new_cache = ({"k_code_pages": kp2, "v_code_pages": vp2, "k_fp": kf2,
                  "v_fp": vf2} if vq_pool
                 else {"k_pages": kp2, "v_pages": vp2})
    return y, new_cache


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def get_backend(cache_mode: str, *, seq_sharded: bool = False) -> CacheBackend:
    """The singleton backend for one (cache_mode, sharded-ness) — the only
    place a cache-mode string is ever compared."""
    if cache_mode == "fp":
        base: CacheBackend = FPSlabBackend()
    elif cache_mode == "vq":
        base = VQSlabBackend()
    elif cache_mode == "paged":
        base = PagedBackend()
    elif cache_mode == "paged_vq":
        base = PagedVQBackend()
    else:
        raise ValueError(
            f"unknown cache_mode {cache_mode!r}; expected one of "
            f"{CACHE_MODES}")
    if seq_sharded:
        return ShardedBackend(base)
    return base
