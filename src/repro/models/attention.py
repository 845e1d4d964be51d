"""Attention layer: GQA projections + RoPE + (ASTRA mixed-precision |
full-precision) attention.  KV-cache storage (slab / codes / paged / shard)
is owned by ``serving.cache_backend`` — this module computes q/k/v and the
attention math, and hands cache init/prefill-write/decode-attend to
``ctx.backend`` so every layout shares one numerical epilogue.

Layer kinds: "attn" (global), "attn_nope" (global, no RoPE — llama4 iRoPE),
"local" (sliding window), "global" (gemma2 global half).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import vq
from repro.core.astra_block import (
    astra_kv_attention_sim,
    astra_kv_attention_spmd,
    sp_full_attention_spmd,
)
from repro.core.mixed_attention import (
    NEG_INF,
    _gqa_combine,
    _gqa_scores,
    _softcap,
    full_attention,
    partial_attention_stats,
)
from repro.models.context import StepCtx
from repro.models.layers import dense_init
from repro.models.rope import apply_rope


def kind_window(kind: str, cfg) -> int:
    return cfg.window_size if kind == "local" else 0


def kind_theta(kind: str, cfg) -> float:
    return 0.0 if kind == "attn_nope" else cfg.rope_theta


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_attention(key: jax.Array, cfg, dtype=jnp.float32) -> Dict[str, jax.Array]:
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, d, h * hd, dtype),
        "wk": dense_init(k2, d, hkv * hd, dtype),
        "wv": dense_init(k3, d, hkv * hd, dtype),
        "wo": dense_init(k4, h * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_scale"] = jnp.ones((hd,), dtype)
        p["k_scale"] = jnp.ones((hd,), dtype)
    return p


def init_astra_vq(key: jax.Array, cfg, dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Per-layer K/V codebooks for quantize_mode='kv' (C=2, Appendix G)."""
    spec = vq.VQSpec(cfg.d_kv, cfg.astra.groups, cfg.astra.codebook_size)
    kk, kv_ = jax.random.split(key)
    return {"k": vq.init(kk, spec, dtype), "v": vq.init(kv_, spec, dtype)}


def _rms(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


@jax.named_scope("qkv")
def qkv(params, x: jax.Array, cfg, positions, theta: float):
    b, t, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, t, h, hd)
    k = (x @ params["wk"]).reshape(b, t, hkv, hd)
    v = (x @ params["wv"]).reshape(b, t, hkv, hd)
    if cfg.qk_norm:
        q = _rms(q, params["q_scale"].astype(jnp.float32))
        k = _rms(k, params["k_scale"].astype(jnp.float32))
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    return q, k, v


@jax.named_scope("attn_out")
def out_proj(params, out: jax.Array) -> jax.Array:
    """(B, T, ...) attention output -> (B, T, D) through ``wo``."""
    b, t = out.shape[:2]
    return out.reshape(b, t, -1) @ params["wo"]


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def attention_forward(
    params: Dict[str, jax.Array],
    x: jax.Array,
    *,
    ctx: StepCtx,
    kind: str,
    causal: bool,
    vq_params: Optional[Dict] = None,
    navq_stats: Optional[Dict] = None,
    rng: Optional[jax.Array] = None,
    cache: Optional[Dict] = None,
    block_tables=None,
    lengths: Optional[jax.Array] = None,
    page_base: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, Optional[Dict]]:
    """Returns (y, aux, new_cache).  aux = dict(commit=.., navq=(per-dim
    residual mean/var for K and V) or zeros).  ``page_base`` (here and in
    the decode/verify/chunk entry points) is where this layer's pages start
    in the layer-merged pools the backend keeps resident
    (``CacheBackend.resident_keys``)."""
    cfg = ctx.cfg
    t = x.shape[1]
    window = kind_window(kind, cfg)
    theta = kind_theta(kind, cfg)
    positions = jnp.arange(t)[None, :]
    q, k, v = qkv(params, x, cfg, positions, theta)
    cap = cfg.attn_logit_softcap

    aux = _zero_aux(cfg)
    with jax.named_scope("attn_kernel"):
        if ctx.astra_on and kind != "local" and ctx.astra_mode == "sim":
            out, a = astra_kv_attention_sim(
                q, k, v, vq_params["k"], vq_params["v"], cfg.astra,
                num_shards=ctx.num_sim_shards, causal=causal, window=window,
                softcap=cap, train=ctx.train, rng=rng,
                navq_stats_k=navq_stats["k"] if navq_stats else None,
                navq_stats_v=navq_stats["v"] if navq_stats else None)
            aux = _aux_from_sim(a, cfg)
        elif ctx.astra_on and kind != "local" and ctx.astra_mode == "spmd":
            out = astra_kv_attention_spmd(
                ctx.mesh, q, k, v,
                vq_params["k"]["codebook"], vq_params["v"]["codebook"],
                cfg.astra, causal=causal, window=window, softcap=cap,
                chunk=ctx.attn_chunk)
        elif ctx.seq_sharded:
            # SP baseline (Voltage): full-precision K/V all-gather.  Local
            # (SWA) layers take the same path; the window mask bounds useful
            # work.
            out = sp_full_attention_spmd(
                ctx.mesh, q, k, v, causal=causal, window=window, softcap=cap,
                chunk=ctx.attn_chunk)
        else:
            pos = jnp.arange(t)
            out = full_attention(q, k, v, q_pos=pos, k_pos=pos, causal=causal,
                                 window=window, softcap=cap)

    new_cache = None
    if cache is not None:  # prefill writes the cache
        with jax.named_scope("kv_write"):
            new_cache = ctx.backend.prefill_write(
                cache, k, v, ctx=ctx, kind=kind, vq_params=vq_params,
                block_tables=block_tables, lengths=lengths,
                page_base=page_base)
    return out_proj(params, out), aux, new_cache


def _zero_aux(cfg) -> Dict[str, jax.Array]:
    dkv = max(cfg.d_kv, 1)
    z = jnp.zeros((dkv,), jnp.float32)
    return {
        "commit": jnp.zeros((), jnp.float32),
        "navq_k_mean": z, "navq_k_var": z,
        "navq_v_mean": z, "navq_v_var": z,
    }


def _aux_from_sim(a, cfg) -> Dict[str, jax.Array]:
    k_x, k_hat = a["k_pair"]
    v_x, v_hat = a["v_pair"]
    kr = (k_x - k_hat).astype(jnp.float32).reshape(-1, cfg.d_kv)
    vr = (v_x - v_hat).astype(jnp.float32).reshape(-1, cfg.d_kv)
    return {
        "commit": a["commit"],
        "navq_k_mean": jnp.mean(kr, 0), "navq_k_var": jnp.var(kr, 0),
        "navq_v_mean": jnp.mean(vr, 0), "navq_v_var": jnp.var(vr, 0),
    }


# ---------------------------------------------------------------------------
# KV cache: init / prefill-write / decode (delegated to ctx.backend)
# ---------------------------------------------------------------------------


def init_attn_cache(cfg, kind: str, batch: int, max_len: int, ctx: StepCtx,
                    dtype=jnp.bfloat16, *, page_size: int = 0,
                    num_pages=0,
                    prefill_scratch: bool = False) -> Dict[str, jax.Array]:
    """Per-layer cache pytree for this step's backend (``num_pages`` may be
    a per-page-group dict for the paged layouts)."""
    return ctx.backend.init_cache(cfg, kind, batch, max_len, dtype,
                                  page_size=page_size, num_pages=num_pages,
                                  prefill_scratch=prefill_scratch)


@jax.named_scope("kv_write")
def _write_at(buf: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """Per-batch dynamic write: buf (B, S, ...), new (B, 1, ...), idx (B,)."""
    def one(b, n, i):
        return jax.lax.dynamic_update_slice_in_dim(b, n.astype(b.dtype), i, axis=0)
    return jax.vmap(one)(buf, new, idx)


def ring_positions(slots: int, lengths: jax.Array) -> jax.Array:
    """Global position held in each ring slot after writing token at position
    ``lengths`` (B,) into slot ``lengths % W``.  Returns (B, W) positions
    (may be negative during warmup => invalid)."""
    s = jnp.arange(slots)[None, :]
    l = lengths[:, None]
    return l - jnp.mod(l - s, slots)


def attention_decode(
    params: Dict[str, jax.Array],
    x: jax.Array,
    cache: Dict[str, jax.Array],
    lengths: jax.Array,
    *,
    ctx: StepCtx,
    kind: str,
    vq_params: Optional[Dict] = None,
    block_tables=None,
    page_base: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step.  x: (B, 1, D); lengths: (B,) current sequence length
    (the new token's position).  Returns (y, new_cache)."""
    cfg = ctx.cfg
    positions = lengths[:, None]
    q, k_new, v_new = qkv(params, x, cfg, positions, kind_theta(kind, cfg))
    return ctx.backend.decode_attend(
        params, q, k_new, v_new, cache, lengths, ctx=ctx, kind=kind,
        vq_params=vq_params, block_tables=block_tables,
        page_base=page_base)


def attention_verify(
    params: Dict[str, jax.Array],
    x: jax.Array,  # (B, W, D) current token + k drafted continuations
    cache: Dict[str, jax.Array],
    starts: jax.Array,  # (B,) per-row position of the first verify token
    *,
    ctx: StepCtx,
    kind: str,
    vq_params: Optional[Dict] = None,
    block_tables=None,
    page_base: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Speculative verify step: score W = k+1 positions in one forward.

    Token j of row b sits at global position ``starts[b] + j`` — unlike the
    chunked-prefill path the offset is per-row, so RoPE and the causal mask
    ride (B, W) position grids.  The backend writes all W keys/values and
    attends each query over history + the drafted prefix before it, exactly
    as W sequential decode steps would.  Returns (y (B, W, D), new_cache);
    rejected positions leave stale K/V behind — callers roll the cache back
    (in-jit for rings via ``backend.verify_rollback``, host-side lengths for
    the rest)."""
    cfg = ctx.cfg
    w = x.shape[1]
    positions = starts[:, None] + jnp.arange(w)[None, :]
    q, k_new, v_new = qkv(params, x, cfg, positions, kind_theta(kind, cfg))
    return ctx.backend.verify_attend(
        params, q, k_new, v_new, cache, starts, ctx=ctx, kind=kind,
        vq_params=vq_params, block_tables=block_tables,
        page_base=page_base)


def _masked_decode_attn(params, q, k_all, v_all, valid, cap) -> jax.Array:
    """Shared single-token decode epilogue: masked partial-softmax stats,
    normalize, project through wo.  Every cache layout funnels through this
    so the cache modes cannot drift numerically."""
    with jax.named_scope("attn_kernel"):
        m, l, o = partial_attention_stats(q, k_all, v_all, k_valid=valid,
                                          softcap=cap)
        out = o / jnp.maximum(jnp.moveaxis(l, 1, 2)[..., None], 1e-30)
    return out_proj(params, out)


# ---------------------------------------------------------------------------
# Pallas epilogue twins (StepCtx.use_pallas): same y = flash(q, KV) @ wo
# contract as the jnp funnels above, but the score block never materializes
# — the online-softmax runs in the kernels (interpret on CPU, compiled TPU)
# ---------------------------------------------------------------------------


def _pallas_decode_attn(params, q, k_all, v_all, lengths, window,
                        cap) -> jax.Array:
    """Pallas twin of ``_masked_decode_attn`` for fp views: the validity
    mask is derived inside the kernel from ``lengths`` with ring semantics
    (identical to the dense masks for every serving layout — see
    ``kernels.vq_decode_attn``)."""
    from repro.kernels import ops

    with jax.named_scope("attn_kernel"):
        out = ops.decode_attention(q, k_all, v_all, lengths, window=window,
                                   softcap=cap)
    return out_proj(params, out)


def _pallas_coded_decode_attn(params, q, k_codes, v_codes, vq_params,
                              lengths, cap) -> jax.Array:
    """Decode directly over a coded cache: VQ codes are dequantized
    block-by-block in VMEM, never materialized in HBM (the jnp path
    dequantizes the whole cache first)."""
    from repro.kernels import ops

    with jax.named_scope("attn_kernel"):
        out = ops.coded_decode_attention(
            q, k_codes, v_codes, vq_params["k"]["codebook"],
            vq_params["v"]["codebook"], lengths, softcap=cap)
    return out_proj(params, out)


def _pallas_chunk_attn(params, q, k_all, v_all, chunk_start, k_pos, window,
                       cap) -> jax.Array:
    """Pallas twin of ``_masked_chunk_attn``: ``chunk_start`` rides the
    kernel's scalar-prefetch operand (traced — the chunk grid walk never
    re-specializes) and ``k_pos`` (1-d, negative = invalid slot) carries
    the prefix/ring key-position map."""
    from repro.kernels import ops

    with jax.named_scope("attn_kernel"):
        out = ops.chunk_attention(q, k_all, v_all, k_pos, chunk_start,
                                  causal=True, window=window, softcap=cap)
    return out_proj(params, out)


def attention_chunk(
    params: Dict[str, jax.Array],
    x: jax.Array,  # (B, W, D) one prefill chunk of hidden states
    cache: Dict[str, jax.Array],
    chunk_start: jax.Array,  # scalar int32: global offset of this chunk
    lengths: jax.Array,  # (B,) true prompt length per row
    *,
    ctx: StepCtx,
    kind: str,
    vq_params: Optional[Dict] = None,
    block_tables=None,
    history_len: int = 0,
    page_base: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One chunked-prefill step: RoPE at the chunk's global positions, then
    the backend writes the chunk's K/V into the cache and attends causally
    over everything written so far (viewing at most the first
    ``history_len`` positions when set).  Returns (y, new_cache)."""
    cfg = ctx.cfg
    w = x.shape[1]
    positions = chunk_start + jnp.arange(w)[None, :]
    q, k_new, v_new = qkv(params, x, cfg, positions, kind_theta(kind, cfg))
    return ctx.backend.chunk_attend(
        params, q, k_new, v_new, cache, chunk_start, lengths, ctx=ctx,
        kind=kind, vq_params=vq_params, block_tables=block_tables,
        history_len=history_len, page_base=page_base)


def _masked_chunk_attn(params, q, k_all, v_all, q_pos, k_pos, window,
                       cap) -> jax.Array:
    """Multi-query analogue of ``_masked_decode_attn`` for a prefill chunk.

    q: (B, W, H, hd); k_all/v_all: (B, S, Hkv, hd); q_pos (W,) or per-row
    (B, W) global query positions; k_pos (S,) or per-row (B, S) global key
    positions, negative = invalid slot.  Masking is causal (+ sliding
    window); rows/positions with no valid key (padding queries) normalize
    against an epsilon instead of NaN-ing, exactly like the decode
    epilogue."""
    with jax.named_scope("attn_kernel"):
        b = q.shape[0]
        kp = k_pos if k_pos.ndim == 2 else jnp.broadcast_to(
            k_pos[None], (b, k_pos.shape[-1]))
        qp = q_pos if q_pos.ndim == 2 else jnp.broadcast_to(
            q_pos[None], (b, q_pos.shape[-1]))
        valid = (kp[:, None, :] >= 0) & (kp[:, None, :] <= qp[:, :, None])
        if window:
            valid &= kp[:, None, :] > qp[:, :, None] - window
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
        s = _softcap(_gqa_scores(q, k_all, scale), cap)  # (B, H, W, S)
        s = jnp.where(valid[:, None], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(valid[:, None], jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1)  # (B, H, W)
        out = _gqa_combine(p, v_all)  # (B, W, H, hd) un-normalised
        out = out / jnp.maximum(jnp.moveaxis(l, 1, 2)[..., None], 1e-30)
    return out_proj(params, out)
