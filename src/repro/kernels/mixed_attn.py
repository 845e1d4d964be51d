"""Pallas TPU kernels: ASTRA mixed-precision flash attention + the serving
chunked-prefill flash step.

``mixed_flash_attention`` is the TPU adaptation of the paper's
Mixed-Precision Attention (DESIGN.md §2): instead of materialising the
dequantized K-hat/V-hat (T x d_kv bf16) in HBM and then running attention
over them, the kernel keeps VQ *codes* in HBM and dequantizes
block-by-block in VMEM while running the online-softmax (flash) loop.  HBM
traffic for the remote sequence drops from T*hd*2 bytes to T*gph*4 bytes
per kv-head (~8-64x less), directly attacking the memory roofline term of
the attention layer.

Blocks entirely inside the device's local shard use the full-precision
local K/V tile instead (eq. (1) splice); the caller guarantees the local
range is block-aligned.  ``q_start`` decouples the query offset from the
local-KV splice offset (both ride the scalar-prefetch operand), so a
prefix view — queries covering only a slice of the key range — traces once
per *shape*, never per offset.

``chunk_flash_attention`` is the serving sibling used by the chunked
prefill pipeline (``serving.cache_backend.chunk_attend``): fp K/V view,
causal-within-chunk + prefix masking against an explicit key-position map
(ring slots pass their real positions, negative = invalid), optional
sliding window, traced ``chunk_start``.  It replaces
``attention._masked_chunk_attn``'s dense (B, H, W, view) score block with
an online-softmax loop over (bq, bkv) tiles.

Grid: (B, H, Tq/bq, T/bkv) with the kv dim innermost; (m, l, acc) scratch
carries the flash state across kv blocks.  Scalar operands arrive via
``PrefetchScalarGridSpec`` so index_maps and masks can depend on them.
Codes and codebooks take the per-kv-head layouts of
``vq_decode_attn.head_codes`` / ``head_codebooks`` (the TPU tiling rule),
and the m / l partials leave ``chunk_flash_partials`` as (rows, 1) columns.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import flash
from repro.kernels.vq_decode_attn import dequant_tile, head_codebooks, head_codes

NEG_INF = flash.NEG_INF


def _kernel(offs_ref, q_ref, kl_ref, vl_ref, kc_ref, vc_ref, cbk_ref,
            cbv_ref, out_ref, m_s, l_s, acc_s, *, bq, bkv, nkb, hd,
            causal, softcap, tl):
    ki = pl.program_id(3)
    qi = pl.program_id(2)
    offset = offs_ref[0]
    q_start = offs_ref[1]

    @pl.when(ki == 0)
    def _init():
        flash.init_state(m_s, l_s, acc_s)

    # --- assemble the kv tile: dequantized codes or local FP --------------
    k_hat = dequant_tile(kc_ref[0, 0], cbk_ref)  # (bkv, hd) fp32
    v_hat = dequant_tile(vc_ref[0, 0], cbv_ref)
    k_loc = kl_ref[0, 0]  # (bkv, hd) — local tile (clamped index when remote)
    v_loc = vl_ref[0, 0]
    is_local = jnp.logical_and(ki * bkv >= offset, ki * bkv < offset + tl)
    k_tile = jnp.where(is_local, k_loc.astype(jnp.float32), k_hat)
    v_tile = jnp.where(is_local, v_loc.astype(jnp.float32), v_hat)

    # --- flash update ------------------------------------------------------
    q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
    s = jax.lax.dot_general(q, k_tile, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    valid = jnp.ones((bq, bkv), bool)
    if causal:
        q_pos = q_start + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        k_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        valid = q_pos >= k_pos
        s = jnp.where(valid, s, NEG_INF)
    flash.update(m_s, l_s, acc_s, s, valid, v_tile)

    @pl.when(ki == nkb - 1)
    def _emit():
        out_ref[0, 0] = flash.normalized(acc_s[...],
                                         l_s[...]).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "softcap", "block_q", "block_kv", "interpret"))
def mixed_flash_attention(
    q: jax.Array,  # (B, H, Tq, hd)
    k_local: jax.Array,  # (B, Hkv, Tl, hd)
    v_local: jax.Array,
    k_codes: jax.Array,  # (B, T, G)
    v_codes: jax.Array,
    cb_k: jax.Array,  # (G, K, dg)
    cb_v: jax.Array,
    offset: jax.Array,  # () int32, multiple of block_kv
    *,
    causal: bool = True,
    softcap: float = 0.0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
    q_start: Optional[jax.Array] = None,  # () int32 query offset; None = offset
) -> jax.Array:
    from repro.kernels.ops import resolve_interpret

    b, h, tq, hd = q.shape
    hkv, tl = k_local.shape[1], k_local.shape[2]
    t, g = k_codes.shape[1], k_codes.shape[2]
    k = cb_k.shape[1]
    dg = cb_k.shape[2]
    rep = h // hkv
    gph = g // hkv
    assert gph * dg == hd, (gph, dg, hd)
    bq = min(block_q, tq)
    bkv = min(block_kv, t)
    assert tq % bq == 0 and t % bkv == 0 and tl % bkv == 0
    nkb = t // bkv
    nlb = tl // bkv

    grid = (b, h, tq // bq, nkb)

    def li(bi, hi, qi, ki, off_ref):
        """local tile index, clamped into range when the kv block is remote"""
        blk = ki - off_ref[0] // bkv
        return (bi, hi // rep, jnp.clip(blk, 0, nlb - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi, ki, o: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bkv, hd), li),
            pl.BlockSpec((1, 1, bkv, hd), li),
            pl.BlockSpec((1, 1, bkv, gph), lambda bi, hi, qi, ki, o: (bi, hi // rep, ki, 0)),
            pl.BlockSpec((1, 1, bkv, gph), lambda bi, hi, qi, ki, o: (bi, hi // rep, ki, 0)),
            pl.BlockSpec((gph, k, hd), lambda bi, hi, qi, ki, o: (hi // rep, 0, 0)),
            pl.BlockSpec((gph, k, hd), lambda bi, hi, qi, ki, o: (hi // rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda bi, hi, qi, ki, o: (bi, hi, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    kern = functools.partial(
        _kernel, bq=bq, bkv=bkv, nkb=nkb, hd=hd, causal=causal,
        softcap=softcap, tl=tl)
    offset = jnp.asarray(offset, jnp.int32)
    qs = offset if q_start is None else jnp.asarray(q_start, jnp.int32)
    offs = jnp.stack([offset, qs]).reshape(2)
    return pl.pallas_call(
        kern,
        name="mixed_flash_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=resolve_interpret(interpret),
    )(offs, q, k_local, v_local, head_codes(k_codes, hkv),
      head_codes(v_codes, hkv), head_codebooks(cb_k, hkv),
      head_codebooks(cb_v, hkv))


# ---------------------------------------------------------------------------
# Serving: chunked-prefill flash attention (fp view, explicit key positions)
# ---------------------------------------------------------------------------


def _chunk_kernel(cs_ref, q_ref, k_ref, v_ref, kp_ref, out_ref, m_s, l_s,
                  acc_s, *, bq, bkv, nkb, hd, causal, window, softcap):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        flash.init_state(m_s, l_s, acc_s)

    q = q_ref[0, 0].astype(jnp.float32)      # (bq, hd)
    k_t = k_ref[0, 0].astype(jnp.float32)    # (bkv, hd)
    v_t = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k_t, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = cs_ref[0] + qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bkv), 0)
    k_pos = jnp.broadcast_to(kp_ref[0][None, :], (bq, bkv))
    valid = k_pos >= 0  # negative = invalid slot (ring warmup / padding)
    if causal:
        valid = jnp.logical_and(valid, k_pos <= q_pos)
    if window:
        valid = jnp.logical_and(valid, k_pos > q_pos - window)
    s = jnp.where(valid, s, NEG_INF)
    flash.update(m_s, l_s, acc_s, s, valid, v_t)

    @pl.when(ki == nkb - 1)
    def _emit():
        out_ref[0, 0] = flash.normalized(acc_s[...], l_s[...])


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_kv",
                     "interpret"))
def chunk_flash_attention(
    q: jax.Array,      # (B, W, H, hd) — one prefill chunk's queries
    k: jax.Array,      # (B, S, Hkv, hd) — the attention view
    v: jax.Array,
    k_pos: jax.Array,  # (S,) int32 global key positions, negative = invalid
    chunk_start: jax.Array,  # () int32 — global offset of the chunk (traced)
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention for one chunked-prefill step.

    Masking: a key slot is attendable iff ``k_pos[j] >= 0`` (ring slots with
    no real source are negative), ``k_pos[j] <= q_pos`` (causal) and, for
    windowed layers, ``k_pos[j] > q_pos - window``, with
    ``q_pos = chunk_start + query index``.  ``chunk_start`` rides the
    scalar-prefetch operand so the grid walk never re-specializes; query /
    key spans that don't divide the block sizes are zero-padded (padded key
    slots carry ``k_pos = -1``; padded query rows are sliced off).  Returns
    the normalized (B, W, H, hd) output in fp32, matching the precision of
    the dense jnp epilogue it replaces.
    """
    from repro.kernels.ops import resolve_interpret

    b, w, h, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    assert h % hkv == 0, (h, hkv)
    rep = h // hkv
    bq = min(block_q, w)
    bkv = min(block_kv, s)
    pad_q = (-w) % bq
    pad_kv = (-s) % bkv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad_kv), constant_values=-1)
    wq, sk = w + pad_q, s + pad_kv
    nkb = sk // bkv

    # kernel-friendly layouts: heads outermost, (token, hd) innermost tiles
    qt = jnp.moveaxis(q, 2, 1)   # (B, H, Wq, hd)
    kt = jnp.moveaxis(k, 2, 1)   # (B, Hkv, Sk, hd)
    vt = jnp.moveaxis(v, 2, 1)

    grid = (b, h, wq // bq, nkb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi, ki, cs: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda bi, hi, qi, ki, cs: (bi, hi // rep, ki, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda bi, hi, qi, ki, cs: (bi, hi // rep, ki, 0)),
            pl.BlockSpec((1, bkv), lambda bi, hi, qi, ki, cs: (0, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda bi, hi, qi, ki, cs: (bi, hi, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    kern = functools.partial(_chunk_kernel, bq=bq, bkv=bkv, nkb=nkb, hd=hd,
                             causal=causal, window=window, softcap=softcap)
    out = pl.pallas_call(
        kern,
        name="chunk_flash_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, wq, hd), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(jnp.reshape(jnp.asarray(chunk_start, jnp.int32), (1,)), qt, kt, vt,
      k_pos.astype(jnp.int32).reshape(1, sk))
    return jnp.moveaxis(out, 1, 2)[:, :w]


def _chunk_partials_kernel(cs_ref, q_ref, k_ref, v_ref, kp_ref, m_ref, l_ref,
                           acc_ref, m_s, l_s, acc_s, *, bq, bkv, nkb, hd,
                           causal, window, softcap):
    """``_chunk_kernel`` body, but the emit keeps the flash statistics
    un-normalised: (m, l, acc) per query row, for cross-shard merging."""
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        flash.init_state(m_s, l_s, acc_s)

    q = q_ref[0, 0].astype(jnp.float32)      # (bq, hd)
    k_t = k_ref[0, 0].astype(jnp.float32)    # (bkv, hd)
    v_t = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k_t, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = cs_ref[0] + qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bkv), 0)
    k_pos = jnp.broadcast_to(kp_ref[0][None, :], (bq, bkv))
    valid = k_pos >= 0
    if causal:
        valid = jnp.logical_and(valid, k_pos <= q_pos)
    if window:
        valid = jnp.logical_and(valid, k_pos > q_pos - window)
    s = jnp.where(valid, s, NEG_INF)
    flash.update(m_s, l_s, acc_s, s, valid, v_t)

    @pl.when(ki == nkb - 1)
    def _emit():
        m_ref[0, 0] = m_s[...]
        l_ref[0, 0] = l_s[...]
        acc_ref[0, 0] = acc_s[...]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_kv",
                     "interpret"))
def chunk_flash_partials(
    q: jax.Array,      # (B, W, H, hd) — one prefill chunk's queries
    k: jax.Array,      # (B, S_loc, Hkv, hd) — one shard's attention view
    v: jax.Array,
    k_pos: jax.Array,  # (S_loc,) int32 global key positions, negative = invalid
    chunk_start: jax.Array,  # () int32 — global offset of the chunk (traced)
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
):
    """Partials twin of ``chunk_flash_attention`` for the seq-sharded
    chunked prefill: same masking and online-softmax recurrence, but the
    per-row statistics leave the kernel un-normalised so the caller merges
    them across shards with ``merge_partial_stats``.  Returns
    (m (B, H, W), l (B, H, W), acc (B, W, H, hd)), fp32; padded query rows
    are sliced off (their m stays at the flash init floor)."""
    from repro.kernels.ops import resolve_interpret

    b, w, h, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    assert h % hkv == 0, (h, hkv)
    rep = h // hkv
    bq = min(block_q, w)
    bkv = min(block_kv, s)
    pad_q = (-w) % bq
    pad_kv = (-s) % bkv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad_kv), constant_values=-1)
    wq, sk = w + pad_q, s + pad_kv
    nkb = sk // bkv

    qt = jnp.moveaxis(q, 2, 1)   # (B, H, Wq, hd)
    kt = jnp.moveaxis(k, 2, 1)   # (B, Hkv, Sk, hd)
    vt = jnp.moveaxis(v, 2, 1)

    grid = (b, h, wq // bq, nkb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi, ki, cs: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda bi, hi, qi, ki, cs: (bi, hi // rep, ki, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda bi, hi, qi, ki, cs: (bi, hi // rep, ki, 0)),
            pl.BlockSpec((1, bkv), lambda bi, hi, qi, ki, cs: (0, ki)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi, ki, cs: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi, ki, cs: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, hd),
                         lambda bi, hi, qi, ki, cs: (bi, hi, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    kern = functools.partial(_chunk_partials_kernel, bq=bq, bkv=bkv, nkb=nkb,
                             hd=hd, causal=causal, window=window,
                             softcap=softcap)
    m, l, acc = pl.pallas_call(
        kern,
        name="chunk_flash_partials",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, wq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, wq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, wq, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(jnp.reshape(jnp.asarray(chunk_start, jnp.int32), (1,)), qt, kt, vt,
      k_pos.astype(jnp.int32).reshape(1, sk))
    return m[:, :, :w, 0], l[:, :, :w, 0], jnp.moveaxis(acc, 1, 2)[:, :w]
