"""Pallas TPU kernels: flash-decoding over VQ-compressed and fp KV caches.

``vq_decode_attention`` — the Appendix-G runtime stores non-local KV as VQ
codes (uint8/16 per group).  At decode, the reference path dequantizes the
WHOLE cache to bf16 in HBM (S x d_kv bytes) before attention; this kernel
keeps codes in HBM and dequantizes block-by-block in VMEM while running
the online-softmax loop — the decode-side sibling of ``mixed_attn.py``
(HBM traffic drops by the dequant ratio, ~12.8x for G=32/K=1024 vs bf16).

``fp_decode_attention`` — the same flash-decoding loop over a
full-precision slab or ring: the serving path for every layout whose
decode view is fp (dense slabs, SWA rings, page-table-gathered tiles, and
coded layers whose group geometry the vq kernel cannot split).  Slot
validity uses *ring semantics*: slot ``j`` holds the greatest position
``p ≡ j (mod S)`` at or below ``lengths`` — exactly
``attention.ring_positions`` — which degenerates to the plain
``pos <= lengths`` prefix mask whenever ``lengths < S``, so one mask
covers dense and windowed layouts alike.

Both emit per-device flash partials (m, l, acc) so the sequence-sharded
decode can merge across shards with ``merge_partial_stats`` (one tiny
collective), exactly mirroring ``attention._decode_sharded``.

Grid: (B, Hkv, S/bkv), kv innermost; scratch carries the flash state.
Key spans that don't divide ``block_kv`` are zero-padded and the padded
slots masked out via the static real length.

TPU tiling: every block's last two dims are (8, 128)-aligned or the whole
array's, so the coded kernels read each kv head's codes from a
(B, Hkv, S, gph) copy (``head_codes``) and dequantize with one-hot x
codebook matmuls (``dequant_tile``): the TPU compiler lowers neither a
(bkv, gph) window onto the G axis nor a gather inside a kernel.  The m / l
partials leave the kernels as (rows, 1) columns (``flash.update``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import flash

NEG_INF = flash.NEG_INF


def head_codes(codes: jax.Array, hkv: int) -> jax.Array:
    """(B, S, G) codes of any integer dtype -> (B, Hkv, S, gph) int32: each
    kv head's ``gph = G / Hkv`` groups as one array axis, so a (bkv, gph)
    block spans that axis whole."""
    b, s, g = codes.shape
    return jnp.moveaxis(
        codes.astype(jnp.int32).reshape(b, s, hkv, g // hkv), 2, 1)


def head_codebooks(cb: jax.Array, hkv: int) -> jax.Array:
    """(G, K, dg) codebooks -> (G, K, hd): group ``g`` zero-padded into its
    own ``dg`` columns of its kv head's dim, so the sum of a head's ``gph``
    one-hot products is its (bkv, hd) tile with no lane concatenation.
    Padding, not a placement matmul, keeps the values exact.  Kv head ``i``
    reads the (gph, K, hd) block ``i``."""
    g, k, dg = cb.shape
    gph = g // hkv
    hd = gph * dg
    cbh = cb.reshape(hkv, gph, k, dg)
    return jnp.stack(
        [jnp.pad(cbh[:, j], ((0, 0), (0, 0), (j * dg, hd - (j + 1) * dg)))
         for j in range(gph)], axis=1).reshape(g, k, hd)


def dequant_tile(codes: jax.Array, cb_ref) -> jax.Array:
    """codes (bkv, gph) int32 x ``cb_ref`` (gph, K, hd) -> the (bkv, hd)
    fp32 tile, as one one-hot x codebook matmul per group.  HIGHEST
    precision keeps the selected fp32 codebook rows exact on the MXU, so
    this equals the ``jnp.take`` dequantize of ``ref.dequant_head``."""
    gph, k, _ = cb_ref.shape
    ids = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], k), 1)
    tile = None
    for j in range(gph):
        onehot = (codes[:, j:j + 1] == ids).astype(jnp.float32)
        part = jax.lax.dot_general(
            onehot, cb_ref[j].astype(jnp.float32), (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        tile = part if tile is None else tile + part
    return tile


def _kernel(lengths_ref, q_ref, kc_ref, vc_ref, cbk_ref, cbv_ref,
            m_ref, l_ref, acc_ref, m_s, l_s, acc_s, *,
            bkv, nkb, s_real, hd, rep, softcap):
    ki = pl.program_id(2)
    bi = pl.program_id(0)
    length = lengths_ref[bi]

    @pl.when(ki == 0)
    def _init():
        flash.init_state(m_s, l_s, acc_s)

    k_tile = dequant_tile(kc_ref[0, 0], cbk_ref)  # (bkv, hd)
    v_tile = dequant_tile(vc_ref[0, 0], cbv_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (rep, hd) — queries of this kv head
    s = jax.lax.dot_general(q, k_tile, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (rep, bkv), 1)
    valid = jnp.logical_and(pos < s_real, pos <= length)
    s = jnp.where(valid, s, NEG_INF)
    flash.update(m_s, l_s, acc_s, s, valid, v_tile)

    @pl.when(ki == nkb - 1)
    def _emit():
        m_ref[0, 0] = m_s[...]
        l_ref[0, 0] = l_s[...]
        acc_ref[0, 0] = acc_s[...]


@functools.partial(jax.jit,
                   static_argnames=("block_kv", "softcap", "interpret"))
def vq_decode_attention(
    q: jax.Array,  # (B, H, hd) — one decode step's queries
    k_codes: jax.Array,  # (B, S, G) any uint8/16/int dtype
    v_codes: jax.Array,
    cb_k: jax.Array,  # (G, K, dg)
    cb_v: jax.Array,
    lengths: jax.Array,  # (B,) — positions <= lengths[b] are valid
    *,
    softcap: float = 0.0,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
):
    """Returns flash partials (m (B,H), l (B,H), acc (B,H,hd)) over the
    coded cache.  out = acc / l; cross-shard merging follows
    ``merge_partial_stats`` semantics."""
    from repro.kernels.ops import resolve_interpret

    b, h, hd = q.shape
    s, g = k_codes.shape[1], k_codes.shape[2]
    k = cb_k.shape[1]
    dg = cb_k.shape[2]
    # infer kv-head grouping from the code groups: gph groups per kv head
    hkv = (g * dg) // hd
    rep = h // hkv
    gph = g // hkv
    assert gph * dg == hd, (gph, dg, hd)
    bkv = min(block_kv, s)
    pad = (-s) % bkv
    kc = head_codes(k_codes, hkv)  # (B, Hkv, S, gph) int32
    vc = head_codes(v_codes, hkv)
    if pad:  # zero-pad to a block multiple; code 0 is valid, mask rejects
        kc = jnp.pad(kc, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nkb = (s + pad) // bkv

    qg = q.reshape(b, hkv, rep, hd)
    grid = (b, hkv, nkb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), lambda bi, gi, ki, L: (bi, gi, 0, 0)),
            pl.BlockSpec((1, 1, bkv, gph), lambda bi, gi, ki, L: (bi, gi, ki, 0)),
            pl.BlockSpec((1, 1, bkv, gph), lambda bi, gi, ki, L: (bi, gi, ki, 0)),
            pl.BlockSpec((gph, k, hd), lambda bi, gi, ki, L: (gi, 0, 0)),
            pl.BlockSpec((gph, k, hd), lambda bi, gi, ki, L: (gi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rep, 1), lambda bi, gi, ki, L: (bi, gi, 0, 0)),
            pl.BlockSpec((1, 1, rep, 1), lambda bi, gi, ki, L: (bi, gi, 0, 0)),
            pl.BlockSpec((1, 1, rep, hd), lambda bi, gi, ki, L: (bi, gi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, hd), jnp.float32),
        ],
    )
    kern = functools.partial(_kernel, bkv=bkv, nkb=nkb, s_real=s, hd=hd,
                             rep=rep, softcap=softcap)
    m, l, acc = pl.pallas_call(
        kern,
        name="vq_decode_attention",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, rep, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, rep, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, rep, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(lengths.astype(jnp.int32), qg, kc, vc, head_codebooks(cb_k, hkv),
      head_codebooks(cb_v, hkv))
    return (m.reshape(b, h), l.reshape(b, h), acc.reshape(b, h, hd))


# ---------------------------------------------------------------------------
# fp flash decode (dense slabs, SWA rings, gathered page tiles)
# ---------------------------------------------------------------------------


def _fp_kernel(lengths_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
               m_s, l_s, acc_s, *, bkv, nkb, s_real, hd, rep, window,
               softcap):
    ki = pl.program_id(2)
    bi = pl.program_id(0)
    length = lengths_ref[bi]

    @pl.when(ki == 0)
    def _init():
        flash.init_state(m_s, l_s, acc_s)

    k_tile = k_ref[0, 0].astype(jnp.float32)  # (bkv, hd)
    v_tile = v_ref[0, 0].astype(jnp.float32)
    q = q_ref[0, 0].astype(jnp.float32)       # (rep, hd)
    s = jax.lax.dot_general(q, k_tile, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    # ring semantics: slot j holds the greatest position ≡ j (mod S) at or
    # below `length` (== j itself whenever length < S); negative = warmup.
    j = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (rep, bkv), 1)
    pos = length - jnp.mod(length - j, s_real)
    valid = jnp.logical_and(j < s_real,
                            jnp.logical_and(pos >= 0, pos <= length))
    if window:
        valid = jnp.logical_and(valid, pos > length - window)
    s = jnp.where(valid, s, NEG_INF)
    flash.update(m_s, l_s, acc_s, s, valid, v_tile)

    @pl.when(ki == nkb - 1)
    def _emit():
        m_ref[0, 0] = m_s[...]
        l_ref[0, 0] = l_s[...]
        acc_ref[0, 0] = acc_s[...]


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "block_kv", "interpret"))
def fp_decode_attention(
    q: jax.Array,        # (B, H, hd) — one decode step's queries
    k: jax.Array,        # (B, S, Hkv, hd) fp slab / ring / gathered tile
    v: jax.Array,
    lengths: jax.Array,  # (B,) — the new token's position per row
    *,
    window: int = 0,
    softcap: float = 0.0,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
):
    """Returns flash partials (m (B,H), l (B,H), acc (B,H,hd)) over an fp
    KV view with ring-semantics masking (see module docstring).  out =
    acc / l; cross-shard merging follows ``merge_partial_stats``."""
    from repro.kernels.ops import resolve_interpret

    b, h, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    assert h % hkv == 0, (h, hkv)
    rep = h // hkv
    bkv = min(block_kv, s)
    pad = (-s) % bkv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkb = (s + pad) // bkv

    qg = q.reshape(b, hkv, rep, hd)
    kt = jnp.moveaxis(k, 2, 1)  # (B, Hkv, Sk, hd)
    vt = jnp.moveaxis(v, 2, 1)
    grid = (b, hkv, nkb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), lambda bi, gi, ki, L: (bi, gi, 0, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda bi, gi, ki, L: (bi, gi, ki, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda bi, gi, ki, L: (bi, gi, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rep, 1), lambda bi, gi, ki, L: (bi, gi, 0, 0)),
            pl.BlockSpec((1, 1, rep, 1), lambda bi, gi, ki, L: (bi, gi, 0, 0)),
            pl.BlockSpec((1, 1, rep, hd), lambda bi, gi, ki, L: (bi, gi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, hd), jnp.float32),
        ],
    )
    kern = functools.partial(_fp_kernel, bkv=bkv, nkb=nkb, s_real=s, hd=hd,
                             rep=rep, window=window, softcap=softcap)
    m, l, acc = pl.pallas_call(
        kern,
        name="fp_decode_attention",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, rep, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, rep, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, rep, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return (m.reshape(b, h), l.reshape(b, h), acc.reshape(b, h, hd))
