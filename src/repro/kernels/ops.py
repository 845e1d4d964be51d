"""jit'd public wrappers for the Pallas kernels + the platform gate.

Interpret-mode contract
-----------------------
Every Pallas entry point in this package takes ``interpret=None`` by
default and resolves it through :func:`resolve_interpret` — compiled on
TPU, interpret-mode (the kernel body runs as traced jnp) everywhere else.
The old scheme (``interpret: bool = True`` with every caller remembering
``interpret=not ON_TPU``) shipped the interpreter to the TPU hot path the
moment one caller forgot; now no caller passes ``interpret`` at all unless
a test explicitly pins a mode.

Serving entry points
--------------------
``chunk_attention`` / ``decode_attention`` / ``coded_decode_attention`` are
the three calls ``serving.cache_backend`` routes through when
``StepCtx.use_pallas`` is set: chunked-prefill flash over an fp view,
flash decode over an fp slab/ring, and flash decode directly over VQ code
slabs (codes are never dequantized in HBM).  They accept the serving
layouts as-is ((B, T, H(kv), hd) / (B, S, G)) and return what the shared
jnp epilogues (``attention._masked_{chunk,decode}_attn``) would have
produced before the ``wo`` projection, so the backends keep one numerical
contract for both paths.  ``KERNEL_INVOCATIONS`` counts wrapper hits at
trace time so the conformance harness can assert the Pallas path really
engaged (a silent fallback would otherwise pass every parity test).
"""
from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.mixed_attn import (
    chunk_flash_attention,
    chunk_flash_partials,
    mixed_flash_attention,
)
from repro.kernels.vq_assign import vq_assign
from repro.kernels.vq_decode_attn import fp_decode_attention, vq_decode_attention

# trace-time routing counter: wrapper-name -> hits.  Incremented when the
# wrapper traces (the serving steps are jitted, so one hit per compiled
# shape); the conformance harness snapshots it around engine runs.
KERNEL_INVOCATIONS: collections.Counter = collections.Counter()

# trace-time counter of model-step paths that are not kernels, read the same
# way: "pool_in_place" counts layer-scan bodies traced with the paged pools
# resident in the scan's carry (``models.transformer.run_stages``), so a
# silent fall back to per-layer pool slices shows as zero hits.
PATH_INVOCATIONS: collections.Counter = collections.Counter()


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The single platform gate for every Pallas entry point: an explicit
    True/False wins; ``None`` (the default everywhere) runs compiled on TPU
    and interpret-mode on every other backend."""
    if interpret is None:
        return not on_tpu()
    return bool(interpret)


def vq_kernel_geometry_ok(num_kv_heads: int, groups: int) -> bool:
    """Whether the coded-decode kernel can split the VQ groups per kv head
    (it dequantizes ``groups / num_kv_heads`` whole groups per head block).
    When False the serving path dequantizes in jnp and still routes the
    attention itself through the fp flash kernel."""
    return (num_kv_heads > 0 and groups >= num_kv_heads
            and groups % num_kv_heads == 0)


# ---------------------------------------------------------------------------
# VQ assignment
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("groups", "use_pallas"))
def assign_codes(x: jax.Array, codebook: jax.Array, *, groups: int,
                 use_pallas: bool = False) -> jax.Array:
    """x: (..., D) -> codes (..., G) using the vq_assign kernel or oracle."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    dg = d // groups
    xg = x.reshape(-1, groups, dg)
    if use_pallas:
        # pad token dim to a block multiple
        t = xg.shape[0]
        bt = 256 if t >= 256 else t
        pad = (-t) % bt
        if pad:
            xg = jnp.concatenate([xg, jnp.zeros((pad, groups, dg), xg.dtype)], 0)
        codes = vq_assign(xg, codebook, block_t=bt)
        codes = codes[:t]
    else:
        codes = ref.vq_assign_ref(xg, codebook)
    return codes.reshape(*lead, groups)


# ---------------------------------------------------------------------------
# Mixed-precision prefill attention (local fp splice + remote codes)
# ---------------------------------------------------------------------------


def mixed_attention(q, k_local, v_local, k_codes, v_codes, cb_k, cb_v,
                    offset, *, causal=True, softcap=0.0, use_pallas=False,
                    block_q=128, block_kv=128, q_start=None):
    """(B,H,Tq,hd) x local FP KV x global codes -> (B,H,Tq,hd)."""
    if use_pallas:
        return mixed_flash_attention(
            q, k_local, v_local, k_codes, v_codes, cb_k, cb_v, offset,
            causal=causal, softcap=softcap, block_q=block_q,
            block_kv=block_kv, q_start=q_start)
    return ref.mixed_flash_ref(q, k_local, v_local, k_codes, v_codes,
                               cb_k, cb_v, offset, causal=causal,
                               softcap=softcap, q_start=q_start)


# ---------------------------------------------------------------------------
# Serving: chunked-prefill flash attention
# ---------------------------------------------------------------------------


def chunk_attention(q, k, v, k_pos, chunk_start, *, causal=True, window=0,
                    softcap=0.0, block_q=128, block_kv=128, interpret=None):
    """One chunked-prefill attention step, serving layout.

    q: (B, W, H, hd) chunk queries; k/v: (B, S, Hkv, hd) the attention view
    (written prefix / ring+chunk concat / gathered pages); k_pos: (S,)
    int32 global key positions (negative = invalid slot); chunk_start: ()
    traced int32 — the chunk's global query offset rides a scalar-prefetch
    operand, so walking the chunk grid never re-specializes.  Returns the
    normalized (B, W, H, hd) attention output (fp32), exactly what
    ``attention._masked_chunk_attn`` feeds its ``wo`` projection.
    """
    KERNEL_INVOCATIONS["chunk_attention"] += 1
    return chunk_flash_attention(q, k, v, k_pos, chunk_start, causal=causal,
                                 window=window, softcap=softcap,
                                 block_q=block_q, block_kv=block_kv,
                                 interpret=interpret)


def chunk_attention_partials(q, k, v, k_pos, chunk_start, *, causal=True,
                             window=0, softcap=0.0, use_pallas: bool = False,
                             block_q=128, block_kv=128):
    """Flash partials (m, l, acc) for one chunked-prefill step over one
    sequence shard's attention view — the chunk-wide sibling of
    ``fp_decode_partials`` (seq-sharded chunked prefill merges across
    shards with ``merge_partial_stats`` semantics).

    q: (B, W, H, hd); k/v: (B, S_loc, Hkv, hd); k_pos: (S_loc,) int32
    global key positions (negative = invalid slot); chunk_start: () traced
    int32.  Returns (m (B, H, W), l (B, H, W), acc (B, W, H, hd))."""
    if use_pallas:
        KERNEL_INVOCATIONS["chunk_attention_partials"] += 1
        return chunk_flash_partials(q, k, v, k_pos, chunk_start,
                                    causal=causal, window=window,
                                    softcap=softcap, block_q=block_q,
                                    block_kv=block_kv)
    return ref.chunk_flash_partials_ref(q, k, v, k_pos, chunk_start,
                                        causal=causal, window=window,
                                        softcap=softcap)


# ---------------------------------------------------------------------------
# Serving: flash decode over fp slabs / rings
# ---------------------------------------------------------------------------


def decode_attention(q, k, v, lengths, *, window=0, softcap=0.0,
                     block_kv=128, interpret=None):
    """One decode step over an fp slab or ring, serving layout.

    q: (B, 1, H, hd); k/v: (B, S, Hkv, hd); lengths: (B,) the new token's
    position.  Slot validity uses ring semantics (slot j holds the greatest
    position ≡ j mod S at or below ``lengths``), which reduces to the plain
    ``pos <= lengths`` mask whenever ``lengths < S`` — one mask covers the
    dense slab, the SWA ring and the page-table-gathered ring.  Returns the
    normalized (B, 1, H, hd) output.
    """
    KERNEL_INVOCATIONS["decode_attention"] += 1
    m, l, acc = fp_decode_attention(q[:, 0], k, v, lengths, window=window,
                                    softcap=softcap, block_kv=block_kv,
                                    interpret=interpret)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out[:, None]


def fp_decode_partials(q, k, v, lengths, *, window=0, softcap=0.0,
                       use_pallas: bool = False, block_kv=128):
    """Flash partials (m, l, acc) over an fp KV shard for one decode step —
    the fp sibling of ``decode_attention_partials`` (sequence-sharded decode
    merges across shards with ``merge_partial_stats`` semantics).
    q: (B, H, hd); k/v: (B, S, Hkv, hd); lengths: (B,)."""
    if use_pallas:
        KERNEL_INVOCATIONS["fp_decode_partials"] += 1
        return fp_decode_attention(q, k, v, lengths, window=window,
                                   softcap=softcap, block_kv=block_kv)
    return ref.fp_decode_attn_ref(q, k, v, lengths, window=window,
                                  softcap=softcap)


# ---------------------------------------------------------------------------
# Serving: flash decode over VQ code slabs (codes stay compressed in HBM)
# ---------------------------------------------------------------------------


def coded_decode_attention(q, k_codes, v_codes, cb_k, cb_v, lengths, *,
                           softcap=0.0, block_kv=128, interpret=None):
    """One decode step directly over a coded cache, serving layout.

    q: (B, 1, H, hd); codes: (B, S, G) any uint8/16/int dtype; cb: (G, K,
    dg); lengths: (B,).  The cache is dequantized block-by-block in VMEM —
    never materialized in HBM — and the normalized (B, 1, H, hd) output
    matches the dequantize-then-attend jnp path.
    """
    KERNEL_INVOCATIONS["coded_decode_attention"] += 1
    m, l, acc = vq_decode_attention(q[:, 0], k_codes, v_codes, cb_k, cb_v,
                                    lengths, softcap=softcap,
                                    block_kv=block_kv, interpret=interpret)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out[:, None]


@functools.partial(jax.jit,
                   static_argnames=("use_pallas", "block_kv", "softcap"))
def decode_attention_partials(q, k_codes, v_codes, cb_k, cb_v, lengths, *,
                              use_pallas: bool = False, softcap: float = 0.0,
                              block_kv: int = 128):
    """Flash partials (m, l, acc) over a VQ-coded cache for one decode step.

    q: (B, H, hd); codes: (B, S, G); lengths: (B,).  Merge across sequence
    shards with ``core.mixed_attention.merge_partial_stats`` semantics."""
    if use_pallas:
        KERNEL_INVOCATIONS["decode_attention_partials"] += 1
        return vq_decode_attention(q, k_codes, v_codes, cb_k, cb_v, lengths,
                                   softcap=softcap, block_kv=block_kv)
    return ref.vq_decode_attn_ref(q, k_codes, v_codes, cb_k, cb_v, lengths,
                                  softcap=softcap)
