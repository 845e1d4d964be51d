"""Shared online-softmax (flash) state algebra for the Pallas kernels.

Four kernel bodies (mixed / chunk prefill attention, fp / coded flash
decode) carry the same numerically delicate recurrence across kv blocks:

    m' = max(m, max_j s_j)                 running row max
    p  = where(valid, exp(s - m'), 0)      shifted probabilities
    l' = l * exp(m - m') + sum_j p_j       running normalizer
    a' = a * exp(m - m') + p @ V           running weighted values

Keeping it in one place pins the rescale ordering and the normalizer
epsilon once — the conformance harness's permutation-of-arrival property
test then covers every kernel that calls it.  All helpers operate on the
kernels' VMEM scratch refs in place.  The per-row statistics ``m`` and
``l`` are (rows, 1) columns, not 1-d vectors: a column keeps the row axis
on sublanes, so the TPU compiler tiles the scratch, the broadcasts against
the (rows, bkv) score block and the kernels' m / l output blocks without a
lane-to-sublane relayout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def init_state(m_s, l_s, acc_s) -> None:
    """Reset the (m, l, acc) scratch at the first kv block of a row."""
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)


def update(m_s, l_s, acc_s, s: jax.Array, valid: jax.Array,
           v_tile: jax.Array) -> None:
    """One kv-block update.  ``s``: (rows, bkv) fp32 scores already set to
    NEG_INF where invalid; ``valid``: bool, same shape (zeroes p exactly so
    a fully-masked row accumulates nothing); ``v_tile``: (bkv, hd) fp32;
    ``m_s`` / ``l_s``: (rows, 1)."""
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
        p, v_tile, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_s[...] = m_new


def normalized(acc: jax.Array, l: jax.Array) -> jax.Array:
    """acc / l with the shared epsilon (fully-masked rows emit 0, matching
    the jnp epilogues); ``l``: (rows, 1)."""
    return acc / jnp.maximum(l, 1e-30)
