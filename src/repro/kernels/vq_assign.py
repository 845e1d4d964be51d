"""Pallas TPU kernel: grouped nearest-centroid VQ assignment.

ASTRA adds a per-layer, per-token codebook search on the hot path; on TPU we
map it onto the MXU as ||x-e||^2 = ||e||^2 - 2 x.e^T (the ||x||^2 term is
constant per row) over (token-block x codebook-block) VMEM tiles with a
running (min, argmin) carried in scratch across the codebook grid dimension.

Grid: (G, T // bt, K // bk), codebook dim innermost so the scratch
accumulator pattern matches the sequential TPU grid execution.  The kernel
reads tokens group-major, (G, T, dg), and writes codes as a (G, T, 1)
column per group: the TPU tiling rule refuses a (bt, 1, dg) window onto
the G axis and a (bt, 1) window onto the codes' G axis.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = 3.4e38


def _kernel(x_ref, cb_ref, out_ref, best_val, best_idx, *, bk: int, nk: int):
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _init():
        best_val[...] = jnp.full_like(best_val, -NEG)
        best_idx[...] = jnp.zeros_like(best_idx)

    x = x_ref[0].astype(jnp.float32)  # (bt, dg)
    cb = cb_ref[0].astype(jnp.float32)  # (bk, dg)
    # negative distance so we can keep a running max: 2 x.e - ||e||^2
    score = 2.0 * jax.lax.dot_general(
        x, cb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) - jnp.sum(cb * cb, axis=-1)[None, :]
    loc_best = jnp.max(score, axis=1, keepdims=True)  # (bt, 1)
    loc_arg = jnp.argmax(score, axis=1, keepdims=True).astype(
        jnp.int32) + k_i * bk
    # strict > keeps the lowest index on ties (matches jnp.argmin order)
    better = loc_best > best_val[...]
    best_val[...] = jnp.where(better, loc_best, best_val[...])
    best_idx[...] = jnp.where(better, loc_arg, best_idx[...])

    @pl.when(k_i == nk - 1)
    def _emit():
        out_ref[0] = best_idx[...]


@functools.partial(jax.jit, static_argnames=("block_t", "block_k", "interpret"))
def vq_assign(
    x: jax.Array,  # (T, G, dg)
    codebook: jax.Array,  # (G, K, dg)
    *,
    block_t: int = 256,
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    from repro.kernels.ops import resolve_interpret

    t, g, dg = x.shape
    k = codebook.shape[1]
    bt = min(block_t, t)
    bk = min(block_k, k)
    assert t % bt == 0 and k % bk == 0
    nk = k // bk

    grid = (g, t // bt, nk)
    codes = pl.pallas_call(
        functools.partial(_kernel, bk=bk, nk=nk),
        name="vq_assign",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, dg), lambda gi, ti, ki: (gi, ti, 0)),
            pl.BlockSpec((1, bk, dg), lambda gi, ti, ki: (gi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, 1), lambda gi, ti, ki: (gi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((g, t, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(jnp.moveaxis(x, 1, 0), codebook)
    return codes[:, :, 0].T
