"""The serving Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) runs the kernel bodies as jnp and
cannot see the TPU compiler's refusals: block shapes whose last two dims
are neither (8, 128)-aligned nor the whole array's, in-kernel gathers,
unaligned lane slices.  Here each kernel is lowered and compiled for one
chip of a *described* v5e topology (the TPU compiler is installed; no chip
is attached) at GPT-2 small widths, plus the fp flash kernels at Llama-3
8B's grouped-query geometry.  Nothing runs: these tests prove the compile,
the conformance harness proves the numerics.

The topology is described inside a module-scoped fixture, never while a
module is imported (only one process may load the TPU library at a time,
and every test worker imports every test file).
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.mixed_attn import (
    chunk_flash_attention,
    chunk_flash_partials,
    mixed_flash_attention,
)
from repro.kernels.vq_assign import vq_assign
from repro.kernels.vq_decode_attn import fp_decode_attention, vq_decode_attention


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _cases(name):
    """(kernel, argument shapes) for one case; shapes as (shape, dtype)."""
    gpt2 = get_config("gpt2-small")
    h, hd = gpt2.num_heads, gpt2.head_dim
    b, s, w, k = 8, 1024, 128, gpt2.astra.codebook_size
    gph, dg = 2, hd // 2  # coded kernels: whole groups per kv head
    g = h * gph
    f32, i32 = jnp.float32, jnp.int32
    llama = get_config("llama3-8b")
    lh, lkv, lhd = llama.num_heads, llama.num_kv_heads, llama.head_dim
    return {
        "fp_decode_attention": (
            lambda q, kk, vv, ln: fp_decode_attention(
                q, kk, vv, ln, interpret=False),
            [((b, h, hd), f32), ((b, s, h, hd), f32), ((b, s, h, hd), f32),
             ((b,), i32)]),
        "vq_decode_attention": (
            lambda q, kc, vc, cbk, cbv, ln: vq_decode_attention(
                q, kc, vc, cbk, cbv, ln, interpret=False),
            [((b, h, hd), f32), ((b, s, g), jnp.uint16),
             ((b, s, g), jnp.uint16), ((g, k, dg), f32), ((g, k, dg), f32),
             ((b,), i32)]),
        "chunk_flash_attention": (
            lambda q, kk, vv, kp, cs: chunk_flash_attention(
                q, kk, vv, kp, cs, interpret=False),
            [((1, w, h, hd), f32), ((1, s, h, hd), f32),
             ((1, s, h, hd), f32), ((s,), i32), ((), i32)]),
        "chunk_flash_partials": (
            lambda q, kk, vv, kp, cs: chunk_flash_partials(
                q, kk, vv, kp, cs, interpret=False),
            [((1, w, h, hd), f32), ((1, s // 4, h, hd), f32),
             ((1, s // 4, h, hd), f32), ((s // 4,), i32), ((), i32)]),
        "mixed_flash_attention": (
            lambda q, kl, vl, kc, vc, cbk, cbv, off: mixed_flash_attention(
                q, kl, vl, kc, vc, cbk, cbv, off, interpret=False),
            [((1, h, 256, hd), f32), ((1, h, 256, hd), f32),
             ((1, h, 256, hd), f32), ((1, s, g), i32), ((1, s, g), i32),
             ((g, k, dg), f32), ((g, k, dg), f32), ((), i32)]),
        # GPT-2's quantizer: one 768-wide group, K = 1024
        "vq_assign": (
            lambda x, cb: vq_assign(x, cb, interpret=False),
            [((1024, 1, gpt2.d_model), f32), ((1, k, gpt2.d_model), f32)]),
        "fp_decode_attention_gqa": (
            lambda q, kk, vv, ln: fp_decode_attention(
                q, kk, vv, ln, interpret=False),
            [((b, lh, lhd), f32), ((b, s, lkv, lhd), f32),
             ((b, s, lkv, lhd), f32), ((b,), i32)]),
        "chunk_flash_attention_gqa": (
            lambda q, kk, vv, kp, cs: chunk_flash_attention(
                q, kk, vv, kp, cs, interpret=False),
            [((1, w, lh, lhd), f32), ((1, s, lkv, lhd), f32),
             ((1, s, lkv, lhd), f32), ((s,), i32), ((), i32)]),
    }[name]


@pytest.mark.parametrize("name", [
    "fp_decode_attention", "vq_decode_attention", "chunk_flash_attention",
    "chunk_flash_partials", "mixed_flash_attention", "vq_assign",
    "fp_decode_attention_gqa", "chunk_flash_attention_gqa",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _cases(name)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
