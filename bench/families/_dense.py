"""A dense pre-norm decoder on the program's ``arch_type="dense"`` path:
the program's config and parameter tree for a reference ``Spec``.

Family files whose models take this path (``gpt2``, ``starcoder2``,
``qwen2``) read their configuration's keys into a ``Spec`` and use these
functions; a family that the program serves another way brings its own.

The tree: LayerNorm norms carry ``scale`` and ``bias``, RMSNorm norms
``scale`` alone; a SwiGLU MLP carries ``w_gate`` beside ``w_up`` and
``w_down``.  The program's dense path reads no q/k/v biases, so a spec
with ``qkv_bias`` is refused (``model_config``) rather than served
without them.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ASTRAConfig, ModelConfig

# the reference's activation -> the program's name for it (the program's
# "gelu" is jax.nn.gelu(approximate=True), the tanh form)
_ACT = {"gelu_tanh": "gelu", "swiglu": "swiglu"}


def model_config(name: str, cfg: Dict, spec) -> ModelConfig:
    """The program's config for the file's model, as run: no ASTRA
    codebooks (the cell serves the fp paged cache), activations in
    ``compute.dtype``, parameters in ``compute.param_dtype``; norm,
    activation and head tying as the spec read them from the file.  A
    spec with q/k/v biases is refused: the program has none to read."""
    if spec.qkv_bias:
        raise ValueError(f"{name}: the program's dense path reads no q/k/v "
                         "biases, so it cannot serve this model")
    compute = cfg["compute"]
    return ModelConfig(
        name=name, arch_type="dense", num_layers=spec.layers,
        d_model=spec.d_model, num_heads=spec.heads,
        num_kv_heads=spec.kv_heads, d_ff=spec.ffn, vocab_size=spec.vocab,
        rope_theta=spec.rope_theta, norm=spec.norm,
        activation=_ACT[spec.act], tie_embeddings=spec.tied,
        astra=ASTRAConfig(enabled=False),
        dtype=compute["dtype"], param_dtype=compute["param_dtype"],
        max_seq_len=spec.positions or cfg["deployment"]["max_len"])


def _norm(spec, scale, bias):
    if spec.norm == "rmsnorm":
        return {"scale": scale}
    return {"scale": scale, "bias": bias}


def params_builder(ref, spec, param_dtype: str, sharding=None):
    """A jitted function from a PRNG key to the program's parameter tree,
    made from the reference's seeded weights (``ref.embed_weights`` /
    ``ref.layer_weights``) layer by layer under ``lax.map``, so no
    temporary the size of a stacked leaf is ever live.  With
    ``sharding`` every leaf comes out with it (on a mesh each chip makes
    the copy it keeps)."""
    dt = jnp.dtype(param_dtype)

    def build(key):
        e = ref.embed_weights(key, spec)

        def one(layer):
            w = ref.layer_weights(key, spec, layer)
            mlp = {"w_up": w["w_up"], "w_down": w["w_down"]}
            if spec.act == "swiglu":
                mlp["w_gate"] = w["w_gate"]
            return jax.tree.map(lambda a: a.astype(dt), {
                "norm1": _norm(spec, w["ln1_scale"], w["ln1_bias"]),
                "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                         "wo": w["wo"]},
                "norm2": _norm(spec, w["ln2_scale"], w["ln2_bias"]),
                "mlp": mlp,
            })

        params = {
            "embed": e["embed"].astype(dt),
            "final_norm": jax.tree.map(
                lambda a: a.astype(dt),
                _norm(spec, e["lnf_scale"], e["lnf_bias"])),
            "stages": [{"sub0": jax.lax.map(
                one, jnp.arange(spec.layers, dtype=jnp.uint32))}],
        }
        if "pos_embed" in e:
            params["pos_embed"] = e["pos_embed"].astype(dt)
        if "head" in e:
            params["lm_head"] = e["head"].astype(dt)
        return params

    if sharding is None:
        return jax.jit(build)
    return jax.jit(build, out_shardings=sharding)


def make_params(ref, spec, seed: int, param_dtype: str, sharding=None):
    """The program's parameter tree for ``seed``, made on the device in one
    jitted call; with ``sharding`` (a mesh cell: ``NamedSharding(mesh,
    P())``, every chip the whole tree) made straight into it."""
    return params_builder(ref, spec, param_dtype, sharding)(
        ref.base_key(seed))
