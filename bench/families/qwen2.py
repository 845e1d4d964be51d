"""Qwen2 (Qwen1.5, CodeQwen1.5): a configuration file with the keys of the
published ``config.json`` (``model_type`` "qwen2"), read into the
reference's spec; to be served on the program's dense path.

Qwen2 is RMSNorm, RoPE, grouped-query attention with biases on the q, k
and v projections (none on the output projection or the MLP), and a
gated MLP by its architecture; ``hidden_act`` and
``tie_word_embeddings`` are read from the file.  A sliding window is not
modelled: a file that turns it on is refused.  The q/k/v biases and
``rms_norm_eps`` go to the program as ``_dense`` maps them: the biases
as ``bq``/``bk``/``bv`` in each layer's attention, both as keys of the
program's ``ModelConfig``.  While the program's dense path reads no
biases (its ``ModelConfig`` has no ``qkv_bias``), ``model_config``
refuses a Qwen2 file rather than serve it without them.
"""
from __future__ import annotations

from typing import Dict

from bench.families._dense import make_params, model_config, params_builder

__all__ = ["spec", "model_config", "params_builder", "make_params"]

# published activation name -> the reference's (gated: silu(x Wg) * x Wu)
_ACT = {"silu": "swiglu"}


def spec(cfg: Dict) -> Dict:
    """The reference ``Spec``'s fields, from the file's keys."""
    if cfg.get("use_sliding_window"):
        raise ValueError("sliding windows are not modelled")
    return dict(layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                ffn=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
                norm="rmsnorm", act=_ACT[cfg["hidden_act"]],
                tied=cfg.get("tie_word_embeddings", False), qkv_bias=True)
