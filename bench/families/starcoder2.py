"""StarCoder2: a configuration file with the keys of the published
``config.json`` (bigcode/starcoder2-3b), read into the reference's spec;
served on the program's dense path.

StarCoder2 is LayerNorm, RoPE and grouped-query attention by its
architecture; ``hidden_act`` and ``tie_word_embeddings`` are read from the
file.  Biases and a sliding window are not modelled: a file that asks for
either is refused.
"""
from __future__ import annotations

from typing import Dict

from bench.families._dense import make_params, model_config, params_builder

__all__ = ["spec", "model_config", "params_builder", "make_params"]

# published activation name -> the reference's
_ACT = {"gelu_pytorch_tanh": "gelu_tanh"}


def spec(cfg: Dict) -> Dict:
    """The reference ``Spec``'s fields, from the file's keys."""
    if cfg.get("use_bias") or cfg.get("sliding_window"):
        raise ValueError("biases and sliding windows are not modelled")
    return dict(layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                ffn=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                eps=cfg["norm_epsilon"], rope_theta=cfg["rope_theta"],
                norm="layernorm", act=_ACT[cfg["hidden_act"]],
                tied=cfg.get("tie_word_embeddings", True))
