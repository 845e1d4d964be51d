"""GPT-2: a configuration file with the keys of the published
``config.json`` (openai-community/gpt2), read into the reference's spec;
served on the program's dense path.

GPT-2 is LayerNorm and learned absolute positions by its architecture;
``activation_function`` and ``tie_word_embeddings`` are read from the file.
"""
from __future__ import annotations

from typing import Dict

from bench.families._dense import make_params, model_config, params_builder

__all__ = ["spec", "model_config", "params_builder", "make_params"]

# published activation name -> the reference's
_ACT = {"gelu_new": "gelu_tanh"}


def spec(cfg: Dict) -> Dict:
    """The reference ``Spec``'s fields, from the file's keys."""
    d = cfg["n_embd"]
    return dict(layers=cfg["n_layer"], d_model=d, heads=cfg["n_head"],
                kv_heads=cfg["n_head"], ffn=cfg["n_inner"] or 4 * d,
                vocab=cfg["vocab_size"], eps=cfg["layer_norm_epsilon"],
                positions=cfg["n_positions"], norm="layernorm",
                act=_ACT[cfg["activation_function"]],
                tied=cfg.get("tie_word_embeddings", True))
