"""The plain reference: the weights and hidden states of every spec it
modelled before RMSNorm, SwiGLU and q/k/v biases came in stay as they
were, bit for bit; long sequences attend in query blocks that agree with
whole attention; and a Qwen2-shaped configuration (RMSNorm, SwiGLU)
runs through the harness, while one with q/k/v biases, which the program
does not read, is refused."""
import dataclasses
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench.run as br  # noqa: E402
from bench.families import qwen2  # noqa: E402
from bench.reference import dense_lm  # noqa: E402
from test_bench_check import make_root  # noqa: E402

SEED = 2 ** 33 + 7
SEQ = [5, 17, 3, 88, 41, 2, 60]
LEARNED = dense_lm.Spec(layers=2, d_model=32, heads=4, kv_heads=4, ffn=64,
                        vocab=97, eps=1e-6, positions=64)
ROPE_GQA = dense_lm.Spec(layers=2, d_model=32, heads=4, kv_heads=2, ffn=64,
                         vocab=97, eps=1e-6, rope_theta=10000.0, tied=False)
QWEN = dense_lm.Spec(layers=2, d_model=32, heads=4, kv_heads=2, ffn=64,
                     vocab=97, eps=1e-6, rope_theta=10000.0, tied=False,
                     norm="rmsnorm", act="swiglu", qkv_bias=True)

# read from the reference before RMSNorm, SwiGLU and biases were added:
# hidden[6, :4], hidden[0, -2:], argmax of the head at positions 0-6
PINNED = {
    "learned": (LEARNED,
                [0.1251561939716339, -1.3756712675094604,
                 -2.7384181022644043, -0.8271600008010864],
                [1.7698333263397217, -3.710984706878662],
                [0, 28, 28, 28, 28, 28, 28]),
    "rope_gqa": (ROPE_GQA,
                 [-1.6505047082901, 0.7795438766479492, 0.7448336482048035,
                  0.8618013262748718],
                 [0.2822096347808838, 1.1192759275436401],
                 [67, 67, 4, 67, 4, 67, 67]),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_hidden_states_of_existing_specs_are_pinned(name):
    spec, row6, row0, argmax = PINNED[name]
    ref = dense_lm.Reference(spec, SEED)
    x = np.asarray(ref.hidden([SEQ])[0])
    np.testing.assert_array_equal(x[6, :4], np.float32(row6))
    np.testing.assert_array_equal(x[0, -2:], np.float32(row0))
    _, _, am = ref.stats(ref.hidden([SEQ])[0], SEQ[1:] + [0])
    assert am[:7].tolist() == argmax


@pytest.mark.parametrize("spec", [LEARNED, ROPE_GQA, QWEN],
                         ids=["learned", "rope_gqa", "qwen"])
def test_weights_drawn_before_the_new_keys_are_pinned(spec):
    key = dense_lm.base_key(SEED)
    w = dense_lm.layer_weights(key, spec, 1)
    assert float(w["wq"][3, 5]) == np.float32(0.03993087261915207)
    assert float(w["ln2_bias"][7]) == np.float32(-0.0345236174762249)
    assert float(w["w_down"][10, 2]) == np.float32(0.010070368647575378)
    e = dense_lm.embed_weights(key, spec)
    assert float(e["embed"][11, 4]) == np.float32(0.014425594359636307)
    assert float(e["lnf_scale"][3]) == np.float32(1.0465213060379028)
    new = {"w_gate", "bq", "bk", "bv"} & set(w)
    if spec is QWEN:
        assert new == {"w_gate", "bq", "bk", "bv"}
        assert w["bk"].shape == (spec.kv_heads * spec.head_dim,)
        assert not np.allclose(w["w_gate"], w["w_up"])
    else:
        assert new == set()


@pytest.mark.parametrize("spec", [ROPE_GQA, QWEN], ids=["rope_gqa", "qwen"])
def test_blocked_attention_agrees_with_whole(spec):
    key = dense_lm.base_key(SEED)
    w = dense_lm.layer_weights(key, spec, 0)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, spec.d_model))
    whole = dense_lm._layer(x, w, s=spec, quant=None)
    blocked = dense_lm._layer(x, w, s=spec, quant=None, block=16)
    np.testing.assert_allclose(blocked, whole, rtol=1e-6, atol=1e-6)


def test_long_sequences_take_the_blocked_path(monkeypatch):
    ref = dense_lm.Reference(QWEN, SEED)
    seq = [(7 * i + 3) % QWEN.vocab for i in range(600)]
    whole = np.asarray(ref.hidden([seq])[0])
    calls = []
    layer = dense_lm._layer

    def spy(x, w, **kw):
        calls.append(kw.get("block"))
        return layer(x, w, **kw)

    targets = seq[1:] + [0]
    want = ref.stats(ref.hidden([seq])[0], targets)
    monkeypatch.setattr(dense_lm, "BLOCK_FROM", 256)
    monkeypatch.setattr(dense_lm, "Q_BLOCK", 128)
    monkeypatch.setattr(dense_lm, "_layer", spy)
    blocked = ref.hidden([seq])[0]
    assert calls == [128] * QWEN.layers
    np.testing.assert_allclose(np.asarray(blocked)[:600], whole[:600],
                               rtol=1e-6, atol=1e-6)
    got = ref.stats(blocked, targets)  # the head in blocks of 128 rows
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a[:600], b[:600], rtol=1e-5, atol=1e-5)


def test_rmsnorm_swiglu_and_biases_change_the_forward():
    plain = dataclasses.replace(QWEN, qkv_bias=False)
    a = np.asarray(dense_lm.Reference(QWEN, SEED).hidden([SEQ])[0])
    b = np.asarray(dense_lm.Reference(plain, SEED).hidden([SEQ])[0])
    assert np.abs(a[:7] - b[:7]).max() > 1e-2
    with pytest.raises(ValueError):
        dataclasses.replace(QWEN, act="relu")


TINY_QWEN = {
    "source": "test", "family": "qwen2", "reference": "dense_lm",
    "model_type": "qwen2", "num_hidden_layers": 2, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "vocab_size": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "tie_word_embeddings": False, "use_sliding_window": False,
    # float32 throughout: the served path then reads the reference's gap
    # of 0, and a departure in the architecture cannot hide in rounding
    "compute": {"dtype": "float32", "param_dtype": "float32"},
    "deployment": {"cache_mode": "paged", "slots": 4, "max_len": 256,
                   "page_size": 16, "use_pallas": False},
    "check": {"max_logit_gap": 0.01, "min_tokens": 40},
}


def test_family_maps_qwen2_keys_and_tree():
    spec = dense_lm.Spec(**qwen2.spec(TINY_QWEN))
    assert (spec.norm, spec.act, spec.qkv_bias, spec.tied) == \
        ("rmsnorm", "swiglu", True, False)
    with pytest.raises(ValueError, match="q/k/v biases"):
        qwen2.model_config("tiny", TINY_QWEN, spec)  # the program has none
    spec = dataclasses.replace(spec, qkv_bias=False)
    mcfg = qwen2.model_config("tiny", TINY_QWEN, spec)
    assert (mcfg.norm, mcfg.activation) == ("rmsnorm", "swiglu")
    params = qwen2.make_params(dense_lm, spec, SEED, "float32")
    layer = params["stages"][0]["sub0"]
    assert set(layer["norm1"]) == {"scale"}
    assert set(layer["mlp"]) == {"w_gate", "w_up", "w_down"}
    assert set(layer["attn"]) == {"wq", "wk", "wv", "wo"}
    w = dense_lm.layer_weights(dense_lm.base_key(SEED), spec, 1)
    np.testing.assert_array_equal(layer["mlp"]["w_gate"][1], w["w_gate"])
    with pytest.raises(ValueError):
        qwen2.spec(dict(TINY_QWEN, use_sliding_window=True))


def test_qwen_shaped_config_runs_through_the_harness(tmp_path, monkeypatch):
    """RMSNorm and SwiGLU serve as the reference computes them (the
    q/k/v biases taken out of the spec, since the program reads none)."""
    load_model = br.load_model

    def without_biases(cfg):
        ref, family, spec = load_model(cfg)
        assert spec.qkv_bias and spec.norm == "rmsnorm"
        return ref, family, dataclasses.replace(spec, qkv_bias=False)

    monkeypatch.setattr(br, "load_model", without_biases)
    out = br.run_cell(make_root(tmp_path, TINY_QWEN), "tiny-chat",
                      2 ** 31 + 33, 1.5, False, require_accelerator=False)
    check = out["check"]
    assert check["requests_checked"]["value"] >= 1
    assert check["wrong_length"]["value"] == 0
    assert out["correct"] is True, check
