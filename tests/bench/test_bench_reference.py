"""The plain reference: the weights and hidden states of every spec it
modelled before RMSNorm, SwiGLU and q/k/v biases came in stay as they
were, bit for bit; long sequences attend in query blocks that agree with
whole attention.  The dense family: every key the reference reads
(q/k/v biases and the norm epsilon among them) reaches a program whose
``ModelConfig`` takes it, and a file that asks for one the program
cannot take is refused; the bias leaves stand in each layer's attention
tree; the committed configurations map to the program config and tree
they always had; a Qwen2-shaped configuration (RMSNorm, SwiGLU) runs
through the harness, and one whose served model leaves out the biases
that the reference adds reads not correct."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench.run as br  # noqa: E402
from bench.families import _dense, qwen2  # noqa: E402
from bench.reference import dense_lm  # noqa: E402
from repro.configs.base import ASTRAConfig, ModelConfig  # noqa: E402
from repro.models.transformer import init_lm  # noqa: E402
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


def _program(*extra):
    """A stand-in for the program's ``ModelConfig``: the fields that
    ``_dense.model_config`` always passes, and ``extra``."""
    return dataclasses.make_dataclass("ModelConfig", [
        "name", "arch_type", "num_layers", "d_model", "num_heads",
        "num_kv_heads", "d_ff", "vocab_size", "rope_theta", "norm",
        "activation", "tie_embeddings", "astra", "dtype", "param_dtype",
        "max_seq_len", *extra], frozen=True)


def test_family_maps_qwen2_keys_and_tree(monkeypatch):
    cfg = dict(TINY_QWEN, rms_norm_eps=1e-5)
    spec = dense_lm.Spec(**qwen2.spec(cfg))
    assert (spec.norm, spec.act, spec.qkv_bias, spec.tied, spec.eps) == \
        ("rmsnorm", "swiglu", True, False, 1e-5)
    # a program whose ModelConfig takes both keys is told both
    monkeypatch.setattr(_dense, "ModelConfig",
                        _program("qkv_bias", "norm_eps"))
    mcfg = qwen2.model_config("tiny", cfg, spec)
    assert mcfg.qkv_bias is True
    assert mcfg.norm_eps == cfg["rms_norm_eps"]
    assert (mcfg.norm, mcfg.activation, mcfg.tie_embeddings) == \
        ("rmsnorm", "swiglu", False)
    # one that lacks them serves no biases and a fixed epsilon of 1e-6:
    # the file is refused unless it asks for just that
    monkeypatch.setattr(_dense, "ModelConfig", _program())
    with pytest.raises(ValueError, match="q/k/v biases"):
        qwen2.model_config("tiny", cfg, spec)
    with pytest.raises(ValueError, match="norm epsilon"):
        qwen2.model_config("tiny", cfg,
                           dataclasses.replace(spec, qkv_bias=False))
    plain = dataclasses.replace(spec, qkv_bias=False, eps=1e-6)
    mcfg = qwen2.model_config("tiny", TINY_QWEN, plain)
    assert (mcfg.norm, mcfg.activation) == ("rmsnorm", "swiglu")
    monkeypatch.undo()

    params = qwen2.make_params(dense_lm, spec, SEED, "float32")
    layer = params["stages"][0]["sub0"]
    assert set(layer["norm1"]) == {"scale"}
    assert set(layer["mlp"]) == {"w_gate", "w_up", "w_down"}
    assert set(layer["attn"]) == {"wq", "wk", "wv", "wo", "bq", "bk", "bv"}
    w = dense_lm.layer_weights(dense_lm.base_key(SEED), spec, 1)
    for k, heads in (("bq", spec.heads), ("bk", spec.kv_heads),
                     ("bv", spec.kv_heads)):
        assert layer["attn"][k].shape == (spec.layers,
                                          heads * spec.head_dim)
        assert layer["attn"][k].dtype == jnp.float32
        np.testing.assert_array_equal(layer["attn"][k][1], w[k])
    np.testing.assert_array_equal(layer["mlp"]["w_gate"][1], w["w_gate"])
    # without biases the tree is the one the program itself makes
    bare = qwen2.make_params(dense_lm, plain, SEED, "float32")
    assert set(bare["stages"][0]["sub0"]["attn"]) == {"wq", "wk", "wv", "wo"}
    _assert_program_tree(bare, qwen2.model_config("tiny", TINY_QWEN, plain),
                         "float32")
    with pytest.raises(ValueError):
        qwen2.spec(dict(TINY_QWEN, use_sliding_window=True))


def _assert_program_tree(params, mcfg, param_dtype):
    """``params`` (arrays or shapes) has the structure, shapes and dtypes
    of the tree that the program's own ``init_lm`` makes for ``mcfg``."""
    want = jax.eval_shape(lambda k: init_lm(k, mcfg, jnp.dtype(param_dtype)),
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for got, exp in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert (got.shape, got.dtype) == (exp.shape, exp.dtype)


# the program config each committed configuration mapped to before the
# biases and the epsilon were mapped
COMMITTED = {
    "gpt2-small": dict(num_layers=12, d_model=768, num_heads=12,
                       num_kv_heads=12, d_ff=3072, vocab_size=50257,
                       rope_theta=0.0, max_seq_len=1024),
    "starcoder2-3b": dict(num_layers=20, d_model=3072, num_heads=24,
                          num_kv_heads=2, d_ff=12288, vocab_size=49152,
                          rope_theta=999999.4420358813, max_seq_len=4096),
}


@pytest.mark.parametrize("name", sorted(COMMITTED))
def test_committed_configs_keep_their_program_and_tree(name, monkeypatch):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    ref, family, spec = br.load_model(cfg)
    assert (spec.qkv_bias, spec.eps) == (False, 1e-6)
    mcfg = family.model_config(name, cfg, spec)
    assert mcfg == ModelConfig(
        name=name, arch_type="dense", norm="layernorm", activation="gelu",
        tie_embeddings=True, astra=ASTRAConfig(enabled=False),
        dtype="bfloat16", param_dtype="float32", **COMMITTED[name])
    shapes = jax.eval_shape(family.params_builder(ref, spec, "float32"),
                            ref.base_key(SEED))
    _assert_program_tree(shapes, mcfg, "float32")
    # a program that takes both keys is told what it serves without them
    monkeypatch.setattr(_dense, "ModelConfig",
                        _program("qkv_bias", "norm_eps"))
    mcfg = family.model_config(name, cfg, spec)
    assert (mcfg.qkv_bias, mcfg.norm_eps) == (False, 1e-6)


def test_qwen2_file_is_served_whole_or_refused(tmp_path):
    """A Qwen2 file with biases and a published epsilon of 1e-5: where
    the program's ModelConfig takes both keys the harness serves it and
    it reads correct; where it lacks one, the harness refuses the file
    before anything is served."""
    cfg = dict(TINY_QWEN, rms_norm_eps=1e-5)
    root = make_root(tmp_path, cfg)
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    if not {"qkv_bias", "norm_eps"} <= fields:
        with pytest.raises(ValueError, match="cannot reach the program"):
            br.run_cell(root, "tiny-chat", 2 ** 31 + 35, 1.5, False,
                        require_accelerator=False)
        return
    out = br.run_cell(root, "tiny-chat", 2 ** 31 + 35, 1.5, False,
                      require_accelerator=False)
    assert out["check"]["requests_checked"]["value"] >= 1
    assert out["correct"] is True, out["check"]


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


def test_qwen_biases_left_out_of_the_served_model_read_not_correct(
        tmp_path, monkeypatch):
    """The reference adds the q/k/v biases; the served model is made and
    configured without them (its bias leaves dropped from the tree): the
    check reads not correct."""
    load_model = br.load_model

    def program_without_biases(cfg):
        ref, family, spec = load_model(cfg)
        assert spec.qkv_bias
        plain = dataclasses.replace(spec, qkv_bias=False)

        class Family:
            @staticmethod
            def model_config(name, cfg, _):
                return family.model_config(name, cfg, plain)

            @staticmethod
            def make_params(ref, _, *args, **kw):
                return family.make_params(ref, plain, *args, **kw)

        return ref, Family, spec

    monkeypatch.setattr(br, "load_model", program_without_biases)
    out = br.run_cell(make_root(tmp_path, TINY_QWEN), "tiny-chat",
                      2 ** 31 + 33, 1.5, False, require_accelerator=False)
    check = out["check"]
    assert check["requests_checked"]["value"] >= 1
    assert check["max_logit_gap"]["value"] > 10 * check["max_logit_gap"][
        "limit"], check
    assert out["correct"] is False
