"""The output check at a size a CPU test can hold: a run of the real
harness (everything but the look for a chip) reads correct; the same run
with a fault planted in the served path reads not correct (a served
token altered where it is produced; the tokens of one slot altered; the
cache left as it was before each step); and the control (the reference
in float8), put through the same rule, reads not correct."""
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import bench.run as br  # noqa: E402
from bench.core import check as chk  # noqa: E402
from bench.families import starcoder2  # noqa: E402
from bench.reference import dense_lm  # noqa: E402

# tiny model served in float32: sound runs read a gap of 0.0 whichever
# requests finish in the window; the control reads 0.14, the planted
# faults far more
LIMIT = 0.01
TINY = {
    "source": "test", "family": "starcoder2", "reference": "dense_lm",
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 128, "vocab_size": 512,
    "rope_theta": 10000.0, "norm_epsilon": 1e-6,
    "hidden_act": "gelu_pytorch_tanh", "tie_word_embeddings": False,
    "compute": {"dtype": "float32", "param_dtype": "float32"},
    "deployment": {"cache_mode": "paged", "slots": 4, "max_len": 256,
                   "page_size": 16, "use_pallas": False},
    "check": {"max_logit_gap": LIMIT, "min_tokens": 40},
}
# the same model served in bfloat16, as the committed cells are, with a
# limit of its own: a near-tie among logits of ~2.5 (one bfloat16 step
# there is 0.0156) flips, so sound runs read up to 0.0256, more the more
# requests finish in the window; the fp8 control, once 200 served tokens
# are checked, reads 0.185 or more
BF16_LIMIT = 0.08
TINY_BF16 = dict(TINY, compute={"dtype": "bfloat16", "param_dtype": "float32"},
                 check={"max_logit_gap": BF16_LIMIT, "min_tokens": 200})
MIX = {"loop": "open", "rate_per_s": 20, "sizes": 32, "warm_s": 0.3,
       "schedule_seed": 3,
       "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                  "min": 8, "max": 150},
       "output": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                  "min": 4, "max": 40}}


def make_root(d: Path, cfg: dict, chips: int = 1) -> Path:
    """A checkout at ``d`` holding one cell, "tiny-chat": ``cfg`` under
    ``MIX``, every metric reported in it."""
    (d / "bench" / "configs").mkdir(parents=True)
    (d / "bench" / "traffic").mkdir(parents=True)
    (d / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (d / "bench" / "traffic" / "tiny-open.json").write_text(json.dumps(MIX))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "file": "bench/configs/tiny.json"}]
    bench["workloads"] = [{"name": "tiny-chat", "config": "tiny",
                           "traffic": "tiny-open", "chips": chips}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    return d


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"), TINY)


def _run(root, seed, **kw):
    return br.run_cell(root, "tiny-chat", seed, 1.5, False,
                       require_accelerator=False, **kw)


def test_sound_run_is_correct_and_control_fails_the_limit(root):
    out = _run(root, 5, control="fp8")
    assert out["correct"] is True
    gap = out["check"]["max_logit_gap"]
    assert gap["limit"] == LIMIT and gap["value"] < LIMIT
    assert out["check"]["requests_checked"]["value"] >= 1
    assert out["control"]["max_logit_gap"] > LIMIT
    assert out["control"]["correct"] is False
    assert list(out["check"]) == list(out["check"])  # printed in order
    assert list(out)[-1] == "check"
    assert {"ttft_p50_ms", "setup_s"} <= set(out["metrics"])


def test_bfloat16_run_is_correct_and_control_fails_its_limit(tmp_path):
    out = br.run_cell(make_root(tmp_path, TINY_BF16), "tiny-chat",
                      2 ** 31 + 45, 3.0, False, require_accelerator=False,
                      control="fp8")
    gap = out["check"]["max_logit_gap"]
    assert gap["limit"] == BF16_LIMIT
    assert out["correct"] is True, out["check"]
    assert out["check"]["served_tokens_checked"]["value"] >= 150
    assert out["control"]["max_logit_gap"] > BF16_LIMIT
    assert out["control"]["correct"] is False


def test_altered_token_reads_not_correct(root, monkeypatch):
    from repro.serving.scheduler import ContinuousBatchingEngine

    step = ContinuousBatchingEngine.step

    def broken(self):
        n = step(self)
        for req in self.active:
            if req is not None and req.output:
                req.output[-1] = (req.output[-1] + 1) % self.cfg.vocab_size
        return n

    monkeypatch.setattr(ContinuousBatchingEngine, "step", broken)
    out = _run(root, 6)
    assert out["correct"] is False
    assert out["check"]["max_logit_gap"]["value"] > LIMIT


def _alter_last(req, vocab):
    req.output[-1] = (req.output[-1] + 1) % vocab


@pytest.mark.parametrize("fault", ["one_slot_tokens", "cache_unchanged"])
def test_planted_fault_reads_not_correct(root, monkeypatch, fault):
    from repro.serving.scheduler import ContinuousBatchingEngine

    step = ContinuousBatchingEngine.step

    def broken(self):
        before = self.caches
        n = step(self)
        if fault == "cache_unchanged":
            self.caches = before  # the step's cache writes are lost
        elif self.active[1] is not None and self.active[1].output:
            _alter_last(self.active[1], self.cfg.vocab_size)
        return n

    monkeypatch.setattr(ContinuousBatchingEngine, "step", broken)
    out = _run(root, 7)
    assert out["correct"] is False
    assert out["check"]["max_logit_gap"]["value"] > LIMIT


class _R:
    def __init__(self, i, slot, n_out, n_prompt=10):
        self.i, self.slot, self.n_out, self.n_prompt = i, slot, n_out, n_prompt
        self.done = 1.0


def test_sample_covers_every_slot_then_tokens():
    reqs = [_R(i, i % 4, 50) for i in range(40)] + [_R(40, 2, 60, 100)]
    picked = chk.sample(reqs, 2 ** 31 + 9, min_tokens=10)
    assert picked[0].i == 40  # the longest first
    assert {r.slot for r in picked} == {0, 1, 2, 3}
    assert len(picked) == 4
    more = chk.sample(reqs, 2 ** 31 + 9, min_tokens=400)
    assert sum(r.n_out for r in more) >= 400
    assert len({id(r) for r in more}) == len(more)
    assert chk.sample(reqs, 5, 400) == chk.sample(reqs, 5, 400)


def test_verdict_rules():
    ok = chk.compared([], 10, 0.01, 5, 0.05)
    assert chk.verdict(ok) is False  # nothing checked
    assert chk.verdict(chk.compared([], 10, None, 0, 0.05)) is False
    nums = {"wrong_length": {"value": 0, "limit": 0},
            "requests_checked": {"value": 3, "limit": 1},
            "max_logit_gap": {"value": 0.05, "limit": 0.05},
            "served_tokens_checked": {"value": 9, "limit": 1}}
    assert chk.verdict(nums) is True
    nums["max_logit_gap"]["value"] = 0.0501
    assert chk.verdict(nums) is False


def test_served_weights_are_the_reference_weights():
    spec = dense_lm.Spec(**starcoder2.spec(TINY))
    params = starcoder2.make_params(dense_lm, spec, 2 ** 33 + 3, "float32")
    key = dense_lm.base_key(2 ** 33 + 3)
    for layer in range(spec.layers):
        w = dense_lm.layer_weights(key, spec, layer)
        got = params["stages"][0]["sub0"]
        np.testing.assert_allclose(got["attn"]["wk"][layer], w["wk"],
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(got["mlp"]["w_down"][layer], w["w_down"],
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(got["norm2"]["bias"][layer],
                                   w["ln2_bias"], rtol=1e-6, atol=1e-7)
    e = dense_lm.embed_weights(key, spec)
    np.testing.assert_allclose(params["embed"], e["embed"], rtol=1e-6,
                               atol=1e-8)
    np.testing.assert_allclose(params["lm_head"], e["head"], rtol=1e-6,
                               atol=1e-8)
    assert jax.tree.leaves(params)[0].dtype == np.float32


@pytest.mark.parametrize("name", ["gpt2-small", "starcoder2-3b"])
def test_family_reads_the_published_keys(name):
    from bench.run import load_model

    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    ref, family, spec = load_model(cfg)
    mcfg = family.model_config(name, cfg, spec)
    assert (mcfg.norm, mcfg.activation, mcfg.tie_embeddings) == \
        ("layernorm", "gelu", True)
    assert (mcfg.d_model, mcfg.num_layers) == (spec.d_model, spec.layers)
    assert spec.head_dim == (64 if name == "gpt2-small" else 128)
    with pytest.raises(KeyError):
        family.spec(dict(cfg, **{"activation_function": "relu",
                                 "hidden_act": "relu"}))
