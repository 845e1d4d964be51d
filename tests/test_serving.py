"""Serving engine: prefill/decode parity, vq cache mode, batched generate."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model_factory as mf
from repro.models import transformer as tlm
from repro.models.context import StepCtx
from repro.serving.engine import ServingEngine
from repro.serving.sampler import sample_tokens


def small_lm(arch="gpt2-small", astra=False):
    cfg = get_config(arch).reduced()
    if not astra:
        cfg = dataclasses.replace(
            cfg, astra=dataclasses.replace(cfg.astra, enabled=False))
    params = mf.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_disagg_needs_disjoint_device_groups():
    """A P:D split the host cannot give disjoint devices is refused, not
    run with the prefill and decode groups overlapping."""
    from repro.serving.disagg import DisaggregatedEngine

    cfg, params = small_lm()
    n = jax.device_count()
    with pytest.raises(ValueError, match=f"needs {n + 1} devices"):
        DisaggregatedEngine(cfg, params, max_len=64 * n, split=f"{n}:1")


def test_greedy_decode_matches_teacher_forcing():
    """Greedy generation through the KV-cache path must match argmax of the
    cache-free full forward at every step (astra off => exact)."""
    cfg, params = small_lm()
    engine = ServingEngine(cfg, params, max_len=48, astra_mode="off")
    prompts = [[5, 9, 3], [7, 2, 8, 4, 1]]
    out = engine.generate(prompts, max_new_tokens=6, temperature=0.0)

    ctx = StepCtx(cfg=cfg, mode="prefill", astra_mode="off")
    for p, gen in zip(prompts, out.tokens):
        seq = list(p)
        for tok in gen:
            logits, _, _, _ = tlm.lm_forward(
                params, {"tokens": jnp.asarray([seq], jnp.int32)}, ctx=ctx)
            want = int(jnp.argmax(logits[0, -1]))
            assert tok == want, (seq, tok, want)
            seq.append(tok)


def test_generate_respects_lengths_in_batch():
    """Mixed prompt lengths in one batch: each row conditions only on its
    own prompt (padding beyond `lengths` must not leak)."""
    cfg, params = small_lm()
    engine = ServingEngine(cfg, params, max_len=32, astra_mode="off")
    out_a = engine.generate([[5, 9, 3]], max_new_tokens=4, temperature=0.0)
    out_b = engine.generate([[5, 9, 3], [7, 2, 8, 4, 1, 6, 2]],
                            max_new_tokens=4, temperature=0.0)
    assert out_a.tokens[0] == out_b.tokens[0]


def test_vq_cache_mode_runs_and_is_close():
    """Appendix-G codes-only cache: runs, and stays correlated with fp."""
    cfg, params = small_lm(astra=True)
    fp = ServingEngine(cfg, params, max_len=32, astra_mode="off",
                       cache_mode="fp")
    vqe = ServingEngine(cfg, params, max_len=32, astra_mode="off",
                        cache_mode="vq")
    prompts = [[5, 9, 3, 4]]
    a = fp.generate(prompts, max_new_tokens=4, temperature=0.0)
    b = vqe.generate(prompts, max_new_tokens=4, temperature=0.0)
    ca = np.asarray(a.prefill_logits).ravel()
    cb = np.asarray(b.prefill_logits).ravel()
    assert np.corrcoef(ca, cb)[0, 1] > 0.3  # random codebook, still aligned


def test_eos_stops_generation():
    cfg, params = small_lm()
    engine = ServingEngine(cfg, params, max_len=32, astra_mode="off")
    out = engine.generate([[1, 2, 3]], max_new_tokens=16, temperature=0.0)
    eos = out.tokens[0][0]  # greedy repeats; use its first choice as "eos"
    out2 = engine.generate([[1, 2, 3]], max_new_tokens=16, temperature=0.0,
                           eos_id=eos)
    assert len(out2.tokens[0]) <= len(out.tokens[0])
    assert out2.tokens[0][-1] == eos


def test_generate_rejects_prompt_budget_overflow():
    """Prompt + budget beyond max_len fails fast instead of silently
    clamping (dense slab) or cycling the last page (paged)."""
    cfg, params = small_lm()
    for mode in ("fp", "paged"):
        engine = ServingEngine(cfg, params, max_len=16, astra_mode="off",
                               cache_mode=mode, page_size=8)
        with pytest.raises(ValueError, match="max_len"):
            engine.generate([[1] * 10], max_new_tokens=10)


def test_sampler_greedy_and_topk():
    logits = jnp.asarray([[1.0, 3.0, 2.0, -1.0]])
    g = sample_tokens(jax.random.PRNGKey(0), logits, temperature=0.0)
    assert int(g[0]) == 1
    # top-k=2 restricted sampling only ever picks indices {1, 2}
    picks = {
        int(sample_tokens(jax.random.PRNGKey(s), logits, temperature=1.0,
                          top_k=2)[0])
        for s in range(20)
    }
    assert picks <= {1, 2}


def test_encdec_generation():
    cfg = get_config("seamless-m4t-large-v2").reduced()
    params = mf.init_params(jax.random.PRNGKey(0), cfg)
    ctx = StepCtx(cfg=cfg, mode="decode", astra_mode="off")
    b = 2
    frames = jax.random.normal(jax.random.PRNGKey(1), (b, 16,
                                                       cfg.frontend_dim))
    caches = mf.init_cache(params, cfg, b, 32, ctx,
                           batch={"frame_embeds": frames},
                           dtype=jnp.float32)
    token = jnp.zeros((b, 1), jnp.int32)
    lengths = jnp.zeros((b,), jnp.int32)
    for i in range(4):
        logits, caches = mf.decode_step(params, token, caches, lengths,
                                        ctx=ctx)
        token = jnp.argmax(logits, -1).astype(jnp.int32)
        lengths = lengths + 1
    assert bool(jnp.all(jnp.isfinite(logits)))


# ---------------------------------------------------------------------------
# EOS regressions + chunked-decode behaviour (repro.serving.steps)
# ---------------------------------------------------------------------------


def test_eos_on_first_token():
    """The prefill-sampled token must be EOS-checked too: with eos_id equal
    to the very first greedy token, generation stops immediately."""
    cfg, params = small_lm()
    engine = ServingEngine(cfg, params, max_len=32, astra_mode="off")
    ref = engine.generate([[1, 2, 3]], max_new_tokens=16,
                          temperature=0.0).tokens[0]
    out = engine.generate([[1, 2, 3]], max_new_tokens=16, temperature=0.0,
                          eos_id=ref[0])
    assert out.tokens[0] == [ref[0]]


def test_eos_mid_stream_truncates_exactly():
    """eos_id first appearing at position j>0 stops that row at j (the EOS
    token itself is kept, nothing after it)."""
    cfg, params = small_lm()
    engine = ServingEngine(cfg, params, max_len=32, astra_mode="off")
    ref = engine.generate([[1, 2, 3]], max_new_tokens=16,
                          temperature=0.0).tokens[0]
    v = next((t for i, t in enumerate(ref) if i >= 1 and t not in ref[:i]),
             None)
    if v is None:
        pytest.skip("greedy sequence has no fresh mid-stream token")
    j = ref.index(v)
    out = engine.generate([[1, 2, 3]], max_new_tokens=16, temperature=0.0,
                          eos_id=v)
    assert out.tokens[0] == ref[: j + 1]


def test_generate_invariant_to_decode_chunk_size():
    """Greedy output must not depend on how the on-device loop is chunked."""
    cfg, params = small_lm()
    prompts = [[5, 9, 3], [7, 2, 8, 4, 1]]
    outs = [
        ServingEngine(cfg, params, max_len=48, astra_mode="off",
                      decode_chunk=c).generate(
            prompts, max_new_tokens=7, temperature=0.0).tokens
        for c in (1, 3, 8)
    ]
    assert outs[0] == outs[1] == outs[2]


def test_engines_greedy_parity():
    """ServingEngine and ContinuousBatchingEngine share one jitted decode
    chunk and must emit identical greedy tokens for the same prompts."""
    from repro.serving.scheduler import ContinuousBatchingEngine

    cfg, params = small_lm()
    prompts = [[5, 9, 3], [7, 2, 8, 4, 1], [11, 12]]
    static = ServingEngine(cfg, params, max_len=64, astra_mode="off",
                           decode_chunk=3)
    want = static.generate(prompts, max_new_tokens=6, temperature=0.0).tokens
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_len=64,
                                   decode_chunk=2)
    for p in prompts:
        eng.submit(p, max_new_tokens=6)
    eng.run_until_drained()
    got = {tuple(r.prompt): r.output for r in eng.finished}
    for p, w in zip(prompts, want):
        assert got[tuple(p)] == w, (p, got[tuple(p)], w)


def test_host_syncs_scale_with_chunks_not_tokens():
    """Device->host transfers are O(max_new_tokens / chunk): one fetch for
    the prefill token, one per decode chunk, one for prefill_logits."""
    cfg, params = small_lm()
    engine = ServingEngine(cfg, params, max_len=48, astra_mode="off",
                           decode_chunk=8)
    engine.generate([[1, 2, 3]], max_new_tokens=17, temperature=0.0)
    budget = 16
    n_chunks = -(-budget // 8)  # ceil
    assert engine.host_syncs == 2 + n_chunks  # NOT 2 + budget

    # per-token chunking really would cost one sync per token
    engine1 = ServingEngine(cfg, params, max_len=48, astra_mode="off",
                            decode_chunk=1)
    engine1.generate([[1, 2, 3]], max_new_tokens=17, temperature=0.0)
    assert engine1.host_syncs == 2 + budget
