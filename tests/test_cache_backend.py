"""CacheBackend conformance: every cache layout behind one interface.

One parametrized suite runs all backends (fp / vq slabs, paged / paged_vq
pools, the seq-sharded shard cache) through BOTH engines and pins:
  * greedy token parity against each layout's exactness reference,
  * mid-stream EOS truncation,
  * decode-chunk invariance,
  * compile-once (decode chunk AND slot prefill, with per-layer block
    tables and donated caches),
  * the protocol surface (advance / release / bytes_report /
    donate_argnums),
plus the windowed page-cap accounting (gemma2 / recurrentgemma pools
shrink to window-sized rings with unchanged outputs), the decode-chunk
autotune store, and the ``repro.analysis`` rule (``cache-mode-dispatch``)
forbidding ``cache_mode`` string dispatch outside serving/cache_backend.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.configs import get_config
from repro.core.sequence_parallel import LOCAL, MeshContext
from repro.kernels import ops as kops
from repro.models import model_factory as mf
from repro.models.context import StepCtx
from repro.serving import autotune as serving_autotune
from repro.serving import cache_backend as cbe
from repro.serving.engine import ServingEngine
from repro.serving import kv_cache as kvc
from repro.serving.kv_cache import (
    PagedKVCache,
    page_group_spans,
    paged_pool_bytes,
    pool_bytes,
)
from repro.serving.scheduler import ContinuousBatchingEngine

# name -> (cache_mode, needs astra codebooks, seq-sharded mesh, reference
# backend whose greedy tokens must match exactly)
SPECS = {
    "fp": ("fp", False, False, "fp"),
    "vq": ("vq", True, False, "vq"),
    "paged": ("paged", False, False, "fp"),
    "paged_vq": ("paged_vq", True, False, "vq"),
    "sharded_fp": ("fp", False, True, "fp"),
    "sharded_vq": ("vq", True, True, "vq"),
    "sharded_paged": ("paged", False, True, "fp"),
    "sharded_paged_vq": ("paged_vq", True, True, "vq"),
}

_MODELS = {}


def small_lm(astra=False):
    if astra not in _MODELS:
        cfg = get_config("gpt2-small").reduced()
        if not astra:
            cfg = dataclasses.replace(
                cfg, astra=dataclasses.replace(cfg.astra, enabled=False))
        params = mf.init_params(jax.random.PRNGKey(0), cfg)
        _MODELS[astra] = (cfg, params)
    return _MODELS[astra]


def mesh_ctx_for(sharded: bool) -> MeshContext:
    if not sharded:
        return LOCAL
    return MeshContext(mesh=make_mesh((1,), ("model",)), batch_axes=(),
                       seq_axis="model")


def static_gen(name, prompts, max_new, *, eos=None, chunk=3, donate=None):
    mode, astra, sharded, _ = SPECS[name]
    cfg, params = small_lm(astra)
    eng = ServingEngine(cfg, params, max_len=64, astra_mode="off",
                        cache_mode=mode, decode_chunk=chunk, page_size=8,
                        mesh_ctx=mesh_ctx_for(sharded), donate=donate)
    out = eng.generate(prompts, max_new_tokens=max_new, temperature=0.0,
                       eos_id=eos)
    return out.tokens, eng


def drain(name, jobs, *, chunk=2, slots=2, donate=None, **kw):
    mode, astra, sharded, _ = SPECS[name]
    cfg, params = small_lm(astra)
    eng = ContinuousBatchingEngine(cfg, params, slots=slots, max_len=64,
                                   decode_chunk=chunk, cache_mode=mode,
                                   page_size=8,
                                   mesh_ctx=mesh_ctx_for(sharded),
                                   donate=donate, **kw)
    for prompt, max_new, eos in jobs:
        eng.submit(prompt, max_new_tokens=max_new, eos_id=eos)
    eng.run_until_drained()
    return {tuple(r.prompt): r.output for r in eng.finished}, eng


def _mid_stream_token(ref):
    return next((t for i, t in enumerate(ref) if i >= 1 and t not in ref[:i]),
                None)


# ---------------------------------------------------------------------------
# Conformance: parity / EOS / chunk invariance / compile-once, all backends
# ---------------------------------------------------------------------------


PROMPTS = [[5, 9, 3], [7, 2, 8, 4, 1], [11, 12]]


@pytest.mark.parametrize("name", sorted(SPECS))
def test_static_engine_parity_and_mid_stream_eos(name):
    ref = SPECS[name][3]
    want, _ = static_gen(ref, PROMPTS, 7)
    got, eng = static_gen(name, PROMPTS, 7)
    assert got == want, (name, got, want)
    assert eng._decode_chunk.trace_count == 1
    eos = _mid_stream_token(want[0])
    if eos is not None:  # mid-stream EOS truncates identically
        assert static_gen(name, PROMPTS[:1], 7, eos=eos)[0] == \
            static_gen(ref, PROMPTS[:1], 7, eos=eos)[0]


@pytest.mark.parametrize("name", sorted(SPECS))
def test_continuous_engine_parity_and_compile_once(name):
    ref = SPECS[name][3]
    # 5 requests through 2 slots: admission, retirement, slot reuse
    jobs = [(PROMPTS[0], 6, None), (PROMPTS[1], 4, None),
            (PROMPTS[2], 6, None), ([4, 4, 4], 3, None), ([9], 5, None)]
    want, _ = drain(ref, jobs)
    got, eng = drain(name, jobs)
    assert got == want, (name, got, want)
    assert eng.kv.pages_in_use == 0  # trivially 0 for slabs, drained paged
    assert eng._decode_chunk.trace_count == 1
    # every layout chunks (seq-sharded included since PR 9): compiles are
    # O(bucket widths) under the traced chunk_start, and the on-device
    # slot merge (traced slot index) compiles once
    assert eng.prefill_mode == "chunked"
    assert 1 <= eng._prefill_chunk.trace_count <= len(
        eng.prefill_buckets)
    assert eng._merge.trace_count == 1
    assert eng._prefill.trace_count == 0


@pytest.mark.parametrize("name", sorted(SPECS))
def test_decode_chunk_invariance(name):
    a, _ = static_gen(name, PROMPTS[:2], 7, chunk=2)
    b, _ = static_gen(name, PROMPTS[:2], 7, chunk=5)
    assert a == b


# ---------------------------------------------------------------------------
# Protocol surface
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(SPECS))
def test_backend_state_protocol(name):
    mode, astra, sharded, _ = SPECS[name]
    if sharded:
        pytest.skip("engine-state protocol is exercised via the slab specs")
    cfg, _ = small_lm(astra)
    backend = cbe.get_backend(mode)
    ctx = StepCtx(cfg=cfg, mode="decode", astra_mode="off", cache_mode=mode)
    state = backend.make_state(cfg, slots=2, max_len=64, ctx=ctx,
                               page_size=8, dtype=jnp.float32)
    assert backend.advance(state, 0, 64)  # full budget always fits
    rep = backend.bytes_report(cfg, max_len=64, slots=2, page_size=8)
    assert rep["mode"] == mode and rep["cache_bytes"] > 0
    if backend.paged:
        assert rep["cache_bytes"] == state.pool_bytes()
        assert state.pages_in_use > 0
        tables = state.tables()
        assert set(tables) == set(page_group_spans(cfg, 64, 8))
        for group, t in tables.items():
            assert t.shape == (2, page_group_spans(cfg, 64, 8)[group])
    else:
        assert state.tables() is None
    assert backend.release(state, 0) >= 0
    assert state.pages_in_use == 0


def test_unknown_cache_mode_rejected():
    with pytest.raises(ValueError, match="unknown cache_mode"):
        cbe.get_backend("nope")
    for eng_cls, kw in ((ServingEngine, {}),
                        (ContinuousBatchingEngine, {})):
        cfg, params = small_lm()
        with pytest.raises(ValueError, match="unknown cache_mode"):
            eng_cls(cfg, params, cache_mode="nope", **kw)


def test_paged_plus_seq_sharded_constructs():
    """Paged pools under the mesh are supported (PR 9): the shard cache
    wraps the paged backends and the pool splits into per-shard
    allocators with shard-local page ids."""
    for mode in ("paged", "paged_vq"):
        backend = cbe.get_backend(mode, seq_sharded=True)
        assert backend.sharded and backend.paged
        assert backend.name == f"sharded_{mode}"


def test_explicit_chunked_with_astra_sim_raises():
    """An explicit ``prefill_mode="chunked"`` the engine cannot honor must
    raise, never silently downgrade; the *default* still resolves to the
    padded astra-sim prefill (the one remaining fallback)."""
    cfg, params = small_lm(astra=True)
    for eng_cls, kw in ((ServingEngine, {}),
                        (ContinuousBatchingEngine, {"slots": 2})):
        with pytest.raises(ValueError, match="astra simulation"):
            eng_cls(cfg, params, max_len=64, astra_mode="sim",
                    prefill_mode="chunked", **kw)
        eng = eng_cls(cfg, params, max_len=64, astra_mode="sim", **kw)
        assert eng.prefill_mode == "padded"  # default: documented fallback
        with pytest.raises(ValueError, match="unknown prefill_mode"):
            eng_cls(cfg, params, max_len=64, astra_mode="off",
                    prefill_mode="bogus", **kw)


# ---------------------------------------------------------------------------
# Donation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(SPECS))
def test_donate_argnums_platform_gating(name):
    mode, _, sharded, _ = SPECS[name]
    backend = cbe.get_backend(mode, seq_sharded=sharded)
    assert backend.donate_argnums((2,), platform="tpu") == (2,)
    assert backend.donate_argnums((2, 4), platform="gpu") == (2, 4)
    assert backend.donate_argnums((2,), platform="cpu") == ()


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("name", ["fp", "paged"])
def test_forced_donation_matches_undonated(name):
    """donate=True threads donate_argnums through prefill + decode chunk;
    on CPU XLA copies, so outputs must be identical and compile-once must
    hold (the real aliasing is asserted on the dry-run path)."""
    want, _ = static_gen(name, PROMPTS[:2], 6, donate=False)
    got, eng = static_gen(name, PROMPTS[:2], 6, donate=True)
    assert got == want
    assert eng._decode_chunk.donate_argnums == (2,)
    assert eng._decode_chunk.trace_count == 1
    assert eng._prefill_chunk.donate_argnums == (3, 5)
    jobs = [(PROMPTS[0], 4, None), ([9], 3, None), ([4, 4], 4, None)]
    want_c, _ = drain(name, jobs, donate=False)
    got_c, ceng = drain(name, jobs, donate=True)
    assert got_c == want_c
    assert ceng._prefill.donate_argnums == (4,)
    assert ceng._merge.donate_argnums == (0,)
    assert ceng._decode_chunk.trace_count == 1
    assert ceng._prefill_chunk.trace_count >= 1
    assert ceng._merge.trace_count == 1


# ---------------------------------------------------------------------------
# Host rollback: grant high-water + page accounting (speculative decoding)
# ---------------------------------------------------------------------------


def _backend_state(mode, *, slots=2, max_len=64, ps=8, cfg=None):
    if cfg is None:
        cfg, _ = small_lm(mode.endswith("vq"))
    backend = cbe.get_backend(mode)
    ctx = StepCtx(cfg=cfg, mode="decode", astra_mode="off", cache_mode=mode)
    return backend, backend.make_state(cfg, slots=slots, max_len=max_len,
                                       ctx=ctx, page_size=ps,
                                       dtype=jnp.float32)


@pytest.mark.parametrize("mode", ["paged", "paged_vq"])
def test_paged_rollback_page_accounting(mode):
    """The grant retreats token-granular; pages free only when the retreat
    crosses their boundary, and the allocator balances at every step."""
    backend, kv = _backend_state(mode)
    assert backend.advance(kv, 0, 20)   # 3 pages at page_size=8
    base = kv.pages_in_use
    assert kv.granted(0) == 20
    assert backend.rollback(kv, 0, 0) == 0          # n=0: no-op
    assert kv.granted(0) == 20 and kv.pages_in_use == base
    assert backend.rollback(kv, 0, 1) == 0          # 20 -> 19: mid-page
    assert kv.granted(0) == 19 and kv.pages_in_use == base
    assert backend.rollback(kv, 0, 3) == 1          # 19 -> 16: boundary
    assert kv.granted(0) == 16 and kv.pages_in_use == base - 1
    kv.check_invariants()
    assert backend.rollback(kv, 0, 100) == 2        # past everything
    assert kv.granted(0) == 0 and kv.pages_in_use == 0
    kv.check_invariants()
    assert backend.advance(kv, 0, 10)               # grant grows again
    assert kv.granted(0) == 10 and kv.pages_in_use == 2
    with pytest.raises(ValueError, match=">= 0"):
        backend.rollback(kv, 0, -1)
    assert backend.release(kv, 0) >= 0
    assert kv.pages_in_use == 0


def test_paged_rollback_keeps_window_ring_pages():
    """A true SWA ring page always holds live in-window positions, so a
    length retreat frees only the full-span (global) tail."""
    cfg = _no_astra(get_config("gemma2-27b").reduced())
    backend, kv = _backend_state("paged", slots=1, max_len=256, ps=16,
                                 cfg=cfg)
    assert backend.advance(kv, 0, 200)
    ring = kv.groups["window"].allocator
    glob = kv.groups["global"].allocator
    ring_held, glob_held = len(ring.owned(0)), len(glob.owned(0))
    freed = backend.rollback(kv, 0, 40)             # 200 -> 160 tokens
    assert kv.granted(0) == 160
    assert len(ring.owned(0)) == ring_held          # ring: nothing freed
    assert len(glob.owned(0)) == -(-160 // 16)      # global: tail returned
    assert freed == glob_held - len(glob.owned(0))
    kv.check_invariants()


@pytest.mark.parametrize("mode", ["fp", "vq"])
def test_slab_rollback_is_noop(mode):
    """Slab rows span max_len: the host op frees nothing (device rings are
    verify_rollback's job) but still validates its argument."""
    backend, state = _backend_state(mode)
    assert backend.advance(state, 0, 30)
    assert backend.rollback(state, 0, 5) == 0
    assert backend.rollback(state, 0, 0) == 0
    with pytest.raises(ValueError, match=">= 0"):
        backend.rollback(state, 0, -1)


# ---------------------------------------------------------------------------
# Windowed page caps: pools shrink, outputs unchanged
# ---------------------------------------------------------------------------


def _no_astra(cfg):
    return dataclasses.replace(
        cfg, astra=dataclasses.replace(cfg.astra, enabled=False))


def test_gemma2_windowed_pools_shrink_to_window_pages():
    """gemma2 (alternating local/global): the local half's pools hold
    window/page_size-page rings while the global half keeps max_len —
    measurably smaller than the uncapped accounting, same greedy tokens."""
    cfg = _no_astra(get_config("gemma2-27b").reduced())
    params = mf.init_params(jax.random.PRNGKey(0), cfg)
    max_len, ps = 256, 16
    spans = page_group_spans(cfg, max_len, ps)
    assert spans == {"global": max_len // ps,
                     "window": -(-cfg.window_size // ps)}
    assert spans["window"] < spans["global"]
    ctx = StepCtx(cfg=cfg, mode="decode", astra_mode="off",
                  cache_mode="paged")
    kv = PagedKVCache(cfg, slots=1, max_len=max_len, ctx=ctx, page_size=ps)
    measured = pool_bytes(kv.init_cache())
    capped = paged_pool_bytes(cfg, max_len=max_len, page_size=ps, slots=1)
    uncapped = paged_pool_bytes(cfg, max_len=max_len, page_size=ps, slots=1,
                                window_cap=False)
    assert measured == capped == kv.pool_bytes()
    assert capped < uncapped
    # outputs unchanged vs the dense fp ring
    prompts = [[5, 9, 3, 7, 11], [2, 8]]
    fp = ServingEngine(cfg, params, max_len=max_len, astra_mode="off",
                       decode_chunk=4)
    want = fp.generate(prompts, max_new_tokens=6, temperature=0.0).tokens
    pg = ServingEngine(cfg, params, max_len=max_len, astra_mode="off",
                       cache_mode="paged", page_size=ps, decode_chunk=4)
    assert pg.generate(prompts, max_new_tokens=6,
                       temperature=0.0).tokens == want


def test_rg_windowed_pools_shrink_and_drain_parity():
    """recurrentgemma: every attention layer is windowed, so the "window"
    group is the whole paged cache (and owns the num_pages knob); pools
    shrink to the ring size and the continuous engine's outputs still match
    fp through admission / retirement / slot reuse."""
    cfg = _no_astra(get_config("recurrentgemma-9b").reduced())
    params = mf.init_params(jax.random.PRNGKey(0), cfg)
    max_len, ps = 128, 8
    spans = page_group_spans(cfg, max_len, ps)
    assert spans == {"window": -(-cfg.window_size // ps)}
    assert spans["window"] < max_len // ps
    ctx = StepCtx(cfg=cfg, mode="decode", astra_mode="off",
                  cache_mode="paged")
    kv = PagedKVCache(cfg, slots=2, max_len=max_len, ctx=ctx, page_size=ps)
    assert pool_bytes(kv.init_cache()) == kv.pool_bytes() < paged_pool_bytes(
        cfg, max_len=max_len, page_size=ps, slots=2, window_cap=False)

    jobs = [([5, 9, 3, 7, 11], 5, None), ([2, 8], 4, None), ([6], 5, None)]

    def rg_drain(mode):
        eng = ContinuousBatchingEngine(cfg, params, slots=2, max_len=max_len,
                                       decode_chunk=2, cache_mode=mode,
                                       page_size=ps)
        for prompt, max_new, eos in jobs:
            eng.submit(prompt, max_new_tokens=max_new, eos_id=eos)
        eng.run_until_drained()
        return {tuple(r.prompt): r.output for r in eng.finished}, eng

    want, _ = rg_drain("fp")
    got, eng = rg_drain("paged")
    assert got == want
    assert eng.kv.pages_in_use == 0


def test_windowed_decode_past_window_parity_paged_ring():
    """Decoding well past the window wraps the page ring; tokens must stay
    identical to the dense ring cache (gemma2, window crossed)."""
    cfg = _no_astra(get_config("gemma2-27b").reduced())
    params = mf.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 9, 3, 7, 11]]
    fp = ServingEngine(cfg, params, max_len=96, astra_mode="off",
                       decode_chunk=8)
    want = fp.generate(prompts, max_new_tokens=85, temperature=0.0).tokens
    assert len(prompts[0]) + len(want[0]) > cfg.window_size  # crossed it
    pg = ServingEngine(cfg, params, max_len=96, astra_mode="off",
                       cache_mode="paged", page_size=8, decode_chunk=8)
    assert pg.generate(prompts, max_new_tokens=85,
                       temperature=0.0).tokens == want


def test_prompt_longer_than_window_paged_matches_fp():
    """Prompt overflowing the window: the paged ring prefill must keep each
    ring slot's latest *real* position (token-granular, deterministic) just
    like the dense ring slab — a page-wise scatter would let the wrapped
    last page clobber in-window history with padding junk."""
    cfg = _no_astra(get_config("gemma2-27b").reduced())
    params = mf.init_params(jax.random.PRNGKey(0), cfg)
    prompt = [((7 * i) % (cfg.vocab_size - 2)) + 1
              for i in range(cfg.window_size + 5)]  # 5 past the window
    fp = ServingEngine(cfg, params, max_len=96, astra_mode="off",
                       decode_chunk=4)
    want = fp.generate([prompt], max_new_tokens=6, temperature=0.0).tokens
    pg = ServingEngine(cfg, params, max_len=96, astra_mode="off",
                       cache_mode="paged", page_size=8, decode_chunk=4)
    assert pg.generate([prompt], max_new_tokens=6,
                       temperature=0.0).tokens == want
    # continuous engine pads to max_len on top of the overflow
    def one(mode):
        eng = ContinuousBatchingEngine(cfg, params, slots=1, max_len=96,
                                       decode_chunk=2, cache_mode=mode,
                                       page_size=8)
        eng.submit(prompt, max_new_tokens=6)
        eng.run_until_drained()
        return eng.finished[0].output

    assert one("paged") == one("fp") == want[0]


def test_windowed_ring_prefill_ignores_prompt_padding():
    """Regression (found by backend unification): the scheduler pads every
    prompt to max_len, and the dense ring slab used to keep the *last S
    buffer positions* — pure right-padding junk whenever max_len > window —
    so windowed continuous decoding silently conditioned on garbage.  The
    ring prefill now gathers each slot's real position, so the continuous
    engine must match the static engine (whose prompts are never padded
    past the longest prompt) at max_len > window."""
    cfg = _no_astra(get_config("recurrentgemma-9b").reduced())
    params = mf.init_params(jax.random.PRNGKey(0), cfg)
    max_len = 2 * cfg.window_size  # padding region larger than the ring
    prompts = [[5, 9, 3, 7, 11], [2, 8]]
    static = ServingEngine(cfg, params, max_len=max_len, astra_mode="off",
                           decode_chunk=3)
    want = static.generate(prompts, max_new_tokens=6, temperature=0.0).tokens
    eng = ContinuousBatchingEngine(cfg, params, slots=2, max_len=max_len,
                                   decode_chunk=2)
    for p in prompts:
        eng.submit(p, max_new_tokens=6)
    eng.run_until_drained()
    got = {tuple(r.prompt): r.output for r in eng.finished}
    for p, w in zip(prompts, want):
        assert got[tuple(p)] == w, (p, got[tuple(p)], w)


# ---------------------------------------------------------------------------
# Resident page pools: the layer scan carries the stacked pools and each
# layer writes its tokens in place (transformer.run_stages)
# ---------------------------------------------------------------------------


def _pool_hits() -> int:
    return kops.PATH_INVOCATIONS["pool_in_place"]


def _prefill_then_decode(cfg, params, mode, prompts, prefill_mode):
    """The cache tree after a prefill and after one 3-step decode chunk
    (as host arrays), and the ``pool_in_place`` hits traced on the way."""
    eng = ServingEngine(cfg, params, max_len=96, astra_mode="off",
                        cache_mode=mode, page_size=8, decode_chunk=3,
                        prefill_mode=prefill_mode)
    b = len(prompts)
    lens = np.array([len(p) for p in prompts], np.int32)
    toks = np.zeros((b, int(lens.max())), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    before = _pool_hits()
    last, caches, tables = eng._run_prefill(toks, lens, 8)
    prefilled = jax.device_get(caches)
    out = eng._decode_chunk(
        eng.params, jnp.argmax(last, -1).astype(jnp.int32), caches,
        jnp.asarray(lens), jnp.full((b,), 8, jnp.int32),
        jnp.full((b,), -1, jnp.int32), jnp.zeros((b,), bool),
        jax.random.PRNGKey(0), tables, num_steps=3, temperature=0.0,
        top_k=0)
    return prefilled, jax.device_get(out[3]), _pool_hits() - before


# (arch, mode): gpt2 global pools, gpt2 code pools, gemma2's windowed ring
# group beside its global group; the gemma2 prompt overflows the 64-token
# window, so the ring prefill takes the token-granular keep-latest path
RESIDENT_CASES = [("gpt2-small", "paged"), ("gpt2-small", "paged_vq"),
                  ("gemma2-27b", "paged")]


@pytest.mark.parametrize("prefill_mode", ["chunked", "padded"])
@pytest.mark.parametrize("arch,mode", RESIDENT_CASES)
def test_resident_pools_match_layer_by_layer_writes(arch, mode,
                                                    prefill_mode,
                                                    monkeypatch):
    """Pools written in place through the stacked arrays equal, bit for
    bit, pools written layer by layer (each layer's pool sliced out, written
    through the functional ``_pool_write`` / ``_scatter_pages`` and stacked
    back) — scratch page 0 included."""
    astra = mode == "paged_vq"
    if arch == "gpt2-small":
        cfg, params = small_lm(astra)
        prompts = [[5, 9, 3], [7, 2, 8, 4, 1, 6, 3, 2, 9, 10, 4]]
    else:
        cfg = _no_astra(get_config(arch).reduced())
        params = mf.init_params(jax.random.PRNGKey(0), cfg)
        prompts = [[(7 * i) % 50 + 1 for i in range(cfg.window_size + 5)],
                   [2, 8]]
    got = _prefill_then_decode(cfg, params, mode, prompts, prefill_mode)
    monkeypatch.setattr(cbe.PagedBackend, "resident_keys", frozenset())
    want = _prefill_then_decode(cfg, params, mode, prompts, prefill_mode)
    assert got[2] > 0 and want[2] == 0
    for g, w in zip(got[:2], want[:2]):
        assert (jax.tree_util.tree_structure(g)
                == jax.tree_util.tree_structure(w))
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
    pools = [x for stage in got[1] for sub in stage.values()
             for k, x in sub.items() if k in kvc.PAGED_LEAF_KEYS]
    assert any(np.any(x[:, 0]) for x in pools)  # scratch page 0 was hit
    if arch == "gemma2-27b":  # a window ring group beside the global one
        assert len({x.shape[1] for x in pools}) == 2


@pytest.mark.parametrize("name", sorted(SPECS))
def test_pool_in_place_counter_follows_the_backend(name):
    """Single-host page pools take the resident path in every traced step;
    slabs and the sequence-sharded wrapper (paged or not) never do."""
    mode, _, sharded, _ = SPECS[name]
    before = _pool_hits()
    static_gen(name, PROMPTS[:2], 4)
    resident = bool(cbe.get_backend(mode, seq_sharded=sharded).resident_keys)
    assert resident == (name in ("paged", "paged_vq"))
    assert (_pool_hits() > before) == resident


@pytest.mark.parametrize("mode", ["paged", "paged_vq"])
def test_pool_in_place_engages_in_verify(mode):
    cfg, params = small_lm(mode == "paged_vq")
    eng = ServingEngine(cfg, params, max_len=64, astra_mode="off",
                        cache_mode=mode, page_size=8, speculative=2)
    lens = np.array([3, 5], np.int32)
    toks = np.array([[5, 9, 3, 0, 0], [7, 2, 8, 4, 1]], np.int32)
    _, caches, tables = eng._run_prefill(toks, lens, 6)
    before = _pool_hits()
    eng._verify_chunk.lower(
        eng.params, jnp.zeros((2,), jnp.int32), jnp.zeros((2, 2), jnp.int32),
        caches, jnp.asarray(lens), jnp.full((2,), 6, jnp.int32),
        jnp.full((2,), -1, jnp.int32), jnp.zeros((2,), bool),
        jax.random.PRNGKey(0), tables, num_drafted=2, temperature=0.0,
        top_k=0)
    assert _pool_hits() > before


# ---------------------------------------------------------------------------
# Decode-chunk autotune: sweep persists, engines read
# ---------------------------------------------------------------------------


def test_autotune_sweep_persists_and_engines_read(tmp_path, monkeypatch):
    monkeypatch.setattr(serving_autotune, "RESULTS_DIR", str(tmp_path))
    cfg, params = small_lm()
    out = serving_autotune.sweep_decode_chunk(
        cfg, params, batch=2, max_len=64, prompt_len=4, max_new_tokens=8,
        candidates=(2, 4), repeats=1)
    best = out["best_decode_chunk"]
    assert best in (2, 4)
    assert (tmp_path / f"decode_chunk_{cfg.name}.json").exists()
    assert serving_autotune.load_decode_chunk(cfg.name) == best
    assert serving_autotune.load_decode_chunk(cfg.name, batch=2) == best
    # engines constructed without an explicit decode_chunk pick up the winner
    eng = ServingEngine(cfg, params, max_len=64, astra_mode="off")
    assert eng.decode_chunk == best
    ceng = ContinuousBatchingEngine(cfg, params, slots=2, max_len=64)
    assert ceng.decode_chunk == best


def test_autotune_absent_falls_back_to_defaults(tmp_path, monkeypatch):
    monkeypatch.setattr(serving_autotune, "RESULTS_DIR", str(tmp_path))
    cfg, params = small_lm()
    from repro.serving import engine as engine_mod
    from repro.serving import scheduler as scheduler_mod

    assert serving_autotune.load_decode_chunk(cfg.name) is None
    eng = ServingEngine(cfg, params, max_len=64, astra_mode="off")
    assert eng.decode_chunk == engine_mod.DEFAULT_DECODE_CHUNK
    ceng = ContinuousBatchingEngine(cfg, params, slots=2, max_len=64)
    assert ceng.decode_chunk == scheduler_mod.DEFAULT_DECODE_CHUNK


# ---------------------------------------------------------------------------
# No cache_mode string dispatch outside serving/cache_backend.py
# ---------------------------------------------------------------------------


def test_no_cache_mode_dispatch_outside_cache_backend():
    # the tokenize-based grep lives in repro.analysis now (rule
    # cache-mode-dispatch, with serving/cache_backend.py as the structural
    # exemption); this stays the backend-owned assertion over the tree
    from repro.analysis import run_rules

    findings = run_rules(rules=["cache-mode-dispatch"])
    assert not findings, (
        "cache_mode string dispatch outside serving/cache_backend.py (add "
        "a CacheBackend method instead):\n"
        + "\n".join(str(f) for f in findings))
