"""The serving engine's tracing: profiler spans around each phase of a
tick, host stamps on every request, and named scopes in the compiled
steps, so a device trace can split the tick, TTFT and device time by
layer."""
import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import model_factory as mf
from repro.serving import kv_cache as kvc
from repro.serving import steps as serving_steps
from repro.serving.scheduler import ContinuousBatchingEngine

PHASES = ("engine.admit", "engine.decode_dispatch", "engine.sync",
          "engine.emit")
IN_ADMIT = ("engine.prefill_chunk", "engine.first_token")
_MODEL = {}


def small_lm():
    if not _MODEL:
        cfg = get_config("gpt2-small").reduced()
        cfg = dataclasses.replace(
            cfg, astra=dataclasses.replace(cfg.astra, enabled=False))
        _MODEL["m"] = (cfg, mf.init_params(jax.random.PRNGKey(0), cfg))
    return _MODEL["m"]


def _engine(**kw):
    cfg, params = small_lm()
    args = dict(slots=2, max_len=64, cache_mode="paged", page_size=8,
                decode_chunk=2, prefill_chunk=16, astra_mode="off")
    args.update(kw)
    return ContinuousBatchingEngine(cfg, params, **args)


PROMPTS = [[5, 9, 3, 7, 2, 8, 4, 1, 6, 2, 9, 3, 3, 7, 1, 5, 8, 2, 4],
           [11, 4, 4, 6, 2], [7, 7, 1, 3, 9, 2, 6, 8, 5]]


def _engine_events(log_dir):
    """(name, start_ns, end_ns, stats) of every ``engine.*`` host event."""
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("engine.")]


def _inside(ev, outer):
    return any(o[1] <= ev[1] and ev[2] <= o[2] for o in outer)


def test_engine_spans_nest_under_step_and_carry_uid(tmp_path):
    eng = _engine()
    eng.step()  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        uids = [eng.submit(p, 6) for p in PROMPTS]
        eng.step()
        eng.step()
        eng.preempt(0)
        eng.run_until_drained()
    events = _engine_events(tmp_path)
    by = {}
    for ev in events:
        by.setdefault(ev[0], []).append(ev)
    steps = by["engine.step"]
    assert len(steps) == eng.step_count - 1
    for name in PHASES:
        assert by[name] and all(_inside(ev, steps) for ev in by[name])
    for name in IN_ADMIT:
        assert by[name] and all(_inside(ev, by["engine.admit"])
                                for ev in by[name])
    # spans of one request carry its uid
    assert sorted(ev[3]["uid"] for ev in by["engine.submit"]) == uids
    assert {ev[3]["uid"] for ev in by["engine.first_token"]} == set(uids)
    assert {ev[3]["uid"] for ev in by["engine.prefill_chunk"]} == set(uids)
    victim = [ev[3]["uid"] for ev in by["engine.preempt"]]
    assert len(victim) == 1 and victim[0] in uids
    assert [ev[3]["uid"] for ev in by["engine.restore"]] == victim
    assert "uid" not in steps[0][3]


@pytest.mark.parametrize("prefill_mode,preempt_mode", [
    ("chunked", "swap"), ("chunked", "recompute"), ("padded", "swap")])
def test_request_stamps_ordered_through_preemption(prefill_mode,
                                                   preempt_mode):
    eng = _engine(prefill_mode=prefill_mode, preempt_mode=preempt_mode)
    uids = [eng.submit(p, 16) for p in PROMPTS[:2]]
    for _ in range(3):
        eng.step()
    victim = eng.active[0]
    assert victim is not None and victim.t_first is not None
    admitted, first = victim.t_admit, victim.t_first
    eng.preempt(0)
    stats = eng.run_until_drained()
    done = {r.uid: r for r in eng.finished}
    assert sorted(done) == sorted(uids)
    for r in done.values():
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done
    # the preempted request keeps its first admission and first token
    assert victim.preemptions == 1
    assert (victim.t_admit, victim.t_first) == (admitted, first)
    assert 0.0 < stats["ttft_ms_p50"] <= stats["ttft_ms_p90"]
    assert stats["ttft_ms_p50"] < stats["e2e_ms_p50"] <= stats["e2e_ms_p90"]


def test_queued_request_has_only_its_submit_stamp():
    eng = _engine(slots=1)
    eng.submit(PROMPTS[1], 4)
    eng.submit(PROMPTS[2], 4)
    eng.step()
    waiting = eng.queue[0]
    assert waiting.t_submit is not None
    assert (waiting.t_admit, waiting.t_first, waiting.t_done) == (
        None, None, None)


def _op_names(lowered):
    """The op_name metadata of the compiled HLO: what a device trace
    reports for each operation."""
    return set(re.findall(r'op_name="([^"]*)"',
                          lowered.compile().as_text()))


def _scopes(op_names):
    return {part for n in op_names for part in n.split("/")}


@pytest.mark.parametrize("use_pallas", [False, True])
def test_compiled_steps_carry_the_named_scopes(use_pallas):
    """decode_chunk and prefill_chunk of a paged engine name their page
    gather, pool writes, attention, MLP and sampling in the HLO op_name
    metadata that the device trace reports."""
    cfg, _ = small_lm()
    eng = _engine(use_pallas=use_pallas)
    slots = eng.slots
    lowered = eng._decode_chunk.lower(
        eng.params, eng.cur_token, eng.caches, eng.lengths,
        jnp.full((slots,), 4, jnp.int32), jnp.full((slots,), -1, jnp.int32),
        jnp.zeros((slots,), bool), jax.random.PRNGKey(0), eng._bt,
        num_steps=eng.decode_chunk, temperature=0.0, top_k=0)
    decode = _scopes(_op_names(lowered))
    assert {"page_gather", "kv_write", "attn_kernel", "attn_out", "qkv",
            "mlp", "embed", "head", "sample"} <= decode

    assert eng.backend.advance(eng.kv, 0, 24)
    caches = kvc.adopt_pools(eng.kv.init_cache(1, prefill_scratch=True),
                             eng.caches)
    w = serving_steps.plan_chunks(19, eng.prefill_buckets)[0][1]
    lowered = eng._prefill_chunk.lower(
        eng.params, jnp.zeros((1, w), jnp.int32), jnp.asarray(0, jnp.int32),
        caches, jnp.asarray([19], jnp.int32),
        jnp.zeros((1, cfg.vocab_size), jnp.float32),
        {k: t[:1] for k, t in eng.kv.tables().items()},
        history_len=serving_steps.view_bucket(w, eng.max_len))
    prefill = _scopes(_op_names(lowered))
    assert {"page_gather", "kv_write", "attn_kernel", "mlp",
            "head"} <= prefill

    # the slot merge moves dense per-slot leaves (paged pools are written
    # in place), so a slab layout exercises it
    slab = _engine(cache_mode="fp", use_pallas=use_pallas)
    merged = _scopes(_op_names(slab._merge.lower(
        slab.caches, slab.kv.init_cache(1), jnp.asarray(0, jnp.int32))))
    assert "slot_merge" in merged


def test_first_token_samples_under_its_scope():
    lowered = jax.jit(serving_steps.first_token).lower(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.float32),
        jnp.full((1,), -1, jnp.int32))
    assert "sample" in _scopes(_op_names(lowered))


def test_every_pallas_call_is_named_after_its_wrapper():
    """A kernel's trace events carry its wrapper's name
    (``fp_decode_attention`` is what ``decode_attn_roofline`` reads)."""
    import inspect

    from repro.kernels import mixed_attn, vq_assign, vq_decode_attn

    named = {}
    for mod in (mixed_attn, vq_assign, vq_decode_attn):
        for chunk in inspect.getsource(mod).split("\ndef ")[1:]:
            if "pl.pallas_call(" in chunk:
                wrapper = chunk.split("(", 1)[0]
                names = re.findall(r'pl\.pallas_call\(\s*\n[^\n]*\n'
                                   r'\s*name="(\w+)"', chunk)
                assert names == [wrapper], (mod.__name__, wrapper, names)
                named[wrapper] = mod.__name__
    assert len(named) == 6 and "fp_decode_attention" in named
