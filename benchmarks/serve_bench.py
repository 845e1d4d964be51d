"""Serving benchmark: prefill/decode throughput + compile/sync accounting.

Writes ``BENCH_serving.json`` — the serving-perf trajectory every later
perf PR diffs against.  Sections:

* **prefill**: static-engine wall-clock and tok/s vs prompt length at a
  fixed ``max_len`` for both prefill modes ("padded" = legacy one-shot
  prefill, "chunked" = the bucketed chunk pipeline).
* **admission**: the headline ``short_prompt_speedup`` — one short
  (<=128-token) request admitted through the continuous engine, whose
  padded path really does prefill a full ``(1, max_len)`` buffer.  Under a
  >=1024 ``max_len`` the chunked pipeline must admit it measurably (>=2x)
  faster: prefill cost scales with the prompt, not ``max_len``.
* **decode**: steady-state decode steps/s through the shared jitted chunk.
* **continuous**: ContinuousBatchingEngine drain stats (tok/s, TTFT,
  prefill chunk ticks) under chunked admission.
* **prefix_cache**: TTFT vs prefix-hit-rate rows through the paged
  engine with ``prefix_cache=True`` — a donor warms the radix prefix
  index, probes share {0, 50, 100}% of its prefix; a full hit runs only
  the divergent tail's chunks (asserted on ``prefill_chunk_ticks``) with
  greedy outputs identical to a prefix-cache-off engine.
* **pallas** (``--use-pallas``, implied by ``--smoke`` so the CI fast lane
  carries the row): the same small workload through ``use_pallas=True``
  vs the jnp reference.  On a box without a TPU the kernels execute in
  interpret mode, so the wall-clock column measures the *interpreter* and
  is marked ``interpret_mode: true`` — the assertable signal is greedy
  parity, identical compile counts and identical host syncs, which hold on
  every backend.
* **speculative** (``--speculate``; ``--smoke`` carries one row):
  draft/verify decoding through the static engine — accept-rate,
  tokens-per-round and tok/s vs draft length k (the ``SPEC_K_LADDER``
  rungs) and drafter mode (n-gram self-draft vs a paired draft model),
  with greedy output asserted token-identical to the sequential baseline
  and one verify compile per rung.
* **mesh** (``--mesh``; ``--smoke`` carries one row): the multi-device
  serving columns — a seq-sharded engine over every host device (greedy
  parity vs the single-host engine, decode tok/s, and the collective
  payload each compiled decode step moves, read off the optimized HLO),
  plus the disaggregated prefill/decode hand-off: per-migration bytes
  fp-vs-vq costed through ``core.comm_model`` at 10/100/500 Mbps.  On a
  single-device host the mesh collapses to one shard and the disagg rows
  are left out (the prefill and decode groups need a device each).
* compile counts (CountingJit traces) and host syncs for every engine run.
* **traffic** (written by ``benchmarks/traffic_bench.py``, merged into the
  same report): SLA numbers from seeded Poisson/bursty arrival traces
  through the priority/deadline scheduler.  One row per trace mode, each
  with ``p50_ttft_steps``/``p99_ttft_steps`` (plus ``mean_ttft_ms``),
  ``steps_per_token``/``ms_per_token``, ``goodput_tokens`` +
  ``goodput_tok_per_s`` (tokens from requests that met their TTFT
  deadline), ``slo`` (met/total per the trace's priority classes),
  ``admission_stalls`` (episodes), ``preemptions`` /
  ``preempted_requests``, ``swap`` (arena swap_outs/ins + bytes moved),
  and the replay artifact: the ``events`` log with its ``events_sha256``
  (identical across same-seed runs — the CI ``traffic`` lane diffs it).

Usage:  PYTHONPATH=src python -m benchmarks.serve_bench [--smoke]
            [--use-pallas] [--speculate] [--mesh] [--out F]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time


def _engine(cfg, params, mode, max_len, **kw):
    from repro.serving.engine import ServingEngine

    return ServingEngine(cfg, params, max_len=max_len, astra_mode="off",
                         prefill_mode=mode, **kw)


def bench_prefill(cfg, params, *, max_len, prompt_lens, repeats, seed=0):
    """Time generate(max_new_tokens=1) — prefill + one sampled token — per
    prompt length for both prefill modes."""
    import numpy as np

    rng = np.random.RandomState(seed)
    out = {}
    for mode in ("padded", "chunked"):
        eng = _engine(cfg, params, mode, max_len, decode_chunk=1)
        rows = []
        for pl in prompt_lens:
            prompts = [rng.randint(1, cfg.vocab_size, size=pl).tolist()]
            eng.generate(prompts, max_new_tokens=1)  # compile warmup
            t0 = time.perf_counter()
            for _ in range(repeats):
                eng.generate(prompts, max_new_tokens=1, seed=seed)
            dt = (time.perf_counter() - t0) / repeats
            rows.append({"prompt_len": int(pl), "wall_s": dt,
                         "prefill_tok_per_s": pl / dt})
        out[mode] = {
            "rows": rows,
            "prefill_compiles": (eng._prefill_chunk.trace_count
                                 if mode == "chunked"
                                 else eng._prefill.trace_count),
            "host_syncs": eng.host_syncs,
        }
    return out


def bench_decode(cfg, params, *, max_len, batch, max_new, repeats, seed=0):
    import numpy as np

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, cfg.vocab_size, size=8).tolist()
               for _ in range(batch)]
    eng = _engine(cfg, params, "chunked", max_len, decode_chunk=8)
    eng.generate(prompts, max_new_tokens=max_new)  # compile warmup
    t0 = time.perf_counter()
    for _ in range(repeats):
        eng.generate(prompts, max_new_tokens=max_new, seed=seed)
    dt = (time.perf_counter() - t0) / repeats
    return {
        "batch": batch, "max_new_tokens": max_new,
        "decode_steps_per_s": max_new / dt,
        "decode_tok_per_s": batch * max_new / dt,
        "decode_compiles": eng._decode_chunk.trace_count,
        "host_syncs": eng.host_syncs,
    }


def bench_admission(cfg, params, *, max_len, prompt_len, repeats, seed=0):
    """Admission latency for ONE short request per prefill mode: submit +
    drain with a 1-token budget, so the measurement is the scheduler's
    prefill path (padded = one (1, max_len)-wide step; chunked = the
    bucketed pipeline with prompt-sized attention views)."""
    import numpy as np

    from repro.serving.scheduler import ContinuousBatchingEngine

    rng = np.random.RandomState(seed)
    prompt = rng.randint(1, cfg.vocab_size, size=prompt_len).tolist()
    out = {}
    for mode in ("padded", "chunked"):
        eng = ContinuousBatchingEngine(cfg, params, slots=1, max_len=max_len,
                                       decode_chunk=1, prefill_mode=mode)
        eng.submit(prompt, max_new_tokens=1)
        eng.run_until_drained()  # compile warmup
        t0 = time.perf_counter()
        for _ in range(repeats):
            eng.submit(prompt, max_new_tokens=1)
            eng.run_until_drained()
        out[mode] = {"wall_s": (time.perf_counter() - t0) / repeats,
                     "prefill_compiles": (eng._prefill_chunk.trace_count
                                          if mode == "chunked"
                                          else eng._prefill.trace_count)}
    out["prompt_len"] = int(prompt_len)
    out["speedup_chunked_vs_padded"] = (out["padded"]["wall_s"]
                                        / out["chunked"]["wall_s"])
    return out


def bench_continuous(cfg, params, *, max_len, n_requests, prompt_len,
                     max_new, seed=0):
    import numpy as np

    from repro.serving.scheduler import ContinuousBatchingEngine

    rng = np.random.RandomState(seed)
    eng = ContinuousBatchingEngine(cfg, params, slots=4, max_len=max_len,
                                   decode_chunk=4)
    for _ in range(n_requests):
        pl = int(rng.randint(2, prompt_len + 1))
        eng.submit(rng.randint(1, cfg.vocab_size, size=pl).tolist(),
                   max_new_tokens=max_new)
    stats = eng.run_until_drained()
    stats["prefill_chunk_ticks"] = eng.prefill_chunk_ticks
    stats["prefill_compiles"] = eng._prefill_chunk.trace_count
    stats["decode_compiles"] = eng._decode_chunk.trace_count
    stats["host_syncs"] = eng.host_syncs
    return stats


def bench_pallas(cfg, params, *, max_len, prompt_lens, max_new, repeats,
                 seed=0):
    """The --use-pallas column: one small chunked-prefill + decode workload
    through both attention routes.  Returns per-route wall/compile/sync
    rows plus the cross-route invariants the CI lane asserts."""
    import numpy as np

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, cfg.vocab_size, size=pl).tolist()
               for pl in prompt_lens]
    rows, toks = {}, {}
    for use_pallas in (False, True):
        eng = _engine(cfg, params, "chunked", max_len, decode_chunk=4,
                      use_pallas=use_pallas)
        res = eng.generate(prompts, max_new_tokens=max_new)  # compile warmup
        t0 = time.perf_counter()
        for _ in range(repeats):
            eng.generate(prompts, max_new_tokens=max_new, seed=seed)
        dt = (time.perf_counter() - t0) / repeats
        key = "pallas" if use_pallas else "jnp"
        rows[key] = {
            "wall_s": dt,
            "decode_tok_per_s": len(prompts) * max_new / dt,
            "prefill_compiles": eng._prefill_chunk.trace_count,
            "decode_compiles": eng._decode_chunk.trace_count,
            "host_syncs": eng.host_syncs,
        }
        toks[key] = res.tokens
    from repro.kernels import ops as kops

    out = {
        # interpret-mode wall-clock measures the interpreter, not the TPU
        # kernel — only the invariants below are meaningful off-TPU
        "interpret_mode": not kops.on_tpu(),
        "prompt_lens": [int(p) for p in prompt_lens],
        "max_new_tokens": int(max_new),
        "jnp": rows["jnp"],
        "pallas": rows["pallas"],
        "greedy_parity": toks["jnp"] == toks["pallas"],
        "compile_parity": (
            rows["jnp"]["prefill_compiles"] == rows["pallas"]["prefill_compiles"]
            and rows["jnp"]["decode_compiles"] == rows["pallas"]["decode_compiles"]),
        "host_sync_parity": (
            rows["jnp"]["host_syncs"] == rows["pallas"]["host_syncs"]),
    }
    assert out["greedy_parity"] and out["compile_parity"] \
        and out["host_sync_parity"], out
    return out


def bench_prefix_cache(cfg, params, *, max_len, prefix_len, tail_len,
                       max_new, repeats, seed=0):
    """TTFT vs prefix-hit-rate: a donor request warms the radix prefix
    index, then probes sharing {0, 50, 100}% of the donor's prefix admit
    through a fresh-token tail.  A full hit must skip the shared prefix's
    chunks entirely (only the divergent tail's chunks run), so TTFT and
    ``prefill_chunk_ticks`` fall with the hit rate; greedy outputs stay
    token-identical to a prefix-cache-off engine."""
    import numpy as np

    from repro.serving.scheduler import ContinuousBatchingEngine

    rng = np.random.RandomState(seed)
    prefix = rng.randint(1, cfg.vocab_size, size=prefix_len).tolist()
    donor = prefix + rng.randint(1, cfg.vocab_size, size=tail_len).tolist()

    def make_engine(prefix_cache):
        return ContinuousBatchingEngine(
            cfg, params, slots=2, max_len=max_len, decode_chunk=2,
            cache_mode="paged", page_size=8, prefill_chunk=32,
            prefix_cache=prefix_cache)

    rows = []
    for hit_rate in (0.0, 0.5, 1.0):
        shared = int(prefix_len * hit_rate)
        eng = make_engine(True)
        eng.submit(donor, max_new_tokens=max_new)
        eng.run_until_drained()  # warm the index (and the compile cache)
        best, ticks, parity = float("inf"), None, True
        for rep in range(repeats):
            # fresh divergent tail per repeat: a drained probe inserts its
            # own pages, so re-submitting it verbatim would measure a 100%
            # hit on every later repeat regardless of hit_rate
            probe = (prefix[:shared] + rng.randint(
                1, cfg.vocab_size,
                size=prefix_len - shared + tail_len).tolist())
            ticks0 = eng.prefill_chunk_ticks
            t0 = time.perf_counter()
            uid = eng.submit(probe, max_new_tokens=max_new)
            while not any(r is not None and r.uid == uid and r.output
                          for r in list(eng.active) + eng.finished):
                eng.step()
            best = min(best, time.perf_counter() - t0)
            eng.run_until_drained()
            if ticks is None:
                ticks = eng.prefill_chunk_ticks - ticks0
            if rep == 0:
                cold = make_engine(False)
                cold.submit(probe, max_new_tokens=max_new)
                cold.run_until_drained()
                probe_out = next(r.output for r in eng.finished
                                 if r.uid == uid)
                parity = probe_out == cold.finished[-1].output
        rows.append({
            "hit_rate": hit_rate,
            "shared_tokens": shared,
            "ttft_s": best,
            "prefill_chunk_ticks": ticks,
            "prefix_hit_tokens": eng.prefix_hit_tokens,
            "token_parity_vs_cold": parity,
        })
    out = {
        "prefix_len": int(prefix_len),
        "tail_len": int(tail_len),
        "rows": rows,
        "full_hit_tick_reduction":
            rows[0]["prefill_chunk_ticks"] - rows[-1]["prefill_chunk_ticks"],
    }
    # a fully cached prefix must not re-prefill: only the tail's chunks run
    assert rows[-1]["prefill_chunk_ticks"] < rows[0]["prefill_chunk_ticks"], out
    assert all(r["token_parity_vs_cold"] for r in rows), out
    return out


def bench_speculative(cfg, params, *, max_len, batch, max_new, repeats,
                      ks=(2, 4, 8), modes=("ngram", "model"), seed=0):
    """The ``--speculate`` section: accept-rate, tokens/round and tok/s vs
    draft length k and drafter mode, against the sequential-decode
    baseline.  Greedy spec output is asserted token-identical to the
    baseline first — losslessness is the contract, the knobs only move
    throughput.  "ngram" self-drafts from each row's history (cyclic
    prompts here so the lookup has something to find); "model" pairs the
    target with itself — every greedy proposal is the target's own argmax,
    an acceptance upper bound that must clear 1 token/round."""

    prompts = [([5, 9, 3, 7, 11, 2] * max_len)[:8 + 2 * i]
               for i in range(batch)]

    def timed(eng):
        got = eng.generate(prompts, max_new_tokens=max_new,
                           temperature=0.0).tokens  # compile warmup
        t0 = time.perf_counter()
        for _ in range(repeats):
            eng.generate(prompts, max_new_tokens=max_new, temperature=0.0,
                         seed=seed)
        return got, (time.perf_counter() - t0) / repeats

    base = _engine(cfg, params, "chunked", max_len, decode_chunk=4)
    want, base_dt = timed(base)
    new_tokens = sum(len(t) for t in want)
    rows = []
    for mode_name in modes:
        draft = None if mode_name == "ngram" else (cfg, params)
        for k in ks:
            eng = _engine(cfg, params, "chunked", max_len,
                          speculative=k, draft=draft)
            got, dt = timed(eng)
            assert got == want, (mode_name, k)  # lossless by construction
            per_round = eng.spec_tokens / max(eng.spec_active_rows, 1)
            rows.append({
                "drafter": mode_name,
                "k": eng.spec_k,
                "wall_s": dt,
                "tok_per_s": new_tokens / dt,
                "speedup_vs_sequential": base_dt / dt,
                "tokens_per_round": per_round,
                # drafted positions accepted per active row-round
                "accept_rate": (per_round - 1) / eng.spec_k,
                "rounds": eng.spec_rounds,
                "verify_compiles": eng._verify_chunk.trace_count,
                "host_syncs": eng.host_syncs,
            })
            # one verify trace per rung, ever — the k-ladder contract
            assert eng._verify_chunk.trace_count == 1, rows[-1]
    out = {
        "batch": batch, "max_new_tokens": int(max_new),
        "baseline": {"wall_s": base_dt, "tok_per_s": new_tokens / base_dt},
        "rows": rows,
    }
    # speculation must actually speculate: some row clears 1 token/round
    assert any(r["tokens_per_round"] > 1.0 for r in rows), out
    return out


def bench_mesh(cfg, params, *, arch, max_len, prompt_lens, max_new,
               repeats, migrate_modes=("fp", "vq"),
               bandwidths_mbps=(10.0, 100.0, 500.0), seed=0):
    """The ``--mesh`` section: seq-sharded serving + disaggregated hand-off.

    *serving*: one engine on a mesh over every host device (1 shard when
    ``max_len`` does not divide) vs the single-host reference — greedy
    parity, decode tok/s, and ``collective_bytes_per_decode_step``: the
    summed result payload of every collective in the compiled decode
    chunk.  The per-step body lowers once inside the scan, so this is the
    wire traffic each decode step moves — the number the partial-stats
    merge keeps at (B, H)-sized stats instead of embed-sized gathers.

    *migration*: a ``DisaggregatedEngine`` per cache mode (``vq`` builds
    its own astra-enabled model for the codebooks); ``migration_report``
    costs the measured hand-off bytes against the fp-equivalent bytes of
    the same tree at the paper's bandwidth grid.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis import hlo as hlo_lint
    from repro.compat import make_mesh
    from repro.configs import get_config
    from repro.core.sequence_parallel import MeshContext
    from repro.models import model_factory as mf
    from repro.serving.disagg import DisaggregatedEngine

    n = jax.device_count()
    num_shards = n if max_len % n == 0 else 1
    mesh_kw = {}
    if num_shards > 1:
        mesh_kw["mesh_ctx"] = MeshContext(
            mesh=make_mesh((num_shards,), ("model",)), batch_axes=(),
            seq_axis="model")
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, cfg.vocab_size, size=pl).tolist()
               for pl in prompt_lens]
    b = len(prompts)

    ref = _engine(cfg, params, "chunked", max_len, decode_chunk=4)
    want = ref.generate(prompts, max_new_tokens=max_new,
                        temperature=0.0).tokens
    eng = _engine(cfg, params, "chunked", max_len, decode_chunk=4, **mesh_kw)
    got = eng.generate(prompts, max_new_tokens=max_new,
                       temperature=0.0).tokens  # compile warmup + parity
    t0 = time.perf_counter()
    for _ in range(repeats):
        eng.generate(prompts, max_new_tokens=max_new, temperature=0.0,
                     seed=seed)
    dt = (time.perf_counter() - t0) / repeats

    # lower the jitted decode chunk exactly as the engine calls it and
    # read the collective payload off the optimized HLO
    toks = np.zeros((b, max(len(p) for p in prompts)), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lens = np.array([len(p) for p in prompts], np.int32)
    _, caches, tables = eng._run_prefill(toks, lens, max_new)
    lowered = eng._decode_chunk.lower(
        eng.params, jnp.zeros((b,), jnp.int32), caches, jnp.asarray(lens),
        jnp.full((b,), max_new, jnp.int32), jnp.full((b,), -1, jnp.int32),
        jnp.zeros((b,), bool), jax.random.PRNGKey(0), tables,
        num_steps=eng.decode_chunk, temperature=0.0, top_k=0)
    hlo = lowered.compile().as_text()
    colls = hlo_lint.find_collectives(hlo)
    leaf = jax.tree.leaves(params)[0]
    embed_bytes = cfg.vocab_size * cfg.d_model * leaf.dtype.itemsize
    serving = {
        "num_shards": num_shards,
        "greedy_parity": got == want,
        "wall_s": dt,
        "decode_tok_per_s": b * max_new / dt,
        "collective_bytes_per_decode_step": sum(c.bytes for c in colls),
        "num_collectives": len(colls),
        "largest_allgather_bytes":
            hlo_lint.largest_allgather_bytes(hlo),
        "prefill_compiles": eng._prefill_chunk.trace_count,
        "decode_compiles": eng._decode_chunk.trace_count,
    }
    assert serving["greedy_parity"], (got, want)
    # the dryrun/trace_audit invariant, re-asserted on the bench artifact:
    # no embed-sized all-gather in the sharded decode step
    assert serving["largest_allgather_bytes"] < embed_bytes, serving

    half = max(num_shards // 2, 1)
    migration = {}
    # disjoint prefill / decode groups need two devices at least
    for mode in (migrate_modes if n >= 2 else ()):
        if mode == "vq":  # vq layouts need the astra codebooks in params
            mcfg = get_config(arch).reduced()
            mparams = mf.init_params(jax.random.PRNGKey(0), mcfg)
        else:
            mcfg, mparams = cfg, params
        mref = _engine(mcfg, mparams, "chunked", max_len, decode_chunk=4,
                       cache_mode=mode)
        mwant = mref.generate(prompts, max_new_tokens=max_new,
                              temperature=0.0).tokens
        deng = DisaggregatedEngine(
            mcfg, mparams, max_len=max_len, split=f"{half}:{half}",
            cache_mode=mode, decode_chunk=4,
            bandwidths_mbps=bandwidths_mbps)
        dtoks = deng.generate(prompts, max_new_tokens=max_new,
                              temperature=0.0).tokens
        rep = deng.migration_report()
        rep["greedy_parity"] = dtoks == mwant
        migration[mode] = rep
        if mode == "vq":
            # the hand-off acceptance bar: codes <= 1/8 of the fp bytes
            assert rep["coded_bytes"] * 8 <= rep["fp_bytes"], rep
        else:
            assert rep["coded_bytes"] == rep["fp_bytes"], rep
        assert rep["greedy_parity"], (mode, dtoks)
    return {
        "num_shards": num_shards,
        "max_len": int(max_len),
        "prompt_lens": [int(p) for p in prompt_lens],
        "max_new_tokens": int(max_new),
        "serving": serving,
        "migration": migration,
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small max_len, one repeat); "
                         "implies --use-pallas")
    ap.add_argument("--use-pallas", action="store_true",
                    help="add the Pallas-kernel attention column "
                         "(interpret-mode numbers marked as such off-TPU)")
    ap.add_argument("--speculate", action="store_true",
                    help="add the speculative-decoding section: accept "
                         "rate / tokens-per-round / tok/s vs draft length "
                         "k and drafter mode (n-gram self-draft + paired "
                         "draft model); --smoke carries one row")
    ap.add_argument("--mesh", action="store_true",
                    help="add the multi-device section: seq-sharded "
                         "serving over every host device (parity, tok/s, "
                         "collective bytes per compiled decode step) and "
                         "the disaggregated fp-vs-vq hand-off costed at "
                         "10/100/500 Mbps; --smoke carries one row")
    ap.add_argument("--arch", default="gpt2-small")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_serving.json"))
    args = ap.parse_args(argv)

    import jax
    import numpy as np  # noqa: F401  (seeded helpers above)

    from repro.configs import get_config
    from repro.models import model_factory as mf

    cfg = get_config(args.arch).reduced()
    cfg = dataclasses.replace(
        cfg, astra=dataclasses.replace(cfg.astra, enabled=False))
    params = mf.init_params(jax.random.PRNGKey(0), cfg)

    if args.smoke:
        max_len, prompt_lens, repeats = 256, (16, 48), 1
        adm_kw = dict(prompt_len=24, repeats=1)
        decode_kw = dict(batch=2, max_new=16, repeats=1)
        cont_kw = dict(n_requests=4, prompt_len=24, max_new=6)
        px_kw = dict(prefix_len=64, tail_len=8, max_new=4, repeats=1)
    else:
        max_len, prompt_lens, repeats = 1024, (16, 64, 128, 256, 512), 3
        adm_kw = dict(prompt_len=64, repeats=3)
        decode_kw = dict(batch=4, max_new=64, repeats=3)
        cont_kw = dict(n_requests=12, prompt_len=96, max_new=24)
        px_kw = dict(prefix_len=128, tail_len=16, max_new=8, repeats=3)

    t0 = time.time()
    prefill = bench_prefill(cfg, params, max_len=max_len,
                            prompt_lens=prompt_lens, repeats=repeats)
    admission = bench_admission(cfg, params, max_len=max_len, **adm_kw)
    report = {
        "arch": cfg.name,
        "smoke": bool(args.smoke),
        "max_len": max_len,
        "prefill": prefill,
        "admission": admission,
        "short_prompt_speedup_chunked_vs_padded":
            admission["speedup_chunked_vs_padded"],
        "decode": bench_decode(cfg, params, max_len=max_len, **decode_kw),
        "continuous": bench_continuous(cfg, params, max_len=max_len,
                                       **cont_kw),
        "prefix_cache": bench_prefix_cache(cfg, params, max_len=max_len,
                                           **px_kw),
    }
    if args.use_pallas or args.smoke:
        # always smoke-sized: off-TPU the kernels run interpreted, so a
        # bigger workload would only benchmark the interpreter harder
        report["pallas"] = bench_pallas(cfg, params, max_len=min(max_len, 256),
                                        prompt_lens=(16, 48), max_new=8,
                                        repeats=1)
    if args.speculate or args.smoke:
        spec_kw = (dict(batch=2, max_new=8, repeats=1, ks=(2,),
                        modes=("model",))  # one row rides the CI lane
                   if args.smoke else
                   dict(batch=4, max_new=24, repeats=3))
        report["speculative"] = bench_speculative(
            cfg, params, max_len=min(max_len, 256), **spec_kw)
    if args.mesh or args.smoke:
        mesh_kw = (dict(prompt_lens=(9, 16), max_new=8, repeats=1,
                        migrate_modes=("vq",))  # one row rides the CI lane
                   if args.smoke else
                   dict(prompt_lens=(16, 64), max_new=16, repeats=3))
        report["mesh"] = bench_mesh(cfg, params, arch=args.arch,
                                    max_len=min(max_len, 256), **mesh_kw)
    report["bench_wall_s"] = time.time() - t0
    out_path = os.path.abspath(args.out)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"# serve_bench ({cfg.name}, max_len={max_len})")
    for mode in ("padded", "chunked"):
        for r in prefill[mode]["rows"]:
            print(f"  prefill[{mode}] len={r['prompt_len']:4d}: "
                  f"{r['wall_s'] * 1e3:8.1f} ms  "
                  f"({r['prefill_tok_per_s']:8.0f} tok/s)")
    print(f"  admission len={admission['prompt_len']}: "
          f"padded {admission['padded']['wall_s'] * 1e3:.1f} ms, "
          f"chunked {admission['chunked']['wall_s'] * 1e3:.1f} ms -> "
          f"{admission['speedup_chunked_vs_padded']:.2f}x")
    print(f"  decode: {report['decode']['decode_steps_per_s']:.1f} steps/s")
    print(f"  continuous: {report['continuous']['tok_per_s']:.1f} tok/s, "
          f"{report['continuous']['prefill_chunk_ticks']} prefill ticks")
    for r in report["prefix_cache"]["rows"]:
        print(f"  prefix-cache hit={r['hit_rate']:.1f}: "
              f"ttft {r['ttft_s'] * 1e3:8.1f} ms, "
              f"{r['prefill_chunk_ticks']} prefill ticks, "
              f"parity={r['token_parity_vs_cold']}")
    if "speculative" in report:
        for r in report["speculative"]["rows"]:
            print(f"  speculative[{r['drafter']}] k={r['k']}: "
                  f"{r['tokens_per_round']:.2f} tok/round "
                  f"(accept {r['accept_rate']:.2f}), "
                  f"{r['speedup_vs_sequential']:.2f}x vs sequential")
    if "mesh" in report:
        m = report["mesh"]
        s = m["serving"]
        print(f"  mesh[{m['num_shards']} shard(s)]: "
              f"{s['decode_tok_per_s']:.1f} tok/s, "
              f"{s['collective_bytes_per_decode_step']:,} B collective "
              f"per decode step ({s['num_collectives']} collectives), "
              f"parity={s['greedy_parity']}")
        for mode, r in m["migration"].items():
            print(f"  disagg[{mode}] {r['split']}: "
                  f"{r['bytes_per_migration']:,.0f} B/migration "
                  f"({r['compression']:.1f}x vs fp), "
                  f"parity={r['greedy_parity']}")
            for bw, t in r["transfer_s"].items():
                print(f"    {bw} Mbps: fp {t['fp'] * 1e3:8.2f} ms -> "
                      f"coded {t['coded'] * 1e3:8.2f} ms")
    if "pallas" in report:
        p = report["pallas"]
        tag = " [interpret]" if p["interpret_mode"] else ""
        print(f"  pallas{tag}: jnp {p['jnp']['wall_s'] * 1e3:.1f} ms vs "
              f"pallas {p['pallas']['wall_s'] * 1e3:.1f} ms; "
              f"parity greedy={p['greedy_parity']} "
              f"compiles={p['compile_parity']} "
              f"syncs={p['host_sync_parity']}")
    print(f"  -> {out_path}")
    return report


if __name__ == "__main__":
    main()
