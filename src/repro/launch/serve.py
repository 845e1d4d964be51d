"""Serving launcher: batched generation with the ASTRA engine.

On CPU this serves a reduced-config model end-to-end (prefill + decode with
per-request lengths); on a pod the same engine runs with a sequence-sharded
mesh context.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch gpt2-small --reduced \
      --requests 8 --max-new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_factory as mf
from repro.serving.cache_backend import CACHE_MODES
from repro.serving.engine import ServingEngine
from repro.training import checkpoint


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--cache-mode", default="fp", choices=list(CACHE_MODES))
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size (tokens) for the paged cache modes")
    ap.add_argument("--decode-chunk", type=int, default=0,
                    help="on-device decode chunk size; 0 = the persisted "
                         "autotune winner (results/autotune/) or the "
                         "engine default")
    ap.add_argument("--use-pallas", action="store_true",
                    help="route the attention hot loops (decode + chunked "
                         "prefill, every cache mode) through the Pallas "
                         "kernels: compiled on TPU, interpret-mode (slow, "
                         "correctness-equivalent) elsewhere")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="speculative decoding: draft K tokens per round "
                         "and verify all K+1 positions in one jitted step "
                         "(K snaps onto serving.steps.SPEC_K_LADDER); "
                         "greedy outputs are identical to plain decode")
    ap.add_argument("--draft", default="ngram",
                    help="drafter for --speculative: 'ngram' (self-draft "
                         "from each row's history), 'auto' (the paired "
                         "model from repro.configs.DRAFT_PAIRS, randomly "
                         "initialized unless --draft-checkpoint), or a "
                         "config name")
    ap.add_argument("--draft-checkpoint", default="",
                    help="checkpoint for the paired draft model")
    ap.add_argument("--disagg", default="", metavar="P:D",
                    help="disaggregated serving: prefill on P devices, "
                         "decode on D (seq-sharded within each group when "
                         ">1); the finished prefill cache migrates between "
                         "the groups — as VQ codes under --cache-mode vq — "
                         "and the hand-off bytes are reported against the "
                         "fp baseline at 10/100/500 Mbps")
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the continuous-batching scheduler "
                         "(slot-based admission, chunked prefill, "
                         "priority/deadline-aware preemption) instead of "
                         "one static batch")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots for --continuous")
    ap.add_argument("--priority", default="",
                    help="comma-separated priority classes cycled across "
                         "the requests (lower = more urgent, e.g. "
                         "'0,1,1,2'); default: every request class 1. "
                         "Needs --continuous")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request TTFT deadline in scheduler steps "
                         "(0 = none); missed deadlines still finish but "
                         "count against goodput. Needs --continuous")
    ap.add_argument("--preempt-mode", default="swap",
                    choices=("swap", "recompute"),
                    help="how --continuous evicts a low-priority decode "
                         "under pressure: 'swap' stashes its exact cache "
                         "bytes host-side (bitwise restore), 'recompute' "
                         "re-prefills on re-admission")
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if (args.priority or args.deadline) and not args.continuous:
        raise SystemExit("--priority/--deadline need --continuous (the "
                         "static engine has no scheduler to honor them)")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.arch_type in ("vit",):
        raise SystemExit("vit is not generative; use launch.train")

    key = jax.random.PRNGKey(args.seed)
    params = mf.init_params(key, cfg)
    if args.checkpoint:
        params = checkpoint.restore(args.checkpoint, params)

    draft = None
    if args.speculative and args.draft != "ngram":
        from repro.configs import draft_for

        dname = draft_for(args.arch) if args.draft == "auto" else args.draft
        dcfg = get_config(dname)
        if args.reduced:
            dcfg = dcfg.reduced()
        dparams = mf.init_params(jax.random.PRNGKey(args.seed + 1), dcfg)
        if args.draft_checkpoint:
            dparams = checkpoint.restore(args.draft_checkpoint, dparams)
        draft = (dcfg, dparams)

    if args.continuous:
        from repro.serving.scheduler import ContinuousBatchingEngine

        if args.disagg:
            raise SystemExit("--continuous does not compose with --disagg")
        if args.speculative and args.draft != "ngram":
            raise SystemExit("--continuous drafts by n-gram only")
        eng = ContinuousBatchingEngine(
            cfg, params, slots=args.slots, max_len=args.max_len,
            astra_mode="off", cache_mode=args.cache_mode,
            page_size=args.page_size,
            decode_chunk=args.decode_chunk or None,
            temperature=args.temperature, seed=args.seed,
            use_pallas=args.use_pallas, speculative=args.speculative,
            preempt_mode=args.preempt_mode)
        classes = ([int(x) for x in args.priority.split(",")]
                   if args.priority else [1])
        rng = np.random.RandomState(args.seed)
        for i in range(args.requests):
            prompt = rng.randint(
                1, cfg.vocab_size,
                size=rng.randint(4, args.prompt_len + 1)).tolist()
            eng.submit(prompt, args.max_new_tokens,
                       priority=classes[i % len(classes)],
                       deadline=args.deadline or None)
        stats = eng.run_until_drained()
        slo = stats["slo"]
        print(f"arch={cfg.name} continuous slots={args.slots} "
              f"requests={stats['requests']} tokens={stats['tokens']} "
              f"steps={stats['steps']} ({stats['tok_per_s']:.1f} tok/s)")
        print(f"  TTFT steps: mean {stats['mean_ttft_steps']:.1f} "
              f"p50 {stats['p50_ttft_steps']:.0f} "
              f"p99 {stats['p99_ttft_steps']:.0f} | "
              f"TTFT ms p50 {stats['ttft_ms_p50']:.1f} "
              f"p90 {stats['ttft_ms_p90']:.1f} | "
              f"end-to-end ms p50 {stats['e2e_ms_p50']:.1f} "
              f"p90 {stats['e2e_ms_p90']:.1f} | "
              f"stall episodes {stats['admission_stalls']} | "
              f"preemptions {stats['preemptions']}")
        print(f"  SLO: {slo['met']}/{slo['requests']} met "
              f"({slo['with_deadline']} with deadlines), goodput "
              f"{slo['goodput_tokens']} tok | swap "
              f"{stats['swap']['bytes_out']:,} B out")
        return

    if args.disagg:
        from repro.serving.disagg import DisaggregatedEngine

        if args.speculative:
            raise SystemExit("--disagg does not compose with --speculative")
        engine = DisaggregatedEngine(
            cfg, params, max_len=args.max_len, split=args.disagg,
            astra_mode="off", cache_mode=args.cache_mode,
            decode_chunk=args.decode_chunk or None,
            use_pallas=args.use_pallas)
    else:
        engine = ServingEngine(
            cfg, params, max_len=args.max_len,
            astra_mode="sim" if cfg.astra.enabled else "off",
            cache_mode=args.cache_mode, page_size=args.page_size,
            decode_chunk=args.decode_chunk or None,
            use_pallas=args.use_pallas,
            speculative=args.speculative, draft=draft)

    rng = np.random.RandomState(args.seed)
    prompts = [
        rng.randint(1, cfg.vocab_size,
                    size=rng.randint(4, args.prompt_len + 1)).tolist()
        for _ in range(args.requests)
    ]
    t0 = time.time()
    result = engine.generate(prompts, max_new_tokens=args.max_new_tokens,
                             temperature=args.temperature, seed=args.seed)
    dt = time.time() - t0
    total_new = sum(len(t) for t in result.tokens)
    print(f"arch={cfg.name} requests={args.requests} "
          f"new_tokens={total_new} wall={dt:.2f}s "
          f"({total_new/dt:.1f} tok/s)")
    if args.speculative:
        rounds = max(engine.spec_rounds, 1)
        print(f"speculative: k={engine.spec_k} rounds={engine.spec_rounds} "
              f"tokens/round={engine.spec_tokens / rounds:.2f}")
    for i, toks in enumerate(result.tokens[:4]):
        print(f"  req{i} len={len(prompts[i])} -> {toks[:12]}...")
    if args.disagg:
        rep = engine.migration_report()
        print(f"disagg {rep['split']} cache_mode={rep['cache_mode']}: "
              f"{rep['bytes_per_migration']:,.0f} B/migration, "
              f"{rep['compression']:.1f}x vs fp")
        for bw, t in rep["transfer_s"].items():
            print(f"  {bw} Mbps: fp {t['fp']*1e3:.2f} ms -> "
                  f"coded {t['coded']*1e3:.2f} ms")
    else:
        comm = engine.prefill_comm_bits_per_device(
            max(len(p) for p in prompts), 4)
        print(f"ASTRA prefill wire bits/device (4 dev): {comm:,.0f}")


if __name__ == "__main__":
    main()
