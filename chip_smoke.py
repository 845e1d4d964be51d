"""Chip smoke test: serve GPT-2 small at its published widths on a TPU.

Drives the serving main path once through the entry points a user calls,
with random weights made from ``--seed`` (``init_params``), and checks
what comes out by the repository's own means.  One process holds the chip
and starts no other.

  python3 chip_smoke.py             # one chip: phases (a), (b), (c)
  python3 chip_smoke.py --chips 4   # four chips: the mesh phase only

(a) every serving Pallas kernel, compiled, at GPT-2 small head widths
    against its ``kernels/ref.py`` oracle;
(b) ``ContinuousBatchingEngine`` (what ``repro.launch.serve --continuous``
    drives) with ``use_pallas=True``, cache modes ``fp`` and ``paged_vq``:
    8 slots, max_len 1024, 16 seeded requests with prompts of 16-512
    tokens and 32 new tokens each.  Every request must finish, the decode
    chunk must compile once, and the chunk-prefill and decode kernels must
    engage;
(c) the Pallas route against the jnp route on the chip: prefill
    last-token logits within ``ROUTE_LOGIT_RTOL``, greedy-token agreement
    printed.
--chips 4 runs only the mesh phase: (i) seq-sharded ``ServingEngine`` on
a 4-way sequence axis against the same engine on one device (greedy
parity, one decode compile, cache spread over all four devices); (ii)
ASTRA's ``astra_mode="spmd"`` prefill forward against ``astra_mode="sim"``
with four simulated shards (max |diff| within ``SPMD_LOGIT_RTOL``).

Lines starting with ``[smoke]`` are smoke output, not benchmark numbers.
The last line is one JSON object: ``{"ok": true, "device": {...}}``.  The
script exits non-zero, and prints no such line, when JAX finds no TPU or
any phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Kernel-vs-oracle tolerance.  The oracles run at float32 matmul precision;
# the kernels' in-kernel fp32 dots may take bf16 MXU passes (~3 significant
# digits), which this bounds for unit-variance inputs at head dim 64.
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2
# Pallas vs jnp route: max |logit diff| over max |logit| at the last prompt
# token.  Both routes share every projection; only attention differs
# (flash kernel vs dense einsum, both at the chip's default precision).
ROUTE_LOGIT_RTOL = 5e-2
# ASTRA spmd vs sim prefill (float32 matmul precision): the two paths do
# the same arithmetic in a different order.
SPMD_LOGIT_RTOL = 1e-3


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileStats:
    """Backend compiles (persistent-cache reads included) and persistent
    cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.cache_hits)

    def since(self, snap) -> str:
        s, c, h = snap
        return (f"compile {self.seconds - s:.2f}s over {self.compiles - c} "
                f"programs ({self.cache_hits - h} persistent-cache hits)")


def peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def gpt2_small():
    from repro.configs import get_config

    return get_config("gpt2-small")


# ---------------------------------------------------------------------------
# (a) kernels against their oracles
# ---------------------------------------------------------------------------


def _close(name, got, want, atol=KERNEL_ATOL, rtol=KERNEL_RTOL):
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise AssertionError(f"{name}: shape {got.shape} vs {want.shape} "
                             f"or non-finite output")
    err = float(np.max(np.abs(got - want)))
    bound = atol + rtol * float(np.max(np.abs(want)))
    if err > bound:
        raise AssertionError(f"{name}: max|diff| {err:.3e} > {bound:.3e}")
    return err


def phase_kernels(seed: int, cstats: CompileStats) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.mixed_attn import (chunk_flash_attention,
                                          chunk_flash_partials,
                                          mixed_flash_attention)
    from repro.kernels.ops import assign_codes, on_tpu
    from repro.kernels.vq_decode_attn import (fp_decode_attention,
                                              vq_decode_attention)

    assert on_tpu(), "kernels would run in interpret mode"
    cfg = gpt2_small()
    h, hd = cfg.num_heads, cfg.head_dim
    b, s, w, kk = 8, 1024, 128, cfg.astra.codebook_size
    # the coded kernels at GPT-2 head widths: 2 groups of 32 per head (the
    # GPT-2 config itself quantizes with one 768-wide group, which the
    # serving path dequantizes in jnp before the fp decode kernel)
    gph = 2
    dg = hd // gph
    g = h * gph
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 24))
    nrm = lambda shape: jax.random.normal(next(ks), shape, jnp.float32)
    codes = lambda shape, dt: jax.random.randint(
        next(ks), shape, 0, kk, jnp.int32).astype(dt)
    lengths = jnp.asarray([0, 15, 127, 128, 511, 700, 1000, 1023], jnp.int32)
    hi = jax.default_matmul_precision("float32")

    def run(name, kernel, oracle, compare):
        snap = cstats.snapshot()
        t0 = time.perf_counter()
        got = jax.block_until_ready(kernel())
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = jax.block_until_ready(kernel())
        t_warm = time.perf_counter() - t0
        with hi:
            want = jax.block_until_ready(jax.jit(oracle)())
        err = compare(got, want)
        log(f"kernel {name}: ok max|diff| {err:.3e}; first call "
            f"{t_first:.3f}s ({cstats.since(snap)}), warm call "
            f"{t_warm * 1e3:.3f} ms")

    def partials(got, want):
        (m, l, acc), (m_r, l_r, acc_r) = got, want
        _close("m", m, m_r)
        out = np.asarray(acc) / np.maximum(np.asarray(l)[..., None], 1e-30)
        out_r = np.asarray(acc_r) / np.maximum(
            np.asarray(l_r)[..., None], 1e-30)
        return _close("acc/l", out, out_r)

    # fp flash decode: 8 slots x 1024 positions, GPT-2 heads
    q = nrm((b, h, hd))
    k, v = nrm((b, s, h, hd)), nrm((b, s, h, hd))
    run("fp_decode_attention",
        lambda: fp_decode_attention(q, k, v, lengths),
        lambda: ref.fp_decode_attn_ref(q, k, v, lengths), partials)

    # coded flash decode over uint16 code slabs
    kc, vc = codes((b, s, g), jnp.uint16), codes((b, s, g), jnp.uint16)
    cbk, cbv = nrm((g, kk, dg)), nrm((g, kk, dg))
    run("vq_decode_attention",
        lambda: vq_decode_attention(q, kc, vc, cbk, cbv, lengths),
        lambda: ref.vq_decode_attn_ref(q, kc, vc, cbk, cbv, lengths),
        partials)

    # chunked-prefill flash: one 128-token chunk at offset 384 over a
    # 1024-slot view (the continuous engine prefills batch 1)
    qc = nrm((1, w, h, hd))
    kv_, vv = nrm((1, s, h, hd)), nrm((1, s, h, hd))
    kpos = jnp.arange(s, dtype=jnp.int32)
    cs = jnp.asarray(384, jnp.int32)
    run("chunk_flash_attention",
        lambda: chunk_flash_attention(qc, kv_, vv, kpos, cs),
        lambda: ref.chunk_flash_ref(qc, kv_, vv, kpos, cs),
        lambda got, want: _close("out", got, want))

    def chunk_partials(got, want):
        (m, l, acc), (m_r, l_r, acc_r) = got, want
        _close("m", m, m_r)
        lt = np.moveaxis(np.asarray(l), 1, 2)[..., None]
        lt_r = np.moveaxis(np.asarray(l_r), 1, 2)[..., None]
        return _close("acc/l", np.asarray(acc) / np.maximum(lt, 1e-30),
                      np.asarray(acc_r) / np.maximum(lt_r, 1e-30))

    # the seq-sharded prefill's per-shard partials: one 256-slot shard
    # holding positions 256..511
    kpos_sh = jnp.arange(256, 512, dtype=jnp.int32)
    run("chunk_flash_partials",
        lambda: chunk_flash_partials(qc, kv_[:, :256], vv[:, :256],
                                     kpos_sh, cs),
        lambda: ref.chunk_flash_partials_ref(qc, kv_[:, :256], vv[:, :256],
                                             kpos_sh, cs),
        chunk_partials)

    # ASTRA mixed-precision prefill: local fp tile at 256..511, codes
    # everywhere else
    tl = 256
    qm = nrm((1, h, tl, hd))
    kl, vl = nrm((1, h, tl, hd)), nrm((1, h, tl, hd))
    mkc, mvc = codes((1, s, g), jnp.int32), codes((1, s, g), jnp.int32)
    off = jnp.asarray(256, jnp.int32)
    run("mixed_flash_attention",
        lambda: mixed_flash_attention(qm, kl, vl, mkc, mvc, cbk, cbv, off),
        lambda: ref.mixed_flash_ref(qm, kl, vl, mkc, mvc, cbk, cbv, 256),
        lambda got, want: _close("out", got, want))

    # VQ assignment at GPT-2's quantizer geometry: 768-wide vectors, one
    # group, K=1024.  An argmin under rounding may pick a near-tie, so the
    # check is on distance: the kernel's code must be as near as the best.
    x = nrm((1024, cfg.d_model))
    cb = nrm((1, kk, cfg.d_model))

    def assign_check(got, want):
        xf, cbf = np.asarray(x, np.float64), np.asarray(cb[0], np.float64)
        d = ((xf[:, None, :] - cbf[None]) ** 2).sum(-1)
        rows = np.arange(len(xf))
        d_got = d[rows, np.asarray(got)[:, 0]]
        d_best = d.min(axis=1)
        gap = float(np.max((d_got - d_best) / d_best))
        agree = float(np.mean(np.asarray(got) == np.asarray(want)))
        log(f"kernel vq_assign: code agreement with the oracle {agree:.4f}, "
            f"max relative distance excess {gap:.3e}")
        if gap > 1e-3:
            raise AssertionError(f"vq_assign: distance excess {gap:.3e}")
        return gap

    run("vq_assign",
        lambda: assign_codes(x, cb, groups=1, use_pallas=True),
        lambda: assign_codes(x, cb, groups=1, use_pallas=False),
        assign_check)


# ---------------------------------------------------------------------------
# (b) the served runs
# ---------------------------------------------------------------------------

SLOTS, MAX_LEN, NEW_TOKENS, REQUESTS = 8, 1024, 32, 16
# prompt lengths (spread evenly, then shuffled) for (b), (c) and the mesh
SERVE_PROMPTS, ROUTE_PROMPTS, MESH_PROMPTS = (16, 512), (16, 128), (16, 384)


def requests(cfg, seed: int, n: int, lo: int, hi: int):
    import numpy as np

    rng = np.random.RandomState(seed)
    lens = np.linspace(lo, hi, n).astype(int)
    rng.shuffle(lens)
    return [rng.randint(1, cfg.vocab_size, size=int(m)).tolist()
            for m in lens]


def drain(eng, prompts):
    import jax

    for p in prompts:
        eng.submit(p, NEW_TOKENS)
    t0 = time.perf_counter()
    stats = eng.run_until_drained()
    jax.block_until_ready((eng.caches, eng.lengths))
    return time.perf_counter() - t0, stats


def phase_serve(cfg, params, seed: int, cache_mode: str,
                cstats: CompileStats) -> None:
    from repro.kernels.ops import KERNEL_INVOCATIONS
    from repro.serving.scheduler import ContinuousBatchingEngine

    before = dict(KERNEL_INVOCATIONS)
    snap = cstats.snapshot()
    eng = ContinuousBatchingEngine(
        cfg, params, slots=SLOTS, max_len=MAX_LEN, astra_mode="off",
        cache_mode=cache_mode, seed=seed, use_pallas=True)
    prompts = requests(cfg, seed, REQUESTS, *SERVE_PROMPTS)
    t_cold, stats = drain(eng, prompts)
    compile_line = cstats.since(snap)
    engaged = {k: KERNEL_INVOCATIONS[k] - before.get(k, 0)
               for k in KERNEL_INVOCATIONS
               if KERNEL_INVOCATIONS[k] != before.get(k, 0)}
    done = {tuple(r.prompt): r.output for r in eng.finished}
    problems = []
    if len(done) != len(prompts):
        problems.append(f"{len(done)}/{len(prompts)} requests finished")
    for out in done.values():
        if len(out) != NEW_TOKENS or not all(
                0 <= t < cfg.vocab_size for t in out):
            problems.append(f"bad output {out[:8]}...")
            break
    if eng._decode_chunk.trace_count != 1:
        problems.append(f"decode chunk traced "
                        f"{eng._decode_chunk.trace_count} times")
    for kern in ("chunk_attention", "decode_attention"):
        if not engaged.get(kern):
            problems.append(f"kernel {kern} did not engage")
    log(f"serve[{cache_mode}]: {stats['requests']} requests, "
        f"{stats['tokens']} tokens, {stats['steps']} scheduler steps, "
        f"{stats['prefill_chunk_ticks']} prefill chunks")
    log(f"serve[{cache_mode}]: first drain {t_cold:.2f}s incl. "
        f"{compile_line}")
    log(f"serve[{cache_mode}]: traces decode="
        f"{eng._decode_chunk.trace_count} prefill_chunk="
        f"{eng._prefill_chunk.trace_count} merge={eng._merge.trace_count}; "
        f"kernel engagement {engaged}")
    # the same requests again on the warm engine: no compile, no retrace
    snap = cstats.snapshot()
    traces = (eng._decode_chunk.trace_count, eng._prefill_chunk.trace_count)
    t_warm, stats = drain(eng, prompts)
    log(f"serve[{cache_mode}]: warm drain {t_warm:.2f}s for "
        f"{REQUESTS * NEW_TOKENS} tokens ({cstats.since(snap)}); "
        f"peak_bytes_in_use {peak_bytes():,}")
    if (eng._decode_chunk.trace_count,
            eng._prefill_chunk.trace_count) != traces:
        problems.append("the warm drain retraced a step")
    if len(eng.finished) != 2 * len(prompts):
        problems.append("the warm drain left requests unfinished")
    if problems:
        raise AssertionError(f"serve[{cache_mode}]: " + "; ".join(problems))


# ---------------------------------------------------------------------------
# (c) Pallas route vs jnp route
# ---------------------------------------------------------------------------


def agreement(tokens_a, tokens_b) -> str:
    same = sum(a == b for ta, tb in zip(tokens_a, tokens_b)
               for a, b in zip(ta, tb))
    full = sum(ta == tb for ta, tb in zip(tokens_a, tokens_b))
    return (f"{same}/{sum(len(t) for t in tokens_b)} positions, "
            f"{full}/{len(tokens_b)} requests identical")


def phase_routes(cfg, params, seed: int, cstats: CompileStats) -> None:
    import numpy as np

    from repro.serving.engine import ServingEngine

    prompts = requests(cfg, seed + 1, SLOTS, *ROUTE_PROMPTS)
    res = {}
    for use_pallas in (True, False):
        snap = cstats.snapshot()
        eng = ServingEngine(cfg, params, max_len=MAX_LEN, astra_mode="off",
                            cache_mode="fp", use_pallas=use_pallas)
        t0 = time.perf_counter()
        res[use_pallas] = eng.generate(prompts, max_new_tokens=NEW_TOKENS,
                                       seed=seed)
        log(f"route[{'pallas' if use_pallas else 'jnp'}]: "
            f"{time.perf_counter() - t0:.2f}s incl. {cstats.since(snap)}")
    lp, lj = res[True].prefill_logits, res[False].prefill_logits
    if not (np.all(np.isfinite(lp)) and np.all(np.isfinite(lj))):
        raise AssertionError("route: non-finite prefill logits")
    err = float(np.max(np.abs(lp - lj)))
    scale = float(np.max(np.abs(lj)))
    log(f"route: prefill last-token logits max|diff| {err:.3e} over "
        f"max|logit| {scale:.3e} (bound {ROUTE_LOGIT_RTOL:g} relative); "
        f"greedy-token agreement "
        f"{agreement(res[True].tokens, res[False].tokens)}")
    if err > ROUTE_LOGIT_RTOL * scale:
        raise AssertionError(f"route: logits differ by {err:.3e}")


# ---------------------------------------------------------------------------
# --chips 4: the mesh phase
# ---------------------------------------------------------------------------


def phase_mesh(cfg, params, seed: int, cstats: CompileStats) -> None:
    """Both comparisons run in float32: activations (the config's compute
    dtype is bfloat16; the widths stay) and matmul precision, as in the
    CPU parity tests.  In bfloat16 the sharded attention merges per-shard
    partials in another order than one device does, the logits round to
    bfloat16 (one ulp is 2**-6 at |logit| ~ 3), and with random weights
    the greedy argmax over 50257 such logits sits on ties that one ulp
    flips."""
    import jax

    from repro.compat import make_mesh
    from repro.core.sequence_parallel import MeshContext

    n = len(jax.devices())
    mesh = make_mesh((n,), ("model",), devices=jax.devices())
    mctx = MeshContext(mesh=mesh, batch_axes=(), seq_axis="model")
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("float32"):
        mesh_serving(cfg32, params, seed, mctx, cstats)
        mesh_astra(cfg32, seed, mctx, cstats)


def mesh_serving(cfg, params, seed, mctx, cstats: CompileStats) -> None:
    """(i) seq-sharded serving vs the same engine on one device."""
    import jax
    import numpy as np

    from repro.serving.engine import ServingEngine

    n = len(jax.devices())
    prompts = requests(cfg, seed + 2, SLOTS, *MESH_PROMPTS)
    out = {}
    for name, kw in (("one-device", {}), ("seq-sharded", {"mesh_ctx": mctx})):
        snap = cstats.snapshot()
        eng = ServingEngine(cfg, params, max_len=MAX_LEN, astra_mode="off",
                            cache_mode="fp", use_pallas=True, **kw)
        t0 = time.perf_counter()
        out[name] = eng.generate(prompts, max_new_tokens=NEW_TOKENS,
                                 seed=seed)
        log(f"mesh[{name}]: {time.perf_counter() - t0:.2f}s incl. "
            f"{cstats.since(snap)}; decode traces "
            f"{eng._decode_chunk.trace_count}, compiled decode programs "
            f"{eng._decode_chunk._jit._cache_size()}")
        if name == "seq-sharded":
            sharded = eng
    lo, ls = out["one-device"], out["seq-sharded"]
    err = float(np.max(np.abs(lo.prefill_logits - ls.prefill_logits)))
    log(f"mesh: greedy parity {lo.tokens == ls.tokens} "
        f"({agreement(lo.tokens, ls.tokens)}); prefill logits max|diff| "
        f"{err:.3e}")
    if lo.tokens != ls.tokens:
        raise AssertionError("mesh: seq-sharded tokens differ")
    if (sharded._decode_chunk.trace_count != 1
            or sharded._decode_chunk._jit._cache_size() != 1):
        raise AssertionError("mesh: the sharded decode chunk compiled twice")
    lens = np.array([len(p) for p in prompts], np.int32)
    toks = np.zeros((len(prompts), int(lens.max())), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    _, caches, _ = sharded._run_prefill(toks, lens, NEW_TOKENS)
    spread = {len(leaf.sharding.device_set)
              for leaf in jax.tree.leaves(caches)}
    log(f"mesh: shard-cache leaves span {sorted(spread)} device(s)")
    if spread != {n}:
        raise AssertionError(f"mesh: cache leaves on {spread} devices")


def mesh_astra(cfg, seed, mctx, cstats: CompileStats) -> None:
    """(ii) ASTRA spmd prefill forward vs the simulated view."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model_factory as mf
    from repro.models.context import StepCtx

    n = len(jax.devices())
    acfg = dataclasses.replace(
        cfg, astra=dataclasses.replace(cfg.astra, noise_lambda=0.0))
    aparams = mf.init_params(jax.random.PRNGKey(seed), acfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 3), (2, 512), 0,
                                acfg.vocab_size, jnp.int32)
    ctx_spmd = StepCtx(cfg=acfg, mesh=mctx, mode="prefill",
                       astra_mode="spmd")
    ctx_sim = StepCtx(cfg=acfg, mode="prefill", astra_mode="sim",
                      num_sim_shards=n)
    logits = {}
    for name, ctx in (("spmd", ctx_spmd), ("sim", ctx_sim)):
        snap = cstats.snapshot()
        fwd = jax.jit(lambda p, t, ctx=ctx: mf.forward(
            p, {"tokens": t}, ctx=ctx)[0])
        t0 = time.perf_counter()
        logits[name] = np.asarray(jax.block_until_ready(fwd(aparams, tokens)))
        log(f"astra[{name}]: forward {logits[name].shape} "
            f"{time.perf_counter() - t0:.2f}s incl. {cstats.since(snap)}")
    err = float(np.max(np.abs(logits["spmd"] - logits["sim"])))
    scale = float(np.max(np.abs(logits["sim"])))
    log(f"astra: spmd vs sim max|diff| {err:.3e} over max|logit| "
        f"{scale:.3e} (bound {SPMD_LOGIT_RTOL:g} relative)")
    if not np.all(np.isfinite(logits["spmd"])) or err > SPMD_LOGIT_RTOL * scale:
        raise AssertionError(f"astra: spmd vs sim differ by {err:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernels, served runs, route check; 4: the "
                         "mesh phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    import numpy as np

    from repro.models import model_factory as mf

    cache_dir = enable_compile_cache()
    cstats = CompileStats()
    log(f"device_kind {dev.device_kind!r} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {cache_dir}")
    cfg = gpt2_small()
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        mf.init_params(jax.random.PRNGKey(args.seed), cfg))
    log(f"{cfg.name}: {cfg.num_layers}L d={cfg.d_model} {cfg.num_heads}H "
        f"vocab {cfg.vocab_size}, "
        f"{sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)):,} "
        f"parameters "
        f"from seed {args.seed} in {time.perf_counter() - t0:.2f}s")

    t_all = time.perf_counter()
    if args.chips == 4:
        phase_mesh(cfg, params, args.seed, cstats)
    else:
        phase_kernels(args.seed, cstats)
        for mode in ("fp", "paged_vq"):
            phase_serve(cfg, params, args.seed, mode, cstats)
        phase_routes(cfg, params, args.seed, cstats)
    log(f"all phases {time.perf_counter() - t_all:.2f}s, compile "
        f"{cstats.seconds:.2f}s over {cstats.compiles} programs, "
        f"{cstats.cache_hits} persistent-cache hits; peak_bytes_in_use "
        f"{peak_bytes():,}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
