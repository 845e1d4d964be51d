"""Training launcher.

Two modes:
  * ``--runtime sim`` (default on CPU): the paper's fine-tuning recipe on a
    single process — ASTRA simulated with ``num_devices_sim`` shards
    (NAVQ noise, straight-through VQ, distributed class tokens).
  * ``--runtime spmd``: the production path — pjit + shard_map over a mesh
    (host devices unless --production), ASTRA's VQ-code all-gather live.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch gpt2-small --reduced \
      --steps 50
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train --arch starcoder2-3b --reduced \
      --runtime spmd --steps 10
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPE_BY_NAME, get_config
from repro.configs.base import ShapeSpec
from repro.data import pipeline
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.training import checkpoint, optimizer as opt_mod
from repro.training.trainer import Trainer
from repro.models import model_factory as mf


def data_for(cfg, batch: int, seq: int, *, seed: int = 0):
    if cfg.arch_type == "vit":
        return pipeline.classification_batches(
            batch, seq, cfg.frontend_dim, cfg.num_classes, seed=seed)
    if cfg.arch_type == "encdec":
        t_src = max(int(seq * cfg.frontend_tokens_ratio), 8)
        return pipeline.seq2seq_batches(batch, t_src, seq, cfg.frontend_dim,
                                        cfg.vocab_size, seed=seed)
    if cfg.arch_type == "vlm":
        n_patch = max(int(seq * cfg.frontend_tokens_ratio), 8)
        base = pipeline.lm_batches(
            pipeline.LMDataConfig(batch_size=batch, seq_len=seq, seed=seed))

        def gen():
            rng = np.random.RandomState(seed)
            for b in base:
                b["patch_embeds"] = rng.randn(
                    batch, n_patch, cfg.frontend_dim).astype(np.float32)
                yield b

        return gen()
    return pipeline.lm_batches(
        pipeline.LMDataConfig(batch_size=batch, seq_len=seq, seed=seed))


def run_sim(cfg, args) -> None:
    tr = Trainer(cfg, num_devices_sim=args.num_devices,
                 astra_mode="sim" if cfg.astra.enabled else "off",
                 seed=args.seed)
    data = data_for(cfg, args.batch, args.seq)
    hist = tr.fit(data, args.steps, log_every=args.log_every)
    print(f"final loss {hist[-1]['loss']:.4f}")
    if args.checkpoint:
        checkpoint.save(args.checkpoint, tr.state.params,
                        {"arch": cfg.name, "steps": args.steps})
        print(f"saved params -> {args.checkpoint}")


def run_spmd(cfg, args) -> None:
    from repro.training.metrics import JsonlLogger, ThroughputMeter

    logger = JsonlLogger(args.metrics_jsonl or None)
    meter = ThroughputMeter()
    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production else make_host_mesh())
    shape = ShapeSpec("cli", args.seq, args.batch, "train")
    bundle = steps_mod.build_train(
        cfg, shape, mesh, mode="astra" if cfg.astra.enabled else "sp",
        remat=args.remat)
    jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     donate_argnums=bundle.donate_argnums)
    key = jax.random.PRNGKey(args.seed)
    params = mf.init_params(key, cfg, dtype=jnp.dtype(cfg.param_dtype))
    opt = opt_mod.init_opt_state(params, opt_mod.AdamWConfig())
    data = data_for(cfg, args.batch, args.seq)
    t0 = time.time()
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        rng = jax.random.fold_in(key, i)
        params, opt, metrics = jitted(params, opt, batch, rng)
        thr = meter.tick(args.batch * args.seq)
        logger.log(i, loss=float(metrics["loss"]), **thr)
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(metrics['loss']):.4f} "
                  f"({time.time()-t0:.1f}s, {thr['tok_per_s']:.0f} tok/s)")
    if args.checkpoint:
        checkpoint.save(args.checkpoint, params,
                        {"arch": cfg.name, "steps": args.steps})


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant (CPU-friendly)")
    ap.add_argument("--runtime", default="sim", choices=["sim", "spmd"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--num-devices", type=int, default=4,
                    help="simulated ASTRA shards (sim runtime)")
    ap.add_argument("--production", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--metrics-jsonl", default="",
                    help="append step metrics to this JSONL file")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"runtime={args.runtime}")
    if args.runtime == "sim":
        run_sim(cfg, args)
    else:
        run_spmd(cfg, args)


if __name__ == "__main__":
    main()
