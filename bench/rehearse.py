"""Compile a configuration's serving programs for a described TPU v5e chip,
without one, and print what each needs of the chip's memory.

    JAX_PLATFORMS=cpu python bench/rehearse.py --config starcoder2-3b

Compiles, at the configuration's published widths and deployment: the
weight generator, the decode chunk, and the prefill chunk at its widest
width and longest attention view.  Nothing runs, so this says nothing of
results or times; it finds a program that the chip's compiler refuses or
that does not fit.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--slots", type=int, help="override the deployment's")
    ap.add_argument("--layers", type=int, help="override the layer count")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import bench.run as br
    from repro.kernels import ops
    from repro.models.context import StepCtx
    from repro.serving import cache_backend as cbe
    from repro.serving import steps as serving_steps

    jax.config.update("jax_enable_compilation_cache", False)
    ops.on_tpu = lambda: True  # lower the Pallas kernels compiled
    cfg = json.loads((BENCH / "configs" / f"{args.config}.json").read_text())
    ref, family, spec = br.load_model(cfg)
    if args.layers:
        spec = dataclasses.replace(spec, layers=args.layers)
    dep = cfg["deployment"]
    if args.slots:
        dep["slots"] = args.slots
    mcfg = family.model_config(args.config, cfg, spec)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def report(name, compiled):
        m = compiled.memory_analysis()
        live = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(f"{name}: arguments {m.argument_size_in_bytes:,} B, outputs "
              f"{m.output_size_in_bytes:,} B, aliased "
              f"{m.alias_size_in_bytes:,} B, temporaries "
              f"{m.temp_size_in_bytes:,} B; live at once {live:,} B",
              flush=True)
        return live

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    build = family.params_builder(ref, spec, cfg["compute"]["param_dtype"])
    report("weights", build.lower(key).compile())
    params = on_chip(jax.eval_shape(build, key))

    backend = cbe.get_backend(dep["cache_mode"])
    ctx_d = StepCtx(cfg=mcfg, mode="decode", cache_mode=dep["cache_mode"],
                    use_pallas=bool(dep["use_pallas"]))
    ctx_p = StepCtx(cfg=mcfg, mode="prefill", cache_mode=dep["cache_mode"],
                    use_pallas=bool(dep["use_pallas"]))
    kv = backend.make_state(mcfg, slots=int(dep["slots"]),
                            max_len=int(dep["max_len"]), ctx=ctx_d,
                            page_size=int(dep["page_size"]),
                            dtype=jnp.float32)
    caches = on_chip(jax.eval_shape(kv.init_cache))
    tables = on_chip(jax.eval_shape(lambda: {
        n: jnp.asarray(t) for n, t in kv.tables().items()}))
    slots = int(dep["slots"])

    def vec(dt):
        return jax.ShapeDtypeStruct((slots,), dt, sharding=one)

    decode = serving_steps.make_decode_chunk(ctx_d, donate=True)
    live_d = report("decode_chunk", decode.lower(
        params, vec(jnp.int32), caches, vec(jnp.int32), vec(jnp.int32),
        vec(jnp.int32), vec(jnp.bool_), key, tables,
        num_steps=4, temperature=0.0, top_k=0).compile())

    width = max(serving_steps.prefill_buckets())
    view = int(dep["max_len"])
    pcaches = on_chip(jax.eval_shape(
        lambda: kv.init_cache(1, prefill_scratch=True)))
    prefill = serving_steps.make_prefill_chunk(ctx_p, donate=True)
    one_tables = jax.tree.map(lambda t: jax.ShapeDtypeStruct(
        (1,) + t.shape[1:], t.dtype, sharding=one), tables)
    live_p = report(f"prefill_chunk width {width} view {view}", prefill.lower(
        params, jax.ShapeDtypeStruct((1, width), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one), pcaches,
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((1, spec.vocab), jnp.float32, sharding=one),
        one_tables, history_len=view).compile())
    print(json.dumps({"config": args.config, "decode_live_bytes": live_d,
                      "prefill_live_bytes": live_p}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
