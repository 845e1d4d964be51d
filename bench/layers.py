"""Where a cell's tick, TTFT and device time go, by the engine's own spans,
stamps and named scopes.

    python bench/layers.py --workload <name> --seed <n> --seconds <s>
        [--keep <dir>]

Builds and warms the cell as ``bench/run.py`` does, runs its traffic for
the mix's warm-up, then ``--seconds`` untraced and 3 s traced (the same
tick-aligned trace as a ``--trace 1`` run), and reads the trace as a
``bench.core.scopes.ScopedTrace``.  Prints one JSON object: the
per-layer metrics of ``bench/metrics/`` that read the engine's spans,
stamps and scopes, beside the harness's own (``tick_ms``,
``device_idle_share``, ``decode_attn_roofline``...); the device self time
per program scope and the device idle time by innermost span, per traced
tick; the check that the scopes add up to the device busy time; and the
untraced against the traced tick.  ``--keep dir`` keeps the trace there
(``bench.core.scopes.ScopedTrace.from_dir`` reads it back).  Needs an
accelerator.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
TRACE_SECONDS = 3.0
METRICS = ("tick_ms", "queue_wait_ms", "step_mfu", "decode_attn_roofline",
           "device_idle_share", "admit_wait_ms", "prefill_span_ms",
           "tick_host_ms", "page_gather_ms", "kv_write_ms",
           "unscoped_op_share")


def _per_tick(secs, n: int):
    """Seconds by key -> ms per tick, largest first."""
    return {k: 1e3 * v / n
            for k, v in sorted(secs.items(), key=lambda kv: -kv[1])}


def measure(workload: str, seed: int, seconds: float, keep: str = None):
    import jax

    from bench import run as bench_run
    from bench.core import sut, work
    from bench.core.drive import LoadLoop
    from bench.core.scopes import ScopedTrace
    from bench.core.traffic import Traffic

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    ccfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / ccfg["file"]).read_text())
    mix = json.loads((ROOT / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    devs = bench_run._device_info(jax, int(cell["chips"]), True)
    ref, family, spec = bench_run.load_model(cfg)
    deploy = cfg["deployment"]
    params = jax.block_until_ready(family.make_params(
        ref, spec, seed, cfg["compute"]["param_dtype"]))
    eng = sut.make_engine(family.model_config(ccfg["name"], cfg, spec),
                          params, deploy, seed)
    traffic = Traffic(mix, seed, vocab=spec.vocab,
                      max_len=int(deploy["max_len"]))
    sut.warm_up(eng, [[1 + k % (spec.vocab - 1) for k in range(n)]
                      for n in sut.warm_lengths(eng,
                                                traffic.prompt_lengths())])

    drv = LoadLoop(eng, traffic)
    drv.start()
    drv.run_until(drv.t0 + traffic.warm_s)
    t_open = time.perf_counter()
    drv.run_until(t_open + seconds)
    untraced = [t.t1 - t.t0 for t in drv.ticks if t.t0 >= t_open]
    log_dir = keep or tempfile.mkdtemp(prefix="bench_layers_")
    state = {}

    def on_tick(n):
        now = time.perf_counter()
        if "a" not in state:
            jax.profiler.start_trace(log_dir)
            state["a"], state["t"] = n, now
        elif "b" not in state and now >= state["t"] + TRACE_SECONDS:
            jax.profiler.stop_trace()
            state["b"] = n

    t_traced = time.perf_counter()
    drv.run_until(t_traced + TRACE_SECONDS + 1.0, on_tick)
    if "b" not in state:
        jax.profiler.stop_trace()
        state["b"] = len(drv.ticks)
    t_close = time.perf_counter()
    jax.block_until_ready((eng.caches, eng.lengths))

    tr = ScopedTrace.from_dir(log_dir)
    if not keep:
        shutil.rmtree(log_dir, ignore_errors=True)
    ticks = tr.spans("tick")
    lo, hi = ticks[0][0], ticks[-1][1]
    run = bench_run.Run(
        reqs=drv.reqs, ticks=drv.ticks, open=t_open, close=t_close,
        seconds=t_close - t_open, setup_s=None, spec=spec,
        peaks=work.peaks(devs[0].device_kind), compiles_in_window=None,
        trace=tr, trace_lo=lo, trace_hi=hi,
        traced=(state["a"], state["b"]))
    metrics = {}
    for name in METRICS:
        val = bench_run._load_module(
            ROOT / "bench" / "metrics" / f"{name}.py").read(run)
        metrics[name] = None if val is None else float(val)

    n = len(ticks)
    scopes = tr.scope_seconds(lo, hi)
    idle = tr.idle_by_span(lo, hi)
    busy = tr.busy_s(lo, hi)
    ttft = [r.first - r.arrival for r in drv.reqs
            if run.in_window(getattr(r.obj, "t_admit", None))
            and r.first is not None]
    out = {
        "workload": workload, "seed": seed, "device": devs[0].device_kind,
        "metrics": metrics,
        "traced_ticks": n,
        "scope_ms_per_tick": _per_tick(scopes, n),
        "idle_ms_per_tick_by_span": _per_tick(idle, n),
        "busy_ms_per_tick": 1e3 * busy / n,
        "scopes_over_busy": sum(scopes.values()) / busy if busy else None,
        "top_ops": tr.top_ops(lo, hi),
        "tick_ms_untraced": (1e3 * sum(untraced) / len(untraced)
                             if untraced else None),
        "ttft_mean_ms": 1e3 * sum(ttft) / len(ttft) if ttft else None,
        "requests_admitted": len(ttft),
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import run as bench_run

    bench_run._prepare_env()
    try:
        out = measure(args.workload, args.seed, args.seconds, args.keep)
    except bench_run.NoAccelerator as e:
        print(f"layers: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
