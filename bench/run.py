"""Serving benchmark: one cell of ``BENCHMARK.json``, one seed, one window.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration (``bench/configs/<config>.json``) and
traffic mix (``bench/traffic/<traffic>.json``), makes the weights on the
device from the seed (``bench/families/<family>.py`` maps the
configuration onto the program), warms every program the cell's traffic
can reach, runs the traffic for the mix's warm-up, then measures for
``--seconds``.
With ``--trace 0`` the last stdout line carries the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, each computed by
``bench/metrics/<metric>.py``.  Either way the served tokens of a seeded
sample of finished requests are then checked against the configuration's
plain reference (``bench/reference/<reference>.py``), and ``correct``
says whether every compared number kept to its limit.

A configuration whose deployment names a ``mesh`` is served over the
cell's chips, the weights replicated on each (``bench/core/sut.py``).
Needs an accelerator: with none, or fewer chips than the cell asks for,
it exits non-zero and prints no result.  JAX's persistent compilation
cache lives in ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_SECONDS = 3.0  # length of the traced part of a --trace 1 window


class NoAccelerator(RuntimeError):
    pass


def _prepare_env() -> None:
    """Before JAX is imported: a fixed in-checkout compile cache with no
    size cap, caching every compile.  ``LIBTPU_INIT_ARGS`` is left as the
    machine set it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_model(cfg: dict):
    """A configuration file's reference module
    (``bench/reference/<reference>.py``), family file
    (``bench/families/<family>.py``) and the reference's spec, which the
    family file reads from the configuration's keys."""
    ref = _load_module(BENCH / "reference" / f"{cfg['reference']}.py")
    family = _load_module(BENCH / "families" / f"{cfg['family']}.py")
    return ref, family, ref.Spec(**family.spec(cfg))


class CompileStats:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _device_info(jax, chips: int, require_accelerator: bool):
    devs = jax.devices()
    if require_accelerator and devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no accelerator (platform "
                            f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def _peak_bytes(devs) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return max(peaks)


class Run:
    """What one run recorded, as the metric readers see it."""

    chips = 1  # the cell's chips, whose peaks add up

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def in_window(self, t) -> bool:
        return t is not None and self.open <= t <= self.close

    def traced_ticks(self):
        if self.trace is None:
            return []
        a, b = self.traced
        return self.ticks[a:b]


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, require_accelerator: bool = True,
             control: str = None, inspect=None) -> dict:
    """Run one cell and return the result line's object.  ``control``
    (calibration only) also reads the reference computed in that
    precision over the same sample, under the key "control".
    ``inspect``, if given, is called with the run's ``Run`` once the
    trace is read, before the metrics (``bench/layers.py``)."""
    import jax

    from bench.core import check as chk
    from bench.core import sut, work
    from bench.core.drive import LoadLoop
    from bench.core.scopes import ScopedTrace
    from bench.core.traffic import Traffic

    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    ccfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((root / ccfg["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    devs = _device_info(jax, int(cell["chips"]), require_accelerator)
    pk = work.peaks(devs[0].device_kind) if require_accelerator else None
    cstats = CompileStats()

    ref, family, spec = load_model(cfg)
    deploy = cfg["deployment"]
    mesh_ctx = sut.mesh_context(deploy, devs)
    mcfg = family.model_config(ccfg["name"], cfg, spec)
    t = time.perf_counter()
    params = jax.block_until_ready(family.make_params(
        ref, spec, seed, cfg["compute"]["param_dtype"],
        sharding=sut.weight_sharding(mesh_ctx)))
    t_weights = time.perf_counter() - t
    eng = sut.make_engine(mcfg, params, deploy, seed, mesh_ctx)
    traffic = Traffic(mix, seed, vocab=spec.vocab,
                      max_len=int(deploy["max_len"]))
    t = time.perf_counter()
    lengths = sut.warm_lengths(eng, traffic.prompt_lengths())
    sut.warm_up(eng, [[1 + k % (spec.vocab - 1) for k in range(n)]
                      for n in lengths])
    t_warm = time.perf_counter() - t
    log(f"weights {t_weights:.3f}s; warm-up {t_warm:.3f}s over prompts "
        f"{lengths}; {cstats.compiles} compiles "
        f"({cstats.seconds:.3f}s), {cstats.cache_hits} read from the "
        f"persistent cache")

    drv = LoadLoop(eng, traffic)
    drv.start()
    t_open = drv.t0 + traffic.warm_s
    drv.run_until(t_open)
    t_open = time.perf_counter()
    setup_s = t_open - T_START
    compiles_at_open = cstats.compiles
    t_close = t_open + seconds
    trace_dir, traced = None, None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        t_from = t_open + max(0.0, (seconds - TRACE_SECONDS) / 2)
        state = {}

        def on_tick(n):
            now = time.perf_counter()
            if "a" not in state and now >= t_from:
                jax.profiler.start_trace(trace_dir)
                state["a"] = n
            elif "a" in state and "b" not in state \
                    and now >= t_from + TRACE_SECONDS:
                jax.profiler.stop_trace()
                state["b"] = n

        drv.run_until(t_close, on_tick)
        if "a" in state and "b" not in state:
            jax.profiler.stop_trace()
            state["b"] = len(drv.ticks)
        traced = (state.get("a", 0), state.get("b", 0))
    else:
        drv.run_until(t_close)
    jax.block_until_ready((eng.caches, eng.lengths))
    compiles_in_window = cstats.compiles - compiles_at_open
    memory_peak = _peak_bytes(devs)

    tr, tlo, thi = None, None, None
    if trace_dir is not None:
        tr = ScopedTrace.from_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        spans = tr.spans("tick")
        if spans:
            tlo, thi = spans[0][0], spans[-1][1]
        else:
            tr = None
    run = Run(reqs=drv.reqs, ticks=drv.ticks, open=t_open, close=t_close,
              seconds=float(seconds), setup_s=setup_s, spec=spec, peaks=pk,
              chips=len(devs), compiles_in_window=compiles_in_window,
              trace=tr, trace_lo=tlo, trace_hi=thi, traced=traced)
    if inspect is not None:
        inspect(run)

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench[kind]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        val = _load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    breakdown = None
    if tr is not None:
        device["busy_s"] = tr.busy_s(tlo, thi)
        device["window_s"] = thi - tlo
        breakdown = {"device_ops": [[n, s] for n, s in tr.top_ops(tlo, thi)],
                     "idle_gaps": [[n, s] for n, s in tr.idle_gaps(tlo, thi)]}
    attempted = sum(1 for r in drv.reqs if r.submitted <= t_close) \
        + drv.failed
    tick_ms = sorted(1e3 * (t.t1 - t.t0) for t in drv.ticks
                     if t_open <= t.t0 and t.t1 <= t_close)
    log(f"window {seconds}s: {attempted} requests sent, "
        f"{sum(r.done is not None for r in drv.reqs)} finished, "
        f"{len(drv.ticks)} ticks; {compiles_in_window} compiles in the "
        f"window; generator lateness {drv.lateness()}")
    if tick_ms:
        med = tick_ms[len(tick_ms) // 2]
        log(f"ticks in the window: {len(tick_ms)}, ms median {med:.2f}, "
            f"p99 {tick_ms[int(0.99 * (len(tick_ms) - 1))]:.2f}, max "
            f"{tick_ms[-1]:.2f}; {sum(x > 2 * med for x in tick_ms)} over "
            f"twice the median, {sum(x for x in tick_ms if x > 2 * med):.0f}"
            f" ms in them")

    # free the served model before the reference runs
    for leaf in jax.tree.leaves((eng.caches, eng.params, eng.lengths,
                                 eng.cur_token)):
        leaf.delete()
    drv.eng = None
    del eng, params, drv.eng
    gc.collect()

    checks = cfg["check"]
    picked = chk.sample(run.reqs, seed, int(checks["min_tokens"]))
    gap, ctl_gap, n_tokens = None, None, 0
    if picked:
        t = time.perf_counter()
        served, ctl = chk.gaps(ref.Reference(spec, seed),
                               chk.sequences(picked), control)
        gap = max(float(g.max()) for g in served)
        n_tokens = sum(len(g) for g in served)
        if ctl is not None:
            ctl_gap = max(float(g.max()) for g in ctl)
        log(f"reference over {len(picked)} requests on "
            f"{len({r.slot for r in picked})} slots "
            f"{time.perf_counter() - t:.3f}s")
    compared = chk.compared(picked, spec.vocab, gap, n_tokens,
                            checks["max_logit_gap"])
    correct = chk.verdict(compared)
    for k, v in compared.items():
        log(f"check {k} = {v['value']!r} (limit {chk.rule(k)} "
            f"{v['limit']!r})")
    out = {"correct": correct, "attempted": attempted, "failed": drv.failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["generator_lateness_s"] = drv.lateness()
    if ctl_gap is not None:
        # the control through the same rule: it has to read not correct
        ctl_numbers = chk.compared(picked, spec.vocab, ctl_gap, n_tokens,
                                   checks["max_logit_gap"])
        out["control"] = {"quant": control, "max_logit_gap": ctl_gap,
                          "correct": chk.verdict(ctl_numbers)}
    out["check"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    _prepare_env()
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
