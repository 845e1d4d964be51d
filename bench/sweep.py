"""Sweeps that size a cell, on the chip.

    python bench/sweep.py --workload gpt2s-chat --seed 7 --seconds 20 \\
        --rates 2,3,4,5
    python bench/sweep.py --workload gpt2s-decode --seed 7 --seconds 20 \\
        --slots 4,8,16,32

``--rates`` finds an open-loop cell's knee, the highest arrival rate the
engine sustains with no growing backlog.  One engine: for each rate (the
cell's mix with only ``rate_per_s`` changed) the traffic runs for the
mix's warm-up and then ``--seconds``; the engine is drained between
rates.  Prints one JSON line per rate: requests due and finished per
second in the window, the queue's length at the window's open and close,
and TTFT percentiles over the first and second half of the window.  A
backlog that grows shows as a queue that lengthens and a second half
slower than the first.

``--slots`` finds the slot count at which a closed-loop cell serves most:
for each count (ascending), a new engine with that many slots, warmed up,
and the cell's mix with as many clients as slots (its warm-up at least
``--warm-per-slot`` seconds a slot, so the window sees every slot busy).
Prints one JSON line per count: output tokens per second, the p90 of time
per output token, the mean tick, and the process's peak device memory so
far.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    way = ap.add_mutually_exclusive_group(required=True)
    way.add_argument("--rates")
    way.add_argument("--slots")
    ap.add_argument("--warm-per-slot", type=float, default=0.0,
                    help="--slots: warm up at least this many seconds per "
                    "slot, so that every slot is admitted before the window")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import bench.run as br

    br._prepare_env()
    import jax

    from bench.core import sut
    from bench.core.drive import LoadLoop
    from bench.core.traffic import Traffic, percentile

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = json.loads((ROOT / next(c["file"] for c in bench["configs"]
                                  if c["name"] == cell["config"])).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    devs = br._device_info(jax, int(cell["chips"]), True)
    ref, family, spec = br.load_model(cfg)
    mcfg = family.model_config(cell["config"], cfg, spec)
    params = family.make_params(ref, spec, args.seed,
                                cfg["compute"]["param_dtype"])

    def engine(dep, mix):
        eng = sut.make_engine(mcfg, params, dep, args.seed)
        probe = Traffic(mix, args.seed, vocab=spec.vocab,
                        max_len=int(dep["max_len"]))
        sut.warm_up(eng, [[1 + k % (spec.vocab - 1) for k in range(n)]
                          for n in sut.warm_lengths(eng,
                                                    probe.prompt_lengths())])
        return eng

    def window(eng, mix, dep):
        traffic = Traffic(mix, args.seed, vocab=spec.vocab,
                          max_len=int(dep["max_len"]))
        drv = LoadLoop(eng, traffic)
        drv.start()
        drv.run_until(drv.t0 + traffic.warm_s)
        t_open = time.perf_counter()
        q_open = len(eng.queue)
        drv.run_until(t_open + args.seconds)
        return drv, t_open, time.perf_counter(), q_open

    if args.slots:
        if mix["loop"] != "closed":
            raise SystemExit("only a closed-loop mix has clients to follow "
                             "the slots")
        for slots in sorted(int(s) for s in args.slots.split(",")):
            dep = dict(cfg["deployment"], slots=slots)
            smix = dict(mix, clients=slots, warm_s=max(
                float(mix["warm_s"]), args.warm_per_slot * slots))
            eng = engine(dep, smix)
            drv, t_open, t_close, _ = window(eng, smix, dep)
            ticks = [t for t in drv.ticks
                     if t_open <= t.t0 and t.t1 <= t_close]
            tpot = [(r.last - r.first) / (r.n_out - 1) for r in drv.reqs
                    if r.done is not None and t_open <= r.done <= t_close]
            print(json.dumps({
                "slots": slots, "clients": slots,
                "output_tok_s": sum(t.tokens for t in ticks) / args.seconds,
                "finished": len(tpot),
                "tpot_p90_ms": 1e3 * percentile(tpot, 90) if tpot else None,
                "tick_ms_mean": 1e3 * sum(t.t1 - t.t0 for t in ticks)
                / max(len(ticks), 1),
                "memory_peak_bytes": br._peak_bytes(devs),
                "device": devs[0].device_kind}), flush=True)
            for leaf in jax.tree.leaves((eng.caches, eng.lengths,
                                         eng.cur_token)):
                leaf.delete()
            del eng, drv
            gc.collect()
        return 0

    if mix["loop"] != "open":
        raise SystemExit("only an open-loop mix has a rate to sweep")
    dep = cfg["deployment"]
    eng = engine(dep, mix)
    for rate in (float(r) for r in args.rates.split(",")):
        drv, t_open, t_close, q_open = window(eng, dict(mix, rate_per_s=rate),
                                              dep)
        q_close = len(eng.queue)
        mid = t_open + args.seconds / 2

        def ttft(lo, hi, p):
            v = [r.first - r.arrival for r in drv.reqs
                 if r.first is not None and lo <= r.first <= hi]
            return 1e3 * percentile(v, p) if v else None

        due = sum(1 for r in drv.reqs if t_open <= r.arrival <= t_close)
        done = sum(1 for r in drv.reqs
                   if r.done is not None and t_open <= r.done <= t_close)
        print(json.dumps({
            "rate_per_s": rate, "due_per_s": due / args.seconds,
            "finished_per_s": done / args.seconds,
            "queue_at_open": q_open, "queue_at_close": q_close,
            "ttft_p50_ms_first_half": ttft(t_open, mid, 50),
            "ttft_p50_ms_second_half": ttft(mid, t_close, 50),
            "ttft_p90_ms_first_half": ttft(t_open, mid, 90),
            "ttft_p90_ms_second_half": ttft(mid, t_close, 90),
            "tick_ms_mean": 1e3 * sum(t.t1 - t.t0 for t in drv.ticks)
            / max(len(drv.ticks), 1),
            "device": devs[0].device_kind}), flush=True)
        eng.queue.clear()
        while not eng.idle:
            eng.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
