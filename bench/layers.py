"""Where a cell's tick and device time go, by the engine's own spans and
named scopes.

    python bench/layers.py --workload <name> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does (``run_cell``: the same
window, the same 3 s trace mid-window, the same check) and prints one
JSON object: the result line's object under "result", and from the same
trace the device self time per program scope and the device idle time by
innermost harness or engine span, in ms per traced tick, with the check
that the scopes add up to the device busy time.  Needs an accelerator.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _per_tick(secs, n: int):
    """Seconds by key -> ms per tick, largest first."""
    return {k: 1e3 * v / n
            for k, v in sorted(secs.items(), key=lambda kv: -kv[1])}


def tables(run) -> dict:
    """The per-scope and idle-by-span tables of a traced run."""
    tr, lo, hi = run.trace, run.trace_lo, run.trace_hi
    if tr is None:
        return {}
    n = len(tr.spans("tick"))
    scopes = tr.scope_seconds(lo, hi)
    busy = tr.busy_s(lo, hi)
    return {
        "traced_ticks": n,
        "scope_ms_per_tick": _per_tick(scopes, n),
        "idle_ms_per_tick_by_span": _per_tick(tr.idle_by_span(lo, hi), n),
        "busy_ms_per_tick": 1e3 * busy / n,
        "scopes_over_busy": sum(scopes.values()) / busy if busy else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import run as bench_run

    bench_run._prepare_env()
    seen = {}
    try:
        result = bench_run.run_cell(
            ROOT, args.workload, args.seed, args.seconds, True,
            inspect=lambda run: seen.update(tables(run)))
    except bench_run.NoAccelerator as e:
        print(f"layers: {e}", file=sys.stderr)
        return 3
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "result": result, **seen}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
