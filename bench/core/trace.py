"""Reduction of a JAX profiler trace to device busy time, kernel time, the
heaviest device operations and the longest device-idle gaps.

A trace is read with ``jax.profiler.ProfileData``.  Device planes are
those named ``/device:<KIND>:<n>``; their "XLA Ops" line holds one event
per operation run on the device, named by its HLO text
(``%fp_decode_attention.9 = (f32[...]...``).  Control-flow operations
(``%while.42``) span the operations of their bodies, so a name's time is
its self time: its events' duration less the events nested in them.  The
benchmark's own host spans (``jax.profiler.TraceAnnotation`` around
``tick``, ``submit`` and ``wait_arrival``) sit on the host plane, on the
same clock.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # (start, end), seconds on the trace clock

OPS_LINE = "XLA Ops"
HOST_SPANS = ("tick", "submit", "wait_arrival")
_SUFFIX = re.compile(r"\.\d+$")


def op_name(hlo_text: str) -> str:
    """``%fp_decode_attention.9 = (...) custom-call(...)`` ->
    ``fp_decode_attention``: the instruction's name without its number."""
    return _SUFFIX.sub("", hlo_text.split(" = ", 1)[0].lstrip("%"))


def self_times(ops: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float, float, float]]:
    """(name, start, end, self seconds) per event: its duration less the
    events nested in it (events of one line nest or do not overlap)."""
    out = []
    stack: List[int] = []
    for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            p = out[stack[-1]]
            out[stack[-1]] = (p[0], p[1], p[2], p[3] - (min(b, p[2]) - a))
        out.append((name, a, b, b - a))
        stack.append(len(out) - 1)
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged, non-overlapping cover of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def covered(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no interval of ``busy`` covers."""
    out, t = [], lo
    for a, b in union(clip(busy, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


class Trace:
    """Device operations (per device) and host spans of one trace."""

    def __init__(self, device_ops: Dict[str, List[Tuple[str, float, float]]],
                 host_spans: List[Tuple[str, float, float]]):
        self.device_ops = device_ops  # device -> [(name, start, end)]
        self.host_spans = sorted(host_spans, key=lambda s: s[1])

    @classmethod
    def from_dir(cls, log_dir: str) -> "Trace":
        import jax

        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        data = jax.profiler.ProfileData.from_file(max(paths,
                                                      key=os.path.getmtime))
        device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
        host: List[Tuple[str, float, float]] = []
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                ops = device_ops.setdefault(plane.name, [])
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for e in line.events:
                        t0 = e.start_ns * 1e-9
                        ops.append((op_name(e.name), t0,
                                    t0 + e.duration_ns * 1e-9))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name in HOST_SPANS:
                            t0 = e.start_ns * 1e-9
                            host.append((e.name, t0,
                                         t0 + e.duration_ns * 1e-9))
        return cls({k: v for k, v in device_ops.items() if v}, host)

    def spans(self, name: str) -> List[Interval]:
        return [(a, b) for n, a, b in self.host_spans if n == name]

    def busy_s(self, lo: float, hi: float) -> float:
        """Seconds of [lo, hi] in which an operation ran, averaged over
        the devices."""
        if not self.device_ops:
            return 0.0
        return sum(covered(clip([(a, b) for _, a, b in ops], lo, hi))
                   for ops in self.device_ops.values()) / len(self.device_ops)

    def _self_seconds(self, lo: float, hi: float) -> Dict[str, float]:
        """Self time per operation name inside [lo, hi], averaged over
        the devices."""
        acc: Dict[str, float] = collections.defaultdict(float)
        for ops in self.device_ops.values():
            for n, _, _, s in self_times(list(clip_ops(ops, lo, hi))):
                acc[n] += s / len(self.device_ops)
        return acc

    def op_seconds(self, lo: float, hi: float, name: str) -> float:
        """Device self time of the operations named ``name`` inside
        [lo, hi], averaged over the devices."""
        return self._self_seconds(lo, hi).get(name, 0.0)

    def top_ops(self, lo: float, hi: float, k: int = 10
                ) -> List[Tuple[str, float]]:
        acc = self._self_seconds(lo, hi)
        return sorted(acc.items(), key=lambda kv: -kv[1])[:k]

    def idle_gaps(self, lo: float, hi: float, k: int = 10
                  ) -> List[Tuple[str, float]]:
        """The ``k`` longest stretches of [lo, hi] with no operation on the
        first device, each named by the host span it falls in (the span
        covering its midpoint, else "host")."""
        if not self.device_ops:
            return []
        first = sorted(self.device_ops)[0]
        busy = [(a, b) for _, a, b in self.device_ops[first]]
        out = []
        for a, b in gaps(busy, lo, hi):
            mid = 0.5 * (a + b)
            name = next((n for n, s0, s1 in self.host_spans
                         if s0 <= mid <= s1), "host")
            out.append((name, b - a))
        return sorted(out, key=lambda g: -g[1])[:k]


def clip_ops(ops, lo: float, hi: float):
    for n, a, b in ops:
        a2, b2 = max(a, lo), min(b, hi)
        if b2 > a2:
            yield n, a2, b2
