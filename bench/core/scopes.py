"""The serving engine's own spans and scopes in a JAX profiler trace.

``ScopedTrace`` is a ``bench.core.trace.Trace`` (device operations by name
and the harness's host spans, read by ``Trace.from_dir`` itself, so every
inherited method returns what it returns for a plain ``Trace``) that also
keeps what the program puts into the trace:

* engine spans: the host ``TraceAnnotation`` events named ``engine.*``
  (``serving/scheduler.py``: ``engine.step`` and the phases inside it,
  ``engine.submit``, ``engine.preempt``, ``engine.restore``), each with
  the ``uid`` of the request it belongs to, or None;
* program scopes: each device operation's ``jax.named_scope`` path, the
  ``tf_op`` stat of its event metadata on a TPU plane (the op_name
  metadata of its HLO instruction, ``jit(counted)/while/body/...``),
  reduced to the innermost of ``SCOPES`` in it.  An operation under none
  of them, or with no ``tf_op`` (copies and converts XLA inserts), is
  ``UNSCOPED``.  ``jax.profiler.ProfileData`` does not expose event
  metadata stats, so ``op_paths`` reads them from the ``.xplane.pb``
  file's protobuf wire format itself.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench.core.trace import OPS_LINE, Trace, clip_ops, gaps

# the named scopes of models/, serving/cache_backend.py, serving/steps.py
# and serving/kv_cache.py
SCOPES = ("embed", "qkv", "kv_write", "page_gather", "attn_kernel",
          "attn_out", "mlp", "head", "sample", "slot_merge")
UNSCOPED = "unscoped"
ENGINE_PREFIX = "engine."
SCOPE_STAT = "tf_op"

Span = Tuple[str, float, float, Optional[int]]  # (name, start, end, uid)


def _varint(buf, i: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) of one protobuf message in ``buf[i:end]``: an
    int for varints, a (start, end) span for length-delimited fields,
    None for fixed-width ones."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire in (1, 5):
            val, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def op_paths(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """Device plane -> {event name (the op's HLO text) -> its ``tf_op``
    path}, from a serialized ``XSpace``.  Planes' lines, the bulk of the
    file, are skipped unread.  (XSpace.planes = 1; XPlane.name = 2,
    event_metadata = 4, stat_metadata = 5, both maps of key 1 to value 2;
    XEventMetadata.name = 2, stats = 5; XStatMetadata.id = 1, name = 2;
    XStat.metadata_id = 1, str_value = 5, ref_value = 7.)"""
    buf = memoryview(xspace)

    def text(span):
        return str(buf[span[0]:span[1]], "utf-8")

    def values(span):
        return [v for f, v in _fields(buf, *span) if f == 2]

    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        fields = list(_fields(buf, *plane))
        name = next((text(v) for f2, v in fields if f2 == 2), "")
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for f2, entry in fields:
            if f2 == 5:
                for meta in values(entry):
                    sm = dict(_fields(buf, *meta))
                    stat_names[sm.get(1, 0)] = text(sm[2]) if 2 in sm else ""
        paths = out.setdefault(name, {})
        for f2, entry in fields:
            if f2 != 4:
                continue
            for meta in values(entry):
                ev_name, path = "", ""
                for f3, v in _fields(buf, *meta):
                    if f3 == 2:
                        ev_name = text(v)
                    elif f3 == 5:
                        st = dict(_fields(buf, *v))
                        if stat_names.get(st.get(1)) != SCOPE_STAT:
                            continue
                        path = (text(st[5]) if 5 in st
                                else stat_names.get(st.get(7), ""))
                if path:
                    paths[ev_name] = path
    return out


def scope_of(path: str) -> str:
    """The innermost program scope in an op_name path, else ``UNSCOPED``:
    ``jit(decode_chunk)/while/body/page_gather/gather`` -> ``page_gather``.
    A trailing ``:<op type>`` (as ``tf_op`` may carry) is ignored."""
    for part in reversed(path.split(":", 1)[0].split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def exclusive_times(ops: Sequence[Tuple[str, float, float]], eps: float = 5e-10
                    ) -> List[Tuple[str, float, float, float]]:
    """(name, start, end, self seconds) per event, like
    ``trace.self_times``, but nesting is decided at the trace's 1 ns
    resolution (an event that starts within ``eps`` of another's end is
    its sibling, not its child: times are nanoseconds turned into float
    seconds) and a child is clipped to its parent, so the self times add
    up to the time the events cover."""
    out: List[Tuple[str, float, float, float]] = []
    stack: List[int] = []
    for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and out[stack[-1]][2] <= a + eps:
            stack.pop()
        if stack:
            p = out[stack[-1]]
            b = min(b, p[2])
            out[stack[-1]] = (p[0], p[1], p[2], p[3] - (b - a))
        out.append((name, a, b, b - a))
        stack.append(len(out) - 1)
    return out


class ScopedTrace(Trace):
    def __init__(self, device_ops, host_spans,
                 engine_spans: Iterable[Span] = (),
                 device_scopes: Optional[Dict[str, List[str]]] = None):
        """``device_scopes[device][i]`` is the scope of
        ``device_ops[device][i]``."""
        super().__init__(device_ops, host_spans)
        self.engine_spans: List[Span] = sorted(engine_spans,
                                               key=lambda s: s[1])
        self.device_scopes = device_scopes or {
            d: [UNSCOPED] * len(ops) for d, ops in device_ops.items()}

    @classmethod
    def from_dir(cls, log_dir: str) -> "ScopedTrace":
        import jax

        base = Trace.from_dir(log_dir)
        files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        path = max(files, key=os.path.getmtime)
        data = jax.profiler.ProfileData.from_file(path)
        with open(path, "rb") as fh:
            tf_ops = op_paths(fh.read())
        scopes: Dict[str, List[str]] = {}
        engine: List[Span] = []
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                names = scopes.setdefault(plane.name, [])
                ops = tf_ops.get(plane.name, {})
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for e in line.events:
                        names.append(scope_of(ops.get(e.name, "")))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(ENGINE_PREFIX):
                            uid = dict(e.stats).get("uid")
                            t0 = e.start_ns * 1e-9
                            engine.append((e.name, t0,
                                           t0 + e.duration_ns * 1e-9,
                                           None if uid is None
                                           else int(uid)))
        return cls(base.device_ops, base.host_spans, engine,
                   {d: scopes[d] for d in base.device_ops})

    # -- engine spans ---------------------------------------------------------
    def engine(self, name: str, lo: float = float("-inf"),
               hi: float = float("inf")) -> List[Span]:
        """The engine spans named ``name`` that lie inside [lo, hi]."""
        return [s for s in self.engine_spans
                if s[0] == name and lo <= s[1] and s[2] <= hi]

    def tick_host_s(self, lo: float, hi: float) -> List[float]:
        """Per ``engine.step`` inside [lo, hi]: its seconds less those of
        the ``engine.sync`` and ``engine.first_token`` spans inside it, the
        two places the host waits on the device."""
        waits = [s for s in self.engine_spans
                 if s[0] in ("engine.sync", "engine.first_token")]
        out = []
        for _, a, b, _ in self.engine("engine.step", lo, hi):
            out.append((b - a) - sum(w1 - w0 for _, w0, w1, _ in waits
                                     if a <= w0 and w1 <= b))
        return out

    # -- device time by program scope -----------------------------------------
    def scope_seconds(self, lo: float, hi: float) -> Dict[str, float]:
        """Device self time per program scope inside [lo, hi], averaged
        over the devices.  With ``UNSCOPED`` the values add up to
        ``busy_s(lo, hi)``."""
        acc: Dict[str, float] = collections.defaultdict(float)
        for dev, ops in self.device_ops.items():
            named = [(sc, a, b) for sc, (_, a, b)
                     in zip(self.device_scopes[dev], ops)]
            for sc, _, _, s in exclusive_times(list(clip_ops(named, lo,
                                                             hi))):
                acc[sc] += s / len(self.device_ops)
        return dict(acc)

    # -- device idle time by span ---------------------------------------------
    def idle_by_span(self, lo: float, hi: float) -> Dict[str, float]:
        """Seconds of [lo, hi] with no operation on the first device, keyed
        by the innermost harness or engine span that covers each gap's
        midpoint (the one that started last), else "host"."""
        if not self.device_ops:
            return {}
        first = sorted(self.device_ops)[0]
        busy = [(a, b) for _, a, b in self.device_ops[first]]
        spans: Sequence[Tuple[str, float, float]] = sorted(
            list(self.host_spans)
            + [(n, a, b) for n, a, b, _ in self.engine_spans],
            key=lambda s: (s[1], -s[2]))
        acc: Dict[str, float] = collections.defaultdict(float)
        for a, b in gaps(busy, lo, hi):
            mid = 0.5 * (a + b)
            name = "host"
            for n, s0, s1 in spans:
                if s0 > mid:
                    break
                if mid <= s1:
                    name = n
            acc[name] += b - a
        return dict(acc)


def scope_ms_per_tick(run, scope: str) -> Optional[float]:
    """Device self time under ``scope`` per harness tick of the traced
    part of a run, ms; None when the run's trace has no program scopes."""
    tr = run.trace
    if not isinstance(tr, ScopedTrace):
        return None
    ticks = tr.spans("tick")
    secs = tr.scope_seconds(run.trace_lo, run.trace_hi)
    if not ticks or not secs or set(secs) == {UNSCOPED}:
        return None
    return 1e3 * secs.get(scope, 0.0) / len(ticks)
