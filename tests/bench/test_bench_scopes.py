"""The readers of the engine's spans, stamps and named scopes, on
synthetic traces and runs, and on a real CPU trace of the engine."""
import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.core.scopes import (UNSCOPED, ScopedTrace,  # noqa: E402
                               exclusive_times, op_paths, scope_of)
from bench.core.trace import Trace  # noqa: E402
from bench.run import Run, _load_module  # noqa: E402

DEV = "/device:TPU:0"


def _metric(name):
    return _load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


def _ops():
    # (name, start, end) with the scope of each, in the same order
    return [("while", 0.0, 1.8, UNSCOPED),
            ("gather_fusion", 0.1, 0.4, "page_gather"),
            ("dynamic-update-slice", 0.4, 0.6, "kv_write"),
            ("fp_decode_attention", 0.6, 1.0, "attn_kernel"),
            ("copy", 1.0, 1.5, UNSCOPED),
            ("fusion", 2.5, 3.0, "mlp"),
            ("gather_fusion", 3.0, 3.2, "page_gather")]


HOST = [("tick", 0.0, 2.0), ("wait_arrival", 2.0, 2.4), ("tick", 2.4, 3.6)]
ENGINE = [("engine.step", 0.0, 1.95, None),
          ("engine.admit", 0.0, 0.05, None),
          ("engine.decode_dispatch", 0.05, 0.1, None),
          ("engine.sync", 0.1, 1.9, None),
          ("engine.emit", 1.9, 1.95, None),
          ("engine.step", 2.45, 3.55, None),
          ("engine.admit", 2.45, 2.6, None),
          ("engine.prefill_chunk", 2.45, 2.5, 7),
          ("engine.first_token", 2.5, 2.6, 7),
          ("engine.decode_dispatch", 2.6, 2.62, None),
          ("engine.sync", 2.62, 3.5, None),
          ("engine.emit", 3.5, 3.55, None)]


def _scoped():
    ops = _ops()
    return ScopedTrace({DEV: [o[:3] for o in ops]}, HOST, ENGINE,
                       {DEV: [o[3] for o in ops]})


def test_scope_of_takes_the_innermost_program_scope():
    assert scope_of("jit(decode_chunk)/while/body/page_gather/gather") \
        == "page_gather"
    assert scope_of("jit(f)/mlp/attn_kernel/jit(fp_decode_attention)/"
                    "pallas_call") == "attn_kernel"
    assert scope_of("jit(decode_chunk)/while/body/kv_write/scatter:Scatter") \
        == "kv_write"
    assert scope_of("jit(decode_chunk)/while") == UNSCOPED
    assert scope_of("") == UNSCOPED


def _varint(n):
    out = b""
    while True:
        low, n = n & 0x7F, n >> 7
        out += bytes([low | (0x80 if n else 0)])
        if not n:
            return out


def _msg(*fields):
    """Protobuf wire format of (field number, int | bytes | str) pairs."""
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def test_op_paths_reads_tf_op_from_event_metadata():
    ops_line = _msg((1, 1), (2, "XLA Ops"), (4, _msg((1, 1), (2, 5))))
    stat_meta = [_msg((1, 7), (2, _msg((1, 7), (2, "tf_op")))),
                 _msg((1, 8), (2, _msg((1, 8), (2, "hlo_category")))),
                 _msg((1, 9), (2, _msg((1, 9), (2, "jit(f)/mlp/dot:"))))]
    event_meta = [
        _msg((1, 1), (2, _msg((1, 1), (2, "%gather.3 = f32[8] gather()"),
                              (5, _msg((1, 8), (5, "data movement"))),
                              (5, _msg((1, 7), (5, "jit(f)/while/body/"
                                                   "page_gather/gather:")))))),
        # tf_op as a reference to an interned string
        _msg((1, 2), (2, _msg((1, 2), (2, "%dot.1 = f32[8] dot()"),
                              (5, _msg((1, 7), (7, 9)))))),
        # an op XLA inserted: no tf_op
        _msg((1, 3), (2, _msg((1, 3), (2, "%copy.4 = f32[8] copy()"))))]
    device = _msg((1, 3), (2, "/device:TPU:0"), (3, ops_line),
                  *[(4, m) for m in event_meta], *[(5, m) for m in stat_meta])
    host = _msg((1, 4), (2, "/host:CPU"), *[(4, m) for m in event_meta])
    got = op_paths(_msg((1, host), (1, device), (3, "warning")))
    assert got == {"/device:TPU:0": {
        "%gather.3 = f32[8] gather()": "jit(f)/while/body/page_gather/gather:",
        "%dot.1 = f32[8] dot()": "jit(f)/mlp/dot:"}}
    assert {scope_of(p) for p in got["/device:TPU:0"].values()} == {
        "page_gather", "mlp"}


def test_exclusive_times_tolerate_nanoseconds_as_floats():
    # 58047318 ns as float seconds: the first op's end lands a hair past
    # the second's start; they are siblings all the same
    ops = [("a", 0.058, 0.058047318 + 1e-17), ("b", 0.058047318, 0.06),
           ("loop", 0.1, 0.2), ("body", 0.15, 0.25)]
    got = {n: s for n, _, _, s in exclusive_times(ops)}
    assert got["a"] == pytest.approx(0.000047318)
    assert got["b"] == pytest.approx(0.06 - 0.058047318)
    # a child running past its parent is clipped to it
    assert got["body"] == pytest.approx(0.05)
    assert got["loop"] == pytest.approx(0.05)
    assert sum(got.values()) == pytest.approx(0.002 + 0.1)


def test_scope_self_time_adds_up_to_the_busy_time():
    tr = _scoped()
    secs = tr.scope_seconds(0.0, 3.6)
    assert secs["page_gather"] == pytest.approx(0.3 + 0.2)
    assert secs["kv_write"] == pytest.approx(0.2)
    assert secs["attn_kernel"] == pytest.approx(0.4)
    assert secs["mlp"] == pytest.approx(0.5)
    # the while loop's own time (1.8 less its nested ops) and the copy
    assert secs[UNSCOPED] == pytest.approx(1.8 - 0.9 - 0.5 + 0.5)
    assert sum(secs.values()) == pytest.approx(tr.busy_s(0.0, 3.6))
    # clipped to a window
    assert tr.scope_seconds(3.05, 3.6)["page_gather"] == pytest.approx(0.15)


def test_idle_by_span_names_the_innermost_span():
    tr = _scoped()
    idle = tr.idle_by_span(0.0, 3.6)
    # gaps: (1.8, 2.5) midpoint 2.15 under wait_arrival only,
    # (3.2, 3.6) midpoint 3.4 under tick > engine.step > engine.sync
    assert idle == {"wait_arrival": pytest.approx(0.7),
                    "engine.sync": pytest.approx(0.4)}
    assert sum(idle.values()) == pytest.approx(3.6 - tr.busy_s(0.0, 3.6))
    assert tr.idle_by_span(0.0, 1.5) == {}
    bare = ScopedTrace({DEV: [("a", 0.0, 1.0)]}, [], [])
    assert bare.idle_by_span(0.0, 2.0) == {"host": pytest.approx(1.0)}


def test_tick_host_time_excludes_the_waits_on_the_device():
    tr = _scoped()
    host = tr.tick_host_s(0.0, 3.6)
    assert host == [pytest.approx(1.95 - 1.8),
                    pytest.approx(1.1 - 0.88 - 0.1)]
    assert tr.engine("engine.prefill_chunk") == [
        ("engine.prefill_chunk", 2.45, 2.5, 7)]
    assert tr.tick_host_s(0.0, 1.0) == []


def test_trace_methods_are_unchanged_by_the_engine_spans():
    ops = [o[:3] for o in _ops()]
    plain = Trace({DEV: list(ops)}, list(HOST))
    scoped = _scoped()
    assert scoped.host_spans == plain.host_spans
    for name in ("tick", "wait_arrival", "submit", "engine.step"):
        assert scoped.spans(name) == plain.spans(name)
    assert scoped.spans("engine.step") == []
    for lo, hi in ((0.0, 3.6), (0.5, 2.7)):
        assert scoped.busy_s(lo, hi) == plain.busy_s(lo, hi)
        assert scoped.top_ops(lo, hi) == plain.top_ops(lo, hi)
        assert scoped.idle_gaps(lo, hi) == plain.idle_gaps(lo, hi)
        for name in ("copy", "gather_fusion", "fp_decode_attention"):
            assert scoped.op_seconds(lo, hi, name) == \
                plain.op_seconds(lo, hi, name)


def _run(trace, reqs=()):
    return Run(reqs=list(reqs), ticks=[], open=0.0, close=10.0,
               seconds=10.0, setup_s=1.0, spec=None, peaks=None,
               compiles_in_window=0, trace=trace, trace_lo=0.0,
               trace_hi=3.6, traced=(0, 2))


def test_device_trace_readers():
    run = _run(_scoped())
    assert _metric("page_gather_ms")(run) == pytest.approx(1e3 * 0.5 / 2)
    assert _metric("kv_write_ms")(run) == pytest.approx(1e3 * 0.2 / 2)
    assert _metric("tick_host_ms")(run) == pytest.approx(
        1e3 * (0.15 + 0.12) / 2)
    share = _metric("unscoped_op_share")(run)
    assert share == pytest.approx(100 * 0.9 / 2.5)


@pytest.mark.parametrize("name", ["page_gather_ms", "kv_write_ms",
                                  "tick_host_ms", "unscoped_op_share"])
def test_device_trace_readers_find_nothing_in_a_plain_trace(name):
    plain = Trace({DEV: [o[:3] for o in _ops()]}, list(HOST))
    assert _metric(name)(_run(plain)) is None
    assert _metric(name)(_run(None)) is None
    unscoped = ScopedTrace({DEV: [o[:3] for o in _ops()]}, HOST, [])
    assert _metric(name)(_run(unscoped)) is None


@dataclasses.dataclass
class _Obj:
    t_admit: float = None
    t_first: float = None


@dataclasses.dataclass
class _Req:
    arrival: float
    obj: object


def test_stamp_readers():
    reqs = [_Req(1.0, _Obj(1.5, 2.0)),   # wait 0.5, prefill 0.5
            _Req(2.0, _Obj(3.0, 3.25)),  # wait 1.0, prefill 0.25
            _Req(3.0, _Obj(4.0, None)),  # admitted, no first token yet
            _Req(9.0, _Obj(None, None)),  # still queued
            _Req(-2.0, _Obj(-1.0, 0.5))]  # admitted before the window
    run = _run(None, reqs)
    assert _metric("admit_wait_ms")(run) == pytest.approx(
        1e3 * (0.5 + 1.0 + 1.0) / 3)
    assert _metric("prefill_span_ms")(run) == pytest.approx(
        1e3 * (0.5 + 0.25) / 2)
    # a program without the stamps reads nothing
    bare = _run(None, [_Req(1.0, object())])
    assert _metric("admit_wait_ms")(bare) is None
    assert _metric("prefill_span_ms")(bare) is None


def test_engine_spans_of_a_cpu_trace(tmp_path):
    """A real trace of the engine on the CPU: the host spans carry their
    names and uids; the CPU has no device plane, so no op is scoped."""
    import jax

    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.models import model_factory as mf
    from repro.serving.scheduler import ContinuousBatchingEngine

    cfg = get_config("gpt2-small").reduced()
    cfg = dataclasses.replace(
        cfg, astra=dataclasses.replace(cfg.astra, enabled=False))
    eng = ContinuousBatchingEngine(
        cfg, mf.init_params(jax.random.PRNGKey(0), cfg), slots=2,
        max_len=64, cache_mode="paged", page_size=8, decode_chunk=2,
        prefill_chunk=16, astra_mode="off")
    eng.step()
    with jax.profiler.trace(str(tmp_path)):
        uid = eng.submit([3, 1, 4, 1, 5], 4)
        while not eng.idle:
            eng.step()
    tr = ScopedTrace.from_dir(str(tmp_path))
    names = {s[0] for s in tr.engine_spans}
    assert {"engine.submit", "engine.step", "engine.admit",
            "engine.prefill_chunk", "engine.first_token", "engine.sync",
            "engine.emit", "engine.decode_dispatch"} <= names
    assert {s[3] for s in tr.engine_spans
            if s[0] == "engine.prefill_chunk"} == {uid}
    assert tr.host_spans == Trace.from_dir(str(tmp_path)).host_spans
    steps = tr.engine("engine.step")
    assert steps and len(tr.tick_host_s(steps[0][1], steps[-1][2])) \
        == len(steps)
