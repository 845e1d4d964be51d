"""Interval arithmetic and the trace reduction, on synthetic event lists."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.core.trace import (Trace, clip, covered, gaps, op_name,  # noqa: E402
                              self_times, union)


def test_union_merges_overlaps_and_touching():
    got = union([(5, 6), (0, 2), (1, 3), (3, 4), (8, 8)])
    assert got == [(0, 4), (5, 6)]


def test_clip_and_covered():
    iv = [(0, 2), (1, 3), (5, 9)]
    assert clip(iv, 1, 6) == [(1, 2), (1, 3), (5, 6)]
    assert covered(iv) == pytest.approx(3 + 4)


def test_gaps_cover_the_complement():
    busy = [(1, 2), (1.5, 3), (5, 6)]
    assert gaps(busy, 0, 7) == [(0, 1), (3, 5), (6, 7)]
    assert gaps([], 0, 1) == [(0, 1)]
    assert gaps([(0, 10)], 2, 3) == []


def _trace():
    ops = {"/device:TPU:0": [("fusion", 0.0, 1.0), ("kern", 1.0, 1.5),
                             ("fusion", 4.0, 4.1), ("kern", 3.0, 4.0)]}
    host = [("tick", 0.0, 2.0), ("wait_arrival", 2.0, 2.9),
            ("tick", 2.9, 4.2)]
    return Trace(ops, host)


def test_busy_and_idle_share():
    tr = _trace()
    assert tr.busy_s(0.0, 4.2) == pytest.approx(2.6)
    idle = 1 - tr.busy_s(0.0, 4.2) / 4.2
    assert 0 < idle < 1


def test_kernel_time_matches_name_only():
    tr = _trace()
    assert tr.op_seconds(0.0, 4.2, "kern") == pytest.approx(1.5)
    assert tr.op_seconds(0.0, 3.5, "kern") == pytest.approx(1.0)
    assert tr.op_seconds(0.0, 4.2, "ker") == 0.0


def test_op_name_strips_hlo_text_and_number():
    assert op_name("%fp_decode_attention.9 = (f32[32,12,1,1]{3,2,1,0}) "
                   "custom-call(...)") == "fp_decode_attention"
    assert op_name("%while.42 = (s32[]) while(...)") == "while"
    assert op_name("copy.3") == "copy"


def test_self_time_subtracts_nested_events():
    ops = [("while", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 2.0, 2.5),
           ("a", 4.0, 5.0), ("c", 11.0, 12.0)]
    got = {(n, a): s for n, a, _, s in self_times(ops)}
    assert got[("while", 0.0)] == pytest.approx(7.0)
    assert got[("a", 1.0)] == pytest.approx(1.5)
    assert got[("b", 2.0)] == pytest.approx(0.5)
    assert got[("c", 11.0)] == pytest.approx(1.0)
    tr = Trace({"/device:TPU:0": ops}, [])
    assert tr.op_seconds(0.0, 12.0, "a") == pytest.approx(2.5)
    assert tr.top_ops(0.0, 12.0)[0] == ("while", pytest.approx(7.0))


def test_top_ops_and_idle_gaps_named_by_host_span():
    tr = _trace()
    assert tr.top_ops(0.0, 4.2)[0] == ("kern", pytest.approx(1.5))
    got = tr.idle_gaps(0.0, 4.2)
    assert got[0] == ("wait_arrival", pytest.approx(1.5))
    assert ("tick", pytest.approx(0.1)) in got
    assert sum(s for _, s in got) == pytest.approx(4.2 - 2.6)


def test_devices_are_averaged():
    ops = {"/device:TPU:0": [("a", 0.0, 1.0)],
           "/device:TPU:1": [("a", 0.0, 3.0)]}
    tr = Trace(ops, [])
    assert tr.busy_s(0.0, 4.0) == pytest.approx(2.0)
    assert tr.op_seconds(0.0, 4.0, "a") == pytest.approx(2.0)
