"""Roofline and step-MFU arithmetic against hand counts."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.core import work  # noqa: E402
from bench.core.trace import Trace  # noqa: E402
from bench.reference import dense_lm  # noqa: E402
from bench.run import Run, _load_module  # noqa: E402

PK = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0, "hbm_bytes": 1.0}


def _spec(**kw):
    base = dict(layers=2, d_model=8, heads=2, kv_heads=1, ffn=16, vocab=10,
                eps=1e-6, rope_theta=1e4)
    base.update(kw)
    return dense_lm.Spec(**base)


def test_matmul_params_by_hand():
    s = _spec()  # head_dim 4: q 8x8, k 8x4, v 8x4, o 8x8, mlp 2 x 8x16
    assert work.matmul_params(s) == 64 + 32 + 32 + 64 + 256


def test_token_flops_by_hand():
    s = _spec()
    attn = 4 * 2 * 4 * 5 * 2  # 4 * H * hd * ctx * layers at ctx 5
    assert work.attn_flops(s, 5) == attn
    assert work.token_flops(s, 5, head=False) == 2 * 448 * 2 + attn
    assert work.token_flops(s, 5, head=True) == 2 * 448 * 2 + attn + 160


def test_decode_attention_need_by_hand():
    s = _spec()
    flops, byts = work.decode_attn_need(s, [3, 5])
    assert flops == work.attn_flops(s, 3) + work.attn_flops(s, 5)
    # per layer: K and V of ctx positions (1 kv head x 4 x 4 B) + q, out
    qo = 2 * 2 * 4 * 4
    assert byts == 2 * ((2 * 3 * 4 * 4 + qo) + (2 * 5 * 4 * 4 + qo))


def test_least_seconds_takes_the_binding_roof():
    assert work.least_seconds(100.0, 1.0, PK) == pytest.approx(1.0)
    assert work.least_seconds(1.0, 100.0, PK) == pytest.approx(10.0)


def test_peaks_table_keyed_by_device_kind():
    table = json.loads(work.PEAKS_FILE.read_text())
    assert "source" in table
    pk = work.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("no such chip")


class _Tick:
    def __init__(self, t0, t1, decode_ctx, prefill_ctx, head_tokens):
        self.t0, self.t1 = t0, t1
        self.decode_ctx, self.prefill_ctx = decode_ctx, prefill_ctx
        self.head_tokens = head_tokens


def _run(kernel_s, ticks, pk=PK):
    ops = {"/device:TPU:0": [("fp_decode_attention", 0.0, kernel_s)]}
    return Run(spec=_spec(), peaks=pk, trace=Trace(ops, []), trace_lo=0.0,
               trace_hi=1e9, traced=(0, len(ticks)), ticks=ticks)


def _metric(name):
    return _load_module(ROOT / "bench" / "metrics" / f"{name}.py")


def test_decode_roofline_share_by_hand_and_bounded():
    ticks = [_Tick(0, 1, [3, 5], [], 2)]
    flops, byts = work.decode_attn_need(_spec(), [3, 5])
    least = max(flops / 100.0, byts / 10.0)
    share = _metric("decode_attn_roofline").read(_run(2 * least, ticks))
    assert share == pytest.approx(50.0)
    # the kernel can take no less than the least time: never above 100 %
    assert _metric("decode_attn_roofline").read(_run(least, ticks)) \
        == pytest.approx(100.0)


def test_decode_roofline_silent_without_kernel_or_decode():
    assert _metric("decode_attn_roofline").read(
        _run(1.0, [_Tick(0, 1, [], [1, 2], 1)])) is None
    run = _run(1.0, [_Tick(0, 1, [3], [], 1)])
    run.trace = Trace({"/device:TPU:0": [("fusion", 0.0, 1.0)]}, [])
    assert _metric("decode_attn_roofline").read(run) is None


def test_step_mfu_by_hand():
    s = _spec()
    ticks = [_Tick(0.0, 2.0, [4], [1, 2], 2)]
    flops = (work.token_flops(s, 4, head=False)
             + work.token_flops(s, 1, head=False)
             + work.token_flops(s, 2, head=False)
             + 2 * 2 * s.d_model * s.vocab)
    got = _metric("step_mfu").read(_run(1.0, ticks, dict(PK,
                                                          flops_per_s=1e6)))
    assert got == pytest.approx(100.0 * flops / (2.0 * 1e6))
    assert got <= 100.0


def test_idle_share_by_hand():
    run = _run(2.5, [_Tick(0, 1, [1], [], 1)])
    run.trace_hi = 10.0
    assert _metric("device_idle_share").read(run) == pytest.approx(75.0)


def test_mesh_cell_holds_work_against_all_its_chips():
    """On a mesh of four chips the same work over the same (per-chip
    averaged) time is a quarter of the chips' peaks together."""
    ticks = [_Tick(0.0, 2.0, [3, 5], [1, 2], 2)]
    for name in ("decode_attn_roofline", "step_mfu"):
        one = _metric(name).read(_run(1.0, ticks))
        run = _run(1.0, ticks)
        run.chips = 4
        assert _metric(name).read(run) == pytest.approx(one / 4)
