"""Kernels: fp_decode_attention's share of its roofline, %.

The least time the work decode attention needs would take at the peaks
of the cell's chips together (every active row's valid cache positions,
each layer and each decode token: float32 K/V bytes, q and out bytes,
4 * H * hd * ctx operations), over the summed device time of the
kernel's events in the traced ticks, averaged over the chips.  The work
is counted from the harness's record of rows and lengths, not from the
kernel's padded shapes.

In a TPU trace the kernel's events carry the Pallas call's HLO name,
``%fp_decode_attention.<n>`` (the jitted wrapper in
``kernels/vq_decode_attn.py``, whose body is ``_fp_kernel``); the
coded-cache kernel is ``vq_decode_attention`` and is not matched.
"""
from bench.core import work

KERNEL = "fp_decode_attention"


def read(run):
    ticks = run.traced_ticks()
    if run.trace is None or not ticks or run.peaks is None:
        return None
    secs = run.trace.op_seconds(run.trace_lo, run.trace_hi, KERNEL)
    ctxs = [c for t in ticks for c in t.decode_ctx]
    if secs <= 0 or not ctxs:
        return None
    flops, byts = work.decode_attn_need(run.spec, ctxs)
    return 100.0 * work.least_seconds(flops, byts, run.peaks) \
        / run.chips / secs
