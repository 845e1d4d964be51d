"""Operations and bytes that the served work needs, counted from the
harness's own record of rows and lengths (never from a kernel's padded
shapes), and the device peaks they are held against.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Tuple

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """{"flops_per_s", "hbm_bytes_per_s", "hbm_bytes"} of one chip of
    ``device_kind``; a kind missing from ``peaks.json`` is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def matmul_params(s) -> int:
    """Weights one token multiplies through in one layer (q, k, v, o and
    the two MLP matrices)."""
    d, hd = s.d_model, s.head_dim
    return (d * s.heads * hd + 2 * d * s.kv_heads * hd + s.heads * hd * d
            + 2 * d * s.ffn)


def attn_flops(s, ctx: int) -> int:
    """Attention operations of one query token over ``ctx`` positions,
    all layers: q.k and p.v, two operations per multiply-add."""
    return 4 * s.heads * s.head_dim * ctx * s.layers


def token_flops(s, ctx: int, head: bool) -> int:
    """Model operations of one token at context ``ctx``: 2 x matmul
    parameters of every layer, attention, and the tied head where the
    token's logits are needed."""
    f = 2 * matmul_params(s) * s.layers + attn_flops(s, ctx)
    if head:
        f += 2 * s.d_model * s.vocab
    return f


def decode_attn_need(s, contexts: Iterable[int], cache_bytes: int = 4
                     ) -> Tuple[int, int]:
    """(operations, bytes) that decode attention needs for query tokens at
    the given contexts (positions each attends over), all layers: the
    valid K and V positions in the cache's dtype, the query read and the
    output written in float32."""
    flops = byts = 0
    qo = 2 * s.heads * s.head_dim * 4
    for ctx in contexts:
        flops += attn_flops(s, ctx)
        byts += (2 * ctx * s.kv_heads * s.head_dim * cache_bytes + qo) \
            * s.layers
    return flops, byts


def least_seconds(flops: float, byts: float, pk: Dict[str, float]) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / pk["flops_per_s"], byts / pk["hbm_bytes_per_s"])
