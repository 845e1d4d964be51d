"""What decides ``correct``: the served tokens of a seeded sample of the
finished requests, against the plain reference run over each prompt with
its served tokens.

The number compared is the widest logit gap: over every served token,
how far the reference's logit for that token lies below the reference's
best logit at that position.  Greedy decoding that matched the reference
exactly would read 0; rounding in the served path lets near-ties flip,
which reads as a small gap.  The control (the reference itself computed
in a lower precision) reads the same gap for the token it puts first.
"""
from __future__ import annotations

import random
from typing import Dict, List, Optional, Sequence

import numpy as np


def sample(reqs: Sequence, seed: int, min_tokens: int) -> List:
    """Finished requests to check: the longest (prompt + output) first,
    then, in a seeded order, one from every other slot that finished one
    (a fault confined to one slot shows), then others in the same order
    until ``min_tokens`` served tokens are covered."""
    done = [r for r in reqs if r.done is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.n_prompt + r.n_out, r.i))
    rest = sorted((r for r in done if r is not longest), key=lambda r: r.i)
    random.Random(seed).shuffle(rest)
    picked, slots = [longest], {longest.slot}
    for r in rest:
        if r.slot not in slots:
            picked.append(r)
            slots.add(r.slot)
    tokens = sum(r.n_out for r in picked)
    chosen = {id(r) for r in picked}
    for r in rest:
        if tokens >= min_tokens:
            break
        if id(r) not in chosen:
            picked.append(r)
            tokens += r.n_out
    return picked


def sequences(picked: Sequence):
    """(sequence, first, served) per request: the prompt followed by the
    served tokens but the last, the position whose logits predict the
    first served token, and the served tokens."""
    out = []
    for r in picked:
        served = list(r.obj.output[: r.n_out])
        out.append((list(r.obj.prompt) + served[:-1], r.n_prompt - 1,
                    served))
    return out


def gaps(ref, seqs, control: Optional[str] = None):
    """Per request, the gap of each served token below the reference's
    best logit; with ``control``, also the gap of the token that the
    reference computed in that precision puts first (else None)."""
    xs = ref.hidden([s for s, _, _ in seqs])
    served = []
    for x, (seq, first, toks) in zip(xs, seqs):
        targets = np.zeros((len(seq),), np.int64)
        targets[first:] = toks
        best, at, _ = ref.stats(x, targets)
        served.append((best - at)[first: len(seq)])
    if control is None:
        return served, None
    ctl = ref.hidden([s for s, _, _ in seqs], quant=control)
    picks = [ref.stats(c, np.zeros((len(s),), np.int64), quant=control)[2]
             for c, (s, _, _) in zip(ctl, seqs)]
    del ctl
    ctl_gaps = []
    for x, p, (seq, first, _) in zip(xs, picks, seqs):
        best, at, _ = ref.stats(x, p[: len(seq)])
        ctl_gaps.append((best - at)[first: len(seq)])
    return served, ctl_gaps


def structure(picked: Sequence, vocab: int) -> Dict[str, int]:
    """Counts that must read 0: sampled requests whose output is not the
    requested length, and served ids outside the vocabulary."""
    short = sum(len(r.obj.output) != r.n_out for r in picked)
    bad = sum(int(not 0 <= t < vocab) for r in picked for t in r.obj.output)
    return {"wrong_length": short, "bad_token_ids": bad}


# each compared number's rule against its limit
AT_LEAST = ("requests_checked", "served_tokens_checked")


def compared(picked: Sequence, vocab: int, gap: Optional[float],
             served_tokens: int, limit: float) -> Dict[str, Dict]:
    """Every number compared, each with its limit: the structure counts,
    how many requests and served tokens were checked, and (where any
    request was) the widest logit gap."""
    out = {k: {"value": v, "limit": 0}
           for k, v in structure(picked, vocab).items()}
    out["requests_checked"] = {"value": len(picked), "limit": 1}
    if gap is not None:
        out["max_logit_gap"] = {"value": gap, "limit": float(limit)}
        out["served_tokens_checked"] = {"value": served_tokens, "limit": 1}
    return out


def rule(name: str) -> str:
    return ">=" if name in AT_LEAST else "<="


def verdict(numbers: Dict[str, Dict]) -> bool:
    """Correct: the gap was read, and every number keeps to its limit."""
    return "max_logit_gap" in numbers and all(
        (v["value"] >= v["limit"]) if rule(k) == ">="
        else (v["value"] <= v["limit"]) for k, v in numbers.items())
