"""Model step: model operations of the tokens the traced ticks processed
over the ticks' summed host time at the peak of the cell's chips, %.

Tokens are the real prompt tokens of prefill chunks and the decode
tokens emitted (``work.token_flops``: 2 x the matmul parameters of every
layer and attention over the token's valid positions); the tied head is
counted for the tokens whose logits were needed (each decode token and
each prompt's last token)."""
from bench.core import work


def read(run):
    ticks = run.traced_ticks()
    secs = sum(t.t1 - t.t0 for t in ticks)
    if not ticks or secs <= 0 or run.peaks is None:
        return None
    s = run.spec
    flops = sum(work.token_flops(s, ctx, head=False)
                for t in ticks for ctx in t.prefill_ctx + t.decode_ctx)
    flops += sum(t.head_tokens for t in ticks) * 2 * s.d_model * s.vocab
    if not flops:
        return None
    return 100.0 * flops / (secs * run.peaks["flops_per_s"] * run.chips)
