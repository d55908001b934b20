"""The whole step's share of the chip's bf16 peak, in %: the useful
operations of the prompt and output tokens the traced steps processed
(``bench/costs/dense_lm``; the masked columns of the chunk scan do not
count) over the traced window times the peak."""
from bench.costs import dense_lm


def read(r):
    if not r["trace"] or not r["step_log"]:
        return None
    flops = sum(dense_lm.flops(r["model"], n, ctx) for n, ctx, _ in r["step_log"])
    return 100.0 * flops / (r["trace"].window_s * r["peaks"]["bf16_flops_per_s"])
