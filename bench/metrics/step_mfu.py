"""The whole step's share of the chip's bf16 peak: model FLOPs of every
token computed in the session steps of the traced window (decode tokens,
attending the pages their step selects, and the prompt tokens the prefills
scanned, attending all earlier tokens; ``bench/costs.py``) over the span of
those steps, over the peak of ``bench/peaks.py``."""

from bench import peaks
from bench import trace as tr


def read(ctx):
    spans = tr.step_spans(ctx.trace, ctx.trace_steps)
    if not spans:
        return None
    flops = sum(step["flops"] for _, _, step in spans)
    seconds = (spans[-1][1] - spans[0][0]) / 1e9
    if flops <= 0 or seconds <= 0:
        return None
    return 100.0 * flops / seconds / peaks.for_kind(ctx.kind)["bf16_flops"]
