"""Share of the sectored attention kernel's roofline: the least time the
chip could take for the kernel's calls in the traced window (one call per
layer per wave, over every slot of the wave, ``selected`` pages per KV head;
operations and bytes from ``bench/costs.py``, peaks from ``bench/peaks.py``)
over the kernel's device time. At these shapes the bound is memory."""

from bench import costs, peaks
from bench import trace as tr


def read(ctx):
    waves = tr.in_window(ctx.trace, tr.matching(ctx.trace.modules, tr.WAVE))
    kernel = tr.within(tr.matching(ctx.trace.ops, tr.KERNEL), waves)
    kernel_s = sum(e[2] for e in kernel) / 1e9
    if not waves or kernel_s <= 0:
        return None
    flops, nbytes = costs.kernel_call(ctx.sizes, ctx.max_batch, ctx.selected)
    least, _ = costs.roofline_s(flops, nbytes, peaks.for_kind(ctx.kind))
    calls = len(waves) * ctx.sizes["n_layers"]
    return 100.0 * calls * least / kernel_s
