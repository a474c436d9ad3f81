"""Device time of the sectored attention kernel per decode wave: the
kernel's op events inside the wave programs of the traced window, over the
number of waves."""

from bench import trace as tr


def read(ctx):
    waves = tr.in_window(ctx.trace, tr.matching(ctx.trace.modules, tr.WAVE))
    kernel = tr.within(tr.matching(ctx.trace.ops, tr.KERNEL), waves)
    if not waves or not kernel:
        return None
    return sum(e[2] for e in kernel) / len(waves) / 1e6
