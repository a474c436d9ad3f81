"""Device time of one decode wave: the wave program's device events in the
traced window over their number."""

from bench import trace as tr


def read(ctx):
    waves = tr.in_window(ctx.trace, tr.matching(ctx.trace.modules, tr.WAVE))
    if not waves:
        return None
    return sum(e[2] for e in waves) / len(waves) / 1e6
