"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device's op intervals over the window."""

from bench import trace as tr


def read(ctx):
    return 100.0 * (1.0 - tr.busy_ns(ctx.trace) / 1e9 / ctx.trace.window_s)
