"""Share of the wave's slots that held a request: decode steps over waves
times ``max_batch``, from the session's counters over the window."""


def read(ctx):
    before, after = ctx.stats
    waves = after["waves"] - before["waves"]
    if waves <= 0:
        return None
    steps = after["decode_steps"] - before["decode_steps"]
    return 100.0 * steps / (waves * ctx.max_batch)
