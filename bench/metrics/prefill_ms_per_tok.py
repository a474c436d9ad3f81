"""Device time of the cold and warm (suffix) prefill programs per prompt
token they scanned, over the session steps of the traced window that
admitted a request. A prefill runs inside the step that admits its request
and that step blocks on it, so each prefill program is the step's whose
host span it overlaps most (the two clocks differ by up to about a
millisecond). Steps whose prefill the trace did not record count neither
time nor tokens."""

from bench import trace as tr


def read(ctx):
    spans = tr.step_spans(ctx.trace, ctx.trace_steps)
    prefills = [e for e in ctx.trace.modules
                if any(n in e[0] for n in tr.PREFILLS)]
    owner = tr.owners(prefills, [(a, b) for a, b, _ in spans])
    time: dict[int, float] = {}
    for e, i in zip(prefills, owner):
        if i is not None and spans[i][2]["prefilled"]:
            time[i] = time.get(i, 0.0) + e[2]
    tokens = sum(spans[i][2]["prefilled"] for i in time)
    if not tokens:
        return None
    return sum(time.values()) / tokens / 1e6
