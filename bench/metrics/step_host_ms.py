"""Host self time of a session step that ran a wave: over the traced
window's ``session.step`` spans that hold a ``session.wave_sync``, the
step's duration less the durations of the ``session.wave_sync`` (blocked
on the wave) and ``session.prefill`` (dispatch through the first token)
spans that start inside it, averaged."""

from bench import spans as sp
from bench import trace as tr


def read(ctx):
    syncs = sp.named(ctx.trace, sp.SYNC)
    inner = syncs + sp.named(ctx.trace, sp.PREFILL)
    own = []
    for _, start, dur in sp.named(ctx.trace, tr.STEP):
        if sp.starting_in(syncs, start, start + dur):
            own.append(dur - sum(h[2] for h in
                                 sp.starting_in(inner, start, start + dur)))
    if not own:
        return None
    return sum(own) / len(own) / 1e6
