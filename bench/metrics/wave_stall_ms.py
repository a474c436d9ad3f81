"""How long the host holds the device back between waves: the 95th
percentile, over consecutive ``session.wave_sync`` spans of the traced
window, of the next one's start less the previous one's end. A pair with
a ``wait_arrivals`` span between them is left out: an idle session has no
gap between tokens."""

import numpy as np

from bench import spans as sp


def read(ctx):
    syncs = sp.named(ctx.trace, sp.SYNC)
    waits = sp.named(ctx.trace, sp.WAIT)
    stalls = [b[1] - (a[1] + a[2]) for a, b in zip(syncs, syncs[1:])
              if not sp.starting_in(waits, a[1] + a[2], b[1])]
    if not stalls:
        return None
    return float(np.percentile(stalls, 95)) / 1e6
