"""How late the open-loop generator submitted: 95th percentile, over the
requests due in the window, of submission time minus due time (host clock).
A closed loop has no schedule to be late for, so it reads nothing."""

import numpy as np


def read(ctx):
    if ctx.traffic["loop"] != "open" or not ctx.lags_ms:
        return None
    return float(np.percentile(ctx.lags_ms, 95))
