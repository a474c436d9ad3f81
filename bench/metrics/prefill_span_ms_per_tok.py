"""Host time of the prefills per prompt token they scanned: the total
duration of the traced window's ``session.prefill`` spans (dispatch through
the first token and its logprob) over the change of the session's
``stats["prefill_tokens"]`` across the traced span. A program that keeps no
such counter reads nothing."""

from bench import spans as sp


def read(ctx):
    before, after = ctx.stats
    if "prefill_tokens" not in after:
        return None
    tokens = after["prefill_tokens"] - before["prefill_tokens"]
    prefills = sp.named(ctx.trace, sp.PREFILL)
    if tokens <= 0 or not prefills:
        return None
    return sum(h[2] for h in prefills) / tokens / 1e6
