"""The decode waves' share of the chip's HBM bandwidth: the least bytes the
traced waves had to read, over the peak bandwidth (``bench/peaks.py``),
over the waves' device time (the wave program's device events in the
traced window).

Least bytes (the family's ``wave_bytes``): every weight once a wave, but
the routed experts only where a wave touched them (the session's
``stats["experts_touched"]``) and the embedding not at all (a lookup);
the latent rows each decode step attended, its slot's valid rows and not
the padding (``stats["context_tokens"]``), once in each layer. Counters
are differenced over the traced span, which the waves' device events also
cover; the bytes are taken per counted wave, times the waves the trace
holds, so a wave at the window's edge counts neither way. A family without ``wave_bytes``, or a program without the
counters, reads nothing."""

from bench import families, peaks
from bench import trace as tr

COUNTERS = ("waves", "experts_touched", "context_tokens")


def read(ctx):
    wave_bytes = getattr(families.of(ctx.sizes), "wave_bytes", None)
    before, after = ctx.stats
    if wave_bytes is None or any(k not in after for k in COUNTERS):
        return None
    waves = tr.in_window(ctx.trace, tr.matching(ctx.trace.modules, tr.WAVE))
    seconds = sum(e[2] for e in waves) / 1e9
    n = {k: after[k] - before[k] for k in COUNTERS}
    if not waves or seconds <= 0 or n["waves"] <= 0:
        return None
    per_wave = wave_bytes(ctx.sizes, n["waves"], n["experts_touched"],
                          n["context_tokens"]) / n["waves"]
    least_s = (per_wave * len(waves)
               / peaks.for_kind(ctx.kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
