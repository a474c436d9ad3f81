"""A cell cut to a size the CPU runs in seconds (Pallas in interpret mode):
the same harness, session path, traffic shape and comparison, with toy
widths and lengths. For the CPU tests only; no chip number comes from it."""

import copy

from bench import families, run

SEED = 2**31 + 12345
# readings of sound runs at this size are under 0.01; a wrong token, a
# stale state or the float8 control reads over 0.05
LIMITS = {"gap": 0.03}


def spec(name: str, doc: int = 256):
    s = run.load_cell(name)
    s.sizes = families.of(s.sizes).tiny(s.sizes)
    t = copy.deepcopy(s.traffic)
    if "shared_prefix_tokens" in t:
        t["shared_prefix_tokens"] = doc
    t["prompt_tokens"]["values"] = [v // 8 for v in
                                    t["prompt_tokens"]["values"]]
    lo, hi = t["new_tokens"]["loguniform"]
    t["new_tokens"] = {"loguniform": [max(lo // 16, 2), max(hi // 16, 4)]}
    if t["loop"] == "closed":
        t.update(clients=4, warmup_completions=4)
    else:
        t.update(rate_per_s=4.0, warmup_s=1.0)
    s.traffic = t
    c = copy.deepcopy(s.cell)
    c["session"]["max_batch"] = 4
    c["session"]["seq_len"] = (t.get("shared_prefix_tokens", 0)
                               + max(t["prompt_tokens"]["values"])
                               + t["new_tokens"]["loguniform"][1])
    c["check"] = dict(LIMITS)
    s.cell = c
    return s


def run_tiny(name: str, seconds: float = 2.0, **kw):
    return run.run_cell(spec(name), SEED, seconds, False, require_tpu=False,
                        **kw)
