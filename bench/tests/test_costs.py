"""Operations and bytes against counts by hand for one shape."""

from types import SimpleNamespace

import numpy as np

from bench import costs, peaks, run

YI = dict(n_layers=8, d_model=4096, n_heads=32, n_kv_heads=4, head_dim=128,
          d_ff=11008, vocab=64000)


def test_kernel_call_by_hand():
    # 16 slots, 4 KV heads, 10 pages of 128 rows of 128 dims, bf16 K and V
    flops, nbytes = costs.kernel_call(YI, 16, 10)
    assert flops == 4 * 16 * 32 * 10 * 128 * 128
    kv = 2 * 16 * 4 * 10 * 128 * 128 * 2  # 41,943,040 bytes
    q = 16 * 32 * 128 * 2
    out = 16 * 32 * 128 * 4 + 16 * 4 * 10 * 4
    assert nbytes == kv + q + out == 42_338_816
    least, bound = costs.roofline_s(flops, nbytes, peaks.for_kind(
        "TPU v5 lite"))
    assert bound == "memory"
    assert abs(least - 42_338_816 / 819e9) < 1e-15


def test_token_flops_by_hand():
    layer = 4096 * 128 * (32 + 8) + 32 * 128 * 4096 + 3 * 4096 * 11008
    params = 8 * layer + 64000 * 4096
    assert costs.matmul_params(YI) == params == 1_646_264_320
    assert costs.token_flops(YI, 1000) == 2 * params + 8 * 4 * 32 * 128 * 1000


def test_attended_decode():
    assert costs.attended_decode(100, 10) == 101  # one page, all valid
    assert costs.attended_decode(9 * 128 + 5, 10) == 9 * 128 + 6
    assert costs.attended_decode(8200, 10) == 9 * 128 + 8200 % 128 + 1


class _Handle:
    def __init__(self):
        self.new, self.done = [], False

    def poll(self):
        new, self.new = self.new, []
        return new


class _Session:
    """Admits every request in flight in one step; the cache matched the
    first one's document and nothing of the second."""

    def __init__(self, recs, matched):
        self.recs, self.prefix_cache = recs, SimpleNamespace(
            stats={"hit_tokens": 0}, matched={})
        self.matched = matched

    def step(self):
        for rec, m in zip(self.recs, self.matched):
            self.prefix_cache.stats["hit_tokens"] += m
            self.prefix_cache.matched[rec.prompt.tobytes()] = m
            rec.handle.new = [7]


def test_prefill_count_follows_the_cache_match():
    prompts = [np.arange(300, dtype=np.int32), np.arange(1, 41,
                                                         dtype=np.int32)]
    recs = [run.Rec(rid=i, prompt=p, new_tokens=4, due=0.0, submitted=0.0,
                    handle=_Handle()) for i, p in enumerate(prompts)]
    session = _Session(recs, [256, 0])
    loop = run.Loop(session, None, YI, 10, lambda: 0.0)
    loop.inflight = list(recs)
    loop.step()
    step = loop.steps[0]
    assert step["admitted_prompt"] == 340 and step["hit_tokens"] == 256
    assert step["prefilled"] == 44 + 40
    scanned = list(range(256, 300)) + list(range(40))
    assert step["flops"] == sum(costs.token_flops(YI, p + 1)
                                for p in scanned)
    assert session.prefix_cache.matched == {}


def test_unknown_device_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v9 imaginary")
