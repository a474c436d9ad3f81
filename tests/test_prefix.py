"""Cross-request prefix cache: radix longest-prefix matching, refcounted
shared KV entries, copy-on-write page accounting, the cold-vs-warm stream
identity oracle, per-token logprobs, and shared-fetch energy attribution.

The load-bearing contract (mirrors docs/serving.md "Prefix cache"): a
warm-cache session must emit bit-identical token streams AND logprobs to
a cold one — greedy and counter-keyed sampled alike — across both
schedulers and under KV-pool preemption. Sharing is an accounting and
energy optimization, never a semantic one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro import configs
from repro.models import model
from repro.runtime import sectored_decode
from repro.sample import token_logprobs
from repro.serve import (AlwaysDense, FifoScheduler, KVPagePool,
                         OverlapScheduler, PrefixCache, Request, SamplerSpec,
                         ServeSession, ServingBackend)
from repro.telemetry import KVGeometry, MeteredBackend, WaveMeter

TOK = st.integers(0, 3)


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get("yi-6b").reduced(n_layers=2, d_model=64, n_heads=4,
                                       n_kv_heads=2, d_ff=128, vocab=128,
                                       head_dim=32)
    params = model.init_params(cfg, jax.random.key(0))
    backend = sectored_decode.make_serving_fns(cfg, params=params,
                                               seq_len=48)
    return cfg, backend


# -- radix tree: reference-model and property tests --------------------------


def _lcp(a, b) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


@settings(max_examples=25, deadline=None)
@given(st.lists(st.lists(TOK, min_size=1, max_size=8), min_size=1,
                max_size=8),
       st.lists(TOK, min_size=1, max_size=8))
def test_match_equals_reference_longest_common_prefix(prompts, probe):
    """The path-compressed radix walk must agree with the brute-force
    longest-common-prefix over every inserted prompt."""
    cache = PrefixCache(capacity_pages=1_000_000, page_size=4)
    for i, p in enumerate(prompts):
        cache.insert(tuple(p), state=("s", i))
    donor, m = cache.match(tuple(probe))
    ref = max(_lcp(probe, p) for p in prompts)
    assert m == (ref if ref >= 1 else 0)
    if m:
        assert donor is not None
        assert _lcp(probe, donor.tokens) >= m


@settings(max_examples=25, deadline=None)
@given(st.lists(st.lists(TOK, min_size=1, max_size=10), min_size=1,
                max_size=6),
       st.lists(TOK, min_size=1, max_size=10))
def test_match_length_monotone_in_query_prefix(prompts, probe):
    """Extending the query can only deepen (never shorten) the match."""
    cache = PrefixCache(capacity_pages=1_000_000, page_size=4)
    for i, p in enumerate(prompts):
        cache.insert(tuple(p), state=i)
    matches = [cache.match(tuple(probe[:k]))[1]
               for k in range(1, len(probe) + 1)]
    assert matches == sorted(matches)
    assert all(m <= k for k, m in enumerate(matches, start=1))


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.lists(TOK, min_size=1, max_size=10),
                          st.booleans()),
                min_size=1, max_size=10))
# the insert that evicted the node its own walk ended on
@example(entries=[([0], False), ([1], False), ([0, 0], False)])
def test_never_evicts_referenced_entries(entries):
    """Refcount > 0 pins an entry through arbitrary admission pressure:
    a 2-page cache under a stream of inserts evicts constantly, but every
    leased prompt must stay fully matchable until released."""
    cache = PrefixCache(capacity_pages=2, page_size=4)
    leases = []
    for i, (toks, hold) in enumerate(entries):
        cache.insert(tuple(toks), state=i)
        if hold:
            lease = cache.acquire(tuple(toks))
            if lease is not None:
                leases.append(lease)
    cache.shed(1_000_000)  # max pressure: drop everything unreferenced
    for lease in leases:
        assert lease.entry.refcount > 0
        donor, m = cache.match(tuple(lease.entry.tokens))
        assert m == len(lease.entry.tokens)
    for lease in leases:
        cache.release(lease)
    cache.shed(1_000_000)
    assert cache.held_pages == 0


@settings(max_examples=25, deadline=None)
@given(st.lists(TOK, min_size=2, max_size=12), st.integers(2, 4))
def test_release_is_idempotent(tokens, n_releases):
    cache = PrefixCache(capacity_pages=64, page_size=4)
    cache.insert(tuple(tokens), state=0)
    lease = cache.acquire(tuple(tokens))
    assert lease is not None
    assert lease.entry.refcount == 1
    for _ in range(n_releases):
        cache.release(lease)
    assert lease.entry.refcount == 0
    again = cache.acquire(tuple(tokens))
    assert again is not None and again.entry.refcount == 1
    cache.release(again)
    cache.release(again)
    assert again.entry.refcount == 0


def test_shared_tokens_page_aligned_and_cow_counted():
    """Only complete pages count as shared; a non-aligned match is a
    copy-on-write admission (the partial page is privately rebuilt)."""
    cache = PrefixCache(capacity_pages=64, page_size=4)
    cache.insert(tuple(range(10)), state="e")
    lease = cache.acquire(tuple(range(10)))
    assert lease.matched_tokens == 10
    assert lease.shared_tokens == 8  # 2 complete pages of 4
    assert lease.shared_pages == 2
    assert cache.stats["cow_copies"] == 1
    cache.release(lease)
    aligned = cache.acquire(tuple(range(8)))
    assert aligned.matched_tokens == 8 and aligned.shared_tokens == 8
    assert cache.stats["cow_copies"] == 1  # aligned match copies nothing


def test_lru_evicts_unreferenced_only_and_dedupe_refreshes():
    cache = PrefixCache(capacity_pages=3, page_size=4)
    a, b, c = (1, 1, 1, 1), (2, 2, 2, 2), (3, 3, 3, 3)
    for i, toks in enumerate((a, b, c)):
        cache.insert(toks, state=i)
    assert cache.insert(a, state="dup") is False  # dedupe refreshes a
    cache.insert((4, 4, 4, 4), state=3)  # over capacity: LRU (b) goes
    assert cache.match(b)[1] == 0
    assert cache.match(a)[1] == 4  # refreshed entry survived
    assert cache.stats["evictions"] == 1


# -- the cold-vs-warm oracle over the real backend ---------------------------


def _prefix_requests(cfg, n=5, shared=12, tail=4, max_new=6):
    """Mixed greedy + counter-keyed sampled requests sharing a prefix."""
    rng = np.random.default_rng(7)
    common = rng.integers(0, cfg.vocab, size=shared).astype(np.int32)
    tails = np.random.default_rng(42)
    reqs = []
    for rid in range(n):
        t = tails.integers(0, cfg.vocab, size=tail).astype(np.int32)
        spec = (SamplerSpec(temperature=0.8, seed=100 + rid)
                if rid % 2 else None)
        reqs.append(Request(rid, np.concatenate([common, t]),
                            max_new_tokens=max_new, sampler=spec))
    return reqs


def _run_streams(backend, cfg, scheduler_cls, cache, pool):
    """Staggered arrivals (one submit per step) so later requests can
    hit entries inserted by earlier ones; returns streams + the session."""
    sess = ServeSession(backend, max_batch=3, scheduler=scheduler_cls(),
                        policy=AlwaysDense(), prefix_cache=cache,
                        page_pool=pool)
    handles = []
    for r in _prefix_requests(cfg):
        handles.append(sess.submit(r))
        sess.step()
    sess.run_until_drained()
    return {h.rid: (tuple(h.peek()), tuple(h.logprobs()))
            for h in handles}, sess


@pytest.mark.parametrize("scheduler", [FifoScheduler, OverlapScheduler],
                         ids=["fifo", "overlap"])
def test_warm_streams_and_logprobs_identical_uncontended(setup, scheduler):
    """The tentpole contract, uncontended: warm admissions (suffix-only
    prefill from a shared entry) emit bit-identical tokens AND logprobs
    to cold ones, greedy and sampled alike."""
    cfg, backend = setup
    cache = PrefixCache(capacity_pages=8, page_size=16)
    cold, _ = _run_streams(backend, cfg, scheduler, None, None)
    warm, _ = _run_streams(backend, cfg, scheduler, cache, None)
    assert warm == cold
    assert cache.stats["hits"] == 4  # every follower matched the prefix
    assert cache.stats["hit_tokens"] > 0


def test_warm_streams_identical_under_fifo_preemption(setup):
    """Preempting pool, fifo: growth past a page boundary evicts the
    youngest stream in BOTH runs; resume re-prefills are cold by design
    and the streams still match bit-for-bit."""
    cfg, backend = setup
    cache = PrefixCache(capacity_pages=16, page_size=4)
    cold, csess = _run_streams(backend, cfg, FifoScheduler, None,
                               KVPagePool(11, page_size=4))
    warm, wsess = _run_streams(backend, cfg, FifoScheduler, cache,
                               KVPagePool(11, page_size=4))
    assert warm == cold
    assert csess.stats["preemptions"] > 0
    assert wsess.stats["preemptions"] > 0


def test_warm_sharing_relieves_overlap_preemption_pressure(setup):
    """Preempting pool, overlap: the cold run preempts; the warm run's
    shared pages shrink its footprint, so it preempts strictly less —
    with streams still bit-identical. Sharing buys capacity, never
    different tokens."""
    cfg, backend = setup
    cache = PrefixCache(capacity_pages=16, page_size=4)
    cold, csess = _run_streams(backend, cfg, OverlapScheduler, None,
                               KVPagePool(10, page_size=4))
    warm, wsess = _run_streams(backend, cfg, OverlapScheduler, cache,
                               KVPagePool(10, page_size=4))
    assert warm == cold
    assert csess.stats["preemptions"] > 0
    assert wsess.stats["preemptions"] < csess.stats["preemptions"]
    assert cache.stats["hits"] > 0


def test_warm_run_meters_fewer_prefill_joules(setup):
    """Warm admissions charge only the suffix fraction of prefill fetch
    energy and bank the reuse in ``prefix_hit_tokens`` — total metered
    energy drops while ``prefill_tokens`` keeps full-prompt semantics."""
    cfg, backend = setup
    cold_metered = MeteredBackend(backend)
    _run_streams(cold_metered, cfg, FifoScheduler, None, None)
    cold = cold_metered.meter.report()
    warm_metered = MeteredBackend(backend)
    cache = PrefixCache(capacity_pages=8, page_size=16)
    _run_streams(warm_metered, cfg, FifoScheduler, cache, None)
    warm = warm_metered.meter.report()
    assert warm["prefix_hit_tokens"] > 0
    assert warm["prefill_tokens"] == cold["prefill_tokens"]
    assert warm["prefill_j"] < cold["prefill_j"]
    assert warm["energy_j"] < cold["energy_j"]


# -- configuration refusals --------------------------------------------------


def test_prefix_cache_requires_seeding_hooks(setup):
    """A backend without state_prefix/suffix_prefill cannot serve warm
    admissions — the session refuses loudly instead of silently going
    cold."""
    cfg, _ = setup

    def prefill_fn(tokens):
        B = tokens.shape[0]
        return jnp.zeros((B, 1, 8)), dict(pos=jnp.zeros((B,), jnp.int32))

    def decode_fn(state, token):
        return jnp.zeros((token.shape[0], 8)), state

    dense = ServingBackend(prefill_fn, decode_fn)
    with pytest.raises(ValueError, match="state_prefix"):
        ServeSession(dense, max_batch=2, prefix_cache=PrefixCache(8))


def test_prefix_cache_page_size_must_match_pool(setup):
    cfg, backend = setup
    with pytest.raises(ValueError, match="page_size"):
        ServeSession(backend, max_batch=2,
                     prefix_cache=PrefixCache(8, page_size=16),
                     page_pool=KVPagePool(8, page_size=4))


# -- per-token logprobs ------------------------------------------------------


def test_token_logprob_matches_log_softmax():
    logits = np.linspace(-3.0, 5.0, 16, dtype=np.float32)
    toks = jnp.asarray([3, 11], jnp.int32)
    stacked = jnp.stack([jnp.asarray(logits)] * 2)[:, None, :]
    got = np.asarray(token_logprobs(stacked, toks))
    want = np.asarray(jax.nn.log_softmax(jnp.asarray(logits)))[[3, 11]]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_logprobs_cover_every_token_and_match_looped_wave(setup):
    """StreamHandle.logprobs() is per emitted token, from the raw
    (untempered) distribution, and identical between the fused
    vectorized wave and the per-slot looped reference wave."""
    cfg, backend = setup

    def run(vectorized):
        sess = ServeSession(backend, max_batch=3, policy=AlwaysDense(),
                            vectorized=vectorized)
        handles = [sess.submit(r) for r in _prefix_requests(cfg, n=3)]
        sess.run_until_drained()
        return {h.rid: (tuple(h.peek()), tuple(h.logprobs()))
                for h in handles}

    fused = run(True)
    looped = run(False)
    for rid, (toks, lps) in fused.items():
        assert len(lps) == len(toks) > 0
        assert all(lp <= 0.0 for lp in lps)  # raw logprob of the chosen id
    assert looped == fused
