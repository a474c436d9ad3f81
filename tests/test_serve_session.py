"""ServeSession redesign: streaming handles, Scheduler / SectorPolicy /
DecodeBackend protocols, prefill-decode overlap, paged-KV admission."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.models import model
from repro.runtime import sector_predictor, sectored_decode
from repro.serve import (Engine, EngineConfig, FifoScheduler,
                         HysteresisPolicy, OverlapScheduler, PathDecision,
                         Request, SamplerSpec, ServeSession, ServingBackend,
                         StreamHandle)

VOCAB = 32


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get("yi-6b").reduced(n_layers=2, d_model=64, n_heads=4,
                                       n_kv_heads=2, d_ff=128, vocab=128,
                                       head_dim=32)
    params = model.init_params(cfg, jax.random.key(0))
    return cfg, params


def _dense_backend(cfg, params, sectored=False):
    @jax.jit
    def prefill_fn(tokens):
        return model.prefill(params, cfg, tokens)

    @jax.jit
    def decode_fn(state, token):
        return model.decode_step(params, cfg, state, token)

    return ServingBackend(prefill_fn, decode_fn,
                          decode_fn if sectored else None)


def _reqs(cfg, n, max_new_tokens, seed=0, size=6):
    rng = np.random.default_rng(seed)
    return [Request(rid, rng.integers(0, cfg.vocab, size=size).astype(np.int32),
                    max_new_tokens=max_new_tokens) for rid in range(n)]


def _fake_backend(quantum=4):
    """Deterministic toy backend whose decode-state shape is the prompt
    length rounded up to ``quantum`` — a stand-in for page-padded KV."""

    def prefill_fn(tokens):
        B, S = tokens.shape
        q = quantum * ((S + quantum - 1) // quantum)
        kv = jnp.broadcast_to(
            jnp.sum(tokens, axis=1, keepdims=True).astype(jnp.float32),
            (B, q)) * 1.0
        logits = jax.nn.one_hot(jnp.sum(tokens, axis=1) % VOCAB, VOCAB)
        return logits, dict(kv=kv, pos=jnp.zeros((B,), jnp.int32))

    def decode_fn(state, token):
        logits = jax.nn.one_hot((token[:, 0] + 1) % VOCAB, VOCAB)
        return logits, dict(kv=state["kv"], pos=state["pos"] + 1)

    return ServingBackend(prefill_fn, decode_fn)


# -- streaming handles -------------------------------------------------------


def test_submit_returns_streaming_handle_no_request_mutation():
    """ServeSession.submit() streams through a handle; the Request object
    is left untouched (the legacy in-place contract lives in the shims)."""
    sess = ServeSession(_fake_backend(), max_batch=2)
    reqs = [Request(rid, np.arange(3, dtype=np.int32), max_new_tokens=4)
            for rid in range(2)]
    handles = [sess.submit(r) for r in reqs]
    assert all(isinstance(h, StreamHandle) for h in handles)
    assert handles[0].poll() == []  # nothing produced yet
    sess.step()
    first = handles[0].poll()
    assert len(first) >= 1
    assert handles[0].poll() == []  # cursor advanced: no re-delivery
    sess.run_until_drained()
    rest = handles[0].poll()
    assert first + rest == handles[0].peek()
    assert len(handles[0].peek()) == 4
    for r in reqs:
        assert r.generated == [] and r.done is False  # no in-place mutation
    assert all(h.done for h in handles)


def test_tokens_iterator_drives_session():
    sess = ServeSession(_fake_backend(), max_batch=2)
    handles = [sess.submit(Request(rid, np.arange(3 + rid, dtype=np.int32),
                                   max_new_tokens=5))
               for rid in range(3)]
    streamed = list(handles[2].tokens())
    assert streamed == handles[2].peek()
    assert len(streamed) == 5
    sess.run_until_drained()
    assert all(h.done for h in handles)


# -- scheduler: admission order + overlap equivalence ------------------------


def test_queue_is_deque_and_admission_order_preserved():
    """The request queue is a deque (O(1) popleft) and admission strictly
    preserves submission order: equal-length requests complete in rid
    order even when they outnumber the slots."""
    sess = ServeSession(_fake_backend(), max_batch=2)
    assert isinstance(sess.queue, collections.deque)
    for rid in range(6):
        sess.submit(Request(rid, np.arange(4, dtype=np.int32),
                            max_new_tokens=3))
    sess.run_until_drained()
    assert sess.completion_order == list(range(6))


def test_overlap_matches_fifo_tokens_and_overlaps_prefill(setup):
    """Acceptance: OverlapScheduler is token-identical to FifoScheduler on
    the same request trace while issuing >= 1 prefill concurrently with a
    decode wave (scheduler stats)."""
    cfg, params = setup

    def run(scheduler):
        sess = ServeSession(_dense_backend(cfg, params), max_batch=2,
                            scheduler=scheduler)
        handles = [sess.submit(r) for r in _reqs(cfg, 5, max_new_tokens=4,
                                                 seed=3)]
        stats = sess.run_until_drained()
        return {h.rid: h.peek() for h in handles}, dict(stats)

    toks_fifo, stats_fifo = run(FifoScheduler())
    toks_ov, stats_ov = run(OverlapScheduler())
    assert toks_ov == toks_fifo
    assert stats_ov["overlapped_prefills"] >= 1
    assert stats_fifo["overlapped_prefills"] == 0
    assert stats_ov["completed"] == stats_fifo["completed"] == 5
    # batched (vmapped) prefill: fewer prefill dispatches than requests
    assert stats_ov["prefill_calls"] < stats_fifo["prefill_calls"]


def test_overlap_matches_fifo_on_sectored_backend(setup):
    """The shipped --true-sectored + overlap combination: fifo and overlap
    stay token-identical over the SectoredState backend with the top-k
    path and demand merge active (both schedulers admit at the same step
    boundaries on this trace)."""
    cfg, params = setup

    def run(scheduler):
        backend = sectored_decode.make_serving_fns(cfg, params=params,
                                                   seq_len=48)
        sess = ServeSession(backend, max_batch=2, scheduler=scheduler,
                            policy=HysteresisPolicy(min_occupancy=0.5))
        shared = np.arange(6, dtype=np.int32) % cfg.vocab
        rng = np.random.default_rng(9)
        handles = []
        for rid in range(4):  # two shared-prefix, two distinct prompts
            prompt = (shared.copy() if rid < 2 else
                      rng.integers(0, cfg.vocab, size=6).astype(np.int32))
            handles.append(sess.submit(Request(rid, prompt,
                                               max_new_tokens=4)))
        stats = sess.run_until_drained()
        assert stats["sectored_waves"] > 0
        return {h.rid: h.peek() for h in handles}

    assert run(FifoScheduler()) == run(OverlapScheduler())


def test_overlap_matches_fifo_under_sampling(setup):
    """The stochastic-decoding oracle: with a mixed greedy+sampled batch
    on the real SectoredState backend, fifo and overlap produce
    bit-identical token streams (counter-based RNG keys depend only on
    (request_seed, position), never on admission timing), and a second
    run replays the first exactly."""
    cfg, params = setup

    def run(scheduler):
        backend = sectored_decode.make_serving_fns(cfg, params=params,
                                                   seq_len=48)
        sess = ServeSession(backend, max_batch=2, scheduler=scheduler,
                            policy=HysteresisPolicy(min_occupancy=0.5))
        rng = np.random.default_rng(5)
        handles = []
        for rid in range(5):
            prompt = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
            spec = (SamplerSpec(temperature=0.9, top_p=0.95,
                                seed=40 + rid) if rid % 2 else None)
            handles.append(sess.submit(Request(rid, prompt,
                                               max_new_tokens=4,
                                               sampler=spec)))
        stats = sess.run_until_drained()
        assert stats["sectored_waves"] > 0
        return {h.rid: h.peek() for h in handles}

    toks_fifo = run(FifoScheduler())
    assert toks_fifo == run(OverlapScheduler())
    assert toks_fifo == run(FifoScheduler())  # per-seed replay


def test_overlap_with_sectored_backend_merges_demands(setup):
    """Overlap scheduling composes with the SectoredState backend: the
    shared-prefix OR-merge still runs before sectored waves."""
    cfg, params = setup
    backend = sectored_decode.make_serving_fns(cfg, params=params, seq_len=48)
    sess = ServeSession(backend, max_batch=2, scheduler=OverlapScheduler(),
                        policy=HysteresisPolicy(min_occupancy=0.5))
    shared = np.arange(6, dtype=np.int32) % cfg.vocab
    handles = [sess.submit(Request(rid, shared.copy(), max_new_tokens=3))
               for rid in range(2)]
    stats = sess.run_until_drained()
    assert stats["completed"] == 2
    assert stats["sectored_waves"] > 0
    assert stats["merged_slots"] > 0
    assert handles[0].peek() == handles[1].peek()  # identical prompts


# -- paged-KV admission ------------------------------------------------------


def test_paged_admission_same_quantum_shares_wave():
    """Prompts of different raw length but the same page quantum produce
    identically shaped states and share one vectorized wave."""
    sess = ServeSession(_fake_backend(quantum=4), max_batch=4,
                        scheduler=OverlapScheduler())
    sess.submit(Request(0, np.arange(3, dtype=np.int32), max_new_tokens=3))
    sess.submit(Request(1, np.arange(4, dtype=np.int32), max_new_tokens=3))
    sess.step()
    assert sess.active_slots() == [0, 1]  # both admitted to the same wave
    sess.run_until_drained()
    assert sess.stats["completed"] == 2


def test_paged_admission_mixed_quanta_waits_for_drain():
    """A request whose padded state doesn't match the in-flight wave is
    parked by the scheduler and admitted once the wave drains."""
    sess = ServeSession(_fake_backend(quantum=4), max_batch=4,
                        scheduler=OverlapScheduler())
    sess.submit(Request(0, np.arange(3, dtype=np.int32), max_new_tokens=3))
    sess.submit(Request(1, np.arange(4, dtype=np.int32), max_new_tokens=3))
    sess.submit(Request(2, np.arange(6, dtype=np.int32), max_new_tokens=3))
    sess.step()
    # quantum-4 prompts share the wave; the quantum-8 prompt is prefilled
    # but parked (paged-KV admission)
    assert sess.active_slots() == [0, 1]
    assert sess.scheduler.pending() == 1
    sess.run_until_drained()
    assert sess.stats["completed"] == 3
    assert sess.completion_order == [0, 1, 2]


def test_paged_admission_no_starvation_under_steady_load():
    """A parked mismatched-quantum group must not be overtaken forever by
    steady same-quantum traffic: admission is head-of-line, so the wave
    drains and the parked request completes within bounded steps."""
    sess = ServeSession(_fake_backend(quantum=4), max_batch=2,
                        scheduler=OverlapScheduler())
    for rid in range(2):
        sess.submit(Request(rid, np.arange(3, dtype=np.int32),
                            max_new_tokens=6))
    sess.step()  # wave busy with quantum-4 slots
    parked = sess.submit(Request(100, np.arange(6, dtype=np.int32),
                                 max_new_tokens=3))
    for i in range(40):  # steady quantum-4 arrivals while it waits
        sess.submit(Request(200 + i, np.arange(3, dtype=np.int32),
                            max_new_tokens=2))
        sess.step()
        if parked.done:
            break
    assert parked.done, "mismatched-quantum request was starved"


def test_max_new_tokens_one_completes_at_prefill():
    """A quota the prefill token already satisfies finishes at install:
    exactly max_new_tokens tokens, no decode wave burned on the slot."""
    sess = ServeSession(_fake_backend(), max_batch=2)
    h1 = sess.submit(Request(0, np.arange(3, dtype=np.int32),
                             max_new_tokens=1))
    h2 = sess.submit(Request(1, np.arange(3, dtype=np.int32),
                             max_new_tokens=3))
    stats = sess.run_until_drained()
    assert len(h1.peek()) == 1 and h1.done
    assert len(h2.peek()) == 3 and h2.done
    assert stats["completed"] == 2
    # and via the overlap (group-install) path too
    sess2 = ServeSession(_fake_backend(), max_batch=2,
                         scheduler=OverlapScheduler())
    handles = [sess2.submit(Request(r, np.arange(3, dtype=np.int32),
                                    max_new_tokens=1)) for r in range(3)]
    sess2.run_until_drained()
    assert all(len(h.peek()) == 1 and h.done for h in handles)


def test_fifo_mixed_quanta_raises():
    """The FIFO scheduler has no paged admission: installing a mismatched
    state into an in-flight wave is a loud error, not silent corruption."""
    sess = ServeSession(_fake_backend(quantum=4), max_batch=4,
                        scheduler=FifoScheduler())
    sess.submit(Request(0, np.arange(3, dtype=np.int32), max_new_tokens=4))
    sess.step()
    sess.submit(Request(1, np.arange(6, dtype=np.int32), max_new_tokens=4))
    with pytest.raises(ValueError, match="cannot join the in-flight wave"):
        sess.step()


# -- SectorPolicy ------------------------------------------------------------


def test_hysteresis_policy_band_edges():
    """Edge semantics: exactly at the threshold switches ON; exactly at
    (threshold - hysteresis) stays on (strict <); below the band -> off."""
    pol = HysteresisPolicy(min_occupancy=0.5, hysteresis=0.125)
    assert pol.decide(0.499, {}).use_sectored is False  # below: stays off
    assert pol.decide(0.5, {}).use_sectored is True  # exactly at: on
    assert pol.decide(0.375, {}).use_sectored is True  # at thr - hyst: on
    assert pol.decide(0.25, {}).use_sectored is False  # below band: off
    assert pol.decide(0.5, {}).use_sectored is True  # re-arms at threshold


def test_engine_select_path_matches_policy_edges():
    """The legacy Engine._select_path shim exposes the same band edges."""
    dummy = object()
    eng = Engine(dummy, lambda s, t: (s, t), lambda s, t: (s, t),
                 EngineConfig(max_batch=8, sectored_min_occupancy=0.5,
                              sectored_hysteresis=0.125))

    def set_occupancy(n):
        sess = eng.session
        sess.slots = [StreamHandle(sess, Request(i, np.arange(2), 1))
                      if i < n else None for i in range(8)]

    set_occupancy(3)  # 0.375 from the off state: stays off
    assert eng._select_path() is False
    set_occupancy(4)  # exactly at the 0.5 threshold: on
    assert eng._select_path() is True
    set_occupancy(3)  # exactly at threshold - hysteresis: stays on
    assert eng._select_path() is True and eng._sectored_on
    set_occupancy(2)  # 0.25, below the band: off
    assert eng._select_path() is False


def test_hysteresis_policy_stats_passthrough():
    """The session hands its live stats mapping to every decide() call —
    a policy can steer on decode_steps/sectored_waves without extra
    plumbing (AdaptiveSectorPolicy's recorder rides next to this)."""

    class SpyHysteresis(HysteresisPolicy):
        def decide(self, occupancy, stats):
            self.seen_stats = stats
            return super().decide(occupancy, stats)

    policy = SpyHysteresis(min_occupancy=0.5)
    sess = ServeSession(_fake_backend(), max_batch=2, policy=policy)
    for rid in range(3):
        sess.submit(Request(rid, np.arange(4, dtype=np.int32),
                            max_new_tokens=3))
    sess.run_until_drained()
    assert policy.seen_stats is sess.stats  # the live dict, not a copy
    assert policy.seen_stats["decode_steps"] > 0
    # and the base policy treats stats as read-only context
    before = dict(sess.stats)
    HysteresisPolicy().decide(1.0, sess.stats)
    assert sess.stats == before


def test_path_decision_merge_demands_false_reaches_backend_unmerged():
    """A policy can disable the shared-prefix OR-merge per wave:
    merge_demands=False must keep the backend's merge hook un-invoked
    even for same-prefix co-resident requests."""

    class CountingBackend(ServingBackend):
        merge_calls = 0

        def merge_demands(self, stacked_state, group_ids):
            self.merge_calls += 1
            return super().merge_demands(stacked_state, group_ids)

    class FixedPolicy:
        def __init__(self, merge):
            self.merge = merge

        def decide(self, occupancy, stats):
            return PathDecision(use_sectored=True, merge_demands=self.merge)

    def run(policy):
        fake = _fake_backend()
        backend = CountingBackend(fake.prefill_fn, fake.decode_fn,
                                  fake.decode_fn,
                                  demand_merge_fn=lambda s, g: s)
        sess = ServeSession(backend, max_batch=2, policy=policy)
        shared = np.arange(4, dtype=np.int32)
        for rid in range(2):  # identical prompts: same prefix group
            sess.submit(Request(rid, shared.copy(), max_new_tokens=3))
        stats = sess.run_until_drained()
        return backend, stats

    backend, stats = run(FixedPolicy(merge=False))
    assert backend.merge_calls == 0
    assert stats["merged_slots"] == 0
    # control: the default decision (merge_demands=True) does merge
    backend_on, stats_on = run(FixedPolicy(merge=True))
    assert backend_on.merge_calls > 0
    assert stats_on["merged_slots"] > 0


def test_path_decision_topk_frac_respecialises_backend(setup):
    """A PathDecision topk_frac hint gets a per-k jitted sectored step;
    None means the backend default, and variants are cached."""
    cfg, params = setup
    backend = sectored_decode.make_serving_fns(cfg, params=params, seq_len=48,
                                               topk_frac=0.5)
    assert backend.sectored_fn_for(None) is backend.sectored_fn
    wide = backend.sectored_fn_for(1.0)
    assert backend.sectored_fn_for(1.0) is wide  # cached per distinct k
    decision = PathDecision(use_sectored=True, topk_frac=1.0)
    assert decision.merge_demands is True


# -- demand merge: property-based (any slot count, any group labeling) -------
#
# The old example-based cases (one fixed non-contiguous grouping, two fixed
# out-of-range ids) are generalized into properties over arbitrary
# groupings.


def _tables_and_gids(n_slots, raw_gids, seed):
    """Deterministic (S, L, B, H, P) score tables + in-range group ids
    derived from drawn integers."""
    rng = np.random.default_rng(seed)
    tables = rng.random((n_slots, 1, 1, 2, 4)).astype(np.float32)
    gids = np.asarray([raw_gids[i % len(raw_gids)] % n_slots
                       for i in range(n_slots)], np.int32)
    return tables, gids


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=1, max_value=6),
       st.lists(st.integers(min_value=0, max_value=63), min_size=1,
                max_size=6),
       st.integers(min_value=0, max_value=2**16))
def test_pool_demands_merged_is_superset_and_idempotent(n_slots, raw_gids,
                                                        seed):
    """OR-merge properties, for every grouping:

    * superset — each slot's merged demand dominates EVERY member of its
      group element-wise (max == OR on thresholded demand bits), so a
      group fetch can never drop a page a member wanted;
    * members of a group end up with identical demands (one fetch serves
      the group);
    * slots in singleton groups are untouched;
    * idempotent — pooling an already-pooled table is a no-op (bitwise:
      max has no rounding).
    """
    tables, gids = _tables_and_gids(n_slots, raw_gids, seed)
    pooled = np.asarray(sector_predictor.pool_demands(
        jnp.asarray(tables), gids))
    for s in range(n_slots):
        members = [m for m in range(n_slots) if gids[m] == gids[s]]
        for m in members:
            assert (pooled[s] >= tables[m]).all(), (s, m, gids)
        np.testing.assert_array_equal(pooled[s],
                                      tables[members].max(axis=0))
        np.testing.assert_array_equal(pooled[s], pooled[members[0]])
        if members == [s]:
            np.testing.assert_array_equal(pooled[s], tables[s])
    again = np.asarray(sector_predictor.pool_demands(
        jnp.asarray(pooled), gids))
    np.testing.assert_array_equal(again, pooled)  # idempotent, bitwise


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=1, max_value=6),
       st.lists(st.integers(min_value=0, max_value=63), min_size=1,
                max_size=6),
       st.integers(min_value=0, max_value=2**16))
def test_or_merge_demands_pools_table_only(n_slots, raw_gids, seed):
    """or_merge_demands = pool_demands on the table leaf; kv and position
    pass through untouched for every grouping."""
    tables, gids = _tables_and_gids(n_slots, raw_gids, seed)
    kv = jnp.arange(n_slots * 2, dtype=jnp.float32).reshape(n_slots, 2)
    position = jnp.arange(n_slots, dtype=jnp.int32)
    state = sectored_decode.SectoredState(
        kv=kv, table=jnp.asarray(tables), position=position)
    merged = sectored_decode.or_merge_demands(state, gids)
    assert merged.kv is kv
    np.testing.assert_array_equal(np.asarray(merged.position),
                                  np.asarray(position))
    np.testing.assert_array_equal(
        np.asarray(merged.table),
        np.asarray(sector_predictor.pool_demands(jnp.asarray(tables), gids)))


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=1, max_value=6),
       st.integers(min_value=0, max_value=100),
       st.booleans())
def test_pool_demands_rejects_out_of_range_ids(n_slots, offset, negative):
    """Any id outside [0, n_slots) — below or above, by any margin — is a
    loud ValueError (the gather would silently clamp it into demand
    corruption otherwise)."""
    table = jnp.ones((n_slots, 3))
    bad = -1 - offset if negative else n_slots + offset
    gids = np.asarray([0] * (n_slots - 1) + [bad], np.int32)
    with pytest.raises(ValueError, match="group_ids"):
        sector_predictor.pool_demands(table, gids)
    # the all-in-range control keeps passing
    sector_predictor.pool_demands(table, np.zeros(n_slots, np.int32))


# -- legacy shim hygiene -----------------------------------------------------


def test_engine_config_not_shared_across_instances():
    """Regression: the old ``cfg: EngineConfig = EngineConfig()`` default
    was evaluated once and aliased by every engine."""
    f = lambda *a: None  # noqa: E731 - callables never invoked here
    e1, e2 = Engine(f, f), Engine(f, f)
    assert e1.cfg is not e2.cfg
    e1.cfg.max_batch = 99
    assert e2.cfg.max_batch == 8
