"""The session's profiler spans and its scanned-token counter.

A tiny session (``bench/tests/tiny.py`` sizes) runs under ``jax.profiler``
on the CPU, which records the host spans (no device ops). Every phase the
chip benchmark reads (``bench/spans.py``, matched by name) appears inside a
``session.step``; the profiler changes no token, logprob or counter; and
``stats["prefill_tokens"]`` counts the prompt tokens the prefill programs
scan, cold, warm and grouped.
"""

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from bench import run, weights
from bench import spans as sp
from bench import trace as tr
from bench.tests import tiny
from repro.launch.serve import build_backend, build_policy
from repro.serve import (FifoScheduler, OverlapScheduler, PrefixCache,
                         Request, ServeSession)


@pytest.fixture(scope="module")
def backend():
    sizes = tiny.spec("yi6b-chat-short").sizes
    return build_backend(run.program_config(sizes),
                         weights.make(sizes, tiny.SEED), true_sectored=True,
                         seq_len=384, kernel="fused")


def _session(backend, policy="sectored", scheduler=None, cache=None):
    return ServeSession(backend, max_batch=4,
                        scheduler=scheduler or FifoScheduler(),
                        policy=build_policy(policy), prefix_cache=cache)


def _prompts(n, length, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, size=length).astype(np.int32)
            for _ in range(n)]


def _serve(session, prompts, new_tokens=4):
    """Drive the session as the benchmark does: every step inside a
    ``session.step`` span."""
    handles = [session.submit(Request(i, p, max_new_tokens=new_tokens))
               for i, p in enumerate(prompts)]
    while not session.idle:
        with TraceAnnotation(tr.STEP):
            session.step()
    return handles


def _traced(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation(tr.WINDOW):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, tr.load(tr.find_xplane(str(tmp_path)))


def test_every_phase_lies_inside_a_step(backend, tmp_path):
    # 6 requests on 4 slots: admissions into a running wave, merged demands
    _, r = _traced(tmp_path,
                   lambda: _serve(_session(backend), _prompts(6, 16)))
    steps = sp.named(r, tr.STEP)
    assert steps
    for name in sp.PHASES:
        spans = sp.named(r, name)
        assert spans, f"no {name!r} span in the trace"
        for _, start, dur in spans:
            assert any(a <= start and start + dur <= a + d
                       for _, a, d in steps), name


def test_profiler_changes_no_token_or_counter(backend, tmp_path):
    prompts = _prompts(6, 16, seed=1)
    off_session = _session(backend)
    off = _serve(off_session, prompts)
    on_session = _session(backend)
    on, _ = _traced(tmp_path, lambda: _serve(on_session, prompts))
    for a, b in zip(off, on):
        assert a.peek() == b.peek()
        assert a.logprobs() == b.logprobs()
    assert off_session.stats == on_session.stats


def test_prefill_tokens_count_what_the_prefills_scan(backend):
    # cold: the whole prompt
    cold = _session(backend, policy="dense")
    _serve(cold, _prompts(1, 40, seed=2))
    assert cold.stats["prefill_tokens"] == 40

    # warm: the prompt less what the prefix cache matched
    cache = PrefixCache(16)
    warm = _session(backend, policy="dense", cache=cache)
    doc = _prompts(1, 300, seed=3)[0]
    _serve(warm, [doc])
    assert warm.stats["prefill_tokens"] == 300
    hits0 = cache.stats["hit_tokens"]
    _serve(warm, [np.concatenate([doc[:256], _prompts(1, 20, seed=4)[0]])])
    matched = cache.stats["hit_tokens"] - hits0
    assert 0 < matched <= 256
    assert warm.stats["prefill_tokens"] == 300 + 276 - matched

    # a vmapped group: the sum of its lengths, in one prefill call
    group = _session(backend, policy="dense", scheduler=OverlapScheduler())
    _serve(group, _prompts(3, 24, seed=5))
    assert group.stats["prefill_calls"] == 1
    assert group.stats["prefill_tokens"] == 3 * 24
