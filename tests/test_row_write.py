"""The decode step writes each token's K/V row in place.

``sectored_decode_step`` carries the stacked cache through its layer loop
and writes one row per sequence and layer into it
(``sectored_decode.append_row``). The reference kept here is the step
this replaced: each layer's cache scanned in and stacked out, and the new
row appended with a one-hot select over every row of the layer. The two
must agree bitwise — logits, K, V, the sector table, lengths and
positions — for the exact dispatch path and the fused kernel (interpret
mode on the CPU), over lengths that land on the first row of a page and
on the last row of the padded cache, for one step, a prefill scan and the
slot-vmapped wave.

The session's fused wave donates its stacked state, so the rows are
written into the buffer it was given: that buffer is gone after the
wave, and the streams, stops and held-expert counts still equal the
undonated reference wave's (``fuse_wave=False``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention, layers, model
from repro.runtime import sectored_decode
from repro.sample import SamplerRows
from repro.serve import (AlwaysDense, OverlapScheduler, PrefixCache, Request,
                         SamplerSpec, ServeSession)
from repro.serve.backend import fused_select_step, fused_wave_program

SEQ_LEN = 300  # 8 pages of 128: 1024 padded rows per sequence


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get("yi-6b").reduced(n_layers=2, d_model=64, n_heads=4,
                                       n_kv_heads=2, d_ff=128, vocab=128,
                                       head_dim=32)
    params = model.init_params(cfg, jax.random.key(0))
    return cfg, params


def _one_hot_append(cache, length, row):
    """The append the step had before the row write: a masked select over
    every row of the layer's cache."""
    slot = jnp.arange(cache.shape[1])[None, :, None, None]
    sel = slot == length[:, None, None, None]
    return jnp.where(sel, row.astype(cache.dtype), cache)


def _reference_step(params, state, token, *, cfg, k_pages, kernel,
                    probe=False):
    """The step before the row write: each layer's cache is a scan input
    and the new caches a scan output, stacked anew."""
    x = layers.embed(params, token)

    def body(x, scans):
        lp, k, v, length, table_l = scans
        h = layers.rms_norm(x, lp["norm1"], cfg.norm_eps)
        q, k_new, v_new = attention.qkv(lp["attn"], cfg, h, length[:, None])
        k = _one_hot_append(k, length, k_new)
        v = _one_hot_append(v, length, v_new)
        att, table_new = sectored_decode.sectored_attend(
            lp["attn"], cfg, h, q, k, v, length, table_l, k_pages,
            probe=probe, kernel=kernel)
        x = x + att
        h = layers.rms_norm(x, lp["norm2"], cfg.norm_eps)
        x = x + layers.swiglu(lp["mlp"], h)
        return x, (k, v, table_new)

    kv = state.kv
    x, (k, v, table) = jax.lax.scan(
        body, x, (params["layers"], kv.k, kv.v, kv.length, state.table))
    hidden = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = model.logits_fn(params, cfg, hidden)[:, 0, :]
    return logits, sectored_decode.SectoredState(
        kv=attention.KVCache(k=k, v=v, length=kv.length + 1), table=table,
        position=state.position + 1)


def _state(cfg, lengths, seed=0):
    """A state holding random K/V, a random sector table and the given
    per-sequence lengths."""
    base = sectored_decode.init_state(cfg, len(lengths), SEQ_LEN)
    kk, kv_, kt = jax.random.split(jax.random.key(seed), 3)
    shape = base.kv.k.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    kv = attention.KVCache(
        k=jax.random.normal(kk, shape, jnp.float32).astype(base.kv.k.dtype),
        v=jax.random.normal(kv_, shape, jnp.float32).astype(base.kv.v.dtype),
        length=jnp.broadcast_to(lengths, base.kv.length.shape))
    table = jax.random.uniform(kt, base.table.shape, jnp.float32)
    return sectored_decode.SectoredState(kv=kv, table=table,
                                         position=lengths)


def _assert_bitwise(got, want):
    for path, a, b in zip(
            jax.tree_util.tree_flatten_with_path(want)[0],
            jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path[0]))


def _pages(cfg):
    return sectored_decode.SectoredKVBackend(
        cfg, None, seq_len=SEQ_LEN).pages


# the exact path reads every page; the narrow budgets select 4 of 8
PATHS = {"exact": ("dispatch", None), "dispatch": ("dispatch", 4),
         "fused": ("fused", 4)}
# a page's first row (128), a page's last (255), the padded cache's last
# row (1023) and a fresh sequence (0)
LENGTHS = [0, 128, 255, 1023]


def _path(cfg, name):
    kernel, k = PATHS[name]
    return kernel, (_pages(cfg) if k is None else k)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_step_matches_one_hot_append(setup, path):
    cfg, params = setup
    kernel, k_pages = _path(cfg, path)
    state = _state(cfg, LENGTHS)
    assert state.kv.k.shape[2] - 1 == LENGTHS[-1]
    token = jnp.asarray([[3], [17], [64], [127]], jnp.int32)
    # narrow budgets probe one page more, as the serving backend runs them
    probe = k_pages < _pages(cfg)
    got = sectored_decode.sectored_decode_step(params, cfg, state, token,
                                               k_pages, probe=probe,
                                               kernel=kernel)
    want = jax.jit(functools.partial(
        _reference_step, cfg=cfg, k_pages=k_pages, kernel=kernel,
        probe=probe))(params, state, token)
    _assert_bitwise(got, want)
    # the rows really were written: each sequence's row at its length
    k_new = np.asarray(got[1].kv.k)
    k_old = np.asarray(state.kv.k)
    for b, n in enumerate(LENGTHS):
        assert not np.array_equal(k_new[:, b, n], k_old[:, b, n])
        np.testing.assert_array_equal(np.delete(k_new[:, b], n, axis=1),
                                      np.delete(k_old[:, b], n, axis=1))


def test_prefill_scan_matches_one_hot_append(setup):
    """The warm prefill program (``suffix_prefill``: a scan of the exact
    step over the prompt) from lengths that cross a page boundary and run
    past the padded cache's last row, where both appends write nothing."""
    cfg, params = setup
    backend = sectored_decode.make_serving_fns(cfg, params=params,
                                               seq_len=SEQ_LEN)
    state = _state(cfg, [124, 1020], seed=1)
    tokens = jnp.asarray(np.arange(12).reshape(2, 6) * 7 % cfg.vocab,
                         jnp.int32)
    got = backend.suffix_prefill(state, tokens)
    step = functools.partial(_reference_step, cfg=cfg,
                             k_pages=backend.pages, kernel="dispatch")

    def scan(params, state, tokens):
        logits, state = step(params, state, tokens[:, :1])

        def body(carry, tok):
            return step(params, carry[1], tok[:, None]), None

        return jax.lax.scan(body, (logits, state), tokens[:, 1:].T)[0]

    _assert_bitwise(got, jax.jit(scan)(params, state, tokens))
    assert np.asarray(got[1].kv.length).tolist() == [[130, 1026]] * 2


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("path", ["exact", "fused"])
def test_wave_matches_one_hot_append(setup, path, sampled):
    """The session's slot-vmapped wave against the vmapped reference."""
    cfg, params = setup
    kernel, k_pages = _path(cfg, path)
    backend = sectored_decode.make_serving_fns(cfg, params=params,
                                               seq_len=SEQ_LEN, kernel=kernel)
    step = backend._step_for(k_pages)
    slots = len(LENGTHS)
    # one B=1 row per sequence, stacked on a leading slot axis as the
    # session holds them: (slots, L, 1, ...) leaves, (slots, 1) positions
    stacked = jax.tree.map(
        lambda x: (jnp.moveaxis(x, 1, 0)[:, :, None] if x.ndim > 1
                   else x[:, None]), _state(cfg, LENGTHS, seed=2))
    tokens = jnp.asarray([[[5]], [[9]], [[33]], [[100]]], jnp.int32)
    rows = SamplerRows.from_specs(
        [SamplerSpec(temperature=0.9, seed=7 + s) if sampled else None
         for s in range(slots)], [1] * slots)
    ref_fn = functools.partial(_reference_step, cfg=cfg, k_pages=k_pages,
                               kernel=kernel, probe=step.keywords["probe"])
    want = jax.jit(jax.vmap(fused_select_step(ref_fn, sampled=sampled),
                            in_axes=(None, 0, 0, 0)))(
        (params,), stacked, tokens, rows)
    jitted, bound = fused_wave_program(step, sampled=sampled)
    _assert_bitwise(jitted(bound, stacked, tokens, rows), want)


def _doc_requests(cfg, stops=None):
    """A shared document prefix, greedy and sampled questions on it, and
    staggered arrivals so admissions fall between waves. ``stops`` maps a
    request id to its stop token."""
    stops = stops or {}
    doc = (np.arange(40) * 5 % cfg.vocab).astype(np.int32)
    reqs = []
    for rid in range(6):
        suffix = (np.arange(3 + rid % 3) * (rid + 2) % cfg.vocab).astype(
            np.int32)
        spec = (SamplerSpec(temperature=0.8, top_p=0.9, seed=40 + rid)
                if rid % 2 else None)
        reqs.append(Request(rid, np.concatenate([doc, suffix]),
                            max_new_tokens=5 + rid % 4, sampler=spec,
                            stop_tokens=((stops[rid],) if rid in stops
                                         else ())))
    return reqs


def _serve(cfg, params, *, fuse_wave, stops=None):
    backend = sectored_decode.make_serving_fns(cfg, params=params,
                                               seq_len=SEQ_LEN)
    cache = PrefixCache(capacity_pages=64, page_size=16)
    sess = ServeSession(backend, max_batch=3, scheduler=OverlapScheduler(),
                        policy=AlwaysDense(), prefix_cache=cache,
                        fuse_wave=fuse_wave)
    handles = []
    for req in _doc_requests(cfg, stops):
        handles.append(sess.submit(req))
        sess.step()
    sess.run_until_drained()
    streams = {h.rid: (tuple(h.peek()), tuple(h.logprobs()))
               for h in handles}
    return streams, sess, cache


def test_session_streams_match_pre_fused_wave(setup):
    """Waves with admissions between them, greedy and sampled slots, warm
    prefix admissions: the streams match the pre-fused wave
    (``fuse_wave=False``)."""
    cfg, params = setup
    fused, sess, cache = _serve(cfg, params, fuse_wave=True)
    reference, _, _ = _serve(cfg, params, fuse_wave=False)
    assert fused == reference
    assert sess.stats["waves"] > 0
    assert cache.stats["hits"] > 0  # warm admissions took part


@pytest.mark.parametrize("fuse_wave", [True, False],
                         ids=["fused", "reference"])
def test_wave_consumes_its_input_state(setup, fuse_wave):
    """The fused wave donates the session's stacked state: after a step
    with no admission, every leaf of the state it started from is
    deleted, and the session holds the wave's output. The pre-fused
    reference wave donates nothing."""
    cfg, params = setup
    backend = sectored_decode.make_serving_fns(cfg, params=params,
                                               seq_len=SEQ_LEN)
    sess = ServeSession(backend, max_batch=2, policy=AlwaysDense(),
                        fuse_wave=fuse_wave)
    for rid in range(2):
        sess.submit(Request(rid, np.arange(5 + rid, dtype=np.int32),
                            max_new_tokens=6))
    sess.step()  # admits both, then one wave
    before = jax.tree.leaves(sess.batched)
    assert sess.step() == 2  # a wave alone: both slots decode
    assert all(x.is_deleted() == fuse_wave for x in before)
    assert not any(x.is_deleted() for x in jax.tree.leaves(sess.batched))


def test_donated_waves_match_the_reference_with_stops(setup):
    """Admissions during the wave's overlap, warm prefix hits, sampled
    slots, and slots vacated on their stop token, over donated waves:
    the streams equal the pre-fused reference's (``fuse_wave=False``)."""
    cfg, params = setup
    free, _, _ = _serve(cfg, params, fuse_wave=False)
    # a greedy and a sampled request stop on a token they first emit in
    # a wave: the stream's third token or later, not seen before it
    stops = {}
    for rid in (1, 2):
        toks = free[rid][0]
        stops[rid] = next(t for i, t in enumerate(toks)
                          if i >= 2 and t not in toks[:i])
    fused, sess, cache = _serve(cfg, params, fuse_wave=True, stops=stops)
    reference, _, _ = _serve(cfg, params, fuse_wave=False, stops=stops)
    assert fused == reference
    for rid, stop in stops.items():
        toks = fused[rid][0]
        assert toks[-1] == stop and len(toks) < len(free[rid][0])
        assert toks == free[rid][0][:len(toks)]
    assert sess.stats["eos_stops"] == len(stops)
    assert sess.stats["overlapped_prefills"] > 0
    assert cache.stats["hits"] > 0


def test_latent_session_counts_experts_after_donated_waves():
    """DeepSeek-V2-Lite's blocks at tiny sizes: the held-expert counts the
    session reads from the state a donated wave returns equal the
    pre-fused reference's, and so do the streams."""
    from bench import run, weights
    from bench.tests import tiny

    sizes = tiny.spec("dsv2lite-doc8k-dense").sizes
    cfg = run.program_config(sizes)
    params = weights.make(sizes, tiny.SEED)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, size=12 + i).astype(np.int32)
               for i in range(5)]

    def serve(fuse_wave):
        backend = sectored_decode.SectoredKVBackend(cfg, params, seq_len=384)
        sess = ServeSession(backend, max_batch=3, policy=AlwaysDense(),
                            fuse_wave=fuse_wave)
        handles = [sess.submit(Request(i, p, max_new_tokens=5))
                   for i, p in enumerate(prompts)]
        sess.run_until_drained()
        return sess.stats, [tuple(h.peek()) for h in handles]

    (stats, streams), (ref_stats, ref_streams) = serve(True), serve(False)
    assert streams == ref_streams
    assert stats["expert_tokens"] > 0
    assert stats["expert_tokens"] == ref_stats["expert_tokens"]
    assert stats["experts_touched"] == ref_stats["experts_touched"]
