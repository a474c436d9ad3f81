"""Latent attention and held experts (DeepSeek-V2-Lite's blocks) served
through ``ServeSession``, at tiny sizes on the CPU.

The whole path, prefill and decode through the session with prefix-cache
hits, is checked against ``bench/families/mla_moe.py``'s float32 reference
on logits (the gap of each served token's reference logit below the
reference's best). The expert layer's shares add up to the uncut layer;
YaRN's bounds and temperature are the published ones; a sequence decodes
the same alone and in a full batch; and the dispatch path over latent
pages equals exact mode when its pages cover every valid one.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, weights
from bench.tests import tiny
from repro import configs
from repro.models import layers, mla, model, moe
from repro.runtime import sectored_decode
from repro.serve import AlwaysDense, PrefixCache, Request, ServeSession
from repro.telemetry.meters import KVGeometry

CELL = "dsv2lite-doc8k-dense"


@pytest.fixture(scope="module")
def served():
    """The cell's sizes cut to ``bench/tests/tiny.py``'s, its weights from
    the tiny seed, and its exact-mode backend."""
    sizes = tiny.spec(CELL).sizes
    cfg = run.program_config(sizes)
    params = weights.make(sizes, tiny.SEED)
    backend = sectored_decode.SectoredKVBackend(cfg, params, seq_len=384)
    return cfg, params, backend


def _prompts(n, length, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=length).astype(np.int32)
            for _ in range(n)]


def _serve(backend, prompts, *, max_batch=4, new_tokens=6, cache=None):
    session = ServeSession(backend, max_batch=max_batch, policy=AlwaysDense(),
                           prefix_cache=cache)
    handles = [session.submit(Request(i, p, max_new_tokens=new_tokens))
               for i, p in enumerate(prompts)]
    session.run_until_drained()
    return session, handles


# -- the path against the reference ------------------------------------------


def test_served_logits_match_the_float32_reference_and_float8_does_not():
    """The doc cell at tiny size: the shared document prefilled into the
    prefix cache, warm suffix prefills, decode waves of 4 slots. Limit
    ``tiny.LIMITS["gap"]`` = 0.03: sound runs at this size read under 0.01
    (bf16 weights, cache and activations against float32), a wrong token,
    a stale state or the float8 control over 0.05."""
    sessions = []
    result = tiny.run_tiny(CELL, control=True, session_hook=sessions.append)
    assert result["correct"], result["check"]
    assert result["info"]["checked_tokens"] > 20
    assert sessions[0].prefix_cache.stats["hit_tokens"] > 0
    assert not result["control"]["correct"], result["control"]


def _zero_routed(moe_params):
    return dict(moe_params, w_down=jnp.zeros_like(moe_params["w_down"]))


def _shift_held(moe_params):
    # the program's held expert j carries expert j + 1's weights: a held
    # range off by one
    return dict(moe_params, **{n: jnp.roll(moe_params[n], -1, axis=1)
                               for n in ("w_gate", "w_up", "w_down")})


@pytest.mark.parametrize("fault", [_zero_routed, _shift_held],
                         ids=["routed-part-zeroed", "held-range-shifted"])
def test_a_wrong_held_expert_part_reads_not_correct(fault, monkeypatch):
    """The check sees the routed experts: the program served with its held
    experts' part dropped, or with the held range off by one, while the
    reference keeps the true experts, reads over the gap limit."""
    make = weights.make
    calls = []

    def program_faulted(sizes, seed):
        params = make(sizes, seed)
        calls.append(seed)
        if len(calls) > 1:  # the reference's own weights
            return params
        lp = dict(params["layers"], moe=fault(params["layers"]["moe"]))
        return dict(params, layers=lp)

    monkeypatch.setattr(weights, "make", program_faulted)
    result = tiny.run_tiny(CELL)
    assert len(calls) == 2
    assert not result["correct"], result["check"]


def test_renormalized_gates_read_not_correct(monkeypatch):
    """Gates renormalized over the top-k (not DeepSeek-V2's routing) in the
    program alone read over the gap limit."""
    def renormalized(router, cfg, x):
        gate, idx = route(router, cfg, x)
        return gate / gate.sum(axis=-1, keepdims=True), idx

    route = moe.route
    monkeypatch.setattr(moe, "route", renormalized)
    jax.clear_caches()  # retrace the jitted steps with the faulted route
    try:
        result = tiny.run_tiny(CELL)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not result["correct"], result["check"]


def test_fused_kernels_refuse_latent_attention(served):
    cfg, params, _ = served
    for kernel in ("fused", "fused_q8"):
        with pytest.raises(ValueError, match="latent"):
            sectored_decode.SectoredKVBackend(cfg, params, seq_len=384,
                                              kernel=kernel)


# -- the expert layer ----------------------------------------------------------


def _moe_cfg(**held):
    cfg = dataclasses.replace(configs.get("deepseek-v2-lite").reduced(),
                              dtype="float32")
    moe_cfg = dataclasses.replace(cfg.moe, n_experts=8, top_k=3, **held)
    return dataclasses.replace(cfg, moe=moe_cfg)


def test_held_shares_add_up_to_the_uncut_layer():
    """Four chips of two experts each: the sum of their held parts, the
    shared experts counted once, is the layer that holds all eight."""
    whole = _moe_cfg()
    params = moe.init_moe(jax.random.key(0), whole, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (12, whole.d_model))
    shared = layers.swiglu(params["shared"], x)
    with jax.default_matmul_precision("highest"):
        want, counts = moe.held_moe(params, whole, x)
        total = shared
        for first in range(0, 8, 2):
            share = dict(params, **{n: params[n][first:first + 2]
                                    for n in ("w_gate", "w_up", "w_down")})
            got, part = moe.held_moe(
                share, _moe_cfg(held_first=first, held_count=2), x)
            total = total + got - shared
            np.testing.assert_array_equal(part, counts[:, first:first + 2])
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)
    assert (np.asarray(counts).sum(axis=1) == 3).all()


def test_routing_is_softmax_then_greedy_top_k_unnormalized():
    cfg = _moe_cfg()
    router = jax.random.normal(jax.random.key(2), (cfg.d_model, 8))
    x = jax.random.normal(jax.random.key(3), (5, cfg.d_model))
    gate, idx = moe.route(router, cfg, x)
    scores = jax.nn.softmax(np.asarray(x) @ np.asarray(router), axis=-1)
    np.testing.assert_allclose(
        gate, np.take_along_axis(scores, np.asarray(idx), axis=-1),
        rtol=1e-5)
    assert (np.asarray(gate).sum(axis=-1) < 1).all()


# -- YaRN ----------------------------------------------------------------------


def test_yarn_is_deepseek_v2_lites():
    yarn = configs.get("deepseek-v2-lite").yarn
    assert layers.yarn_bounds(64, yarn) == (10, 23)
    assert layers.yarn_mscale(40, 0.707) == pytest.approx(1.2608, abs=1e-4)
    inv = layers.yarn_inv_freq(64, yarn)
    theta = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # pairs below low keep their frequency, those from high on are divided
    # by the factor, and the ramp runs between
    np.testing.assert_allclose(inv[:11], theta[:11])
    np.testing.assert_allclose(inv[23:], theta[23:] / 40)
    r = (16 - 10) / (23 - 10)
    assert inv[16] == pytest.approx(theta[16] * (1 - r) + theta[16] / 40 * r)
    cfg = configs.get("deepseek-v2-lite")
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.2608 ** 2,
                                                   rel=1e-4)


# -- decode ----------------------------------------------------------------------


def test_decode_is_the_same_alone_and_in_a_full_batch(served):
    """Dropless routing and per-slot attention: a request's tokens and
    logprobs do not depend on what else is in its waves."""
    cfg, _, backend = served
    prompts = _prompts(4, 37, cfg.vocab, seed=5)
    _, alone = _serve(backend, prompts[:1])
    _, together = _serve(backend, prompts)
    assert alone[0].peek() == together[0].peek()
    assert alone[0].logprobs() == together[0].logprobs()


def test_wave_logits_of_a_slot_ignore_the_other_slots(served):
    cfg, params, backend = served
    states = [backend.prefill_fn(p[None])[1]
              for p in _prompts(4, 21, cfg.vocab, seed=7)]
    step = jax.jit(jax.vmap(backend.decode_fn))
    tokens = jnp.full((4, 1, 1), 3, jnp.int32)
    full = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    lone = jax.tree.map(lambda *xs: jnp.stack(xs),
                        *([states[0]] + [jax.tree.map(jnp.zeros_like,
                                                      states[0])] * 3))
    np.testing.assert_array_equal(step(full, tokens)[0][0],
                                  step(lone, tokens)[0][0])


def test_dispatch_over_every_valid_page_equals_exact_mode(served):
    """k covering the valid pages gathers them and attends over the
    gathered rows; exact mode reads the whole cache in place."""
    cfg, params, backend = served
    prompt = _prompts(1, 300, cfg.vocab, seed=9)[0]  # 3 valid pages of 4
    _, state = backend.prefill_fn(prompt[None])
    token = jnp.asarray([[11]], jnp.int32)
    valid = 300 // sectored_decode.PAGE_SIZE + 1
    assert valid < backend.pages
    exact = sectored_decode.sectored_decode_step(params, cfg, state, token,
                                                 backend.pages)
    paged = sectored_decode.sectored_decode_step(params, cfg, state, token,
                                                 valid, kernel="dispatch")
    np.testing.assert_allclose(paged[0], exact[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(paged[1].kv.rows, exact[1].kv.rows)
    np.testing.assert_allclose(paged[1].table, exact[1].table, atol=1e-6)


def test_sectored_wave_over_fewer_pages_runs(served):
    """Below every valid page the predictor's pages plus the probe are
    gathered: the step runs and yields finite logits."""
    cfg, _, backend = served
    prompt = _prompts(1, 300, cfg.vocab, seed=9)[0]
    _, state = backend.prefill_fn(prompt[None])
    logits, new = backend._step_for(1)(state, jnp.asarray([[11]], jnp.int32))
    assert np.isfinite(np.asarray(logits)).all()
    assert int(new.position[0]) == 301


# -- counters and geometry ---------------------------------------------------------


def test_session_counts_held_expert_tokens(served):
    """Every expert held (held_count 0): each decoded token lands top_k
    times in each MoE layer. Held experts touched never exceed waves x
    layers x experts, and the attended rows are each step's length + 1."""
    cfg, params, _ = served
    all_held = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, held_first=0, held_count=0))
    sizes = tiny.spec(CELL).sizes
    n = sizes["experts_routed_over"]
    params = dict(params, layers=dict(params["layers"], moe=dict(
        params["layers"]["moe"],
        **{k: jnp.concatenate([v] * (n // v.shape[1]), axis=1)
           for k, v in params["layers"]["moe"].items()
           if k in ("w_gate", "w_up", "w_down")})))
    backend = sectored_decode.SectoredKVBackend(all_held, params, seq_len=384)
    prompts = _prompts(3, 20, cfg.vocab, seed=11)
    session, handles = _serve(backend, prompts, new_tokens=5)
    stats = session.stats
    moe_layers = cfg.n_layers - cfg.first_dense_layers
    assert stats["expert_tokens"] == (stats["decode_steps"] * moe_layers
                                      * cfg.moe.top_k)
    assert 0 < stats["experts_touched"] <= stats["waves"] * moe_layers * n
    assert stats["context_tokens"] == sum(
        20 + j for h in handles for j in range(1, len(h.peek())))


def test_kv_geometry_counts_one_latent_row_a_token():
    cfg = configs.get("deepseek-v2-lite")
    geometry = KVGeometry.from_model_cfg(cfg, seq_len=1024, page_size=128)
    assert geometry.token_kv_bytes == 576 * 2


def test_published_sizes():
    cfg = configs.get("deepseek-v2-lite")
    assert 15.5e9 < cfg.param_count() < 16.0e9  # 15.7B published
    assert cfg.layer_kinds == ("attn",) * 27
    state = jax.eval_shape(lambda: sectored_decode.init_state(cfg, 1, 9344))
    assert state.kv.rows.shape == (27, 1, 10240, 640)
    assert state.table.shape == (27, 1, 1, 80)
    assert state.experts.shape == (26, 1, 64)


def test_model_decode_follows_its_forward():
    """``model.decode_step`` (absorbed, over the latent cache) against
    ``model.forward`` (keys and values formed per head), float32 with a
    float32 cache."""
    cfg = dataclasses.replace(configs.get("deepseek-v2-lite").reduced(),
                              dtype="float32")
    params = model.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 9), 0, cfg.vocab)
    state = model.init_decode_state(cfg, 2, 9, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        for t in range(9):
            logits, state = model.decode_step(params, cfg, state,
                                              tokens[:, t:t + 1])
        want = model.logits_fn(params, cfg,
                               model.forward(params, cfg, tokens))[:, -1]
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-4)
