"""Fused sectored-decode kernel contracts (tentpole oracle).

Three layers of guarantees, each asserted here:

* **Kernel vs reference, bitwise** — ``ops.sectored_attention`` (and the
  serving-layout ``sectored_attention_paged``) in interpret mode must be
  bit-identical to the *jitted* jnp oracle. The jitted oracle is the
  contract target deliberately: XLA fuses the eager reference into a
  different float expression tree (last-ulp differences at long lengths),
  while every production caller — dispatch attend, serving steps, prefill
  scans — runs under ``jax.jit``. ``test_ref_jit_is_the_bitwise_target``
  pins this down so nobody "fixes" the oracle back to eager.
* **Fused vs dispatch serving step, bitwise** — ``sectored_decode_step``
  with ``kernel="fused"`` must produce bit-identical logits, SHT tables,
  and KV caches to ``kernel="dispatch"``, per step and chained, and the
  full session (tokens / logprobs / joules) must be invariant across the
  {fifo, overlap} x {unbounded, preempting pool} matrix.
* **Quantized tolerance** — ``kernel="fused_q8"`` is gated by a logprob
  max-abs-err bound (``Q8_LOGPROB_TOL``) against the f32 dispatch path
  under teacher forcing, never by bitwise equality.

Kernel-boundary bugfix regressions ride along: the validity mask's count
convention at page edges (``k*page - 1 / k*page / k*page + 1``), the
interpret-mode auto-detect default (compiled on TPU), and loud
``page_idx`` shape-vs-flag validation for the shared-page-set path.
"""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import backend as kbackend
from repro.kernels import ops, quantized_kv, ref
from repro.models import model
from repro.runtime import sectored_decode
from repro.serve import (AlwaysSectored, FifoScheduler, KVPagePool,
                         OverlapScheduler, Request, ServeSession)
from repro.telemetry import MeteredBackend

PAGE = 128
REF_JIT = jax.jit(ref.sectored_attention_ref)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def make_case(seed, B, Hkv, rep, P, page, hd, K, dtype, *, shared=False,
              lengths=None):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = rand(ks[0], (B, Hkv, rep, hd), dtype)
    kp = rand(ks[1], (B, Hkv, P, page, hd), dtype)
    vp = rand(ks[2], (B, Hkv, P, page, hd), dtype)
    heads = 1 if shared else Hkv
    idx = jax.vmap(lambda k: jax.random.choice(k, P, (K,), replace=False))(
        jax.random.split(ks[3], B * heads)
    ).reshape(B, heads, K).astype(jnp.int32)
    idx = jnp.sort(idx, axis=-1)  # predictor emits ascending pages
    if lengths is None:
        length = jax.random.randint(ks[4], (B,), 1, P * page + 1, jnp.int32)
    else:
        length = jnp.asarray(lengths, jnp.int32)
    return q, kp, vp, idx, length


# ------------------------------------------------- kernel vs jitted ref


def test_ref_jit_is_the_bitwise_target():
    """Document WHY the oracle is jitted: the eager reference is a
    different XLA program (fusion changes last-ulp rounding at long
    lengths), so eager-vs-jit equality is not part of the contract —
    kernel-vs-jitted-ref equality is."""
    q, kp, vp, idx, length = make_case(0, 2, 2, 4, 8, PAGE, 32, 4,
                                       jnp.float32)
    out = ops.sectored_attention(q, kp, vp, idx, length, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(REF_JIT(q, kp, vp, idx, length)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hkv,rep,P,hd,K", [
    (1, 1, 2, 4, 32, 2),
    (2, 2, 4, 8, 64, 4),
    (1, 2, 2, 4, 32, 4),   # K == P: every page selected (exact mode)
    (2, 1, 8, 8, 32, 3),
])
def test_sectored_attention_bitwise_vs_jitted_ref(B, Hkv, rep, P, hd, K,
                                                  dtype):
    """Property-style sweep: page counts, rep sizes, ragged lengths,
    K < P and K == P — kernel output must be bit-identical to the jitted
    reference, not merely allclose."""
    for seed in range(3):
        q, kp, vp, idx, length = make_case(seed, B, Hkv, rep, P, PAGE, hd,
                                           K, dtype)
        out = ops.sectored_attention(q, kp, vp, idx, length, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(REF_JIT(q, kp, vp, idx, length)))


@pytest.mark.parametrize("edge", [1, 2, 3])
@pytest.mark.parametrize("delta", [-1, 0, +1])
def test_mask_count_convention_at_page_edges(edge, delta):
    """Regression for the off-by-one: ``length`` is a COUNT (positions
    0..length-1 valid, mask ``tok_pos < length``), matching
    ``attention.decode_attend``'s ``spos <= cache.length`` with the new
    token at ``cache.length``. Swept at ``k*page - 1 / k*page /
    k*page + 1`` where the pre-fix ``<=`` leaked one extra token."""
    B, Hkv, rep, P, hd, K = 1, 2, 2, 4, 32, 4
    length = edge * PAGE + delta
    q, kp, vp, idx, _ = make_case(7, B, Hkv, rep, P, PAGE, hd, K,
                                  jnp.float32)
    lengths = jnp.array([length], jnp.int32)
    out = ops.sectored_attention(q, kp, vp, idx, lengths, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(REF_JIT(q, kp, vp, idx, lengths)))
    # semantic half: token at position `length` must be invisible — zero
    # its K/V row and the output cannot change
    pg, off = divmod(length, PAGE)
    if pg < P:
        kp2 = kp.at[:, :, pg, off].set(1e4)
        vp2 = vp.at[:, :, pg, off].set(1e4)
        out2 = ops.sectored_attention(q, kp2, vp2, idx, lengths,
                                      interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_shared_page_set_bitwise():
    """(B, 1, K) page_idx — one sector set per sequence (share-heads /
    demand-merge layout) — is bit-identical to the reference and to the
    explicit per-head broadcast."""
    q, kp, vp, idx1, length = make_case(11, 2, 4, 2, 8, PAGE, 32, 4,
                                        jnp.float32, shared=True)
    out = ops.sectored_attention(q, kp, vp, idx1, length, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(REF_JIT(q, kp, vp, idx1, length)))
    bcast = jnp.broadcast_to(idx1, (2, 4, 4))
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(ops.sectored_attention(q, kp, vp, bcast, length,
                                          interpret=True)))


def test_page_idx_shape_validation_raises():
    """Shape-vs-flag agreement is enforced loudly: a page_idx whose head
    axis is neither 1 nor Hkv would silently steer every head through
    the wrong page schedule."""
    q, kp, vp, idx, length = make_case(13, 1, 4, 2, 8, PAGE, 32, 4,
                                       jnp.float32)
    with pytest.raises(ValueError, match="head axis"):
        ops.sectored_attention(q, kp, vp, idx[:, :2], length,
                               interpret=True)
    with pytest.raises(ValueError, match=r"\(B, Hkv, K\)"):
        ops.sectored_attention(q, kp, vp, idx[:, 0], length,
                               interpret=True)
    qp = jnp.transpose(kp, (0, 2, 3, 1, 4))  # (B,P,page,Hkv,hd) serving
    with pytest.raises(ValueError, match="head axis"):
        ops.sectored_attention_paged(q, qp, qp, idx[:, :2], length,
                                     interpret=True)
    with pytest.raises(ValueError, match="k_scale and v_scale"):
        ops.sectored_attention_paged(q, qp, qp, idx, length,
                                     k_scale=jnp.ones((1, 8, 4)),
                                     interpret=True)


# --------------------------------------- paged (serving) kernel contracts


def _dispatch_formulation(qg, kp_pm, vp_pm, page_idx, length):
    """The dispatch path's gather+attend (sectored_attend steps 2-4),
    reproduced over the page-major cache view: the fused kernel's
    bitwise target, with ``length`` as a count (= cache.length + 1)."""
    B, P, page, Hkv, hd = kp_pm.shape
    pages = jnp.broadcast_to(page_idx, (B, Hkv, page_idx.shape[-1]))
    kh = kp_pm.transpose(0, 3, 1, 2, 4)  # (B, Hkv, P, page, hd)
    vh = vp_pm.transpose(0, 3, 1, 2, 4)
    k_sel = jnp.take_along_axis(kh, pages[..., None, None], axis=2)
    v_sel = jnp.take_along_axis(vh, pages[..., None, None], axis=2)
    K = pages.shape[-1]
    scores = jnp.einsum("bgrk,bgcpk->bgrcp", qg.astype(k_sel.dtype), k_sel,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    scores = scores.reshape(B, Hkv, -1, K * page)
    tok_pos = pages[..., None] * page + jnp.arange(page)
    valid = (tok_pos < length[:, None, None, None]).reshape(B, Hkv, 1, -1)
    scores = jnp.where(valid, scores, ref.NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    e = jnp.where(valid, e, 0.0)
    num = jnp.einsum("bgrs,bgsk->bgrk", e.astype(v_sel.dtype),
                     v_sel.reshape(B, Hkv, K * page, hd),
                     preferred_element_type=jnp.float32)
    den = jnp.sum(e, axis=-1)[..., None]
    out = num / jnp.maximum(den, 1e-30)
    mass = jnp.sum(e.reshape(B, Hkv, -1, K, page), axis=(2, 4)) / jnp.maximum(
        jnp.sum(e, axis=(2, 3))[..., None], 1e-30)
    return out, mass


DISPATCH_JIT = jax.jit(_dispatch_formulation)


@pytest.mark.parametrize("shared", [False, True])
@pytest.mark.parametrize("B,Hkv,rep,P,hd,K", [
    (2, 2, 2, 8, 32, 3),
    (1, 4, 2, 8, 64, 8),   # K == P
])
def test_paged_kernel_bitwise_vs_dispatch_formulation(B, Hkv, rep, P, hd,
                                                      K, shared):
    """The serving kernel (bf16 operands, page-major layout) must match
    the dispatch gather+attend bit-for-bit — output AND the per-page
    attention mass that feeds the SHT update."""
    q, kp, vp, idx, length = make_case(17, B, Hkv, rep, P, PAGE, hd, K,
                                       jnp.bfloat16, shared=shared)
    kp_pm = jnp.transpose(kp, (0, 2, 3, 1, 4))  # head- to page-major
    vp_pm = jnp.transpose(vp, (0, 2, 3, 1, 4))
    out, mass = ops.sectored_attention_paged(q, kp_pm, vp_pm, idx, length,
                                             interpret=True)
    want_out, want_mass = DISPATCH_JIT(q, kp_pm, vp_pm, idx, length)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
    np.testing.assert_array_equal(np.asarray(mass), np.asarray(want_mass))


def test_paged_kernel_quantized_within_tolerance():
    """int8 pages + per-(B, P, Hkv) scales, dequantized in the kernel's
    f32 accumulate: close to the f32 result, never bitwise."""
    q, kp, vp, idx, length = make_case(19, 2, 2, 2, 8, PAGE, 32, 3,
                                       jnp.bfloat16)
    kp_pm = jnp.transpose(kp, (0, 2, 3, 1, 4))
    vp_pm = jnp.transpose(vp, (0, 2, 3, 1, 4))
    kq, ks = quantized_kv.quantize_pages(kp_pm)
    vq, vs = quantized_kv.quantize_pages(vp_pm)
    assert kq.dtype == jnp.int8 and ks.shape == (2, 8, 2)
    out, mass = ops.sectored_attention_paged(
        q, kq, vq, idx, length, k_scale=ks, v_scale=vs, interpret=True)
    want, _ = DISPATCH_JIT(q, kp_pm, vp_pm, idx, length)
    err = np.max(np.abs(np.asarray(out) - np.asarray(want)))
    assert 0 < err < 0.05, err  # differs (int8 is lossy) but tightly
    np.testing.assert_allclose(np.asarray(mass).sum(-1), 1.0, atol=1e-5)


def test_quantize_roundtrip_error_bounded():
    """Symmetric per-sector int8: roundtrip error <= scale/2 = amax/254
    per (sequence, page, kv-head) group."""
    pages = rand(jax.random.key(23), (2, 4, PAGE, 2, 32), jnp.bfloat16)
    q8, scale = quantized_kv.quantize_pages(pages)
    back = quantized_kv.dequantize_pages(q8, scale)
    amax = np.abs(np.asarray(pages, np.float32)).max(axis=(2, 4))
    bound = amax / (2 * quantized_kv.INT8_MAX) + 1e-6
    err = np.abs(np.asarray(back) - np.asarray(pages, np.float32)
                 ).max(axis=(2, 4))
    assert (err <= bound).all()
    assert quantized_kv.kv_word_fraction() == 0.5


# ------------------------------------------------ interpret-mode default


def test_default_interpret_compiled_on_tpu(monkeypatch):
    """Regression for the interpret=True-everywhere default: on a TPU
    backend the kernels must default to compiled Mosaic."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kbackend.default_interpret() is False
    assert kbackend.resolve_interpret(None) is False
    assert kbackend.resolve_interpret(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert kbackend.default_interpret() is True
    assert kbackend.resolve_interpret(False) is False


@pytest.mark.parametrize("fn", [ops.vbl_gather, ops.sectored_attention,
                                ops.sectored_attention_paged,
                                ops.flash_attention])
def test_kernel_wrappers_default_to_auto_interpret(fn):
    """Every public kernel wrapper defaults interpret=None (auto-detect),
    not a hardwired True."""
    assert inspect.signature(fn).parameters["interpret"].default is None


def test_vbl_gather_threads_resolved_interpret(monkeypatch):
    """vbl_gather consults backend.resolve_interpret rather than pinning
    interpret=True: the resolver sees the wrapper's None."""
    seen = []

    def spy(flag):
        seen.append(flag)
        return True  # CPU container: still run the interpreter

    monkeypatch.setattr(kbackend, "resolve_interpret", spy)
    data = jnp.ones((2, 8, 128), jnp.float32)
    masks = jnp.array([0xFF, 0x0F], jnp.uint32)
    out, cnt = ops.vbl_gather(data, masks)
    want, wcnt = ref.vbl_gather_ref(data, masks)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(wcnt))
    assert seen == [None]


@pytest.mark.parametrize("seed", range(4))
def test_vbl_gather_bitwise_sweep(seed):
    """vbl_gather == vbl_gather_ref bitwise (not allclose) over random
    sector masks, including empty and full."""
    rng = np.random.default_rng(seed)
    data = jnp.asarray(rng.normal(size=(5, 8, 128)), jnp.float32)
    masks = jnp.asarray(
        np.concatenate([[0x00, 0xFF], rng.integers(0, 256, 3)]), jnp.uint32)
    out, cnt = ops.vbl_gather(data, masks, interpret=True)
    want, wcnt = ref.vbl_gather_ref(data, masks)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(wcnt))


# ------------------------------------- serving step: fused vs dispatch


@pytest.fixture(scope="module")
def setup():
    # The serving-step oracles below compile full 2-layer scan graphs with
    # the interpret-mode kernel inlined; on top of a whole suite's worth of
    # cached executables the XLA CPU compiler can segfault. Shed the
    # accumulated cache before this module's heavy compiles.
    jax.clear_caches()
    cfg = configs.get("yi-6b").reduced(n_layers=2, d_model=64, n_heads=4,
                                       n_kv_heads=2, d_ff=128, vocab=128,
                                       head_dim=32)
    params = model.init_params(cfg, jax.random.key(0))
    return cfg, params


def _prefilled(cfg, params, seq_len, prompt_len, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    state = sectored_decode.init_state(cfg, batch, seq_len)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (batch, prompt_len)),
                         jnp.int32)
    for i in range(prompt_len):
        logits, state = sectored_decode.sectored_decode_step(
            params, cfg, state, tokens[:, i:i + 1], k_pages=8)
    return state, jnp.argmax(logits, -1)[:, None].astype(jnp.int32)


@pytest.mark.slow
@pytest.mark.parametrize("prompt_len", [3, PAGE - 1, PAGE, PAGE + 1])
def test_fused_step_bitwise_with_dispatch(setup, prompt_len):
    """The whole serving step — logits, SHT table, KV cache — is bitwise
    invariant to the kernel flavor, chained over several tokens, and at
    the page-edge cache lengths where the mask bug lived (the appended
    token sits AT cache.length: dispatch masks ``tok_pos <= length``,
    fused passes count ``length + 1``)."""
    cfg, params = setup
    state_d, tok = _prefilled(cfg, params, seq_len=384,
                              prompt_len=prompt_len)
    state_f = state_d
    for _ in range(3):
        ld, state_d = sectored_decode.sectored_decode_step(
            params, cfg, state_d, tok, k_pages=2, kernel="dispatch")
        lf, state_f = sectored_decode.sectored_decode_step(
            params, cfg, state_f, tok, k_pages=2, kernel="fused")
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lf))
        np.testing.assert_array_equal(np.asarray(state_d.table),
                                      np.asarray(state_f.table))
        np.testing.assert_array_equal(np.asarray(state_d.kv.k),
                                      np.asarray(state_f.kv.k))
        tok = jnp.argmax(ld, -1)[:, None].astype(jnp.int32)


def test_eager_steps_compile_once(setup):
    """Regression for the page-edge failure at prompt length PAGE + 1:
    stepping token by token outside ``jit`` re-traced and re-compiled the
    layer scan on every call (a fresh body closure each time), and the
    hundreds of compiles that ``_prefilled`` piles up crashed the XLA CPU
    compiler on the longest prompt. The step is jitted with its
    configuration static, so a token-by-token loop compiles once."""
    cfg, params = setup
    state = sectored_decode.init_state(cfg, 1, 384)
    token = jnp.zeros((1, 1), jnp.int32)
    step = sectored_decode.sectored_decode_step
    _, state = step(params, cfg, state, token, k_pages=8)
    compiled = step._cache_size()
    for _ in range(4):
        _, state = step(params, cfg, state, token, k_pages=8)
    assert step._cache_size() == compiled
    assert int(state.position[0]) == 5


@pytest.mark.slow
def test_fused_step_bitwise_share_heads(setup):
    """sector_share_heads mode feeds the kernel a (B, 1, K) shared page
    set; the step must stay bitwise with dispatch there too."""
    cfg, params = setup
    cfg = dataclasses.replace(cfg, sector_share_heads=True)
    state, tok = _prefilled(cfg, params, seq_len=384, prompt_len=5)
    ld, sd = sectored_decode.sectored_decode_step(
        params, cfg, state, tok, k_pages=2, kernel="dispatch")
    lf, sf = sectored_decode.sectored_decode_step(
        params, cfg, state, tok, k_pages=2, kernel="fused")
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lf))
    np.testing.assert_array_equal(np.asarray(sd.table), np.asarray(sf.table))


Q8_LOGPROB_TOL = quantized_kv.LOGPROB_TOL  # the documented tolerance


@pytest.mark.slow
def test_quantized_step_within_logprob_tolerance(setup):
    """fused_q8 under teacher forcing: per-step logprob max-abs-err vs
    the f32 dispatch path stays inside the documented tolerance — and is
    nonzero, so the oracle cannot pass vacuously."""
    cfg, params = setup
    state_d, tok = _prefilled(cfg, params, seq_len=384, prompt_len=5)
    state_q = state_d
    worst = 0.0
    for _ in range(4):
        ld, state_d = sectored_decode.sectored_decode_step(
            params, cfg, state_d, tok, k_pages=2, kernel="dispatch")
        lq, state_q = sectored_decode.sectored_decode_step(
            params, cfg, state_q, tok, k_pages=2, kernel="fused_q8")
        err = np.max(np.abs(np.asarray(jax.nn.log_softmax(ld))
                            - np.asarray(jax.nn.log_softmax(lq))))
        worst = max(worst, float(err))
        tok = jnp.argmax(ld, -1)[:, None].astype(jnp.int32)  # teacher force
    assert 0 < worst <= Q8_LOGPROB_TOL, worst


# --------------------------------------- session matrix: fused invariance


def _run_session(cfg, backend, scheduler, pool_pages):
    sched = OverlapScheduler() if scheduler == "overlap" else FifoScheduler()
    pool = (None if pool_pages is None
            else KVPagePool(pool_pages, page_size=16))
    sess = ServeSession(MeteredBackend(backend), max_batch=2,
                        scheduler=sched, policy=AlwaysSectored(),
                        page_pool=pool)
    rng = np.random.default_rng(3)
    handles = [sess.submit(Request(
        rid, rng.integers(0, cfg.vocab, size=12).astype(np.int32),
        max_new_tokens=6)) for rid in range(4)]
    stats = sess.run_until_drained()
    return dict(
        tokens={h.rid: tuple(h.peek()) for h in handles},
        logprobs={h.rid: tuple(h.logprobs()) for h in handles},
        joules={h.rid: h.energy_j for h in handles},
        preemptions=stats["preemptions"],
    )


@pytest.mark.slow
def test_session_matrix_fused_invariant(setup):
    """The serving oracle: across {fifo, overlap} x {unbounded, small
    preempting pool}, a fused-kernel backend serves bit-identical
    tokens, logprobs, AND joules to the dispatch backend."""
    cfg, params = setup
    backends = {k: sectored_decode.make_serving_fns(
        cfg, params=params, seq_len=256, min_topk=1, kernel=k)
        for k in ("dispatch", "fused")}
    preempted = False
    for scheduler in ("fifo", "overlap"):
        for pool in (None, 3):
            legs = {k: _run_session(cfg, b, scheduler, pool)
                    for k, b in backends.items()}
            name = f"{scheduler}/{pool}"
            assert legs["fused"]["tokens"] == legs["dispatch"]["tokens"], name
            assert (legs["fused"]["logprobs"]
                    == legs["dispatch"]["logprobs"]), name
            assert legs["fused"]["joules"] == legs["dispatch"]["joules"], name
            preempted |= legs["dispatch"]["preemptions"] > 0
    assert preempted  # the contended legs must actually contend


@pytest.mark.slow
def test_session_quantized_saves_energy(setup):
    """fused_q8 serving: strictly lower metered joules than dispatch on
    the same workload (int8 reads halve the bytes per fetched word), and
    the geometry advertises the word fraction the meter charged."""
    cfg, params = setup
    runs = {}
    for k in ("dispatch", "fused_q8"):
        b = sectored_decode.make_serving_fns(cfg, params=params, seq_len=256,
                                             min_topk=1, kernel=k)
        runs[k] = _run_session(cfg, b, "fifo", None)
    q8 = sectored_decode.make_serving_fns(cfg, params=params, seq_len=256,
                                          min_topk=1, kernel="fused_q8")
    assert q8.kv_geometry().kv_word_fraction == 0.5
    total = {k: sum(r["joules"].values()) for k, r in runs.items()}
    assert total["fused_q8"] < total["dispatch"]


def test_backend_rejects_unknown_kernel(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="kernel"):
        sectored_decode.make_serving_fns(cfg, params=params, seq_len=256,
                                         kernel="mosaic")
