"""Sectored KV-cache decode — the paper's SA+VBL adapted to TPU serving.

Instead of reading the full KV cache (the 'coarse-grained activation' of a
decode step), each step:

  1. asks the Sector Predictor for the top-K KV *sectors* (token pages) per
     (batch, kv-head) — the sector bits;
  2. gathers only those pages HBM->VMEM — Variable Burst Length: the
     transfer size is K*page_size tokens, not seq_len;
  3. attends over the gathered pages (plus the always-fetched recency pages,
     the LSQ-lookahead analogue);
  4. feeds the observed per-page attention mass back into the predictor —
     the SHT update.

Semantics note (DESIGN.md §2): unlike DRAM sector misses, a skipped KV page
changes the output. This is Quest/H2O-class approximate attention; the
sector predictor makes the approximation principled, and `exact` mode
(sector_topk_frac=1.0) degenerates to dense attention for bitwise parity —
asserted in tests.

The memory-roofline win is K*page/seq_len, reported per cell in
EXPERIMENTS.md §Perf — the TPU equivalent of the paper's channel-byte
savings (Fig. 14's RD/WR reduction).

Serving note: the per-step functions here return raw ``(logits, state)``
— token *selection* is not their concern. A ``ServeSession`` composes
them into the fused wave executable (``serve.backend.make_fused_wave``:
on-device greedy argmax or the ``repro.sample`` stochastic kernel, with
zero-copy token feedback), which is the single- and multi-device default
since the fused-selection pipeline was promoted out of ``MeshBackend``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels import ops, quantized_kv
from repro.models import attention, layers, mla, model, moe
from repro.models.attention import append_row
from repro.parallel import sharding
from repro.runtime import sector_predictor
from repro.serve.backend import ServingBackend

PAGE_SIZE = 128  # tokens per KV sector (one TPU-friendly tile of KV)
TOPK_FRAC = 1 / 8  # fraction of pages fetched (8 sectors -> select 1/8..8/8)
MIN_TOPK = 4
NEG_INF = -1e30


def n_pages(seq_len: int) -> int:
    return (seq_len + PAGE_SIZE - 1) // PAGE_SIZE


def topk_for(seq_len: int, frac: float = TOPK_FRAC,
             min_topk: int = MIN_TOPK) -> int:
    """Pages a fraction resolves to, floored at ``min_topk``.

    The default floor (4 pages) is a quality guard for production serving;
    energy studies that sweep the fetch budget to the bottom of the range
    (``benchmarks/serve_energy.py``) lower it explicitly."""
    return max(int(n_pages(seq_len) * frac), min_topk, 1)


@dataclasses.dataclass
class SectoredState:
    # stacked attention.KVCache (L, B, Spad, Hkv, hd), or for latent
    # attention an mla.LatentCache (L, B, Spad, mla.row_width(cfg))
    kv: Any
    table: jax.Array  # (L, B, Hkv, P) sector-history table
    position: jax.Array  # (B,)
    # held-expert layers: each held expert's token count of the last step,
    # (L_moe, B, held experts); None without them
    experts: Any = None


jax.tree_util.register_dataclass(
    SectoredState, ["kv", "table", "position", "experts"], [])


def init_state(cfg, batch, seq_len, dtype=jnp.bfloat16) -> SectoredState:
    if cfg.n_layers == 0:  # dry-run probe base
        return SectoredState(kv=None, table=jnp.zeros((0,), jnp.float32),
                             position=jnp.zeros((batch,), jnp.int32))
    # page count padded to a multiple of 8 so the token buffer (pages*128)
    # divides every mesh-axis product (<= 512 = 4*128)
    pages = ((n_pages(seq_len + 8) + 7) // 8) * 8
    pad = pages * PAGE_SIZE
    position = jnp.zeros((batch,), jnp.int32)
    if cfg.mla:
        moe_layers = cfg.n_layers - cfg.first_dense_layers
        return SectoredState(
            kv=mla.init_cache(cfg, batch, pad, dtype),
            table=sector_predictor.init_table(cfg.n_layers, batch, 1, pages),
            position=position,
            experts=jnp.zeros((moe_layers, batch, cfg.moe.n_held), jnp.int32))
    cache = attention.init_cache(cfg, batch, pad, dtype)
    kv = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), cache)
    table = sector_predictor.init_table(cfg.n_layers, batch, cfg.n_kv_heads,
                                        pages)
    return SectoredState(kv=kv, table=table, position=position)


def sectored_attend(attn_params, cfg, x, q, k, v, length, table_l,
                    k_pages: int, probe: bool = False,
                    kernel: str = "dispatch"):
    """One-token decode attention over predictor-selected KV sectors.

    x: (B,1,D); ``q`` its query projection; ``k``/``v`` the layer's cache
    (B, Spad, Hkv, hd) with this token's row already written at
    ``length`` (B,), the pre-append lengths. Returns (out, new_table_l).

    ``probe=True`` widens the selection by ONE page chosen round-robin
    over the valid pages (``sector_predictor.probe_page_for``): the probed
    page's true attention mass re-enters the SHT update each visit, so the
    table's scores for narrowly-unfetched pages stay honest instead of
    decaying toward zero (the paper's periodic SHT refresh). Off by
    default — exact mode and direct callers keep bit-exact behaviour; the
    serving backend enables it whenever the budget is genuinely narrow.

    ``kernel`` selects how steps 2–3 execute:

    * ``"dispatch"`` (default) — gather the selected pages, then attend,
      as separate XLA dispatches.
    * ``"fused"`` — ONE Pallas kernel (``ops.sectored_attention_paged``)
      whose scalar-prefetched page indices steer per-page HBM->VMEM DMAs
      straight into the attend (SA+VBL in a single kernel); arithmetic is
      operand-for-operand the dispatch attend, so tokens, logprobs and
      the SHT mass are **bitwise** identical to ``"dispatch"``.
    * ``"fused_q8"`` — the fused kernel over per-sector int8 KV
      (``kernels/quantized_kv.py``): pages are quantized from the bf16
      master cache with per-(sequence, page, kv-head) scales and
      dequantized inside the kernel's f32 accumulate. Tolerance-gated.
    """
    B = x.shape[0]
    hkv, hd = cfg.n_kv_heads, cfg.head_dim_
    rep = cfg.n_heads // hkv
    probe_page = None
    select_k = k_pages
    if probe:
        probe_page = sector_predictor.probe_page_for(length, PAGE_SIZE)
        select_k = k_pages + 1

    share_heads = getattr(cfg, "sector_share_heads", False)
    if share_heads:
        # §Perf: one sector set per sequence (summed head scores). The
        # gather then walks axis 1 of the page-major cache directly — no
        # head-major transpose copy and no per-head cross-shard exchange.
        shared = jnp.sum(table_l, axis=1, keepdims=True)  # (B, 1, P)
        pages1 = sector_predictor.predict_topk(
            shared, length, PAGE_SIZE, select_k,
            probe_page=probe_page)  # (B, 1, K)
        pages = jnp.broadcast_to(pages1, (B, hkv, select_k))
        page_idx = pages1  # singleton head axis: shared sector set
    else:
        # 1. sector bits: predictor top-k pages per (B, Hkv)
        pages = sector_predictor.predict_topk(
            table_l, length, PAGE_SIZE, select_k,
            probe_page=probe_page)  # (B, Hkv, K)
        page_idx = pages

    if kernel != "dispatch":
        return _attend_fused(
            attn_params, cfg, x, q, k, v, length, table_l, page_idx, pages,
            quantized=(kernel == "fused_q8"))

    if share_heads:
        kp = k.reshape(B, -1, PAGE_SIZE, hkv, hd)
        vp = v.reshape(B, -1, PAGE_SIZE, hkv, hd)
        k_g = jnp.take_along_axis(
            kp, pages1[:, 0][..., None, None, None], axis=1
        )  # (B, K, page, Hkv, hd)
        v_g = jnp.take_along_axis(
            vp, pages1[:, 0][..., None, None, None], axis=1)
        k_sel = k_g.transpose(0, 3, 1, 2, 4)  # (B, Hkv, K, page, hd)
        v_sel = v_g.transpose(0, 3, 1, 2, 4)
    else:
        # 2. VBL gather: only the selected pages move (K*PAGE tokens, not S)
        kp = k.reshape(B, -1, PAGE_SIZE, hkv, hd)
        vp = v.reshape(B, -1, PAGE_SIZE, hkv, hd)
        k_sel = jnp.take_along_axis(
            kp.transpose(0, 3, 1, 2, 4),  # (B, Hkv, P, page, hd)
            pages[..., None, None], axis=2
        )  # (B, Hkv, K, page, hd)
        v_sel = jnp.take_along_axis(
            vp.transpose(0, 3, 1, 2, 4), pages[..., None, None], axis=2)

    # 3. attention over the gathered sectors. The arithmetic mirrors
    # attention.decode_attend operand-for-operand (bf16 operands, f32
    # accumulation, same mask/softmax formulation): with every valid page
    # selected (exact mode) the gathered buffer is the dense cache prefix in
    # ascending-page order, so the logits are bit-exact with the dense path.
    qg = q[:, 0].reshape(B, hkv, rep, hd)
    K = pages.shape[-1]
    scores = jnp.einsum("bgrk,bgcpk->bgrcp", qg.astype(k_sel.dtype), k_sel,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    # the K gathered pages form one flat (K * page) softmax axis, as in
    # the fused kernel and the dense decode_attend
    scores = scores.reshape(B, hkv, rep, K * PAGE_SIZE)
    # causal/validity mask on absolute token positions
    tok_pos = pages[..., None] * PAGE_SIZE + jnp.arange(PAGE_SIZE)  # (B,H,K,p)
    valid = (tok_pos <= length[:, None, None, None]).reshape(
        B, hkv, 1, K * PAGE_SIZE)
    scores = jnp.where(valid, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    e = jnp.where(valid, e, 0.0)
    num = jnp.einsum("bgrs,bgsk->bgrk", e.astype(v_sel.dtype),
                     v_sel.reshape(B, hkv, K * PAGE_SIZE, hd),
                     preferred_element_type=jnp.float32)
    den = jnp.sum(e, axis=-1)[..., None]
    out = (num / jnp.maximum(den, 1e-30)).astype(x.dtype)
    out = out.reshape(B, 1, cfg.n_heads, hd)
    out = jnp.einsum("bqhk,hkd->bqd", out, attn_params["wo"])

    # 4. SHT update: per-page attention mass (summed over q-head group)
    mass = jnp.sum(e.reshape(B, hkv, rep, K, PAGE_SIZE), axis=(2, 4)) / (
        jnp.maximum(jnp.sum(e, axis=(2, 3))[..., None], 1e-30))  # (B,Hkv,K)
    return out, sector_predictor.update(table_l, pages, mass)


def _attend_fused(attn_params, cfg, x, q, k, v, length, table_l, page_idx,
                  pages, *, quantized: bool):
    """Steps 2–4 of :func:`sectored_attend` as ONE Pallas kernel.

    ``q`` is the prologue's query projection; ``k``/``v`` the layer's
    post-append caches and ``length`` its pre-append lengths;
    ``page_idx`` the predictor selection as the kernel wants it
    ((B,1,K) in ``sector_share_heads`` mode, (B,Hkv,K) otherwise) and
    ``pages`` the head-broadcast copy the SHT update consumes — identical
    to what the dispatch path feeds it.

    The page-major view is a free reshape (no copy); the kernel's
    scalar-prefetched index steering fetches exactly the selected pages
    HBM->VMEM and masks the newest page's tail at ``length + 1`` valid
    tokens (the count convention of ``kernels/sectored_attention``), which
    is bit-for-bit the dispatch path's ``tok_pos <= length``.
    The unquantized kernel mirrors the dispatch attend op-for-op and this
    epilogue mirrors its tail, so the whole step is bitwise identical.
    """
    B = x.shape[0]
    hkv, hd = cfg.n_kv_heads, cfg.head_dim_
    rep = cfg.n_heads // hkv
    qg = q[:, 0].reshape(B, hkv, rep, hd)
    kp = k.reshape(B, -1, PAGE_SIZE, hkv, hd)
    vp = v.reshape(B, -1, PAGE_SIZE, hkv, hd)
    if quantized:
        kq, k_scale = quantized_kv.quantize_pages(kp)
        vq, v_scale = quantized_kv.quantize_pages(vp)
        out, mass = ops.sectored_attention_paged(
            qg, kq, vq, page_idx, length + 1,
            k_scale=k_scale, v_scale=v_scale)
    else:
        out, mass = ops.sectored_attention_paged(
            qg, kp, vp, page_idx, length + 1)
    out = out.astype(x.dtype).reshape(B, 1, cfg.n_heads, hd)
    out = jnp.einsum("bqhk,hkd->bqd", out, attn_params["wo"])
    return out, sector_predictor.update(table_l, pages, mass)


def latent_sectored_attend(attn_params, cfg, x_dtype, q, rows, length,
                           table_l, k_pages: int, probe: bool = False):
    """One-token latent attention (``mla.latent_attend``) over the layer's
    latent cache ``rows`` (B, Spad, W), this token's row already written
    at ``length`` (B,), the pre-append lengths. The cache is one group, so
    ``table_l`` (B, 1, P) holds each page's mass summed over the heads.

    With every page selected and no probe (exact mode) the rows are read in
    place: no page is gathered and no slice of the cache is copied. Below
    that, the predictor's top-k pages (plus the probe) are gathered first,
    as ``sectored_attend``'s dispatch path does. Returns (out, new
    table_l)."""
    B, S, W = rows.shape
    P = S // PAGE_SIZE
    if k_pages >= P and not probe:
        valid = jnp.arange(S)[None, :] <= length[:, None]
        out, e = mla.latent_attend(attn_params, cfg, x_dtype, q, rows, valid)
        pages = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (B, 1, P))
        K = P
    else:
        probe_page = (sector_predictor.probe_page_for(length, PAGE_SIZE)
                      if probe else None)
        pages = sector_predictor.predict_topk(
            table_l, length, PAGE_SIZE, k_pages + int(probe),
            probe_page=probe_page)  # (B, 1, K)
        K = pages.shape[-1]
        sel = jnp.take_along_axis(
            rows.reshape(B, P, PAGE_SIZE, W),
            pages[:, 0, :, None, None], axis=1)  # (B, K, page, W)
        tok_pos = pages[:, 0, :, None] * PAGE_SIZE + jnp.arange(PAGE_SIZE)
        valid = (tok_pos <= length[:, None, None]).reshape(B, K * PAGE_SIZE)
        out, e = mla.latent_attend(attn_params, cfg, x_dtype, q,
                                   sel.reshape(B, K * PAGE_SIZE, W), valid)
    mass = jnp.sum(e.reshape(B, -1, K, PAGE_SIZE), axis=(1, 3)) / (
        jnp.maximum(jnp.sum(e, axis=(1, 2))[:, None], 1e-30))  # (B, K)
    return out, sector_predictor.update(table_l, pages, mass[:, None])


def _latent_decode_step(params, cfg, state: SectoredState, token,
                        k_pages: int, probe: bool):
    """:func:`sectored_decode_step` for latent attention: the leading
    dense layers, then the held-expert ones (``mla.decode_layers``)."""
    x = layers.embed(params, token)

    def attend(p, q, rows, length, table_l):
        return latent_sectored_attend(p, cfg, x.dtype, q, rows, length,
                                      table_l, k_pages, probe)

    x, kv, table, experts = mla.decode_layers(params, cfg, x, state.kv,
                                              state.table, attend)
    hidden = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = model.logits_fn(params, cfg, hidden)[:, 0, :]
    kv = mla.LatentCache(rows=kv.rows, length=kv.length + 1)
    return logits, SectoredState(kv=kv, table=table,
                                 position=state.position + 1,
                                 experts=experts)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "k_pages", "probe", "kernel"))
def sectored_decode_step(params, cfg, state: SectoredState, token,
                         k_pages: int, probe: bool = False,
                         kernel: str = "dispatch"):
    """Full-model one-token decode with sectored attention per layer.

    Jitted with the configuration static and the weights an argument, so
    a caller that steps token by token compiles once per configuration
    and state shape — not once per call, as an eager ``lax.scan`` over a
    fresh layer-body closure would.

    ``probe`` forwards to :func:`sectored_attend` — default off, so direct
    callers (the exact-mode oracle, mesh factories, prefill scans) keep
    their bit-exact selection; ``SectoredKVBackend`` turns it on for
    genuinely narrow page budgets. ``kernel`` likewise forwards (see
    :func:`sectored_attend`): ``"fused"`` runs the single-Pallas-kernel
    attend (bitwise with ``"dispatch"``), ``"fused_q8"`` adds per-sector
    int8 KV (tolerance-gated). Latent attention (``cfg.mla``) runs
    :func:`_latent_decode_step`, dispatch only."""
    if cfg.mla and cfg.n_layers:
        return _latent_decode_step(params, cfg, state, token, k_pages, probe)
    x = layers.embed(params, token)
    if cfg.n_layers == 0:  # dry-run probe base
        hidden = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        return model.logits_fn(params, cfg, hidden)[:, 0, :], state

    # the stacked cache rides in the loop carry, one buffer that each
    # layer writes its row into; scanned in and out as xs/ys it would be
    # copied whole into a fresh stack every step
    def body(carry, scans):
        x, kv = carry
        lp, table_l, layer = scans
        h = layers.rms_norm(x, lp["norm1"], cfg.norm_eps)
        length = kv.length[layer]  # (B,)
        q, k_new, v_new = attention.qkv(lp["attn"], cfg, h, length[:, None])
        kv = attention.KVCache(k=append_row(kv.k, layer, length, k_new),
                               v=append_row(kv.v, layer, length, v_new),
                               length=kv.length)
        att, table_new = sectored_attend(
            lp["attn"], cfg, h, q, kv.k[layer], kv.v[layer], length,
            table_l, k_pages, probe=probe, kernel=kernel)
        x = x + att
        h = layers.rms_norm(x, lp["norm2"], cfg.norm_eps)
        if cfg.moe:
            x = x + moe.moe_ffn(lp["moe"], cfg, h)
        else:
            x = x + layers.swiglu(lp["mlp"], h)
        return (x, kv), table_new

    (x, kv), table_new = jax.lax.scan(
        body, (x, state.kv),
        (params["layers"], state.table, jnp.arange(cfg.n_layers)))
    hidden = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = model.logits_fn(params, cfg, hidden)[:, 0, :]
    kv = attention.KVCache(k=kv.k, v=kv.v, length=kv.length + 1)
    new = SectoredState(kv=kv, table=table_new,
                        position=state.position + 1)
    return logits, new


def _serving_step(params, state, token, *, cfg, k_pages: int, probe: bool,
                  kernel: str):
    """:func:`sectored_decode_step` in the ``(params, state, token)``
    order the serving backend binds its weights into."""
    return sectored_decode_step(params, cfg, state, token, k_pages,
                                probe=probe, kernel=kernel)


def make_sectored_decode_step(cfg, mesh, *, batch: int, seq_len: int,
                              long_context: bool = False,
                              topk_frac: float = TOPK_FRAC):
    """Factory mirroring train.step.make_decode_step for the sectored path."""
    k_pages = topk_for(seq_len, topk_frac)

    def fn(params, state, token):
        return sectored_decode_step(params, cfg, state, token, k_pages)

    pspec = sharding.param_shardings(
        mesh, jax.eval_shape(lambda: model.init_params(cfg, jax.random.key(0))))
    state_shape = jax.eval_shape(lambda: init_state(cfg, batch, seq_len))
    dp = sharding.data_axes(mesh)
    sspec = sharding.sectored_state_shardings(mesh, state_shape,
                                              long_context=long_context)
    tok_spec = NamedSharding(mesh, P(dp if not long_context else None, None))
    return fn, (pspec, sspec, tok_spec), state_shape


def or_merge_demands(stacked_state: SectoredState, group_ids) -> SectoredState:
    """Shared-prefix sector-demand OR-merge over an engine's stacked states.

    ``stacked_state`` is a SectoredState whose leaves carry a leading slot
    axis (the serving engine's batched pytree); ``group_ids`` (slots,) int
    marks slots whose requests attend the same KV pages (shared prompt
    prefix). Their sector-history scores are pooled (element-wise max ==
    OR on demand bits) before the fetch is issued, so every group member
    predicts the same sector set and one sectored fetch serves the group —
    the paper's LSQ Lookahead merging sector demands of in-flight accesses.
    """
    if stacked_state.kv is None:  # dry-run probe base: nothing to pool
        return stacked_state
    pooled = sector_predictor.pool_demands(stacked_state.table, group_ids)
    return dataclasses.replace(stacked_state, table=pooled)


def unique_fetches(pages, group_ids) -> int:
    """Distinct (group, layer/head, page) sectored fetches a wave issues.

    pages: (slots, Hkv, K) selected page indices per slot; slots in the same
    group fetch from the same KV pool, so duplicates collapse. The merge
    test asserts this shrinks when demands are OR-merged first.
    """
    pages = np.asarray(pages)
    gids = np.asarray(group_ids)
    S, H, K = pages.shape
    seen = {(int(gids[s]), h, int(pages[s, h, k]))
            for s in range(S) for h in range(H) for k in range(K)}
    return len(seen)


class SectoredKVBackend(ServingBackend):
    """DecodeBackend over SectoredState with per-fraction specialization.

    All paths drive SectoredState, so slots migrate freely between the
    dense-equivalent path (exact mode: every valid page selected, logits
    bit-exact with model.decode_step) and the sectored path (predictor
    top-k). A :class:`~repro.serve.policy.PathDecision` carrying a
    ``topk_frac`` hint gets a sectored step jitted for exactly that page
    budget (cached per distinct k), so a SectorPolicy can widen or narrow
    the fetch without rebuilding the backend.

    The per-k steps stay selection-free ``(state, token) -> (logits,
    state)`` callables: the session fuses greedy/sampled token selection
    around them per wave (``serve.backend.fused_select_step``), so one
    compiled sectored step serves every sampler mix.
    """

    KERNELS = ("dispatch", "fused", "fused_q8")

    def __init__(self, cfg, params, *, seq_len: int,
                 topk_frac: float = TOPK_FRAC, min_topk: int = MIN_TOPK,
                 kernel: str = "dispatch"):
        if kernel not in self.KERNELS:
            raise ValueError(f"kernel must be one of {self.KERNELS}; "
                             f"got {kernel!r}")
        if cfg.mla and kernel != "dispatch":
            raise ValueError(
                f"kernel {kernel!r}: the fused kernels attend grouped-query "
                f"K/V pages and have no latent-attention form; "
                f"{cfg.name!r} uses latent attention, serve it with kernel "
                f"'dispatch'")
        self.cfg = cfg
        self.params = params
        self.seq_len = seq_len
        self.topk_frac = topk_frac
        self.min_topk = min_topk
        # how genuinely-sectored steps attend (see sectored_attend): the
        # exact path (k == pages) and prefill always run "dispatch" — they
        # carry the dense-parity and prefix-cache bitwise contracts, and
        # exact mode has no narrowed fetch for a fused kernel to win on
        self.kernel = kernel
        self.pages = ((n_pages(seq_len + 8) + 7) // 8) * 8
        self._k_cache: dict[int, Any] = {}
        self._prefill_cache: dict[int, Any] = {}
        self._suffix_cache: dict[int, Any] = {}
        # jitted single-token steps: compiled once per token shape, so
        # prefill (on the admission critical path) and looped-wave decode
        # don't pay per-op eager dispatch for a full model traversal
        exact_fn = self._step_for(self.pages)  # every page: exact mode
        super().__init__(functools.partial(self._prefill, params), exact_fn,
                         self._step_for(self.k_for(topk_frac)),
                         or_merge_demands, vocab=cfg.vocab)

    def _step_for(self, k_pages: int):
        fn = self._k_cache.get(k_pages)
        if fn is None:
            # genuinely narrow budgets widen by one probe page per wave so
            # the SHT stays honest on long narrow runs; exact mode
            # (k == pages) stays probe-free and bit-exact with dense
            probe = self.probe_pages_for(k_pages) > 0
            kernel = self.kernel if 0 < k_pages < self.pages else "dispatch"
            # weights bound outside jit: a wave that vmaps this step
            # passes them to its own jit as an argument (split_bound)
            fn = functools.partial(_serving_step, self.params,
                                   cfg=self.cfg, k_pages=k_pages,
                                   probe=probe, kernel=kernel)
            self._k_cache[k_pages] = fn
        return fn

    def probe_pages_for(self, k_pages: int) -> int:
        """Extra probe pages a sectored step at this budget fetches per
        wave (0 in exact mode) — the number the telemetry meter adds to
        its per-slot fetch accounting."""
        return 1 if 0 < k_pages < self.pages else 0

    def k_for(self, topk_frac: float | None = None) -> int:
        """Concrete page budget a policy fraction resolves to — the number
        the telemetry meter charges fetch energy for (None = default)."""
        if topk_frac is None:
            topk_frac = self.topk_frac
        return min(topk_for(self.seq_len, topk_frac, self.min_topk),
                   self.pages)

    def kv_geometry(self):
        """Cache layout for :class:`repro.telemetry.meters.WaveMeter`.

        A ``fused_q8`` backend's sectored fetches move int8 words, so the
        geometry carries the bytes-per-word fraction the meter feeds into
        ``kv_fetch_energy`` (prefill and exact/dense waves read the bf16
        master cache and stay at full width)."""
        from repro.telemetry.meters import KVGeometry
        word_fraction = (quantized_kv.kv_word_fraction()
                         if self.kernel == "fused_q8" else 1.0)
        return KVGeometry.from_model_cfg(self.cfg, seq_len=self.seq_len,
                                         page_size=PAGE_SIZE,
                                         total_pages=self.pages,
                                         kv_word_fraction=word_fraction)

    def sectored_fn_for(self, topk_frac: float | None):
        if topk_frac is None:
            return self.sectored_fn
        return self._step_for(self.k_for(topk_frac))

    def _scan_prompt(self, params, state, tokens):
        """Exact-mode steps over a prompt as ONE ``lax.scan`` (the same
        step the dense decode path runs, so prefill numerics are shared
        by every scheduler/policy combination). Returns the last token's
        logits and the state."""
        cfg, k_pages = self.cfg, self.pages
        logits, state = sectored_decode_step(params, cfg, state,
                                             tokens[:, :1], k_pages)

        def body(carry, tok):
            _, state = carry
            return sectored_decode_step(params, cfg, state, tok[:, None],
                                        k_pages), None

        (logits, state), _ = jax.lax.scan(body, (logits, state),
                                          tokens[:, 1:].T)
        return logits, state

    def _prefill(self, params, tokens):
        """Exact-mode prefill of a fresh state, jitted per prompt length
        (the session binds ``params``: ``prefill_fn``)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        fn = self._prefill_cache.get(tokens.shape[1])
        if fn is None:
            fn = jax.jit(lambda params, tokens: self._scan_prompt(
                params, init_state(self.cfg, tokens.shape[0], self.seq_len),
                tokens))
            self._prefill_cache[tokens.shape[1]] = fn
        return fn(params, tokens)

    # -- prefix-cache hooks (serve.prefix.PrefixCache warm admission) ------

    def state_prefix(self, state: SectoredState, n_tokens: int
                     ) -> SectoredState:
        """Donor state truncated to its first ``n_tokens`` — metadata only.

        KV rows for positions < n depend only on those n tokens, and the
        exact-mode attend masks every row >= ``cache.length`` to exactly
        zero before the softmax max (then zeroes ``e`` again), so stale
        rows beyond n are bit-invisible; the row write overwrites row n
        next. JAX arrays are immutable, so aliasing the donor's
        k/v buffers is safe — only the length/position leaves change.
        The sector-history table is carried as-is: ``predict_topk`` at
        k = all pages returns every page in ascending order regardless
        of table content, so the exact path is table-independent (the
        sectored top-k path shares the cached table's history, the same
        approximation the within-wave OR-merge already makes).
        """
        n = int(n_tokens)
        kv = state.kv
        new_kv = dataclasses.replace(kv, length=jnp.full_like(kv.length, n))
        return dataclasses.replace(state, kv=new_kv,
                                   position=jnp.full_like(state.position, n))

    def suffix_prefill(self, state: SectoredState, tokens):
        """Resume exact-mode prefill from a seeded state (warm admission:
        only the un-matched prompt suffix is re-prefilled).

        Same scan as :meth:`_prefill` — the exact-mode decode step — but
        starting from ``state`` instead of a fresh one, so (seed at n) +
        (suffix scan) is bitwise the cold full prefill. Jitted per suffix
        length.
        """
        tokens = jnp.asarray(tokens, jnp.int32)
        fn = self._suffix_cache.get(tokens.shape[1])
        if fn is None:
            fn = jax.jit(self._scan_prompt)
            self._suffix_cache[tokens.shape[1]] = fn
        return fn(self.params, state, tokens)


def make_serving_fns(cfg, *, params, seq_len: int,
                     topk_frac: float = TOPK_FRAC,
                     min_topk: int = MIN_TOPK,
                     kernel: str = "dispatch") -> SectoredKVBackend:
    """Build the SectoredState serving backend.

    Returns a :class:`SectoredKVBackend`; it still unpacks as the legacy
    ``(prefill_fn, exact_fn, sectored_fn, merge_fn)`` 4-tuple for
    pre-redesign call sites. ``kernel`` selects the sectored decode
    flavor ("dispatch" | "fused" | "fused_q8" — see
    :func:`sectored_attend`).
    """
    return SectoredKVBackend(cfg, params, seq_len=seq_len,
                             topk_frac=topk_frac, min_topk=min_topk,
                             kernel=kernel)


def bytes_saved_fraction(seq_len: int, topk_frac: float = TOPK_FRAC) -> float:
    """The paper's headline metric on TPU: fraction of KV bytes NOT moved."""
    k = topk_for(seq_len, topk_frac)
    return 1.0 - k / n_pages(seq_len)
