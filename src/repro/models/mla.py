"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1).

Per layer, from the normed input ``h`` (no query compression):

    q = h W_q                      16 heads x [q_nope 128 | q_pe 64]
    [c | k_pe] = h W_kva           512 + 64; c = RMSNorm(c) with its own scale
    q_pe, k_pe: RoPE (YaRN) at the token's position; k_pe shared by the heads
    k_nope_h = c W_uk,h,  v_h = c W_uv,h        (W_kvb = [W_uk | W_uv])
    score_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) * scale
    out = concat_h(softmax(score_h) v_h) W_o

``scale = qk_dim**-0.5 * m**2`` with YaRN's ``m = 0.1 * mscale_all_dim *
ln(factor) + 1``. The cache holds one row per token, ``[c | k_pe]`` (576
values), read by every head.

Decode runs the absorbed form over that cache: ``q_lat_h = q_nope_h
W_uk,h^T``, scores ``[q_lat_h | q_pe_h] . [c | k_pe]``, and ``out_h = (sum_s
p_s c_s) W_uv,h``, so no per-head key or value is ever formed. ``attend``
(the training and reference-free forward) forms them, as the equations do.

``decode_layers`` runs one token through the whole stack: the leading dense
layers, then the MoE ones (``moe.held_moe``), writing each layer's row into
the stacked latent cache in place.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import layers, moe
from repro.models.attention import NEG_INF, append_row


@dataclasses.dataclass
class LatentCache:
    """Stacked latent cache: rows (L, B, S_pad, row_width(cfg)), one group
    for a sector table, and length (L, B).

    A row is ``[c | k_pe]`` and zeros up to a whole number of 128-lane
    tiles (576 -> 640). A TPU pads a minor axis of 576 to 640 in any case;
    left at 576, the compiler lays the array out tokens-minor and
    transposes the whole cache into and out of every wave."""

    rows: jax.Array
    length: jax.Array


jax.tree_util.register_dataclass(LatentCache, ["rows", "length"], [])


LANES = 128


def row_width(cfg) -> int:
    """A cache row's stored width: ``cache_width`` rounded up to lanes."""
    return -(-cfg.mla.cache_width // LANES) * LANES


def init_cache(cfg, batch, seq_len, dtype=jnp.bfloat16) -> LatentCache:
    L = cfg.n_layers
    return LatentCache(
        rows=jnp.zeros((L, batch, seq_len, row_width(cfg)), dtype),
        length=jnp.zeros((L, batch), jnp.int32))


def _pad_lanes(cfg, x):
    pad = row_width(cfg) - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def init_mla(key, cfg, dtype):
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 4)
    return dict(
        wq=jax.random.normal(ks[0], (d, h, m.qk_dim), dtype) * d ** -0.5,
        wkv_a=jax.random.normal(ks[1], (d, m.cache_width), dtype)
        * d ** -0.5,
        kv_norm=layers.init_rms(m.kv_rank, dtype),
        wkv_b=jax.random.normal(
            ks[2], (m.kv_rank, h, m.qk_nope_dim + m.v_head_dim), dtype)
        * m.kv_rank ** -0.5,
        wo=jax.random.normal(ks[3], (h, m.v_head_dim, d), dtype)
        * (h * m.v_head_dim) ** -0.5,
    )


def softmax_scale(cfg) -> float:
    scale = cfg.mla.qk_dim ** -0.5
    if cfg.yarn is not None:
        scale *= layers.yarn_mscale(cfg.yarn.factor,
                                    cfg.yarn.mscale_all_dim) ** 2
    return scale


def project(p, cfg, x, positions):
    """x (B, S, D) normed -> q_nope (B,S,H,nope), q_pe (B,S,H,rope) roped,
    and the cache rows (B, S, cache_width): the normed latent and the roped
    shared key."""
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_pe = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    kv = jnp.einsum("bsd,dw->bsw", x, p["wkv_a"])
    c = layers.rms_norm(kv[..., :m.kv_rank], p["kv_norm"], cfg.norm_eps)
    q_pe = layers.apply_rope(q_pe, positions, "standard", yarn=cfg.yarn)
    k_pe = layers.apply_rope(kv[:, :, None, m.kv_rank:], positions,
                             "standard", yarn=cfg.yarn)[:, :, 0]
    return q_nope, q_pe, jnp.concatenate([c, k_pe.astype(c.dtype)], axis=-1)


def attend(p, cfg, x, positions):
    """Causal full attention over the sequence, keys and values formed
    per head (the training path)."""
    m = cfg.mla
    q_nope, q_pe, rows = project(p, cfg, x, positions)
    c, k_pe = rows[..., :m.kv_rank], rows[..., m.kv_rank:]
    kvb = jnp.einsum("bsc,chk->bshk", c, p["wkv_b"])
    k_nope, v = kvb[..., :m.qk_nope_dim], kvb[..., m.qk_nope_dim:]
    scores = (jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bsr->bhqs", q_pe, k_pe,
                           preferred_element_type=jnp.float32))
    scores = scores * softmax_scale(cfg)
    mask = positions[:, None, :] <= positions[:, :, None]  # (B, q, s)
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqs,bshk->bqhk", w, v)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"])


def absorb(p, cfg, q_nope, q_pe):
    """Decode query in the latent space: (B, H, nope), (B, H, rope) ->
    (B, H, row_width) = [q_nope_h W_uk,h^T | q_pe_h | 0]."""
    m = cfg.mla
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope, p["wkv_b"][..., :m.qk_nope_dim],
                       preferred_element_type=jnp.float32)
    return _pad_lanes(cfg, jnp.concatenate([q_lat.astype(q_nope.dtype), q_pe],
                                           axis=-1))


def latent_attend(p, cfg, x_dtype, q, rows, valid):
    """One query per sequence over latent rows.

    q (B, H, W); rows (B, S, W), read twice in place (scores, then the
    weighted sum) with bf16 operands and f32 accumulation; valid (B, S).
    Returns (out (B, 1, D), e (B, H, S) the unnormalized weights, zero off
    ``valid``)."""
    m = cfg.mla
    B, H, _ = q.shape
    scores = jnp.einsum("bhw,bsw->bhs", q.astype(rows.dtype), rows,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(valid[:, None, :], scores * softmax_scale(cfg),
                       NEG_INF)
    mx = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.where(valid[:, None, :],
                  jnp.exp(scores - jax.lax.stop_gradient(mx)), 0.0)
    num = jnp.einsum("bhs,bsw->bhw", e.astype(rows.dtype), rows,
                     preferred_element_type=jnp.float32)
    ctx = num[..., :m.kv_rank] / jnp.maximum(jnp.sum(e, -1)[..., None],
                                             1e-30)
    out = jnp.einsum("bhc,chv->bhv", ctx.astype(x_dtype),
                     p["wkv_b"][..., m.qk_nope_dim:],
                     preferred_element_type=jnp.float32).astype(x_dtype)
    return jnp.einsum("bhv,hvd->bd", out, p["wo"])[:, None], e


def decode_layers(params, cfg, x, cache: LatentCache, tables, attend_fn):
    """One token (x (B, 1, D) embedded) through every layer.

    Each layer writes its row ``[c | k_pe]`` at ``length`` into the stacked
    cache, which rides in the loop carry, then calls ``attend_fn(p_attn, q,
    rows_l, length, table_l) -> (att (B,1,D), new table_l)`` with the
    absorbed query and the layer's cache (B, S_pad, W). ``tables``
    (L, B, 1, P) or None is scanned alongside. Returns (x, cache, new
    tables, counts (L_moe, B, held experts) of each held expert's tokens).
    """
    Ld = cfg.first_dense_layers

    def layer(x, cache, lp, table_l, i):
        h = layers.rms_norm(x, lp["norm1"], cfg.norm_eps)
        length = cache.length[i]
        q_nope, q_pe, row = project(lp["attn"], cfg, h, length[:, None])
        rows = append_row(cache.rows, i, length, _pad_lanes(cfg, row))
        cache = LatentCache(rows=rows, length=cache.length)
        q = absorb(lp["attn"], cfg, q_nope[:, 0], q_pe[:, 0])
        att, table_new = attend_fn(lp["attn"], q, rows[i], length, table_l)
        x = x + att
        return x, cache, table_new, layers.rms_norm(x, lp["norm2"],
                                                    cfg.norm_eps)

    def dense(carry, scans):
        x, cache = carry
        lp, table_l, i = scans
        x, cache, table_new, h = layer(x, cache, lp, table_l, i)
        return (x + layers.swiglu(lp["mlp"], h), cache), table_new

    def sparse(carry, scans):
        x, cache = carry
        lp, table_l, i = scans
        x, cache, table_new, h = layer(x, cache, lp, table_l, i)
        y, counts = moe.held_moe(lp["moe"], cfg, h[:, 0])
        return (x + y[:, None], cache), (table_new, counts)

    L = cfg.n_layers
    dense_tables = moe_tables = None
    if tables is not None:
        dense_tables, moe_tables = tables[:Ld], tables[Ld:]
    (x, cache), t_dense = jax.lax.scan(
        dense, (x, cache), (params["dense"], dense_tables, jnp.arange(Ld)))
    (x, cache), (t_moe, counts) = jax.lax.scan(
        sparse, (x, cache),
        (params["layers"], moe_tables, jnp.arange(Ld, L)))
    new_tables = (None if tables is None
                  else jnp.concatenate([t_dense, t_moe], axis=0))
    return x, cache, new_tables, counts
