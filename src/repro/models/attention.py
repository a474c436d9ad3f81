"""Grouped-query attention with the assigned archs' options (qk-norm, QKV
bias, RoPE variants, sliding window) and both execution paths:

* ``attend``       — full (pre-fill / training) attention, optionally windowed.
* ``decode_attend``— one-token decode against a KV cache, written as explicit
  max/sum softmax so XLA SPMD partitions the KV sequence axis cleanly
  (flash-decoding-style partial softmax + rescale under sharding).
* sectored decode (the paper's technique on TPU) lives in repro.runtime.

All shapes: x (B, S, D); q (B, S, H, hd); kv (B, S, Hkv, hd).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import layers

NEG_INF = -1e30


def init_attention(key, cfg, dtype):
    d, hd = cfg.d_model, cfg.head_dim_
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 5)
    s = d ** -0.5
    p = dict(
        wq=jax.random.normal(ks[0], (d, h, hd), dtype) * s,
        wk=jax.random.normal(ks[1], (d, hkv, hd), dtype) * s,
        wv=jax.random.normal(ks[2], (d, hkv, hd), dtype) * s,
        wo=jax.random.normal(ks[3], (h, hd, d), dtype) * s,
    )
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((hkv, hd), dtype)
        p["bv"] = jnp.zeros((hkv, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def qkv(params, cfg, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.qk_norm:
        q = layers.rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = layers.rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = layers.apply_rope(q, positions, cfg.rope)
    k = layers.apply_rope(k, positions, cfg.rope)
    return q, k, v


def _expand_kv(k, n_heads):
    """(B,S,Hkv,hd) -> (B,S,H,hd) by repeating each kv head H/Hkv times."""
    hkv = k.shape[2]
    rep = n_heads // hkv
    return jnp.repeat(k, rep, axis=2)


def attend(params, cfg, x, positions, window: int = 0):
    """Causal (optionally sliding-window) full attention."""
    B, S, D = x.shape
    q, k, v = qkv(params, cfg, x, positions)
    if getattr(cfg, "blocked_attention", False) and window == 0:
        out = _attend_blocked(cfg, q, k, v, positions)
        return jnp.einsum("bqhk,hkd->bqd", out, params["wo"])
    hd = cfg.head_dim_
    kf = _expand_kv(k, cfg.n_heads)
    vf = _expand_kv(v, cfg.n_heads)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, kf).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    qpos = positions[:, :, None]
    kpos = positions[:, None, :]
    mask = kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqs,bshk->bqhk", w, vf)
    return jnp.einsum("bqhk,hkd->bqd", out, params["wo"])


def _attend_blocked(cfg, q, k, v, positions, block: int = 512):
    """Flash-style blocked causal attention in pure XLA (§Perf opt).

    Streams KV blocks through a lax.scan with running max/sum accumulators:
    no (S x S) score tensor is ever materialized, cutting the memory
    roofline term of training/prefill cells by ~an order of magnitude. The
    math mirrors kernels/flash_attention.py (the Pallas version); this path
    partitions under SPMD.
    """
    B, S, H, hd = q.shape
    rep = H // cfg.n_kv_heads
    qg = q.reshape(B, S, cfg.n_kv_heads, rep, hd)
    nb = S // block
    kb = k.reshape(B, nb, block, cfg.n_kv_heads, hd)
    vb = v.reshape(B, nb, block, cfg.n_kv_heads, hd)
    qpos = positions  # (B, S)

    def body(carry, xs):
        m, l, acc = carry
        kblk, vblk, blk_idx = xs
        s_ = jnp.einsum("bsgrk,bcgk->bsgrc", qg, kblk,
                        preferred_element_type=jnp.float32)
        s_ = s_ * (1.0 / jnp.sqrt(jnp.float32(hd)))
        kpos = blk_idx * block + jnp.arange(block)
        mask = kpos[None, None, :] <= qpos[:, :, None]  # (B,S,block)
        s_ = jnp.where(mask[:, :, None, None, :], s_, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
        p = jnp.exp(s_ - m_new[..., None])
        p = jnp.where(mask[:, :, None, None, :], p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bsgrc,bcgk->bsgrk", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, S, cfg.n_kv_heads, rep), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, cfg.n_kv_heads, rep), jnp.float32)
    a0 = jnp.zeros((B, S, cfg.n_kv_heads, rep, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
         jnp.arange(nb)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, S, H, hd).astype(q.dtype)


@dataclasses.dataclass
class KVCache:
    """Dense decode cache: k/v (B, S_max, Hkv, hd), length (B,)."""

    k: jax.Array
    v: jax.Array
    length: jax.Array  # (B,) int32 current fill


def init_cache(cfg, batch, seq_len, dtype=jnp.bfloat16):
    hkv, hd = cfg.n_kv_heads, cfg.head_dim_
    return KVCache(
        k=jnp.zeros((batch, seq_len, hkv, hd), dtype),
        v=jnp.zeros((batch, seq_len, hkv, hd), dtype),
        length=jnp.zeros((batch,), jnp.int32),
    )


jax.tree_util.register_dataclass(KVCache, ["k", "v", "length"], [])


def append_row(buf, layer, length, row):
    """``buf`` (L, B, Spad, ...) with ``row`` (B, 1, ...) written at
    ``[layer, b, length[b]]`` (a K or V cache, or a latent cache): one row
    per sequence, written in place into the layer loop's carry. A masked
    select over every row gives bitwise the same buffer but reads and
    writes the whole cache; ``mode="drop"`` writes nothing for a
    ``length`` past the buffer, as that select did.

    One append serves one device and a mesh alike. With the page axis
    sharded (``MeshBackend``'s wave, ``sharding.wave_state_shardings``)
    the SPMD partitioner offsets the scatter's index by each shard's
    first row and drops what falls outside it, so the write stays on the
    shard that owns the row and no collective moves the cache: no select
    is needed to keep it local (``tests/test_tpu_compile.py`` compiles
    the sharded wave)."""
    b = jnp.arange(row.shape[0])
    return buf.at[layer, b, length].set(row[:, 0].astype(buf.dtype),
                                        mode="drop")


def decode_attend(params, cfg, x, cache: KVCache, window: int = 0):
    """One new token per sequence against the cache.

    x: (B, 1, D). Returns (out (B,1,D), new_cache). The softmax is written as
    explicit masked max/exp/sum so a KV cache sharded along the sequence axis
    partitions into per-shard partial reductions + small cross-shard
    combines (flash-decoding under SPMD).
    """
    B = x.shape[0]
    pos = cache.length[:, None]  # (B,1) position of the new token
    q, k_new, v_new = qkv(params, cfg, x, pos)
    # Append at position `length` via a one-hot where(): a batched scatter
    # would force the SPMD partitioner to replicate the sharded cache, the
    # masked select keeps every shard local.
    idx = cache.length  # (B,)
    slot = jnp.arange(cache.k.shape[1])[None, :, None, None]  # (1,S,1,1)
    sel = slot == idx[:, None, None, None]
    k = jnp.where(sel, k_new.astype(cache.k.dtype), cache.k)
    v = jnp.where(sel, v_new.astype(cache.v.dtype), cache.v)

    hkv = cfg.n_kv_heads
    rep = cfg.n_heads // hkv
    qg = q[:, 0].reshape(B, hkv, rep, cfg.head_dim_)
    # bf16 operands with f32 accumulation: no materialized f32 cache copy
    scores = jnp.einsum("bgrk,bsgk->bgrs", qg.astype(k.dtype), k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(cfg.head_dim_))
    spos = jnp.arange(k.shape[1])[None, None, None, :]
    valid = spos <= idx[:, None, None, None]
    if window:
        valid &= spos > (idx[:, None, None, None] - window)
    scores = jnp.where(valid, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    e = jnp.where(valid, e, 0.0)
    num = jnp.einsum("bgrs,bsgk->bgrk", e.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    den = jnp.sum(e, axis=-1)[..., None]
    out = (num / jnp.maximum(den, 1e-30)).astype(x.dtype)
    out = out.reshape(B, 1, cfg.n_heads, cfg.head_dim_)
    out = jnp.einsum("bqhk,hkd->bqd", out, params["wo"])
    new_cache = KVCache(k=k, v=v, length=cache.length + 1)
    return out, new_cache
