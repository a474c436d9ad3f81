"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def sectored_attention_ref(q, k_pages, v_pages, page_idx, length):
    """Decode attention over selected KV sectors.

    q: (B, Hkv, rep, hd) — grouped query heads.
    k_pages/v_pages: (B, Hkv, P, page, hd).
    page_idx: (B, Hkv, K) int32 selected sectors; a singleton head axis
        ((B, 1, K)) shares one sector set across all kv heads.
    length: (B,) int32 **count** of valid tokens (positions 0..length-1
        exist) — the convention of `attention.decode_attend`, where the
        token appended at `cache.length` makes `cache.length + 1` rows
        valid.
    Returns (B, Hkv, rep, hd) float32.
    """
    B, Hkv, P, page, hd = k_pages.shape
    page_idx = jnp.broadcast_to(page_idx,
                                (B, Hkv, page_idx.shape[-1]))
    k_sel = jnp.take_along_axis(
        k_pages, page_idx[..., None, None], axis=2)  # (B,Hkv,K,page,hd)
    v_sel = jnp.take_along_axis(v_pages, page_idx[..., None, None], axis=2)
    K = page_idx.shape[-1]
    scores = jnp.einsum("bgrk,bgcpk->bgrcp", q.astype(jnp.float32),
                        k_sel.astype(jnp.float32)) / jnp.sqrt(jnp.float32(hd))
    # the K selected pages form one flat (K * page) softmax axis
    scores = scores.reshape(B, Hkv, -1, K * page)
    tok_pos = page_idx[..., None] * page + jnp.arange(page)
    valid = (tok_pos < length[:, None, None, None]).reshape(B, Hkv, 1, -1)
    scores = jnp.where(valid, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.where(valid, jnp.exp(scores - m), 0.0)
    num = jnp.einsum("bgrs,bgsk->bgrk", e,
                     v_sel.astype(jnp.float32).reshape(B, Hkv, K * page, hd))
    den = jnp.maximum(jnp.sum(e, axis=-1), 1e-30)
    return num / den[..., None]


def vbl_gather_ref(data, masks):
    """Variable Burst Length compaction.

    data: (N, 8, W) — 8 sectors per row; masks: (N,) uint32 sector bits.
    Returns (out (N, 8, W), counts (N,)): enabled sectors packed at the
    front in sector order (the Read-FIFO skip of §4.2), rest zero.
    """
    N, S, W = data.shape
    bits = ((masks[:, None].astype(jnp.uint32)
             >> jnp.arange(S, dtype=jnp.uint32)) & 1).astype(bool)
    dest = jnp.cumsum(bits, axis=1) - 1  # target slot per enabled sector
    out = jnp.zeros_like(data)
    rows = jnp.arange(N)[:, None]
    dest_safe = jnp.where(bits, dest, S - 1)
    contrib = jnp.where(bits[..., None], data, 0)
    out = out.at[rows, dest_safe].add(contrib)
    # rows where a disabled sector aliased slot S-1 added 0, so this is exact
    return out, jnp.sum(bits, axis=1).astype(jnp.int32)


def flash_attention_ref(q, k, v, causal=True):
    """q,k,v: (B, H, S, hd). Returns (B, H, S, hd) float32."""
    B, H, S, hd = q.shape
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(hd))
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32))
