"""Composable LM stack: embeddings -> blocks -> head, for every
architecture of the registry, with three entry points:

* ``forward``  — training / prefill forward pass (scan over uniform layers,
  unrolled for hybrid patterns; latent-attention models scan their leading
  dense layers, then their MoE layers, ``models/mla.py``).
* ``prefill``  — forward + decode-state construction (KV caches / recurrent
  states), returns logits for the last position.
* ``decode_step`` — one-token decode against the decode state.

Everything is pure-function + dict pytrees so the same code lowers under
jax.jit on a 512-device mesh and runs eagerly on CPU for smoke tests.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention, griffin, layers, mla, moe, rwkv


# --- parameter construction ----------------------------------------------------

def _init_attn_layer(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    p = dict(
        norm1=layers.init_rms(cfg.d_model, dtype),
        norm2=layers.init_rms(cfg.d_model, dtype),
        attn=attention.init_attention(k1, cfg, dtype),
    )
    if cfg.moe:
        p["moe"] = moe.init_moe(k2, cfg, dtype)
    else:
        p["mlp"] = layers.init_swiglu(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def _init_rwkv_layer(key, cfg, dtype):
    return dict(
        norm1=layers.init_rms(cfg.d_model, dtype),
        norm2=layers.init_rms(cfg.d_model, dtype),
        tmix=rwkv.init_rwkv(key, cfg, dtype),
    )


def _init_rec_layer(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return dict(
        norm1=layers.init_rms(cfg.d_model, dtype),
        norm2=layers.init_rms(cfg.d_model, dtype),
        rec=griffin.init_rec(k1, cfg, dtype),
        mlp=layers.init_swiglu(k2, cfg.d_model, cfg.d_ff, dtype),
    )


_LAYER_INIT = {"attn": _init_attn_layer, "rwkv": _init_rwkv_layer,
               "rec": _init_rec_layer}


def _init_mla_layer(key, cfg, dtype, dense: bool):
    k1, k2 = jax.random.split(key)
    p = dict(norm1=layers.init_rms(cfg.d_model, dtype),
             norm2=layers.init_rms(cfg.d_model, dtype),
             attn=mla.init_mla(k1, cfg, dtype))
    if dense:
        p["mlp"] = layers.init_swiglu(k2, cfg.d_model, cfg.d_ff, dtype)
    else:
        p["moe"] = moe.init_moe(k2, cfg, dtype)
    return p


def _init_mla_stack(key, cfg, dtype) -> dict:
    """Latent-attention models: the leading dense layers stacked under
    ``dense``, the MoE layers stacked under ``layers``."""
    Ld = cfg.first_dense_layers
    keys = jax.random.split(key, cfg.n_layers)
    return dict(
        dense=jax.vmap(lambda k: _init_mla_layer(k, cfg, dtype, True))(
            keys[:Ld]),
        layers=jax.vmap(lambda k: _init_mla_layer(k, cfg, dtype, False))(
            keys[Ld:]))


def init_params(cfg, key) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    kinds = cfg.layer_kinds
    k_emb, k_layers = jax.random.split(key)
    params: dict[str, Any] = layers.init_embed(
        k_emb, cfg.vocab, cfg.d_model, dtype, cfg.tie_embeddings
    )
    params["final_norm"] = layers.init_rms(cfg.d_model, dtype)
    if cfg.n_layers == 0:  # dry-run probe: base graph without the stack
        params["layers"] = {}
    elif cfg.mla:
        params.update(_init_mla_stack(k_layers, cfg, dtype))
    elif cfg.uniform_layers or cfg.attn_free:
        # stacked params, applied via lax.scan over the leading L axis
        lkeys = jax.random.split(k_layers, cfg.n_layers)
        init_one = _LAYER_INIT[kinds[0]]
        params["layers"] = jax.vmap(lambda k: init_one(k, cfg, dtype))(lkeys)
    else:
        lkeys = jax.random.split(k_layers, cfg.n_layers)
        params["layers"] = [
            _LAYER_INIT[kind](lkeys[i], cfg, dtype)
            for i, kind in enumerate(kinds)
        ]
    return params


def abstract_params(cfg, key=None):
    """ShapeDtypeStruct pytree of the parameters (no allocation)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))


# --- blocks --------------------------------------------------------------------

def _attn_block(lp, cfg, x, positions, window=0):
    h = layers.rms_norm(x, lp["norm1"], cfg.norm_eps)
    x = x + attention.attend(lp["attn"], cfg, h, positions, window=window)
    h = layers.rms_norm(x, lp["norm2"], cfg.norm_eps)
    if cfg.moe:
        x = x + moe.moe_ffn(lp["moe"], cfg, h)
    else:
        x = x + layers.swiglu(lp["mlp"], h)
    return x


def _mla_block(lp, cfg, x, positions):
    h = layers.rms_norm(x, lp["norm1"], cfg.norm_eps)
    x = x + mla.attend(lp["attn"], cfg, h, positions)
    h = layers.rms_norm(x, lp["norm2"], cfg.norm_eps)
    if "mlp" in lp:
        return x + layers.swiglu(lp["mlp"], h)
    y, _ = moe.held_moe(lp["moe"], cfg, h.reshape(-1, h.shape[-1]))
    return x + y.reshape(x.shape)


def _rec_block(lp, cfg, x, h0):
    h = layers.rms_norm(x, lp["norm1"], cfg.norm_eps)
    y, h_last = griffin.rglru(lp["rec"], h, h0)
    x = x + y
    h = layers.rms_norm(x, lp["norm2"], cfg.norm_eps)
    x = x + layers.swiglu(lp["mlp"], h)
    return x, h_last


# --- forward (train / prefill trunk) --------------------------------------------

def forward(params, cfg, tokens=None, *, embeds=None, positions=None,
            remat: bool = False):
    """Trunk: tokens or embeds -> final hidden states (B, S, D)."""
    if embeds is None:
        x = layers.embed(params, tokens)
    else:
        x = embeds
    B, S = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    kinds = cfg.layer_kinds
    if cfg.n_layers == 0:
        pass
    elif cfg.mla:
        def body(x, lp):
            return _mla_block(lp, cfg, x, positions), None
        if remat:
            body = jax.checkpoint(body)
        for stack in ("dense", "layers"):
            x, _ = jax.lax.scan(body, x, params[stack])
    elif cfg.attn_free:
        def body(x, lp):
            state = rwkv.init_state(cfg, B)
            y, _ = rwkv.rwkv_block(lp, cfg, x, state)
            return y, None
        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
    elif cfg.uniform_layers:
        def body(x, lp):
            return _attn_block(lp, cfg, x, positions), None
        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
    else:
        for lp, kind in zip(params["layers"], kinds):
            if kind == "attn":
                x = _attn_block(lp, cfg, x, positions,
                                window=cfg.local_window)
            elif kind == "rec":
                x, _ = _rec_block(lp, cfg, x, griffin.init_rec_state(cfg, B))
            elif kind == "rwkv":
                y, _ = rwkv.rwkv_block(lp, cfg, x, rwkv.init_state(cfg, B))
                x = y
    return layers.rms_norm(x, params["final_norm"], cfg.norm_eps)


def logits_fn(params, cfg, hidden):
    return layers.unembed(params, hidden, cfg.tie_embeddings)


def lm_loss(params, cfg, tokens, labels, remat: bool = False):
    """Causal LM loss: mean cross entropy over all positions.

    The gold-logit term is computed as a masked reduction (iota == label)
    rather than a gather so that vocab-sharded logits reduce shard-locally
    under SPMD — a take_along_axis over a sharded vocab axis would force a
    full all-gather of the logits.
    """
    hidden = forward(params, cfg, tokens, remat=remat)
    logits = logits_fn(params, cfg, hidden).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0),
                   axis=-1)
    return jnp.mean(logz - gold)


# --- decode state ----------------------------------------------------------------

@dataclasses.dataclass
class DecodeState:
    """Per-layer decode state; leaves stacked over layers where uniform."""

    kv: Any  # attention caches (stacked KVCache or list)
    rec: Any  # recurrent states (rwkv dict / rg-lru arrays / None)
    position: jax.Array  # (B,) next position


jax.tree_util.register_dataclass(DecodeState, ["kv", "rec", "position"], [])


def _pad_seq(n: int, mult: int = 1024) -> int:
    """KV buffer length: divisible by every mesh-axis product (<=512)."""
    return ((n + 8 + mult - 1) // mult) * mult


def init_decode_state(cfg, batch, seq_len, dtype=jnp.bfloat16) -> DecodeState:
    kinds = cfg.layer_kinds
    if cfg.n_layers == 0:
        return DecodeState(kv=None, rec=None,
                           position=jnp.zeros((batch,), jnp.int32))
    if cfg.mla:
        return DecodeState(
            kv=mla.init_cache(cfg, batch, _pad_seq(seq_len), dtype),
            rec=None, position=jnp.zeros((batch,), jnp.int32))
    if cfg.attn_free:
        rec = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape),
            rwkv.init_state(cfg, batch),
        )
        return DecodeState(kv=None, rec=rec,
                           position=jnp.zeros((batch,), jnp.int32))
    if cfg.uniform_layers:
        cache = attention.init_cache(cfg, batch, _pad_seq(seq_len), dtype)
        kv = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), cache
        )
        return DecodeState(kv=kv, rec=None,
                           position=jnp.zeros((batch,), jnp.int32))
    # hybrid: list per layer; local-attention layers keep a bounded window
    kv, rec = [], []
    for kind in kinds:
        if kind == "attn":
            w = cfg.local_window or seq_len
            kv.append(attention.init_cache(
                cfg, batch, min(_pad_seq(w, 256), _pad_seq(seq_len)), dtype))
            rec.append(None)
        elif kind == "rec":
            kv.append(None)
            rec.append(griffin.init_rec_state(cfg, batch))
        else:
            kv.append(None)
            rec.append(rwkv.init_state(cfg, batch))
    return DecodeState(kv=kv, rec=rec,
                       position=jnp.zeros((batch,), jnp.int32))


def decode_step(params, cfg, state: DecodeState, token):
    """token (B, 1) int32 -> (logits (B, vocab), new state)."""
    x = layers.embed(params, token)
    B = x.shape[0]
    if cfg.n_layers == 0:
        new = state
    elif cfg.mla:
        def attend(p, q, rows, length, table_l):
            valid = jnp.arange(rows.shape[1])[None] <= length[:, None]
            out, _ = mla.latent_attend(p, cfg, x.dtype, q, rows, valid)
            return out, None
        x, kv, _, _ = mla.decode_layers(params, cfg, x, state.kv, None,
                                        attend)
        kv = mla.LatentCache(rows=kv.rows, length=kv.length + 1)
        new = DecodeState(kv=kv, rec=None, position=state.position + 1)
    elif cfg.attn_free:
        def body(x, scans):
            lp, st = scans
            y, st_new = rwkv.rwkv_block(lp, cfg, x, st)
            return y, st_new
        x, rec_new = jax.lax.scan(body, x, (params["layers"], state.rec))
        new = DecodeState(kv=None, rec=rec_new, position=state.position + 1)
    elif cfg.uniform_layers:
        def body(x, scans):
            lp, cache = scans
            h = layers.rms_norm(x, lp["norm1"], cfg.norm_eps)
            att, cache_new = attention.decode_attend(lp["attn"], cfg, h, cache)
            x = x + att
            h = layers.rms_norm(x, lp["norm2"], cfg.norm_eps)
            if cfg.moe:
                x = x + moe.moe_ffn(lp["moe"], cfg, h)
            else:
                x = x + layers.swiglu(lp["mlp"], h)
            return x, cache_new
        x, kv_new = jax.lax.scan(body, x, (params["layers"], state.kv))
        new = DecodeState(kv=kv_new, rec=None, position=state.position + 1)
    else:
        kv_new, rec_new = [], []
        for i, (lp, kind) in enumerate(zip(params["layers"], cfg.layer_kinds)):
            if kind == "attn":
                h = layers.rms_norm(x, lp["norm1"], cfg.norm_eps)
                att, c = attention.decode_attend(
                    lp["attn"], cfg, h, state.kv[i],
                    window=cfg.local_window)
                x = x + att
                h = layers.rms_norm(x, lp["norm2"], cfg.norm_eps)
                x = x + layers.swiglu(lp["mlp"], h)
                kv_new.append(c)
                rec_new.append(None)
            elif kind == "rec":
                h = layers.rms_norm(x, lp["norm1"], cfg.norm_eps)
                y, hh = griffin.rglru_step(lp["rec"], h, state.rec[i])
                x = x + y
                h = layers.rms_norm(x, lp["norm2"], cfg.norm_eps)
                x = x + layers.swiglu(lp["mlp"], h)
                kv_new.append(None)
                rec_new.append(hh)
            else:
                y, st = rwkv.rwkv_block(lp, cfg, x, state.rec[i])
                x = y
                kv_new.append(None)
                rec_new.append(st)
        new = DecodeState(kv=kv_new, rec=rec_new, position=state.position + 1)
    hidden = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, cfg, hidden)[:, 0, :]
    return logits, new


def prefill(params, cfg, tokens):
    """Run the prompt and return (last-position logits, DecodeState).

    For uniform attention archs the KV cache is built by re-projecting K/V
    (one extra pass over the prompt projections, cheap relative to attention).
    """
    B, S = tokens.shape
    if cfg.mla:
        # the decode step scanned over the prompt: the cache rows are the
        # ones decode writes
        def body(carry, tok):
            _, state = carry
            return decode_step(params, cfg, state, tok[:, None]), None
        init = decode_step(params, cfg, init_decode_state(cfg, B, S),
                           tokens[:, :1])
        (logits, state), _ = jax.lax.scan(body, init, tokens[:, 1:].T)
        return logits, state
    hidden = forward(params, cfg, tokens)
    logits = logits_fn(params, cfg, hidden)[:, -1, :]
    state = init_decode_state(cfg, B, S)
    if state.kv is not None and cfg.uniform_layers and cfg.n_layers:
        x = layers.embed(params, tokens)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        def body(x, scans):
            lp, cache = scans
            h = layers.rms_norm(x, lp["norm1"], cfg.norm_eps)
            _, k, v = attention.qkv(lp["attn"], cfg, h, positions)
            cache = attention.KVCache(
                k=cache.k.at[:, :S].set(k.astype(cache.k.dtype)),
                v=cache.v.at[:, :S].set(v.astype(cache.v.dtype)),
                length=jnp.full((B,), S, jnp.int32),
            )
            x = _attn_block(lp, cfg, x, positions)
            return x, cache
        _, kv = jax.lax.scan(body, x, (params["layers"], state.kv))
        state = DecodeState(kv=kv, rec=state.rec,
                            position=jnp.full((B,), S, jnp.int32))
    else:
        state = DecodeState(kv=state.kv, rec=state.rec,
                            position=jnp.full((B,), S, jnp.int32))
    return logits, state
