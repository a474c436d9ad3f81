"""Mixture-of-Experts layer (top-k routing, expert-parallel friendly).

Dispatch uses the sort-based grouped formulation: token->expert assignments
are argsorted by expert id, gathered into (E, capacity, d) blocks, pushed
through a batched expert einsum, and combined back with router weights.
Under pjit with the expert axis sharded over "model", XLA SPMD lowers the
gathers into the expected all-to-all exchanges. Capacity overflow drops
tokens (standard capacity-factor semantics); dropped tokens fall back to the
residual path.

``held_moe`` is the dropless layer of a chip that holds a share of the
experts (``MoEConfig.held_first`` / ``held_count``): it routes every token
over all experts, computes the held experts' weighted part and the shared
experts, and has no capacity, so a token's output never depends on what
else is in the batch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CAPACITY_FACTOR = 1.25


def init_moe(key, cfg, dtype):
    d, e = cfg.d_model, cfg.moe
    ks = jax.random.split(key, 5)
    s = d ** -0.5
    n = e.n_held
    p = dict(
        router=jax.random.normal(ks[0], (d, e.n_experts), jnp.float32) * s,
        w_gate=jax.random.normal(ks[1], (n, d, e.d_expert), dtype) * s,
        w_up=jax.random.normal(ks[2], (n, d, e.d_expert), dtype) * s,
        w_down=jax.random.normal(ks[3], (n, e.d_expert, d), dtype)
        * (e.d_expert ** -0.5),
    )
    if e.n_shared_experts:
        from repro.models.layers import init_swiglu

        p["shared"] = init_swiglu(ks[4], d, e.d_expert * e.n_shared_experts,
                                  dtype)
    return p


def moe_ffn(params, cfg, x):
    """x: (B, S, D) -> (B, S, D)."""
    e = cfg.moe
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    gate, idx = jax.lax.top_k(logits, e.top_k)  # (T, k)
    gate = jax.nn.softmax(gate, axis=-1)

    # flatten (token, k) assignments and group by expert via argsort
    flat_expert = idx.reshape(-1)  # (T*k,)
    flat_token = jnp.repeat(jnp.arange(T), e.top_k)
    flat_gate = gate.reshape(-1)
    order = jnp.argsort(flat_expert)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]

    cap = int(T * e.top_k * CAPACITY_FACTOR / e.n_experts) + 1
    # position of each assignment within its expert group
    ones = jnp.ones_like(sorted_expert)
    pos_in_expert = jax.lax.associative_scan(jnp.add, ones) - 1
    seg_start = jnp.searchsorted(sorted_expert, jnp.arange(e.n_experts))
    pos_in_expert = pos_in_expert - seg_start[sorted_expert]
    keep = pos_in_expert < cap

    slot = jnp.where(keep, sorted_expert * cap + pos_in_expert, e.n_experts * cap)
    # scatter tokens into (E*cap + 1 overflow, D)
    buf = jnp.zeros((e.n_experts * cap + 1, D), x.dtype)
    buf = buf.at[slot].set(xt[sorted_token])
    grouped = buf[: e.n_experts * cap].reshape(e.n_experts, cap, D)

    # batched expert FFN (expert axis shardable over "model")
    g = jnp.einsum("ecd,edf->ecf", grouped, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", grouped, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    # combine back: gather each kept assignment's expert output * gate
    y_flat = y.reshape(e.n_experts * cap, D)
    contrib = jnp.where(
        keep[:, None], y_flat[jnp.clip(slot, 0, e.n_experts * cap - 1)], 0.0
    ) * sorted_gate[:, None].astype(x.dtype)
    out = jnp.zeros((T, D), x.dtype).at[sorted_token].add(contrib)

    if e.n_shared_experts:
        from repro.models.layers import swiglu

        out = out + swiglu(params["shared"], xt)
    return out.reshape(B, S, D)


def route(router, cfg, x):
    """Router weights and experts of each token, in float32: x (T, D) ->
    (gate (T, k), idx (T, k)). DeepSeek-V2's greedy routing: softmax over
    every expert, then the top-k scores as they are (not renormalized,
    scale 1)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.moe.top_k)


def held_moe(params, cfg, x):
    """Dropless held-expert layer: x (T, D) -> (y (T, D), counts (T, held)
    int32, how many of each token's top-k picks fall on each held expert).

    ``y = sum over the token's top-k experts e that this chip holds of
    gate_e SwiGLU_e(x) + SwiGLU_shared(x)``. Every held expert is computed
    for every token and weighted by its gate, 0 where the token did not
    pick it: a decode wave reads each held expert's weights once, whatever
    the routing."""
    e = cfg.moe
    gate, idx = route(params["router"], cfg, x)
    picks = (idx - e.held_first)[..., None] == jnp.arange(e.n_held)
    w = jnp.sum(jnp.where(picks, gate[..., None], 0.0), axis=1)  # (T, held)
    g = jnp.einsum("td,edf->tef", x, params["w_gate"])
    u = jnp.einsum("td,edf->tef", x, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    y = jnp.einsum("tef,efd->td", (h * w[..., None]).astype(x.dtype),
                   params["w_down"], preferred_element_type=jnp.float32)
    if e.n_shared_experts:
        from repro.models.layers import swiglu

        y = y + swiglu(params["shared"], x).astype(jnp.float32)
    return y.astype(x.dtype), jnp.sum(picks, axis=1, dtype=jnp.int32)
