"""Multi-head latent attention and routed experts held at a chip's share,
with YaRN RoPE and leading dense layers (DeepSeek-V2-Lite,
arXiv:2405.04434; its HF config.json). Sizes are read under the published
config's keys.

The reference's layer equations, in float32 (``h = rms(x) * g``, eps
``rms_norm_eps``):

    attention: q = h Wq (H x [nope | rope]); [c | k_pe] = h Wkva;
               c = rms(c) * g_kv; q_pe, k_pe: YaRN RoPE (k_pe one for all
               heads); [k_nope_h | v_h] = c Wkvb,h;
               score = (q_nope.k_nope + q_pe.k_pe) * qk_dim**-0.5 * m**2,
               m = 0.1 * mscale_all_dim * ln(factor) + 1; causal softmax;
               x += concat_h(p v_h) Wo
    dense layers (the first ``first_k_dense_replace``):
               x += (silu(h Wg) * h Wu) Wd
    MoE layers: s = softmax(h Wr) over all experts_routed_over experts;
               the greedy top num_experts_per_tok of s, weights s_e (not
               renormalized, times routed_scaling_factor);
               x += sum over picked experts this chip holds of
                    s_e SwiGLU_e(h) + SwiGLU_shared(h)
    logits = (rms(x) * g) Whead^T

Keys and values are formed per head, as the equations have them; the
program decodes in the absorbed form over the latent cache, so the two
share no order of association. The held experts are
``[first_held_expert, first_held_expert + n_routed_experts)``; the
absent experts' part is left out here as in the program.

YaRN over the ``qk_rope_head_dim / 2`` rotary pairs, rotated as halves
(``departures``): ``theta_i = base**(-2i/dim)``, ``d(beta) = dim ln(L /
(2 pi beta)) / (2 ln base)``, ``low = floor(d(beta_fast))``, ``high =
ceil(d(beta_slow))``, ``r_i = clip((i - low) / (high - low), 0, 1)``,
``theta'_i = theta_i (1 - r_i) + theta_i / factor * r_i``; cos and sin
scaled by ``m(mscale) / m(mscale_all_dim)``.

Requests that share a prefix run as one prefix pass plus one pass per
tail, layer by layer, as ``gqa_dense`` does.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import families
from bench.costs import BF16, F32, PAGE
from bench.reference import NEG, Q_CHUNK, f32, mm, rms
from bench.weights import EMBED_SCALE, NORM_SPREAD

KEYS = ("n_layers", "num_hidden_layers", "first_k_dense_replace",
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "kv_lora_rank", "q_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "n_routed_experts", "experts_routed_over", "first_held_expert",
        "n_shared_experts", "num_experts_per_tok", "scoring_func",
        "topk_method", "norm_topk_prob", "routed_scaling_factor",
        "rope_theta", "rope_scaling", "vocab_size", "vocab", "rms_norm_eps",
        "norm_eps", "tie_word_embeddings", "dtype")
TINY = dict(n_layers=3, num_hidden_layers=3, hidden_size=128,
            intermediate_size=256, moe_intermediate_size=64,
            num_attention_heads=4, n_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            head_dim=40, n_routed_experts=2, experts_routed_over=8,
            first_held_expert=2, num_experts_per_tok=3, vocab_size=512,
            vocab=512)


def _widths(s: dict) -> dict:
    return dict(L=s["num_hidden_layers"], Ld=s["first_k_dense_replace"],
                D=s["hidden_size"], F=s["intermediate_size"],
                Fe=s["moe_intermediate_size"], H=s["num_attention_heads"],
                R=s["kv_lora_rank"], nope=s["qk_nope_head_dim"],
                rope=s["qk_rope_head_dim"], dv=s["v_head_dim"],
                E=s["n_routed_experts"], S=s["n_shared_experts"],
                V=s["vocab_size"])


def program_fields(sizes: dict) -> dict:
    """The program's fields; stops where the sizes ask for what the
    program does not run."""
    from repro.configs.base import MLAConfig, MoEConfig, YarnConfig

    s, y = sizes, sizes["rope_scaling"]
    w = _widths(s)
    if (s["n_layers"] != w["L"] or s["vocab"] != w["V"]
            or s["norm_eps"] != s["rms_norm_eps"]
            or s["q_lora_rank"] is not None or y.get("type") != "yarn"
            or float(s["rope_theta"]) != 10000.0
            or s["scoring_func"] != "softmax" or s["topk_method"] != "greedy"
            or s["norm_topk_prob"] or s["routed_scaling_factor"] != 1
            or s["tie_word_embeddings"]
            or not 0 < w["Ld"] < w["L"]
            or s["first_held_expert"] + w["E"] > s["experts_routed_over"]):
        raise SystemExit("the configuration file asks for sizes the "
                         "program cannot run")
    return dict(
        n_layers=w["L"], d_model=w["D"], n_heads=w["H"], n_kv_heads=1,
        head_dim=w["nope"] + w["rope"], d_ff=w["F"], vocab=w["V"],
        rope="standard", qkv_bias=False, qk_norm=False, layer_pattern=(),
        local_window=0, norm_eps=s["rms_norm_eps"], tie_embeddings=False,
        dtype=s["dtype"], first_dense_layers=w["Ld"],
        mla=MLAConfig(kv_rank=w["R"], qk_nope_dim=w["nope"],
                      qk_rope_dim=w["rope"], v_head_dim=w["dv"]),
        yarn=YarnConfig(factor=float(y["factor"]),
                        original_max_positions=int(
                            y["original_max_position_embeddings"]),
                        beta_fast=float(y["beta_fast"]),
                        beta_slow=float(y["beta_slow"]),
                        mscale=float(y["mscale"]),
                        mscale_all_dim=float(y["mscale_all_dim"])),
        moe=MoEConfig(n_experts=s["experts_routed_over"],
                      top_k=s["num_experts_per_tok"], d_expert=w["Fe"],
                      n_shared_experts=w["S"],
                      held_first=s["first_held_expert"], held_count=w["E"]))


def _attn_specs(prefix, n, w):
    D, H, R = w["D"], w["H"], w["R"]
    return [
        (prefix + ("attn", "wq"), (n, D, H, w["nope"] + w["rope"]), "normal",
         D ** -0.5),
        (prefix + ("attn", "wkv_a"), (n, D, R + w["rope"]), "normal",
         D ** -0.5),
        (prefix + ("attn", "kv_norm"), (n, R), "norm", NORM_SPREAD),
        (prefix + ("attn", "wkv_b"), (n, R, H, w["nope"] + w["dv"]),
         "normal", R ** -0.5),
        (prefix + ("attn", "wo"), (n, H, w["dv"], D), "normal",
         (H * w["dv"]) ** -0.5),
        (prefix + ("norm1",), (n, D), "norm", NORM_SPREAD),
        (prefix + ("norm2",), (n, D), "norm", NORM_SPREAD),
    ]


def _swiglu_specs(prefix, lead, D, F):
    return [(prefix + ("w_gate",), lead + (D, F), "normal", D ** -0.5),
            (prefix + ("w_up",), lead + (D, F), "normal", D ** -0.5),
            (prefix + ("w_down",), lead + (F, D), "normal", F ** -0.5)]


ROUTED = tuple(("layers", "moe", n) for n in ("w_gate", "w_up", "w_down"))


def leaf_specs(sizes: dict) -> list[tuple[tuple[str, ...], tuple, str, float]]:
    """(path, shape, kind, scale) of every leaf, in a fixed order: the
    program's tree, the leading dense layers stacked under ``dense`` and
    the MoE layers under ``layers``."""
    w = _widths(sizes)
    D, Ld, Lm = w["D"], w["Ld"], w["L"] - w["Ld"]
    return ([
        (("embedding",), (w["V"], D), "normal", EMBED_SCALE),
        (("lm_head",), (w["V"], D), "normal", EMBED_SCALE),
        (("final_norm",), (D,), "norm", NORM_SPREAD)]
        + _attn_specs(("dense",), Ld, w)
        + _swiglu_specs(("dense", "mlp"), (Ld,), D, w["F"])
        + _attn_specs(("layers",), Lm, w)
        + [(("layers", "moe", "router"),
            (Lm, D, sizes["experts_routed_over"]), "normal", D ** -0.5)]
        + _swiglu_specs(("layers", "moe"), (Lm, w["E"]), D, w["Fe"])
        + _swiglu_specs(("layers", "moe", "shared"), (Lm,), D,
                        w["S"] * w["Fe"]))


# -- the reference -------------------------------------------------------


def yarn_rope(x, pos, sizes):
    """x (S, heads, rope) rotated as halves at YaRN's frequencies."""
    y, dim = sizes["rope_scaling"], x.shape[-1]
    base = float(sizes["rope_theta"])

    def d(beta):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (beta * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(d(y["beta_fast"])), 0)
    high = min(math.ceil(d(y["beta_slow"])), dim - 1)
    i = np.arange(dim // 2)
    r = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    theta = base ** (-2.0 * i / dim)
    inv = theta * (1 - r) + theta / y["factor"] * r
    gain = _m(y["factor"], y["mscale"]) / _m(y["factor"], y["mscale_all_dim"])
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos = (jnp.cos(ang) * gain)[:, None, :]
    sin = (jnp.sin(ang) * gain)[:, None, :]
    a, b = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _m(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def scale(sizes) -> float:
    y = sizes["rope_scaling"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    return qk ** -0.5 * _m(y["factor"], y["mscale_all_dim"]) ** 2


def project(a, h, pos, sizes, low):
    """Per-head q (S, H, qk) and k (S, H, qk), v (S, H, dv)."""
    nope, R = sizes["qk_nope_head_dim"], sizes["kv_lora_rank"]
    q = mm("sd,dhk->shk", h, a["wq"], low)
    kv = mm("sd,dw->sw", h, a["wkv_a"], low)
    c = rms(kv[:, :R], a["kv_norm"], sizes["rms_norm_eps"])
    k_pe = yarn_rope(kv[:, None, R:], pos, sizes)
    kvb = mm("sc,chk->shk", c, a["wkv_b"], low)
    H = q.shape[1]
    q = jnp.concatenate([q[..., :nope], yarn_rope(q[..., nope:], pos,
                                                  sizes)], axis=-1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (k_pe.shape[0], H,
                                                 k_pe.shape[-1]))], axis=-1)
    return q, k, kvb[..., nope:]


def attend(q, k, v, q_pos, k_pos, s, low):
    """q, k (S, H, qk); v (Sk, H, dv); causal on absolute positions, in
    query chunks of Q_CHUNK rows."""
    def block(qc, pc):
        sc = mm("qhk,shk->hqs", qc, k, low) * s
        sc = jnp.where((k_pos[None, :] <= pc[:, None])[None], sc, NEG)
        return mm("hqs,shv->qhv", jax.nn.softmax(sc, axis=-1), v, low)

    Sq = q.shape[0]
    if Sq <= Q_CHUNK:
        return block(q, q_pos)
    n = -(-Sq // Q_CHUNK)
    extra = n * Q_CHUNK - Sq
    qc = jnp.pad(q, ((0, extra), (0, 0), (0, 0))).reshape(
        n, Q_CHUNK, *q.shape[1:])
    pc = jnp.pad(q_pos, (0, extra), constant_values=-1).reshape(n, Q_CHUNK)
    out = jax.lax.map(lambda t: block(*t), (qc, pc))
    return out.reshape(n * Q_CHUNK, *out.shape[2:])[:Sq]


def ffn(lp, h, sizes, low):
    """The dense SwiGLU, or the held experts' weighted part plus the
    shared experts."""
    def swiglu(p, x):
        g = mm("sd,df->sf", x, p["w_gate"], low)
        u = mm("sd,df->sf", x, p["w_up"], low)
        return mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"], low)

    if "mlp" in lp:
        return swiglu(lp["mlp"], h)
    m = lp["moe"]
    s = jax.nn.softmax(mm("sd,de->se", h, m["router"], low), axis=-1)
    top, idx = jax.lax.top_k(s, sizes["num_experts_per_tok"])
    top = top * sizes["routed_scaling_factor"]
    held = sizes["first_held_expert"] + jnp.arange(sizes["n_routed_experts"])
    w = jnp.sum(jnp.where(idx[:, :, None] == held, top[:, :, None], 0.0),
                axis=1)  # (S, held)
    g = mm("sd,edf->sef", h, m["w_gate"], low)
    u = mm("sd,edf->sef", h, m["w_up"], low)
    y = mm("sef,efd->sed", jax.nn.silu(g) * u, m["w_down"], low)
    return jnp.einsum("se,sed->sd", w, y) + swiglu(m["shared"], h)


def layer(lp, x, k_pre, v_pre, pos, sizes, low):
    """One layer over rows x (T, D) at positions ``pos``, after cached
    prefix keys and values (positions 0..len-1): (new x, k, v)."""
    h = rms(x, lp["norm1"], sizes["rms_norm_eps"])
    q, k, v = project(lp["attn"], h, pos, sizes, low)
    kk = jnp.concatenate([k_pre, k])
    vv = jnp.concatenate([v_pre, v])
    k_pos = jnp.concatenate([jnp.arange(k_pre.shape[0]), pos])
    att = attend(q, kk, vv, pos, k_pos, scale(sizes), low)
    x = x + mm("shv,hvd->sd", att, lp["attn"]["wo"], low)
    return x + ffn(lp, rms(x, lp["norm2"], sizes["rms_norm_eps"]), sizes,
                   low), k, v


@functools.partial(jax.jit, static_argnums=(0, 1))
def prefix_layer(frozen, low, lp, x):
    sizes = families.thaw(frozen)
    H = sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    empty_k = jnp.zeros((0, H, qk), jnp.float32)
    empty_v = jnp.zeros((0, H, sizes["v_head_dim"]), jnp.float32)
    return layer(lp, x, empty_k, empty_v, jnp.arange(x.shape[0]), sizes,
                 low)


@functools.partial(jax.jit, static_argnums=(0, 1))
def tail_layer(frozen, low, lp, x, k_pre, v_pre):
    """One layer over the tails (n, T, D), each after the shared prefix."""
    sizes = families.thaw(frozen)
    pos = k_pre.shape[0] + jnp.arange(x.shape[1])
    return jax.lax.map(
        lambda xt: layer(lp, xt, k_pre, v_pre, pos, sizes, low)[0], x)


def forward(sizes: dict, params, prefix: np.ndarray, inputs: np.ndarray,
            low: bool):
    """Final hidden states (n, T, D) of the tails after the prefix, layer
    by layer: the dense stack, then the MoE stack."""
    frozen = families.freeze(sizes)
    emb = params["embedding"]
    x_pre = (emb[jnp.asarray(prefix)].astype(jnp.float32)
             if len(prefix) else None)
    x = emb[jnp.asarray(inputs)].astype(jnp.float32)
    w = _widths(sizes)
    for stack in ("dense", "layers"):
        for i in range(params[stack]["norm1"].shape[0]):
            lp = f32(jax.tree.map(lambda a: a[i], params[stack]))
            if x_pre is not None:
                x_pre, k_pre, v_pre = prefix_layer(frozen, low, lp, x_pre)
            else:
                k_pre = jnp.zeros((0, w["H"], w["nope"] + w["rope"]))
                v_pre = jnp.zeros((0, w["H"], w["dv"]))
            x = tail_layer(frozen, low, lp, x, k_pre, v_pre)
            del lp
    return x


# -- operations and bytes ------------------------------------------------


def _attn_params(w) -> int:
    H, D, R = w["H"], w["D"], w["R"]
    return (D * H * (w["nope"] + w["rope"]) + D * (R + w["rope"])
            + R * H * (w["nope"] + w["dv"]) + H * w["dv"] * D)


def matmul_params(sizes: dict) -> int:
    """Weights a token multiplies through on this chip: every layer's
    attention (the absorbed decode uses all of Wkvb once), the dense
    layers' SwiGLU, each MoE layer's router, its shared experts and the
    expected held share of its routed picks (num_experts_per_tok x held /
    routed experts, under uniform routing), and the head."""
    w = _widths(sizes)
    D, Lm = w["D"], w["L"] - w["Ld"]
    picks = (sizes["num_experts_per_tok"] * w["E"]
             / sizes["experts_routed_over"])
    moe = (D * sizes["experts_routed_over"]
           + (picks + w["S"]) * 3 * D * w["Fe"])
    return int(w["L"] * _attn_params(w) + w["Ld"] * 3 * D * w["F"]
               + Lm * moe + w["V"] * D)


def _row_flops(w) -> int:
    """Per attended row, per layer: the scores over the whole latent row
    and the weighted sum of its latent part, every head."""
    return 2 * w["H"] * (2 * w["R"] + w["rope"])


def token_flops(sizes: dict, attended: int) -> float:
    """Model FLOPs of one token that attends ``attended`` rows (itself
    included) in every layer."""
    w = _widths(sizes)
    return float(2 * matmul_params(sizes) + w["L"] * _row_flops(w) * attended)


def kernel_call(sizes: dict, batch: int, selected: int
                ) -> tuple[float, float]:
    """(flops, bytes) of one layer's latent-page read: every slot of the
    wave, ``selected`` pages of latent rows each (bf16, read once), the
    absorbed query in (bf16) and the latent context out (f32)."""
    w = _widths(sizes)
    rows = batch * selected * PAGE
    width = w["R"] + w["rope"]
    read = rows * width * BF16 + batch * w["H"] * width * BF16
    write = batch * w["H"] * w["R"] * F32
    return float(rows * _row_flops(w)), float(read + write)


def expert_bytes(sizes: dict) -> int:
    """One routed expert's weights."""
    w = _widths(sizes)
    return 3 * w["D"] * w["Fe"] * BF16


def wave_bytes(sizes: dict, waves: int, touched: int, rows: int) -> float:
    """Least HBM bytes of ``waves`` decode waves: every weight once a wave
    except the embedding (a lookup of a few rows) and the routed experts;
    the ``touched`` (wave, layer, held expert) triples' weights once each;
    ``rows`` latent rows (each slot's valid rows, summed over its steps)
    once in every layer."""
    w = _widths(sizes)
    skip = {("embedding",)} | set(ROUTED)
    weights = sum(int(np.prod(shape)) for path, shape, _, _ in
                  leaf_specs(sizes) if path not in skip) * BF16
    latent = rows * w["L"] * (w["R"] + w["rope"]) * BF16
    return float(waves * weights + touched * expert_bytes(sizes) + latent)


def tiny(sizes: dict) -> dict:
    return dict(sizes, **TINY)
