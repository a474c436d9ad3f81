"""Dense decoder with grouped-query attention and a SwiGLU MLP: one stack of
identical layers (yi-6b, chatglm3-6b).

The reference's layer equations, in float32:

    x = embed[tokens]
    per layer: h = rms(x) * g1; q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv);
               RoPE on q, k; grouped-query causal softmax attention;
               x += attn Wo; h = rms(x) * g2; x += (silu(h Wg) * h Wu) Wd
    logits = (rms(x) * g) Whead^T

Attention is dense and causal over the whole context. Requests that share a
prefix are run as one prefix pass plus one pass per request tail, so the
prefix is computed once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import families
from bench.costs import BF16, F32, PAGE
from bench.reference import chunked_attend, f32, mm, rms, rope
from bench.weights import BIAS_SCALE, EMBED_SCALE, NORM_SPREAD

KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab", "rope", "rope_theta", "qkv_bias", "norm_eps",
        "tie_embeddings", "dtype")
# sizes that map one to one onto the program's ModelConfig fields
FIELDS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
          "vocab", "rope", "qkv_bias", "norm_eps", "tie_embeddings", "dtype")
TINY = dict(n_layers=2, d_model=128, n_heads=4, head_dim=32, d_ff=256,
            vocab=512)


def program_fields(sizes: dict) -> dict:
    """The program's fields: the sizes, and none of the published config's
    experts, windows or q/k norms, which these equations do not have. The
    program's RoPE base is fixed at 10000."""
    if sizes["head_dim"] <= 0 or float(sizes["rope_theta"]) != 10000.0:
        raise SystemExit("the configuration file asks for sizes the "
                         "program cannot run")
    return dict({k: sizes[k] for k in FIELDS}, moe=None, qk_norm=False,
                layer_pattern=(), local_window=0)


def leaf_specs(sizes: dict) -> list[tuple[tuple[str, ...], tuple, str, float]]:
    """(path, shape, kind, scale) of every leaf, in a fixed order."""
    L, D = sizes["n_layers"], sizes["d_model"]
    H, G, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    F, V = sizes["d_ff"], sizes["vocab"]
    specs = [
        (("embedding",), (V, D), "normal", EMBED_SCALE),
        (("lm_head",), (V, D), "normal", EMBED_SCALE),
        (("final_norm",), (D,), "norm", NORM_SPREAD),
        (("layers", "norm1"), (L, D), "norm", NORM_SPREAD),
        (("layers", "norm2"), (L, D), "norm", NORM_SPREAD),
        (("layers", "attn", "wq"), (L, D, H, hd), "normal", D ** -0.5),
        (("layers", "attn", "wk"), (L, D, G, hd), "normal", D ** -0.5),
        (("layers", "attn", "wv"), (L, D, G, hd), "normal", D ** -0.5),
        (("layers", "attn", "wo"), (L, H, hd, D), "normal", D ** -0.5),
        (("layers", "mlp", "w_gate"), (L, D, F), "normal", D ** -0.5),
        (("layers", "mlp", "w_up"), (L, D, F), "normal", D ** -0.5),
        (("layers", "mlp", "w_down"), (L, F, D), "normal", F ** -0.5),
    ]
    if sizes["qkv_bias"]:
        specs += [
            (("layers", "attn", "bq"), (L, H, hd), "normal", BIAS_SCALE),
            (("layers", "attn", "bk"), (L, G, hd), "normal", BIAS_SCALE),
            (("layers", "attn", "bv"), (L, G, hd), "normal", BIAS_SCALE),
        ]
    return specs


def project(lp, x, pos, sizes, low):
    q = mm("sd,dhk->shk", x, lp["attn"]["wq"], low)
    k = mm("sd,dhk->shk", x, lp["attn"]["wk"], low)
    v = mm("sd,dhk->shk", x, lp["attn"]["wv"], low)
    if sizes["qkv_bias"]:
        q, k, v = (q + lp["attn"]["bq"], k + lp["attn"]["bk"],
                   v + lp["attn"]["bv"])
    kind, theta = sizes["rope"], float(sizes["rope_theta"])
    return rope(q, pos, kind, theta), rope(k, pos, kind, theta), v


def finish_layer(lp, x, att, sizes, low):
    x = x + mm("shk,hkd->sd", att, lp["attn"]["wo"], low)
    h = rms(x, lp["norm2"], sizes["norm_eps"])
    g = mm("sd,df->sf", h, lp["mlp"]["w_gate"], low)
    u = mm("sd,df->sf", h, lp["mlp"]["w_up"], low)
    return x + mm("sf,fd->sd", jax.nn.silu(g) * u, lp["mlp"]["w_down"], low)


@functools.partial(jax.jit, static_argnums=(0, 1))
def prefix_layer(frozen, low, lp, x):
    """One layer over the shared prefix: (new x, its k, its v)."""
    sizes = families.thaw(frozen)
    pos = jnp.arange(x.shape[0])
    h = rms(x, lp["norm1"], sizes["norm_eps"])
    q, k, v = project(lp, h, pos, sizes, low)
    att = chunked_attend(q, k, v, pos, pos, low)
    return finish_layer(lp, x, att, sizes, low), k, v


@functools.partial(jax.jit, static_argnums=(0, 1))
def tail_layer(frozen, low, lp, x, k_pre, v_pre):
    """One layer over the tails (n, T, D), each after the shared prefix."""
    sizes = families.thaw(frozen)
    L0 = k_pre.shape[0]
    pos = L0 + jnp.arange(x.shape[1])
    k_pos_pre = jnp.arange(L0)

    def one(xt):
        h = rms(xt, lp["norm1"], sizes["norm_eps"])
        q, k, v = project(lp, h, pos, sizes, low)
        kk = jnp.concatenate([k_pre, k], axis=0)
        vv = jnp.concatenate([v_pre, v], axis=0)
        att = chunked_attend(q, kk, vv, pos,
                             jnp.concatenate([k_pos_pre, pos]), low)
        return finish_layer(lp, xt, att, sizes, low)

    return jax.lax.map(one, x)


def forward(sizes: dict, params, prefix: np.ndarray, inputs: np.ndarray,
            low: bool):
    """Final hidden states (n, T, D) of the tails after the prefix, layer
    by layer."""
    frozen = families.freeze(sizes)
    emb = params["embedding"]
    x_pre = (emb[jnp.asarray(prefix)].astype(jnp.float32)
             if len(prefix) else None)
    x = emb[jnp.asarray(inputs)].astype(jnp.float32)
    G, hd = sizes["n_kv_heads"], sizes["head_dim"]
    for layer in range(sizes["n_layers"]):
        lp = f32(jax.tree.map(lambda a: a[layer], params["layers"]))
        if x_pre is not None:
            x_pre, k_pre, v_pre = prefix_layer(frozen, low, lp, x_pre)
        else:
            k_pre = v_pre = jnp.zeros((0, G, hd), jnp.float32)
        x = tail_layer(frozen, low, lp, x, k_pre, v_pre)
        del lp
    return x


def kernel_call(sizes: dict, batch: int, selected: int
                ) -> tuple[float, float]:
    """(flops, bytes) of one call of ``sectored_attention_paged`` for one
    layer: every slot of the wave (``batch``), every KV head, ``selected``
    pages each. Reads the grouped query (bf16), the K and V rows of each
    selected page of each head; writes the f32 output and the f32 mass of
    each selected page. Operations: the two contractions, q.k and p.v."""
    H, G, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    rows = batch * G * selected * PAGE
    flops = 2 * 2 * batch * H * selected * PAGE * hd
    read = batch * H * hd * BF16 + 2 * rows * hd * BF16
    write = batch * H * hd * F32 + batch * G * selected * F32
    return float(flops), float(read + write)


def matmul_params(sizes: dict) -> int:
    """Weights a token multiplies through: every layer's projections and
    MLP, and the head (the embedding is a lookup)."""
    D, H, G, hd = (sizes["d_model"], sizes["n_heads"], sizes["n_kv_heads"],
                   sizes["head_dim"])
    layer = D * hd * (H + 2 * G) + H * hd * D + 3 * D * sizes["d_ff"]
    return sizes["n_layers"] * layer + sizes["vocab"] * D


def token_flops(sizes: dict, attended: int) -> float:
    """Model FLOPs of one token that attends ``attended`` earlier tokens
    (itself included) in every layer."""
    attn = 2 * 2 * sizes["n_heads"] * sizes["head_dim"] * attended
    return float(2 * matmul_params(sizes) + sizes["n_layers"] * attn)


def tiny(sizes: dict) -> dict:
    return dict(sizes, **TINY, n_kv_heads=min(sizes["n_kv_heads"], 2))
