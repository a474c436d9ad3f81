"""The plain float32 reference, and the comparison that decides ``correct``.

The reference imports nothing of the program. It takes the configuration
file's sizes, the weights that ``bench/weights.py`` makes again from the
seed (bfloat16 values, computed in float32 here), the prompts and the tokens
that the program served. The configuration's family (``bench/families/``)
runs its published layer equations in ``jax.numpy`` from the primitives
here, at ``highest`` matmul precision, layer by layer, to the final hidden
states; ``head`` turns them into logits, ``(rms(x) * g) Whead^T``.

Attention is causal over the whole context. So is the program's in every
cell this reference checks: its prefill selects every page, and its decode
waves attend every valid page (policy ``dense``, or a selection as wide as
the context). A cell whose predictor leaves valid pages out computes
another function, which this reference does not follow.

Requests that share a prefix (the document of the doc cells) are run as one
prefix pass plus one pass per request tail, so the document is computed
once. Where a request's tail is shorter than the padded length, the padding
sits after it and causality keeps it out of every compared position.

The number compared is the gap by which the served token's reference logit
lies below the reference's best logit at the same position, 0 where the
program chose the reference's first choice.

The control (``control=True``) is this reference put in the program's
place and computed one precision below the configuration's bfloat16:
every matmul operand rounded to float8 (e4m3, one scale per tensor). At
each position of the same prompts and served tokens it reads the gap of the
token that float8 puts first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import families

NEG = -1e30
Q_CHUNK = 1024
F8_MAX = 448.0


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def fp8(x):
    """Round to float8 (e4m3) with one scale per tensor, back to float32:
    the control computes every matmul operand in this precision."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(spec, a, b, low):
    if low:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b)


def rope(x, pos, kind: str, theta: float):
    """x (..., S, heads, hd); rotate pairs (i, i + dim/2) of the first
    ``dim`` dims, dim = hd (standard) or hd/2 (rope2d)."""
    hd = x.shape[-1]
    dim = hd if kind == "standard" else hd // 2
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = pos.astype(jnp.float32)[..., None] * inv  # (..., S, dim/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    a, b = x[..., : dim // 2], x[..., dim // 2: dim]
    rot = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate([rot, x[..., dim:]], axis=-1)


def attend(q, k, v, q_pos, k_pos, low):
    """q (Sq, H, hd); k, v (Sk, G, hd); causal on absolute positions."""
    Sq, H, hd = q.shape
    G = k.shape[1]
    qg = q.reshape(Sq, G, H // G, hd)
    s = mm("qgrd,kgd->gqrk", qg, k, low) / jnp.sqrt(jnp.float32(hd))
    mask = k_pos[None, :] <= q_pos[:, None]  # (Sq, Sk)
    s = jnp.where(mask[None, :, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = mm("gqrk,kgd->qgrd", p, v, low)
    return out.reshape(Sq, H, hd)


def chunked_attend(q, k, v, q_pos, k_pos, low):
    """Query chunks of Q_CHUNK rows, so no (Sq, Sk) block is too large."""
    Sq = q.shape[0]
    if Sq <= Q_CHUNK:
        return attend(q, k, v, q_pos, k_pos, low)
    n = -(-Sq // Q_CHUNK)
    extra = n * Q_CHUNK - Sq  # padded queries see no key and are dropped
    qc = jnp.pad(q, ((0, extra), (0, 0), (0, 0))).reshape(
        n, Q_CHUNK, *q.shape[1:])
    pc = jnp.pad(q_pos, (0, extra), constant_values=-1).reshape(n, Q_CHUNK)
    out = jax.lax.map(lambda a: attend(a[0], k, v, a[1], k_pos, low),
                      (qc, pc))
    return out.reshape(n * Q_CHUNK, *q.shape[1:])[:Sq]


@functools.partial(jax.jit, static_argnums=(0, 1))
def head(eps, low, final_norm, lm_head, x, tokens):
    """x (n, T, D); tokens (m, n, T) int32 -> gaps (m, n, T) of each token
    row below the best logit, and the first choice (n, T)."""

    def one(args):
        xt, tok = args  # (T, D), (m, T)
        logits = mm("td,vd->tv", rms(xt, final_norm, eps),
                    lm_head, low)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, tok.T, axis=-1).T  # (m, T)
        return best[None] - got, jnp.argmax(logits, axis=-1)

    gaps, first = jax.lax.map(one, (x, jnp.swapaxes(tokens, 0, 1)))
    return jnp.swapaxes(gaps, 0, 1), first


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def gaps(sizes: dict, params, prefix: np.ndarray, tails: list[np.ndarray],
         served: list[np.ndarray], pad_to: int, control: bool = False):
    """Gap of each served token, per request, and with ``control`` the gap
    of the token that the float8 control puts first at the same positions.

    ``prefix`` is the shared prompt prefix (may be empty); ``tails[i]`` the
    rest of request i's prompt; ``served[i]`` its served tokens. The
    reference reads ``prefix + tails[i] + served[i][:-1]`` and scores
    ``served[i][j]`` at the position that produced it.
    """
    forward, eps = families.of(sizes).forward, float(sizes["norm_eps"])
    n = len(tails)
    inputs = np.zeros((n, pad_to), np.int32)
    scored = np.zeros((n, pad_to), np.int32)
    for i, (tail, out) in enumerate(zip(tails, served)):
        seq = np.concatenate([tail, out[:-1]]).astype(np.int32)
        if len(seq) > pad_to:
            raise ValueError(f"request {i}: {len(seq)} tokens > {pad_to}")
        inputs[i, :len(seq)] = seq
        first = len(tail) - 1
        scored[i, first:first + len(out)] = out
    norm = params["final_norm"].astype(jnp.float32)
    lm_head = params["lm_head"].astype(jnp.float32)
    rows = [jnp.asarray(scored)]
    with jax.default_matmul_precision("highest"):
        if control:
            x8 = forward(sizes, params, prefix, inputs, True)
            _, pick = head(eps, True, norm, lm_head, x8, rows[0][None])
            rows.append(pick.astype(jnp.int32))
            del x8
        x = forward(sizes, params, prefix, inputs, False)
        gap, _ = head(eps, False, norm, lm_head, x, jnp.stack(rows))
    gap = np.asarray(gap)
    out = []
    for row in gap:
        per = []
        for i, (tail, toks) in enumerate(zip(tails, served)):
            first = len(tail) - 1
            per.append(row[i, first:first + len(toks)].astype(np.float64))
        out.append(per)
    return out if control else out[0]
