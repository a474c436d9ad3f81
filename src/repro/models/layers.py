"""Shared neural building blocks (pure-function style, pjit-friendly).

Parameters are plain dict pytrees; every function takes (params, inputs) and
returns outputs, so the whole stack lowers cleanly under jax.jit with
NamedSharding-annotated inputs on a 512-device mesh.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, scale, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def init_rms(d, dtype):
    return jnp.ones((d,), dtype)


# --- rotary position embeddings ----------------------------------------------

def _rope_angles(positions, dim, base=10000.0, yarn=None):
    """positions (...,) -> cos/sin of shape (..., dim//2)."""
    if yarn is None:
        inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
        gain = 1.0
    else:
        inv = jnp.asarray(yarn_inv_freq(dim, yarn, base), jnp.float32)
        gain = (yarn_mscale(yarn.factor, yarn.mscale)
                / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang) * gain, jnp.sin(ang) * gain


# --- YaRN (arXiv:2309.00071, as DeepSeek-V2 runs it) ---------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """The attention-temperature term ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_bounds(dim: int, yarn, base: float = 10000.0) -> tuple[int, int]:
    """``(low, high)``: the rotary pairs below ``low`` keep their frequency,
    those from ``high`` on are interpolated by the whole factor. A pair's
    bound is where its wavelength fits ``beta`` turns into the original
    context: ``d(beta) = dim ln(L / (2 pi beta)) / (2 ln base)``."""
    def d(beta):
        return (dim * math.log(yarn.original_max_positions
                               / (beta * 2 * math.pi))
                / (2 * math.log(base)))
    return (max(math.floor(d(yarn.beta_fast)), 0),
            min(math.ceil(d(yarn.beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, yarn, base: float = 10000.0) -> np.ndarray:
    """Frequencies of the ``dim // 2`` rotary pairs:
    ``theta_i (1 - r_i) + (theta_i / factor) r_i`` with the ramp
    ``r_i = clip((i - low) / (high - low), 0, 1)``."""
    theta = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    low, high = yarn_bounds(dim, yarn, base)
    span = (high - low) or 0.001
    r = np.clip((np.arange(dim // 2) - low) / span, 0.0, 1.0)
    return theta * (1 - r) + theta / yarn.factor * r


def _apply_rot(x, cos, sin):
    """Rotate pairs in the last dim; cos/sin broadcast over heads."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out


def apply_rope(x, positions, kind: str = "standard", base=10000.0,
               yarn=None):
    """x: (B, S, H, hd); positions: (B, S) int32.

    kind:
      standard — full-dim rotation (llama-family); with ``yarn`` (a
                 ``YarnConfig``) at YaRN's frequencies.
      rope2d   — ChatGLM 2-D RoPE: rotate only the first half of head_dim.
      mrope    — Qwen2-VL M-RoPE: head_dim split into 3 sections rotated by
                 (temporal, height, width) position streams; for the text-only
                 backbone stub all three streams equal `positions`.
    """
    hd = x.shape[-1]
    if kind == "none":
        return x
    if kind == "standard":
        cos, sin = _rope_angles(positions, hd, base, yarn)
        return _apply_rot(x, cos[..., None, :], sin[..., None, :]).astype(x.dtype)
    if kind == "rope2d":
        half = hd // 2
        cos, sin = _rope_angles(positions, half, base)
        xr, xp = x[..., :half], x[..., half:]
        xr = _apply_rot(xr, cos[..., None, :], sin[..., None, :])
        return jnp.concatenate([xr.astype(x.dtype), xp], axis=-1)
    if kind == "mrope":
        # 3 sections (t, h, w); the modality frontend is a stub, so all three
        # position streams coincide with the 1-D text positions.
        s1 = hd // 2
        s2 = hd // 4
        s3 = hd - s1 - s2
        outs = []
        off = 0
        for sec in (s1, s2, s3):
            cos, sin = _rope_angles(positions, sec, base)
            outs.append(_apply_rot(x[..., off:off + sec],
                                   cos[..., None, :], sin[..., None, :]))
            off += sec
        return jnp.concatenate(outs, axis=-1).astype(x.dtype)
    raise ValueError(kind)


# --- MLPs ---------------------------------------------------------------------

def swiglu(params, x):
    """Gated MLP: silu(x @ w_gate) * (x @ w_up) @ w_down."""
    g = jnp.einsum("...d,df->...f", x, params["w_gate"])
    u = jnp.einsum("...d,df->...f", x, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, params["w_down"])


def init_swiglu(key, d, f, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    s = d ** -0.5
    return dict(
        w_gate=jax.random.normal(k1, (d, f), dtype) * s,
        w_up=jax.random.normal(k2, (d, f), dtype) * s,
        w_down=jax.random.normal(k3, (f, d), dtype) * (f ** -0.5),
    )


def embed(params, tokens):
    return jnp.take(params["embedding"], tokens, axis=0)


def unembed(params, x, tied: bool):
    w = params["embedding"] if tied else params["lm_head"]
    return jnp.einsum("...d,vd->...v", x, w)


def init_embed(key, vocab, d, dtype, tied: bool):
    k1, k2 = jax.random.split(key)
    p = dict(embedding=jax.random.normal(k1, (vocab, d), dtype) * 0.02)
    if not tied:
        p["lm_head"] = jax.random.normal(k2, (vocab, d), dtype) * 0.02
    return p
