"""Pallas TPU kernel: blocked causal flash attention (prefill hot path).

Standard streaming-softmax formulation: grid over (batch*heads, Q blocks,
KV blocks); one (block_q x hd) query tile stays resident while (block_k x
hd) KV tiles stream through VMEM with running max/sum accumulators. Block
shapes are MXU-aligned (multiples of 128 on the contracting dims).

This is the §Perf lever for the memory-dominated train/prefill cells: the
XLA reference path materializes (S x S) f32 score tensors per head; the
kernel never leaves a (block_q x block_k) tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref,
            *, block_q: int, block_k: int, num_kv: int, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (block_q, hd)
    k = k_ref[0].astype(jnp.float32)  # (block_k, hd)
    v = v_ref[0].astype(jnp.float32)
    hd = q.shape[-1]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    s = s * (1.0 / jnp.sqrt(jnp.float32(hd)))

    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if causal:
        p = jnp.where(kpos <= qpos, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == num_kv - 1)
    def _finish():
        out_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            out_ref.dtype)


def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """q,k,v: (B, H, S, hd) -> (B, H, S, hd) in q.dtype.

    ``interpret=None`` auto-detects: compiled on TPU, interpreter elsewhere.
    """
    return _flash_attention(q, k, v, causal=causal, block_q=block_q,
                            block_k=block_k,
                            interpret=backend.resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret"))
def _flash_attention(q, k, v, causal: bool, block_q: int, block_k: int,
                     interpret: bool):
    B, H, S, hd = q.shape
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0
    grid = (B * H, S // bq, S // bk)

    def qmap(bh, qi, ki):
        return (bh, qi, 0)

    def kmap(bh, qi, ki):
        return (bh, ki, 0)

    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * H, S, hd)
    vf = v.reshape(B * H, S, hd)
    out = pl.pallas_call(
        functools.partial(_kernel, block_q=bq, block_k=bk,
                          num_kv=S // bk, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), qmap),
            pl.BlockSpec((1, bk, hd), kmap),
            pl.BlockSpec((1, bk, hd), kmap),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), qmap),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd)
