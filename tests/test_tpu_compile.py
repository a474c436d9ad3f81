"""Compile the serving path for a described TPU v5e chip (no chip needed).

The TPU compiler is installed alongside JAX, and it compiles for a chip
that is described, not attached: what Mosaic or XLA would refuse on the
chip — a block shape off the tiling, an unsupported contraction, a
program that does not fit — fails here at no chip time. Nothing runs, so
these tests say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import backend as kbackend
from repro.kernels import ops
from repro.launch.serve import build_backend
from repro.models import model
from repro.runtime import sectored_decode
from repro.sample import SamplerRows
from repro.serve.backend import fused_wave_program, vmapped_prefill

HBM_BYTES = 16 * 2**30  # one v5e chip
SLOTS = 4
SEQ_LEN = 1000  # pads to 1024 tokens (8 pages) per slot


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _bytes(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


# ------------------------------------------------ the serving kernel


def _kernel_args(yi, *, quantized, shared, batch, pages, k):
    hkv, hd = yi.n_kv_heads, yi.head_dim_
    rep = yi.n_heads // hkv
    page = sectored_decode.PAGE_SIZE
    kv_dtype = jnp.int8 if quantized else jnp.bfloat16
    args = [jax.ShapeDtypeStruct((batch, hkv, rep, hd), jnp.bfloat16),
            jax.ShapeDtypeStruct((batch, pages, page, hkv, hd), kv_dtype),
            jax.ShapeDtypeStruct((batch, pages, page, hkv, hd), kv_dtype),
            jax.ShapeDtypeStruct((batch, 1 if shared else hkv, k), jnp.int32),
            jax.ShapeDtypeStruct((batch,), jnp.int32)]
    scales = ([jax.ShapeDtypeStruct((batch, pages, hkv), jnp.float32)] * 2
              if quantized else [])
    return args, scales


# (pages, budget): 8k tokens at 1/8 plus the probe page, and a 128k
# context, where the kernel's SMEM (page table, selected scales) must grow
# with the budget and not with the context
CONTEXTS = {"8k": (64, 9), "128k": (1024, 129)}


@pytest.mark.parametrize("context", sorted(CONTEXTS))
@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("shared", [False, True], ids=["per_head", "shared"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_kernel_compiles_at_yi6b_widths(one_chip, quantized, shared,
                                              vmapped, context):
    """``sectored_attention_paged`` at yi-6b's Hkv 4, rep 8, hd 128, page
    128 lowers through Mosaic, alone and under the session's slot vmap."""
    yi = configs.get("yi-6b")
    pages, k = CONTEXTS[context]
    args, scales = _kernel_args(yi, quantized=quantized, shared=shared,
                                batch=1 if vmapped else 2, pages=pages, k=k)

    def call(q, kp, vp, idx, length, *sc):
        return ops.sectored_attention_paged(
            q, kp, vp, idx, length, k_scale=sc[0] if sc else None,
            v_scale=sc[1] if sc else None, interpret=False)

    fn = call
    if vmapped:
        fn = jax.vmap(call)
        args = [jax.ShapeDtypeStruct((SLOTS,) + a.shape, a.dtype)
                for a in args + scales]
    else:
        args = args + scales
    compiled = jax.jit(fn).lower(*_on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _bytes(compiled.memory_analysis()) < HBM_BYTES


# ------------------------------------------------ one decode wave


def _weight_sized_constants(hlo_text: str, floor: int) -> list[str]:
    """Constants in lowered StableHLO with at least ``floor`` elements."""
    big = []
    for m in re.finditer(r"stablehlo\.constant dense.*?: tensor<([0-9x]+)x"
                         r"[a-z0-9]+>", hlo_text):
        if int(np.prod([int(d) for d in m.group(1).split("x")])) >= floor:
            big.append(m.group(0)[:120])
    return big


@pytest.mark.parametrize("kernel", ["fused", "fused_q8"])
def test_decode_wave_compiles_at_full_widths(one_chip, kernel,
                                             record_property, monkeypatch):
    """The sectored decode wave — ``jit(vmap)`` over slots of the per-slot
    step, as the session builds it — at yi-6b's published widths with a
    2-layer depth cut, from ``eval_shape`` weights. The weights enter as
    an argument: the lowered program holds no weight-sized constant."""
    # the kernels pick Mosaic by asking for the default backend, which is
    # the CPU here: steer them to the chip's branch
    monkeypatch.setattr(kbackend, "default_interpret", lambda: False)
    cfg = dataclasses.replace(configs.get("yi-6b"), n_layers=2)
    params = _on(one_chip, jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.key(0))))
    backend = sectored_decode.SectoredKVBackend(cfg, params, seq_len=SEQ_LEN,
                                                kernel=kernel)
    step = backend.sectored_fn_for(None)
    jitted, bound = fused_wave_program(step)
    state = jax.eval_shape(
        lambda: sectored_decode.init_state(cfg, 1, SEQ_LEN))
    stacked = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((SLOTS,) + x.shape, x.dtype), state)
    tokens = jax.ShapeDtypeStruct((SLOTS, 1, 1), jnp.int32)
    rows = jax.eval_shape(lambda: SamplerRows.init(SLOTS))
    lowered = jitted.lower(*_on(one_chip, (bound, stacked, tokens, rows)))
    assert not _weight_sized_constants(lowered.as_text(), cfg.d_model)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    record_property("memory_analysis", str(mem))
    assert "tpu_custom_call" in compiled.as_text()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= weights  # weights are arguments
    assert _bytes(mem) < HBM_BYTES


@pytest.mark.parametrize("program", ["wave", "prefill"])
@pytest.mark.parametrize("true_sectored", [False, True],
                         ids=["dense", "sectored"])
def test_serving_programs_take_weights_as_arguments(true_sectored, program):
    """Every backend ``build_session`` can build hands its weights to the
    session's wave and vmapped prefill as an argument (lowered on the
    host at toy widths; no chip needed)."""
    cfg = configs.get("yi-6b").reduced(n_layers=2, d_model=64, n_heads=4,
                                       n_kv_heads=2, d_ff=128, vocab=128,
                                       head_dim=16)
    params = model.init_params(cfg, jax.random.key(0))
    backend = build_backend(cfg, params, true_sectored=true_sectored,
                            seq_len=48)
    prompts = jnp.zeros((2, 6), jnp.int32)
    if program == "prefill":
        jitted, bound = vmapped_prefill(backend.prefill_fn)
        lowered = jitted.lower(bound, prompts)
    else:
        _, stacked = jax.vmap(lambda p: backend.prefill_fn(p[None, :]))(
            prompts)
        jitted, bound = fused_wave_program(backend.sectored_fn)
        lowered = jitted.lower(bound, stacked,
                               jnp.zeros((2, 1, 1), jnp.int32),
                               SamplerRows.init(2))
    assert bound, "the step binds no weights to pass as an argument"
    assert not _weight_sized_constants(lowered.as_text(),
                                       cfg.d_model * cfg.d_model)


def test_constant_scan_catches_closed_over_weights(one_chip):
    """The constant check is not vacuous: a program that closes over a
    weight-sized array carries it as a constant."""
    w = jnp.ones((64, 64), jnp.bfloat16)
    text = jax.jit(lambda x: x @ w).lower(
        jax.ShapeDtypeStruct((4, 64), jnp.bfloat16)).as_text()
    assert _weight_sized_constants(text, 64 * 64)
