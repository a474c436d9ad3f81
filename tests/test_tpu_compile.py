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


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


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
    assert mem.argument_size_in_bytes >= _nbytes(params)  # weights are args
    assert _bytes(mem) < HBM_BYTES


# ------------------------------------------------ the row write


def _cache_rewrites(hlo_text: str, stacked_shape) -> list[str]:
    """Instructions in compiled HLO that copy or select over the whole
    stacked KV cache (in any layout, transposed or bitcast), or select over
    one layer of it: what a step that rebuilds the cache to change one row
    compiles to. Shapes compare as their sorted non-unit dims."""
    def key(dims):
        return tuple(sorted(d for d in dims if d != 1))

    whole = key(stacked_shape)
    layer = key(stacked_shape[:1] + stacked_shape[2:])
    found = []
    for m in re.finditer(r"%([\w.-]+) = \w+\[([0-9,]+)\]\{[^}]*\} "
                         r"([\w-]+)\(", hlo_text):
        name, dims, opcode = m.groups()
        what = name if opcode == "fusion" else opcode
        shape = key(int(d) for d in dims.split(","))
        if ((shape == whole and ("select" in what or "copy" in what))
                or (shape == layer and "select" in what)):
            found.append(f"{name} {opcode} [{dims}]")
    return found


def _wave_state(cfg, slots, seq_len=SEQ_LEN):
    state = jax.eval_shape(
        lambda: sectored_decode.init_state(cfg, 1, seq_len))
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((slots,) + x.shape, x.dtype), state)


@pytest.mark.parametrize("path", ["exact", "fused"])
def test_decode_wave_writes_rows_in_place(one_chip, path, monkeypatch):
    """The decode wave at yi-6b's published widths (2-layer depth cut)
    and the doc cells' 16 slots of 9344 tokens, on their exact path and
    the chat cell's fused one, writes each token's K/V row in place: the
    wave donates its state, so its output aliases its input (the alias
    covers the whole state), nothing copies the whole cache, and nothing
    selects over the stacked cache or over a layer of it."""
    monkeypatch.setattr(kbackend, "default_interpret", lambda: False)
    cfg = dataclasses.replace(configs.get("yi-6b"), n_layers=2)
    params = _on(one_chip, jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.key(0))))
    slots, seq_len = 16, 9344  # a leaf too large to stage in VMEM
    backend = sectored_decode.SectoredKVBackend(cfg, params, seq_len=seq_len,
                                                kernel="fused")
    step = backend.decode_fn if path == "exact" else backend.sectored_fn
    stacked = _wave_state(cfg, slots, seq_len)
    jitted, bound = fused_wave_program(step)
    compiled = jitted.lower(*_on(one_chip, (
        bound, stacked, jax.ShapeDtypeStruct((slots, 1, 1), jnp.int32),
        jax.eval_shape(lambda: SamplerRows.init(slots))))).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (path == "fused")
    assert _cache_rewrites(text, stacked.kv.k.shape) == []
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= _nbytes(stacked))


def _outside_fusions(hlo_text: str, shapes) -> list[str]:
    """Instructions of compiled HLO, outside fused computations, whose
    array shape is one of ``shapes`` (compared as sorted non-unit dims):
    buffers a program materializes, as opposed to the operands a fusion
    reads in place."""
    def key(dims):
        return tuple(sorted(d for d in dims if d != 1))

    wanted = {key(s) for s in shapes}
    fused = set(re.findall(r"fusion\(.*?calls=(%[\w.-]+)", hlo_text))
    found, computation = [], None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.-]+) \(", line)
        if head:
            computation = head.group(1)
            continue
        m = re.match(r"\s+(?:ROOT )?%([\w.-]+) = \w+\[([0-9,]+)\]\{[^}]*\} "
                     r"([\w-]+)\(", line)
        if m and computation not in fused:
            name, dims, opcode = m.groups()
            if key(int(d) for d in dims.split(",")) in wanted:
                found.append(f"{name} {opcode} [{dims}]")
    return found


def test_latent_wave_reads_the_cache_in_place(one_chip, monkeypatch):
    """DeepSeek-V2-Lite's exact decode wave at published widths (a 3-layer
    cut: the dense layer and two MoE layers, so the MoE layers stay a
    loop), the doc cells' 16 slots of 9344 tokens: no layer of the latent
    cache is materialized (its reads are fused into the attention's dots),
    the whole cache is never copied (the donated input is the output, so
    the alias covers the whole state) and only written in place, and no
    layout change transposes it."""
    monkeypatch.setattr(kbackend, "default_interpret", lambda: False)
    base = configs.get("deepseek-v2-lite")
    cfg = dataclasses.replace(base, n_layers=3, moe=dataclasses.replace(
        base.moe, held_count=8))
    params = _on(one_chip, jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.key(0))))
    slots, seq_len = 16, 9344
    backend = sectored_decode.SectoredKVBackend(cfg, params, seq_len=seq_len)
    stacked = _wave_state(cfg, slots, seq_len)
    jitted, bound = fused_wave_program(backend.decode_fn)
    compiled = jitted.lower(*_on(one_chip, (
        bound, stacked, jax.ShapeDtypeStruct((slots, 1, 1), jnp.int32),
        jax.eval_shape(lambda: SamplerRows.init(slots))))).compile()
    whole = stacked.kv.rows.shape
    text = compiled.as_text()
    assert not _outside_fusions(text, [whole[:1] + whole[2:]])
    found = _outside_fusions(text, [whole])
    assert all(f.split()[1] in ("parameter", "bitcast", "copy", "fusion",
                                "get-tuple-element") for f in found), found
    assert sum(f.split()[1] == "copy" for f in found) == 0, found
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _nbytes(stacked)
    assert _bytes(mem) < HBM_BYTES


def test_rewrite_scan_catches_one_hot_append(one_chip):
    """The rewrite check is not vacuous: the append this step had — each
    layer's cache scanned in, a one-hot select writes the row, the caches
    stacked out — is found, and it selects over the cache."""
    shape = (SLOTS, 2, 1, 1024, 4, 128)

    def one_hot_append(k, row, length):
        def body(_, k_l):
            sel = (jnp.arange(k_l.shape[2])[None, None, :, None, None]
                   == length[:, None, None, None, None])
            return None, jnp.where(sel, row, k_l)

        return jnp.moveaxis(
            jax.lax.scan(body, None, jnp.moveaxis(k, 1, 0))[1], 0, 1)

    compiled = jax.jit(one_hot_append).lower(*_on(one_chip, (
        jax.ShapeDtypeStruct(shape, jnp.bfloat16),
        jax.ShapeDtypeStruct((SLOTS, 1, 1, 4, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((SLOTS,), jnp.int32)))).compile()
    assert any("select" in r
               for r in _cache_rewrites(compiled.as_text(), shape))


def test_sharded_wave_writes_rows_locally(topo):
    """On a 2x2 mesh with the page axis sharded over 'model' (the
    ``MeshBackend`` wave), the row write partitions locally: no
    collective carries a layer's cache or the stacked cache, whole or
    sharded — only the selected pages the gather pulls across shards —
    and each device's shard of the donated state is its output's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.parallel import sharding

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    replicated = NamedSharding(mesh, PartitionSpec())
    cfg = dataclasses.replace(configs.get("yi-6b"), n_layers=2)
    params = jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.key(0)))
    backend = sectored_decode.SectoredKVBackend(cfg, params, seq_len=SEQ_LEN)
    stacked = _wave_state(cfg, SLOTS)
    placed = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        stacked, sharding.wave_state_shardings(mesh, stacked))
    assert placed.kv.k.sharding.spec[3] == "model"
    jitted, bound = fused_wave_program(backend.sectored_fn)
    tokens = jax.ShapeDtypeStruct(
        (SLOTS, 1, 1), jnp.int32,
        sharding=sharding.wave_token_sharding(mesh, (SLOTS, 1, 1)))
    compiled = jitted.lower(
        _on(replicated, bound), placed, tokens,
        _on(replicated, jax.eval_shape(
            lambda: SamplerRows.init(SLOTS)))).compile()
    text = compiled.as_text()
    full = stacked.kv.k.shape
    shard = full[:3] + (full[3] // 2,) + full[4:]
    cache_keys = {tuple(sorted(d for d in s if d != 1))
                  for s in (full, shard, full[:1] + full[2:],
                            shard[:1] + shard[2:])}
    moved = re.findall(r"= (\(?[^=]*?) (?:all-gather|all-reduce|all-to-all|"
                       r"collective-permute|reduce-scatter)(?:-start)?\(",
                       text)
    assert moved  # the gather of the selected pages crosses shards
    for shapes in moved:
        for dims in re.findall(r"\[([0-9,]+)\]", shapes):
            key = tuple(sorted(int(d) for d in dims.split(",")
                               if int(d) != 1))
            assert key not in cache_keys, shapes
    # the memory analysis counts one device: its shard of every leaf
    per_device = sum(
        int(np.prod(x.sharding.shard_shape(x.shape))) * x.dtype.itemsize
        for x in jax.tree.leaves(placed))
    assert compiled.memory_analysis().alias_size_in_bytes >= per_device


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
