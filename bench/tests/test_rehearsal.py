"""Compile the cells' programs for a described TPU v5e (no chip needed).

The sectored attention kernel at every configuration's widths (chatglm3's
2 KV heads of 16 queries are shapes the repository's own rehearsal never
compiled), and each cell's decode wave (the one its policy runs) and
longest prefill at its full cut depth, batch and context, with
``memory_analysis()`` against the chip's memory. Cells and configurations
are those of ``BENCHMARK.json``.
Nothing runs, so this says nothing about results or speed.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_rehearsal.py -s

The topology is described inside a module fixture, never at import.
"""

import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import families, run, weights
from repro.kernels import backend as kbackend
from repro.kernels import ops
from repro.runtime import sectored_decode
from repro.sample import SamplerRows
from repro.serve.backend import fused_wave_program

HBM_BYTES = 16 * 2**30
BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", before)


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _total(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def _report(name: str, mem) -> None:
    print(json.dumps(dict(program=name, argument=mem.argument_size_in_bytes,
                          output=mem.output_size_in_bytes,
                          temp=mem.temp_size_in_bytes,
                          alias=mem.alias_size_in_bytes,
                          total=_total(mem))))


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("config", CONFIGS)
def test_kernel_compiles_at_config_widths(one_chip, config, quantized):
    """The kernel at a doc context's budget (9 + 1 probe of 80 pages) for
    16 slots, as the sectored doc cells would run it."""
    s = json.loads((run.BENCH / "configs" / f"{config}.json").read_text())
    G, hd = s["n_kv_heads"], s["head_dim"]
    rep = s["n_heads"] // G
    slots, pages, k = 16, 80, 10
    kv = jnp.int8 if quantized else jnp.bfloat16
    args = [jax.ShapeDtypeStruct((slots, 1, G, rep, hd), jnp.bfloat16),
            jax.ShapeDtypeStruct((slots, 1, pages, 128, G, hd), kv),
            jax.ShapeDtypeStruct((slots, 1, pages, 128, G, hd), kv),
            jax.ShapeDtypeStruct((slots, 1, G, k), jnp.int32),
            jax.ShapeDtypeStruct((slots, 1), jnp.int32)]
    if quantized:
        args += [jax.ShapeDtypeStruct((slots, 1, pages, G), jnp.float32)] * 2

    def call(q, kp, vp, idx, length, *sc):
        return ops.sectored_attention_paged(
            q, kp, vp, idx, length, k_scale=sc[0] if sc else None,
            v_scale=sc[1] if sc else None, interpret=False)

    compiled = jax.jit(jax.vmap(call)).lower(*_on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total(compiled.memory_analysis()) < HBM_BYTES


@pytest.mark.parametrize("cell", CELLS)
def test_cell_programs_fit_one_chip(one_chip, cell, monkeypatch):
    """The cell's decode wave (every slot) and its longest prefill, at the
    configuration's cut depth, from ``eval_shape`` weights."""
    monkeypatch.setattr(kbackend, "default_interpret", lambda: False)
    spec = run.load_cell(cell)
    ses = spec.cell["session"]
    cfg = run.program_config(spec.sizes)
    params = _on(one_chip, jax.eval_shape(
        lambda: weights.make(spec.sizes, 0)))
    backend = sectored_decode.SectoredKVBackend(
        cfg, params, seq_len=ses["seq_len"], kernel=ses["kernel"])
    sectored = ses["policy"] != "dense"
    jitted, bound = fused_wave_program(
        backend.sectored_fn_for(None) if sectored else backend.decode_fn)
    state = jax.eval_shape(
        lambda: sectored_decode.init_state(cfg, 1, ses["seq_len"]))
    slots = ses["max_batch"]
    stacked = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((slots,) + x.shape, x.dtype), state)
    wave = jitted.lower(*_on(one_chip, (
        bound, stacked, jax.ShapeDtypeStruct((slots, 1, 1), jnp.int32),
        jax.eval_shape(lambda: SamplerRows.init(slots))))).compile()
    assert ("tpu_custom_call" in wave.as_text()) == sectored
    _report(f"{cell} wave", wave.memory_analysis())
    n = (spec.traffic.get("shared_prefix_tokens", 0)
         or max(spec.traffic["prompt_tokens"]["values"]))
    prefill = jax.jit(lambda p, t: backend._scan_prompt(
        p, sectored_decode.init_state(cfg, 1, ses["seq_len"]), t)).lower(
        params, jax.ShapeDtypeStruct((1, n), jnp.int32,
                                     sharding=one_chip)).compile()
    _report(f"{cell} prefill {n}", prefill.memory_analysis())
    assert _total(wave.memory_analysis()) < HBM_BYTES
    assert _total(prefill.memory_analysis()) < HBM_BYTES


@pytest.mark.parametrize("config", CONFIGS)
def test_every_config_names_a_family(config):
    """The family that the configuration names imports and has every name
    of the contract (``bench/families/__init__.py``)."""
    sizes = families.load(run.BENCH / "configs" / f"{config}.json")
    family = families.of(sizes)
    for name in families.CONTRACT:
        assert hasattr(family, name), name
    assert all(callable(getattr(family, n)) for n in families.CONTRACT
               if n != "KEYS")
    assert "dtype" in family.KEYS
    assert set(family.KEYS) <= set(sizes)
