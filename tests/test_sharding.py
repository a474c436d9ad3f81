"""Sharding rules: divisibility repair + roofline HLO parsing.

The FakeMesh tests below are shape-level only — they validate specs
without ever placing an array, so on a single-device host nothing here
used to prove that a real device_put honors them. The real-mesh tests at
the bottom close that gap through the shared ``eight_devices`` fixture
(forced device count in CI's multi-device job; skipped loudly, not
silently, elsewhere).
"""

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.launch import roofline
from repro.parallel import sharding


class FakeMesh:
    axis_names = ("data", "model")
    devices = np.zeros((16, 16))


@settings(max_examples=60, deadline=None)
@given(
    st.lists(st.sampled_from([64, 128, 10, 4, 1, 7, 4096]), min_size=1,
             max_size=4),
    st.lists(st.sampled_from([None, "data", "model"]), min_size=0, max_size=4),
)
def test_fix_spec_always_divisible(shape, axes):
    spec = P(*axes)
    fixed = sharding.fix_spec(spec, tuple(shape), FakeMesh())
    sizes = {"data": 16, "model": 16}
    for dim, entry in zip(shape, tuple(fixed) + (None,) * 4):
        if entry is None:
            continue
        entries = entry if isinstance(entry, tuple) else (entry,)
        prod = 1
        for a in entries:
            prod *= sizes[a]
        assert dim % prod == 0


def test_fix_spec_moves_model_to_contraction_dim():
    # GQA: kv heads (4) < TP (16) -> model moves to the 4096 input dim
    fixed = sharding.fix_spec(P(None, "model", None), (4096, 4, 128),
                              FakeMesh())
    assert tuple(fixed) == ("model", None, None)


def test_collective_bytes_parser():
    hlo = """
  %ar = bf16[256,1024] all-reduce(bf16[256,1024] %x), replica_groups={}
  %ag = f32[16,128]{1,0} all-gather(f32[2,128] %y), dimensions={0}
  %rs = bf16[8,8] reduce-scatter(bf16[64,8] %z), dimensions={0}
  %cp = f32[4,4] collective-permute(f32[4,4] %w)
  %ars = bf16[256,1024] all-reduce-start(bf16[256,1024] %x2)
  %notacoll = f32[999,999] add(f32[999,999] %a, f32[999,999] %b)
"""
    got = roofline.collective_bytes(hlo)
    assert got["all-reduce"] == 2 * 256 * 1024 * 2  # incl. -start variant
    assert got["all-gather"] == 16 * 128 * 4
    assert got["reduce-scatter"] == 8 * 8 * 2
    assert got["collective-permute"] == 4 * 4 * 4
    assert got["all-to-all"] == 0


def test_param_shardings_cover_all_archs():
    """Every arch's param pytree gets valid NamedShardings on a 16x16 mesh
    (shape-level check, no devices needed)."""
    from repro import configs
    from repro.models import model

    mesh = FakeMesh()
    for name in configs.ARCHS:
        cfg = configs.get(name)
        shapes = jax.eval_shape(
            lambda c=cfg: model.init_params(c, jax.random.key(0)))

        def one(path, leaf):
            spec = sharding.fix_spec(
                sharding.param_spec(path, leaf, None), leaf.shape, mesh)
            sizes = {"data": 16, "model": 16}
            for dim, entry in zip(leaf.shape, tuple(spec)):
                if entry is None:
                    continue
                entries = entry if isinstance(entry, tuple) else (entry,)
                prod = 1
                for a in entries:
                    prod *= sizes[a]
                assert dim % prod == 0, (name, path, leaf.shape, spec)
            return spec

        jax.tree_util.tree_map_with_path(one, shapes)


# -- real-mesh assertions (8 forced devices; see tests/conftest.py) ----------

from conftest import spec_entry_axes as _axes_of  # noqa: E402


def test_wave_state_shardings_on_real_mesh(eight_devices):
    """wave_state_shardings on an actual (4, 2) device mesh: slot axis
    over 'data' on every leaf, KV page axis over 'model', and a real
    device_put distributes the data accordingly (shard shapes checked,
    not just specs)."""
    import jax.numpy as jnp

    from repro.launch import mesh as mesh_mod

    mesh = mesh_mod.make_mesh((4, 2), ("data", "model"))
    stacked = dict(
        k=jnp.zeros((8, 2, 1, 512, 2, 16), jnp.bfloat16),
        v=jnp.zeros((8, 2, 1, 512, 2, 16), jnp.bfloat16),
        table=jnp.zeros((8, 2, 1, 2, 4), jnp.float32),
        position=jnp.zeros((8, 1), jnp.int32),
    )
    shardings = sharding.wave_state_shardings(mesh, stacked)
    for name in ("k", "v"):
        spec = shardings[name].spec
        assert _axes_of(spec[0]) == ("data",)
        assert _axes_of(spec[3]) == ("model",)
    assert _axes_of(shardings["table"].spec[0]) == ("data",)
    assert _axes_of(shardings["position"].spec[0]) == ("data",)

    placed = jax.device_put(stacked, shardings)
    k = placed["k"]
    assert len(k.sharding.device_set) == 8
    shard_shapes = {s.data.shape for s in k.addressable_shards}
    assert shard_shapes == {(2, 2, 1, 256, 2, 16)}  # slot/4, pages/2
    # position: slot axis over data, replicated over model
    pos_shapes = {s.data.shape for s in placed["position"].addressable_shards}
    assert pos_shapes == {(2, 1)}

    # indivisible slot axis degrades to replicated instead of erroring
    odd = dict(position=jnp.zeros((3,), jnp.int32))
    odd_sharding = sharding.wave_state_shardings(mesh, odd)["position"]
    assert _axes_of(odd_sharding.spec[0] if odd_sharding.spec else None) == ()

    # regression: an indivisible PAGE axis drops 'model' outright — it
    # must never be re-homed onto another dim (fix_spec's re-placement
    # could land it on a contraction dim and reorder float reductions,
    # breaking the cross-mesh bitwise oracle)
    odd_kv = dict(k=jnp.zeros((8, 2, 1, 511, 2, 16), jnp.bfloat16))
    odd_spec = sharding.wave_state_shardings(mesh, odd_kv)["k"].spec
    flat = [a for e in odd_spec for a in _axes_of(e)]
    assert "model" not in flat, odd_spec
    assert _axes_of(odd_spec[0]) == ("data",)


def test_sectored_state_shardings_real_mesh_matches_decode_rules(
        eight_devices):
    """The refactored sectored_state_shardings (shared by
    make_sectored_decode_step) keeps the decode-state placement rules on a
    real mesh: KV batch over 'data', sequence over 'model'."""
    from repro.launch import mesh as mesh_mod

    mesh = mesh_mod.make_mesh((4, 2), ("data", "model"))
    state_shape = dict(
        k=jax.ShapeDtypeStruct((2, 4, 512, 2, 16), np.dtype("bfloat16")),
        table=jax.ShapeDtypeStruct((2, 4, 2, 4), np.dtype("float32")),
        position=jax.ShapeDtypeStruct((4,), np.dtype("int32")),
    )
    specs = sharding.sectored_state_shardings(mesh, state_shape)
    assert _axes_of(specs["k"].spec[1]) == ("data",)
    assert _axes_of(specs["k"].spec[2]) == ("model",)
    assert _axes_of(specs["table"].spec[1]) == ("data",)
    assert _axes_of(specs["position"].spec[0]) == ("data",)
    # long-context: sequence over every axis, batch replicated
    lc = sharding.sectored_state_shardings(mesh, state_shape,
                                           long_context=True)
    assert set(_axes_of(lc["k"].spec[2])) == {"data", "model"}
