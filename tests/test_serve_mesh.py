"""Mesh-sharded decode waves: the cross-mesh equivalence oracle.

The multi-device serving contract (see ``serve/mesh_backend.py``): a
``MeshBackend`` shards the wave's slot axis over the mesh's data axes and
the paged KV over ``('data', 'model')``, prefill streams on a donor
device, and NONE of it may change what the session generates or meters —
token streams and per-request joules are bit-identical across mesh
shapes (1,), (2, 1), (4, 2) for both shipped schedulers, under greedy
decoding AND stochastic sampling (counter-based RNG keys are pure
functions of (request_seed, position) — see ``repro.sample``).

Every cross-shard interaction the placement induces is pure data
movement (vmapped slot axis, gather-only page shards, host-side energy
counters), which is why the oracle can demand ``==`` rather than
allclose.

The oracle needs 8 devices (``eight_devices`` fixture — forced on CPU by
the CI ``multi-device`` job); the single-device-mesh equivalence test
runs everywhere.
"""

import jax
import numpy as np
import pytest
from conftest import spec_axes, spec_entry_axes

from repro import configs
from repro.launch import mesh as mesh_mod
from repro.models import model
from repro.runtime import sectored_decode
from repro.serve import (AlwaysSectored, FifoScheduler, MeshBackend,
                         OverlapScheduler, Request, SamplerSpec,
                         ServeSession)
from repro.telemetry import MeteredBackend

MESH_SHAPES = ("1", "2x1", "4x2")


def _sampler_for(rid: int) -> SamplerSpec | None:
    """Deterministic mixed-batch sampler assignment: odd rids sample
    (distinct seeds/specs), even rids stay greedy — one fused wave
    carries both."""
    if rid % 2 == 0:
        return None
    return SamplerSpec(temperature=0.8 + 0.1 * (rid % 3),
                       top_k=0 if rid % 4 == 1 else 16,
                       top_p=0.95, seed=1000 + rid)


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get("yi-6b").reduced(n_layers=2, d_model=64, n_heads=4,
                                       n_kv_heads=2, d_ff=128, vocab=128,
                                       head_dim=32)
    params = model.init_params(cfg, jax.random.key(0))
    return cfg, params


def _run(cfg, params, *, mesh_spec, scheduler_cls, n_requests=12,
         max_batch=8, max_new_tokens=5, seed=3, sampled=False,
         kernel="dispatch", seq_len=48, prompt_len=6):
    """One drained metered session; returns (tokens, joules, session).

    ``sampled=True`` attaches the deterministic mixed greedy+sampled
    specs of :func:`_sampler_for` — the stochastic arm of the oracle.
    ``kernel``/``seq_len``/``prompt_len`` drive the fused-kernel arm: the
    fused Pallas step only engages when the cache spans multiple pages
    and the predictor selects a strict subset, which needs prompts well
    past one ``PAGE_SIZE``."""
    inner = sectored_decode.make_serving_fns(cfg, params=params,
                                             seq_len=seq_len, kernel=kernel)
    backend = MeteredBackend(inner)
    if mesh_spec is not None:
        backend = MeshBackend(backend,
                              mesh_mod.make_serving_mesh(mesh_spec))
    sess = ServeSession(backend, max_batch=max_batch,
                        scheduler=scheduler_cls(), policy=AlwaysSectored())
    rng = np.random.default_rng(seed)
    handles = [sess.submit(Request(
        rid, rng.integers(0, cfg.vocab, size=prompt_len).astype(np.int32),
        max_new_tokens=max_new_tokens,
        sampler=_sampler_for(rid) if sampled else None))
        for rid in range(n_requests)]
    sess.run_until_drained()
    assert all(h.done for h in handles)
    tokens = {h.rid: tuple(h.peek()) for h in handles}
    joules = {h.rid: h.energy_j for h in handles}
    return tokens, joules, sess


# -- runs everywhere (tier-1, single device) ---------------------------------


def test_mesh_parse_and_validation():
    assert mesh_mod.parse_mesh_shape("4x2") == ((4, 2), ("data", "model"))
    assert mesh_mod.parse_mesh_shape("2") == ((2,), ("data",))
    with pytest.raises(ValueError, match="mesh spec"):
        mesh_mod.parse_mesh_shape("4x2x1")
    with pytest.raises(ValueError, match="mesh spec"):
        mesh_mod.parse_mesh_shape("abc")
    with pytest.raises(ValueError, match="devices"):
        mesh_mod.make_serving_mesh(str(jax.device_count() * 16))


def test_single_device_mesh_matches_plain_backend(setup):
    """A (1,) mesh is the degenerate case: MeshBackend must reproduce the
    plain backend's tokens AND joules exactly — this is the oracle's
    anchor and it runs on any host."""
    cfg, params = setup
    for scheduler_cls in (FifoScheduler, OverlapScheduler):
        ref_t, ref_j, _ = _run(cfg, params, mesh_spec=None,
                               scheduler_cls=scheduler_cls, n_requests=6)
        t, j, sess = _run(cfg, params, mesh_spec="1",
                          scheduler_cls=scheduler_cls, n_requests=6)
        assert t == ref_t
        assert j == ref_j  # bit-identical, not approx
        assert sess.mesh is not None
        assert sess.meter.mesh_shape == (1,)


def test_single_device_mesh_sampled_matches_plain_backend(setup):
    """The sampled anchor of the cross-mesh oracle, runnable on any
    host: a (1,) mesh reproduces the unmeshed mixed greedy+sampled
    streams and joules bit-identically (counter-based RNG keys never see
    the placement), and the sampled arm genuinely diverges from greedy."""
    cfg, params = setup
    ref_t, ref_j, _ = _run(cfg, params, mesh_spec=None,
                           scheduler_cls=OverlapScheduler, n_requests=6,
                           sampled=True)
    t, j, sess = _run(cfg, params, mesh_spec="1",
                      scheduler_cls=OverlapScheduler, n_requests=6,
                      sampled=True)
    assert t == ref_t
    assert j == ref_j
    assert sess.mesh is not None
    greedy_t, _, _ = _run(cfg, params, mesh_spec=None,
                          scheduler_cls=OverlapScheduler, n_requests=6)
    assert any(t[rid] != greedy_t[rid] for rid in (1, 3, 5))
    assert all(t[rid] == greedy_t[rid] for rid in (0, 2, 4))


def test_mesh_backend_is_transparent_decorator(setup):
    """Protocol surface passes through in both composition orders."""
    cfg, params = setup
    inner = sectored_decode.make_serving_fns(cfg, params=params, seq_len=48)
    mesh = mesh_mod.make_serving_mesh("1")
    meshed = MeshBackend(MeteredBackend(inner), mesh)
    metered = MeteredBackend(MeshBackend(inner, mesh))
    for backend in (meshed, metered):
        assert backend.supports_sectored
        assert backend.k_for(1.0) == inner.k_for(1.0)
        assert backend.decode_fn is inner.decode_fn
        assert backend.sectored_fn_for(None) is inner.sectored_fn
        # mesh provenance is stamped by the session that drives the waves
        # (works in both composition orders)
        assert ServeSession(backend, max_batch=2).meter.mesh_shape == (1,)
    # ... and cleared again when the same meter is reused unmeshed
    assert ServeSession(meshed.inner, max_batch=2).meter.mesh_shape is None
    # page sharding auto-enables only for gather-based (k_for) backends
    assert MeshBackend(inner, mesh).shard_pages is True
    from repro.serve import ServingBackend
    from repro.telemetry import KVGeometry
    dense = ServingBackend(lambda t: None, lambda s, t: None)
    assert MeshBackend(dense, mesh).shard_pages is False
    # regression: MeteredBackend always HAS a k_for method but resolves
    # None over a dense inner — detection must probe the answer, not the
    # attribute, or --telemetry --mesh would page-shard a dense attend
    metered_dense = MeteredBackend(dense, geometry=KVGeometry(
        page_size=4, total_pages=8, page_kv_bytes=512.0, n_layers=2))
    assert MeshBackend(metered_dense, mesh).shard_pages is False
    assert MeshBackend(MeteredBackend(inner), mesh).shard_pages is True


@pytest.mark.parametrize("spec", ["1", "4x2"])
def test_weights_held_once_per_device(setup, spec, request):
    """Waves and donor prefill share ONE copy of the weights per device:
    the replica on the device the weights already live on is their own
    buffer, and the donor prefill reads the donor's replica."""
    if spec != "1":
        request.getfixturevalue("eight_devices")
    cfg, params = setup
    inner = sectored_decode.make_serving_fns(cfg, params=params, seq_len=48)
    backend = MeshBackend(inner, mesh_mod.make_serving_mesh(spec))
    wave = backend._place_bound((params,))
    donor = backend._place_bound((params,), donor=True)
    for p, w, d in zip(jax.tree.leaves(params), jax.tree.leaves(wave),
                       jax.tree.leaves(donor)):
        assert w.is_fully_replicated
        assert len(w.sharding.device_set) == backend.mesh.devices.size
        home = {s.device: s.data.unsafe_buffer_pointer()
                for s in w.addressable_shards}
        (p_dev,) = p.devices()
        assert home[p_dev] == p.unsafe_buffer_pointer()
        assert d.devices() == {backend.donor_device}
        assert d.unsafe_buffer_pointer() == home[backend.donor_device]
    # placed once: a second wave or prefill finds the same arrays
    assert backend._place_bound((params,)) is wave


# -- needs 8 devices (CI multi-device job) -----------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("scheduler_cls", [FifoScheduler, OverlapScheduler],
                         ids=["fifo", "overlap"])
def test_cross_mesh_oracle_tokens_and_joules(setup, eight_devices,
                                             scheduler_cls):
    """THE acceptance oracle: same prompts on mesh shapes (1,), (2, 1),
    (4, 2) produce bit-identical token streams and bit-identical
    ``StreamHandle.energy_j`` for both schedulers; the unmeshed session is
    the reference."""
    cfg, params = setup
    ref_tokens, ref_joules, _ = _run(cfg, params, mesh_spec=None,
                                     scheduler_cls=scheduler_cls)
    for spec in MESH_SHAPES:
        tokens, joules, sess = _run(cfg, params, mesh_spec=spec,
                                    scheduler_cls=scheduler_cls)
        assert tokens == ref_tokens, f"token stream diverged on mesh {spec}"
        assert joules == ref_joules, f"joules diverged on mesh {spec}"
        shape = tuple(int(x) for x in spec.split("x"))
        assert sess.meter.mesh_shape == shape
        assert sess.meter.report()["mesh_shape"] == list(shape)
        if scheduler_cls is OverlapScheduler:
            assert sess.stats["overlapped_prefills"] >= 1


@pytest.mark.slow
@pytest.mark.parametrize("scheduler_cls", [FifoScheduler, OverlapScheduler],
                         ids=["fifo", "overlap"])
def test_cross_mesh_oracle_sampled_tokens_and_joules(setup, eight_devices,
                                                     scheduler_cls):
    """The sampled acceptance oracle: a mixed greedy+sampled batch (fixed
    SamplerSpecs + seeds) produces bit-identical token streams AND
    bit-identical per-request joules across mesh shapes (1,), (2, 1),
    (4, 2) for both schedulers — stochastic decoding keeps every
    guarantee the greedy oracle established, because each draw is keyed
    only on (request_seed, position)."""
    cfg, params = setup
    ref_tokens, ref_joules, _ = _run(cfg, params, mesh_spec=None,
                                     scheduler_cls=scheduler_cls,
                                     n_requests=8, sampled=True)
    for spec in MESH_SHAPES:
        tokens, joules, sess = _run(cfg, params, mesh_spec=spec,
                                    scheduler_cls=scheduler_cls,
                                    n_requests=8, sampled=True)
        assert tokens == ref_tokens, \
            f"sampled token stream diverged on mesh {spec}"
        assert joules == ref_joules, \
            f"sampled joules diverged on mesh {spec}"
        assert sess.meter.mesh_shape == tuple(
            int(x) for x in spec.split("x"))


@pytest.mark.slow
@pytest.mark.parametrize("scheduler_cls", [FifoScheduler, OverlapScheduler],
                         ids=["fifo", "overlap"])
def test_cross_mesh_oracle_fused_kernel(setup, eight_devices, scheduler_cls):
    """The fused-kernel arm of the cross-mesh oracle: with
    ``kernel='fused'`` the whole sectored attend runs as one Pallas call
    whose page DMA is steered by scalar-prefetched predictor indices —
    and the placement must still be invisible. The reference is the
    unmeshed DISPATCH backend, so this asserts fused == dispatch AND
    mesh-invariance in one sweep (tokens and joules, ``==`` not approx).
    Long prompts (200 tokens over a 3-page cache) force the fused step
    to actually engage; at the other tests' seq_len=48 the single-page
    cache always falls back to dispatch."""
    cfg, params = setup
    kw = dict(scheduler_cls=scheduler_cls, n_requests=6, max_batch=4,
              seq_len=384, prompt_len=200)
    ref_tokens, ref_joules, _ = _run(cfg, params, mesh_spec=None,
                                     kernel="dispatch", **kw)
    for spec in (None,) + MESH_SHAPES:
        tokens, joules, _ = _run(cfg, params, mesh_spec=spec,
                                 kernel="fused", **kw)
        assert tokens == ref_tokens, \
            f"fused token stream diverged from dispatch on mesh {spec}"
        assert joules == ref_joules, \
            f"fused joules diverged from dispatch on mesh {spec}"


def test_wave_buffer_lands_on_mesh_shardings(setup, eight_devices):
    """After admission the session's stacked wave buffer is actually
    sharded: slot axis over 'data' on every leaf, KV page axis over
    'model' — asserted on the live buffer's NamedShardings."""
    cfg, params = setup
    inner = sectored_decode.make_serving_fns(cfg, params=params, seq_len=48)
    mesh = mesh_mod.make_serving_mesh("4x2")
    sess = ServeSession(MeshBackend(inner, mesh), max_batch=8,
                        policy=AlwaysSectored())
    rng = np.random.default_rng(0)
    for rid in range(8):
        sess.submit(Request(rid,
                            rng.integers(0, cfg.vocab, size=6).astype(np.int32),
                            max_new_tokens=4))
    sess.step()  # admit + one wave: outputs carry propagated shardings
    assert sess.active_slots() == list(range(8))

    def entry_axes(spec, i):
        return spec_entry_axes(spec[i] if i < len(spec) else None)

    kv = sess.batched.kv
    k_spec = kv.k.sharding.spec
    assert "data" in spec_axes(k_spec), k_spec
    assert "model" in spec_axes(k_spec), k_spec
    assert entry_axes(k_spec, 0) == ("data",)  # slot axis
    # page axis (third-from-last) carries the model shard
    assert entry_axes(k_spec, kv.k.ndim - 3) == ("model",)
    table_spec = sess.batched.table.sharding.spec
    assert entry_axes(table_spec, 0) == ("data",)
    # the buffer is genuinely distributed: more than one addressable shard
    assert len(kv.k.sharding.device_set) == 8


def test_indivisible_max_batch_degrades_not_crashes(setup, eight_devices):
    """max_batch that does not divide the mesh's data axis must degrade
    (slot axis replicated, tokens included) and still reproduce the
    unmeshed stream — regression for a device_put crash on the token
    batch, whose sharding skipped the divisibility repair the state
    leaves get."""
    cfg, params = setup
    ref_t, ref_j, _ = _run(cfg, params, mesh_spec=None,
                           scheduler_cls=OverlapScheduler, n_requests=9,
                           max_batch=6)
    t, j, _ = _run(cfg, params, mesh_spec="4x2",
                   scheduler_cls=OverlapScheduler, n_requests=9,
                   max_batch=6)
    assert t == ref_t
    assert j == ref_j


def test_overlap_prefill_streams_on_donor_device(setup, eight_devices):
    """The overlap second stream is real: group prefill executes on the
    backend's donor device (off the wave's slot shards), and the
    device-to-device handoff at install preserves token equivalence."""
    cfg, params = setup
    inner = sectored_decode.make_serving_fns(cfg, params=params, seq_len=48)
    backend = MeshBackend(inner, mesh_mod.make_serving_mesh("4x2"))
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab, size=(3, 6)).astype(np.int32)
    logits, stacked = backend.vmapped_prefill(prompts)
    donor = backend.donor_device
    assert donor is backend.mesh.devices.reshape(-1)[-1]
    for leaf in jax.tree.leaves(stacked):
        assert leaf.sharding.device_set == {donor}
    # handoff: rows leave the donor and cover the wave devices
    placed = backend.place_rows(stacked)
    for leaf in jax.tree.leaves(placed):
        assert len(leaf.sharding.device_set) == 8
