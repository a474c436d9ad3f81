"""MeshBackend: multi-device wave execution for ``ServeSession``.

The paper's end game is throughput at scale under high memory-level
parallelism (§8): route every access class to the resource that serves it
cheapest. The serving translation of that principle:

* the wave's **slot axis** shards over the mesh's data-parallel axes —
  batch capacity scales with devices while each slot's sectored fetch
  path stays fixed-width per chip;
* the **paged KV cache** additionally spreads its page axis over
  ``'model'`` (storage distributed over the whole mesh); the sectored
  gather then pulls the predictor-selected pages across 'model' shards —
  a device-to-device sector fetch, the VBL transfer crossing chips;
* **prefill** runs on a *donor* device off the wave's critical resources
  (``OverlapScheduler``'s second stream becomes a real second stream),
  and the finished group's KV pages are handed device-to-device into the
  wave placement at admission.

Determinism contract (the cross-mesh oracle, ``tests/test_serve_mesh.py``):
token streams and metered joules are **bit-identical across mesh
shapes** — (1,), (2, 1), (4, 2) all reproduce the single-device stream,
under greedy decoding AND stochastic sampling. That holds because every
cross-shard interaction this placement induces is pure data movement: the
slot axis is vmapped (no cross-slot math), the page-axis shard is only
ever *gathered* (the sectored/exact attend contracts over the gathered
buffer, never over the sharded cache axis), energy derives from host-side
counters, and every RNG key is a counter-based pure function of
``(request_seed, position)`` (``repro.sample.rng``) — placement never
enters a draw. Page sharding is therefore
auto-enabled only for gather-based backends (those exposing ``k_for``,
i.e. ``SectoredKVBackend``); a dense attend contracting over a sharded
sequence axis would reorder float reductions and break the oracle.

``MeshBackend`` is a transparent decorator like
:class:`~repro.telemetry.meters.MeteredBackend` and composes with it in
either order: unknown attributes (``meter``, ``k_for``, ``kv_geometry``,
...) delegate to the wrapped backend.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.parallel import sharding
from repro.serve.backend import (fused_wave_program, split_bound,
                                 vmapped_prefill)


class MeshBackend:
    """Wrap a ``DecodeBackend`` so session waves run sharded over a mesh.

    The session discovers the four optional hooks by ``getattr`` (see
    ``serve.backend.DecodeBackend``): ``wave_for`` (mesh-placed jitted
    wave), ``place_stacked`` (wave-buffer placement), ``place_rows``
    (device-to-device admission handoff), and ``vmapped_prefill`` (donor
    group prefill). A plain backend has none and the session behaves
    exactly as before.
    """

    def __init__(self, inner, mesh, *, shard_pages: bool | None = None,
                 donor_prefill: bool = True):
        self.inner = inner
        self.mesh = mesh
        if shard_pages is None:
            # gather-based data paths only (see module docstring). Probe by
            # CALLING k_for, not by attribute presence: a MeteredBackend
            # always has the method but answers None over a dense inner
            # backend, and a dense attend must never get a sharded page axis
            k_for = getattr(inner, "k_for", None)
            shard_pages = k_for is not None and k_for(None) is not None
        self.shard_pages = shard_pages
        self._token_sharding_cache: dict[tuple, Any] = {}
        self._replicated = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        # donor device for prefill: the last mesh device, so the wave's
        # slot shards (filled from device 0 upward) drain before prefill
        # contention matters on small meshes
        devices = mesh.devices.reshape(-1)
        self._donor = (devices[-1] if donor_prefill else devices[0])
        self._donor_sharding = jax.sharding.SingleDeviceSharding(self._donor)
        self._sharding_cache: dict[tuple, Any] = {}
        self._vp_jit: tuple[Callable, tuple] | None = None
        self._bound_cache: dict[tuple, Any] = {}
        self.prefill_fn = self._donor_prefill
        # NOTE: a meter's mesh_shape provenance stamp is owned by the
        # ServeSession that actually drives waves (it clears the stamp
        # when the same meter is later reused unmeshed) — constructing a
        # wrapper must not mutate shared telemetry state

    # -- mesh identity -----------------------------------------------------

    @property
    def mesh_shape(self) -> tuple[int, ...]:
        return tuple(self.mesh.devices.shape)

    @property
    def donor_device(self):
        """The device prefill streams on (the overlap second stream)."""
        return self._donor

    # -- placement ---------------------------------------------------------

    def wave_shardings(self, stacked: Any):
        """NamedSharding pytree for a slot-stacked state (cached per
        shape/dtype signature — shardings are static per wave layout)."""
        key = tuple((tuple(x.shape), str(x.dtype))
                    for x in jax.tree.leaves(stacked))
        shardings = self._sharding_cache.get(key)
        if shardings is None:
            shardings = sharding.wave_state_shardings(
                self.mesh, stacked, shard_pages=self.shard_pages)
            self._sharding_cache[key] = shardings
        return shardings

    def place_stacked(self, stacked: Any) -> Any:
        """Place (or repair) a wave buffer onto its mesh shardings.

        ``device_put`` onto an already-correct sharding is a no-op, so
        calling this every wave costs a pytree walk, not a transfer.
        """
        return jax.device_put(stacked, self.wave_shardings(stacked))

    def _token_sharding_for(self, shape) -> Any:
        """Token-batch sharding repaired for the concrete (slots, 1, 1)
        shape — an indivisible slot axis degrades to replicated exactly
        like the state leaves do, instead of erroring at device_put."""
        key = tuple(shape)
        sh = self._token_sharding_cache.get(key)
        if sh is None:
            sh = sharding.wave_token_sharding(self.mesh, shape)
            self._token_sharding_cache[key] = sh
        return sh

    def place_rows(self, rows: Any) -> Any:
        """Device-to-device admission handoff: move prefilled rows off the
        donor device and REPLICATE them over the wave's devices so the
        multi-slot admission scatter runs colocated with the sharded wave
        buffer (the scatter keeps the buffer's sharding; each shard then
        reads the rows landing in its slots from its local replica, no
        further transfer). Replication is deliberate simplicity: rows can
        target arbitrary slots, so a slot-exact placement would need the
        scatter's index mapping; the cost is group-size × mesh-size copies
        per admission, paid off the wave's critical path."""
        return jax.device_put(rows, self._replicated)

    # -- wave execution ----------------------------------------------------

    def wave_for(self, fn: Callable, *, sampled: bool = False) -> Callable:
        """Mesh-placed jitted wave for a per-slot step fn.

        Builds the SAME fused pipeline every vectorized session runs
        (``serve.backend.fused_wave_program`` — token selection inside the
        wave executable, ``returns_tokens = True``; ``sampled`` picks
        greedy argmax or the ``repro.sample`` kernel) and adds placement:
        the step's weights are replicated over the mesh once, and the
        stacked state, token batch, and sampler rows are pinned to
        their mesh shardings before each dispatch (output shardings
        propagate, so steady-state waves pay no transfers). Each shard
        selects its own slots' tokens locally, so ONE dispatch per wave
        moves ``(slots,)`` int32 to the host instead of a second eagerly
        dispatched SPMD reduction gathering ``(slots, vocab)`` logits
        across devices. Selection and RNG keys are per-slot pure
        functions (first-max ties, counter-based keys), so tokens stay
        bit-identical to the unmeshed session — greedy *and* sampled
        (the cross-mesh oracle covers both).

        Memoization is the caller's job (``ServeSession._wave_for``
        caches per ``(id(fn), sampled)``); the identity anchors for the
        steady-state short-circuit live in the returned closure, so two
        sessions driving one backend cannot thrash each other's anchors.
        """
        jitted, bound = fused_wave_program(fn, sampled=sampled)
        bound = self._place_bound(bound)
        last_state = last_tokens = last_rows = None

        def wave(stacked, tokens, rows):
            # identity short-circuits: a state/token/rows array this wave
            # itself produced is already placed — steady-state decode
            # re-enters with zero host->device transfers
            nonlocal last_state, last_tokens, last_rows
            if stacked is not last_state:
                stacked = self.place_stacked(stacked)
            if tokens is not last_tokens:
                tokens = jax.device_put(
                    tokens, self._token_sharding_for(tokens.shape))
            if rows is not last_rows:
                # sampler rows are a handful of (slots,) scalars:
                # replicate (like admission handoffs) — the cost is
                # bytes, and the per-slot selection reads only its row
                rows = jax.device_put(rows, self._replicated)
            out, new_state, new_rows = jitted(bound, stacked, tokens, rows)
            last_tokens, last_state, last_rows = out, new_state, new_rows
            return out, new_state, new_rows

        wave.returns_tokens = True
        return wave

    # -- prefill (donor stream) --------------------------------------------

    def _place_bound(self, bound: tuple, *, donor: bool = False) -> tuple:
        """A step's bound weights replicated over the mesh, once per
        weights, so every wave and prefill finds them resident. Each
        device holds ONE copy: a weight already on a mesh device keeps
        its buffer as that device's replica, and the donor prefill
        (``donor=True``) reads the donor device's replica instead of a
        copy of its own."""
        leaves = jax.tree.leaves(bound)
        key = tuple(id(x) for x in leaves)
        hit = self._bound_cache.get(key)
        if hit is None:
            placed = jax.tree.map(self._replicate, bound)
            # the source leaves ride along so their ids stay unique
            hit = (leaves, placed, jax.tree.map(self._donor_replica, placed))
            self._bound_cache[key] = hit
        return hit[2] if donor else hit[1]

    def _replicate(self, x):
        devices = list(self.mesh.devices.flat)
        if isinstance(x, jax.Array) and len(x.devices()) == 1:
            (home,) = x.devices()
            if home in devices:
                return jax.make_array_from_single_device_arrays(
                    x.shape, self._replicated,
                    [x if d == home else jax.device_put(x, d)
                     for d in devices])
        return jax.device_put(x, self._replicated)

    def _donor_replica(self, x):
        """The donor device's shard of a replicated array: a view of its
        buffer, not a copy."""
        return next(s.data for s in x.addressable_shards
                    if s.device == self._donor)

    def _donor_prefill(self, tokens):
        """Single-prompt prefill pinned to the donor device (committed
        inputs and weights make the inner jitted prefill execute there)."""
        step, bound = split_bound(self.inner.prefill_fn)
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32),
                                self._donor_sharding)
        return step(*self._place_bound(bound, donor=True), tokens)

    def vmapped_prefill(self, prompts):
        """Group prefill (ONE vmapped dispatch) on the donor device —
        the scheduler's overlap stream runs here while the decode wave
        occupies the mesh; ``place_rows`` hands the result over at
        install time."""
        if self._vp_jit is None:
            self._vp_jit = vmapped_prefill(self.inner.prefill_fn)
        jitted, bound = self._vp_jit
        prompts = jax.device_put(jnp.asarray(prompts, jnp.int32),
                                 self._donor_sharding)
        return jitted(self._place_bound(bound, donor=True), prompts)

    # -- data-path delegation ----------------------------------------------
    # (identity-stable like MeteredBackend: the session's wave cache keys
    # on id(fn), and wave_for above closes over the delegated identity)

    @property
    def decode_fn(self):
        return self.inner.decode_fn

    @property
    def sectored_fn(self):
        return self.inner.sectored_fn

    @property
    def demand_merge_fn(self):
        return self.inner.demand_merge_fn

    @property
    def supports_sectored(self) -> bool:
        return self.inner.supports_sectored

    def sectored_fn_for(self, topk_frac: float | None):
        return self.inner.sectored_fn_for(topk_frac)

    def merge_demands(self, stacked_state: Any, group_ids: Any) -> Any:
        return self.inner.merge_demands(stacked_state, group_ids)

    def __getattr__(self, name: str):
        # transparent decorator: meter / k_for / kv_geometry / ... pass
        # through so MeshBackend and MeteredBackend compose in either order
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self.inner, name)

    def __repr__(self) -> str:
        return (f"MeshBackend({self.inner!r}, mesh={self.mesh_shape}, "
                f"shard_pages={self.shard_pages})")
