"""DecodeBackend: *how the chip executes* — the data path of the serving
stack (paper §8.1's execution layer).

A backend bundles the four callables the old ``Engine`` constructor took
loose (``prefill_fn`` / ``decode_fn`` / ``sectored_decode_fn`` /
``demand_merge_fn``) into one object, so schedulers and policies can be
swapped without re-wiring the data path. ``ServingBackend`` is the plain
container; ``runtime.sectored_decode.make_serving_fns`` builds the
SectoredState-backed subclass that can also re-specialize its sectored
step for a policy-requested top-k fraction.

This module also owns the **fused wave pipeline** shared by every wave
flavor: :func:`fused_select_step` composes a per-slot decode step with
on-device token selection (greedy first-max, or the full
``repro.sample`` kernel), and :func:`make_fused_wave` jits its vmap —
the single-device wave the session builds by default. This is the
``returns_tokens`` pipeline ``serve.mesh_backend.MeshBackend``
introduced (measured ~1.3x over host-side selection), promoted out of
the mesh so every vectorized session inherits it; the MeshBackend now
wraps the same ``fused_select_step`` with placement on top.

This module is deliberately leaf-level: it imports nothing from
``repro.runtime`` (the runtime imports *us* to construct backends);
``repro.sample`` is a leaf package (jax-only).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Iterator, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.sample import SamplerRows, sample_from_logits, token_logprob


@runtime_checkable
class DecodeBackend(Protocol):
    """The data path: prefill, dense decode, sectored decode, demand merge.

    ``decode_fn`` / ``sectored_fn`` take ``(state, token)`` with ``token``
    shaped ``(B, 1)`` and return ``(logits, new_state)``; ``prefill_fn``
    takes ``(B, S)`` prompt tokens and returns ``(logits, state)``. States
    are arbitrary pytrees — the session stacks them along a fresh leading
    slot axis without knowing their internal layout.

    Optional attributes extend the protocol (discovered via ``getattr``,
    never required):

    * ``meter`` — a :class:`~repro.telemetry.meters.WaveMeter` the session
      drives around each wave (:class:`~repro.telemetry.meters.
      MeteredBackend` is the decorator that adds one to any backend);
    * ``k_for(topk_frac)`` — the concrete page budget a policy fraction
      resolves to, which the meter charges fetch energy for;
    * the mesh hooks a :class:`~repro.serve.mesh_backend.MeshBackend`
      carries: ``wave_for(fn, sampled=...)`` (mesh-placed jitted wave),
      ``place_stacked(stacked)`` (wave-buffer placement),
      ``place_rows(rows)`` (device-to-device admission handoff), and
      ``vmapped_prefill(prompts)`` (donor-device group prefill).

    Wave contract (what ``wave_for`` must return, and what the session's
    default :func:`make_fused_wave` builds): a callable
    ``wave(stacked_state, tokens, sampler_rows) -> (tokens_out,
    new_state, new_rows)`` with ``returns_tokens = True`` — token
    selection (greedy argmax or the ``repro.sample`` kernel, chosen by
    the ``sampled`` flag at build time) runs *inside* the wave
    executable, so one dispatch per wave moves ``(slots,)`` int32 to the
    host instead of ``(slots, vocab)`` logits.
    """

    prefill_fn: Callable
    decode_fn: Callable
    sectored_fn: Callable | None
    demand_merge_fn: Callable | None

    @property
    def supports_sectored(self) -> bool: ...

    def sectored_fn_for(self, topk_frac: float | None) -> Callable: ...

    def merge_demands(self, stacked_state: Any, group_ids: Any) -> Any: ...


class ServingBackend:
    """Concrete DecodeBackend over four loose callables.

    Iterable as the legacy ``(prefill_fn, decode_fn, sectored_fn,
    demand_merge_fn)`` 4-tuple so pre-redesign call sites keep working.
    """

    def __init__(self, prefill_fn: Callable, decode_fn: Callable,
                 sectored_fn: Callable | None = None,
                 demand_merge_fn: Callable | None = None, *,
                 vocab: int | None = None):
        self.prefill_fn = prefill_fn
        self.decode_fn = decode_fn
        self.sectored_fn = sectored_fn
        self.demand_merge_fn = demand_merge_fn
        # vocabulary bound, when known — ServeSession.submit uses it to
        # reject stop tokens that could never match an emitted token
        # (SectoredKVBackend supplies cfg.vocab; None = unvalidated)
        self.vocab = vocab

    @property
    def supports_sectored(self) -> bool:
        return self.sectored_fn is not None

    def sectored_fn_for(self, topk_frac: float | None) -> Callable:
        """The sectored step honoring a policy-requested top-k fraction.

        The base backend has one fixed sectored callable and ignores the
        hint; backends that compile per-k variants (see
        ``runtime.sectored_decode.SectoredKVBackend``) override this.
        Per-k backends may additionally carry a **kernel flavor**
        (``SectoredKVBackend.KERNELS``): ``"dispatch"`` runs the batched
        gather+attend formulation, ``"fused"`` runs the single Pallas
        kernel (scalar-prefetched page steering + per-page DMA + softmax
        attend; bit-exact with dispatch), and ``"fused_q8"`` adds
        per-sector int8 KV dequant inside the kernel (tolerance-gated,
        not bit-exact — see docs/serving.md). The flavor is a backend
        construction choice; ``sectored_fn_for`` returns steps of
        whatever flavor the backend was built with, falling back to
        dispatch only for the exact (all-pages) path where the fused
        kernel's top-k steering does not apply.
        """
        if self.sectored_fn is None:
            raise ValueError("backend has no sectored decode path")
        return self.sectored_fn

    def merge_demands(self, stacked_state: Any, group_ids: Any) -> Any:
        if self.demand_merge_fn is None:
            return stacked_state
        return self.demand_merge_fn(stacked_state, group_ids)

    def __iter__(self) -> Iterator[Callable | None]:
        return iter((self.prefill_fn, self.decode_fn, self.sectored_fn,
                     self.demand_merge_fn))

    def __repr__(self) -> str:
        return (f"{type(self).__name__}(sectored={self.supports_sectored}, "
                f"merge={self.demand_merge_fn is not None})")


# -- weights as arguments ---------------------------------------------------


def split_bound(fn: Callable) -> tuple[Callable, tuple]:
    """Split a step bound with :func:`functools.partial` into ``(step,
    bound args)``.

    Serving steps bind their weights as ``partial(step, params)`` so the
    session can call them as ``(state, token)``. A program that calls such
    a step inside its own ``jax.jit`` — a wave, a vmapped prefill — must
    not close over the weights: ``jit`` embeds closed-over arrays in the
    program as constants, and at real widths that is gigabytes per
    program. It passes the bound args to its ``jit`` as arguments instead
    (unbatched under ``vmap``). A plain callable binds nothing.
    """
    if isinstance(fn, functools.partial):
        return functools.partial(fn.func, **fn.keywords), fn.args
    return fn, ()


def vmapped_prefill(prefill_fn: Callable) -> tuple[Callable, tuple]:
    """``(jitted, bound)``: ``jitted(bound, prompts)`` prefills a stack of
    equal-length prompts ``(n, S)`` in ONE dispatch, the prefill's bound
    weights passed as an argument."""
    step, bound = split_bound(prefill_fn)
    jitted = jax.jit(jax.vmap(lambda b, p: step(*b, p[None, :]),
                              in_axes=(None, 0)))
    return jitted, bound


# -- fused wave pipeline (shared by single-device sessions + MeshBackend) ----


def fused_select_step(fn: Callable, *, sampled: bool = False) -> Callable:
    """Per-slot decode step with token selection fused in.

    Wraps ``fn(*bound, state, token) -> (logits, new_state)`` into
    ``fused(bound, state, token, row) -> (tok, new_state, advanced_row)``
    where ``bound`` is the tuple of leading args :func:`split_bound` split
    off (the weights; ``()`` for a plain step) and ``tok`` keeps the
    token's ``(1, 1)`` row shape so a stacked wave output can feed the
    next wave directly (device-side token feedback).

    ``sampled=False`` builds the pure greedy pipeline — per-slot
    first-max argmax exactly like the host ``np.argmax`` it replaces,
    and exactly the selection MeshBackend's original fused wave ran —
    with no sampling math in the executable, so greedy-only serving
    pays nothing for the sampler's existence. ``sampled=True`` swaps in
    the full ``repro.sample`` kernel; its greedy *branch* is the same
    first-max argmax, which keeps a greedy request's tokens invariant to
    whether stochastic requests share its wave. Both flavors advance the
    per-slot RNG counter in lockstep with the emitted token; inactive
    slots advancing too is inert (counter-based keys mean no shared
    stream exists to burn, and admission rewrites the row — see
    ``repro.sample.rng``).

    **Stop mask** (the EOS contract, folded into the wave): each row
    carries its request's ``stop`` token set (``SamplerRows.stop``,
    ``NO_STOP``-padded). A slot whose *input* token — the one it emitted
    last wave, possibly fed back device-side — hits its stop set is
    finished: the guard re-emits that stop token unchanged and holds the
    slot's RNG counter (``advance(hold)``), so a completed slot can
    never emit a post-EOS token nor burn RNG positions, no matter how
    long host bookkeeping leaves it resident. The session normally
    vacates a stopped slot before the next wave (freeing its KV pages),
    so in steady state the guard is the wave-level enforcement of what
    the host already did — which is exactly why it must freeze token
    and counter *together*: the pre-fused reference wave
    (``fuse_wave=False``) relies on host-side vacating alone, and any
    counter drift between the two flavors would desync their streams.
    """
    if sampled:
        def select(logits, row: SamplerRows):
            return sample_from_logits(logits, row)
    else:
        def select(logits, row: SamplerRows):
            return jnp.argmax(
                logits.reshape(-1, logits.shape[-1])[0]).astype(jnp.int32)

    def fused(bound, state, token, row: SamplerRows):
        logits, new_state = fn(*bound, state, token)
        tok = select(logits, row).reshape(1, 1)
        stopped = jnp.any(token.reshape(-1)[-1] == row.stop)
        tok = jnp.where(stopped, token.reshape(1, 1), tok)
        # per-token logprob rides out in the row (same `token_logprob`
        # kernel as the pre-fused select_tokens, so the fused ==
        # pre-fused oracle covers it); a held slot's logp is frozen at
        # 0 like its token/counter — the host never reads it
        lp = jnp.where(stopped, jnp.float32(0.0),
                       token_logprob(logits, tok.reshape(())))
        advanced = row.advance(hold=stopped)
        return tok, new_state, dataclasses.replace(advanced, logp=lp)

    return fused


def fused_wave_program(fn: Callable, *, sampled: bool = False
                       ) -> tuple[Callable, tuple]:
    """``(jitted, bound)``: ``jitted(bound, stacked, tokens, rows)`` is the
    ``jit(vmap)`` of :func:`fused_select_step` over the slot axis, with
    the step's bound weights an unbatched argument.

    **The wave consumes its input state.** ``stacked`` is donated, so XLA
    aliases every state leaf to the matching output leaf: the layer loop
    writes each token's K/V (or latent) row into the input buffer, and
    no copy of the whole cache into a fresh output is made. After the
    call every leaf of ``stacked`` is deleted; a caller must never read
    it again, only the returned state. Its two callers hold to this:
    ``ServeSession`` (through :func:`make_fused_wave`) replaces its
    stacked state with the output, and ``MeshBackend.wave_for`` keeps
    only the output as its placement anchor. ``bound`` (the weights),
    ``tokens`` (the previous wave's output, fed back) and ``rows`` are
    not donated. The pre-fused reference wave (``fuse_wave=False``)
    stays undonated, as the oracle the tests compare against, and so
    does the warm prefill, whose input aliases the prefix cache's donor
    buffers."""
    step, bound = split_bound(fn)
    jitted = jax.jit(jax.vmap(fused_select_step(step, sampled=sampled),
                              in_axes=(None, 0, 0, 0)),
                     donate_argnums=(1,))
    return jitted, bound


def make_fused_wave(fn: Callable, *, sampled: bool = False) -> Callable:
    """Default (single-device) fused wave: ``jit(vmap)`` of
    :func:`fused_select_step`, advertising ``returns_tokens``.

    This is the promotion of MeshBackend's measured ~1.3x fused
    pipeline to the shared vectorized path — a MeshBackend's
    ``wave_for`` builds the same per-slot step and adds placement.
    Memoization is the caller's job (``ServeSession._wave_for`` caches
    per ``(id(fn), sampled)``).

    Like :func:`fused_wave_program`, the returned ``wave`` consumes its
    input state: ``stacked`` is donated and deleted by the call, so the
    caller keeps only the ``new_state`` it returns.
    """
    jitted, bound = fused_wave_program(fn, sampled=sampled)

    def wave(stacked, tokens, rows: SamplerRows):
        return jitted(bound, stacked, tokens, rows)

    wave.returns_tokens = True
    return wave
