"""ServeSession: the serving facade — policy/mechanism split per §8.1.

The session composes three pluggable protocols:

* :class:`~repro.serve.backend.DecodeBackend` — *how the chip executes*:
  prefill / dense decode / sectored decode / demand merge as one object.
* :class:`~repro.serve.scheduler.Scheduler` — *when accesses issue*: slot
  admission and wave composition (FIFO, or prefill/decode overlap).
* :class:`~repro.serve.policy.SectorPolicy` — *what the controller
  fetches*: the dynamic sectored-on/off decision incl. hysteresis and
  top-k fraction.

``submit()`` returns a :class:`StreamHandle` (``poll()`` for new tokens,
``tokens()`` for a driving iterator) instead of mutating the submitted
``Request`` in place; the legacy ``Engine``/``LoopedEngine`` shims in
``repro.serve.engine`` opt back into in-place mutation via
``bind_request=True``.

Wave execution comes in two flavors: vectorized (per-slot states stacked
along a fresh leading slot axis, ONE ``jit(vmap)`` decode call per step)
and looped (``max_batch`` sequential calls — the equivalence oracle).
The vectorized wave is **fused** by default: token selection (greedy
argmax, or the ``repro.sample`` kernel when any active request carries a
stochastic :class:`~repro.sample.SamplerSpec`) runs inside the wave
executable (``serve.backend.make_fused_wave`` — the MeshBackend pipeline
promoted to the shared path), with device-side token feedback in steady
decode; ``fuse_wave=False`` keeps the pre-fused reference wave (logits
out, one separate selection dispatch) for ablation/benchmarks.
A :class:`~repro.serve.mesh_backend.MeshBackend` extends the vectorized
flavor across a device mesh: the session discovers its placement hooks
(``wave_for`` / ``place_stacked`` / ``place_rows`` / ``vmapped_prefill``)
by ``getattr``, exactly like it discovers a ``MeteredBackend``'s meter,
and the token stream stays bit-identical across mesh shapes
(``tests/test_serve_mesh.py``) — under sampling too: every RNG key is a
pure function of ``(request_seed, position)``, never of slot, wave
composition, scheduler, or placement (``repro.sample.rng``).

Two serving-contract layers ride on top (docs/serving.md "Traffic &
capacity"):

* **EOS** — ``Request.stop_tokens``: a request finishes the moment it
  emits a stop token, freeing its slot (and KV pages) instead of
  burning the remaining ``max_new_tokens`` budget. The stop set also
  travels into the wave executable as a per-slot mask
  (``SamplerRows.stop`` + the guard in
  ``serve.backend.fused_select_step``), so the fused wave itself can
  never emit past EOS nor advance a finished slot's RNG counter.
* **Capacity** — an optional :class:`~repro.serve.pool.KVPagePool`
  bounds total resident KV pages. Admission waits (degrades) when the
  pool is full; mid-stream growth past the budget preempts the
  youngest-admitted requests (``preempt_overcommitted``, driven by the
  schedulers), which requeue at the queue front in submission order
  and later *resume*: re-prefill over ``prompt + generated`` rebuilds
  their state, and counter-keyed RNG restarts sampling at position
  ``len(generated)`` — so on the exact decode path a preempted
  request's stream is bit-identical to an uncontended run.

Each phase of a step's host work runs inside a
``jax.profiler.TraceAnnotation`` named ``session.*`` (schedule, prefill,
install, merge_demands, wave_dispatch, wave_sync, emit). The spans exist
only in a running profiler's trace, on the clock its device events are
aligned to, and cost about a microsecond each without one; they change
nothing that is dispatched or computed (docs/observability.md).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.sample import (MAX_STOP_TOKENS, SamplerRows, SamplerSpec,
                          sample_token, select_tokens, token_logprobs)
from repro.serve.backend import (DecodeBackend, ServingBackend,
                                 make_fused_wave, split_bound,
                                 vmapped_prefill)
from repro.serve.policy import HysteresisPolicy, SectorPolicy
from repro.serve.pool import KVPagePool
from repro.serve.prefix import PrefixCache, PrefixLease
from repro.serve.scheduler import FifoScheduler, Scheduler

PREFIX_KEY_TOKENS = 128  # tokens hashed into the shared-prefix group key


class StreamTruncated(RuntimeError):
    """A stream iterator / drain loop hit its step limit before the
    request (or session) completed. Subclasses RuntimeError so legacy
    callers catching that keep working; the message says how far the
    stream got and which knob raises the limit
    (``ServeSession(max_stream_steps=...)``)."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int
    # None = greedy (exact legacy token streams); a stochastic spec keys
    # every draw on (spec.seed, token position) — see repro.sample
    sampler: SamplerSpec | None = None
    # EOS contract: emitting any of these token ids finishes the request
    # early (the stop token itself IS emitted, nothing after it). At most
    # MAX_STOP_TOKENS ids; validated loudly at submit().
    stop_tokens: tuple = ()
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def prefix_key(self) -> bytes:
        """Requests with equal keys hit the same leading KV pages."""
        return np.asarray(self.prompt[:PREFIX_KEY_TOKENS], np.int32).tobytes()


def _leaf_signature(shape, dtype) -> tuple:
    return (tuple(shape), str(dtype))


def state_signature(state: Any) -> tuple:
    """Shape/dtype fingerprint of a decode state — the page-padded KV
    layout. Two states with equal signatures can share a vectorized wave."""
    return tuple(_leaf_signature(x.shape, x.dtype)
                 for x in jax.tree.leaves(state))


def stacked_row_signature(stacked: Any) -> tuple:
    """``state_signature`` of one row of a stacked state (leading request
    axis stripped) — same format, so group and single-install admission
    keys cannot drift."""
    return tuple(_leaf_signature(x.shape[1:], x.dtype)
                 for x in jax.tree.leaves(stacked))


@dataclasses.dataclass
class PrefillGroup:
    """A batch of prefilled requests kept stacked (leading request axis).

    Produced by ``ServeSession.prefill_group`` and consumed by
    ``install_group`` as ONE multi-slot scatter — per-request rows are
    never extracted, so admitting a group costs one buffer update instead
    of ``n``. ``logits`` stays a lazy device array ((n, 1, vocab)): a
    scheduler prefilling under an in-flight wave must not block on it;
    first tokens are materialized at install time, when the device has
    drained.
    """

    handles: list[StreamHandle]
    logits: Any  # (n, 1, vocab), lazy
    states: Any  # pytree, each leaf (n,) + row shape
    sig: tuple  # per-row state signature (paged-KV admission key)

    def __len__(self) -> int:
        return len(self.handles)


class StreamHandle:
    """Streaming view of one request's generation.

    ``poll()`` returns tokens produced since the last poll without driving
    the session; ``tokens()`` is an iterator that steps the session until
    this request completes, yielding tokens as they land.
    """

    def __init__(self, session: "ServeSession", request: Request):
        self.request = request
        self.done = False
        self.stopped = False  # finished by a stop token (before quota)
        self._session = session
        self._tokens: list[int] = []
        self._cursor = 0
        self._bound = False  # legacy shims mirror state into the Request
        self._stop = frozenset(int(t) for t in (request.stop_tokens or ()))
        # preemption bookkeeping: submission order (requeue ordering) and
        # admission order (youngest-first victim selection)
        self._submit_index = -1
        self._admit_index = -1
        self.preemptions = 0
        # prefix-cache lease (warm admission): released at finish/preempt
        self._lease: PrefixLease | None = None
        # per-token raw logprobs, parallel to _tokens; the prefill token's
        # is stashed by the prefill path and consumed by _emit_first
        self._logprobs: list[float] = []
        self._first_logp = 0.0

    @property
    def rid(self) -> int:
        return self.request.rid

    @property
    def last_token(self) -> int:
        return self._tokens[-1]

    @property
    def prefill_len(self) -> int:
        """Tokens the next (re-)prefill of this request covers: the
        prompt plus everything already generated (non-empty only after a
        preemption — see ``ServeSession.effective_prompt``)."""
        return len(self.request.prompt) + len(self._tokens)

    def peek(self) -> list[int]:
        """All tokens produced so far (does not advance the poll cursor)."""
        return list(self._tokens)

    def poll(self) -> list[int]:
        """New tokens since the last ``poll()`` (non-blocking)."""
        new = self._tokens[self._cursor:]
        self._cursor += len(new)
        return new

    def logprobs(self) -> list[float]:
        """Raw (untempered, unfiltered) log-probability of each emitted
        token, parallel to :meth:`peek` — log P(token | context) under
        the model's own distribution, the best-of-n rescoring quantity.
        Computed by one shared kernel (``repro.sample.token_logprob``)
        on every wave flavor, so the fused == pre-fused == looped
        equivalence extends to these values."""
        return list(self._logprobs)

    def tokens(self, max_steps: int | None = None) -> Iterator[int]:
        """Yield this request's tokens, stepping the session as needed.

        ``max_steps`` bounds the session steps this iterator will drive
        (default: the session's ``max_stream_steps``); hitting the bound
        raises :class:`StreamTruncated` — loudly, with the progress so
        far — instead of silently ending the stream.
        """
        limit = (self._session.max_stream_steps if max_steps is None
                 else max_steps)
        steps = 0
        while True:
            yield from self.poll()
            if self.done:
                return
            self._session.step()
            steps += 1
            if steps > limit:
                if self._session.obs is not None:
                    self._session.obs.on_truncated(self)
                raise StreamTruncated(
                    f"request {self.rid} did not complete within {limit} "
                    f"session steps: {len(self._tokens)} of "
                    f"{self.request.max_new_tokens} tokens emitted, "
                    f"{self.preemptions} preemptions; raise the limit via "
                    f"ServeSession(max_stream_steps=...) or "
                    f"tokens(max_steps=...)")

    def result(self, max_steps: int | None = None) -> list[int]:
        """Drive the session until this request completes; all tokens."""
        for _ in self.tokens(max_steps=max_steps):
            pass
        return self.peek()

    # -- telemetry (populated only when the session's backend is metered) --

    @property
    def telemetry(self) -> dict | None:
        """This request's metered stats (``energy_j``, ``tokens``,
        ``pages_fetched``, ...) or None on an unmetered session."""
        meter = self._session.meter
        return None if meter is None else meter.request_stats(self.rid)

    @property
    def energy_j(self) -> float | None:
        """DRAM joules attributed to this request (None when unmetered)."""
        stats = self.telemetry
        return None if stats is None else stats["energy_j"]


class ServeSession:
    """Facade over backend + scheduler + policy; owns slots and waves."""

    def __init__(self, backend: DecodeBackend, *, max_batch: int = 8,
                 scheduler: Scheduler | None = None,
                 policy: SectorPolicy | None = None,
                 vectorized: bool = True, fuse_wave: bool = True,
                 page_pool: KVPagePool | None = None,
                 prefix_cache: PrefixCache | None = None,
                 obs=None,
                 max_stream_steps: int = 10_000):
        self.backend = backend
        self.max_batch = max_batch
        self.scheduler = scheduler if scheduler is not None else FifoScheduler()
        self.policy = policy if policy is not None else HysteresisPolicy()
        self.vectorized = vectorized
        self.fuse_wave = fuse_wave
        # KV capacity model: None = unbounded (every pre-pool behaviour
        # unchanged); a pool gates admission and arms preemption
        self.page_pool = page_pool
        # cross-request prefix cache (serve.prefix): warm admissions seed
        # from a shared entry and re-prefill only the prompt suffix. The
        # backend hooks are discovered like every other optional hook —
        # but with a cache configured their absence is refused loudly, not
        # silently degraded: the user asked for sharing the backend can't do
        self.prefix_cache = prefix_cache
        self._state_prefix = getattr(backend, "state_prefix", None)
        self._suffix_prefill = getattr(backend, "suffix_prefill", None)
        # every handle currently holding a live lease (installed or
        # prefilled-ahead in a scheduler's ready buffer) — the admission
        # deadlock breaker needs to enumerate the latter
        self._leased_handles: set[StreamHandle] = set()
        if prefix_cache is not None:
            if self._state_prefix is None or self._suffix_prefill is None:
                raise ValueError(
                    f"prefix_cache needs a backend exposing state_prefix() "
                    f"and suffix_prefill() (SectoredKVBackend does); "
                    f"{type(backend).__name__} cannot seed a warm admission")
            if (page_pool is not None
                    and page_pool.page_size != prefix_cache.page_size):
                raise ValueError(
                    f"prefix_cache page_size={prefix_cache.page_size} != "
                    f"page_pool page_size={page_pool.page_size}: shared and "
                    f"private pages must account in the same currency")
        # default bound for StreamHandle.tokens()/result() and
        # run_until_drained(); exceeding it raises StreamTruncated
        if max_stream_steps < 1:
            raise ValueError(
                f"max_stream_steps must be >= 1, got {max_stream_steps}")
        self.max_stream_steps = max_stream_steps
        # vocab bound for stop-token validation, when the backend can say
        # (SectoredKVBackend exposes cfg.vocab; decorators pass through)
        self._vocab = getattr(backend, "vocab", None)
        # metering is discovered, not configured: a MeteredBackend carries a
        # WaveMeter; a plain backend has none and every telemetry branch
        # below reduces to one `is None` check (zero-cost when off)
        self.meter = getattr(backend, "meter", None)
        # mesh placement is discovered the same way: a MeshBackend carries
        # wave/placement hooks (wave_for, place_stacked, place_rows,
        # vmapped_prefill); a plain backend has none and every branch
        # below falls back to the single-device behaviour
        self._backend_wave_for = getattr(backend, "wave_for", None)
        self._place_stacked = getattr(backend, "place_stacked", None)
        self._place_rows = getattr(backend, "place_rows", None)
        self.mesh = getattr(backend, "mesh", None)
        if not fuse_wave and self._backend_wave_for is not None:
            raise ValueError(
                "fuse_wave=False (the pre-fused reference wave) is a "
                "single-device ablation; a backend supplying wave_for "
                "(MeshBackend) always fuses token selection")
        if self.meter is not None and hasattr(self.meter, "mesh_shape"):
            # provenance stamp reflects the mesh THIS session's waves run
            # on (None when unmeshed) — set here, not at wrapper
            # construction, so a meter reused across sessions always
            # reports the placement that actually executed
            self.meter.mesh_shape = (tuple(self.mesh.devices.shape)
                                     if self.mesh is not None else None)
        self.queue: collections.deque[StreamHandle] = collections.deque()
        self.slots: list[StreamHandle | None] = [None] * max_batch
        self.completion_order: list[int] = []
        self.stats = self._zero_stats()
        # vectorized wave state: stacked per-slot pytree + its row signature
        self.batched = None
        self._batched_sig: tuple | None = None
        # stacked per-slot sampler state (seed, RNG counter, spec scalars)
        # riding next to the wave buffer; scattered at admission, advanced
        # on-device by every fused wave (repro.sample.SamplerRows)
        self._sampler_rows = SamplerRows.init(max_batch) if vectorized \
            else None
        # device-side token feedback (token-returning waves only): the
        # previous wave's output tokens + their host copy for validation
        self._token_feedback = None
        self._token_feedback_np: np.ndarray | None = None
        # looped wave state: one pytree per slot
        self.states: list = [None] * max_batch
        self._wave_cache: dict[tuple, Any] = {}
        self._vmapped_prefill = None
        self.wave_in_flight = False  # True between dispatch and blocking
        self._submit_seq = 0  # submission order (preemption requeue key)
        self._admit_seq = 0  # admission order (youngest-first victims)
        # flight recorder (repro.obs): like the meter, every hook site is
        # one `is None` check; the recorder's hooks are pure host
        # bookkeeping, so enabling it cannot perturb streams or joules
        # (the observer-effect oracle in tests/test_obs.py)
        self.obs = obs
        if obs is not None:
            obs.bind(self)

    @staticmethod
    def _zero_stats() -> dict[str, int]:
        return dict(decode_steps=0, sectored_steps=0, completed=0, waves=0,
                    sectored_waves=0, merged_slots=0, overlapped_prefills=0,
                    prefill_calls=0, prefill_tokens=0, preemptions=0,
                    eos_stops=0, context_tokens=0, expert_tokens=0,
                    experts_touched=0)

    def reset_stats(self) -> None:
        self.stats = self._zero_stats()

    # -- request lifecycle ------------------------------------------------

    def submit(self, request: Request, *,
               bind_request: bool = False) -> StreamHandle:
        """Queue a request; returns its streaming handle.

        Degenerate requests are rejected loudly here — an empty prompt,
        a non-positive token budget, or stop tokens outside the vocab
        would otherwise surface as undefined wave behaviour (zero-length
        prefills, slots that never finish, stop masks that can't match).

        ``bind_request=True`` restores the legacy contract for the
        ``Engine`` shims: tokens are mirrored into ``request.generated``
        (shared list) and ``request.done`` is set on completion.
        """
        self._validate(request)
        handle = StreamHandle(self, request)
        handle._submit_index = self._submit_seq
        self._submit_seq += 1
        if bind_request:
            handle._tokens = request.generated
            handle._bound = True
        self.queue.append(handle)
        if self.obs is not None:
            self.obs.on_submit(handle)
        return handle

    def _validate(self, request: Request) -> None:
        prompt = np.asarray(request.prompt)
        if prompt.size == 0:
            raise ValueError(f"request {request.rid}: empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError(
                f"request {request.rid}: max_new_tokens must be >= 1, got "
                f"{request.max_new_tokens} (the prefill always emits one "
                f"token)")
        stop = tuple(int(t) for t in (request.stop_tokens or ()))
        if len(stop) > MAX_STOP_TOKENS:
            raise ValueError(
                f"request {request.rid}: {len(stop)} stop tokens exceed the "
                f"wave-side mask width MAX_STOP_TOKENS={MAX_STOP_TOKENS}")
        bad = [t for t in stop
               if t < 0 or (self._vocab is not None and t >= self._vocab)]
        if bad:
            bound = (f"[0, {self._vocab})" if self._vocab is not None
                     else ">= 0")
            raise ValueError(
                f"request {request.rid}: stop tokens {bad} outside vocab "
                f"({bound}) — they could never match an emitted token")
        if self.page_pool is not None:
            worst = self.page_pool.pages_for(
                prompt.size + request.max_new_tokens)
            if worst > self.page_pool.capacity_pages:
                raise ValueError(
                    f"request {request.rid}: worst-case KV footprint "
                    f"({worst} pages for {prompt.size} prompt + "
                    f"{request.max_new_tokens} new tokens) exceeds the "
                    f"page pool ({self.page_pool.capacity_pages} pages) — "
                    f"it could never run to completion even alone")

    @property
    def occupancy(self) -> float:
        return sum(h is not None for h in self.slots) / self.max_batch

    def active_slots(self) -> list[int]:
        return [s for s, h in enumerate(self.slots) if h is not None]

    def free_slots(self) -> list[int]:
        return [s for s, h in enumerate(self.slots) if h is None]

    @property
    def idle(self) -> bool:
        return (not self.queue and not self.active_slots()
                and not self.scheduler.pending())

    # -- prefill / admission (driven by the Scheduler) --------------------

    @staticmethod
    def effective_prompt(handle: StreamHandle) -> np.ndarray:
        """The tokens a (re-)prefill of this request covers: the prompt,
        plus everything already generated when the request was preempted
        mid-stream. Re-prefilling over ``prompt + generated`` rebuilds
        the KV cache with the same appends the uncontended run made
        (SectoredKVBackend's prefill scans the same exact-mode step its
        decode path runs), which is what keeps a resumed stream
        bit-identical on the exact path."""
        prompt = np.asarray(handle.request.prompt, np.int32)
        if not handle._tokens:
            return prompt
        return np.concatenate(
            [prompt, np.asarray(handle._tokens, np.int32)])

    def prefix_hit(self, handle: StreamHandle) -> int:
        """Peek: tokens a warm admission of this request would reuse
        (0 = cold). Schedulers use this to route hits to the singleton
        prefill path — a vmapped group prefill is all-cold by
        construction. Pure query; the lease is only taken at prefill
        time, and an entry evicted between peek and prefill just turns
        the hit back into a cold prefill (safe, never wrong)."""
        if self.prefix_cache is None or handle._tokens:
            return 0
        prompt = np.asarray(handle.request.prompt)
        _, m = self.prefix_cache.match(prompt, max_match=len(prompt) - 1)
        return m

    def _prefill_states(self, handle: StreamHandle, prompt: np.ndarray):
        """Prefill one prompt, warm when the prefix cache can seed it.

        Returns ``(logits, state, lease)``. A warm admission truncates a
        donor entry's state to the matched length ``m`` (metadata-only —
        ``state_prefix``) and scans only ``prompt[m:]`` through the same
        exact-mode step a cold prefill runs (``suffix_prefill``), so the
        resulting state and logits are bit-identical to the cold path
        (stale KV rows past ``m`` are masked to exact zero and
        overwritten by the row write). The match is capped at
        ``len(prompt) - 1`` so the suffix is never empty — the prefill
        must emit this request's own first-token logits. Resumed
        (post-preemption) re-prefills stay cold: their effective prompt
        includes generated tokens, and the eviction already charged the
        full rebuild.
        """
        lease = None
        if (self.prefix_cache is not None and not handle._tokens
                and len(prompt) > 1):
            lease = self.prefix_cache.acquire(prompt,
                                              max_match=len(prompt) - 1)
        if lease is None:
            self.stats["prefill_tokens"] += len(prompt)
            logits, state = self.backend.prefill_fn(prompt[None, :])
            return logits, state, None
        m = lease.matched_tokens
        self.stats["prefill_tokens"] += len(prompt) - m
        seed = self._state_prefix(lease.entry.state, m)
        logits, state = self._suffix_prefill(seed, prompt[None, m:])
        return logits, state, lease

    def prefill_one(self, handle: StreamHandle):
        """Blocking single-prompt prefill; returns (first_token, state)."""
        prompt = self.effective_prompt(handle)
        with TraceAnnotation("session.prefill", rid=handle.rid) as span:
            logits, state, lease = self._prefill_states(handle, prompt)
            handle._lease = lease
            if lease is not None:
                self._leased_handles.add(handle)
            span.set_metadata(**self._prefill_meta([handle], [prompt]))
            self.stats["prefill_calls"] += 1
            if self.prefix_cache is not None and not handle._tokens:
                # fresh admissions (cold AND warm) insert their full-prompt
                # post-prefill state — warm inserts deepen the shared
                # prefix; dedupe just refreshes recency
                self.prefix_cache.insert(prompt, state)
            if self.meter is not None:
                self.meter.record_prefill(
                    handle.rid, len(prompt), overlapped=self.wave_in_flight,
                    resumed=bool(handle._tokens),
                    cached_tokens=lease.matched_tokens if lease else 0)
            tok = self._first_token(handle, logits[0])
            handle._first_logp = self._logp_of(logits[0], tok)
        return tok, state

    @staticmethod
    def _prefill_meta(handles: list[StreamHandle], prompts: list) -> dict:
        """Profiler metadata of a ``session.prefill`` span, once the
        leases are known: the prompt tokens scanned and the mode (a
        resumed request re-prefills cold; ``warm`` seeds from the prefix
        cache)."""
        matched = sum(h._lease.matched_tokens for h in handles
                      if h._lease is not None)
        mode = ("resume" if any(h._tokens for h in handles) else
                "warm" if matched else "cold")
        return dict(tokens=sum(len(p) for p in prompts) - matched, mode=mode)

    @staticmethod
    def _logp_of(logits_row, tok: int) -> float:
        """Host-side raw logprob of one chosen token — the same
        ``token_logprob`` kernel the waves run, jitted at unit batch."""
        lp = token_logprobs(
            jnp.asarray(logits_row, jnp.float32).reshape(1, 1, -1),
            jnp.asarray([int(tok)], jnp.int32))
        return float(np.asarray(lp)[0])

    @staticmethod
    def _first_token(handle: StreamHandle, logits_row) -> int:
        """Select the prefill-emitted token (RNG counter ``len(tokens)``
        for sampled requests — 0 on a fresh admission, the resume
        position after a preemption; greedy keeps the exact legacy host
        argmax)."""
        spec = handle.request.sampler
        if spec is None or spec.is_greedy:
            return int(np.argmax(np.asarray(logits_row)))
        return sample_token(np.asarray(logits_row), spec,
                            position=len(handle._tokens))

    def prefill_group(self, handles: list[StreamHandle]) -> PrefillGroup:
        """One prefill call over same-length prompts, kept stacked.

        Lengths are *effective* (prompt + generated-so-far), so resumed
        requests group with fresh ones of the same total length. Groups
        of two or more go through a vmapped prefill (ONE dispatch for
        the whole group); singletons take the exact ``prefill_one`` data
        path with a unit leading axis added. Nothing here blocks on
        device results — see :class:`PrefillGroup`.
        """
        prompts = [self.effective_prompt(h) for h in handles]
        lengths = {len(p) for p in prompts}
        if len(lengths) != 1:
            raise ValueError(f"prefill_group needs equal prompt lengths, "
                             f"got {sorted(lengths)}")
        rids = ",".join(str(h.rid) for h in handles)
        with TraceAnnotation("session.prefill", rids=rids) as span:
            group = self._prefill_group(handles, prompts)
            span.set_metadata(**self._prefill_meta(handles, prompts))
        return group

    def _prefill_group(self, handles: list[StreamHandle],
                       prompts: list) -> PrefillGroup:
        self.stats["prefill_calls"] += 1
        if len(handles) == 1:
            # the one branch that can go warm: a prefix-cache hit seeds
            # from the shared entry (schedulers route hits here via
            # prefix_hit — the vmapped group below is all-cold)
            logits, state, lease = self._prefill_states(handles[0],
                                                        prompts[0])
            handles[0]._lease = lease
            if lease is not None:
                self._leased_handles.add(handles[0])
            stacked = jax.tree.map(lambda x: x[None], state)
            logits = logits[None]  # (1, 1, vocab)
        else:
            if self._vmapped_prefill is None:
                # a mesh backend supplies a donor-device group prefill (the
                # overlap second stream); otherwise build the default
                backend_vp = getattr(self.backend, "vmapped_prefill", None)
                if backend_vp is not None:
                    self._vmapped_prefill = backend_vp
                else:
                    jitted, bound = vmapped_prefill(self.backend.prefill_fn)
                    self._vmapped_prefill = functools.partial(jitted, bound)
            stacked_prompts = jnp.asarray(np.stack(prompts), jnp.int32)
            self.stats["prefill_tokens"] += sum(len(p) for p in prompts)
            logits, stacked = self._vmapped_prefill(stacked_prompts)
        if self.prefix_cache is not None:
            for j, (h, p) in enumerate(zip(handles, prompts)):
                if not h._tokens:
                    self.prefix_cache.insert(
                        p, jax.tree.map(lambda x, j=j: x[j], stacked))
        if self.meter is not None:
            for h, p in zip(handles, prompts):
                lease = h._lease
                self.meter.record_prefill(
                    h.rid, len(p), overlapped=self.wave_in_flight,
                    resumed=bool(h._tokens),
                    cached_tokens=(lease.matched_tokens
                                   if lease is not None else 0))
        return PrefillGroup(list(handles), logits, stacked,
                            stacked_row_signature(stacked))

    @staticmethod
    def split_group(group: PrefillGroup,
                    k: int) -> tuple[PrefillGroup, PrefillGroup]:
        """Split a prefill group when fewer than ``len(group)`` slots are
        free; both halves keep the stacked layout."""
        head = PrefillGroup(group.handles[:k], group.logits[:k],
                            jax.tree.map(lambda x: x[:k], group.states),
                            group.sig)
        tail = PrefillGroup(group.handles[k:], group.logits[k:],
                            jax.tree.map(lambda x: x[k:], group.states),
                            group.sig)
        return head, tail

    def wave_accepts(self, sig: tuple) -> bool:
        """Paged-KV admission check: can a state with this page-padded
        signature join the current wave? Looped slots are independent, so
        always; vectorized waves need matching rows unless empty."""
        return (not self.vectorized or self.batched is None
                or self._batched_sig == sig or not self.active_slots())

    def _prepare_wave_buffer(self, sig: tuple, row_shape_of) -> None:
        """(Re)build the stacked wave buffer for a row signature, or raise
        if the signature cannot join the in-flight wave."""
        if (self.batched is None
                or (self._batched_sig != sig and not self.active_slots())):
            self.batched = row_shape_of()
            if self._place_stacked is not None:
                # born on the mesh: the admission scatter below then runs
                # colocated with (and preserves) the wave placement
                self.batched = self._place_stacked(self.batched)
            self._batched_sig = sig
        elif self._batched_sig != sig:
            raise ValueError(
                f"state signature {sig} cannot join the in-flight wave "
                f"(wave signature {self._batched_sig}); use a paged-KV "
                f"aware scheduler (OverlapScheduler) for mixed quanta")

    def install(self, slot: int, handle: StreamHandle, first_token: int,
                state) -> None:
        """Place one prefilled request into a slot and emit its first
        token (the FIFO admission path)."""
        with TraceAnnotation("session.install", rid=handle.rid, slot=slot):
            self._install(slot, handle, first_token, state)

    def _install(self, slot: int, handle: StreamHandle, first_token: int,
                 state) -> None:
        if self.vectorized:
            self._prepare_wave_buffer(
                state_signature(state),
                lambda: jax.tree.map(
                    lambda x: jnp.zeros((self.max_batch,) + x.shape, x.dtype),
                    state))
            if self._place_rows is not None:
                state = self._place_rows(state)  # donor -> wave devices
            self.batched = jax.tree.map(
                lambda big, small: big.at[slot].set(small),
                self.batched, state)
            self._scatter_sampler_rows([slot], [handle])
        else:
            self.states[slot] = state
        self._emit_first(slot, handle, first_token)

    def install_group(self, slots: list[int], group: PrefillGroup) -> None:
        """Admit a whole prefill group with ONE multi-slot scatter.

        ``len(slots)`` must equal ``len(group)`` (use ``split_group`` when
        fewer slots are free). First tokens are materialized here — by the
        time a scheduler installs, the wave the prefill overlapped with has
        drained, so the read doesn't stall a wave window.
        """
        if len(slots) != len(group):
            raise ValueError(f"{len(group)} prefilled requests for "
                             f"{len(slots)} slots")
        with TraceAnnotation(
                "session.install",
                rids=",".join(str(h.rid) for h in group.handles),
                slots=",".join(str(s) for s in slots)):
            self._install_group(slots, group)

    def _install_group(self, slots: list[int], group: PrefillGroup) -> None:
        if self.vectorized:
            self._prepare_wave_buffer(
                group.sig,
                lambda: jax.tree.map(
                    lambda x: jnp.zeros((self.max_batch,) + x.shape[1:],
                                        x.dtype), group.states))
            rows = group.states
            if self._place_rows is not None:
                rows = self._place_rows(rows)  # d2d handoff before admission
            idx = jnp.asarray(np.asarray(slots, np.int32))
            self.batched = jax.tree.map(
                lambda big, rows: big.at[idx].set(rows),
                self.batched, rows)
            self._scatter_sampler_rows(slots, group.handles)
        else:
            for j, slot in enumerate(slots):
                self.states[slot] = jax.tree.map(lambda x: x[j], group.states)
        specs = [h.request.sampler for h in group.handles]
        if any(s is not None and not s.is_greedy for s in specs):
            # ONE stacked selection dispatch over the whole group through
            # the wave kernel (counter 0 fresh, len(generated) on a
            # post-preemption resume); greedy rows take its greedy
            # branch — the same first-max argmax as the path below
            rows = SamplerRows.from_specs(
                specs, [len(h._tokens) for h in group.handles])
            toks, _ = select_tokens(group.logits, rows)
            tokens = np.asarray(toks).reshape(len(group), -1)[:, 0]
        else:
            tokens = np.asarray(jnp.argmax(group.logits, axis=-1)).reshape(
                len(group), -1)[:, 0]
        lps = np.asarray(token_logprobs(
            group.logits, jnp.asarray(tokens, jnp.int32)))
        for j, (slot, handle) in enumerate(zip(slots, group.handles)):
            handle._first_logp = float(lps[j])
            self._emit_first(slot, handle, int(tokens[j]))

    def _scatter_sampler_rows(self, slots: list[int], handles) -> None:
        """Admission scatter for the per-slot sampler state: each handle's
        spec scalars land in its slot with the RNG counter one past the
        tokens already emitted (1 on a fresh admission — the prefill token
        consumed counter 0; ``len(generated) + 1`` on a post-preemption
        resume, keeping the counter in lockstep with the stream). The
        request's stop set rides along as the wave-side EOS mask. Rows of
        vacated slots stay stale — counter-based keying makes them inert,
        and the next admission rewrites them."""
        rows = SamplerRows.from_specs(
            [h.request.sampler for h in handles],
            [len(h._tokens) + 1 for h in handles],
            [h.request.stop_tokens for h in handles])
        idx = jnp.asarray(np.asarray(slots, np.int32))
        self._sampler_rows = jax.tree.map(
            lambda big, row: big.at[idx].set(row), self._sampler_rows, rows)

    def _emit_first(self, slot: int, handle: StreamHandle,
                    first_token: int) -> None:
        """Activate a slot and emit the prefill token; a request whose
        quota the prefill token already meets (max_new_tokens <= 1), or
        whose prefill token is one of its stop tokens, completes here
        without burning a decode wave."""
        self.slots[slot] = handle
        handle._admit_index = self._admit_seq
        self._admit_seq += 1
        if self.obs is not None:
            # before the prefill token lands: the recorder distinguishes a
            # resume (generated tokens survived preemption) from a fresh
            # admission by the pre-emit token count
            self.obs.on_admit(slot, handle)
        if self.page_pool is not None:
            self.page_pool.observe(self._held_pages_total())
        handle._tokens.append(first_token)
        handle._logprobs.append(handle._first_logp)
        if first_token in handle._stop:
            self._finish(slot, stopped=True)
        elif len(handle._tokens) >= handle.request.max_new_tokens:
            self._finish(slot)

    def _release_lease(self, handle: StreamHandle) -> None:
        """Drop a handle's hold on its shared entry (idempotent — safe
        after a lease-breaking preemption pass already released it)."""
        if handle._lease is not None:
            self.prefix_cache.release(handle._lease)
            handle._lease = None
        self._leased_handles.discard(handle)

    def _finish(self, slot: int, *, stopped: bool = False) -> None:
        handle = self.slots[slot]
        handle.done = True
        # last reader out frees the shared pages
        self._release_lease(handle)
        if stopped:
            # EOS: the stop token itself was emitted; the remaining
            # max_new_tokens budget is returned, the slot (and its KV
            # pages) freed now
            handle.stopped = True
            self.stats["eos_stops"] += 1
        if handle._bound:
            handle.request.done = True
        self.slots[slot] = None
        if not self.vectorized:
            self.states[slot] = None
        self.completion_order.append(handle.rid)
        self.stats["completed"] += 1
        if self.obs is not None:
            self.obs.on_finish(slot, handle,
                               reason="eos" if stopped else "quota")

    # -- KV page capacity (pool-gated admission + preemption) -------------

    def _held_pages_total(self, extra_tokens: int = 0) -> int:
        """Pages all resident requests hold, each optionally grown by
        ``extra_tokens`` (1 = the append the next wave makes per slot).
        Derived from live slot lengths every call — the accountant can
        never drift from the truth it accounts.

        With a prefix cache, a leased slot's complete shared pages are
        charged to the *entry* (once, no matter how many readers), so
        the slot counts only its private remainder — the CoW partial
        page plus everything it appends; resident cache entries add
        their one-time charge on top (``PrefixCache.held_pages``)."""
        total = 0
        for h in self.slots:
            if h is None:
                continue
            pages = self.page_pool.pages_for(h.prefill_len + extra_tokens)
            lease = h._lease
            if lease is not None and not lease.released:
                pages = max(pages - lease.shared_pages, 1)
            total += pages
        if self.prefix_cache is not None:
            total += self.prefix_cache.held_pages
        return total

    def _shed_for(self, held: int) -> int:
        """Evict unreferenced cache entries until ``held`` fits (pages
        actually freed returned) — sharing backs off before live work
        does."""
        if self.prefix_cache is None:
            return 0
        overflow = held - self.page_pool.capacity_pages
        return self.prefix_cache.shed(overflow) if overflow > 0 else 0

    def _admission_need(self, handle: StreamHandle) -> int:
        """Pages this handle would hold if installed now (effective
        prompt + the token the prefill emits). A handle already carrying
        a live lease (prefilled ahead by the overlap scheduler) charges
        its *private* remainder only — its complete shared pages are
        already in ``_held_pages_total`` via the entry's one-time charge,
        and counting them again would double-book the very pages sharing
        saved (wedging admission when entries + discounts exactly fill
        the pool)."""
        need = self.page_pool.pages_for(handle.prefill_len + 1)
        lease = handle._lease
        if lease is not None and not lease.released:
            need = max(need - lease.shared_pages, 1)
        return need

    def _break_idle_leases(self) -> int:
        """Deadlock breaker of last resort: release leases held by
        handles that are NOT installed in a slot (they sit prefilled in
        a scheduler's ready buffer). Their entries become sheddable and
        they re-charge at full need — physically honest, since a warm
        handle's state aliases immutable arrays and survives its donor
        entry. Only called when admission is blocked with nothing active
        to drain: without it, ready-buffer leases can pin exactly the
        pages admission is waiting for, forever."""
        installed = {id(h) for h in self.slots if h is not None}
        broken = 0
        for h in list(self._leased_handles):
            if id(h) not in installed:
                self._release_lease(h)
                broken += 1
        return broken

    def pool_admits(self, handle: StreamHandle) -> bool:
        """Can this request be admitted *now*? Its current need
        (:meth:`_admission_need`) must fit next to everyone's current
        holdings. Deliberately not the worst case: the pool overcommits
        against future growth and relies on preemption to unwind —
        that's what lets load beyond capacity degrade instead of
        serialize. The stream oracle is admission-timing-invariant on
        the exact path, so gating here costs correctness nothing."""
        if self.page_pool is None:
            return True
        need = self._admission_need(handle)
        if self.page_pool.fits(self._held_pages_total() + need):
            return True
        self._shed_for(self._held_pages_total() + need)
        if self.page_pool.fits(self._held_pages_total() + need):
            return True
        if (not any(s is not None for s in self.slots)
                and self._break_idle_leases() > 0):
            need = self._admission_need(handle)
            self._shed_for(self._held_pages_total() + need)
            return self.page_pool.fits(self._held_pages_total() + need)
        return False

    def pool_admit_count(self, handles: list[StreamHandle]) -> int:
        """Longest prefix of ``handles`` admissible together right now
        (the group-admission form of :meth:`pool_admits`; order is the
        caller's admission order, so gating a prefix keeps it fair)."""
        if self.page_pool is None:
            return len(handles)
        held = self._held_pages_total()
        n = 0
        for h in handles:
            need = self._admission_need(h)
            if not self.page_pool.fits(held + need):
                freed = self._shed_for(held + need)
                held -= freed
                if (not self.page_pool.fits(held + need) and n == 0
                        and not any(s is not None for s in self.slots)
                        and self._break_idle_leases() > 0):
                    # nothing active to drain, nothing left to shed: the
                    # blocking pages are pinned by ready-buffer leases
                    need = self._admission_need(h)
                    self._shed_for(self._held_pages_total() + need)
                    held = self._held_pages_total()
                if not self.page_pool.fits(held + need):
                    break
            held += need
            n += 1
        return n

    def preempt_overcommitted(self) -> int:
        """Unwind pool overcommit before the next wave grows every slot.

        Pressure is relieved in strict order of what it costs: first
        **shed** unreferenced prefix-cache entries (LRU; pure accounting,
        no stream is touched), then evict the youngest-admitted request —
        LIFO victims keep the oldest streams moving, bounding head-of-line
        latency — requeueing victims at the queue FRONT in submission
        order, ahead of never-admitted requests. Never preempts below one
        active request: a lone request always fits (``submit`` rejected
        anything that couldn't), so every preemption cycle still emits at
        least one token and the loop cannot livelock. If the lone
        survivor still overcommits because its own lease pins a shared
        entry, the lease is broken as a last resort (physically honest —
        the slot owns a full copy of its rows), which unpins the entry
        for the next shed pass. Returns the number of requests preempted.
        """
        if self.page_pool is None:
            return 0
        victims: list[StreamHandle] = []
        while True:
            active = [(s, h) for s, h in enumerate(self.slots)
                      if h is not None]
            held = self._held_pages_total(extra_tokens=1)
            if self.page_pool.fits(held):
                break
            if self._shed_for(held) > 0:
                continue
            if len(active) > 1:
                slot, _ = max(active, key=lambda sh: sh[1]._admit_index)
                victims.append(self._preempt(slot))
                continue
            if (active and active[0][1]._lease is not None
                    and not active[0][1]._lease.released):
                self._release_lease(active[0][1])
                continue
            break
        if victims:
            for h in sorted(victims, key=lambda h: h._submit_index,
                            reverse=True):
                self.queue.appendleft(h)
            self.stats["preemptions"] += len(victims)
        return len(victims)

    def _preempt(self, slot: int) -> StreamHandle:
        """Vacate a slot WITHOUT finishing its request: its KV pages are
        freed (the stacked buffer keeps stale rows — vmapped slots are
        independent and the next admission overwrites them) and the
        handle keeps its generated tokens for the resume re-prefill."""
        handle = self.slots[slot]
        handle.preemptions += 1
        handle._admit_index = -1
        # the resume re-prefill is cold (it rebuilds everything), so the
        # victim's hold on the shared entry ends here
        self._release_lease(handle)
        self.slots[slot] = None
        if not self.vectorized:
            self.states[slot] = None
        if self.meter is not None:
            self.meter.record_eviction(
                handle.rid, kv_tokens=handle.prefill_len,
                kv_pages=self.page_pool.pages_for(handle.prefill_len))
        if self.obs is not None:
            self.obs.on_preempt(slot, handle)
        return handle

    # -- demand merge (shared-prefix OR-merge, LSQ-Lookahead analogue) ----

    def _group_ids(self) -> np.ndarray:
        """(max_batch,) int32: slots whose requests share a prompt prefix
        get the same id (the leader slot's index); free slots their own.

        Two slots merge when they share the first ``PREFIX_KEY_TOKENS``
        tokens (the within-wave key) OR hold leases on the same
        prefix-cache entry — the cross-request extension: warm co-readers
        attend the same shared pages even when their 128-token keys
        differ, so one OR-merged sectored fetch serves them all."""
        gids = np.arange(self.max_batch, dtype=np.int32)
        leaders: dict[Any, int] = {}
        for slot, handle in enumerate(self.slots):
            if handle is None:
                continue
            lease = handle._lease
            key = (("e", lease.entry.entry_id)
                   if lease is not None and not lease.released
                   else ("p", handle.request.prefix_key))
            gids[slot] = leaders.setdefault(key, slot)
        return gids

    def _shared_groups(self, active: list[int]) -> list[dict] | None:
        """Co-resident readers of each shared prefix entry, for the
        meter's shared-fetch amortization: ``[{"slots": [...],
        "shared_tokens": n}, ...]`` with ``shared_tokens`` the smallest
        member's complete-page share (groups of one amortize nothing).
        Host-side lease bookkeeping only — deterministic like every
        other meter input."""
        if self.prefix_cache is None:
            return None
        by_entry: dict[int, list[tuple[int, int]]] = {}
        for s in active:
            lease = self.slots[s]._lease
            if lease is None or lease.released or lease.shared_tokens <= 0:
                continue
            by_entry.setdefault(lease.entry.entry_id, []).append(
                (s, lease.shared_tokens))
        groups = [dict(slots=[s for s, _ in members],
                       shared_tokens=min(t for _, t in members))
                  for members in by_entry.values() if len(members) >= 2]
        return groups or None

    def _merge_groups(self, active_slots: list[int]) -> np.ndarray:
        """Group ids for a sectored wave + merged_slots accounting, shared
        by both wave flavors so their merge behaviour cannot diverge."""
        gids = self._group_ids()
        n_groups = len({int(gids[s]) for s in active_slots})
        self.stats["merged_slots"] += len(active_slots) - n_groups
        return gids

    def _merge_demands(self, active_slots: list[int]) -> None:
        # group ids stay host-side numpy: the merge fn validates them
        # without a device sync in front of the wave dispatch
        if self.vectorized:
            gids = self._merge_groups(active_slots)
            self.batched = self.backend.merge_demands(self.batched, gids)
            return
        if len(active_slots) <= 1:
            return
        # looped flavor: stack the active slots, pool demands, unstack;
        # leader slot ids are remapped to subset-local indices first
        gids = self._merge_groups(active_slots)
        remap: dict[int, int] = {}
        sub_gids = np.asarray(
            [remap.setdefault(int(gids[s]), j)
             for j, s in enumerate(active_slots)], np.int32)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[self.states[s] for s in active_slots])
        merged = self.backend.merge_demands(stacked, sub_gids)
        for j, s in enumerate(active_slots):
            self.states[s] = jax.tree.map(lambda x: x[j], merged)

    # -- wave execution ---------------------------------------------------

    def _wave_for(self, fn, sampled: bool = False):
        """The jitted wave for a per-slot step, cached per (step fn,
        selection flavor). ``sampled`` picks the selection fused into the
        executable: plain greedy argmax (no sampling math — greedy-only
        waves pay nothing for the sampler's existence) or the full
        ``repro.sample`` kernel, whose greedy branch is the same argmax —
        so a greedy request's tokens are invariant to which flavor its
        wave happens to compile."""
        # pre-fused waves are selection-free (logits out), so both
        # flavors would trace the identical jit(vmap(fn)) — collapse the
        # cache key to avoid compiling the same executable twice
        key = (id(fn), sampled and self.fuse_wave)
        wave = self._wave_cache.get(key)
        if wave is None:
            if not self.fuse_wave:
                # pre-fused reference wave: logits out, weights passed as
                # an unbatched argument
                step, bound = split_bound(fn)
                jitted = jax.jit(jax.vmap(lambda b, s, t: step(*b, s, t),
                                          in_axes=(None, 0, 0)))
                wave = functools.partial(jitted, bound)
            elif self._backend_wave_for is not None:
                wave = self._backend_wave_for(fn, sampled=sampled)
            else:
                wave = make_fused_wave(fn, sampled=sampled)
            self._wave_cache[key] = wave
        return wave

    def _wave_sampled(self, active: list[int]) -> bool:
        """True when any active slot needs stochastic selection."""
        return any(
            self.slots[s].request.sampler is not None
            and not self.slots[s].request.sampler.is_greedy for s in active)

    def step(self) -> int:
        """Admit + one decode wave. Returns tokens produced."""
        if self.obs is not None:
            self.obs.advance()  # the virtual step clock every span keys on
        with TraceAnnotation("session.schedule"):
            self.scheduler.schedule(self)
        active = self.active_slots()
        if not active:
            return 0
        decision = self.policy.decide(self.occupancy, self.stats)
        use_sectored = bool(decision.use_sectored
                            and self.backend.supports_sectored)
        if (use_sectored and decision.merge_demands
                and self.backend.demand_merge_fn is not None):
            with TraceAnnotation("session.merge_demands"):
                self._merge_demands(active)
        fn = (self.backend.sectored_fn_for(decision.topk_frac)
              if use_sectored else self.backend.decode_fn)
        sampled = self._wave_sampled(active)
        self.stats["waves"] += 1
        if use_sectored:
            self.stats["sectored_waves"] += 1
        t0 = time.perf_counter() if self.meter is not None else 0.0
        if self.vectorized:
            # dispatch the wave (async), let the scheduler overlap prefill
            # work with it, then block on the results
            with TraceAnnotation("session.wave_dispatch", active=len(active)):
                wave, out = self._launch_vectorized(active, fn, sampled)
            self.wave_in_flight = True
            try:
                self.scheduler.overlap(self)
            finally:
                self.wave_in_flight = False
            with TraceAnnotation("session.wave_sync"):
                next_tok, logps, experts = self._collect_wave(
                    wave, out, sampled)
            if experts is not None:
                self._count_experts(active, experts)
        else:
            with TraceAnnotation("session.wave_dispatch", active=len(active)):
                next_tok, logps = self._run_looped(active, fn)
            self.scheduler.overlap(self)
        # wall_s is snapped first so it brackets just dispatch + device
        # drain + overlap — not the telemetry table pull below or the emit
        # bookkeeping; wave info is captured before _emit_wave (finished
        # slots vacate) and the meter is driven after it
        wall_s = time.perf_counter() - t0 if self.meter is not None else 0.0
        with TraceAnnotation("session.emit"):
            wave_info = (self._meter_wave_info(active, decision, use_sectored)
                         if self.meter is not None else None)
            # (slot, rid) pairs captured before _emit_wave vacates
            # finished slots
            active_rids = ([(s, self.slots[s].rid) for s in active]
                           if self.obs is not None else None)
            produced = self._emit_wave(active, next_tok, logps, use_sectored)
            if wave_info is not None:
                self.meter.record_wave(wall_s=wall_s, **wave_info)
            if self.obs is not None:
                energy = (self.meter.recorder.window(1)[-1]
                          if self.meter is not None else None)
                timeline = (self.meter.last_timeline
                            if self.meter is not None else None)
                self.obs.on_wave(active_rids=active_rids, produced=produced,
                                 sectored=use_sectored, energy=energy,
                                 timeline=timeline)
        return produced

    def _collect_wave(self, wave, out, sampled: bool
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
        """Block on a dispatched wave: its tokens and their logprobs on
        the host, (max_batch,) each, and the held-expert counts its state
        carries (``SectoredState.experts``; None for a model without
        them), all in one read."""
        experts = getattr(self.batched, "experts", None)
        if getattr(wave, "returns_tokens", False):
            # fused pipeline (the default): tokens were selected
            # on-device — per-slot first-max argmax or the sampling
            # kernel, bit-identical to the reference paths below; the
            # per-token logprob rode out in the sampler rows
            out, logp, experts = jax.device_get(
                (out, self._sampler_rows.logp, experts))
            next_tok = out.reshape(self.max_batch, -1)[:, 0]
            self._token_feedback_np = next_tok
            return next_tok, logp, experts
        if sampled:
            # pre-fused reference (fuse_wave=False): one extra jitted
            # dispatch applies the SAME per-slot selection kernel to
            # the wave's logits, advancing the RNG counters exactly
            # like the fused executable does
            toks, self._sampler_rows = select_tokens(out, self._sampler_rows)
            toks, logp, experts = jax.device_get(
                (toks, self._sampler_rows.logp, experts))
            return toks.reshape(self.max_batch, -1)[:, 0], logp, experts
        # greedy pre-fused wave: the literal pre-fusion baseline (host
        # argmax over the pulled logits) — the honest denominator of the
        # benchmark's fused_speedup. Sampler counters need no advance
        # here: greedy draws never read them, and a later-admitted
        # stochastic request gets its counter scattered fresh at install
        next_tok = np.asarray(jnp.argmax(out, axis=-1)).reshape(
            self.max_batch, -1)[:, 0]
        logp, experts = jax.device_get((token_logprobs(
            out, jnp.asarray(next_tok, jnp.int32)), experts))
        return next_tok, logp, experts

    def _count_experts(self, active: list[int], experts: np.ndarray
                       ) -> None:
        """Fold the wave's held-expert counts (``experts``, read with its
        tokens) into ``stats``: the tokens the active slots routed to held
        experts (``expert_tokens``) and the (layer, held expert) pairs that
        at least one of them used (``experts_touched``)."""
        counts = experts[active].sum(axis=0)
        self.stats["expert_tokens"] += int(counts.sum())
        self.stats["experts_touched"] += int(
            np.count_nonzero(counts.sum(axis=-2)))

    def _meter_wave_info(self, active: list[int], decision,
                         use_sectored: bool) -> dict:
        """Host-side wave descriptor for WaveMeter.record_wave.

        Positions are derived from counts the session already tracks
        (prompt length + emitted tokens), never read back from the device:
        at attend time a slot's cache length is ``len(prompt) +
        len(tokens) - 1`` (the prefill token is emitted before the first
        wave). Deterministic counters keep fifo/overlap energy identical
        for identical token streams.
        """
        k_for = getattr(self.backend, "k_for", None)
        k_pages = (k_for(decision.topk_frac)
                   if use_sectored and k_for is not None else None)
        if k_pages is not None:
            # narrow budgets fetch one extra probe page per wave (the SHT
            # refresh); charge it — record_wave caps per-slot fetches at
            # the slot's valid pages, so full-coverage slots never overpay
            probe_for = getattr(self.backend, "probe_pages_for", None)
            if probe_for is not None:
                k_pages += probe_for(k_pages)
        slots = [(s, self.slots[s].rid,
                  len(self.slots[s].request.prompt)
                  + len(self.slots[s]._tokens) - 1)
                 for s in active]
        views = (self._meter_state_views(active)
                 if use_sectored and k_pages is not None else None)
        return dict(sectored=use_sectored, k_pages=k_pages, slots=slots,
                    state_views=views,
                    shared_groups=self._shared_groups(active))

    def _meter_state_views(self, active: list[int]) -> dict | None:
        """Per-slot (table, position) numpy views for the attention-mass
        estimate — duck-typed on the state exposing a predictor ``table``
        (SectoredState does); any other state pytree yields None. The
        device pull happens after the wave's results were already drained
        for tokens, so it adds a copy, not a sync."""
        if self.vectorized:
            table = getattr(self.batched, "table", None)
            position = getattr(self.batched, "position", None)
            if table is None or getattr(table, "ndim", 0) < 3:
                return None
            table = np.asarray(table)
            position = np.asarray(position)
            return {s: (table[s], position[s]) for s in active}
        views = {}
        for s in active:
            state = self.states[s]
            table = getattr(state, "table", None)
            if table is None or getattr(table, "ndim", 0) < 3:
                return None
            views[s] = (np.asarray(table), np.asarray(state.position))
        return views

    def _launch_vectorized(self, active: list[int], fn, sampled: bool):
        """Dispatch one wave; returns (wave callable, raw device output).

        The output is already-selected tokens on the default fused
        pipeline (token selection — greedy argmax or the sampling
        kernel — runs inside the wave executable, so logits never leave
        the device; over a MeshBackend sharded logits never even leave
        their shards), or raw logits on the pre-fused reference wave
        (``fuse_wave=False``) — ``step`` branches on the wave's
        ``returns_tokens`` flag when it blocks on the result.

        Fused waves take and return the stacked sampler rows (RNG
        counters advance on-device, one per emitted token), and enable
        device-side token feedback: when every active slot's next input
        token equals what the previous wave already holds on device
        (steady decode — no admissions between waves), the previous
        output array is fed back directly and the wave launches with
        zero host->device transfers. Slot rows are vmapped
        (independent), so inactive slots' device values being arbitrary
        cannot affect any active slot's tokens.
        """
        desired = np.zeros((self.max_batch,), np.int32)
        for s in active:
            desired[s] = self.slots[s].last_token
        wave = self._wave_for(fn, sampled)
        if (self._token_feedback is not None
                and self._token_feedback_np is not None
                and all(desired[s] == self._token_feedback_np[s]
                        for s in active)):
            tok_in = self._token_feedback
        else:
            tok_in = jnp.asarray(desired.reshape(self.max_batch, 1, 1))
        if getattr(wave, "returns_tokens", False):
            out, self.batched, self._sampler_rows = wave(
                self.batched, tok_in, self._sampler_rows)
            self._token_feedback = out  # (max_batch, 1, 1) device tokens
        else:
            out, self.batched = wave(self.batched, tok_in)
        return wave, out

    def _run_looped(self, active: list[int], fn
                    ) -> tuple[np.ndarray, np.ndarray]:
        next_tok = np.zeros((self.max_batch,), np.int32)
        logps = np.zeros((self.max_batch,), np.float32)
        for s in active:
            handle = self.slots[s]
            last = jnp.asarray([[handle.last_token]], jnp.int32)
            logits, self.states[s] = fn(self.states[s], last)
            spec = handle.request.sampler
            if spec is None or spec.is_greedy:
                next_tok[s] = int(np.argmax(np.asarray(logits[0])))
            else:
                # same kernel, same counter: len(_tokens) tokens emitted
                # so far == the position of the one being sampled now
                next_tok[s] = sample_token(np.asarray(logits[0]), spec,
                                           position=len(handle._tokens))
            logps[s] = self._logp_of(logits[0], int(next_tok[s]))
        return next_tok, logps

    def _emit_wave(self, active: list[int], next_tok: np.ndarray,
                   logps: np.ndarray, use_sectored: bool) -> int:
        produced = 0
        for s in active:
            handle = self.slots[s]
            tok = int(next_tok[s])
            handle._tokens.append(tok)
            handle._logprobs.append(float(logps[s]))
            produced += 1
            self.stats["decode_steps"] += 1
            # the cache rows this step attended: the slot's length + 1
            self.stats["context_tokens"] += (len(handle.request.prompt)
                                             + len(handle._tokens) - 1)
            if use_sectored:
                self.stats["sectored_steps"] += 1
            if tok in handle._stop:
                self._finish(s, stopped=True)
            elif len(handle._tokens) >= handle.request.max_new_tokens:
                self._finish(s)
        return produced

    def run_until_drained(self,
                          max_steps: int | None = None) -> dict[str, int]:
        """Step until every queued request completes (default bound: the
        session's ``max_stream_steps``; the bound raises
        :class:`StreamTruncated` rather than silently returning)."""
        limit = self.max_stream_steps if max_steps is None else max_steps
        steps = 0
        while not self.idle:
            self.step()
            steps += 1
            if steps > limit:
                if self.obs is not None:
                    self.obs.on_truncated()
                raise StreamTruncated(
                    f"engine did not drain within {limit} steps "
                    f"(queued={len(self.queue)}, "
                    f"active={len(self.active_slots())}); raise the limit "
                    f"via ServeSession(max_stream_steps=...) or "
                    f"run_until_drained(max_steps=...)")
        return self.stats


def make_session(backend_or_fns, *, max_batch: int = 8,
                 scheduler: Scheduler | None = None,
                 policy: SectorPolicy | None = None,
                 vectorized: bool = True,
                 fuse_wave: bool = True,
                 page_pool: KVPagePool | None = None,
                 prefix_cache: PrefixCache | None = None,
                 obs=None,
                 max_stream_steps: int = 10_000) -> ServeSession:
    """Convenience constructor accepting a backend or the legacy 4-tuple."""
    if isinstance(backend_or_fns, (tuple, list)):
        backend_or_fns = ServingBackend(*backend_or_fns)
    return ServeSession(backend_or_fns, max_batch=max_batch,
                        scheduler=scheduler, policy=policy,
                        vectorized=vectorized, fuse_wave=fuse_wave,
                        page_pool=page_pool, prefix_cache=prefix_cache,
                        obs=obs, max_stream_steps=max_stream_steps)
