"""Serving launcher: ServeSession with pluggable scheduler / policy /
backend over the sectored decode path.

``python -m repro.launch.serve --arch yi-6b --reduced --requests 8``

Backend modes:

* default — dense DecodeState slots; the sectored/dense toggle exercises the
  §8.1 dynamic mechanism over the same dense step (state migration between
  paths is trivial).
* ``--true-sectored`` — slots hold SectoredState; the dense-equivalent path
  is the bit-exact exact mode (every valid page fetched) and the
  high-occupancy path is predictor top-k with the shared-prefix
  sector-demand OR-merge pooling SHT scores across slots before each fetch.
* ``--fused-kernel`` (needs ``--true-sectored``) — the sectored decode
  step runs as ONE Pallas kernel (scalar-prefetched page steering →
  per-page DMA → softmax attend), bit-exact with the dispatch path;
  ``--kv-quant`` additionally reads per-sector int8 KV dequantized
  inside the kernel (tolerance-gated — see docs/serving.md).

Scheduler modes (``--scheduler``):

* ``fifo`` — blocking head-of-queue admission (legacy behaviour).
* ``overlap`` — prefill double-buffered against the in-flight decode wave
  with paged-KV admission (``stats['overlapped_prefills']`` counts prompts
  prefilled while a wave was in flight).

``--engine looped`` swaps in the per-slot reference wave (for comparison;
``benchmarks/serve_throughput.py`` measures the gap and writes
``BENCH_serve.json``).

``--telemetry`` wraps the backend in a ``MeteredBackend``: every wave is
charged against the paper's calibrated DRAM power model and an end-of-run
energy/coverage table is printed (``--trace-out`` additionally dumps the
per-wave trace as JSONL; ``--bg-energy`` adds the modeled
background/refresh component). ``--policy adaptive`` runs the
coverage-driven ``AdaptiveSectorPolicy`` over the meter's recorder
(implies ``--telemetry``).

``--prefix-cache`` (needs ``--true-sectored``) enables the cross-request
radix prefix cache: admission matches each prompt against previously
prefilled prompts, seeds the slot from the shared entry's read-only KV,
and re-prefills only the unmatched suffix. ``--shared-prefix N`` prepends
N common tokens to every generated prompt so the cache demonstrably hits;
the end-of-run line grows hit-rate / shared-page / CoW columns.

``--obs`` attaches the flight recorder (``repro.obs``): per-request span
tracing on the virtual step clock plus a serving metrics registry, with
an end-of-run summary table and optional ``--obs-trace-out`` (JSONL) /
``--obs-perfetto-out`` (Chrome/Perfetto ``trace_event`` JSON) exports.
``--obs-commands`` (needs ``--telemetry``) additionally records every
metered wave's synthesized DRAM command timeline — the Perfetto export
grows a dedicated command track and the JSONL export a ``.commands``
sibling (see docs/observability.md). Tracing is observer-effect-free:
token streams, logprobs, and joules are bit-identical with the flag on
or off (oracle in benchmarks/traffic.py).

Sampling (``--temperature`` > 0 turns it on): each request gets a
``SamplerSpec(temperature, top_k, top_p, seed=--seed + rid)`` — the
per-request seed derivation is printed as a provenance column so any
single stream can be reproduced in isolation (counter-based RNG:
tokens depend only on (seed, position), never on batch composition,
scheduler, or mesh shape). ``--sample-every N`` samples only every Nth
request (default 1 = all), demonstrating mixed greedy+sampled waves.
"""

from __future__ import annotations

import argparse
import functools

import jax
import numpy as np

from repro import configs
from repro.core import metrics
from repro.launch import mesh as mesh_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model
from repro.runtime import sectored_decode
from repro.obs import (FlightRecorder, MetricsRegistry, write_jsonl,
                       write_perfetto)
from repro.sample import SamplerSpec
from repro.serve import (AdaptiveSectorPolicy, AlwaysDense, AlwaysSectored,
                         EngineConfig, FifoScheduler, HysteresisPolicy,
                         KVPagePool, MeshBackend, OverlapScheduler,
                         PrefixCache, Request, ServeSession, ServingBackend)
from repro.serve import engine as engine_mod  # noqa: F401  (legacy re-export)
from repro.telemetry import KVGeometry, MeteredBackend


def build_backend(cfg, params, *, sectored=True, true_sectored=False,
                  seq_len=256, kernel="dispatch"):
    """The data-path object: SectoredState-backed or dense DecodeState.

    ``kernel`` picks the sectored decode flavor (``--fused-kernel`` /
    ``--kv-quant``): ``"dispatch"`` (batched gather+attend), ``"fused"``
    (single Pallas kernel, bit-exact with dispatch), or ``"fused_q8"``
    (fused + per-sector int8 KV, tolerance-gated).
    """
    if true_sectored and (cfg.attn_free or cfg.layer_pattern):
        raise ValueError(
            f"--true-sectored needs uniform attention layers; arch "
            f"{cfg.name!r} is attention-free or hybrid. Drop the flag to "
            f"serve it on the dense path.")
    if kernel != "dispatch" and not true_sectored:
        raise ValueError(
            "--fused-kernel/--kv-quant need --true-sectored (the dense "
            "DecodeState backend has no paged KV for the kernel to steer)")
    if true_sectored:
        backend = sectored_decode.make_serving_fns(cfg, params=params,
                                                   seq_len=seq_len,
                                                   kernel=kernel)
        if not sectored:
            backend.sectored_fn = None
        return backend

    # weights bound outside jit, so the waves built on these steps take
    # them as an argument (serve.backend.split_bound)
    prefill_fn = functools.partial(
        jax.jit(lambda p, tokens: model.prefill(p, cfg, tokens)), params)
    decode_fn = functools.partial(
        jax.jit(lambda p, state, token: model.decode_step(p, cfg, state,
                                                          token)), params)

    sect_fn = None
    if sectored and not cfg.attn_free and not cfg.layer_pattern:
        # the sectored path drives the same dense state through the paper's
        # technique when occupancy is high (the policy handles the toggle);
        # dense-state compatibility keeps slot migration trivial
        sect_fn = decode_fn
    return ServingBackend(prefill_fn, decode_fn, sect_fn)


def build_policy(name, recorder=None):
    """Shipped SectorPolicy lineup (``--policy``); ``adaptive`` needs the
    meter's TraceRecorder as its coverage source."""
    if name == "adaptive":
        if recorder is None:
            raise ValueError("adaptive policy needs telemetry "
                             "(pass --telemetry / a recorder)")
        return AdaptiveSectorPolicy(recorder)
    return {"hysteresis": HysteresisPolicy, "dense": AlwaysDense,
            "sectored": AlwaysSectored}[name]()


def build_session(cfg, params, *, max_batch=4, sectored=True,
                  scheduler="fifo", vectorized=True, true_sectored=False,
                  seq_len=256, telemetry=False, policy="hysteresis",
                  mesh=None, bg_energy=False,
                  page_pool: KVPagePool | None = None,
                  prefix_cache: PrefixCache | None = None,
                  obs: FlightRecorder | None = None,
                  kernel="dispatch") -> ServeSession:
    backend = build_backend(cfg, params, sectored=sectored,
                            true_sectored=true_sectored, seq_len=seq_len,
                            kernel=kernel)
    if telemetry or policy == "adaptive":
        # the dense DecodeState backend carries no kv_geometry(); derive one
        # from the model config so the meter can convert counters to joules
        geometry = (None if true_sectored else KVGeometry.from_model_cfg(
            cfg, seq_len=seq_len, page_size=sectored_decode.PAGE_SIZE))
        backend = MeteredBackend(backend, geometry=geometry,
                                 background=bg_energy)
        if policy == "adaptive" and backend.k_for(None) is None:
            # without a per-k backend the adaptive fraction would be a
            # silent no-op reported as adaptive results — refuse loudly
            raise ValueError(
                "--policy adaptive needs a backend that resolves topk_frac "
                "to a page budget; add --true-sectored")
        pol = build_policy(policy, backend.meter.recorder)
    else:
        pol = build_policy(policy)
    if mesh is not None:
        mesh_obj = (mesh if not isinstance(mesh, str)
                    else mesh_mod.make_serving_mesh(mesh))
        if not vectorized:
            raise ValueError("--mesh needs the vectorized wave "
                             "(--engine vectorized)")
        # MeshBackend is the outermost decorator: the session discovers
        # its wave/placement hooks directly, the meter passes through
        backend = MeshBackend(backend, mesh_obj)
    sched = OverlapScheduler() if scheduler == "overlap" else FifoScheduler()
    return ServeSession(backend, max_batch=max_batch, scheduler=sched,
                        policy=pol, vectorized=vectorized,
                        page_pool=page_pool, prefix_cache=prefix_cache,
                        obs=obs)


def build_engine(cfg, params, max_batch=4, sectored=True, *,
                 engine_cls=engine_mod.Engine, true_sectored=False,
                 seq_len=256):
    """Legacy constructor kept for pre-redesign call sites: wires the
    backend's callables into an ``Engine``/``LoopedEngine`` shim."""
    backend = build_backend(cfg, params, sectored=sectored,
                            true_sectored=true_sectored, seq_len=seq_len)
    return engine_cls(backend.prefill_fn, backend.decode_fn,
                      backend.sectored_fn, EngineConfig(max_batch=max_batch),
                      demand_merge_fn=backend.demand_merge_fn)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--engine", choices=["vectorized", "looped"],
                    default="vectorized")
    ap.add_argument("--scheduler", choices=["fifo", "overlap"],
                    default="fifo",
                    help="fifo: blocking admission; overlap: prefill "
                         "double-buffered against the in-flight wave")
    ap.add_argument("--true-sectored", action="store_true",
                    help="serve on SectoredState (exact/top-k paths + "
                         "shared-prefix demand merge)")
    ap.add_argument("--fused-kernel", action="store_true",
                    help="with --true-sectored: run the sectored decode "
                         "step as ONE Pallas kernel (scalar-prefetched "
                         "page steering + per-page DMA + softmax attend); "
                         "bit-exact with the dispatch path")
    ap.add_argument("--kv-quant", action="store_true",
                    help="with --fused-kernel: per-sector int8 KV "
                         "quantization — narrower reads (half the bytes "
                         "per word, the paper's VBL analog) dequantized "
                         "inside the kernel; tolerance-gated, not "
                         "bit-exact (see docs/serving.md)")
    ap.add_argument("--telemetry", action="store_true",
                    help="meter every wave against the DRAM power model "
                         "and print an end-of-run energy/coverage table")
    ap.add_argument("--policy", default="hysteresis",
                    choices=["hysteresis", "dense", "sectored", "adaptive"],
                    help="SectorPolicy; adaptive = coverage-driven topk_frac "
                         "(implies --telemetry)")
    ap.add_argument("--trace-out", default=None,
                    help="with --telemetry: dump the per-wave trace JSONL "
                         "here")
    ap.add_argument("--obs", action="store_true",
                    help="attach the flight recorder: per-request span "
                         "tracing on the virtual step clock plus a serving "
                         "metrics registry rendered at end of run "
                         "(observer-effect contract: token streams, "
                         "logprobs, and joules are bit-identical with this "
                         "flag on or off)")
    ap.add_argument("--obs-trace-out", default=None, metavar="PATH",
                    help="with --obs: export the span trace as JSONL")
    ap.add_argument("--obs-perfetto-out", default=None, metavar="PATH",
                    help="with --obs: export the span trace as Chrome/"
                         "Perfetto trace_event JSON (open in ui.perfetto.dev "
                         "or chrome://tracing)")
    ap.add_argument("--obs-commands", action="store_true",
                    help="with --obs and --telemetry: record every metered "
                         "wave's/prefill's synthesized DRAM command "
                         "timeline; the Perfetto export grows a dedicated "
                         "'dram commands' track and --obs-trace-out gains "
                         "a sibling .commands.jsonl file")
    ap.add_argument("--bg-energy", action="store_true",
                    help="with --telemetry: add the modeled background/"
                         "refresh energy component (deterministic, derived "
                         "from the timing model — never wall-clock)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 (default) = greedy. "
                         "> 0 samples every --sample-every'th request")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus truncation mass (1.0 = off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base RNG seed; request rid samples with seed "
                         "(--seed + rid), printed as the provenance column")
    ap.add_argument("--sample-every", type=int, default=1,
                    help="sample every Nth request, leave the rest greedy "
                         "(mixed batches share one fused wave)")
    ap.add_argument("--stop-token", type=int, action="append", default=None,
                    metavar="ID", dest="stop_tokens",
                    help="EOS contract: a request finishes the moment it "
                         "emits this token id (repeatable, up to 8); the "
                         "stop token is emitted, nothing after it, and the "
                         "slot's KV pages free immediately")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="KV page pool capacity (pages); admission waits "
                         "when full and mid-stream growth preempts the "
                         "youngest-admitted requests (they resume "
                         "bit-identically). Default: unbounded")
    ap.add_argument("--kv-page-size", type=int, default=None,
                    help="tokens per pool page (default: the sectored "
                         "runtime's page quantum)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request radix prefix cache: seed admissions "
                         "from previously prefilled prompts' read-only KV "
                         "and re-prefill only the suffix (needs "
                         "--true-sectored: the dense backend has no "
                         "state_prefix/suffix_prefill hooks)")
    ap.add_argument("--prefix-cache-pages", type=int, default=64,
                    help="prefix cache capacity in KV pages (LRU over "
                         "unreferenced entries; default 64)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend N common tokens to every generated prompt "
                         "so --prefix-cache demonstrably hits (0 = fully "
                         "independent prompts)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="shard decode waves over a device mesh, e.g. "
                         "'4x2' (data=4, model=2) or '2' (data only); "
                         "tokens and joules are mesh-shape-invariant "
                         "(simulate devices on CPU with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    args = ap.parse_args(argv)
    if args.sample_every < 1:
        ap.error("--sample-every must be >= 1")
    if args.temperature == 0 and (args.top_k or args.top_p < 1.0
                                  or args.seed or args.sample_every != 1):
        # a filter/seed/stride without a temperature would silently
        # decode greedy — refuse loudly instead of faking a sampling run
        ap.error("--top-k/--top-p/--seed/--sample-every need "
                 "--temperature > 0 (temperature 0 is greedy decoding)")

    if ((args.obs_trace_out or args.obs_perfetto_out) and not args.obs):
        ap.error("--obs-trace-out/--obs-perfetto-out need --obs (there is "
                 "no span trace to export without the flight recorder)")
    if args.obs_commands and not args.obs:
        ap.error("--obs-commands needs --obs (the command track rides on "
                 "the flight recorder)")
    if args.obs_commands and not (args.telemetry
                                  or args.policy == "adaptive"):
        # command timelines are synthesized by the meter; without it the
        # flag would silently record nothing
        ap.error("--obs-commands needs --telemetry (the command timeline "
                 "is synthesized from the meter's counters)")
    if args.kv_page_size is not None and args.kv_pages is None:
        ap.error("--kv-page-size needs --kv-pages (an unbounded pool has "
                 "no page granularity to configure)")
    if args.kv_quant and not args.fused_kernel:
        # quantization lives inside the fused kernel's dequant stage; the
        # dispatch path has no narrow-read analog — refuse loudly
        ap.error("--kv-quant needs --fused-kernel (dequant runs inside "
                 "the fused kernel; the dispatch path reads full-width)")
    if args.fused_kernel and not args.true_sectored:
        ap.error("--fused-kernel needs --true-sectored (the dense backend "
                 "has no paged KV for the kernel to steer)")
    if args.prefix_cache and not args.true_sectored:
        # the dense DecodeState backend cannot seed a slot from a cached
        # KV prefix (no state_prefix/suffix_prefill) — refuse loudly
        # instead of silently serving cold
        ap.error("--prefix-cache needs --true-sectored (the dense backend "
                 "has no prefix-seeding hooks)")
    if args.shared_prefix and not args.prefix_cache:
        ap.error("--shared-prefix needs --prefix-cache (shared tokens "
                 "without a cache would just be re-prefilled every time)")

    enable_compile_cache()
    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = model.init_params(cfg, jax.random.key(0))
    telemetry = args.telemetry or args.policy == "adaptive"
    page_pool = None
    if args.kv_pages is not None:
        pool_kwargs = ({} if args.kv_page_size is None
                       else dict(page_size=args.kv_page_size))
        page_pool = KVPagePool(args.kv_pages, **pool_kwargs)
    prefix_cache = None
    if args.prefix_cache:
        # the cache's page quantum must agree with the pool's so shared
        # pages are charged consistently (the session enforces this)
        cache_kwargs = ({} if args.kv_page_size is None
                        else dict(page_size=args.kv_page_size))
        prefix_cache = PrefixCache(args.prefix_cache_pages, **cache_kwargs)
    obs = (FlightRecorder(MetricsRegistry(), commands=args.obs_commands)
           if args.obs else None)
    kernel = ("fused_q8" if args.kv_quant
              else "fused" if args.fused_kernel else "dispatch")
    sess = build_session(cfg, params, max_batch=args.max_batch,
                         scheduler=args.scheduler,
                         vectorized=args.engine == "vectorized",
                         true_sectored=args.true_sectored,
                         telemetry=telemetry, policy=args.policy,
                         mesh=args.mesh, bg_energy=args.bg_energy,
                         page_pool=page_pool, prefix_cache=prefix_cache,
                         obs=obs, kernel=kernel)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab,
                          size=args.shared_prefix).astype(np.int32)
    handles = []
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, size=8 + rid % 5).astype(np.int32)
        if args.shared_prefix:
            prompt = np.concatenate([shared, prompt])
        sampler = None
        if args.temperature > 0 and rid % args.sample_every == 0:
            # per-request seed derivation IS the provenance contract:
            # seed = --seed + rid, printed below so any one stream can be
            # replayed alone (counter-based RNG makes it bit-identical)
            sampler = SamplerSpec(temperature=args.temperature,
                                  top_k=args.top_k, top_p=args.top_p,
                                  seed=args.seed + rid)
        handles.append(sess.submit(Request(
            rid, prompt, max_new_tokens=args.max_new_tokens,
            sampler=sampler,
            stop_tokens=tuple(args.stop_tokens or ()))))
    stats = sess.run_until_drained()
    assert all(h.done for h in handles)
    mesh_tag = ("" if sess.mesh is None
                else f"mesh={'x'.join(map(str, sess.mesh.devices.shape))} ")
    pool_tag = ("" if sess.page_pool is None
                else f"preemptions={stats['preemptions']} "
                     f"kv_peak_pages={sess.page_pool.peak_pages} ")
    prefix_tag = ""
    if sess.prefix_cache is not None:
        c = sess.prefix_cache
        prefix_tag = (f"prefix_hits={c.stats['hits']}/"
                      f"{c.stats['hits'] + c.stats['misses']} "
                      f"(rate={c.hit_rate:.2f}) "
                      f"prefix_hit_tokens={c.stats['hit_tokens']} "
                      f"shared_pages_held={c.held_pages} "
                      f"cow_copies={c.stats['cow_copies']} "
                      f"prefix_evictions={c.stats['evictions']} ")
    print(f"arch={cfg.name} engine={args.engine} scheduler={args.scheduler} "
          f"{mesh_tag}completed={stats['completed']} "
          f"decode_steps={stats['decode_steps']} waves={stats['waves']} "
          f"sectored_steps={stats['sectored_steps']} "
          f"merged_slots={stats['merged_slots']} "
          f"overlapped_prefills={stats['overlapped_prefills']} "
          f"eos_stops={stats['eos_stops']} {pool_tag}{prefix_tag}"
          f"kv_bytes_saved_at_32k="
          f"{sectored_decode.bytes_saved_fraction(32768):.2f}")
    if args.temperature > 0:
        print_seed_provenance(handles, base_seed=args.seed)
    if telemetry:
        print_energy_report(sess, handles, trace_out=args.trace_out)
    if obs is not None:
        print_obs_report(obs, trace_out=args.obs_trace_out,
                         perfetto_out=args.obs_perfetto_out)


def print_seed_provenance(handles, *, base_seed: int, limit: int = 16) -> None:
    """Per-request seed provenance: how each stream's RNG identity was
    derived, so any one of them can be replayed in isolation."""
    print(f"-- sampling (base seed {base_seed}; per-request seed = "
          f"base + rid) ------------------")
    for h in handles[:limit]:
        spec = h.request.sampler
        desc = spec.describe() if spec is not None else "greedy"
        print(f"  rid={h.rid:3d} sampler={desc:28s} tokens={len(h.peek())}")
    if len(handles) > limit:
        print(f"  ... {len(handles) - limit} more requests")


def print_energy_report(sess, handles, *, trace_out=None) -> None:
    """End-of-run energy/coverage table from the session's WaveMeter."""
    meter = sess.meter
    report = meter.report()
    tokens = report["tokens"]
    print("-- telemetry ---------------------------------------------------")
    print(f"waves={report['waves']} (sectored={report['sectored_waves']} "
          f"dense={report['dense_waves']}) tokens={tokens} "
          f"demand_merges={report['demand_merges']}")
    print(f"pages fetched/valid: {report['pages_fetched']:.1f}/"
          f"{report['pages_valid']:.1f} "
          f"(coverage={report['sector_coverage']:.3f}, "
          f"EMA={report['ema'].get('sector_coverage', float('nan')):.3f}, "
          f"attn-mass EMA={report['ema'].get('attn_mass', float('nan')):.3f})")
    bg = ""
    if report["bg_j"] or report["ref_j"]:
        bg = (f" bg={report['bg_j'] * 1e3:.3f} "
              f"refresh={report['ref_j'] * 1e3:.3f}")
    print(f"DRAM energy: {report['energy_j'] * 1e3:.3f} mJ "
          f"(act={report['act_j'] * 1e3:.3f} rd={report['rd_j'] * 1e3:.3f} "
          f"wr={report['wr_j'] * 1e3:.3f} prefill={report['prefill_j'] * 1e3:.3f}"
          f"{bg}) "
          f"| {metrics.dram_energy_per_token(report['energy_j'], tokens) * 1e6:.3f} uJ/token "
          f"| wall={report['wall_s']:.3f}s")
    if report.get("prefix_hit_tokens") or report.get("shared_act_j"):
        shared_mj = (report["shared_act_j"] + report["shared_rd_j"]) * 1e3
        print(f"prefix reuse: {report['prefix_hit_tokens']} prompt tokens "
              f"served from cache; shared-fetch amortization credited "
              f"{shared_mj:.3f} mJ across co-readers")
    total_ns = report["dram_ns"] + report["prefill_dram_ns"]
    print(f"modeled DRAM time: {total_ns * 1e-3:.3f} us "
          f"(decode={report['dram_ns'] * 1e-3:.3f} "
          f"prefill={report['prefill_dram_ns'] * 1e-3:.3f}) "
          f"| {total_ns / tokens if tokens else 0.0:.1f} ns/token "
          f"(modeled from counters, not wall-clock)")
    if report["audit_checks"]:
        print(f"energy audit: {report['audit_checks']} reconciliations, "
              f"max rel err {report['audit_max_rel_err']:.3e} "
              f"(tolerance 1e-9)")
    for h in handles[:8]:
        t = h.telemetry
        print(f"  rid={h.rid:3d} tokens={t['tokens']:4d} "
              f"energy={t['energy_j'] * 1e6:9.3f} uJ "
              f"({metrics.dram_energy_per_token(t['energy_j'], t['tokens']) * 1e6:.3f} uJ/tok)")
    if len(handles) > 8:
        print(f"  ... {len(handles) - 8} more requests")
    if trace_out:
        path = meter.recorder.to_jsonl(trace_out)
        print(f"wrote per-wave trace: {path}")


def print_obs_report(obs, *, trace_out=None, perfetto_out=None) -> None:
    """Flight-recorder summary: the metrics snapshot table plus optional
    span-trace exports (JSONL and/or Perfetto; command-timeline records,
    when traced, ride along as a .commands.jsonl sibling and a dedicated
    Perfetto track)."""
    spans = obs.spans()
    commands = obs.command_records if obs.trace_commands else None
    print("-- flight recorder ---------------------------------------------")
    tag = (f" command_records={len(commands)}" if commands is not None
           else "")
    print(f"steps={obs.step} spans={len(spans)}{tag}")
    print(MetricsRegistry.render(obs.snapshot()))
    if trace_out:
        path = write_jsonl(spans, trace_out)
        print(f"wrote span trace: {path}")
        if commands is not None:
            cmd_path = write_jsonl(
                commands, str(trace_out) + ".commands.jsonl")
            print(f"wrote command trace: {cmd_path}")
    if perfetto_out:
        path = write_perfetto(spans, perfetto_out, commands=commands)
        print(f"wrote perfetto trace: {path} "
              f"(open in ui.perfetto.dev or chrome://tracing)")


if __name__ == "__main__":
    main()
