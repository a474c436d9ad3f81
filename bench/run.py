"""Chip benchmark of the sectored serving path: one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its files are
found by name: ``bench/workloads/<cell>.json`` (session settings and the
limits of the check), ``bench/configs/<config>.json`` (the sizes, as run),
``bench/families/<family>.py`` (the code that depends on the architecture
the sizes' ``"family"`` names: program fields, weight leaves, reference
equations, operation counts), ``bench/traffic/<traffic>.json`` (the
traffic's parameters, read by ``bench/generator.py``) and
``bench/metrics/<metric>.py`` (one reader per per-layer metric).

A run, in order: refuse to run without a TPU or with fewer chips than the
cell asks for; turn on the compile cache; make the weights on the device
from ``--seed``; build the session through ``repro.launch.serve.
build_session``; prefill the shared document, if the traffic has one, into
the prefix cache; warm up every prompt length and the decode wave; bring the
load to a steady state (all of that is ``setup_s``); measure for
``--seconds``; read the peak device memory; free the program's state; run
the float32 reference over a sample of the finished requests and compare.

Token times are taken on the host clock when ``ServeSession.step()`` has
returned (it blocks on the wave's tokens); every token a handle's ``poll()``
shows is stamped with that time. With ``--trace 1`` the profiler records the
first seconds of the window, and the run prints the per-layer metrics
instead of the end-to-end ones.

The last line of standard output is one JSON object; the numbers compared
for ``correct`` are also the last lines of standard error.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import costs, families, generator  # noqa: E402

BENCH = ROOT / "bench"
OUT = ROOT / "bench_out"
TRACE_SECONDS = 6.0


class NoChip(SystemExit):
    pass


def load_cell(name: str) -> SimpleNamespace:
    """The cell's entry in BENCHMARK.json and its files, by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(entries)}")
    entry = entries[name]
    cell = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(f"{name}: {key} is {entry[key]!r} in "
                             f"BENCHMARK.json but {cell[key]!r} in its file")
    sizes = families.load(BENCH / "configs" / f"{entry['config']}.json")
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    return SimpleNamespace(name=name, cell=cell, sizes=sizes,
                           traffic=generator.load(entry["traffic"]),
                           chips=int(entry["chips"]), per_layer=per_layer,
                           end_to_end=bench["end_to_end"])


def program_config(sizes: dict):
    """The program's config for the sizes: the published config of
    ``sizes["arch"]`` with the fields that the sizes' family sets."""
    from repro import configs
    base = configs.get(sizes["arch"])
    return dataclasses.replace(base, name=f"{base.name}-bench",
                               **families.of(sizes).program_fields(sizes))


def require_chips(chips: int) -> None:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform!r}", file=sys.stderr)
        raise NoChip(2)
    if len(devices) < chips:
        print(f"the cell needs {chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        raise NoChip(2)


def enable_cache() -> None:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@dataclasses.dataclass
class Rec:
    """One request as the benchmark saw it."""

    rid: int
    prompt: np.ndarray
    new_tokens: int
    due: float  # when it was due (open loop) or submitted (closed loop)
    submitted: float
    handle: object = None
    served: np.ndarray | None = None
    stamps: list = dataclasses.field(default_factory=list)


class Compiles:
    """Counts backend compiles (JAX's monitoring event), so a run can show
    that none happened inside its window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.n += 1


class GcPauses:
    """Seconds the interpreter spends collecting garbage, so a run can show
    whether a stall in its window was a collection."""

    def __init__(self, clock):
        self.clock, self.s, self._t = clock, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = self.clock()
        elif self._t is not None:
            self.s += self.clock() - self._t
            self._t = None

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Loop:
    """Drives the session and keeps the records; one per run."""

    def __init__(self, session, planner, sizes, selected, clock):
        self.session = session
        self.planner = planner
        self.sizes = sizes
        self.selected = selected
        self.clock = clock
        self.recs: list[Rec] = []
        self.inflight: list[Rec] = []
        self.steps: list[dict] = []
        self.next_index = 0

    def submit(self, due: float) -> Rec:
        from repro.serve import Request
        planned = self.planner.get(self.next_index)
        self.next_index += 1
        with annotate("submit"):
            now = self.clock()
            rec = Rec(rid=planned.index, prompt=planned.prompt,
                      new_tokens=planned.new_tokens, due=due, submitted=now)
            rec.handle = self.session.submit(Request(
                planned.index, planned.prompt,
                max_new_tokens=planned.new_tokens))
        self.recs.append(rec)
        self.inflight.append(rec)
        return rec

    def step(self) -> list[Rec]:
        """One session step; stamps new tokens; returns finished requests."""
        hits0 = self._hit_tokens()
        t0 = self.clock()
        with annotate("session.step"):
            self.session.step()
        t1 = self.clock()
        flops = 0.0
        decoded = admitted_prompt = 0
        done = []
        with annotate("poll"):
            for rec in self.inflight:
                new = rec.handle.poll()
                if not new:
                    continue
                start = len(rec.stamps)
                rec.stamps += [t1] * len(new)
                for j in range(start, start + len(new)):
                    if j == 0:
                        admitted_prompt += len(rec.prompt)
                        flops += self._prefill_flops(rec)
                    else:
                        pos = len(rec.prompt) + j - 1
                        flops += costs.token_flops(
                            self.sizes, costs.attended_decode(
                                pos, self.selected))
                        decoded += 1
                if rec.handle.done:
                    done.append(rec)
        self.inflight = [r for r in self.inflight if not r.handle.done]
        hit_tokens = self._hit_tokens() - hits0
        self.steps.append(dict(t0=t0, t1=t1, flops=flops, decoded=decoded,
                               prefilled=admitted_prompt - hit_tokens,
                               admitted_prompt=admitted_prompt,
                               hit_tokens=hit_tokens))
        return done

    def _hit_tokens(self) -> int:
        cache = self.session.prefix_cache
        return 0 if cache is None else cache.stats["hit_tokens"]

    def _prefill_flops(self, rec: Rec) -> float:
        """The prompt tokens the prefill scanned, after the tokens that the
        prefix cache matched for this admission."""
        cache = self.session.prefix_cache
        start = 0 if cache is None else cache.matched.pop(
            rec.prompt.tobytes(), 0)
        return sum(costs.token_flops(self.sizes, p + 1)
                   for p in range(start, len(rec.prompt)))

    def drain(self, rec: Rec) -> None:
        while not rec.handle.done:
            self.step()


def pct(values, q: float) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None


def end_to_end(loop: Loop, t0: float, t1: float) -> dict:
    tokens = sum(1 for r in loop.recs for s in r.stamps if t0 <= s < t1)
    ttft = [(r.stamps[0] - r.due) * 1e3 for r in loop.recs
            if r.stamps and t0 <= r.stamps[0] < t1]
    gaps = [(b - a) * 1e3 for r in loop.recs
            for a, b in zip(r.stamps, r.stamps[1:]) if t0 <= b < t1]
    return dict(output_tok_s=tokens / (t1 - t0), ttft_p50_ms=pct(ttft, 50),
                itl_p50_ms=pct(gaps, 50), itl_p95_ms=pct(gaps, 95),
                n_ttft=len(ttft), n_gaps=len(gaps), tokens=tokens)


def check_sample(loop: Loop, seed: int, n: int) -> list[Rec]:
    """The finished requests the reference reads: the longest, then others
    drawn from the seed."""
    done = [r for r in loop.recs if r.handle.done and r.rid >= 0]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    longest = max(done, key=lambda r: (len(r.handle.peek()), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = generator.rng_for(seed, 3)
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def widest(gaps: list) -> dict:
    """The widest gap over every served token of the sample: the first
    token of each request (its prefill) and every decoded one."""
    return dict(gap=max(float(g.max()) for g in gaps))


def judge(found: dict, limits: dict) -> tuple[dict, bool]:
    """Each number compared beside its limit, and whether all are within
    them: the same judgement for the program and for the control."""
    numbers = {k: dict(value=found.get(k), limit=limits[k]) for k in limits}
    return numbers, bool(found) and all(
        v["value"] is not None and v["value"] <= v["limit"]
        for v in numbers.values())


def compare(sizes, seed, recs: list[Rec], traffic,
            control: bool = False) -> dict:
    """Widest gaps of the served tokens below the reference's best."""
    from bench import reference, weights
    doc = int(traffic.get("shared_prefix_tokens", 0))
    params = weights.make(sizes, seed)
    prefix = recs[0].prompt[:doc] if doc else np.zeros((0,), np.int32)
    tails = [r.prompt[doc:] for r in recs]
    served = [r.served for r in recs]
    longest = max(len(t) + len(s) - 1 for t, s in zip(tails, served))
    pad = -(-(generator.max_context(traffic) - doc) // 128) * 128
    if longest > pad:
        raise ValueError(f"a request is longer ({longest}) than the "
                         f"traffic allows ({pad})")
    out = reference.gaps(sizes, params, prefix, tails, served, pad,
                         control=control)
    del params
    found = widest(out[0] if control else out)
    if control:
        found["control"] = widest(out[1])
    return dict(found, tokens=int(sum(len(s) for s in served)),
                requests=len(recs))


def run_cell(spec: SimpleNamespace, seed: int, seconds: float, trace: bool,
             *, kernel: str | None = None, require_tpu: bool = True,
             session_hook=None,
             control: bool = False) -> dict:
    import jax
    from repro.launch.serve import build_session
    from bench import weights

    if require_tpu:
        require_chips(spec.chips)
    enable_cache()
    compiles = Compiles()
    clock = time.perf_counter
    sizes, traffic, cell = spec.sizes, spec.traffic, spec.cell
    ses = cell["session"]
    cfg = program_config(sizes)
    params = weights.make(sizes, seed)
    jax.block_until_ready(params)
    cache_pages = int(ses.get("prefix_cache_pages", 0))
    session = build_session(
        cfg, params, max_batch=int(ses["max_batch"]), true_sectored=True,
        kernel=kernel or ses["kernel"], policy=ses["policy"],
        seq_len=int(ses["seq_len"]), scheduler=ses["scheduler"],
        prefix_cache=_matching_cache(cache_pages) if cache_pages else None)
    if session_hook is not None:
        session_hook(session)
    backend = session.backend
    selected = backend.k_for(None) + backend.probe_pages_for(
        backend.k_for(None))
    planner = generator.Planner(traffic, seed, sizes["vocab"])
    loop = Loop(session, planner, sizes, selected, clock)

    # set-up: the shared document, then one request per prompt length
    doc = planner.doc
    if len(doc):
        loop.drain(_warm(loop, doc, 1, clock))
    warm = generator.rng_for(seed, 4)
    for n in traffic["prompt_tokens"]["values"]:
        own = warm.integers(0, sizes["vocab"], size=n).astype(np.int32)
        loop.drain(_warm(loop, np.concatenate([doc, own]), 2, clock))
    loop.recs.clear()

    # steady state, then the window
    closed = traffic["loop"] == "closed"
    t_ramp = clock()
    if closed:
        for _ in range(int(traffic["clients"])):
            loop.submit(clock())
        completions = 0
        while completions < int(traffic["warmup_completions"]):
            for _ in loop.step():
                completions += 1
                loop.submit(clock())
        t0 = clock()
    else:
        due = t_ramp + planner.get(0).gap_s
        t0 = t_ramp + float(traffic["warmup_s"])
    setup_s = t0 - T_PROCESS
    t1 = t0 + seconds
    stats0 = dict(session.stats)
    compiles0 = compiles.n
    pauses = GcPauses(clock)
    backlog = [None, None]
    tracer = Tracer(trace, clock, loop)
    while True:
        now = clock()
        if now >= t1:
            break
        if now >= t0:
            if backlog[0] is None:
                backlog[0] = len(loop.inflight)
            tracer.poll(now)
        if closed:
            for _ in loop.step():
                loop.submit(clock())
            continue
        while due <= now:
            loop.submit(due)
            due += planner.get(loop.next_index).gap_s
        if session.idle:
            with annotate("wait_arrivals"):
                time.sleep(max(min(due, t1) - clock(), 0.0))
            continue
        loop.step()
    window_compiles = compiles.n - compiles0
    pauses.close()
    tracer.stop()
    backlog[1] = len(loop.inflight)
    stats1 = dict(session.stats)
    window_steps = [s for s in loop.steps if t0 <= s["t0"] and s["t1"] < t1]
    longest_step = max((s["t1"] - s["t0"] for s in window_steps), default=0.0)
    longest_between = max((b["t0"] - a["t1"] for a, b in
                           zip(window_steps, window_steps[1:])), default=0.0)
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in jax.local_devices())
    e2e = end_to_end(loop, t0, t1)
    lags = [(r.submitted - r.due) * 1e3 for r in loop.recs
            if t0 <= r.due < t1]
    attempted = sum(1 for r in loop.recs if t0 <= r.due < t1)

    # what the per-layer readers (bench/metrics/*.py) read
    ctx = SimpleNamespace(
        sizes=sizes, cell=cell, traffic=traffic, selected=selected,
        max_batch=int(ses["max_batch"]), stats=(stats0, stats1),
        steps=window_steps, lags_ms=lags, trace=None, trace_steps=[],
        kind=jax.devices()[0].device_kind)
    device = dict(platform=jax.devices()[0].platform,
                  kind=jax.devices()[0].device_kind,
                  count=len(jax.devices()), memory_peak_bytes=memory_peak)
    breakdown = None
    if trace:
        # the host's per-layer numbers too are read over the traced span:
        # stopping the profiler stalls the loop after it
        a, b = tracer.t_start, tracer.t_stop
        ctx.stats = tracer.stats
        ctx.steps = [s for s in loop.steps if a <= s["t0"] and s["t1"] < b]
        ctx.lags_ms = [(r.submitted - r.due) * 1e3 for r in loop.recs
                       if a <= r.due < b]
        ctx.trace, ctx.trace_steps = tracer.reduce()
        from bench import trace as tr
        device["busy_s"] = tr.busy_ns(ctx.trace) / 1e9
        device["window_s"] = ctx.trace.window_s
        breakdown = dict(device_ops=tr.top_ops(ctx.trace),
                         idle_gaps=tr.idle_gaps(ctx.trace))

    # free the program's state before the reference runs
    sample = check_sample(loop, seed, int(traffic["check_requests"]))
    for rec in loop.recs:
        rec.served = np.asarray(rec.handle.peek(), np.int32)
        rec.handle = None
    session = backend = loop.session = params = None
    gc.collect()
    check = compare(sizes, seed, sample, traffic, control) if sample else {}
    numbers, correct = judge(check, cell["check"])

    if trace:
        metrics = {}
        for m in spec.per_layer:
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
    else:
        metrics = {"setup_s": dict(value=setup_s, unit="s")}
        for m in spec.end_to_end:
            if m["name"] != "setup_s" and e2e.get(m["name"]) is not None:
                metrics[m["name"]] = dict(value=e2e[m["name"]],
                                          unit=m["unit"])
    result = dict(correct=correct, attempted=attempted, failed=0,
                  metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["info"] = dict(
        ttft_samples=e2e["n_ttft"], gap_samples=e2e["n_gaps"],
        window_tokens=e2e["tokens"], window_waves=stats1["waves"]
        - stats0["waves"], completed=stats1["completed"]
        - stats0["completed"], checked_requests=check.get("requests", 0),
        checked_tokens=check.get("tokens", 0),
        gen_lag_max_ms=max(lags, default=0.0), backlog=backlog,
        window_compiles=window_compiles, window_gc_s=pauses.s,
        longest_step_s=longest_step, longest_between_steps_s=longest_between,
        kernel=kernel or ses["kernel"])
    if control:
        ctl_numbers, ctl_correct = judge(check.get("control", {}),
                                         cell["check"])
        result["control"] = dict(check=ctl_numbers, correct=ctl_correct)
    result["check"] = numbers
    return result


def _matching_cache(pages: int):
    """The program's prefix cache, noting how many prompt tokens each
    admission matched (its share of ``stats["hit_tokens"]``), by prompt."""
    from repro.serve.prefix import PrefixCache

    class Matching(PrefixCache):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.matched: dict[bytes, int] = {}

        def acquire(self, tokens, **kwargs):
            lease = super().acquire(tokens, **kwargs)
            key = np.asarray(tokens, np.int32).tobytes()
            self.matched[key] = lease.matched_tokens if lease else 0
            return lease

    return Matching(pages)


def _warm(loop: Loop, prompt: np.ndarray, new_tokens: int, clock) -> Rec:
    from repro.serve import Request
    rec = Rec(rid=-1 - len(loop.recs), prompt=prompt, new_tokens=new_tokens,
              due=clock(), submitted=clock())
    rec.handle = loop.session.submit(Request(rec.rid, prompt,
                                             max_new_tokens=new_tokens))
    loop.recs.append(rec)
    loop.inflight.append(rec)
    return rec


class Tracer:
    """Profiles the first ``TRACE_SECONDS`` of the window of a traced run,
    and on until a prefill has run in it, so that every per-layer metric
    finds something to read."""

    def __init__(self, on: bool, clock, loop: Loop):
        self.on = on
        self.clock = clock
        self.loop = loop
        self.state = "idle"
        self.dir = OUT / "trace"
        self.span = None
        self.first_step = self.last_step = 0

    def poll(self, now: float) -> None:
        import jax
        if not self.on:
            return
        if self.state == "idle":
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True)
            jax.profiler.start_trace(str(self.dir))
            self.span = annotate("bench.window")
            self.span.__enter__()
            self.first_step = len(self.loop.steps)
            self.stats = [dict(self.loop.session.stats), None]
            self.t_start = self.clock()
            self.state = "on"
        elif (self.state == "on" and now - self.t_start >= TRACE_SECONDS
              and any(s["prefilled"] for s in
                      self.loop.steps[self.first_step:])):
            self._close()

    def _close(self) -> None:
        import jax
        self.last_step = len(self.loop.steps)
        self.t_stop = self.clock()
        self.stats[1] = dict(self.loop.session.stats)
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def stop(self) -> None:
        if self.state == "on":
            self._close()

    def reduce(self):
        from bench import trace as tr
        steps = self.loop.steps[self.first_step:self.last_step]
        return tr.load(tr.find_xplane(str(self.dir))), steps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    try:
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        return int(e.code)
    for name, v in result["check"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
