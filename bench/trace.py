"""Reduction of a profiler trace to the events the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; :func:`load` keeps, for the first
TPU device, its op events (line ``XLA Ops``) and its program events (line
``XLA Modules``), and the host annotations that ``bench/run.py`` writes with
``jax.profiler.TraceAnnotation``. All times are nanoseconds on the trace's
one clock. The traced window is the host span named ``WINDOW``.

Device names as the trace gives them on a TPU v5e (read by hand from a
trace of this benchmark): program events (``XLA Modules``) are
``jit_<function>(<fingerprint>)``, so the decode wave is ``jit_fused(..)``,
a cold prefill ``jit__lambda(..)`` (the prefill is a jitted lambda) and a
warm suffix prefill ``jit__scan_prompt(..)``. Op events (``XLA Ops``) are
named by the whole HLO instruction, shapes and layouts included; the
reduction keeps ``<instruction> <opcode>``, e.g. ``fusion.216 fusion``.
The sectored attention kernel, a ``pallas_call`` without a name, is the
wave's ``custom-call`` (``closed_call.N custom-call``); under the wave's
vmap it runs once per slot and layer.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os

WINDOW = "bench.window"
STEP = "session.step"
WAVE = "jit_fused("
PREFILLS = ("jit__lambda(", "jit__scan_prompt(")
KERNEL = " custom-call"
CONTAINERS = ("while", "conditional", "call")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduced:
    ops: list  # [name, start_ns, dur_ns] device ops
    modules: list  # [name, start_ns, dur_ns] device programs
    host: list  # [name, start_ns, dur_ns] host annotations
    window: tuple  # (start_ns, end_ns)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @classmethod
    def from_json(cls, d: dict) -> "Reduced":
        return cls(ops=d["ops"], modules=d["modules"], host=d["host"],
                   window=tuple(d["window"]))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def short_name(name: str) -> str:
    """``%fusion.216 = (bf16[..], ..) fusion(...)`` -> ``fusion.216
    fusion``; a name that is not an HLO instruction stays as it is."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    i = 0
    if rest.startswith("("):  # a tuple shape: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
    rest = rest[i + 1:] if rest.startswith("(") else rest.partition(" ")[2]
    opcode = rest.strip().split("(", 1)[0].split(" ")[0]
    return f"{head.lstrip('%')} {opcode}"


def _events(line, short: bool = False) -> list:
    return [[short_name(e.name) if short else e.name, float(e.start_ns),
             float(e.duration_ns)] for e in line.events]


def load(path: str) -> Reduced:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices = sorted((p for p in data.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: p.name)
    ops, modules, host = [], [], []
    if devices:
        for line in devices[0].lines:
            if line.name == OPS_LINE:
                ops = _events(line, short=True)
            elif line.name == MODULES_LINE:
                modules = _events(line)
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                host += [e for e in _events(line) if e[0].startswith(
                    ("bench.", "session.", "submit", "poll", "wait"))]
    spans = [h for h in host if h[0] == WINDOW]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    w = spans[0]
    return Reduced(ops=ops, modules=modules, host=host,
                   window=(w[1], w[1] + w[2]))


# -- arithmetic on the reduced events ----------------------------------------


def clip(events: list, t0: float, t1: float) -> list:
    """Intervals of the events inside [t0, t1]."""
    out = []
    for _, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: list) -> list:
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(r: Reduced) -> float:
    return sum(b - a for a, b in union(clip(r.ops, *r.window)))


def matching(events: list, needle: str) -> list:
    return [e for e in events if needle in e[0]]


def within(events: list, spans: list) -> list:
    """Events that start inside one of ``spans`` ([name, start, dur]);
    the spans do not overlap."""
    spans = sorted((s, s + d) for _, s, d in spans)
    starts = [a for a, _ in spans]
    out = []
    for e in events:
        i = bisect.bisect_right(starts, e[1]) - 1
        if i >= 0 and e[1] < spans[i][1]:
            out.append(e)
    return out


def owners(events: list, spans: list) -> list:
    """For each event, the index of the span ((start, end)) it overlaps
    most, or None. Host spans and device events lie on clocks that the
    profiler aligns to within about a millisecond, so a program that a
    step launches can start a little before the step's host span."""
    out = []
    for _, s, d in events:
        best, most = None, 0.0
        for i, (a, b) in enumerate(spans):
            lap = min(s + d, b) - max(s, a)
            if lap > most:
                best, most = i, lap
        out.append(best)
    return out


def in_window(r: Reduced, events: list) -> list:
    t0, t1 = r.window
    return [e for e in events if t0 <= e[1] and e[1] + e[2] <= t1]


def top_ops(r: Reduced, n: int = 10) -> list:
    """The ops with the most device time in the window; loops and calls,
    whose time is that of the ops inside them, are left out."""
    total: dict[str, float] = {}
    for name, _, d in in_window(r, r.ops):
        if name.rsplit(" ", 1)[-1] in CONTAINERS:
            continue
        total[name] = total.get(name, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            [:n]]


def idle_gaps(r: Reduced, n: int = 10) -> list:
    """The longest device idle gaps in the window, each named by the
    innermost host annotation (other than the window) that covers its
    middle."""
    t0, t1 = r.window
    busy = union(clip(r.ops, t0, t1))
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = [h for h in r.host if h[0] != WINDOW]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        covering = [h for h in host if h[1] <= mid <= h[1] + h[2]]
        name = (min(covering, key=lambda h: h[2])[0] if covering
                else "no host span")
        out.append([name, (b - a) / 1e9])
    return out


def step_spans(r: Reduced, steps: list) -> list:
    """(start_ns, end_ns, step) of the session steps inside the window,
    paired in order with the benchmark's records of the same steps."""
    t0, t1 = r.window
    spans = sorted((s, s + d) for name, s, d in r.host
                   if name == STEP and t0 <= s and s + d <= t1)
    return [(a, b, step) for (a, b), step in zip(spans, steps)]
