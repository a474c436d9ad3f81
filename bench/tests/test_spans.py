"""The readers of the session's host spans and scanned-token counter: on
a trace written by hand (numbers checked by hand) and through one traced
tiny run of the harness on the CPU, where the profiler records the host
spans and no device ops. The spans themselves are tested with the program
(``tests/test_session_spans.py``).
"""

from types import SimpleNamespace

import pytest

from bench import run
from bench import trace as tr
from bench.metrics import (prefill_span_ms_per_tok, step_host_ms,
                           wave_stall_ms)
from bench.tests import tiny

MS = 1e6  # nanoseconds
NEW_METRICS = ("step_host_ms", "wave_stall_ms", "prefill_span_ms_per_tok")


def hand_trace():
    """20 ms window. Step 1 (0.5-6.5 ms) admits a request: a 2.4 ms
    prefill, then a 2.2 ms wave sync; step 2 (7-10) waits 2.2 ms on its
    wave; the session then waits for arrivals (10.2-14); step 3 (14.2-15)
    runs no wave; step 4 (15-18) waits 2 ms, step 5 (18-19.8) 1.1 ms. A
    sync after the window is not read."""
    host = [["bench.window", 0.0, 20 * MS],
            ["session.step", 0.5 * MS, 6 * MS],
            ["session.schedule", 0.5 * MS, 3 * MS],
            ["session.prefill", 0.6 * MS, 2.4 * MS],
            ["session.install", 3.0 * MS, 0.4 * MS],
            ["session.wave_dispatch", 3.6 * MS, 0.2 * MS],
            ["session.wave_sync", 3.8 * MS, 2.2 * MS],
            ["session.emit", 6.0 * MS, 0.4 * MS],
            ["session.step", 7.0 * MS, 3 * MS],
            ["session.wave_sync", 7.3 * MS, 2.2 * MS],
            ["wait_arrivals", 10.2 * MS, 3.8 * MS],
            ["session.step", 14.2 * MS, 0.8 * MS],
            ["session.schedule", 14.2 * MS, 0.7 * MS],
            ["session.step", 15.0 * MS, 3 * MS],
            ["session.wave_sync", 15.5 * MS, 2 * MS],
            ["session.step", 18.0 * MS, 1.8 * MS],
            ["session.wave_sync", 18.4 * MS, 1.1 * MS],
            ["session.step", 20.2 * MS, 1 * MS],
            ["session.wave_sync", 20.4 * MS, 0.5 * MS]]
    r = tr.Reduced(ops=[], modules=[], host=host, window=(0, 20 * MS))
    return SimpleNamespace(trace=r, stats=({"prefill_tokens": 100},
                                           {"prefill_tokens": 148}))


def test_span_readers_by_hand():
    ctx = hand_trace()
    # steps with a wave: 6 - 2.4 - 2.2, 3 - 2.2, 3 - 2, 1.8 - 1.1 ms
    assert step_host_ms.read(ctx) == pytest.approx((1.4 + 0.8 + 1.0 + 0.7)
                                                   / 4)
    # gaps between syncs: 6 -> 7.3 and 17.5 -> 18.4 ms; 9.5 -> 15.5 holds
    # the wait for arrivals
    assert wave_stall_ms.read(ctx) == pytest.approx(0.9 + 0.95 * 0.4)
    # one 2.4 ms prefill over 48 scanned tokens
    assert prefill_span_ms_per_tok.read(ctx) == pytest.approx(2.4 / 48)


def test_span_readers_find_nothing_without_the_spans():
    """A trace of a program that has neither the spans nor the counter
    reads nothing, and raises nothing."""
    ctx = hand_trace()
    assert prefill_span_ms_per_tok.read(SimpleNamespace(
        trace=ctx.trace, stats=({}, {}))) is None
    ctx.trace = tr.Reduced(
        ops=[], modules=[], host=[h for h in ctx.trace.host
                                  if not h[0].startswith("session.")
                                  or h[0] == tr.STEP],
        window=ctx.trace.window)
    for reader in (step_host_ms, wave_stall_ms, prefill_span_ms_per_tok):
        assert reader.read(ctx) is None


def test_traced_tiny_run_reads_the_span_metrics(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "OUT", tmp_path)
    spec = tiny.spec("yi6b-chat-short")
    # the device readers need a chip's peaks and device events; on the CPU
    # only the host spans are recorded
    spec.per_layer = [m for m in spec.per_layer if m["name"] in NEW_METRICS]
    assert len(spec.per_layer) == len(NEW_METRICS)
    result = run.run_cell(spec, tiny.SEED, 2.0, True, require_tpu=False)
    assert result["correct"], result["check"]
    for name in NEW_METRICS:
        value = result["metrics"][name]["value"]
        assert isinstance(value, float) and value > 0, name
