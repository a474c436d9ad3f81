"""The trace reduction and the readers that use it, on a small trace
written by hand (numbers checked by hand) and on a small excerpt of a trace
recorded on a TPU v5e (``data/trace_excerpt.json``)."""

import json
import pathlib
from types import SimpleNamespace

import pytest

from bench import trace as tr
from bench.metrics import (attn_kernel_ms_per_wave, attn_kernel_roofline,
                           decode_wave_ms, device_idle_share,
                           prefill_ms_per_tok, step_mfu)

DATA = pathlib.Path(__file__).resolve().parent / "data"
YI = dict(n_layers=8, d_model=4096, n_heads=32, n_kv_heads=4, head_dim=128,
          d_ff=11008, vocab=64000)
MS = 1e6  # nanoseconds


def hand_trace():
    """10 ms window: a 1 ms prefill inside step 0, two 3 ms waves each
    holding 1 ms of the kernel (two calls), idle otherwise."""
    ops = [["fusion.1 fusion", 0.5 * MS, 1 * MS],
           ["closed_call.1 custom-call", 2 * MS, 0.5 * MS],
           ["closed_call.1 custom-call", 2.5 * MS, 0.5 * MS],
           ["fusion.2 fusion", 3 * MS, 2 * MS],
           ["closed_call.1 custom-call", 6 * MS, 1 * MS],
           ["fusion.2 fusion", 7 * MS, 2 * MS]]
    modules = [["jit__scan_prompt(1)", 0.5 * MS, 1 * MS],
               ["jit_fused(2)", 2 * MS, 3 * MS],
               ["jit_fused(2)", 6 * MS, 3 * MS]]
    host = [["bench.window", 0.0, 10 * MS],
            ["session.step", 0.2 * MS, 5 * MS],
            ["session.step", 5.5 * MS, 4 * MS],
            ["poll", 5.2 * MS, 0.3 * MS]]
    r = tr.Reduced(ops=ops, modules=modules, host=host, window=(0, 10 * MS))
    steps = [dict(prefilled=50, flops=2e9, decoded=1),
             dict(prefilled=0, flops=3e9, decoded=2)]
    return SimpleNamespace(trace=r, trace_steps=steps, sizes=YI,
                           max_batch=16, selected=10, kind="TPU v5 lite")


def test_busy_idle_and_gaps_by_hand():
    ctx = hand_trace()
    assert tr.busy_ns(ctx.trace) == 7 * MS
    assert device_idle_share.read(ctx) == pytest.approx(30.0)
    gaps = tr.idle_gaps(ctx.trace)
    # idle 0-0.5, 1.5-2, 5-6 and 9-10 ms; 5-6 ms is named by the innermost
    # span over its middle (poll, 5.2-5.5 ms, inside no step but touching)
    assert gaps == [["poll", pytest.approx(0.001)],
                    ["session.step", pytest.approx(0.001)],
                    ["session.step", pytest.approx(0.0005)],
                    ["session.step", pytest.approx(0.0005)]]


def test_program_and_kernel_readers_by_hand():
    ctx = hand_trace()
    assert decode_wave_ms.read(ctx) == pytest.approx(3.0)
    assert attn_kernel_ms_per_wave.read(ctx) == pytest.approx(1.0)
    assert prefill_ms_per_tok.read(ctx) == pytest.approx(1.0 / 50)
    # 2 waves x 8 layers x 42,338,816 bytes at 819 GB/s over 2 ms
    assert attn_kernel_roofline.read(ctx) == pytest.approx(
        100 * 16 * 42_338_816 / 819e9 / 2e-3)
    # 5e9 flops over the steps' span, 0.2 .. 9.5 ms
    assert step_mfu.read(ctx) == pytest.approx(
        100 * 5e9 / 9.3e-3 / 197e12)


def test_prefill_found_across_the_clock_offset():
    """A prefill program recorded 0.7 ms before its step's host span (as a
    v5e trace showed) is that step's; a step whose prefill the trace lost
    counts no tokens."""
    ctx = hand_trace()
    ctx.trace.modules[0] = ["jit__lambda(1)", -0.5 * MS, 1 * MS]
    ctx.trace_steps[1]["prefilled"] = 30
    assert prefill_ms_per_tok.read(ctx) == pytest.approx(1.0 / 50)
    assert tr.owners([["x", 5.1 * MS, 0.8 * MS]],
                     [(0.2 * MS, 5.2 * MS), (5.5 * MS, 9.5 * MS)]) == [1]


def test_short_names_and_top_ops():
    name = ("%closed_call.11 = (f32[1,4,8,128]{3,2,1,0:T(8,128)S(1)}, "
            "f32[1,4,1,5]{3,2,1,0}) custom-call(s32[1,4,5]{2,1,0} %x)")
    assert tr.short_name(name) == "closed_call.11 custom-call"
    assert tr.short_name("%copy.4 = f32[8]{0} copy(f32[8]{0} %a)") == \
        "copy.4 copy"
    assert tr.short_name("jit_fused(1)") == "jit_fused(1)"
    r = hand_trace().trace
    r.ops.append(["while.1 while", 0.0, 9 * MS])  # a loop is no op of its own
    assert tr.top_ops(r) == [["fusion.2 fusion", pytest.approx(0.004)],
                             ["closed_call.1 custom-call",
                              pytest.approx(0.002)],
                             ["fusion.1 fusion", pytest.approx(0.001)]]


def test_readers_find_nothing_in_an_empty_window():
    ctx = hand_trace()
    ctx.trace = tr.Reduced(ops=[], modules=[],
                           host=[["bench.window", 0.0, MS]], window=(0, MS))
    ctx.trace_steps = []
    for reader in (decode_wave_ms, attn_kernel_ms_per_wave,
                   attn_kernel_roofline, prefill_ms_per_tok, step_mfu):
        assert reader.read(ctx) is None
    assert device_idle_share.read(ctx) == pytest.approx(100.0)


@pytest.mark.skipif(not (DATA / "trace_excerpt.json").exists(),
                    reason="no recorded excerpt")
def test_recorded_excerpt():
    d = json.loads((DATA / "trace_excerpt.json").read_text())
    ctx = SimpleNamespace(trace=tr.Reduced.from_json(d["reduced"]),
                          trace_steps=d["steps"], **d["ctx"])
    for key, expected in d["expected"].items():
        reader = __import__(f"bench.metrics.{key}", fromlist=["read"])
        assert reader.read(ctx) == pytest.approx(expected, rel=1e-9), key
    assert 0 < tr.busy_ns(ctx.trace) / 1e9 <= ctx.trace.window_s
    kernel = decode_wave_ms.read(ctx) - attn_kernel_ms_per_wave.read(ctx)
    assert kernel > 0  # the kernel is a part of the wave
