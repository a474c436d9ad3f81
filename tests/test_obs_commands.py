"""Command-timeline properties: modeled service time orderings, the
double-entry audit round-trip, and the replay's boundary behaviour.

The modeled ``dram_ns`` numbers back CI gates (BENCH_latency.json), so
their *shape* is pinned property-style: service time must be monotone in
sectors activated and words fetched, sectored <= static <= dense on
identical access patterns, zero-beat masked transfers must cost column
command slots only, and the command ledger must reconcile with the
meter's books for arbitrary wave shapes — shared prefix groups included.
"""

import json

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import power
from repro.core.timing import DEFAULT_TIMING as T
from repro.obs import audit
from repro.obs import commands as dc
from repro.obs.export import command_trace_events
from repro.obs.metrics import Histogram
from repro.telemetry import KVGeometry, WaveMeter

GEO = KVGeometry(page_size=128, total_pages=6, page_kv_bytes=2048.0,
                 n_layers=2)
GEO_Q8 = KVGeometry(page_size=128, total_pages=6, page_kv_bytes=2048.0,
                    n_layers=2, kv_word_fraction=0.5)


def wave_ns(geometry, *, sectored, k_pages, positions, sectored_hw=True,
            shared_groups=None):
    """Modeled makespan of one wave over the given slot positions."""
    slots = [(i, 100 + i, p) for i, p in enumerate(positions)]
    return dc.replay(dc.wave_commands(
        geometry, sectored=sectored, k_pages=k_pages, slots=slots,
        shared_groups=shared_groups, sectored_hw=sectored_hw)).dram_ns


# -- monotonicity and the dense/static/sectored ordering ---------------------

@settings(deadline=None)
@given(st.integers(min_value=128, max_value=767),
       st.integers(min_value=1, max_value=5))
def test_service_time_monotone_in_fetch_width(position, k):
    """Fetching one more page never models a *shorter* wave: both the
    tFAW token draw (more sector-ACTs) and the bus occupancy (more
    bursts) are non-decreasing in the page budget."""
    narrow = wave_ns(GEO, sectored=True, k_pages=k, positions=[position])
    wide = wave_ns(GEO, sectored=True, k_pages=k + 1, positions=[position])
    assert narrow <= wide


@settings(deadline=None)
@given(st.integers(min_value=128, max_value=767),
       st.integers(min_value=1, max_value=5))
def test_service_time_monotone_in_word_width(position, k):
    """Narrower words (int8 KV: kv_word_fraction=0.5) shorten every RD
    burst, so the modeled time never rises — and strictly falls whenever
    the data bus is the binding phase."""
    full = wave_ns(GEO, sectored=True, k_pages=k, positions=[position])
    half = wave_ns(GEO_Q8, sectored=True, k_pages=k, positions=[position])
    assert half <= full


@settings(deadline=None)
@given(st.lists(st.integers(min_value=128, max_value=767),
                min_size=1, max_size=4),
       st.integers(min_value=1, max_value=4))
def test_sectored_leq_static_leq_dense(positions, k):
    """On one identical access pattern: a narrow sectored fetch models
    at most the full-provision sectored time, which models at most the
    coarse-grained baseline's (full-row ACTs at full tFAW cost, every
    valid page on the bus). The paper's energy ordering, as time."""
    sectored = wave_ns(GEO, sectored=True, k_pages=k, positions=positions)
    static = wave_ns(GEO, sectored=True, k_pages=GEO.total_pages,
                     positions=positions)
    dense = wave_ns(GEO, sectored=False, k_pages=None, positions=positions,
                    sectored_hw=False)
    assert sectored <= static <= dense
    # with the width genuinely binding, the inequality is strict
    if k < min(p // GEO.page_size + 1 for p in positions):
        assert sectored < dense


def test_sectored_strictly_faster_when_width_binds():
    """One slot deep in its sequence: k=1 of 5 valid pages."""
    narrow = wave_ns(GEO, sectored=True, k_pages=1, positions=[640])
    dense = wave_ns(GEO, sectored=False, k_pages=None, positions=[640],
                    sectored_hw=False)
    assert narrow < dense


# -- replay boundary behaviour ----------------------------------------------

def test_zero_beat_transfer_costs_column_slots_only():
    """A fully-masked VBL transfer still issues its RD — one column
    command slot (tCK) each, no data beats, no row overhead."""
    n = 7
    tl = dc.replay([dc.DramCommand("RD", 0, 0, count=float(n), beats=0.0)])
    assert tl.dram_ns == pytest.approx(n * T.tCK)
    assert tl.lead_ns == tl.tail_ns == tl.act_ns == 0.0


def test_empty_stream_costs_nothing():
    tl = dc.replay([])
    assert tl.dram_ns == 0.0 and tl.energy_j == 0.0


def test_act_free_stream_has_no_row_overhead():
    """Pure appends (WR only) cost bus time, never tRCD/tCL/tRP."""
    tl = dc.replay([dc.DramCommand("WR", 0, 0, count=4.0, beats=8.0)])
    assert tl.lead_ns == tl.tail_ns == 0.0
    assert tl.dram_ns == pytest.approx(4.0 * dc.column_slot_ns(8.0))


def test_makespan_is_lead_plus_binding_phase_plus_tail():
    tl = dc.replay(dc.wave_commands(GEO, sectored=True, k_pages=3,
                                    slots=[(0, 0, 640)]))
    assert tl.n_acts > 0
    assert tl.lead_ns == T.tRCD + T.tCL and tl.tail_ns == T.tRP
    assert tl.dram_ns == pytest.approx(
        tl.lead_ns + max(tl.act_ns, tl.bus_ns) + tl.tail_ns)


@settings(deadline=None)
@given(st.integers(min_value=1, max_value=32))
def test_act_issue_span_fluid_token_bucket(n_acts):
    """The closed form: token deficit over the refill rate, floored by
    the tRRD ACT-to-ACT gaps; within the burst allowance only the gaps
    remain."""
    tokens = float(n_acts)  # full-cost ACTs
    span = dc.act_issue_span_ns(float(n_acts), tokens)
    deficit = max(tokens - T.faw_burst_acts, 0.0)
    rate = T.faw_acts / T.tFAW
    assert span == pytest.approx(max(deficit / rate, (n_acts - 1) * T.tRRD))


def test_warm_prefill_shorter_than_cold():
    """A prefix-cache hit shortens the modeled prefill timeline: the
    suffix-scaled read pass and the suffix-only appends both shrink."""
    cold = dc.replay(dc.prefill_commands(GEO, prompt_len=520))
    warm = dc.replay(dc.prefill_commands(GEO, prompt_len=520,
                                         cached_tokens=384))
    assert 0.0 < warm.dram_ns < cold.dram_ns
    assert warm.energy_j < cold.energy_j


# -- the double-entry audit round-trip ---------------------------------------

@settings(deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=767),
                min_size=1, max_size=4),
       st.integers(min_value=1, max_value=6),
       st.booleans(), st.booleans(), st.booleans())
def test_audit_round_trip_random_waves(positions, k, sectored, hw,
                                       background):
    """The meter audits every wave itself (AuditError on divergence);
    random wave shapes across sectored x hardware x background must all
    reconcile, and the command ledger's total must equal the meter's
    wave joules exactly."""
    meter = WaveMeter(GEO, sectored_hw=hw, background=background)
    slots = [(i, i, p) for i, p in enumerate(positions)]
    meter.record_wave(sectored=sectored, k_pages=k, slots=slots)
    tl = meter.last_timeline
    assert tl is not None and meter.totals["audit_checks"] == 1
    assert meter.totals["audit_max_rel_err"] <= audit.AUDIT_REL_TOL
    fetch_and_append = (meter.totals["act_j"] + meter.totals["rd_j"]
                        + meter.totals["wr_j"])
    assert audit.rel_err(tl.act_j + tl.rd_j + tl.wr_j,
                         fetch_and_append) <= audit.AUDIT_REL_TOL
    assert meter.totals["dram_ns"] == tl.dram_ns


@settings(deadline=None)
@given(st.integers(min_value=2, max_value=4),
       st.integers(min_value=1, max_value=3),
       st.integers(min_value=256, max_value=767))
def test_audit_round_trip_shared_groups(n_readers, shared_pages, position):
    """Prefix-cache co-readers scale ACT/RD by the proportional keep
    factor on BOTH sides of the books — the audit holds under sharing."""
    slots = [(i, i, position) for i in range(n_readers)]
    groups = [dict(slots=[s for s, _, _ in slots],
                   shared_tokens=shared_pages * GEO.page_size)]
    meter = WaveMeter(GEO)
    meter.record_wave(sectored=True, k_pages=4, slots=slots,
                      shared_groups=groups)
    assert meter.totals["audit_checks"] == 1
    assert meter.totals["audit_max_rel_err"] <= audit.AUDIT_REL_TOL
    # sharing must strictly reduce the fetch joules vs the unshared twin
    solo = WaveMeter(GEO)
    solo.record_wave(sectored=True, k_pages=4, slots=slots)
    assert (meter.totals["act_j"] + meter.totals["rd_j"]
            < solo.totals["act_j"] + solo.totals["rd_j"])
    # the amortized fetch issues fewer effective commands, so the modeled
    # wave is never slower than its unshared twin
    assert meter.totals["dram_ns"] <= solo.totals["dram_ns"]
    assert meter.totals["dram_ns"] == meter.last_timeline.dram_ns


def test_audit_reconcile_raises_on_divergence():
    with pytest.raises(audit.AuditError):
        audit.reconcile(dict(act_j=1.0), dict(act_j=1.0 + 1e-6),
                        where="unit")
    with pytest.raises(audit.AuditError):
        audit.reconcile(dict(act_j=1.0), dict(rd_j=1.0), where="one-sided")


def test_prefill_audit_and_timeline_recorded():
    meter = WaveMeter(GEO, background=True)
    meter.record_prefill(3, 520)
    tl = meter.prefill_timelines[3]
    assert tl.dram_ns > 0 and meter.totals["audit_checks"] == 1
    assert meter.totals["prefill_dram_ns"] == tl.dram_ns
    # background mode appends the REF entry onto the prefill timeline
    assert any(c.kind == "REF" for c in tl.commands)


# -- replay_by_slot / background split ---------------------------------------

def test_replay_by_slot_partitions_the_stream():
    cmds = dc.wave_commands(GEO, sectored=True, k_pages=3,
                            slots=[(0, 10, 300), (1, 11, 640)])
    per_slot = dc.replay_by_slot(cmds)
    assert set(per_slot) == {0, 1}
    whole = dc.replay(cmds)
    assert sum(t.energy_j for t in per_slot.values()) == \
        pytest.approx(whole.energy_j, rel=1e-12)
    # each slot alone finishes no later than the combined wave
    assert all(t.dram_ns <= whole.dram_ns for t in per_slot.values())


# -- histogram quantiles (the dram_ns summaries ride on these) ---------------

def test_histogram_quantile_interpolates_and_clamps():
    h = Histogram("ns", buckets=(10.0, 100.0, 1000.0))
    for v in (5.0, 50.0, 60.0, 70.0, 900.0):
        h.observe(v)
    p50, from_overflow = h.quantile(0.5)
    assert not from_overflow and 10.0 <= p50 <= 100.0
    snap = h.snapshot()
    assert snap["p50"] == pytest.approx(p50)
    assert "overflow" not in snap
    assert snap["p99"] <= h.max


def test_histogram_overflow_is_loud():
    h = Histogram("ns", buckets=(10.0, 100.0))
    h.observe(5.0)
    h.observe(1e6)  # beyond the top bucket
    snap = h.snapshot()
    assert snap["overflow"] == 1
    p99, from_overflow = h.quantile(0.99)
    # the estimate comes from the +inf bucket: flagged, and only bounded
    # by the tracked max
    assert from_overflow and 100.0 < p99 <= h.max


# -- export determinism ------------------------------------------------------

def test_command_trace_events_deterministic():
    meter = WaveMeter(GEO, background=True)
    meter.record_wave(sectored=True, k_pages=3,
                      slots=[(0, 0, 300), (1, 1, 640)])
    rec = meter.last_timeline.to_record(step=4, kind="wave", seq=0)
    runs = [json.dumps(command_trace_events([rec]), sort_keys=True)
            for _ in range(2)]
    assert runs[0] == runs[1]
    events = command_trace_events([rec])
    names = {e.get("name") for e in events}
    assert {"dram", "act issue", "data bus", "dram_ns"} <= names
