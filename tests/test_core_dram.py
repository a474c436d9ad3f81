"""DRAM timing simulator invariants: exact single-request math, ordering
properties, and hypothesis-random streams."""

import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dram
from repro.core.timing import DEFAULT_TIMING as T


def stream(reqs, tpi=1, n_instr=10_000):
    """Build a 1-core RequestStream from dicts."""
    n = len(reqs)
    d = dict(
        gap_u=np.array([r.get("gap", 160) for r in reqs], np.int32),
        bank=np.array([r.get("bank", 0) for r in reqs], np.int32),
        row=np.array([r.get("row", 0) for r in reqs], np.int32),
        bus_u=np.array([r.get("bus", 40) for r in reqs], np.int32),
        cmd_u=np.array([r.get("cmd", 40) for r in reqs], np.int32),
        lane=np.zeros(n, np.int32),
        col_serial_u=np.zeros(n, np.int32),
        faw_cost=np.array([r.get("faw", 100) for r in reqs], np.int32),
        e_act_nj=np.ones(n, np.float32),
        e_col_nj=np.ones(n, np.float32),
        is_write=np.array([r.get("wr", False) for r in reqs], bool),
        dep=np.array([r.get("dep", False) for r in reqs], bool),
        data_bytes=np.full(n, 64.0),
    )
    return dram.RequestStream(
        **{k: v[None, :] for k, v in d.items()},
        n_req=np.array([n], np.int32),
        tail_u=np.array([0], np.int64),
        n_instructions=np.array([n_instr], np.int64),
    )


def test_single_request_latency_exact():
    """Cold-bank read: ACT + tRCD + tCL + burst + ctrl."""
    res = dram.simulate(stream([dict()]))
    want = T.tRCD + T.tCL + 40 / 16.0 + dram.CTRL_NS
    assert res.read_latency_ns == pytest.approx(want, abs=1.5)


def test_row_hit_faster_than_conflict():
    same_row = dram.simulate(stream([dict(row=0), dict(row=0, gap=10_000)]))
    conflict = dram.simulate(stream([dict(row=0), dict(row=1, gap=10_000)]))
    assert same_row.row_hit_rate == pytest.approx(0.5)
    assert conflict.row_hit_rate == 0.0
    assert same_row.read_latency_ns < conflict.read_latency_ns


def test_conflict_pays_trp_and_tras():
    """Back-to-back conflicts to one bank serialize at ~tRC."""
    reqs = [dict(row=i, gap=1) for i in range(8)]
    res = dram.simulate(stream(reqs))
    # last completion >= 7 * tRC
    assert res.total_ps / 1000.0 >= 7 * T.tRC


def test_vbl_shorter_bursts_reduce_bus_pressure():
    """Saturating one lane: 1-beat bursts finish ~8x sooner than 8-beat."""
    n = 64
    full = dram.simulate(stream(
        [dict(row=0, bus=80, gap=1, bank=0) for _ in range(n)]))
    short = dram.simulate(stream(
        [dict(row=0, bus=10, gap=1, bank=0) for _ in range(n)]))
    assert short.total_ps < full.total_ps
    assert short.read_latency_ns < full.read_latency_ns


def test_faw_reservation_limits_act_rate():
    """>4 cheap-gap ACTs to one rank within tFAW stall; sectored costs
    (act_array_fraction) relax the same stream."""
    reqs = [dict(bank=i % 16, row=5, gap=1, faw=100) for i in range(16)]
    full_cost = dram.simulate(stream(reqs))
    cheap = [dict(bank=i % 16, row=5, gap=1, faw=34) for i in range(16)]
    relaxed = dram.simulate(stream(cheap))
    assert full_cost.faw_stall_frac > relaxed.faw_stall_frac
    assert full_cost.total_ps >= relaxed.total_ps


def test_dep_serializes():
    indep = dram.simulate(stream([dict(bank=i, gap=1) for i in range(8)]))
    dep = dram.simulate(stream([dict(bank=i, gap=1, dep=True)
                                for i in range(8)]))
    assert dep.total_ps > indep.total_ps


def test_writes_do_not_block_core():
    """A slow write burst must not delay subsequent loads' issue. The read
    targets a different *rank* (bank 16) so only core-side coupling could
    delay it — and must not."""
    reqs = [dict(wr=True, bank=0, row=i, gap=1) for i in range(12)]
    reqs += [dict(bank=16, row=0, gap=1)]
    res = dram.simulate(stream(reqs))
    # cold-bank read latency (~70ns) + slack; the ~600ns write backlog on
    # rank 0 must not appear here
    assert res.read_latency_ns < 120


@settings(max_examples=20, deadline=None)
@given(st.lists(
    st.tuples(st.integers(0, 63), st.integers(0, 50), st.booleans(),
              st.integers(1, 8)),
    min_size=1, max_size=40))
def test_random_streams_invariants(rs):
    reqs = [dict(bank=b, row=r, wr=w, bus=10 * beats, gap=50)
            for (b, r, w, beats) in rs]
    res = dram.simulate(stream(reqs))
    assert res.total_ps > 0
    assert res.dram_energy_nj > 0
    assert 0.0 <= res.row_hit_rate <= 1.0
    assert res.n_acts + int(res.row_hit_rate * res.n_requests) <= res.n_requests + 1
    assert np.isfinite(res.ipc).all()
