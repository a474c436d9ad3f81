"""Sector Predictor (paper §5.3.2) and the stage-1 prediction simulation.

The Sector Predictor (SP) associates the set of words used during a cache
block's L1 residency with the *signature* of the memory instruction that
fetched the block. The Sector History Table (SHT) is indexed by XOR-folding
the instruction address with the word offset of the data address; on a miss
the indexed entry's *previously used sectors* are merged into the request's
sector bits; on eviction the entry is overwritten with the residency's
*currently used sectors*.

``simulate_prediction`` runs the full stage-1 pipeline for one core:
episode stream -> (SHT prediction | LSQ lookahead | triggering word) ->
initial fetch mask, sector-miss schedule, overfetch, writeback masks.
It is a single ``lax.scan`` carrying the SHT. Stage 2 (repro.core.dram)
turns the resulting request schedule into DRAM timing and energy.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lsq
from repro.core.sectors import FULL_MASK, compress_mask, mask_from_offset, popcount8

SHT_DEFAULT_ENTRIES = 512  # paper Table 2: 512-entry Sector Predictor
LA_DEFAULT_WINDOW = 128  # paper Table 2: 128-entry LSQ Lookahead


@dataclasses.dataclass(frozen=True)
class FetchPolicy:
    """What the memory controller fetches per miss — one per evaluated config.

    full_fetch=True reproduces the coarse-grained baseline (and HalfDRAM /
    HalfPage / FGA / PRA-reads, which all still move whole cache blocks).
    """

    name: str
    full_fetch: bool = False  # fetch all 8 words (coarse-grained access)
    la_window: int = 0  # LSQ Lookahead reach in instructions (0 = off)
    sp_entries: int = 0  # SHT entries (0 = SP off)
    chop: bool = False  # burst-chop granularity (half blocks, §8.4)
    fine_writebacks: bool = False  # PRA: write only dirty words

    @property
    def sectored(self) -> bool:
        return not self.full_fetch


BASELINE = FetchPolicy("baseline", full_fetch=True)
BASIC = FetchPolicy("basic")
LA16 = FetchPolicy("LA16", la_window=16)
LA128 = FetchPolicy("LA128", la_window=128)
LA2048 = FetchPolicy("LA2048", la_window=2048)
SP512 = FetchPolicy("SP512", sp_entries=512)
LA128_SP512 = FetchPolicy("LA128-SP512", la_window=LA_DEFAULT_WINDOW,
                          sp_entries=SHT_DEFAULT_ENTRIES)
CHOP_LA128_SP512 = FetchPolicy("chop", la_window=128, sp_entries=512, chop=True)
PRA_POLICY = FetchPolicy("pra", full_fetch=True, fine_writebacks=True)


def sht_index(pc: jax.Array, word_offset: jax.Array, n_entries: int) -> jax.Array:
    """XOR-fold of instruction address and word offset (Fig. 8, item 2)."""
    h = (pc.astype(jnp.uint32) * jnp.uint32(2654435761)) ^ (
        word_offset.astype(jnp.uint32) * jnp.uint32(40503)
    )
    return (h % jnp.uint32(n_entries)).astype(jnp.int32)


@dataclasses.dataclass
class PredictionResult:
    """Stage-1 outputs for one core (numpy arrays, length E or (E,8))."""

    m0: np.ndarray  # initial fetch mask per episode
    n_extra: np.ndarray  # sector-miss requests per episode
    extra_masks: np.ndarray  # (E, 8) fetch mask per sector miss
    extra_dists: np.ndarray  # (E, 8) instruction distance of each sector miss
    writeback_mask: np.ndarray  # words written back at eviction
    overfetch_words: np.ndarray  # fetched-but-unused words per episode
    fetched_words: np.ndarray  # total words moved DRAM->CPU per episode

    @property
    def total_requests(self) -> np.ndarray:
        return 1 + self.n_extra


@functools.partial(jax.jit, static_argnames=("la_window", "sp_entries",
                                             "full_fetch", "chop",
                                             "fine_writebacks"))
def _simulate_core(pc, first_word, used_mask, dist, dirty_mask, *,
                   la_window: int, sp_entries: int, full_fetch: bool,
                   chop: bool, fine_writebacks: bool = False):
    n_entries = max(sp_entries, 1)
    table0 = jnp.zeros((n_entries,), jnp.uint32)

    def step(table, ep):
        e_pc, e_first, e_used, e_dist, e_dirty = ep
        e_used = e_used.astype(jnp.uint32)
        idx = sht_index(e_pc, e_first, n_entries)
        pred = jnp.where(jnp.bool_(sp_entries > 0), table[idx], jnp.uint32(0))
        la = lsq.la_mask(e_dist, la_window)
        first_bit = mask_from_offset(e_first)
        m0 = pred | la | first_bit
        if chop:
            m0 = lsq.round_to_halves(m0)
        if full_fetch:
            m0 = jnp.uint32(FULL_MASK)
        n_extra, masks, dists = lsq.cluster_requests(
            e_used, e_dist, m0, la_window, chop=chop
        )
        fetched = m0 | jax.lax.reduce_or(masks, axes=(0,))
        overfetch = popcount8(fetched & ~e_used)
        # SHT learns the words used during this residency (Fig. 8, item 4).
        table = table.at[idx].set(e_used)
        wb = jnp.where(
            jnp.bool_(full_fetch and not fine_writebacks),
            jnp.uint32(FULL_MASK) * (e_dirty != 0),
            e_dirty.astype(jnp.uint32),
        )
        return table, (m0, n_extra, masks, dists, wb, overfetch,
                       popcount8(fetched))

    _, outs = jax.lax.scan(step, table0,
                           (pc, first_word, used_mask, dist, dirty_mask))
    return outs


def simulate_prediction(trace, policy: FetchPolicy) -> PredictionResult:
    """Run stage 1 for one core's episode trace under ``policy``."""
    m0, n_extra, masks, dists, wb, overfetch, fetched = _simulate_core(
        jnp.asarray(trace.pc),
        jnp.asarray(trace.first_word),
        jnp.asarray(trace.used_mask.astype(np.uint32)),
        jnp.asarray(trace.dist),
        jnp.asarray(trace.dirty_mask.astype(np.uint32)),
        la_window=policy.la_window,
        sp_entries=policy.sp_entries,
        full_fetch=policy.full_fetch,
        chop=policy.chop,
        fine_writebacks=policy.fine_writebacks,
    )
    return PredictionResult(
        m0=np.asarray(m0),
        n_extra=np.asarray(n_extra),
        extra_masks=np.asarray(masks),  # (E, MAX_EXTRA)
        extra_dists=np.asarray(dists),
        writeback_mask=np.asarray(wb),
        overfetch_words=np.asarray(overfetch),
        fetched_words=np.asarray(fetched),
    )
