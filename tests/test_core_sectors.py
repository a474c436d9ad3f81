"""Sector bitmask utilities: exact + property-based tests."""

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import sectors


def test_popcount_exact():
    masks = jnp.arange(256, dtype=jnp.uint32)
    got = np.asarray(sectors.popcount8(masks))
    want = np.array([bin(i).count("1") for i in range(256)])
    np.testing.assert_array_equal(got, want)


def test_mask_roundtrip_pre_encoding():
    """Sector bits survive the PRE-command packing (§4.1: 14 spare bits)."""
    rows = jnp.arange(0, 1024, 37, dtype=jnp.uint32)
    masks = (rows * 41) % 256
    word = sectors.encode_pre(rows, masks)
    r2, m2 = sectors.decode_pre(word)
    np.testing.assert_array_equal(np.asarray(r2), np.asarray(rows))
    np.testing.assert_array_equal(np.asarray(m2), np.asarray(masks))


def test_expand_compress_roundtrip():
    masks = jnp.arange(256, dtype=jnp.uint32)
    again = sectors.compress_mask(sectors.expand_mask(masks))
    np.testing.assert_array_equal(np.asarray(again), np.asarray(masks))


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 255), st.integers(0, 255))
def test_popcount_or_subadditive(a, b):
    pa = int(sectors.popcount8(jnp.uint32(a)))
    pb = int(sectors.popcount8(jnp.uint32(b)))
    por = int(sectors.popcount8(jnp.uint32(a | b)))
    assert max(pa, pb) <= por <= pa + pb


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 7), min_size=1, max_size=8))
def test_burst_length_counts_distinct_offsets(offs):
    mask = 0
    for o in offs:
        mask |= 1 << o
    assert int(sectors.burst_length(jnp.uint32(mask))) == len(set(offs))
