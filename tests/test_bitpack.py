"""Bitstream + lane-tiled packing: exact layout properties (hypothesis)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import bitpack as B

WIDTHS = [4, 8, 12, 16, 20, 24, 28, 32]


@settings(max_examples=60, deadline=None)
@given(
    st.sampled_from(WIDTHS),
    st.integers(1, 300),
    st.integers(0, 2**32 - 1),
)
def test_stream_roundtrip(width, n, seed):
    rng = np.random.default_rng(seed)
    mask = (1 << width) - 1 if width < 32 else 0xFFFFFFFF
    codes = (rng.integers(0, 2**32, n, dtype=np.uint32) & np.uint32(mask))
    packed = B.pack_stream(jnp.asarray(codes), width)
    assert packed.shape[0] == B.packed_words(n, width)
    out = np.asarray(B.unpack_stream(packed, width, n))
    assert (out == codes).all()


@settings(max_examples=40, deadline=None)
@given(st.sampled_from(WIDTHS), st.integers(1, 8), st.integers(0, 2**31))
def test_group_layout_equals_stream_layout(width, rows, seed):
    """The lane-tiled layout is the dense stream layout, one stream per
    lane: reading a tile's words lane-major gives exactly the dense
    stream of that tile's codes read lane-major — no padding inside a
    tile, zero metadata. Lengths span one-tile axes and multi-tile
    (128-lane) axes; the in-kernel chunk unpack agrees with the XLA
    unpack on every length."""
    rng = np.random.default_rng(seed)
    g, wg = B.lane_group(width)
    n = 32 * int(rng.integers(1, 8)) * (1 if rng.integers(2) else 4 * g)
    mask = (1 << width) - 1 if width < 32 else 0xFFFFFFFF
    codes = (rng.integers(0, 2**32, (rows, n), dtype=np.uint32)
             & np.uint32(mask))
    grouped = np.asarray(B.pack_groups(jnp.asarray(codes), width))
    lanes = B.tile_lanes(n, width)
    tiles = grouped.shape[-1] // (wg * lanes)
    assert tiles * g * lanes == n
    for r in range(rows):
        c = codes[r].reshape(tiles, g, lanes).transpose(0, 2, 1)
        w = grouped[r].reshape(tiles, wg, lanes).transpose(0, 2, 1)
        stream = np.asarray(B.pack_stream(jnp.asarray(c.reshape(-1)),
                                          width))
        assert (w.reshape(-1) == stream).all()
    out = np.asarray(B.unpack_groups(jnp.asarray(grouped), width, n))
    assert (out == codes).all()
    blk = np.asarray(B.unpack_block(jnp.asarray(grouped), width, lanes))
    assert (blk == codes).all()


@pytest.mark.parametrize("width", WIDTHS)
def test_group_padding(width):
    """Non-multiple-of-32 lengths pad with zeros and round-trip."""
    n = 40
    rng = np.random.default_rng(width)
    mask = (1 << width) - 1 if width < 32 else 0xFFFFFFFF
    codes = rng.integers(0, 2**32, n, dtype=np.uint32) & np.uint32(mask)
    packed = B.pack_groups(jnp.asarray(codes), width)
    assert packed.shape[-1] == B.packed_group_words(n, width)
    out = np.asarray(B.unpack_groups(packed, width, n))
    assert (out == codes).all()


def test_density():
    """Packed size is exactly n*width/32 words — zero metadata overhead,
    matching the paper's slice-packing density claim."""
    for width in WIDTHS:
        n = 320
        assert B.packed_words(n, width) == n * width // 32


def test_width_validation():
    with pytest.raises(ValueError):
        B.pack_stream(jnp.zeros(4, jnp.uint32), 5)
    with pytest.raises(ValueError):
        B.packed_words(10, 0)
