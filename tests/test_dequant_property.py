"""Property-based tests (hypothesis) for the quantized-streaming encoding:
over random block counts, magnitudes, and degenerate planes (zeros,
constant channels, huge dynamic range), the round trip
``dequantize_blocks(quantize_blocks(w))`` stays within half a quantum of
``w`` per output channel, and the int4 nibble packing is loss-free with
respect to its own integer grid."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from repro.kernels import dequantize_blocks, quantize_blocks  # noqa: E402

BLK = 128


@st.composite
def block_planes(draw):
    g = draw(st.integers(min_value=1, max_value=3))
    scale = draw(st.sampled_from((1e-3, 1.0, 64.0, 1e4)))
    seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((g, BLK, BLK)).astype(np.float32) * scale
    if draw(st.booleans()):                # degenerate channels
        w[:, :, draw(st.integers(0, BLK - 1))] = 0.0
    if draw(st.booleans()):
        w[:, :, draw(st.integers(0, BLK - 1))] = scale
    return w


@settings(max_examples=30, deadline=None)
@given(plane=block_planes(),
       precision=st.sampled_from(("int8", "int4")))
def test_roundtrip_within_half_quantum(plane, precision):
    payload, scales = quantize_blocks(jnp.asarray(plane), precision)
    deq = np.asarray(dequantize_blocks(payload, scales, precision))
    # Half a quantum is exact in real arithmetic. In float32 the divide in
    # quantize and the multiply in dequantize each round, which adds a few
    # ulps of |w| (about 1e-3 at |w| ~ 1e4, where no fixed absolute slack
    # holds), so the slack scales with |w|.
    ulps = 4 * np.finfo(np.float32).eps * np.abs(plane)
    bound = 0.5 * np.asarray(scales)[:, None, :] + ulps
    assert (np.abs(plane - deq) <= bound).all()


@settings(max_examples=15, deadline=None)
@given(plane=block_planes())
def test_int4_nibble_packing_is_lossless_on_the_grid(plane):
    payload, scales = quantize_blocks(jnp.asarray(plane), "int4")
    lo = (np.asarray(payload) & 0xF).astype(np.int32) - 8
    hi = ((np.asarray(payload) >> 4) & 0xF).astype(np.int32) - 8
    q = np.clip(np.round(plane / np.asarray(scales)[:, None, :]), -8, 7)
    assert (lo == q[:, 0::2, :]).all() and (hi == q[:, 1::2, :]).all()
