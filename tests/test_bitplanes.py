"""The bit-plane layout (``repro.core.bitplanes``): ``pack`` and ``unpack``
bit for bit against the numpy oracle, for every element type at sizes on
both sides of a word boundary, on values that a float or integer cast would
alter (NaN payloads, -0.0, infinities, subnormals, the extreme integers)."""

import ml_dtypes
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import bitplanes
from repro.core.bitplanes import BF16, F32, fixed

_F32_SPECIAL = [0x7FC00001, 0x7F800001, 0xFFBFFFFF, 0x7F800000, 0xFF800000,
                0x80000000, 0x00000000, 0x00000001, 0x807FFFFF, 0x00400000,
                0x3F800000, 0x7F7FFFFF]
_BF16_SPECIAL = [0x7FC1, 0x7F81, 0xFFBF, 0x7F80, 0xFF80, 0x8000, 0x0000,
                 0x0001, 0x807F, 0x0040, 0x3F80, 0x7F7F]


def _values(t, n, rng):
    """``n`` values of ``t``'s carrier dtype, specials first, then random
    bit patterns; returns (device array, uint32 bit patterns)."""
    if t.kind == "fixed":
        lo, hi = -(2 ** (t.nbits - 1)), 2 ** (t.nbits - 1) - 1
        special = np.array([lo, hi, -1, 0, 1, lo + 1, hi - 1], np.int64)
        rand = rng.integers(lo, hi + 1, n, dtype=np.int64)
        x = np.concatenate([special, rand])[:n].astype(np.int32)
        return jnp.asarray(x), x.view(np.uint32)
    if t.kind == "bf16":
        special = np.array(_BF16_SPECIAL, np.uint16)
        rand = rng.integers(0, 2 ** 16, n, dtype=np.uint64).astype(np.uint16)
        bits = np.concatenate([special, rand])[:n]
        return jnp.asarray(bits.view(ml_dtypes.bfloat16)), bits.astype(np.uint32)
    special = np.array(_F32_SPECIAL, np.uint32)
    rand = rng.integers(0, 2 ** 32, n, dtype=np.uint64).astype(np.uint32)
    bits = np.concatenate([special, rand])[:n]
    return jnp.asarray(bits.view(np.float32)), bits


@pytest.mark.parametrize("n", [1, 31, 32, 33, 4101])
@pytest.mark.parametrize("t", [F32, BF16, fixed(8), fixed(16), fixed(32)],
                         ids=lambda t: t.name)
def test_pack_unpack_bit_exact(t, n):
    x, bits = _values(t, n, np.random.default_rng(n))
    planes = np.asarray(bitplanes.pack((t,), x))
    expected = np.stack([
        bitplanes.np_pack_reference(((bits >> j) & 1).astype(np.uint8))
        for j in range(t.width)])
    assert planes.dtype == np.uint32
    assert np.array_equal(planes, expected)

    [back] = bitplanes.unpack((t,), jnp.asarray(planes), n)
    back = np.asarray(back)
    assert back.dtype == np.asarray(x).dtype and back.shape == (n,)
    view = {"float32": np.uint32, "bf16": np.uint16, "fixed": np.int32}[t.kind]
    assert np.array_equal(back.view(view), np.asarray(x).view(view))
