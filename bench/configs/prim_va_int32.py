"""PrIM VA: vector addition ``a + b`` of int32 with two's-complement wrap.

Source: PrIM (Gomez-Luna et al., arXiv:2105.03814), benchmark VA, int32
elements.  The modelled device is ConvPIM's 48 GiB PIM of 1024-column
crossbars: 402,653,184 rows, one element per row.

Guarantee: every result equals the low 32 bits of the exact sum, read as
a signed int32, bit for bit.

The reference and the control below use numpy (or any array module with
the same methods) and nothing of the program under test.
"""

from __future__ import annotations

import numpy as np

DTYPE = "int32"

CONFIG = {"program": "a + b", "dtype": "int32", "rows": 16_777_216,
          "bases": ["memristive", "dram"]}
SOURCE_CONFIG = {"program": "a + b", "dtype": "int32", "rows": 402_653_184,
                 "bases": ["memristive", "dram"]}
REDUCED = {
    "rows": "2^24 elements per dispatch, cut from the modelled device's "
            "rows so that a window of run_seconds holds hundreds of "
            "dispatches",
}
ASSUMED = {
    "rows": "PrIM sizes VA per DPU; the device modelled here is ConvPIM's "
            "48 GiB PIM, one element per row",
}

INT32_MIN, INT32_MAX = -2 ** 31, 2 ** 31 - 1
SPECIALS = np.array([0, 1, -1, 2, INT32_MAX, INT32_MIN, INT32_MAX - 1,
                     INT32_MIN + 1], np.int32)


def program(a, b):
    return a + b


def make_inputs(rng: np.random.Generator, n: int) -> list[np.ndarray]:
    """Uniform int32 bit patterns (so about half the sums wrap), with every
    pair of ``SPECIALS`` at the head."""
    k = len(SPECIALS)
    head = min(n, k ** 2)
    idx = np.arange(head)
    xs = []
    for i in range(2):
        x = rng.integers(0, 2 ** 32, n, dtype=np.uint32).view(np.int32)
        x[:head] = SPECIALS[(idx // k ** i) % k]
        xs.append(x)
    return xs


def reference(a, b):
    """Exact sum in int64, wrapped to int32."""
    wide = a.astype(np.int64) + b.astype(np.int64)
    return ((wide + 2 ** 31) % 2 ** 32 - 2 ** 31).astype(np.int32)


def control(a, b):
    """The sum in int16 (the low half of each operand), widened back."""
    return (a.astype(np.int16) + b.astype(np.int16)).astype(np.int32)
