"""Fused multiply-accumulate ``a * b + c`` in IEEE binary32.

Source: ConvPIM (arXiv:2305.04122) sections 4-5.  The fused MAC is the
inner step of MatPIM matrix multiplication and of the convolution and
fully connected layers of the paper's CNNs.  The modelled device is a
48 GiB PIM of 1024-column crossbars: 48 GiB x 8 / 1024 = 402,653,184 rows,
one element per row.

Guarantee: every result equals IEEE-754 binary32 ``a * b`` rounded to
nearest-even, then ``+ c`` rounded to nearest-even, with subnormals kept
(numpy's float32 arithmetic), bit for bit; a NaN matches any NaN.

The reference and the control below use numpy (or any array module with
the same methods) and nothing of the program under test.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

DTYPE = "f32"  # the ``repro.pim`` type the program is traced at

# The configuration as it is run.  ``rows`` is the largest element count
# that a cell of this configuration dispatches at once.
CONFIG = {"program": "a * b + c", "dtype": "float32", "rows": 6_422_528,
          "bases": ["memristive", "dram"]}
SOURCE_CONFIG = {"program": "a * b + c", "dtype": "float32",
                 "rows": 402_653_184, "bases": ["memristive", "dram"]}
REDUCED = {
    "rows": "one chip checks one CNN layer's activations per dispatch: "
            "ResNet-50's stem output at 224x224 (112x112x64) times batch 8",
}
ASSUMED = {
    "batch": "8, the batch of benchmarks/fig6_cnn_infer.py",
}

# Every combination of these fills the head of each input.
SPECIALS = np.array(
    [0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, -1.5, 3.4028235e38,
     1.1754944e-38,  # smallest normal
     1e-45,          # smallest subnormal
     -2.5e-39,       # a subnormal
     5.877472e-39],  # half the smallest normal
    np.float32)


def program(a, b, c):
    return a * b + c


def make_inputs(rng: np.random.Generator, n: int) -> list[np.ndarray]:
    """Random bit patterns (NaN, inf and subnormals among them) on even
    elements, normal values scaled by 2^-150..2^0 on odd ones (subnormal
    and underflowing results), and every combination of ``SPECIALS`` at
    the head."""
    k = len(SPECIALS)
    head = min(n, k ** 3)
    idx = np.arange(head)
    xs = []
    for i in range(3):
        x = rng.integers(0, 2 ** 32, n, dtype=np.uint32).view(np.float32)
        odd = rng.standard_normal(n // 2, dtype=np.float32)
        x[1::2] = np.ldexp(odd, rng.integers(-150, 1, n // 2)).astype(np.float32)
        x[:head] = SPECIALS[(idx // k ** i) % k]
        xs.append(x)
    return xs


def reference(a, b, c):
    """Plain numpy float32: two IEEE roundings, subnormals kept."""
    with np.errstate(all="ignore"):
        return (a * b + c).astype(np.float32)


def control(a, b, c):
    """The reference one precision lower (bfloat16), widened back."""
    lo = ml_dtypes.bfloat16
    with np.errstate(all="ignore"):
        return (a.astype(lo) * b.astype(lo) + c.astype(lo)).astype(np.float32)
