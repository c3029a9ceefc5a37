"""One 3x3 convolution of ResNet-50, run as the contraction ``a @ b`` in
IEEE binary32.

Source: He et al., "Deep Residual Learning for Image Recognition"
(arXiv:1512.03385), Table 1, stage conv3_x, layer [3x3, 128]: input
28x28x128, 128 filters of 3x3x128, stride 1, SAME zero padding, no bias.
ResNet-50 is one of the three CNNs of ConvPIM (arXiv:2305.04122) section
5, which runs conv layers as MatPIM matrix products: serial rank-1 updates
``C += A[:, k] (x) B[k, :]``, each a fused MAC over every output row, with
the accumulator kept in the crossbar.

As an im2col GEMM, with ``k = (kh, kw, cin)`` (HWIO weights): ``A [784,
1152]`` (output pixels by patch), ``B [1152, 128]``, ``C [784, 128]``:
100,352 outputs and 115,605,504 MACs per image.  Every 3x3 conv of
ResNet-50 has this MAC count; conv2_x, conv4_x and conv5_x differ only in
their M/K/N split.

Guarantee: ``acc = +0.0``, then for ``k = 0 .. K-1`` in order
``acc = fl(fl(A[m, k] * B[k, n]) + acc)``, each product and each sum an
IEEE-754 binary32 operation rounded to nearest-even with subnormals kept
(numpy's float32 arithmetic, which has no fused multiply-add), bit for bit;
a NaN matches any NaN.  A reordered or tree-shaped sum is a different
result.

Departures from the published layer: no batch norm and no ReLU after the
conv.  ReLU needs compare/select, which the PIM frontend does not trace
yet; at inference batch norm is a per-channel affine map that folds into
the weights.

The reference and the control below use numpy (or any array module with
the same methods) and nothing of the program under test.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

DTYPE = "f32"  # the ``repro.pim`` type the program is traced at

H = W = 28
CIN = COUT = 128
KSIZE = 3
M, K, N = H * W, KSIZE * KSIZE * CIN, COUT

# The configuration as it is run.  ``M``, ``K`` and ``N`` are the GEMM's
# sizes; a cell dispatches ``M' x N`` outputs with ``M' <= M``.
CONFIG = {"program": "a @ b", "dtype": "float32", "height": H, "width": W,
          "in_channels": CIN, "out_channels": COUT, "kernel": KSIZE,
          "stride": 1, "padding": "SAME", "M": M, "K": K, "N": N,
          "batch": 1, "layers": 1}
SOURCE_CONFIG = dict(CONFIG, batch=8, layers=53)
REDUCED = {
    "batch": "8 (fig6's batch) cut to 1 by the run's time limit: at the "
             "loop kernel's speed one image is seconds per dispatch",
    "layers": "one of ResNet-50's 53 convolutions; every 3x3 conv has the "
              "same 115.6M MACs",
}
ASSUMED = {
    "activations": "ReLU of a standard normal (about half exact zeros)",
    "weights": "He-normal, std sqrt(2 / 1152)",
}


def program(a, b):
    return a @ b


def im2col(x: np.ndarray) -> np.ndarray:
    """``x [H, W, CIN]`` → ``[H * W, 9 * CIN]`` 3x3 patches, SAME zero
    padding, columns in (kh, kw, cin) order."""
    p = np.pad(x, ((1, 1), (1, 1), (0, 0)))
    cols = [p[i:i + H, j:j + W] for i in range(KSIZE) for j in range(KSIZE)]
    return np.concatenate(cols, axis=-1).reshape(H * W, K)


def make_inputs(rng: np.random.Generator, n: int) -> list[np.ndarray]:
    """``[A, B]`` of ``n`` outputs (the first ``n / N`` rows of the layer's
    A), with specials planted in the first 4 rows of A and the first 4
    columns of B: NaN, infinities, -0.0, subnormals and values near the
    f32 maximum, so that a few outputs are NaN, overflow or underflow."""
    if n % N or not 0 < n <= M * N:
        raise ValueError(f"n must be a multiple of {N} up to {M * N}")
    x = np.maximum(rng.standard_normal((H, W, CIN), dtype=np.float32), 0)
    w = rng.standard_normal((KSIZE, KSIZE, CIN, COUT), dtype=np.float32)
    w *= np.float32(np.sqrt(2 / K))
    a = im2col(x)[:n // N].copy()
    b = w.reshape(K, N)
    a[0, 5] = np.nan                        # row 0: every output NaN
    a[1, 7], a[1, 300] = np.inf, -np.inf    # row 1: infinities meet
    a[2] *= np.float32(2.0 ** -130)         # row 2: subnormal activations
    a[3, ::97] = np.float32(3.0e38)         # row 3: near the f32 maximum
    a[:4, 11] = -0.0
    b[::2, 0] = -0.0                        # col 0: signed zeros
    b[:, 1] *= np.float32(2.0 ** -126)      # col 1: subnormal weights
    b[::50, 2] = np.float32(3.0e38)         # col 2: overflow to inf
    b[3, 3] = np.inf                        # col 3: inf, NaN where a = 0
    return [a, b]


def _sequential(a, b, dtype):
    acc = np.zeros((a.shape[0], b.shape[1]), dtype)
    with np.errstate(all="ignore"):
        for k in range(a.shape[1]):
            acc = a[:, k, None] * b[None, k, :] + acc
    return acc


def reference(a, b):
    """Plain numpy float32, k in order: two IEEE roundings per step."""
    return _sequential(a, b, np.float32).astype(np.float32)


def control(a, b):
    """The reference one precision lower (bfloat16), widened back."""
    lo = ml_dtypes.bfloat16
    return _sequential(a.astype(lo), b.astype(lo), lo).astype(np.float32)
