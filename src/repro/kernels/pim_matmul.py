"""Pallas TPU kernel: MatPIM-style blocked matmul (paper §4, ref [9]).

MatPIM expresses matrix multiplication as a serial sequence of vectored
(row-parallel) operations: for each k, a rank-1 update C += A[:,k] ⊗ B[k,:]
executes element-parallel across all crossbar rows.  The TPU-native analogue
keeps the *blocked data movement* structure (operand tiles resident in VMEM,
serial accumulation over the contraction dimension) but lets the MXU do the
inner product — this is the "adapt the insight, not the artifact" port
(DESIGN.md §2): the scheduling/blocking layer is the paper's, the arithmetic
unit is the hardware's.

The kernel doubles as the framework's general batched-matmul primitive and is
the shape the §Perf iterations tune (block sizes are MXU-aligned multiples of
128).  The PIM cost model for the same operation (gate-level, bit-serial) is
produced by ``repro.core.analyzer`` — benchmarks compare the two.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 128
DEFAULT_BK = 128
DEFAULT_BN = 128


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Mosaic's default contracts f32 operands in one bf16 MXU pass (max
    # relative error ~2e-3 on a v5e); HIGHEST keeps f32 products exact.
    # Mosaic rejects HIGHEST for bf16 operands, whose products are exact.
    f32 = a_ref.dtype == jnp.float32
    acc_ref[...] += jnp.dot(
        a_ref[0], b_ref[0], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if f32 else None,
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bk", "bn", "interpret")
)
def matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = DEFAULT_BM,
    bk: int = DEFAULT_BK,
    bn: int = DEFAULT_BN,
    interpret: bool,
) -> jnp.ndarray:
    """Batched matmul ``[G, M, K] @ [G, K, N] -> [G, M, N]`` (fp32 accumulate).

    Grid: (G·M/bm, N/bn, K/bk); K innermost so the fp32 accumulator tile in
    VMEM scratch is revisited serially — the MatPIM serial-accumulation
    schedule.  ``interpret`` is static; ``ops.pim_matmul_op`` derives it from
    the operands' platform."""
    G, M, K = a.shape
    G2, K2, N = b.shape
    assert G == G2 and K == K2
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (a.shape, b.shape, bm, bk, bn)
    n_k = K // bk
    grid = (G * (M // bm), N // bn, n_k)
    m_blocks = M // bm

    return pl.pallas_call(
        functools.partial(_mm_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda gm, n, k: (gm // m_blocks, gm % m_blocks, k)),
            pl.BlockSpec((1, bk, bn), lambda gm, n, k: (gm // m_blocks, k, n)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gm, n, k: (gm // m_blocks, gm % m_blocks, n)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), a.dtype),
        scratch_shapes=[_vmem_scratch(bm, bn)],
        interpret=interpret,
    )(a, b)


def _vmem_scratch(bm: int, bn: int):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM((bm, bn), jnp.float32)
