"""Jit'd public wrappers — thin aliases over the ``repro.pim`` frontend.

Every ``pim_*`` arithmetic wrapper is now a one-line alias over a traced
``repro.pim`` program: the frontend packs planes via the
``bitplanes.PimType`` layouts, compiles through the one ``repro.core.ir``
cache (single-op traces canonicalize to the same cache entries as
``ir.compile_op``) and executes on the ``pallas`` backend (interpret mode
when the arrays are on the CPU, the compiled kernel anywhere else).  Adding
a wrapper is a registration, not a new code path, and every wrapper takes
``basis="memristive"|"dram"`` to execute the NOR or the MAJ3/NOT lowering of
the same netlist; the backend picks the executor kernel by gate count
(DESIGN.md §5).
``pim_matmul`` is the MatPIM-schedule blocked matmul.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

import repro.pim as pim

from . import interpret_mode, pim_matmul

_ARITH_FNS = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
    "div": lambda a, b: a / b,
}


@functools.lru_cache(maxsize=None)
def _fn(arith: str, dtype_name: str, nbits: int) -> pim.CompiledPimFunction:
    dtype = {"f32": pim.f32, "bf16": pim.bf16}.get(dtype_name) or pim.fixed(nbits)
    return pim.compile(_ARITH_FNS[arith], dtype=dtype, backend="pallas")


def pim_float_add(x, y, basis: str = "memristive"):
    return _fn("add", "f32", 32)(x, y, basis=basis)


def pim_float_sub(x, y, basis: str = "memristive"):
    return _fn("sub", "f32", 32)(x, y, basis=basis)


def pim_float_mul(x, y, basis: str = "memristive"):
    return _fn("mul", "f32", 32)(x, y, basis=basis)


def pim_float_div(x, y, basis: str = "memristive"):
    return _fn("div", "f32", 32)(x, y, basis=basis)


def pim_bf16_add(x, y, basis: str = "memristive"):
    return _fn("add", "bf16", 16)(x, y, basis=basis)


def pim_bf16_sub(x, y, basis: str = "memristive"):
    return _fn("sub", "bf16", 16)(x, y, basis=basis)


def pim_bf16_mul(x, y, basis: str = "memristive"):
    return _fn("mul", "bf16", 16)(x, y, basis=basis)


def pim_fixed_add(x, y, nbits: int = 32, basis: str = "memristive"):
    return _fn("add", "fixed", nbits)(x, y, basis=basis)


def pim_fixed_sub(x, y, nbits: int = 32, basis: str = "memristive"):
    return _fn("sub", "fixed", nbits)(x, y, basis=basis)


def pim_fixed_mul(x, y, nbits: int = 32, basis: str = "memristive"):
    """Signed N×N multiply; returns the low N bits (wrapping, like int mul)."""
    return _fn("mul", "fixed", nbits)(x, y, basis=basis)


def pim_fixed_div(x, y, nbits: int = 32, basis: str = "memristive"):
    """Signed division (C truncation semantics); x//0 is the netlist's
    documented all-ones convention."""
    return _fn("div", "fixed", nbits)(x, y, basis=basis)


def pim_matmul_op(a, b, *, bm=128, bk=128, bn=128):
    a = jnp.asarray(a)
    return pim_matmul.matmul(a, b, bm=bm, bk=bk, bn=bn,
                             interpret=interpret_mode(a))


def schedule_info(opname: str, nbits: int = 32, basis: str = "memristive"):
    """(recorded schedule length, allocated columns) — benchmarks/tests."""
    from repro.core import ir

    compiled = ir.compile_op(opname, nbits=nbits, basis=basis)
    return compiled.recorded_len, compiled.num_cols
