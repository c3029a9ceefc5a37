"""Trace-and-compile frontend: element-wise PIM programs from plain Python.

Users write functions over typed tracers and get back a compiled multi-op
PIM program (DESIGN.md §3):

    import repro.pim as pim

    mac = pim.compile(lambda a, b, c: a * b + c, dtype=pim.f32)
    out = mac(x, y, z)                      # bit-exact, in-memory
    rep = mac.cost(basis="dram")            # program-level CostReport

Tracing works like ``jax.jit``: the function runs once with :class:`Tracer`
arguments whose arithmetic operators append ops to a :class:`Trace`; the
result is an ``ir.Program`` whose per-op ``aritpim`` netlists are recorded
into **one** ScheduleIR — output values of one op wired directly into the
next, so intermediate planes never round-trip through HBM, and the compiler
passes (fold/cse/fuse/dce/reorder) fire across op boundaries.  Netlists are
picked by the tracer's :class:`~repro.core.bitplanes.PimType` via the
``aritpim.OpSpec`` dtype metadata.  Python scalars mixed into the trace
(``a * b + 2.5``) lower to immediate INIT0/INIT1 constant planes
(``ir.CONST_OP``) — they cost no HBM input traffic and constant folding
sees straight through them.

``a @ b`` over the function's two arguments traces a contraction
(``ir.Contraction``): K steps of the fused MAC ``a * b + c`` with the
accumulator carried, run by one ``pim_contract`` kernel (see
:meth:`CompiledPimFunction._contract`).

A single-op trace canonicalizes to ``ir.Program.single``, so e.g.
``pim.compile(lambda a, b: a + b, dtype=pim.f32)`` shares its compile-cache
entry with ``ir.compile_op("float_add")`` and every legacy wrapper.
"""

from __future__ import annotations

import dataclasses
import inspect
import re
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import aritpim, bitplanes, ir
from repro.core.bitplanes import PimType

# Profiler spans of one call (``CompiledPimFunction.__call__``), in order.
PACK_SPAN = "pim.pack"
EXECUTE_SPAN = "pim.execute"
UNPACK_SPAN = "pim.unpack"


class TraceError(TypeError):
    """Raised for untraceable operations (mixed dtypes, non-scalar
    constants, ...)."""


def _encode_scalar(value, dtype: PimType) -> int:
    """A Python scalar's LSB-first bit pattern in ``dtype``'s plane layout.

    Reuses the exact ``PimType`` pack path (``to_planes`` on a one-element
    array), so constants round/wrap exactly like runtime data:
    floats go through IEEE/bf16 rounding, fixed-point wraps two's-complement
    to ``nbits``.  Non-integral constants are rejected for fixed types."""
    if dtype.kind == "fixed":
        if isinstance(value, float) and not value.is_integer():
            raise TraceError(
                f"constant {value!r} is not representable in {dtype.name}: "
                "fixed-point programs take integral constants only")
        # Wrap to the signed two's-complement representative so the int32
        # carrier accepts it at every width (a raw 32-bit mask of a negative
        # constant would overflow jnp.int32).
        v = int(value) & ((1 << dtype.nbits) - 1)
        if v >= 1 << (dtype.nbits - 1):
            v -= 1 << dtype.nbits
        value = jnp.asarray(v, jnp.int32)
    else:
        # Go through Python float first: an int like 2**35 is exactly what
        # float rounding is for, but would overflow the default int32 path.
        try:
            value = float(value)
        except OverflowError:
            raise TraceError(
                f"constant {value!r} overflows {dtype.name}") from None
    planes = dtype.to_planes(jnp.asarray(value).reshape(1))
    return sum((int(p) & 1) << k for k, p in enumerate(planes[:, 0]))


@dataclasses.dataclass(frozen=True)
class Tracer:
    """A typed abstract value flowing through a traced function."""

    trace: "Trace"
    id: int
    dtype: PimType

    def _bin(self, other, arith: str, reverse: bool = False) -> "Tracer":
        if isinstance(other, (int, float, bool)):
            # Scalar constants lower to INIT0/INIT1 immediate planes — they
            # never become HBM inputs (ir.CONST_OP).
            other = self.trace.constant(other, self.dtype)
        if not isinstance(other, Tracer):
            raise TraceError(
                f"cannot apply {arith!r} to a tracer and {type(other).__name__}: "
                "only Python scalars and tracers of the same dtype combine"
            )
        if other.trace is not self.trace:
            raise TraceError("tracers from different traces cannot be combined")
        if self.trace.contraction is not None:
            raise TraceError(
                "a @ b must be the traced program's whole body: no op may "
                "follow it or use its result (epilogues are not supported)")
        if other.dtype != self.dtype:
            raise TraceError(
                f"dtype mismatch in {arith!r}: {self.dtype.name} vs "
                f"{other.dtype.name} (no implicit promotion)"
            )
        a, b = (other, self) if reverse else (self, other)
        return self.trace.emit(arith, a, b)

    def __add__(self, other):
        return self._bin(other, "add")

    def __radd__(self, other):
        return self._bin(other, "add", reverse=True)

    def __sub__(self, other):
        return self._bin(other, "sub")

    def __rsub__(self, other):
        return self._bin(other, "sub", reverse=True)

    def __mul__(self, other):
        return self._bin(other, "mul")

    def __rmul__(self, other):
        return self._bin(other, "mul", reverse=True)

    def __truediv__(self, other):
        return self._bin(other, "div")

    def __rtruediv__(self, other):
        return self._bin(other, "div", reverse=True)

    def __matmul__(self, other):
        return self.trace.contract(self, other)


class Trace:
    """Accumulates the op graph while the traced function runs."""

    def __init__(self):
        self.in_types: list[PimType] = []
        self.body: list[ir.ProgramOp] = []
        self._next_id = 0
        self._consts: dict[tuple[int, str], Tracer] = {}
        self.contraction: Tracer | None = None  # the result of ``a @ b``

    def _fresh(self) -> int:
        v = self._next_id
        self._next_id += 1
        return v

    def input(self, dtype: PimType) -> Tracer:
        assert not self.body, "inputs must be declared before any op"
        self.in_types.append(dtype)
        return Tracer(self, self._fresh(), dtype)

    def constant(self, value, dtype: PimType) -> Tracer:
        """A scalar immediate: one CONST_OP node holding the bit pattern
        (deduplicated per (bits, dtype) so ``a*2 + b*2`` traces one node —
        the dtype is part of the key because two types can share a bit
        pattern, e.g. int16 16256 and bf16 1.0)."""
        bits = _encode_scalar(value, dtype)
        key = (bits, dtype.name)
        hit = self._consts.get(key)
        if hit is not None:
            return hit
        out = self._fresh()
        self.body.append(
            ir.ProgramOp(ir.CONST_OP, (), out, dtype.width, imm=bits))
        tracer = Tracer(self, out, dtype)
        self._consts[key] = tracer
        return tracer

    def contract(self, a, b) -> Tracer:
        """``a @ b`` over the function's two inputs, in order: the whole
        program (see :meth:`CompiledPimFunction._contract`)."""
        if not (isinstance(a, Tracer) and isinstance(b, Tracer)
                and a.trace is self and b.trace is self):
            raise TraceError("a @ b contracts two tracers of one trace")
        if self.contraction is not None:
            raise TraceError("a program holds at most one contraction")
        if a.dtype != b.dtype:
            raise TraceError(
                f"dtype mismatch in '@': {a.dtype.name} vs {b.dtype.name} "
                "(no implicit promotion)")
        if self.body or len(self.in_types) != 2 or (a.id, b.id) != (0, 1):
            raise TraceError(
                "a @ b must be the traced program's whole body, over the "
                "function's two arguments in order: lambda a, b: a @ b")
        self.contraction = Tracer(self, self._fresh(), a.dtype)
        return self.contraction

    def emit(self, arith: str, a: Tracer, b: Tracer) -> Tracer:
        op = aritpim.op_for(arith, a.dtype.kind)
        out = self._fresh()
        # Keep dtype.width planes of the result: fixed-point multiplies wrap
        # (low half of the 2n-bit product; DCE deletes the dead high half).
        self.body.append(ir.ProgramOp(op, (a.id, b.id), out, a.dtype.width))
        return Tracer(self, out, a.dtype)


def _canonical_program(trace: Trace, outputs: Sequence[Tracer], name: str) -> ir.Program:
    """Build the ir.Program; single-op full-width traces canonicalize to
    ``Program.single`` so they share cache entries with ``compile_op``."""
    if len(trace.body) == 1 and len(outputs) == 1:
        node = trace.body[0]
        spec = aritpim._OP_TABLE[node.op]
        nbits = trace.in_types[0].nbits
        if (
            node.args == (0, 1)
            and outputs[0].id == node.out
            and len(trace.in_types) == 2
            and tuple(t.width for t in trace.in_types) == spec.in_widths(nbits)
            and node.width == spec.out_width(nbits)
        ):
            return ir.Program.single(node.op, nbits)
    return ir.Program(
        in_widths=tuple(t.width for t in trace.in_types),
        body=tuple(trace.body),
        outputs=tuple(t.id for t in outputs),
        name=name,
    )


@dataclasses.dataclass(frozen=True)
class CompiledPimFunction:
    """The compile() artifact: callable + program-level cost reporting.

    Execution and analytics are lazy and cached per ``(basis, passes)`` via
    the ``ir`` compile cache, so constructing one (e.g. at module import in
    ``kernels.ops``) costs only the trace."""

    program: ir.Program | ir.Contraction
    in_types: tuple[PimType, ...]
    out_types: tuple[PimType, ...]
    backend: str = "pallas"

    def compiled(self, basis: str = "memristive",
                 passes: tuple[str, ...] = ir.DEFAULT_PASSES) -> ir.CompiledSchedule:
        """The compiled schedule; for ``a @ b``, the schedule of one MAC
        step, which a dispatch runs K times."""
        return ir.compile_program(self.program, passes, basis)

    def cost(self, basis: str = "memristive",
             passes: tuple[str, ...] = ir.DEFAULT_PASSES) -> ir.CostReport:
        """Program-level CostReport from the analytical backend; for
        ``a @ b``, the cost of one MAC step, which a dispatch runs K times."""
        return ir.program_cost(self.program, passes, basis)

    def __call__(self, *arrays, basis: str = "memristive",
                 passes: tuple[str, ...] = ir.DEFAULT_PASSES,
                 backend: str | None = None):
        """Pack ``arrays`` to bit-planes, run the compiled program, unpack.

        Each phase is a ``jax.profiler.TraceAnnotation`` span, so a
        profiler trace shows where a call's host time goes: ``PACK_SPAN``
        encloses the enqueue of one program (``bitplanes.pack``: cast and
        pack every input), ``EXECUTE_SPAN`` the compile-cache lookup and
        the backend's ``run`` (padding, placement, every kernel launch, the
        trim) and ``UNPACK_SPAN`` the enqueue of one program
        (``bitplanes.unpack``: every result).  Nothing here waits for a
        result, so the spans time the host's enqueue of device work (which
        stalls while the device's queue is full), not the device; under a
        caller's ``jax.jit`` the pack and unpack programs nest in the
        caller's and the spans time tracing.  With no profiler running a
        span costs about a microsecond."""
        if len(arrays) != len(self.in_types):
            raise TypeError(
                f"expected {len(self.in_types)} arrays, got {len(arrays)}")
        if isinstance(self.program, ir.Contraction):
            return self._contract(*arrays, basis=basis, passes=passes,
                                  backend=backend)
        n = jnp.shape(arrays[0])[0]
        with jax.profiler.TraceAnnotation(PACK_SPAN):
            planes = bitplanes.pack(self.in_types, *arrays)
        with jax.profiler.TraceAnnotation(EXECUTE_SPAN):
            compiled = self.compiled(basis, passes)
            out = ir.get_backend(backend or self.backend).run(
                compiled, planes).planes
        with jax.profiler.TraceAnnotation(UNPACK_SPAN):
            results = bitplanes.unpack(self.out_types, out, n)
        return results[0] if len(results) == 1 else results

    def _contract(self, a, b, *, basis, passes, backend):
        """``a [M, K] @ b [K, N]`` → ``[M, N]`` in the input dtype: a serial
        MAC contraction with a fixed rounding order, not a tree reduction.
        ``acc = +0``, then for ``k = 0 .. K-1`` in order
        ``acc = fl(fl(a[m, k] * b[k, n]) + acc)``, each step rounded as the
        program ``a * b + c`` rounds (nearest-even, subnormals kept, for
        floats; two's-complement wrap for fixed types).

        The same spans as an element-wise call: ``PACK_SPAN`` encloses one
        program (``bitplanes.pack_contraction``: the ``[K, 2 * width, W]``
        step operand planes), ``EXECUTE_SPAN`` the cache lookup and one
        ``pim_contract`` launch, ``UNPACK_SPAN`` one program
        (``bitplanes.unpack_contraction``).  Any K is three programs."""
        shapes = (jnp.shape(a), jnp.shape(b))
        if (len(shapes[0]) != 2 or len(shapes[1]) != 2
                or shapes[0][1] != shapes[1][0] or 0 in shapes[0] + shapes[1]):
            raise ValueError(
                f"a @ b takes a [M, K] and b [K, N] with M, K, N >= 1, "
                f"got shapes {shapes[0]} and {shapes[1]}")
        t = self.in_types[0]
        with jax.profiler.TraceAnnotation(PACK_SPAN):
            steps = bitplanes.pack_contraction(t, a, b)
        with jax.profiler.TraceAnnotation(EXECUTE_SPAN):
            compiled = self.compiled(basis, passes)
            out = ir.get_backend(backend or self.backend).contract(
                compiled, self.program, steps)
        with jax.profiler.TraceAnnotation(UNPACK_SPAN):
            return bitplanes.unpack_contraction(t, out, shapes[0][0],
                                                shapes[1][1])


def trace(fn, dtype) -> CompiledPimFunction:
    """Trace ``fn`` into a Program without committing to a backend."""
    try:
        params = list(inspect.signature(fn).parameters.values())
    except (TypeError, ValueError):  # builtins / C callables
        raise TraceError("cannot inspect the traced function's signature")
    if any(p.kind not in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
           for p in params):
        raise TraceError(
            "traced functions must take plain positional arguments "
            "(*args/**kwargs/keyword-only parameters are not traceable)")
    n_args = len(params)
    if isinstance(dtype, PimType):
        dtypes = (dtype,) * n_args
    else:
        dtypes = tuple(dtype)
        if len(dtypes) != n_args:
            raise TraceError(
                f"{len(dtypes)} dtypes for a {n_args}-argument function")
    t = Trace()
    args = [t.input(d) for d in dtypes]
    result = fn(*args)
    outs = result if isinstance(result, (tuple, list)) else (result,)
    if not outs or not all(isinstance(o, Tracer) and o.trace is t for o in outs):
        raise TraceError("the traced function must return its tracer value(s)")
    name = re.sub(r"[^A-Za-z0-9_]", "", getattr(fn, "__name__", "")) or "program"
    if t.contraction is not None:
        if tuple(outs) != (t.contraction,):
            raise TraceError("a program with a @ b returns a @ b alone")
        # The step is the fused MAC, traced as ``a * b + c`` is, so it shares
        # that program's compile-cache entry and schedule; ``c`` is carried.
        step = trace(lambda a, b, c: a * b + c, dtypes[0]).program
        return CompiledPimFunction(
            program=ir.Contraction(step, carry_in=2, carry_out=0),
            in_types=dtypes, out_types=(dtypes[0],))
    program = _canonical_program(t, outs, name)
    return CompiledPimFunction(
        program=program,
        in_types=dtypes,
        out_types=tuple(o.dtype for o in outs),
    )


def compile(fn, dtype, backend: str = "pallas") -> CompiledPimFunction:  # noqa: A001
    """Trace-and-compile an element-wise PIM program, or the contraction
    ``a @ b`` (the public API).

    ``dtype`` is one :class:`PimType` for all arguments or a sequence of
    per-argument types (both operands of every op must agree — there is no
    implicit promotion).  The returned function packs arrays to bit-planes,
    executes the fused program on the requested executor backend
    (``pallas`` by default; Pallas interpret mode when the arrays are on the
    CPU) and unpacks the result; ``.cost(basis=...)`` prices it analytically
    on either basis.
    """
    return dataclasses.replace(trace(fn, dtype), backend=backend)
