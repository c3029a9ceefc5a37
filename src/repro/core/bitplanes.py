"""Bit-plane packing utilities for the digital-PIM abstract machine.

A *bit-plane* is one column of the abstract crossbar model (paper Fig 1e):
one bit per memory row.  We pack 32 rows into one ``uint32`` word so that a
column-parallel logic gate over ``R`` rows becomes a single bitwise op over
``ceil(R/32)`` words — the TPU-native (lane-packed, VPU-friendly) encoding of
the paper's column operation.

An ``N``-bit number vector is ``N`` planes, LSB first: bit ``k`` of word
``w`` of plane ``j`` is bit ``j`` of element ``32 w + k``, and ``N`` is
padded with zero elements to a multiple of 32.  :func:`words_to_planes` and
:func:`planes_to_words` are the one implementation of that layout; each
handles every plane at once, as one jitted program, and the planes are
lane-dense ``[planes, words]``.

:class:`PimType` packages one element type's plane layout (width, packing,
unpacking) so frontends and kernels share a single description instead of
per-dtype boilerplate: ``F32``/``BF16`` for the IEEE formats, ``fixed(n)``
for two's-complement integers.  The ``repro.pim`` tracer picks netlists by
``PimType.kind`` via the ``aritpim.OpSpec`` dtype metadata.  :func:`pack`
and :func:`unpack` convert a whole call's arrays, one program each; the
per-plane list helpers (``int_to_planes``, ``planes_to_f32``, ...) are thin
views of the same layout for callers that hold planes as Python lists.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

WORD = 32
UMAX = jnp.uint32(0xFFFFFFFF)


def num_words(n_elems: int) -> int:
    """Words needed to hold one bit from each of ``n_elems`` rows."""
    return (n_elems + WORD - 1) // WORD


@functools.partial(jax.jit, static_argnums=1)
def words_to_planes(u: jnp.ndarray, width: int) -> jnp.ndarray:
    """uint32 ``[n]`` → ``[width, ceil(n/32)]`` planes of its low ``width`` bits.

    The elements are first transposed to ``[32, words]`` (row ``k`` holds
    element ``32 w + k`` of every word ``w``), so each plane is a sum over
    that array's major axis and the planes come out lane-dense."""
    pad = (-u.shape[0]) % WORD
    if pad:
        u = jnp.pad(u, (0, pad))
    cols = u.reshape(-1, WORD).T
    k = jnp.arange(WORD, dtype=jnp.uint32)[:, None, None]
    j = jnp.arange(width, dtype=jnp.uint32)[:, None]
    return (((cols[:, None, :] >> j) & 1) << k).sum(axis=0, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnums=1)
def planes_to_words(planes: jnp.ndarray, n_elems: int) -> jnp.ndarray:
    """Inverse of :func:`words_to_planes`: ``[width, W]`` → uint32 ``[n_elems]``
    (bits at and above ``width`` are zero)."""
    width = planes.shape[0]
    j = jnp.arange(width, dtype=jnp.uint32)[:, None, None]
    k = jnp.arange(WORD, dtype=jnp.uint32)[:, None]
    cols = (((planes[:, None, :] >> k) & 1) << j).sum(axis=0, dtype=jnp.uint32)
    return cols.T.reshape(-1)[:n_elems]


def _fixed_from_words(u: jnp.ndarray, nbits: int, signed: bool) -> jnp.ndarray:
    """Low ``nbits`` of ``u`` as a two's-complement int32 (or as uint32)."""
    if not signed:
        return u
    if nbits < 32:
        sign = (u >> jnp.uint32(nbits - 1)) & jnp.uint32(1)
        u = u | jnp.where(sign == 1, UMAX << jnp.uint32(nbits), jnp.uint32(0))
    return u.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Typed plane layouts (consumed by repro.pim and kernels/ops.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PimType:
    """One PIM element type: plane count + pack/unpack + netlist selection.

    ``kind`` is the key the tracer matches against ``aritpim.OpSpec.dtype``
    (``"fixed"`` | ``"float32"`` | ``"bf16"``); ``width`` is planes per
    element (LSB first); ``nbits`` parameterizes width-generic netlists
    (equal to ``width`` for every current format)."""

    name: str
    kind: str
    width: int
    nbits: int

    def cast(self, x) -> jnp.ndarray:
        """Coerce an array to this type's carrier jnp dtype."""
        if self.kind == "float32":
            return jnp.asarray(x, jnp.float32)
        if self.kind == "bf16":
            return jnp.asarray(x, jnp.bfloat16)
        return jnp.asarray(x)  # fixed: keep the caller's integer dtype

    def to_words(self, x) -> jnp.ndarray:
        """``[n]`` array → its uint32 bit patterns (the low ``width`` bits)."""
        x = self.cast(x)
        if self.kind == "float32":
            return jax.lax.bitcast_convert_type(x, jnp.uint32)
        if self.kind == "bf16":
            return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        return x.astype(jnp.uint32)

    def from_words(self, u: jnp.ndarray) -> jnp.ndarray:
        """Inverse of :meth:`to_words` (fixed types decode as signed)."""
        if self.kind == "float32":
            return jax.lax.bitcast_convert_type(u, jnp.float32)
        if self.kind == "bf16":
            return jax.lax.bitcast_convert_type(u.astype(jnp.uint16), jnp.bfloat16)
        return _fixed_from_words(u, self.nbits, signed=True)

    def to_planes(self, x) -> jnp.ndarray:
        """``[n]`` array → ``[width, ceil(n/32)]`` packed planes (LSB first)."""
        return words_to_planes(self.to_words(x), self.width)

    def from_planes(self, planes, n_elems: int) -> jnp.ndarray:
        """Inverse of :meth:`to_planes`; ``planes`` is ``[width, W]`` or a
        list of ``width`` planes."""
        planes = jnp.asarray(planes)
        assert planes.shape[0] == self.width, (self.name, planes.shape, self.width)
        return self.from_words(planes_to_words(planes, n_elems))


F32 = PimType("f32", "float32", 32, 32)
BF16 = PimType("bf16", "bf16", 16, 16)


def fixed(nbits: int) -> PimType:
    """Two's-complement fixed-point type with ``nbits`` planes."""
    assert 1 <= nbits <= 32
    return PimType(f"fixed{nbits}", "fixed", nbits, nbits)


@functools.partial(jax.jit, static_argnums=0)
def pack(types: tuple[PimType, ...], *arrays) -> jnp.ndarray:
    """Cast and pack one call's arrays: ``[sum of widths, ceil(n/32)]``."""
    return jnp.concatenate([t.to_planes(x) for t, x in zip(types, arrays)])


@functools.partial(jax.jit, static_argnums=(0, 2))
def unpack(types: tuple[PimType, ...], planes: jnp.ndarray,
           n_elems: int) -> tuple[jnp.ndarray, ...]:
    """Inverse of :func:`pack`: one ``[n_elems]`` array per type."""
    out, i = [], 0
    for t in types:
        out.append(t.from_planes(planes[i:i + t.width], n_elems))
        i += t.width
    return tuple(out)


def _padded_cols(n: int) -> int:
    """N padded to whole words: a contraction's output row fills whole words."""
    return num_words(n) * WORD


@functools.partial(jax.jit, static_argnums=0)
def pack_contraction(t: PimType, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """The step operand planes of ``a [M, K] @ b [K, N]``:
    ``[K, 2 * width, M * N' / 32]``, step ``k``'s ``a_k`` planes then its
    ``b_k`` planes.

    Output element ``e = m * N' + n``, with N padded to ``N'``, a multiple
    of 32 (zero columns of ``b``), so one word holds 32 consecutive ``n`` of
    one ``m``.  ``a_k[e] = a[m, k]``: each word of plane ``j`` is
    ``0 - bit_j(a[m, k])``, all ones or all zeros.  ``b_k[e] = b[k, n]``:
    the planes of row ``k`` of ``b`` (:func:`words_to_planes`), tiled over
    ``m``."""
    m = a.shape[0]
    n_pad = _padded_cols(b.shape[1])
    j = jnp.arange(t.width, dtype=jnp.uint32)[:, None]
    a_bits = (t.to_words(a).T[:, None, :] >> j) & 1              # [K, w, M]
    a_planes = jnp.repeat(jnp.uint32(0) - a_bits, n_pad // WORD, axis=2)
    ub = jnp.pad(t.to_words(b), ((0, 0), (0, n_pad - b.shape[1])))
    b_planes = jax.vmap(words_to_planes, in_axes=(0, None))(ub, t.width)
    return jnp.concatenate([a_planes, jnp.tile(b_planes, (1, 1, m))], axis=1)


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def unpack_contraction(t: PimType, planes: jnp.ndarray, m: int,
                       n: int) -> jnp.ndarray:
    """Inverse layout of :func:`pack_contraction`'s output elements:
    ``[width, M * N' / 32]`` planes → ``[M, N]`` (the padding trimmed)."""
    n_pad = _padded_cols(n)
    u = planes_to_words(planes, m * n_pad).reshape(m, n_pad)[:, :n]
    return t.from_words(u)


# ---------------------------------------------------------------------------
# Per-plane list views (aritpim's PlaneVM and tests hold planes as lists)
# ---------------------------------------------------------------------------


def pack_bits(bits) -> jnp.ndarray:
    """Pack a boolean vector ``[n]`` into ``[ceil(n/32)]`` uint32 (LSB-first in word)."""
    return words_to_planes(jnp.asarray(bits, jnp.uint32), 1)[0]


def unpack_bits(words: jnp.ndarray, n_elems: int) -> jnp.ndarray:
    """Inverse of :func:`pack_bits` → bool ``[n_elems]``."""
    return planes_to_words(jnp.asarray(words)[None], n_elems).astype(bool)


def int_to_planes(x, nbits: int) -> list[jnp.ndarray]:
    """Two's-complement integer vector ``[n]`` → ``nbits`` packed planes (LSB first)."""
    return list(fixed(nbits).to_planes(x))


def planes_to_int(planes: list[jnp.ndarray], n_elems: int, signed: bool = True) -> jnp.ndarray:
    """``nbits`` packed planes → integer vector ``[n_elems]`` (two's complement)."""
    u = planes_to_words(jnp.asarray(planes), n_elems)
    return _fixed_from_words(u, len(planes), signed)


def f32_to_planes(x) -> list[jnp.ndarray]:
    """float32 vector ``[n]`` → 32 packed planes (LSB first: mantissa, exp, sign)."""
    return list(F32.to_planes(x))


def planes_to_f32(planes: list[jnp.ndarray], n_elems: int) -> jnp.ndarray:
    return F32.from_planes(planes, n_elems)


def bf16_to_planes(x) -> list[jnp.ndarray]:
    """bfloat16 vector ``[n]`` → 16 packed planes (LSB first: mantissa, exp, sign)."""
    return list(BF16.to_planes(x))


def planes_to_bf16(planes: list[jnp.ndarray], n_elems: int) -> jnp.ndarray:
    return BF16.from_planes(planes, n_elems)


def np_pack_reference(bits: np.ndarray) -> np.ndarray:
    """NumPy oracle for pack_bits (used by tests)."""
    n = bits.shape[0]
    pad = (-n) % WORD
    if pad:
        bits = np.concatenate([bits, np.zeros(pad, dtype=bits.dtype)])
    bits = bits.reshape(-1, WORD).astype(np.uint64)
    shifts = np.arange(WORD, dtype=np.uint64)
    return (bits << shifts).sum(axis=1).astype(np.uint32)
