"""Work of one contraction dispatch, ``a [M, K] @ b [K, N]``, for the
roofline of the contraction cells.

K and N come from the configuration (``CONFIG["K"]``, ``CONFIG["N"]``)
and M x N, the outputs, from the cell's ``elements``, so the reading is of
the same work whatever kernel implements it.  The contraction kernel is
found in a trace by its name, ``pim_contract`` (the program's
``CONTRACT_KERNEL``), copied here rather than imported: a renamed kernel
makes the readings absent, not silently moved.
"""

from __future__ import annotations

from bench import reduce

KERNEL = "pim_contract"


def shape(cell) -> tuple[int, int, int] | None:
    """``(M, K, N)`` of one dispatch of ``cell``, or None if its
    configuration is no contraction."""
    config = getattr(cell.config, "CONFIG", {})
    if "K" not in config or "N" not in config:
        return None
    return cell.elements // config["N"], config["K"], config["N"]


def word_ops(step_gates: int, k: int, outputs: int) -> int:
    """Bitwise word-ops: each logic gate of the step schedule once per word
    of the outputs' planes, at each of the K steps."""
    return step_gates * k * reduce.words(outputs)


def io_bytes(m: int, k: int, n: int, bytes_per_elem: int = 4) -> int:
    """HBM bytes of the user arrays: A and B in, C out."""
    return bytes_per_elem * (m * k + k * n + m * n)


def least_time(step_gates: int, m: int, k: int, n: int,
               peaks: dict) -> tuple[float, str]:
    """The least seconds one dispatch could take on the chip, and which
    bound (``ops`` or ``bytes``) sets it."""
    ops_s = word_ops(step_gates, k, m * n) / peaks["vector_word_ops_per_s"]
    bytes_s = io_bytes(m, k, n) / peaks["hbm_bytes_per_s"]
    return (ops_s, "ops") if ops_s >= bytes_s else (bytes_s, "bytes")


def kernel_s(summary) -> float:
    """Device seconds of the ops named ``pim_contract*`` in the window."""
    return sum(s for name, s in summary.device_ops if name.startswith(KERNEL))
