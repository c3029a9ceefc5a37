"""Pallas TPU kernels: pim_bitserial (gate-schedule executor) and pim_matmul
(MatPIM-schedule blocked matmul), with ops.py wrappers and ref.py oracles."""

import jax


def interpret_mode(x) -> bool:
    """Whether a kernel over ``x`` runs in Pallas interpret mode: exactly when
    ``x`` lives on the CPU.  Every other platform runs the compiled kernel.
    Under an outer ``jit`` (no concrete devices) the default backend decides."""
    if isinstance(x, jax.core.Tracer):
        return jax.default_backend() == "cpu"
    return all(d.platform == "cpu" for d in x.devices())
