"""The ResNet-50 3x3 conv cell (``a @ b`` through ``pim_contract``) on the
CPU, at 8 output rows of the layer (K = 1152, as published).

One run executes the real program; the planted faults and the control run
the numpy reference (or the control) on the host in the program's place,
so no second kernel runs in interpret mode.  The roofline's work counts
and the per-layer readers are checked against numbers worked by hand.
"""

from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import contraction, harness, reduce  # noqa: E402

CELL = "r50_conv3x3.memristive.b1"
ROWS = 8
SEED = 2 ** 31 + 12345
V5E = harness.load_peaks("TPU v5 lite")


def _small(input_sets: int = 2) -> harness.Cell:
    return dataclasses.replace(harness.load_cell(CELL), elements=ROWS * 128,
                               input_sets=input_sets)


def _run(cell, seed=SEED, seconds=0.01, **kw):
    return harness.run_cell(cell, seed, seconds, False,
                            benchmark=harness.load_benchmark(), peaks=V5E,
                            t_process=time.perf_counter(), **kw)


def _on_host(fn):
    """``fn`` over numpy arrays put in the program's place."""
    def dispatch(*arrays):
        return jnp.asarray(fn(*map(np.asarray, arrays)))
    return lambda _program: dispatch


# ------------------------------------------------------------------ data


def test_inputs_are_the_layer_as_im2col():
    """A is the 3x3 SAME im2col of ReLU'd activations, (kh, kw, cin) order,
    and B He-normal weights; away from the planted specials the reference
    is their float32 product."""
    config = harness.load_cell(CELL).config
    a, b = config.make_inputs(np.random.default_rng(1), 784 * 128)
    assert a.shape == (784, 1152) and b.shape == (1152, 128)
    assert a.dtype == b.dtype == np.float32
    taps = a.reshape(28, 28, 3, 3, 128)
    x = taps[:, :, 1, 1]  # the centre tap is the activation itself
    assert (x[2:] >= 0).all() and 0.4 < (x[2:] == 0).mean() < 0.6
    padded = np.pad(x, ((1, 1), (1, 1), (0, 0)))
    for i in range(3):  # rows 0-3 hold specials: compare from row 56 on
        for j in range(3):
            np.testing.assert_array_equal(taps[2:, :, i, j],
                                          padded[2 + i:28 + i, j:j + 28])
    assert np.std(b[:, 4:]) == pytest.approx(np.sqrt(2 / 1152), rel=0.02)
    got = config.reference(a, b)
    exact = a[4:].astype(np.float64) @ b[:, 4:].astype(np.float64)
    np.testing.assert_allclose(got[4:, 4:], exact, rtol=1e-4, atol=1e-5)
    assert np.isnan(got[0]).all()


def test_inputs_follow_the_seed_and_plant_specials():
    cell = _small()
    first, again = (harness.make_input_sets(cell, SEED) for _ in range(2))
    assert [x.shape for s in first for x in s] == [(ROWS, 1152),
                                                   (1152, 128)] * 2
    for x, y in zip(first[0], again[0]):
        assert x.tobytes() == y.tobytes()
    assert first[0][0].tobytes() != first[1][0].tobytes()
    a, b = first[0]
    out = cell.config.reference(a, b)
    tiny = (out != 0) & (np.abs(out) < np.finfo(np.float32).tiny)
    assert np.isnan(out).any() and np.isinf(out).any() and tiny.any()
    assert np.isfinite(out[4:, 4:]).all()  # the rest is realistic
    with pytest.raises(ValueError):
        cell.config.make_inputs(np.random.default_rng(0), 100)


# ------------------------------------------------------------------ correct


def test_contraction_cell_is_correct():
    """The real program, through the harness: one input set (each dispatch
    runs 1152 steps of the MAC schedule in interpret mode)."""
    result = _run(_small(input_sets=1))
    assert result["correct"], result
    assert result["checks"]["mismatched_elements"]["value"] == 0


def _skip_one_step(a, b):
    return _reference_of(a, b, order=[k for k in range(a.shape[1]) if k != 600])


def _reordered(a, b):
    return _reference_of(a, b, order=range(a.shape[1] - 1, -1, -1))


def _reference_of(a, b, order):
    acc = np.zeros((a.shape[0], b.shape[1]), np.float32)
    with np.errstate(all="ignore"):
        for k in order:
            acc = a[:, k, None] * b[None, k, :] + acc
    return acc


FAULTS = {"one_step_skipped": _skip_one_step, "sum_reordered": _reordered}


@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_faulty_contraction_is_not_correct(fault):
    """The numpy reference in the program's place reads correct; one k-step
    left out, or the sum taken in another order, reads not correct."""
    cell = _small()
    fn = cell.config.reference if fault is None else FAULTS[fault]
    result = _run(cell, wrap=_on_host(fn))
    assert result["correct"] is (fault is None), result
    if fault is not None:
        assert result["checks"]["mismatched_elements"]["value"] > 0


def test_control_is_not_correct():
    """The bf16 control in the program's place fails on several seeds."""
    cell = _small()
    for seed in (1, 2, SEED):
        result = _run(cell, seed=seed, wrap=_on_host(cell.config.control))
        assert not result["correct"], result
        assert result["checks"]["mismatched_elements"]["value"] > 0


# ------------------------------------------------------------------ work


def test_roofline_work_by_hand():
    # The whole layer: 12968 gates x 1152 steps x 3136 words.
    assert contraction.word_ops(12968, 1152, 784 * 128) == 46_849_130_496
    assert contraction.io_bytes(784, 1152, 128) == 4 * 1_150_976
    least, bound = contraction.least_time(12968, 784, 1152, 128, V5E)
    assert bound == "ops"
    assert least == pytest.approx(46_849_130_496 / 6_156_288_000_000)
    # One gate, one step, 1024 outputs: 32 word-ops against 4352 bytes.
    assert contraction.word_ops(1, 1, 1024) == 32
    least, bound = contraction.least_time(1, 32, 1, 32, V5E)
    assert bound == "bytes"
    assert least == pytest.approx(4 * (32 + 32 + 1024) / 819e9)
    assert contraction.shape(harness.load_cell(CELL)) == (784, 1152, 128)
    assert contraction.shape(harness.load_cell("mac_f32.memristive.b1")) \
        is None


def _summary(ops, dispatches=2, busy_s=8.0):
    kernel_s = sum(s for n, s, k in ops if k)
    return reduce.TraceSummary(
        dispatches=dispatches, window_s=10.0, busy_s=busy_s,
        kernel_s=kernel_s, outside_s=sum(s for n, s, k in ops if not k),
        device_ops=[[n, s] for n, s, k in ops], idle_gaps=[])


def _read(name, trace):
    ctx = harness.Context(cell=harness.load_cell(CELL), compiled=None,
                          cost=None, trace_passes_s=0.0, n_args=2,
                          n_outputs=1, peaks=V5E, trace=trace)
    return harness.load_metric(name).read(ctx)


def test_readers_by_hand():
    trace = _summary([("pim_contract.1", 7.0, True), ("fusion.3", 0.5, False),
                      ("pim_loop.1", 0.25, True), ("pack.2", 0.25, False)])
    assert _read("contract_kernel_ms", trace) == pytest.approx(3500.0)
    assert _read("contract_outside_ms", trace) == pytest.approx(500.0)
    # No kernel of that name, or no trace: nothing to read.
    other = _summary([("pim_loop.1", 7.0, True), ("fusion.3", 0.5, False)])
    for name in ("contract_kernel_ms", "contract_outside_ms"):
        assert _read(name, other) is None
        assert _read(name, None) is None


def test_roofline_and_gates_readers():
    cell = harness.load_cell(CELL)
    _, compiled, cost, _, n_args, n_outputs = harness.build_dispatch(cell)
    trace = _summary([("pim_contract.1", 7.0, True)], busy_s=7.6100)
    ctx = harness.Context(cell=cell, compiled=compiled, cost=cost,
                          trace_passes_s=0.0, n_args=n_args,
                          n_outputs=n_outputs, peaks=V5E, trace=trace)
    benchmark = harness.load_benchmark()
    read = harness.read_per_layer(benchmark, ctx)
    assert read["contract_step_gates"] == {"value": 12968, "unit": "gates"}
    # Least time 7.6100 ms over 3.805 s of busy time per dispatch.
    least = 46_849_130_496 / 6_156_288_000_000
    assert read["contract_roofline"]["value"] == pytest.approx(
        100 * least / 3.805)
    assert set(read) == {"contract_kernel_ms", "contract_outside_ms",
                         "contract_roofline", "contract_step_gates"}
