"""Schedule-IR compiler pipeline: pass-by-pass bit-exactness vs the
execute-mode oracle, gate-count monotonicity, column-budget guarantees,
backend agreement (interpreter vs Pallas interpret), the new
int8/int16/bf16 ops through the same compilation path, and the multi-basis
(memristive NOR vs DRAM MAJ3/NOT) lowering invariants."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import aritpim, bitplanes, ir, simulate
from repro.core.machine import (
    OP_INIT0,
    OP_INIT1,
    OP_MAJ3,
    OP_NOR,
    OP_NOT,
    PlaneVM,
    get_basis,
)

np.seterr(all="ignore")

PASS_CONFIGS = [(), ("fold",), ("cse",), ("fuse",), ("dce",), ("reorder",),
                ("levelize",), ir.DEFAULT_PASSES]


def _f32_vec(n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32).view(np.float32)


def _run_f32(compiled, x, y, backend="interpreter"):
    planes = jnp.stack(
        bitplanes.f32_to_planes(jnp.asarray(x)) + bitplanes.f32_to_planes(jnp.asarray(y))
    )
    out = ir.get_backend(backend).run(compiled, planes).planes
    return np.asarray(bitplanes.planes_to_f32([out[i] for i in range(32)], len(x)))


def _check_f32(got, exp):
    ok = (got.view(np.uint32) == exp.view(np.uint32)) | (np.isnan(got) & np.isnan(exp))
    assert ok.all(), f"{(~ok).sum()} ULP mismatches"


# ------------------------------------------------------------------- passes

@pytest.mark.parametrize("basis", ["memristive", "dram"])
@pytest.mark.parametrize("passes", PASS_CONFIGS, ids=lambda p: "+".join(p) or "none")
@pytest.mark.parametrize("op", ["float_add", "float_mul"])
def test_each_pass_preserves_float_semantics(op, passes, basis):
    """Every pass (and the default pipeline) is semantics-preserving on both
    logic bases: the compiled schedule reproduces IEEE float32 bit-for-bit."""
    x, y = _f32_vec(96, 1), _f32_vec(96, 2)
    compiled = ir.compile_op(op, passes=passes, basis=basis)
    got = _run_f32(compiled, x, y)
    exp = (x + y if op == "float_add" else x * y).astype(np.float32)
    _check_f32(got, exp)


@pytest.mark.parametrize("op", ["fixed_add", "fixed_mul", "float_add", "float_mul",
                                "bf16_add", "bf16_mul"])
def test_pipeline_gate_count_non_increasing(op):
    """Acceptance: post-pipeline gate count ≤ recorded gate count, and each
    pass prefix never increases the schedule length."""
    nbits = 16 if op.startswith("bf16") else 32
    recorded = ir.record_op(op, nbits)
    prev = recorded.num_gates
    for k in range(1, len(ir.DEFAULT_PASSES) + 1):
        cur = ir.run_passes(recorded, ir.DEFAULT_PASSES[:k]).num_gates
        assert cur <= prev, (op, ir.DEFAULT_PASSES[:k], cur, prev)
        prev = cur
    compiled = ir.compile_op(op, nbits)
    assert compiled.num_gates <= compiled.recorded_len
    assert compiled.nor_gates <= compiled.recorded_gates


@pytest.mark.parametrize("op", ["fixed_add", "fixed_mul", "float_add", "float_mul"])
def test_pipeline_peak_columns_within_old_compress(op):
    """Acceptance: peak live columns ≤ the old compress_schedule result
    (= lowering the recorded schedule with no passes)."""
    baseline = ir.lower(ir.record_op(op))
    compiled = ir.compile_op(op)
    assert compiled.num_cols <= baseline.num_cols, (op, compiled.num_cols, baseline.num_cols)
    assert compiled.meta["baseline_cols"] == baseline.num_cols
    assert compiled.num_cols <= 1024  # the paper's crossbar budget


def test_fold_constants_unit():
    """NOR against a known constant folds to an INIT."""
    vm = PlaneVM(mode="record")
    a = vm.input_plane()
    one = vm.const1()
    zero = vm.const0()
    x = vm.nor(a, one)   # == 0
    y = vm.nor(zero, zero)  # == 1
    z = vm.nor(a, zero)  # == NOT a, stays a gate
    sched = vm.finish_schedule({"a": [a]}, {"out": [x, y, z]})
    folded = ir.fold_constants(ir.from_schedule(sched))
    ops = {int(o) for o in folded.ops[:, 0]}
    nors = folded.ops[folded.ops[:, 0] == OP_NOR]
    assert OP_INIT0 in ops and OP_INIT1 in ops
    assert len(nors) == 1  # only NOT(a) survives as a gate
    assert int(nors[0][1]) == int(nors[0][2])  # canonicalized to NOR(a, a)


def test_cse_unit():
    """Identical NORs (either operand order) collapse to one gate."""
    vm = PlaneVM(mode="record")
    a, b = vm.input_plane(), vm.input_plane()
    x = vm.nor(a, b)
    y = vm.nor(b, a)  # same value, swapped operands
    sched = vm.finish_schedule({"a": [a], "b": [b]}, {"out": [x, y]})
    out = ir.common_subexpr_elim(ir.from_schedule(sched))
    assert out.num_gates == 1
    o = out.outputs["out"]
    assert o[0] == o[1]  # both outputs alias the surviving value


def test_fuse_not_not_unit():
    """NOT(NOT(x)) folds to x itself (then DCE sweeps the dead NOTs)."""
    vm = PlaneVM(mode="record")
    a, b = vm.input_plane(), vm.input_plane()
    x = vm.nor(a, b)
    nn = vm.nor(vm.not_(x), vm.not_(x))  # NOT(NOT(x)): not-cache dedups the inner NOT
    sched = vm.finish_schedule({"a": [a], "b": [b]}, {"out": [nn]})
    fused = ir.dead_gate_elim(ir.fuse_copies(ir.from_schedule(sched)))
    assert fused.num_gates == 1  # only the original NOR remains
    assert fused.outputs["out"][0] == fused.ops[0][4]  # (op, a, b, c, out)


def test_dce_unit():
    vm = PlaneVM(mode="record")
    a, b = vm.input_plane(), vm.input_plane()
    keep = vm.nor(a, b)
    vm.nor(keep, a)  # dead: never reaches an output
    sched = vm.finish_schedule({"a": [a], "b": [b]}, {"out": [keep]})
    out = ir.dead_gate_elim(ir.from_schedule(sched))
    assert out.num_gates == 1


# ----------------------------------------------------------------- backends

def test_interpreter_and_pallas_agree_on_same_ir():
    """Both executors consume the identical optimized CompiledSchedule."""
    x, y = _f32_vec(257, 3), _f32_vec(257, 4)
    compiled = ir.compile_op("float_add")
    got_i = _run_f32(compiled, x, y, backend="interpreter")
    got_p = _run_f32(compiled, x, y, backend="pallas")
    assert np.array_equal(got_i.view(np.uint32), got_p.view(np.uint32))


def test_cost_backend_reports_compiled_counts():
    rep = ir.op_cost("float_add")
    compiled = ir.compile_op("float_add")
    assert rep.gates == compiled.nor_gates
    assert rep.recorded_gates == compiled.recorded_gates
    assert rep.schedule_len == compiled.num_gates
    assert rep.cycles == 2 * compiled.num_gates
    assert rep.num_cols == compiled.num_cols
    # the pipeline is actually optimizing, not a no-op
    assert rep.gates < rep.recorded_gates


def test_compile_cache_hits():
    a = ir.compile_op("fixed_add", 32)
    b = ir.compile_op("fixed_add", 32)
    assert a is b
    c = ir.compile_op("fixed_add", 32, passes=())
    assert c is not a and c.pass_log == ()


def test_backend_registry():
    assert ir.get_backend("pallas").name == "pallas"
    assert ir.backend_names() == ["cost", "interpreter", "pallas"]


# ------------------------------------------------------- new dtypes (int/bf16)

@pytest.mark.parametrize("nbits", [8, 16])
def test_fixed_add_small_widths_compiled(nbits):
    from repro.kernels import ops

    rng = np.random.default_rng(nbits)
    lo, hi = -(2 ** (nbits - 1)), 2 ** (nbits - 1)
    x = rng.integers(lo, hi, 300, dtype=np.int64).astype(np.int32)
    y = rng.integers(lo, hi, 300, dtype=np.int64).astype(np.int32)
    got = np.asarray(ops.pim_fixed_add(x, y, nbits=nbits))
    mask = (1 << nbits) - 1
    exp = (x.astype(np.int64) + y.astype(np.int64)) & mask
    exp = np.where(exp >= hi, exp - (1 << nbits), exp).astype(np.int32)
    assert np.array_equal(got, exp)


@pytest.mark.parametrize("nbits", [8, 16])
def test_fixed_mul_small_widths_compiled(nbits):
    from repro.kernels import ops

    rng = np.random.default_rng(nbits + 100)
    lo, hi = -(2 ** (nbits - 1)), 2 ** (nbits - 1)
    x = rng.integers(lo, hi, 300, dtype=np.int64).astype(np.int32)
    y = rng.integers(lo, hi, 300, dtype=np.int64).astype(np.int32)
    got = np.asarray(ops.pim_fixed_mul(x, y, nbits=nbits))
    mask = (1 << nbits) - 1
    exp = (x.astype(np.int64) * y.astype(np.int64)) & mask
    exp = np.where(exp >= hi, exp - (1 << nbits), exp).astype(np.int32)
    assert np.array_equal(got, exp)


def _bf16_cases(seed, n=1024):
    import ml_dtypes

    rng = np.random.default_rng(seed)
    x = rng.integers(0, 2**16, n, dtype=np.uint32).astype(np.uint16).view(ml_dtypes.bfloat16)
    sp = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, -1.0,
                   9.2e-41, 3.4e38, 1.18e-38], dtype=ml_dtypes.bfloat16)
    return np.concatenate([x, np.repeat(sp, len(sp))]), None


def _check_bf16(got, exp):
    import ml_dtypes

    gb = np.asarray(got).view(np.uint16)
    eb = np.asarray(exp, dtype=ml_dtypes.bfloat16).view(np.uint16)
    nan = np.isnan(np.asarray(got, np.float32)) & np.isnan(np.asarray(exp, np.float32))
    ok = (gb == eb) | nan
    assert ok.all(), f"{(~ok).sum()} bf16 mismatches"


@pytest.mark.parametrize("op", ["bf16_add", "bf16_mul"])
def test_bf16_bit_exact_through_pipeline(op):
    """bf16 add/mul through record→passes→Pallas(interpret), bit-exact vs the
    float64-exact computation rounded once to bf16 (RNE)."""
    import ml_dtypes

    from repro.kernels import ops

    x, _ = _bf16_cases(11)
    rng = np.random.default_rng(12)
    y = np.concatenate([
        rng.integers(0, 2**16, 1024, dtype=np.uint32).astype(np.uint16).view(ml_dtypes.bfloat16),
        np.tile(np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, -1.0,
                          9.2e-41, 3.4e38, 1.18e-38], dtype=ml_dtypes.bfloat16), 10),
    ])
    xj = jnp.asarray(x.view(np.uint16)).view(jnp.bfloat16)
    yj = jnp.asarray(y.view(np.uint16)).view(jnp.bfloat16)
    fn = ops.pim_bf16_add if op == "bf16_add" else ops.pim_bf16_mul
    got = np.asarray(fn(xj, yj))
    ex64 = (x.astype(np.float64) + y.astype(np.float64)) if op == "bf16_add" \
        else (x.astype(np.float64) * y.astype(np.float64))
    _check_bf16(got, ex64)


def test_bf16_simulate_cost():
    x = np.array([1.5, -2.0, 3.25], dtype=np.float32)
    y = np.array([0.5, 4.0, -1.25], dtype=np.float32)
    res, cost = simulate.bf16_add(x, y)
    assert np.allclose(np.asarray(res, np.float32), x + y)
    assert cost.gates == aritpim.count_gates(aritpim.bf16_add, 16, 16)
    assert 0 < cost.optimized_gates <= cost.gates
    # bf16 add is far cheaper than float32 add — the point of the new dtype
    assert cost.gates < aritpim.count_gates(aritpim.float_add, 32, 32)


# ------------------------------------------------------------- oracle parity

def test_simulate_cost_matches_ir_cost():
    """simulate's OpCost and the analytical backend report the same netlist."""
    _, cost = simulate.float_add(np.ones(3, np.float32), np.ones(3, np.float32))
    rep = ir.op_cost("float_add")
    assert cost.gates == rep.recorded_gates
    assert cost.optimized_gates == rep.gates
    assert cost.peak_cols == rep.num_cols


def test_netlist_gate_counts_keys():
    from repro.core.analyzer import netlist_gate_counts

    g = netlist_gate_counts()
    assert g["fixed32_add"] == 288
    assert set(g) >= {"fixed32_add", "fixed32_mul", "float32_add", "float32_mul"}


# --------------------------------------------------- multi-basis (MAJ3/NOT)

def _basis_nbits(op):
    """Width keeping the all-ops sweep fast: fixed ops at 8 bits, floats at
    their fixed format widths."""
    if op.startswith("fixed"):
        return 8
    return 16 if op.startswith("bf16") else 32


def _random_planes(n_planes, n_words, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.integers(0, 2**32, (n_planes, n_words), dtype=np.uint64).astype(np.uint32)
    )


@pytest.mark.parametrize("op", sorted(aritpim._OP_TABLE))
def test_all_ops_bit_exact_on_dram_basis(op):
    """Acceptance: every _OP_TABLE op compiles on the dram basis and the
    interpreter backend reproduces the execute-mode oracle bit-for-bit on
    random bit patterns (plane-level comparison, no decode)."""
    nbits = _basis_nbits(op)
    wa, wb, wout = aritpim.op_widths(op, nbits)
    n_words = 2
    planes = _random_planes(wa + wb, n_words, seed=sum(map(ord, op)))

    vm = PlaneVM(mode="execute", n_words=n_words)
    A = [planes[i] for i in range(wa)]
    B = [planes[wa + i] for i in range(wb)]
    exp = aritpim._OP_TABLE[op].builder(vm, A, B)
    assert len(exp) == wout
    exp = np.stack([np.asarray(p) for p in exp])

    compiled = ir.compile_op(op, nbits, basis="dram")
    assert compiled.basis == "dram"
    assert compiled.nor_gates == 0  # fully lowered out of the NOR basis
    got = np.asarray(ir.get_backend("interpreter").run(compiled, planes).planes)
    assert np.array_equal(got, exp), op


@pytest.mark.parametrize("nbits", [8, 16, 32])
def test_dram_fixed_add_maj_bound(nbits):
    """Acceptance: the ripple adder's MAJ3 count stays at or below the
    textbook majority-form full adder (3 MAJ per bit) — the FA rewrite fires
    instead of the naive per-NOR expansion."""
    rep = ir.op_cost("fixed_add", nbits, basis="dram")
    assert rep.maj_gates <= 3 * nbits, (rep.maj_gates, 3 * nbits)
    assert rep.gates == rep.maj_gates + rep.not_gates


@pytest.mark.parametrize("basis", ["memristive", "dram"])
@pytest.mark.parametrize("op", ["fixed_add", "fixed_mul", "float_add", "float_mul"])
def test_pipeline_invariants_both_bases(op, basis):
    """Acceptance: for both bases, passes never increase the native gate
    count relative to the pre-pass (basis-lowered) program, and peak columns
    stay within the unoptimized allocation and the crossbar budget."""
    pre = ir.record_op(op)
    if basis == "dram":
        pre = ir.lower_to_dram(pre)
    baseline = ir.lower(pre, basis=basis)
    compiled = ir.compile_op(op, basis=basis)
    assert compiled.native_gates <= pre.gate_count(basis)
    assert compiled.num_cols <= baseline.num_cols
    assert compiled.peak_rows <= 1024  # crossbar/subarray row budget
    assert compiled.meta["baseline_cols"] == baseline.num_cols


def test_dram_lowering_unit():
    """NOR(x', y') folds to MAJ(x, y, 0) and NOR(x, x) to NOT(x)."""
    vm = PlaneVM(mode="record")
    a, b = vm.input_plane(), vm.input_plane()
    na = vm.not_(a)
    nb = vm.not_(b)
    and_ab = vm.nor(na, nb)  # a AND b
    sched = vm.finish_schedule({"a": [a], "b": [b]}, {"out": [and_ab]})
    lowered = ir.dead_gate_elim(ir.lower_to_dram(ir.from_schedule(sched)))
    codes = [int(o) for o in lowered.ops[:, 0]]
    assert codes.count(OP_MAJ3) == 1
    assert OP_NOR not in codes


def test_dram_full_adder_rewrite_unit():
    """The recorded 9-NOR full adder lowers to 3 MAJ + 2 NOT."""
    vm = PlaneVM(mode="record")
    a, b, c = (vm.input_plane() for _ in range(3))
    s, co = vm.full_adder(a, b, c)
    sched = vm.finish_schedule({"a": [a], "b": [b], "c": [c]}, {"out": [s, co]})
    lowered = ir.lower_to_dram(ir.from_schedule(sched))
    codes = [int(o) for o in lowered.ops[:, 0]]
    assert codes.count(OP_MAJ3) == 3 and codes.count(OP_NOT) == 2
    assert OP_NOR not in codes


def test_maj3_execute_matches_interpreter():
    """PlaneVM.maj3 (execute) and the interpreter's OP_MAJ3 agree."""
    vm = PlaneVM(mode="record")
    a, b, c = (vm.input_plane() for _ in range(3))
    m = vm.maj3(a, b, c)
    sched = vm.finish_schedule({"a": [a], "b": [b], "c": [c]}, {"out": [m]})
    compiled = ir.lower(ir.from_schedule(sched), basis="dram")
    planes = _random_planes(3, 4, seed=5)
    got = np.asarray(ir.get_backend("interpreter").run(compiled, planes).planes[0])
    va, vb, vc = (np.asarray(planes[i]) for i in range(3))
    exp = (va & vb) | (va & vc) | (vb & vc)
    assert np.array_equal(got, exp)


def test_per_basis_compile_cache_distinct():
    m = ir.compile_op("fixed_add", 32)
    d = ir.compile_op("fixed_add", 32, basis="dram")
    assert m is not d
    assert m.basis == "memristive" and d.basis == "dram"
    assert d is ir.compile_op("fixed_add", 32, basis="dram")  # cached


def test_dram_cost_report_cycles():
    """Cost backend: dram cycles are the AAP/TRA weighted sum (5 per MAJ,
    2 per NOT, 1 per COPY/INIT), not schedule_len × cycles_per_gate."""
    rep = ir.op_cost("fixed_add", 32, basis="dram")
    compiled = ir.compile_op("fixed_add", 32, basis="dram")
    inits = compiled.num_gates - compiled.maj_gates - compiled.not_gates
    assert rep.basis == "dram"
    assert rep.cycles == 5 * rep.maj_gates + 2 * rep.not_gates + inits
    assert rep.peak_rows == compiled.num_cols + get_basis("dram").compute_rows
    assert rep.copy_aaps > 0
    # independently derived, but within 25% of the paper's clock-scaled
    # convention for the calibration op (576 cycles for fixed32_add)
    assert abs(rep.cycles - 576) / 576 < 0.25


def test_interpreter_and_pallas_agree_on_dram_basis():
    x, y = _f32_vec(257, 5), _f32_vec(257, 6)
    compiled = ir.compile_op("float_add", basis="dram")
    got_i = _run_f32(compiled, x, y, backend="interpreter")
    got_p = _run_f32(compiled, x, y, backend="pallas")
    assert np.array_equal(got_i.view(np.uint32), got_p.view(np.uint32))


# ------------------------------------------------- division (full pipeline)

@pytest.mark.parametrize("basis", ["memristive", "dram"])
@pytest.mark.parametrize("nbits", [8, 16])
def test_fixed_div_compiled_interpreter(nbits, basis):
    """fixed_div through the full compiled path (all default passes +
    interpreter backend), vs C truncation-toward-zero semantics."""
    rng = np.random.default_rng(nbits)
    lo, hi = -(2 ** (nbits - 1)), 2 ** (nbits - 1)
    x = rng.integers(lo, hi, 200, dtype=np.int64)
    y = rng.integers(lo, hi, 200, dtype=np.int64)
    y[y == 0] = 1  # divide-by-zero convention is tested at the oracle level
    planes = jnp.stack(
        bitplanes.int_to_planes(jnp.asarray(x.astype(np.int32)), nbits)
        + bitplanes.int_to_planes(jnp.asarray(y.astype(np.int32)), nbits)
    )
    compiled = ir.compile_op("fixed_div", nbits, basis=basis)
    out = ir.get_backend("interpreter").run(compiled, planes).planes
    got = np.asarray(
        bitplanes.planes_to_int([out[i] for i in range(nbits)], len(x), signed=True)
    )
    exp = (np.trunc(x / y)).astype(np.int64)
    exp = np.where(exp >= hi, exp - (1 << nbits), exp).astype(np.int32)  # INT_MIN/-1 wrap
    assert np.array_equal(got, exp)


@pytest.mark.parametrize("basis", ["memristive", "dram"])
def test_float_div_compiled_interpreter(basis):
    """float_div through the full compiled path, bit-exact vs IEEE division
    including specials (0, inf, nan, subnormals)."""
    rng = np.random.default_rng(9)
    x = rng.integers(0, 2**32, 64, dtype=np.uint64).astype(np.uint32).view(np.float32)
    y = rng.integers(0, 2**32, 64, dtype=np.uint64).astype(np.uint32).view(np.float32)
    sp = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, -1.0, 1e-40, 3.4e38],
                  dtype=np.float32)
    x = np.concatenate([x, np.repeat(sp, len(sp))])
    y = np.concatenate([y, np.tile(sp, len(sp))])
    compiled = ir.compile_op("float_div", basis=basis)
    got = _run_f32(compiled, x, y)
    with np.errstate(all="ignore"):
        exp = (x / y).astype(np.float32)
    _check_f32(got, exp)
