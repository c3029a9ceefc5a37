"""The benchmark harness on the CPU, at tiny sizes.

The run command refuses to run without a TPU or on a device kind the peaks
table lacks; cells, configurations and per-layer metrics are found by name;
and a run whose timed path is broken, or which has the control in the
program's place, comes out not correct.  Only the int32 VA program runs
through ``pim.compile`` here (one 130-gate segment in interpret mode): the
f32 MAC's schedule is compiled, but its dispatch is the numpy reference,
broken or not, so no f32 kernel runs in interpret mode.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench import run as run_cmd  # noqa: E402

SMALL = 4096  # elements: holds the f32 specials head (12^3 = 1728)
SEED = 2 ** 31 + 12345  # larger than 32 signed bits hold


def _small(name: str, bench: Path = harness.BENCH) -> harness.Cell:
    return dataclasses.replace(harness.load_cell(name, bench), elements=SMALL)


def _run(cell, seed=SEED, seconds=0.3, **kw):
    return harness.run_cell(cell, seed, seconds, False,
                            benchmark=harness.load_benchmark(),
                            peaks=harness.load_peaks("TPU v5 lite"),
                            t_process=time.perf_counter(), **kw)


def _host_reference(cell):
    """The numpy reference put in the program's place."""
    def dispatch(*arrays):
        return jnp.asarray(cell.config.reference(*map(np.asarray, arrays)))
    return lambda _program: dispatch


# ------------------------------------------------------------------ refusal


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "va_i32.dram.n24",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "needs a TPU" in out.stderr


@dataclasses.dataclass
class _FakeDevice:
    device_kind: str
    platform: str = "tpu"


def test_run_refuses_unknown_device_kind(monkeypatch, capsys):
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice("TPU v99")])
    rc = run_cmd.main(["--workload", "va_i32.dram.n24", "--seed", "1",
                       "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "TPU v99" in out.err


def test_peaks_missing_kind_is_an_error():
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.load_peaks("TPU v99")
    v5e = harness.load_peaks("TPU v5 lite")
    clock = v5e["bf16_flops_per_s"] / (4 * 128 * 128 * 2)
    assert v5e["vector_word_ops_per_s"] >= 8 * 128 * 4 * clock  # rounded up


# ------------------------------------------------------------------ data


def test_benchmark_names_existing_files():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config_name == w["config"]
        spec = json.loads((harness.BENCH / "cells" / f"{w['name']}.json")
                          .read_text())
        assert (spec["traffic"], spec["why"]) == (w["traffic"], w["why"])
    for m in bench["per_layer"]:
        assert callable(harness.load_metric(m["name"]).read)
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_inputs_follow_the_seed():
    cell = _small("mac_f32.memristive.b8")
    a = harness.make_input_sets(cell, SEED)
    b = harness.make_input_sets(cell, SEED)
    c = harness.make_input_sets(cell, -3)
    assert len(a) == 2 and all(len(s) == 3 for s in a)
    for x, y in zip(a[0], b[0]):
        assert x.tobytes() == y.tobytes()
    assert a[0][0].tobytes() != a[1][0].tobytes()  # the two sets differ
    assert a[0][0].tobytes() != c[0][0].tobytes()
    assert [x.shape for s in c for x in s] == [(SMALL,)] * 6


def test_new_files_are_found_without_code_edits(tmp_path):
    """A cell, a configuration and a per-layer metric dropped into a copy
    of the benchmark are found by name."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(bench / "configs" / "prim_va_int32.py",
                bench / "configs" / "va_copy.py")
    (bench / "cells" / "va_copy.tiny.json").write_text(json.dumps({
        "config": "va_copy", "traffic": "tiny", "basis": "dram",
        "elements": SMALL, "input_sets": 2, "why": "test"}))
    (bench / "metrics" / "twice_gates.py").write_text(
        "def read(ctx):\n    return 2 * ctx.compiled.num_gates\n")
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    benchmark["workloads"].append({"name": "va_copy.tiny", "config": "va_copy",
                                   "traffic": "tiny", "chips": 1, "why": "t"})
    benchmark["per_layer"].append({
        "name": "twice_gates", "unit": "gates", "better": "lower",
        "source": "program_counter", "layer": "compiler",
        "moves": "elems_per_s", "workloads": ["va_copy.tiny"]})

    cell = harness.load_cell("va_copy.tiny", bench)
    assert cell.config.__file__ == str(bench / "configs" / "va_copy.py")
    result = harness.run_cell(cell, 5, 0.2, False, benchmark=benchmark,
                              peaks=harness.load_peaks("TPU v5 lite", bench),
                              t_process=time.perf_counter())
    assert result["correct"], result
    assert set(result["metrics"]) == {"elems_per_s", "dispatch_ms_p90",
                                      "setup_s"}

    _, compiled, cost, _, n_args, n_outputs = harness.build_dispatch(cell)
    ctx = harness.Context(cell=cell, compiled=compiled, cost=cost,
                          trace_passes_s=0.5, n_args=n_args,
                          n_outputs=n_outputs, peaks={})
    assert harness.read_per_layer(benchmark, ctx) == {
        "twice_gates": {"value": 260, "unit": "gates"}}


# ------------------------------------------------------------------ correct


def test_va_program_agrees_with_reference():
    """The generator, the numpy reference and the comparison agree with
    ``pim.compile`` at a tiny size, and the result line has its keys."""
    result = _run(_small("va_i32.dram.n24"))
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["checks"] == {"mismatched_elements": {"value": 0,
                                                        "limit": 0}}


def _altered(dispatch):
    """One bit of one answer altered where it is produced."""
    def f(*xs):
        out = dispatch(*xs)
        bits = jax.lax.bitcast_convert_type(out, jnp.uint32)
        bits = bits.at[SMALL // 2].set(bits[SMALL // 2] ^ jnp.uint32(1 << 7))
        return jax.lax.bitcast_convert_type(bits, out.dtype)
    return f


def _half_left_out(dispatch):
    """Only the first half of the elements computed; the rest left zero."""
    def f(*xs):
        half = dispatch(*(x[:SMALL // 2] for x in xs))
        return jnp.concatenate([half, jnp.zeros_like(half)])
    return f


def _unchanged(dispatch):
    """The last operand handed back unchanged, as a program that returns
    its state untouched."""
    return lambda *xs: xs[-1]


FAULTS = {"altered": _altered, "half_left_out": _half_left_out,
          "unchanged": _unchanged}


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_va_program_is_not_correct(fault):
    result = _run(_small("va_i32.dram.n24"), wrap=FAULTS[fault])
    assert not result["correct"], result
    assert result["failed"] > 0
    assert result["checks"]["mismatched_elements"]["value"] > 0


@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_broken_reference_in_mac_cell_is_not_correct(fault):
    """The f32 MAC cell driven with the numpy reference in the program's
    place reads correct; each planted fault reads not correct."""
    cell = _small("mac_f32.dram.b8")
    ref = _host_reference(cell)
    wrap = ref if fault is None else (lambda p: FAULTS[fault](ref(p)))
    result = _run(cell, wrap=wrap)
    assert result["correct"] is (fault is None), result


@pytest.mark.parametrize("cell", ["mac_f32.memristive.b8", "va_i32.dram.n24"])
def test_control_is_not_correct(cell):
    """The control, the reference one precision lower, in the program's
    place, fails the comparison on several seeds."""
    cell = _small(cell)
    control = jax.jit(cell.config.control)
    for seed in (1, 2, SEED):
        result = _run(cell, seed=seed, seconds=0.1, wrap=lambda _: control)
        assert not result["correct"], result
        assert result["checks"]["mismatched_elements"]["value"] > 0
