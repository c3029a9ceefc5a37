"""The trace reduction and the executor's work counts.

The reduction is checked on a small synthetic trace whose numbers are worked
out by hand below, and on a trace recorded on a TPU v5e (one dispatch of
the VA program, ``data/``) against values read from it apart from the
reduction.  The work
counts are checked against the compiler's ``CostReport`` at tiny sizes.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest
from google.protobuf import text_format

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, reduce, xplane  # noqa: E402

# Host spans (ns): warmup [0, 100], dispatch [100, 300] and [300, 500],
# check [500, 600].  Device ops (ns): a non-kernel op [90, 110] across the
# window's start, kernel [120, 220], fusion [230, 260], kernel [310, 400],
# copy [410, 450], one of XLA's own custom calls [460, 470], which is no
# kernel, and a copy [520, 560] after the window.  The kernels are marked
# by their HLO text, kept as their name.  A device plane with no op line
# sorts before the TPU's, as on a v5e.
_SPANS = [("warmup", 0, 100), ("dispatch", 100, 200), ("dispatch", 300, 200),
          ("check", 500, 100)]
_OPS = [("convert", 90, 20), ("k", 120, 100), ("fusion.1", 230, 30),
        ("k2", 310, 90), ("copy", 410, 40), ("concat", 460, 10),
        ("copy", 520, 40)]
_MARK = 'custom_call_target="tpu_custom_call"'
_HLO = {"k": f"%k = u32[32,32768] custom-call(%p), {_MARK}",
        "k2": f"%k2 = u32[32,32768] custom-call(%p), {_MARK}",
        "concat": '%concat = u32[16,8] custom-call(%a, %b), '
                  'custom_call_target="ConcatBitcast"'}
_STATS = {"convert": 'str_value: "convert"', "k": 'str_value: "custom-call"',
          "fusion.1": 'str_value: "loop fusion"',
          "concat": 'str_value: "custom-call"'}


def _events(rows, stats):
    names = sorted({r[0] for r in rows})
    out = [f"events {{ metadata_id: {names.index(n) + 1} offset_ps: "
           f"{start * 1000} duration_ps: {dur * 1000} "
           + (f"stats {{ metadata_id: 1 {stats[n]} }}" if n in stats else "")
           + " }" for n, start, dur in rows]
    meta = [f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
            f'name: {json.dumps(_HLO.get(n, n))} }} }}'
            for i, n in enumerate(names)]
    return " ".join(out), " ".join(meta)


def _space(text):
    return xplane.parse(
        text_format.Parse(text, xplane.XSpace()).SerializeToString())


def _synthetic():
    ops, ops_meta = _events(_OPS, _STATS)
    spans, spans_meta = _events(_SPANS, {})
    return _space(f"""
    planes {{ id: 3 name: "/device:CUSTOM:Megascale Trace" }}
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ops} }}
      lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
        events {{ metadata_id: 1 offset_ps: 0 duration_ps: 600000 }} }}
      {ops_meta}
      stat_metadata {{ key: 1 value {{ id: 1 name: "hlo_category" }} }} }}
    planes {{ id: 2 name: "/host:CPU"
      lines {{ id: 1 name: "python" timestamp_ns: 0 {spans} }}
      {spans_meta} }}
    """)


def test_reduction_of_a_synthetic_trace():
    s = reduce.summarize(_synthetic())
    assert s.dispatches == 2
    assert s.window_s == pytest.approx(400e-9)
    # In the window [100, 500]: kernels 100 + 90; the rest 10 + 30 + 40 + 10.
    assert s.kernel_s == pytest.approx(190e-9)
    assert s.outside_s == pytest.approx(90e-9)
    assert s.busy_s == pytest.approx(280e-9)
    assert s.idle_share == pytest.approx(1 - 280 / 400)
    assert s.device_ops == [["k", pytest.approx(100e-9)],
                            ["k2", pytest.approx(90e-9)],
                            ["copy", pytest.approx(40e-9)],
                            ["fusion.1", pytest.approx(30e-9)],
                            ["convert", pytest.approx(10e-9)],
                            ["concat", pytest.approx(10e-9)]]
    # Gaps over [0, 600]: [0, 90] warmup, [110, 120], [220, 230], [260, 310],
    # [400, 410], [450, 460], [470, 520] dispatch, [560, 600] check.
    assert s.idle_gaps == [["warmup", pytest.approx(90e-9)],
                           ["dispatch", pytest.approx(50e-9)],
                           ["dispatch", pytest.approx(50e-9)],
                           ["check", pytest.approx(40e-9)],
                           ["dispatch", pytest.approx(10e-9)],
                           ["dispatch", pytest.approx(10e-9)],
                           ["dispatch", pytest.approx(10e-9)],
                           ["dispatch", pytest.approx(10e-9)]]


# Recorded on a TPU v5e: the VA cell at 65,536 elements, one traced dispatch
# (``run_cell(..., trace_dir=...)``), pruned to the planes and events the
# reduction reads (the device planes, and the harness's spans on the host)
# and without the ops' source locations.  The values below were read with
# ``jax.profiler.ProfileData``, apart from this module: the window is the one
# ``dispatch`` span; the one op whose HLO names ``tpu_custom_call`` is
# ``_unrolled_segment.1``; 1199 ops run in the window.
RECORDED = ROOT / "bench" / "tests" / "data" / "va_i32_n65536.xplane.pb.gz"


def test_reduction_of_a_recorded_trace():
    space = reduce.load(RECORDED)
    s = reduce.summarize(space)
    assert s.dispatches == 1
    assert s.window_s == pytest.approx(0.338748587, rel=1e-6)
    assert s.kernel_s == pytest.approx(4.199e-06, rel=1e-3)
    assert s.outside_s == pytest.approx(0.000963843, rel=1e-3)
    assert 0.99 < s.idle_share < 1
    ops = [o for o in reduce.device_ops(space)
           if o.end_ns > 694270420 and o.start_ns < 1033019007]
    assert len(ops) == 1199
    assert [o.name for o in ops if o.kernel] == ["_unrolled_segment.1"]


def test_reduction_needs_the_window_span():
    with pytest.raises(ValueError, match="dispatch"):
        reduce.summarize(_space('planes { id: 1 name: "/device:TPU:0" }'))


# ------------------------------------------------------------------ work


@pytest.mark.parametrize("cell,n", [("va_i32.dram.n24", 4096),
                                    ("va_i32.dram.n24", 1000),
                                    ("mac_f32.memristive.b8", 96),
                                    ("mac_f32.dram.b8", 33)])
def test_work_counts_match_cost_report(cell, n):
    import repro.pim as pim
    from repro.core.machine import OP_MAJ3, OP_NOR, OP_NOT

    cell = harness.load_cell(cell)
    fn = pim.compile(cell.config.program, dtype=getattr(pim, cell.config.DTYPE))
    cost = fn.cost(basis=cell.basis)
    compiled = fn.compiled(basis=cell.basis)
    logic = sum(int((compiled.ops[:, 0] == op).sum())
                for op in (OP_NOR, OP_MAJ3, OP_NOT))
    assert cost.gates == logic  # INIT and COPY rows are not logic gates
    words = -(-n // 32)
    assert reduce.words(n) == words
    assert reduce.word_ops(cost.gates, n) == logic * words
    n_args, n_out = len(fn.in_types), len(fn.out_types)
    # 32-bit elements: the user arrays' bytes are the boundary planes'.
    assert reduce.io_bytes(32 * words, n_args, n_out) == \
        cost.hbm_planes * words * 4
    assert reduce.io_bytes(n, n_args, n_out) == n * 4 * (n_args + n_out)


def test_least_time_names_its_bound():
    peaks = harness.load_peaks("TPU v5 lite")
    # VA at 2^24: 128 logic gates x 2^19 words is far below 12 B x 2^24.
    t, bound = reduce.least_time(128, 2 ** 24, 2, 1, peaks)
    assert bound == "bytes"
    assert t == pytest.approx(2 ** 24 * 12 / 819e9)
    t, bound = reduce.least_time(12968, 6422528, 3, 1, peaks)
    assert bound == "ops"
    assert t == pytest.approx(12968 * 200704 / peaks["vector_word_ops_per_s"])
