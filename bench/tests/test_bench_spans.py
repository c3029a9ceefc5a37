"""The reduction to the phases of each dispatch (``bench/spans.py``).

Checked on a small synthetic trace whose numbers are worked out by hand
below, and on traces recorded on a TPU v5e: one with the program's spans
(``data/va_i32_n65536_spans.xplane.pb.gz``) against values read from it
with ``jax.profiler.ProfileData``, and the older one without them, which
reads as a program that opens no spans.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest
from google.protobuf import text_format

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import reduce, spans, xplane  # noqa: E402

# Host spans (ns): warmup [0, 100]; dispatch [100, 300] holding pim.pack
# [105, 150], pim.execute [150, 200], pim.unpack [200, 290]; dispatch
# [300, 500] holding pim.pack [305, 360], pim.execute [360, 400], pim.unpack
# [400, 480]; check [500, 600].  Device ops (ns): a convert [40, 60] in the
# warm-up; dispatch 1: convert [130, 140], fusion.1 [155, 165], kernel k
# [210, 250], copy [260, 284]; dispatch 2: convert [320, 330], kernel k
# [370, 390], fusion.2 [392, 396] between two kernels, kernel k2 [400, 420],
# copy [430, 470]; a copy [520, 540] in the check.  XLA programs start at
# 35 (warm-up), 125, 205, 255, 315, 365, 425 and 515 (check).
_SPANS = [("warmup", 0, 100), ("dispatch", 100, 200), ("pim.pack", 105, 45),
          ("pim.execute", 150, 50), ("pim.unpack", 200, 90),
          ("dispatch", 300, 200), ("pim.pack", 305, 55),
          ("pim.execute", 360, 40), ("pim.unpack", 400, 80),
          ("check", 500, 100)]
_OPS = [("convert", 40, 20), ("convert", 130, 10), ("fusion.1", 155, 10),
        ("k", 210, 40), ("copy", 260, 24), ("convert", 320, 10),
        ("k", 370, 20), ("fusion.2", 392, 4), ("k2", 400, 20),
        ("copy", 430, 40), ("copy", 520, 20)]
_MODULES = [(f"jit_{i}", t, 1) for i, t in
            enumerate([35, 125, 205, 255, 315, 365, 425, 515])]
_MARK = 'custom_call_target="tpu_custom_call"'
_HLO = {"k": f"%k = u32[32,32768] custom-call(%p), {_MARK}",
        "k2": f"%k2 = u32[32,32768] custom-call(%p), {_MARK}"}


def _line(line_id, name, rows, names):
    events = " ".join(
        f"events {{ metadata_id: {names.index(n) + 1} "
        f"offset_ps: {start * 1000} duration_ps: {dur * 1000} }}"
        for n, start, dur in rows)
    return (f'lines {{ id: {line_id} name: "{name}" timestamp_ns: 0 '
            f'{events} }}')


def _meta(names):
    return " ".join(f"event_metadata {{ key: {i + 1} value {{ id: {i + 1} "
                    f"name: {json.dumps(_HLO.get(n, n))} }} }}"
                    for i, n in enumerate(names))


def _synthetic(host_spans=_SPANS):
    dev = sorted({r[0] for r in _OPS + _MODULES})
    host = sorted({r[0] for r in host_spans})
    text = f"""
    planes {{ id: 3 name: "/device:CUSTOM:Megascale Trace" }}
    planes {{ id: 1 name: "/device:TPU:0"
      {_line(1, "XLA Modules", _MODULES, dev)}
      {_line(2, "XLA Ops", _OPS, dev)} {_meta(dev)} }}
    planes {{ id: 2 name: "/host:CPU"
      {_line(1, "python", host_spans, host)} {_meta(host)} }}
    """
    return xplane.parse(
        text_format.Parse(text, xplane.XSpace()).SerializeToString())


def test_spans_of_a_synthetic_trace():
    space = _synthetic()
    s = spans.summarize(space)
    assert s.dispatches == 2
    assert s.kernels == 3
    # Host: pim.pack 45 + 55, pim.execute 50 + 40, pim.unpack 90 + 80.
    assert s.host_s == {"pim.pack": pytest.approx(100e-9),
                        "pim.execute": pytest.approx(90e-9),
                        "pim.unpack": pytest.approx(170e-9)}
    # Device: before the first kernel 10 + 10 and 10; after the last 24 and
    # 40; fusion.2 between; the warm-up's and the check's ops in none.
    assert s.pack_s == pytest.approx(30e-9)
    assert s.unpack_s == pytest.approx(64e-9)
    assert s.between_s == pytest.approx(4e-9)
    assert s.programs == 6
    # Gaps in the window [100, 500], by the innermost span at the midpoint:
    # [100, 130] [140, 155] [330, 370] pim.pack; [165, 210] [390, 392]
    # [396, 400] pim.execute; [250, 260] [420, 430] pim.unpack; [284, 320]
    # (midpoint 302) and [470, 500] dispatch.
    assert s.idle_s == {"pim.pack": pytest.approx(85e-9),
                        "pim.execute": pytest.approx(51e-9),
                        "pim.unpack": pytest.approx(20e-9),
                        "dispatch": pytest.approx(66e-9)}
    t = reduce.summarize(space)
    assert s.pack_s + s.between_s + s.unpack_s == pytest.approx(t.outside_s)
    assert sum(s.idle_s.values()) == pytest.approx(t.window_s - t.busy_s)
    assert spans.metrics(s) == {
        "host_pack_ms": pytest.approx(50e-6),
        "host_execute_ms": pytest.approx(45e-6),
        "host_unpack_ms": pytest.approx(85e-6),
        "pack_ms": pytest.approx(15e-6),
        "unpack_ms": pytest.approx(32e-6),
        "pack_idle_ms": pytest.approx(42.5e-6),
        "unpack_idle_ms": pytest.approx(10e-6),
        "programs_per_dispatch": 3.0,
    }
    device, idle = (line.split(": ") for line in spans.report(s))
    assert device[0] == "spans" and idle[0] == "idle_by_span"
    assert _fields(device[1]) == {
        "dispatches": 2, "programs": 6, "pack_ms": pytest.approx(15e-6),
        "between_ms": pytest.approx(2e-6), "unpack_ms": pytest.approx(32e-6),
        "pim.pack": pytest.approx(50e-6), "pim.execute": pytest.approx(45e-6),
        "pim.unpack": pytest.approx(85e-6)}
    # In the order of the spans: the program's, then the harness's.
    assert list(_fields(idle[1]).items()) == [
        ("pim.pack", pytest.approx(42.5e-6)),
        ("pim.execute", pytest.approx(25.5e-6)),
        ("pim.unpack", pytest.approx(10e-6)),
        ("dispatch", pytest.approx(33e-6))]


def _fields(text):
    return {k: float(v) for k, v in (f.split("=") for f in text.split())}


# Recorded on a TPU v5e: the VA cell at 65,536 elements, one traced dispatch
# (``run_cell(..., trace_dir=...)``), pruned as ``data/va_i32_n65536``'s
# trace was (the device planes without the ops' source locations, and on
# the host only the harness's and the program's spans).  Read with
# ``jax.profiler.ProfileData``, apart from this module: the one dispatch
# span holds pim.pack 176.155016 ms, pim.execute 7.833579 ms and pim.unpack
# 147.979598 ms, and 1018 ``XLA Modules`` events; its one kernel is
# ``pim_segment_0.1``; the other ops starting before it take 0.55555 ms, those
# after it 0.406739 ms; its idle gaps, by the innermost span at the
# midpoint, 175.745893 ms under pim.pack, 8.077202 ms under pim.execute and
# 147.228751 ms under pim.unpack.
RECORDED = ROOT / "bench" / "tests" / "data" / "va_i32_n65536_spans.xplane.pb.gz"


def test_spans_of_a_recorded_trace():
    space = reduce.load(RECORDED)
    s = spans.summarize(space)
    assert s.dispatches == 1 and s.kernels == 1 and s.programs == 1018
    assert s.host_s == {"pim.pack": pytest.approx(0.176155016, rel=1e-6),
                        "pim.execute": pytest.approx(0.007833579, rel=1e-6),
                        "pim.unpack": pytest.approx(0.147979598, rel=1e-6)}
    assert s.pack_s == pytest.approx(0.55555e-3, rel=1e-3)
    assert s.unpack_s == pytest.approx(0.406739e-3, rel=1e-3)
    assert s.between_s == 0
    assert s.idle_s == {"pim.pack": pytest.approx(0.175745893, rel=1e-5),
                        "pim.execute": pytest.approx(0.008077202, rel=1e-5),
                        "pim.unpack": pytest.approx(0.147228751, rel=1e-5)}
    assert [o.name for o in reduce.device_ops(space)
            if o.kernel][-1] == "pim_segment_0.1"
    t = reduce.summarize(space)
    assert s.pack_s + s.unpack_s == pytest.approx(t.outside_s, rel=1e-9)
    assert sum(s.host_s.values()) <= t.window_s


def test_spans_without_program_spans():
    """A program that opens no spans (as before they were added) gives no
    host or idle-by-span readings, and the device readings still."""
    bare = [r for r in _SPANS if not r[0].startswith("pim.")]
    m = spans.metrics(spans.summarize(_synthetic(bare)))
    assert m == {"pack_ms": pytest.approx(15e-6),
                 "unpack_ms": pytest.approx(32e-6),
                 "programs_per_dispatch": 3.0}
    old = reduce.load(ROOT / "bench" / "tests" / "data"
                      / "va_i32_n65536.xplane.pb.gz")
    assert sorted(spans.metrics(spans.summarize(old))) == [
        "pack_ms", "programs_per_dispatch", "unpack_ms"]


def test_spans_need_the_window_span():
    with pytest.raises(ValueError, match="dispatch"):
        spans.summarize(xplane.XSpace())
