"""Reduction of a profiler trace (``.xplane.pb``) to the per-layer numbers,
and the work counts of the executor's roofline.

Device time is split into the executor kernels and everything else.  A
kernel is found as a Mosaic custom call: its HLO text, which the profiler
keeps as the op's name, names the target ``tpu_custom_call``.  It is not
found by its function name, so a renamed kernel is still found; and not by
the HLO category ``custom-call``, which XLA's own custom calls
(``ConcatBitcast``) share.  The traced steady window runs from the start
of the first ``dispatch`` host span to the end of the last; per-dispatch
numbers divide by the spans in it.
"""

from __future__ import annotations

import dataclasses
import gzip
import math
from pathlib import Path

from bench import xplane

# Host spans the harness opens, in the order they come.
SPANS = ("warmup", "dispatch", "check")
WINDOW_SPAN = "dispatch"
TOP = 10  # entries of each breakdown list
# The text in an op's HLO that marks it as a Mosaic kernel.
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
# The line of a device plane that holds one event per executed op.
OPS_LINE = "XLA Ops"


def load(path):
    """The ``XSpace`` of an ``.xplane.pb`` file (gzipped if ``.gz``)."""
    data = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        data = gzip.decompress(data)
    return xplane.parse(data)


@dataclasses.dataclass(frozen=True)
class DeviceOp:
    name: str
    start_ns: float
    end_ns: float
    kernel: bool


@dataclasses.dataclass
class TraceSummary:
    dispatches: int
    window_s: float
    busy_s: float      # union of device-op intervals in the window
    kernel_s: float    # device time of the executor kernels in the window
    outside_s: float   # device time of every other device op in the window
    device_ops: list   # [[name, seconds], ...] most time first
    idle_gaps: list    # [[host span open then, seconds], ...] longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _events(plane, line_name=None):
    """``(name, start_ns, end_ns, metadata)`` of every event (of the lines
    called ``line_name``, if given)."""
    for line in plane.lines:
        if line_name is not None and line.name != line_name:
            continue
        for ev in line.events:
            meta = plane.event_metadata[ev.metadata_id]
            start = line.timestamp_ns + ev.offset_ps / 1000
            yield meta.name, start, start + ev.duration_ps / 1000, meta


def _short_name(meta) -> str:
    """The op's HLO name (``_run.1``, ``fusion.15``), not its whole text."""
    return meta.display_name or meta.name.split(" = ")[0].lstrip("%")


def host_spans(space, names=SPANS) -> list[tuple[str, float, float]]:
    """The harness's own spans, ``(name, start_ns, end_ns)``, by start."""
    spans = [(name, s, e) for plane in space.planes
             if plane.name.startswith("/host:")
             for name, s, e, _ in _events(plane) if name in names]
    return sorted(spans, key=lambda x: x[1])


def device_ops(space) -> list[DeviceOp]:
    """Every op event of the first device plane that runs ops, by start.
    Planes such as ``/device:CUSTOM:Megascale Trace`` hold no op line."""
    planes = sorted((p for p in space.planes if p.name.startswith("/device:")
                     and any(line.name == OPS_LINE for line in p.lines)),
                    key=lambda p: p.name)
    ops = [DeviceOp(_short_name(meta), s, e, KERNEL_MARK in name)
           for plane in planes[:1]
           for name, s, e, meta in _events(plane, OPS_LINE)]
    return sorted(ops, key=lambda o: o.start_ns)


def _union(intervals):
    """Merged, sorted intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def summarize(space) -> TraceSummary:
    spans = host_spans(space)
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not window:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = window[0][0], window[-1][1]
    ops = device_ops(space)
    if not ops:
        raise ValueError("the trace holds no device op")

    def clip(op):
        return max(op.start_ns, w0), min(op.end_ns, w1)

    kernel = outside = 0.0
    per_name: dict[str, float] = {}
    in_window = []
    for op in ops:
        s, e = clip(op)
        if e <= s:
            continue
        in_window.append((s, e))
        if op.kernel:
            kernel += e - s
        else:
            outside += e - s
        per_name[op.name] = per_name.get(op.name, 0.0) + (e - s)
    busy = sum(e - s for s, e in _union(in_window))

    # Idle gaps over the whole traced span, each labelled with the host span
    # open at its midpoint.
    t0, t1 = spans[0][1], spans[-1][2]
    gaps, cursor = [], t0
    for s, e in _union([(o.start_ns, o.end_ns) for o in ops]):
        if s > cursor:
            gaps.append((cursor, min(s, t1)))
        cursor = max(cursor, e)
    if cursor < t1:
        gaps.append((cursor, t1))
    labelled = []
    for s, e in gaps:
        if e <= s:
            continue
        mid = (s + e) / 2
        label = next((n for n, a, b in spans if a <= mid <= b), "none")
        labelled.append([label, (e - s) * 1e-9])
    labelled.sort(key=lambda g: -g[1])
    top_ops = sorted(([n, t * 1e-9] for n, t in per_name.items()),
                     key=lambda x: -x[1])
    return TraceSummary(
        dispatches=len(window), window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9,
        kernel_s=kernel * 1e-9, outside_s=outside * 1e-9,
        device_ops=top_ops[:TOP], idle_gaps=labelled[:TOP])


# ------------------------------------------------------------------ work


def words(elements: int) -> int:
    """32-row words per bit-plane: ceil(N / 32)."""
    return math.ceil(elements / 32)


def word_ops(logic_gates: int, elements: int) -> int:
    """Bitwise word-ops of one dispatch: each logic gate (NOR, MAJ3, NOT;
    INIT and COPY count 0) once per word."""
    return logic_gates * words(elements)


def io_bytes(elements: int, n_args: int, n_outputs: int,
             bytes_per_elem: int = 4) -> int:
    """HBM bytes of the user arrays in and out of one dispatch."""
    return elements * bytes_per_elem * (n_args + n_outputs)


def least_time(logic_gates: int, elements: int, n_args: int, n_outputs: int,
               peaks: dict) -> tuple[float, str]:
    """The least seconds one dispatch could take on the chip, and which
    bound (``ops`` or ``bytes``) sets it."""
    ops_s = word_ops(logic_gates, elements) / peaks["vector_word_ops_per_s"]
    bytes_s = io_bytes(elements, n_args, n_outputs) / peaks["hbm_bytes_per_s"]
    return (ops_s, "ops") if ops_s >= bytes_s else (bytes_s, "bytes")
