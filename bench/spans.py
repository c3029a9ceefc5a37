"""Reduction of a traced window to the phases of each dispatch: the host
spans the program opens in ``CompiledPimFunction.__call__``, the device
time before and after the executor kernels, and idle time by span.

It reads the same ``XSpace`` as ``reduce.summarize``, with its helpers, and
leaves that reduction as it is.  Per ``dispatch`` span, every device op
that starts inside the span belongs to that dispatch: ``block_until_ready``
ends each span, and the device runs one stream in order.  Within a
dispatch, the ops before the first executor kernel are pack (and the
unrolled executor's placement), the ops after the last kernel are unpack
(gather, trim and unpack), and any op between two kernels is counted apart.
Each idle gap of the window is labelled with the innermost span open at its
midpoint: a program span where one is open, else the harness's.
"""

from __future__ import annotations

import bisect
import dataclasses

from bench import reduce

# The program's span names, copied here rather than imported: a later
# rename in the program makes the readings below absent, not silently moved.
PACK = "pim.pack"
EXECUTE = "pim.execute"
UNPACK = "pim.unpack"
PROGRAM_SPANS = (PACK, EXECUTE, UNPACK)
# The line of a device plane that holds one event per executed XLA program.
MODULES_LINE = "XLA Modules"
NO_SPAN = "none"


@dataclasses.dataclass
class SpanSummary:
    dispatches: int
    host_s: dict       # program span -> host seconds in it, over the window
    pack_s: float      # device time before each dispatch's first kernel
    between_s: float   # device time of non-kernel ops between two kernels
    unpack_s: float    # device time after each dispatch's last kernel
    kernels: int       # executor kernels run in the window
    idle_s: dict       # innermost span open -> idle seconds in the window
    programs: int      # XLA programs started inside the dispatch spans

    def ms(self, seconds: float) -> float:
        """Milliseconds per dispatch."""
        return seconds * 1e3 / self.dispatches


def _op_plane(space):
    """The device plane ``reduce.device_ops`` reads."""
    planes = sorted((p for p in space.planes if p.name.startswith("/device:")
                     and any(line.name == reduce.OPS_LINE
                             for line in p.lines)),
                    key=lambda p: p.name)
    return planes[0] if planes else None


def summarize(space) -> SpanSummary:
    spans = reduce.host_spans(space, names=reduce.SPANS + PROGRAM_SPANS)
    window = [(s, e) for n, s, e in spans if n == reduce.WINDOW_SPAN]
    if not window:
        raise ValueError(f"the trace holds no {reduce.WINDOW_SPAN!r} span")
    w0, w1 = window[0][0], window[-1][1]
    ops = reduce.device_ops(space)
    starts = [o.start_ns for o in ops]

    def inside(s, e, times):
        return slice(bisect.bisect_left(times, s), bisect.bisect_left(times, e))

    pack = between = unpack = 0.0
    kernels = 0
    for s, e in window:
        mine = ops[inside(s, e, starts)]
        ks = [o for o in mine if o.kernel]
        kernels += len(ks)
        first = ks[0].start_ns if ks else float("inf")
        last = max((k.end_ns for k in ks), default=float("inf"))
        for o in mine:
            if o.kernel:
                continue
            if o.start_ns < first:
                pack += o.end_ns - o.start_ns
            elif o.start_ns >= last:
                unpack += o.end_ns - o.start_ns
            else:
                between += o.end_ns - o.start_ns

    host: dict[str, float] = {}
    window_starts = [s for s, _ in window]
    for n, s, e in spans:
        i = bisect.bisect_right(window_starts, s) - 1
        if n in PROGRAM_SPANS and i >= 0 and s < window[i][1]:
            host[n] = host.get(n, 0.0) + (e - s) * 1e-9

    idle: dict[str, float] = {}
    cursor = w0
    gaps = []
    for s, e in reduce._union([(o.start_ns, o.end_ns) for o in ops]):
        if s > cursor:
            gaps.append((cursor, min(s, w1)))
        cursor = max(cursor, e)
        if cursor >= w1:
            break
    if cursor < w1:
        gaps.append((cursor, w1))
    for s, e in gaps:
        if e <= s:
            continue
        mid = (s + e) / 2
        open_ = [(a, n) for n, a, b in spans if a <= mid <= b]
        label = max(open_)[1] if open_ else NO_SPAN  # the latest to open
        idle[label] = idle.get(label, 0.0) + (e - s) * 1e-9

    plane = _op_plane(space)
    module_starts = sorted(
        start for _, start, _, _ in reduce._events(plane, MODULES_LINE)
    ) if plane is not None else []
    programs = sum(len(module_starts[inside(s, e, module_starts)])
                   for s, e in window)
    return SpanSummary(
        dispatches=len(window), host_s=host, pack_s=pack * 1e-9,
        between_s=between * 1e-9, unpack_s=unpack * 1e-9, kernels=kernels,
        idle_s=idle, programs=programs)


def metrics(s: SpanSummary) -> dict[str, float]:
    """The per-dispatch readings, by metric name.  A reading with nothing to
    read is left out: the host spans and idle time by span where the program
    opens no spans, pack and unpack where no kernel ran, the program count
    where the trace has no ``XLA Modules`` line."""
    out = {}
    if all(n in s.host_s for n in PROGRAM_SPANS):
        out.update(host_pack_ms=s.ms(s.host_s[PACK]),
                   host_execute_ms=s.ms(s.host_s[EXECUTE]),
                   host_unpack_ms=s.ms(s.host_s[UNPACK]),
                   pack_idle_ms=s.ms(s.idle_s.get(PACK, 0.0)),
                   unpack_idle_ms=s.ms(s.idle_s.get(UNPACK, 0.0)))
    if s.kernels:
        out.update(pack_ms=s.ms(s.pack_s), unpack_ms=s.ms(s.unpack_s))
    if s.programs:
        out["programs_per_dispatch"] = s.programs / s.dispatches
    return out


def report(s: SpanSummary) -> list[str]:
    """Lines for standard error: device time by phase, and idle time by the
    span open, each in milliseconds per dispatch."""
    device = [f"dispatches={s.dispatches}", f"programs={s.programs}",
              f"pack_ms={s.ms(s.pack_s)!r}",
              f"between_ms={s.ms(s.between_s)!r}",
              f"unpack_ms={s.ms(s.unpack_s)!r}"]
    host = [f"{n}={s.ms(s.host_s[n])!r}" for n in PROGRAM_SPANS
            if n in s.host_s]
    order = PROGRAM_SPANS + reduce.SPANS + (NO_SPAN,)
    idle = [f"{n}={s.ms(s.idle_s[n])!r}" for n in order if n in s.idle_s]
    return ["spans: " + " ".join(device + host),
            "idle_by_span: " + " ".join(idle)]
