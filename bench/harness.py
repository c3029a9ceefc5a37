"""One run of one cell: inputs from the seed, set-up, a timed closed loop of
``pim.compile`` dispatches, a check of what the loop produced against the
configuration's numpy reference, and the result line.

The entry the window drives is the public call
``pim.compile(program, dtype)(*arrays, basis=cell basis)`` on
device-resident arrays, each call ended by ``block_until_ready``, with no
executor mode and no backend named: what users get.  Two input sets made
from the seed alternate, so no dispatch repeats the previous one's inputs.

Nothing here looks for a chip; ``run.py`` does that before calling
:func:`run_cell`, so tests can drive a whole run on the CPU.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import statistics
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Callable

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# Outputs kept from the window for the check, per input set: a sample drawn
# from the seed (reservoir sampling over every dispatch of that set).
CHECK_PER_SET = 2
# A traced run traces at most this many seconds of dispatches: the trace of
# a longer window is large and slow to read, and its per-dispatch averages
# need only some tens of dispatches.
TRACE_SECONDS = 5.0
# The comparison is exact: every element bit for bit (a NaN matches any NaN).
MISMATCH_LIMIT = 0


# ------------------------------------------------------------------ loading


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    config: ModuleType
    basis: str
    elements: int
    input_sets: int
    bench: Path  # the benchmark directory the cell was found in


def _load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, bench: Path = BENCH) -> Cell:
    spec = json.loads((bench / "cells" / f"{name}.json").read_text())
    config = _load_module(bench / "configs" / f"{spec['config']}.py",
                          f"bench_config_{spec['config']}")
    return Cell(name=name, config_name=spec["config"], config=config,
                basis=spec["basis"], elements=int(spec["elements"]),
                input_sets=int(spec["input_sets"]), bench=bench)


def load_metric(name: str, bench: Path = BENCH) -> ModuleType:
    return _load_module(bench / "metrics" / f"{name}.py",
                        f"bench_metric_{name.replace('.', '_')}")


def load_peaks(device_kind: str, bench: Path = BENCH) -> dict:
    """The peaks of ``device_kind``; a kind missing from the table is an
    error, never a default."""
    table = json.loads((bench / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def cell_metrics(benchmark: dict, cell: str, group: str) -> list[dict]:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) that this
    cell reports: those with no ``workloads`` list, or that list it."""
    return [m for m in benchmark[group]
            if "workloads" not in m or cell in m["workloads"]]


# ------------------------------------------------------------------ inputs


def _rng(seed: int, *stream: int) -> np.random.Generator:
    # Any whole number, negative ones too, is a seed.
    return np.random.default_rng([seed % 2 ** 64, *stream])


def make_input_sets(cell: Cell, seed: int) -> list[list[np.ndarray]]:
    """``cell.input_sets`` host input sets, each the same sizes for every
    seed."""
    return [cell.config.make_inputs(_rng(seed, 1, s), cell.elements)
            for s in range(cell.input_sets)]


def count_mismatches(got: np.ndarray, expect: np.ndarray) -> int:
    """Elements that differ bit for bit; for floats a NaN matches any NaN."""
    got = np.asarray(got)
    if got.shape != expect.shape or got.dtype != expect.dtype:
        return int(expect.size)
    if expect.dtype.kind == "f":
        bits = np.dtype(f"u{expect.dtype.itemsize}")
        ok = (got.view(bits) == expect.view(bits)) | (np.isnan(got)
                                                      & np.isnan(expect))
    else:
        ok = got == expect
    return int(np.count_nonzero(~ok))


# ------------------------------------------------------------------ window


class _CompileCounter:
    """Counts JAX traces and backend compiles (or persistent-cache loads)
    while ``active``: there should be none inside the window."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax

        self.active = False
        self.counts = {"traces": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def close(self) -> None:
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self._on_event)


@dataclasses.dataclass
class Window:
    times: list[float]          # host seconds of each dispatch
    seconds: float              # window start to the last dispatch's end
    kept: list[list]            # per input set: the sampled outputs


def run_window(dispatch: Callable, dev_sets: list, seconds: float,
               rng: np.random.Generator, span: str | None = None) -> Window:
    """Closed loop, one client: the next dispatch starts when the last has
    finished, until ``seconds`` have passed.  Input sets alternate.  Keeps
    ``CHECK_PER_SET`` outputs of each set, drawn by ``rng``."""
    import jax

    annotate = (jax.profiler.TraceAnnotation if span
                else lambda _name: contextlib.nullcontext())
    n_sets = len(dev_sets)
    seen = [0] * n_sets
    kept: list[list] = [[] for _ in range(n_sets)]
    times: list[float] = []
    i = 0
    start = time.perf_counter()
    while True:
        s = i % n_sets
        t0 = time.perf_counter()
        with annotate(span):
            out = jax.block_until_ready(dispatch(*dev_sets[s]))
        t1 = time.perf_counter()
        times.append(t1 - t0)
        if seen[s] < CHECK_PER_SET:
            kept[s].append(out)
        else:
            r = int(rng.integers(0, seen[s] + 1))
            if r < CHECK_PER_SET:
                kept[s][r] = out
        seen[s] += 1
        i += 1
        if t1 - start >= seconds:
            return Window(times, t1 - start, kept)


def p90(values: list[float]) -> float:
    """The 90th percentile, Python's inclusive quantile method."""
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=10, method="inclusive")[8]


# ------------------------------------------------------------------ check


def check(cell: Cell, host_sets: list, kept: list[list]) -> dict:
    """Compare every kept output with the reference of its input set."""
    mismatched = checked = failed = 0
    for inputs, outs in zip(host_sets, kept):
        if not outs:
            continue
        expect = cell.config.reference(*inputs)
        for out in outs:
            bad = count_mismatches(np.asarray(out), expect)
            mismatched += bad
            failed += bad > 0
            checked += 1
    covered = all(kept)
    return {"mismatched": mismatched, "checked": checked, "failed": failed,
            "correct": covered and mismatched <= MISMATCH_LIMIT}


# ------------------------------------------------------------------ run


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    cell: Cell
    compiled: object            # repro CompiledSchedule of the cell's program
    cost: object                # its CostReport
    trace_passes_s: float
    n_args: int
    n_outputs: int
    peaks: dict
    trace: object | None = None  # reduce.TraceSummary of the traced window


def read_per_layer(benchmark: dict, ctx: Context) -> dict:
    """Each per-layer metric of the cell, read by ``metrics/<name>.py``;
    a reader that finds nothing to read returns None and is left out."""
    metrics = {}
    for m in cell_metrics(benchmark, ctx.cell.name, "per_layer"):
        value = load_metric(m["name"], ctx.cell.bench).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def build_dispatch(cell: Cell):
    """Trace, run the compiler's passes and lower the cell's program.
    Returns ``(dispatch, compiled, cost, trace_passes_s, n_args,
    n_outputs)``."""
    import repro.pim as pim

    fn = pim.compile(cell.config.program, dtype=getattr(pim, cell.config.DTYPE))
    t0 = time.perf_counter()
    compiled = fn.compiled(basis=cell.basis)
    trace_passes_s = time.perf_counter() - t0
    cost = fn.cost(basis=cell.basis)

    def dispatch(*arrays):
        return fn(*arrays, basis=cell.basis)

    return (dispatch, compiled, cost, trace_passes_s, len(fn.in_types),
            len(fn.out_types))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             benchmark: dict, peaks: dict, t_process: float,
             wrap: Callable | None = None,
             trace_dir: str | None = None) -> dict:
    """One run; returns the result line's object.

    ``t_process`` is the ``time.perf_counter()`` reading taken when the
    process started.  ``wrap`` replaces the dispatch by ``wrap(dispatch)``
    (tests plant faults with it).  A traced run writes its trace under
    ``trace_dir``, or under a temporary directory it deletes."""
    import jax

    marks = [("start", time.perf_counter())]
    host_sets = make_input_sets(cell, seed)
    dev_sets = [[jax.device_put(x) for x in s] for s in host_sets]
    marks.append(("inputs", time.perf_counter()))
    dispatch, compiled, cost, trace_passes_s, n_args, n_outputs = \
        build_dispatch(cell)
    if wrap is not None:
        dispatch = wrap(dispatch)
    marks.append(("build", time.perf_counter()))
    for s in dev_sets:  # warm-up: every program and shape the window uses
        jax.block_until_ready(dispatch(*s))
    jax.block_until_ready(dispatch(*dev_sets[0]))
    marks.append(("warmup", time.perf_counter()))
    print("setup: before_cell=%r " % (marks[0][1] - t_process)
          + " ".join(f"{name}={t - t0!r}" for (_, t0), (name, t)
                     in zip(marks, marks[1:])), file=sys.stderr, flush=True)

    counter = _CompileCounter()
    rng = _rng(seed, 2)
    summary = None
    try:
        if trace:
            with tempfile.TemporaryDirectory() as tmp:
                out_dir = trace_dir or tmp
                window, summary = _traced_window(dispatch, dev_sets, seconds,
                                                 rng, counter, out_dir)
            setup_s = None
        else:
            t_window = time.perf_counter()
            setup_s = t_window - t_process
            counter.active = True
            window = run_window(dispatch, dev_sets, seconds, rng)
            counter.active = False
    finally:
        counter.close()

    device = jax.devices()[0]
    stats = device.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    del dev_sets
    verdict = check(cell, host_sets, window.kept)
    q = [float(x) for x in np.quantile(window.times, [0, 0.5, 0.9, 1]) * 1e3]
    print(f"window: dispatch_ms min={q[0]!r} p50={q[1]!r} p90={q[2]!r} "
          f"max={q[3]!r}", file=sys.stderr)
    print(f"window: dispatches={len(window.times)} seconds={window.seconds!r} "
          f"traces_in_window={counter.counts['traces']} "
          f"compiles_in_window={counter.counts['compiles']}",
          file=sys.stderr, flush=True)

    device_info = {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace:
        ctx = Context(cell=cell, compiled=compiled, cost=cost,
                      trace_passes_s=trace_passes_s, n_args=n_args,
                      n_outputs=n_outputs, peaks=peaks, trace=summary)
        metrics = read_per_layer(benchmark, ctx)
        device_info.update(busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        values = {
            "elems_per_s": len(window.times) * cell.elements / window.seconds,
            "dispatch_ms_p90": p90(window.times) * 1e3,
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(benchmark, cell.name, "end_to_end")}

    checks = {"mismatched_elements": {"value": verdict["mismatched"],
                                      "limit": MISMATCH_LIMIT}}
    print(f"check: outputs={verdict['checked']} of "
          f"{len(window.times)} dispatches", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}={c['value']} limit={c['limit']}", file=sys.stderr,
              flush=True)
    result = {"correct": verdict["correct"], "attempted": len(window.times),
              "failed": verdict["failed"], "metrics": metrics,
              "device": device_info}
    if trace:
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    return result


def _traced_window(dispatch, dev_sets, seconds, rng, counter, out_dir):
    """The traced run: spans ``warmup`` (one more dispatch per input set),
    ``dispatch`` (each dispatch of the window) and ``check`` (the kept
    outputs copied to the host), then the trace's reduction."""
    import jax

    from bench import reduce

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("warmup"):
            for s in dev_sets:
                jax.block_until_ready(dispatch(*s))
        counter.active = True
        window = run_window(dispatch, dev_sets, min(seconds, TRACE_SECONDS),
                            rng, span="dispatch")
        counter.active = False
        with jax.profiler.TraceAnnotation("check"):
            window.kept = [[np.asarray(o) for o in outs]
                           for outs in window.kept]
    finally:
        jax.profiler.stop_trace()
    paths = sorted(Path(out_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {out_dir}")
    summary = reduce.summarize(reduce.load(paths[-1]))
    return window, summary
