"""One run of one cell: set-up, warm-up, the measured window, the checks,
and the result line. Everything specific to a configuration, a traffic mix,
a kind of traffic or a per-layer metric is in a file of its own that this
runner finds by the names in BENCHMARK.json."""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from . import checks, device, spec
from .compile_events import CompileWatch
from .xplane import Trace, newest_trace_file

WORK_DIR = ".benchmark_work"


def load_module(path: str, name: str):
    """A kind, a data generator or a per-layer reader, imported from its
    file."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


@dataclass
class Context:
    """What a kind's driver is handed. `data` is the configuration's
    generator (a module with `make(params, seed)`), `program` the adapter
    to the system under test."""
    root: str
    cell: str
    config: Dict
    traffic: Dict
    seed: int
    seconds: float
    trace: bool
    workdir: str
    program: object
    data: object
    facts: Dict = field(default_factory=dict)
    _exits: List[Callable[[], None]] = field(default_factory=list)

    def log(self, message: str) -> None:
        print(f"[{self.cell}] {message}", flush=True)

    def annotate(self, name: str):
        """A host annotation in the profiler's trace (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def on_exit(self, fn: Callable[[], None]) -> None:
        self._exits.append(fn)

    def close(self) -> None:
        while self._exits:
            self._exits.pop()()


@dataclass
class Reading:
    """What a per-layer reader is handed: the window's counters, the
    kind's facts, the compile watch and, in a traced run, the trace."""
    cell: str
    config: Dict
    traffic: Dict
    seconds: float
    facts: Dict
    counters_start: Dict[str, float]
    counters_end: Dict[str, float]
    compiles: CompileWatch
    device: Dict
    program: object
    trace: Optional[Trace] = None

    def counter_delta(self, name: str) -> float:
        return self.counters_end.get(name, 0.0) - \
            self.counters_start.get(name, 0.0)


def program_checks(reading: Reading) -> List[checks.Check]:
    """Held in every cell: every audited dispatch on the device route, no
    counter of a hidden fallback moved, nothing compiled in the window."""
    routes = reading.program.routes()
    total = sum(routes.values())
    share = 100.0 * routes.get("device", 0) / total if total else 0.0
    out = [checks.exactly("all.route_device_share_pct", share, 100.0,
                          f"{total} audited dispatches: {routes}")]
    for name in reading.program.ZERO_COUNTERS:
        out.append(checks.exactly(f"all.{name}",
                                  reading.counters_end.get(name, 0.0), 0.0))
    in_window = reading.compiles.between("window_start", "window_end")
    out.append(checks.exactly("all.compile_requests_in_window",
                              in_window["requests"], 0.0,
                              "a shape the warm-up missed"))
    return out


def idle_labels(ctx: Context, trace: Trace, anchor_s: float
                ) -> List:
    """The program's recorder spans on the trace's clock, anchored at the
    start of the window annotation."""
    lo, _ = trace.window()
    return [(name, lo + (a - anchor_s) * 1e9, lo + (b - anchor_s) * 1e9)
            for name, a, b in ctx.program.recorder_spans()]


def run(root: str, cell: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True,
        bench: Optional[Dict] = None, program=None) -> Dict:
    """Drive one run and return the result object (also printed by `main`).
    `require_chip=False`, a `bench` object and a `program` stand-in exist
    for the tests, which drive this function on the CPU at a tiny size; the
    command has no flag for any of them."""
    bench = bench or spec.load_benchmark(root)
    parts = spec.resolve(root, bench, cell)
    chips = int(parts["workload"]["chips"])
    import jax
    if require_chip:
        devices = device.require_tpu(chips)[:chips]
    else:
        devices = jax.devices()[:chips]
    described = device.describe(devices)
    print(f"[{cell}] device {described}, seed {seed}, window {seconds}s, "
          f"trace {int(trace)}", flush=True)

    compiles = CompileWatch()
    compiles.install()
    if program is None:
        from . import program
    workdir = os.path.join(root, WORK_DIR, cell)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    cache_dir = program.configure(parts["config"].get("conf", {}))
    print(f"[{cell}] compile cache at {cache_dir}", flush=True)
    kind = load_module(parts["kind_path"],
                       "bench_kind_" + parts["traffic"]["kind"])
    ctx = Context(root=root, cell=cell, config=parts["config"],
                  traffic=parts["traffic"], seed=int(seed),
                  seconds=float(seconds), trace=trace, workdir=workdir,
                  program=program,
                  data=load_module(parts["data_path"], "bench_data"))
    try:
        state = kind.setup(ctx)
        gc.collect()
        gc.freeze()
        trace_dir = os.path.join(workdir, "trace")
        if trace:
            # the benchmark's annotations are level-1 host events; Python's
            # own call tracer would slow the host path that is measured
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        compiles.mark("window_start")
        counters_start = program.counters()
        setup_s = time.perf_counter() - t_start
        anchor_s = time.perf_counter()
        try:
            with ctx.annotate("bench.window"):
                result = kind.window(ctx, state)
        finally:
            window_s = time.perf_counter() - anchor_s
            if trace:
                jax.profiler.stop_trace()
        compiles.mark("window_end")
        counters_end = program.counters()
        memory_peak = device.memory_peak_bytes(devices)
        print(f"[{cell}] device memory: {devices[0].memory_stats()}",
              flush=True)

        reading = Reading(cell=cell, config=parts["config"],
                          traffic=parts["traffic"], seconds=float(seconds),
                          facts=ctx.facts, counters_start=counters_start,
                          counters_end=counters_end, compiles=compiles,
                          device=described, program=program)
        report = kind.report(ctx, state, result)
        verdicts = kind.check(ctx, state, result) + program_checks(reading)
        for c in verdicts:
            print(c.line(), flush=True)

        out_device = dict(described, memory_peak_bytes=memory_peak)
        metrics: Dict[str, Dict] = {}
        breakdown: Dict[str, object] = {}
        if trace:
            reading.trace = Trace.from_file(newest_trace_file(trace_dir))
            lo, hi = reading.trace.window()
            out_device["busy_s"] = reading.trace.busy_ns(lo, hi) / 1e9
            out_device["window_s"] = (hi - lo) / 1e9
            breakdown["breakdown"] = {
                "device_ops": [[n, s] for n, s in
                               reading.trace.top_ops(lo, hi)],
                "idle_gaps": [[n, s] for n, s in reading.trace.idle_gaps(
                    lo, hi, idle_labels(ctx, reading.trace, anchor_s))]}
            for m in spec.metrics_for(bench, cell, "per_layer"):
                reader = load_module(parts["readers"][m["name"]],
                                     "bench_metric")
                value = reader.read(reading)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
        else:
            values = dict(report["end_to_end"], setup_s=setup_s)
            for m in spec.metrics_for(bench, cell, "end_to_end"):
                if m["name"] in values:
                    metrics[m["name"]] = {"value": float(values[m["name"]]),
                                          "unit": m["unit"]}
        print(f"[{cell}] set-up {setup_s:.2f}s, window {window_s:.2f}s, "
              f"compile: {compiles.until('window_start')}", flush=True)
        return {"correct": checks.all_ok(verdicts),
                "attempted": int(report["attempted"]),
                "failed": int(report["failed"]),
                "metrics": metrics, "device": out_device, **breakdown}
    finally:
        ctx.close()


def main(root: str, cell: str, seed: int, seconds: float, trace: bool,
         t_start: float) -> int:
    try:
        line = run(root, cell, seed, seconds, trace, t_start)
    except (device.NoChip, spec.SpecError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0
