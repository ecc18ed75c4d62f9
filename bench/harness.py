"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

Set-up generates the configuration's graph from the seed, builds the
program's partition, chunk formats and (out of core) chunk store, opens
the ``Engine`` (over a mesh of the cell's chips, one partition on each,
where the cell has more than one) and runs the traffic's warm-up jobs,
which compile or load from the persistent cache every program the window
runs (a program the engine jits afresh on every call compiles again in
the window, as it does for a user; ``window_compiles`` counts it).  The
window runs the traffic's jobs back to back and closes at the end of the
first job that finishes at or after ``seconds``, so it holds whole jobs
only.  Once
it has closed and the device's peak memory has been read, the program's
state is freed and every job's answer is compared with the benchmark's
own numpy reference (``algorithms/<name>.py`` of the traffic's algorithm).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

import bench.catalog as catalog
import bench.graph500 as g500
from bench.spans import CompileMeter, HostMeter, Spans


class NoChip(RuntimeError):
    """JAX found no accelerator the cell can be measured on."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def check_chip(chips: int):
    """The devices to measure on; raises :class:`NoChip` when JAX finds no
    TPU, fewer chips than the cell asks for, a chip with no entry in
    ``peaks.json``, or Pallas kernels that would run interpreted."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees "
                     f"{len(devices)}")
    catalog.peaks(devices[0].device_kind)
    from repro.kernels.csr_spmv import default_interpret
    if default_interpret():
        raise NoChip("the Pallas kernels would run interpreted")
    return devices[:chips]


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class System:
    graph: g500.Graph
    engine: object
    stages: dict            # set-up stage -> seconds


def build_system(config: dict, seed: int, workdir: str,
                 devices: list) -> System:
    """The configuration's graph from ``seed``, and the engine over it.

    On one device the engine is the configuration's executor (LOCAL or
    OOC).  Over several it is SHARD_MAP: a ``("part",)`` mesh of
    ``devices``, one partition on each."""
    import jax
    from repro.core import (ChunkStore, Engine, EngineConfig,
                            build_dist_graph, build_formats, make_spec)
    from repro.data.graphs import GraphData
    stages = {}
    t = time.perf_counter()

    def stage(name):
        nonlocal t
        now = time.perf_counter()
        stages[name] = now - t
        t = now

    graph = g500.graph500_graph(config, seed)
    stage("generate")
    g = GraphData(*graph, None)
    spec = make_spec(g, num_partitions=int(config["num_partitions"]))
    dist = build_dist_graph(g, spec)
    stage("partition")
    fmts = build_formats(dist)
    stage("formats")
    engine_cfg = EngineConfig(**config["engine"])
    store = None
    if engine_cfg.executor == "ooc":
        store = ChunkStore.build(dist, fmts, os.path.join(workdir, "store"))
        stage("store")
    if len(devices) > 1:
        mesh = jax.sharding.Mesh(np.asarray(devices), ("part",))
        engine = Engine(dist, fmts, engine_cfg, store=store, mesh=mesh,
                        axis="part")
    else:
        engine = Engine(dist, fmts, engine_cfg, store=store)
    stage("engine")
    return System(graph, engine, stages)


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class JobRecord:
    job: object             # the algorithm module's Job
    seconds: float
    values: object = None   # numpy answer, None if the job raised
    pe_calls: int = 0       # ProcessEdges calls (RunStats.iterations)
    counters: dict = dataclasses.field(default_factory=dict)
    error: str | None = None
    host: dict = dataclasses.field(default_factory=dict)  # HostMeter delta


@dataclasses.dataclass
class Window:
    """What the per-layer metric readers read (``layer_metrics/*.py``)."""
    cell: object            # catalog.Cell
    jobs: list              # [JobRecord]
    seconds: float          # first job's start to last job's end
    compiles: dict          # CompileMeter deltas over the window
    spans: Spans | None     # host spans (traced run only)
    trace: object = None    # trace_reduce.TraceSummary (traced run only)
    memory_peak_bytes: int = 0

    @property
    def done(self) -> list:
        return [r for r in self.jobs if r.error is None]

    @property
    def pe_calls(self) -> int:
        return sum(r.pe_calls for r in self.done)

    def counter(self, key: str):
        """A counter summed over the window's jobs; None where the
        engine does not report it."""
        done = self.done
        if not done or any(key not in r.counters for r in done):
            return None
        return sum(float(r.counters[key]) for r in done)


def run_window(engine, alg, jobs: list, seconds: float,
               spans: Spans | None):
    """Run ``jobs`` of the algorithm module ``alg`` in order, cycling,
    until one ends at or after ``seconds``; returns ``(records, window
    seconds)``."""
    records = []
    job_span = spans.span if spans else (lambda _: contextlib.nullcontext())
    host = HostMeter()
    t_start = time.perf_counter()
    t_end = t_start
    for i in range(sys.maxsize):
        job = jobs[i % len(jobs)]
        h0 = host.snapshot()
        t0 = time.perf_counter()
        try:
            with job_span("job"):
                values, stats = alg.run(engine, job)
                values = np.asarray(values)
            rec = JobRecord(job, 0.0, values, int(stats.iterations),
                            dict(stats.counters))
        except Exception as exc:  # a job that raises is a failed job
            traceback.print_exc(file=sys.stderr)
            rec = JobRecord(job, 0.0, error=f"{type(exc).__name__}: {exc}")
        t_end = time.perf_counter()
        rec.seconds = t_end - t0
        rec.host = HostMeter.delta(h0, host.snapshot())
        records.append(rec)
        if t_end - t_start >= seconds:
            break
    host.close()
    return records, t_end - t_start


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# Correctness: every job of the window against the reference
# ---------------------------------------------------------------------------

def compare(records: list, traffic: dict, graph: g500.Graph):
    """``(failed job count, checks)``: each check is ``{"value", "limit"}``
    and a job fails when it raised or its answer's gap from the reference
    is beyond the limit."""
    alg = catalog.algorithm(traffic["algorithm"])
    limit = traffic["limits"][alg.CHECK]
    ref = alg.Reference(graph)
    worst, failed = 0, 0
    for r in records:
        if r.error is not None:
            failed += 1
            continue
        gap = alg.gap(r.values, ref.answer(r.job))
        worst = max(worst, gap)
        failed += not gap <= limit
    return failed, {alg.CHECK: {"value": worst, "limit": limit},
                    "jobs_failed": {"value": failed, "limit": 0}}


def is_correct(records: list, failed: int) -> bool:
    return len(records) > 0 and failed == 0


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             t_process: float | None = None,
             require_chip: bool = True) -> dict:
    """Run ``cell`` once and return the result line's object.

    ``t_process`` is when the process started (set-up is timed from it);
    ``require_chip`` off lets the tests drive a run on the CPU, without
    the persistent compilation cache."""
    t_process = time.perf_counter() if t_process is None else t_process
    parts = int(cell.config["num_partitions"])
    if cell.chips > 1 and parts != cell.chips:
        raise catalog.CatalogError(
            f"cell {cell.name!r} spans {cell.chips} chips, so its "
            f"configuration needs num_partitions {cell.chips} (one per "
            f"chip), not {parts}")
    if require_chip:
        devices = check_chip(cell.chips)
        # the program's own cache set-up, as its entry points run it
        from repro.utils import enable_compile_cache
        enable_compile_cache()
    else:
        import jax
        devices = jax.devices()[:cell.chips]
        if len(devices) < cell.chips:
            raise NoChip(f"the cell needs {cell.chips} devices; JAX sees "
                         f"{len(devices)}")
    meter = CompileMeter()
    workdir = tempfile.mkdtemp(prefix="bench-")
    try:
        return _run(cell, seed, seconds, trace, devices, meter, workdir,
                    t_process)
    finally:
        meter.close()
        shutil.rmtree(workdir, ignore_errors=True)


def _run(cell, seed, seconds, trace, devices, meter, workdir,
         t_process) -> dict:
    import jax
    log(f"{cell.name}: seed {seed}, {seconds} s window, trace {int(trace)}, "
        f"{len(devices)} x {devices[0].device_kind}")
    system = build_system(cell.config, seed, workdir, devices)
    alg = catalog.algorithm(cell.traffic["algorithm"])
    warmup, jobs = alg.jobs(cell.traffic, system.graph, seed)
    t = time.perf_counter()
    for job in warmup:
        alg.run(system.engine, job)
    system.stages["warmup"] = time.perf_counter() - t
    spans = None
    if trace:
        spans = Spans(block=True)
        for call in ("process_edges", "process_vertices"):
            setattr(system.engine, call,
                    spans.wrap(call, getattr(system.engine, call)))
    setup_s = time.perf_counter() - t_process
    log(f"set-up {setup_s:.2f} s: " + ", ".join(
        f"{k} {v:.2f}" for k, v in system.stages.items()))

    trace_dir = os.path.join(workdir, "trace")
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0 = meter.snapshot()
    with (spans.span("window") if trace else contextlib.nullcontext()):
        records, window_s = run_window(system.engine, alg, jobs, seconds,
                                       spans)
    c1 = meter.snapshot()
    if trace:
        jax.profiler.stop_trace()
    peak = memory_peak(devices)
    window = Window(cell, records, window_s,
                    {k: c1[k] - c0[k] for k in c0}, spans,
                    memory_peak_bytes=peak)
    log(f"window {window_s:.3f} s, {len(records)} jobs: "
        + ", ".join(f"{r.seconds:.3f}" for r in records)
        + "; in the window: " + ", ".join(
            f"{k} {v:g}" for k, v in window.compiles.items()))
    for k in ("cpu_s", "gc_s", "disk_blocks", "preempted"):
        log(f"per job {k}: " + ", ".join(f"{r.host.get(k, 0):g}"
                                        for r in records))

    graph, stages = system.graph, system.stages
    del system
    gc.collect()
    shutil.rmtree(os.path.join(workdir, "store"), ignore_errors=True)
    t = time.perf_counter()
    failed, checks = compare(records, cell.traffic, graph)
    log(f"reference and comparison {time.perf_counter() - t:.2f} s")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": is_correct(records, failed),
              "attempted": len(records), "failed": failed}
    if trace:
        from bench import trace_reduce
        window.trace = trace_reduce.reduce_trace(trace_dir)
        device["busy_s"] = window.trace.busy_s
        device["window_s"] = window.trace.window_s
        result["metrics"] = layer_metrics(cell, window)
        result["device"] = device
        result["breakdown"] = {
            "device_ops": trace_reduce.top(window.trace.op_s),
            "idle_gaps": trace_reduce.top(window.trace.idle_by_label)}
    else:
        result["metrics"] = end_to_end_metrics(cell, window, setup_s)
        result["device"] = device
    result["setup_stages"] = stages
    result["job_s"] = [r.seconds for r in records]
    result["job_pe_calls"] = [r.pe_calls for r in records]
    result["job_host"] = [r.host for r in records]
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return result


def graph_work(config: dict) -> int:
    """Vertices plus edges of the graph a job runs over (LDBC Graphalytics'
    EVPS numerator): 2**scale + edge_factor * 2**scale, counting each
    undirected edge once."""
    n = 1 << int(config["scale"])
    return n + int(config["edge_factor"]) * n


def end_to_end_metrics(cell, window: Window, setup_s: float) -> dict:
    values = {
        "evps": len(window.done) * graph_work(cell.config) / window.seconds,
        "setup_s": setup_s,
    }
    out = {}
    for m in cell.end_to_end:
        if m.name not in values:
            raise catalog.CatalogError(f"end-to-end metric {m.name!r} has "
                                       "no measurement in the harness")
        out[m.name] = {"value": values[m.name], "unit": m.unit}
    return out


def layer_metrics(cell, window: Window) -> dict:
    out = {}
    for m in cell.per_layer:
        v = catalog.layer_reader(m.name)(window)
        if v is not None and math.isfinite(v):
            out[m.name] = {"value": v, "unit": m.unit}
    return out
