"""The benchmark's own instrumentation: host spans around the calls into
each layer of the program, a count of compilations, and what the host did
during each job.

Spans are written as ``jax.profiler.TraceAnnotation`` so that they share
the device trace's clock (``trace_reduce`` labels device time and idle
gaps by them), and are also kept on the host clock for the span metrics.
"""
from __future__ import annotations

import contextlib
import gc
import resource
import threading
import time

import jax

PREFIX = "bench."

# jax.monitoring events: a backend compilation ends with the last one
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    """Counts traces, backend compilations and persistent-cache loads
    through ``jax.monitoring``, from construction until :meth:`close`."""

    def __init__(self):
        self._lock = threading.Lock()
        self.traces = 0
        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            with self._lock:
                self.compile_s += duration
                if event == _COMPILE_EVENTS[0]:
                    self.traces += 1
                elif event == _COMPILE_EVENTS[-1]:
                    self.compiles += 1

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(traces=self.traces, compiles=self.compiles,
                        cache_hits=self.cache_hits,
                        compile_s=self.compile_s)

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


class HostMeter:
    """What the process did during a job, to tell a job that worked longer
    from one that waited (``getrusage``): CPU seconds of all its threads,
    seconds in Python's garbage collector, blocks read from disk (reads
    the page cache missed) and involuntary context switches (threads
    preempted by other work on the host)."""

    def __init__(self):
        self.gc_s = 0.0
        self._gc_t0 = None
        gc.callbacks.append(self._gc)

    def _gc(self, phase, _info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def snapshot(self) -> dict:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return {"cpu_s": ru.ru_utime + ru.ru_stime, "gc_s": self.gc_s,
                "disk_blocks": ru.ru_inblock, "preempted": ru.ru_nivcsw}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}

    def close(self) -> None:
        gc.callbacks.remove(self._gc)


class Spans:
    """Host spans ``(name, t0, t1)`` on ``time.perf_counter``.

    With ``block`` set (the traced run), a wrapped call waits for its
    outputs before its span closes, so the device work it started falls
    inside it."""

    def __init__(self, block: bool):
        self.block = block
        self.records: list = []

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation(PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def wrap(self, name: str, fn):
        def wrapped(*args, **kwargs):
            with self.span(name):
                out = fn(*args, **kwargs)
                if self.block:
                    jax.block_until_ready(out)
            return out
        return wrapped

    def total(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.records if n == name)

    def count(self, name: str) -> int:
        return sum(1 for n, _, _ in self.records if n == name)
