"""Reduce a profiler trace (``.xplane.pb``) to device busy time, device
time per operation and idle gaps labelled by the benchmark's host spans.

The reduction reads the trace through ``jax.profiler.ProfileData`` only:

* the devices are the planes named ``/device:TPU:<n>``; their busy time is
  the union of the intervals of the events on the ``XLA Ops`` line, within
  the window, averaged over the devices;
* the window is the host span ``bench.window``;
* every instant of the window is labelled by the innermost ``bench.*``
  host span open then (``window`` between jobs, ``job`` in the algorithm,
  ``process_edges`` / ``process_vertices`` in an engine call), and idle
  and busy time are split by those labels;
* device time per operation sums the op events' durations, keyed by
  ``<program>/<instruction>``: the ``XLA Modules`` event (the jitted
  program) the op runs in, and the op's HLO instruction name.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

import numpy as np

SPAN_PREFIX = "bench."
WINDOW = "window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                 # mean over devices
    devices: int
    op_s: dict                    # "<program>/<op>" -> seconds, all devices
    module_s: dict                # module name -> seconds, all devices
    busy_by_label: dict           # host label -> device busy seconds (mean)
    idle_by_label: dict           # host label -> device idle seconds (mean)

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s

    @property
    def idle_share(self) -> float:
        return self.idle_s / self.window_s


def find_xspace(log_dir: str) -> str:
    """The newest ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _instruction(event_name: str) -> str:
    """An XLA op event's instruction name: ``%fusion.3 = f32[...] ...``
    gives ``fusion.3``."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _module(event_name: str) -> str:
    """A module event's program name without its fingerprint:
    ``jit_step(1234)`` gives ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merge intervals into sorted disjoint ``(starts, ends)``."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


def _covered(us: np.ndarray, ue: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Covered length of the disjoint intervals ``(us, ue)`` before each
    time in ``t``."""
    before = np.concatenate([[0.0], np.cumsum(ue - us)])
    i = np.searchsorted(us, t, side="right")
    part = np.clip(t - us[np.maximum(i - 1, 0)], 0.0,
                   (ue - us)[np.maximum(i - 1, 0)])
    return before[np.maximum(i - 1, 0)] + np.where(i > 0, part, 0.0)


def _label_segments(spans, w0: float, w1: float):
    """Split ``[w0, w1]`` at every span boundary and label each piece by
    the innermost span open in it (spans on one thread nest)."""
    cuts = sorted({w0, w1, *[t for _, a, b in spans for t in (a, b)
                             if w0 < t < w1]})
    mids = [(a + b) / 2 for a, b in zip(cuts[:-1], cuts[1:])]
    labels = []
    for m in mids:
        best, best_start = WINDOW, -np.inf
        for name, a, b in spans:
            if a <= m < b and a >= best_start:
                best, best_start = name, a
        labels.append(best)
    return np.asarray(cuts[:-1]), np.asarray(cuts[1:]), labels


def reduce_profile(profile) -> TraceSummary:
    """Reduce a loaded ``ProfileData``."""
    spans, devices = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    windows = [(a, b) for n, a, b in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {SPAN_PREFIX}{WINDOW} span")
    if not devices:
        raise ValueError("the trace has no device plane")
    w0, w1 = windows[0]
    inner = [s for s in spans if s[0] != WINDOW and s[1] < w1 and s[2] > w0]
    seg_a, seg_b, seg_label = _label_segments(inner, w0, w1)
    op_s, module_s = collections.Counter(), collections.Counter()
    busy_total = 0.0
    busy_by, idle_by = collections.Counter(), collections.Counter()
    for plane in devices:
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns,
                        _module(ev.name))
                       for ev in lines.get(MODULES_LINE, ())))
        mod_starts = np.asarray([m[0] for m in mods], float)
        for a, b, name in mods:
            if w0 <= a < w1:
                module_s[name] += (b - a) * 1e-9
        starts, ends = [], []
        for ev in lines.get(OPS_LINE, ()):
            a, b = ev.start_ns, ev.start_ns + ev.duration_ns
            if b <= w0 or a >= w1:
                continue
            starts.append(max(a, w0))
            ends.append(min(b, w1))
            i = int(np.searchsorted(mod_starts, a, side="right")) - 1
            prog = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            op_s[f"{prog}/{_instruction(ev.name)}"] += ev.duration_ns * 1e-9
        us, ue = _union(np.asarray(starts, float), np.asarray(ends, float))
        busy_total += float((ue - us).sum())
        seg_busy = (_covered(us, ue, seg_b) - _covered(us, ue, seg_a)
                    if us.size else np.zeros(seg_a.size))
        for lab, a, b, busy in zip(seg_label, seg_a, seg_b, seg_busy):
            busy_by[lab] += busy
            idle_by[lab] += (b - a) - busy
    n = len(devices)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_total / n * 1e-9, devices=n,
        op_s=dict(op_s), module_s=dict(module_s),
        busy_by_label={k: v / n * 1e-9 for k, v in busy_by.items()},
        idle_by_label={k: v / n * 1e-9 for k, v in idle_by.items()})


def reduce_trace(path: str) -> TraceSummary:
    """Reduce the ``.xplane.pb`` at ``path`` (or the newest under it, if
    it is a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xspace(path)
    return reduce_profile(ProfileData.from_file(path))


def top(table: dict, k: int = 10) -> list:
    """The ``k`` largest entries as ``[[name, value], ...]``."""
    return [[name, v] for name, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:k]]
