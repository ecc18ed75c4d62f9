"""Reduce the program's own spans and scopes in a profiler trace.

The engine marks its host work as ``dfo.<name>`` annotations
(``repro.core.tracing``: phases of the out-of-core ProcessEdges call on the
main thread, chunk reads and decodes on the prefetch thread, vertex-spill
I/O), and the phases of the jitted LOCAL step as name-stack scopes
(``jax.named_scope``), which a TPU trace carries in the ``tf_op`` stat of
each XLA op's event metadata (``jit(step)/vmap(combine)/gather:``).  This
module reads both from the ``.xplane.pb`` that ``trace_reduce`` reads, over
the same window (the host span ``bench.window``), in one pass over the
XSpace protobuf: ``jax.profiler.ProfileData`` gives an event's own stats
but not those of its metadata, where ``tf_op`` sits.  Event times are the
whole nanoseconds ``ProfileData`` gives, so the window and the ``bench.*``
labels are ``trace_reduce``'s to the bit.

* every instant of the window is labelled twice: by the innermost
  ``bench.*`` span open then (``trace_reduce._label_segments``) and by the
  innermost program span open on the main thread (the host line that
  holds ``bench.window``): a ``dfo.*`` span, or JAX's own
  ``backend_compile*`` / ``lower_sharding_computation`` event
  (``jax.compile`` / ``jax.lower``).  Device idle and busy time are split
  by the pair, keyed ``"<bench label>/<program label>"``, or the bench
  label alone where no program span is open, so the entries of one bench
  label sum to ``trace_reduce``'s figure for it.  The program spans, some
  thousands per window, are labelled by a sort-and-sweep over their
  boundaries (``_sweep``); it gives the labels of ``trace_reduce``'s
  quadratic scan;
* the seconds of each ``dfo.*`` name, per thread (a span nested in one of
  its own name counts once) and summed over the threads, in the whole
  window and by the bench label open where each span starts; and the
  same less the span's direct ``dfo.*`` children on its own thread (self
  time);
* device time under each name-stack scope: the union of the op
  intervals, per device, averaged over the devices.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import heapq
import os
import tempfile

import numpy as np

from bench import trace_reduce as tr

PREFIX = "dfo."
JAX_EVENTS = (("backend_compile", "jax.compile"),
              ("lower_sharding_computation", "jax.lower"))
OP_NAME_STAT = "tf_op"
# name-stack wrappers a transform puts around a scope: vmap(combine)
_TRANSFORMS = ("vmap", "pmap", "jvp", "transpose", "remat", "checkpoint",
               "shard_map")


@dataclasses.dataclass
class ProgramTrace:
    window: tuple                 # (w0, w1) in ns
    devices: int
    idle_by_span: dict            # "<bench>/<program>" -> device idle s
    busy_by_span: dict            # "<bench>/<program>" -> device busy s
    span_s: dict                  # dfo name -> seconds over all threads
    span_s_by_label: dict         # "<bench>/<dfo name>" -> seconds
    self_s_by_label: dict         # "<bench>/<dfo name>" -> self seconds
    # per device: (starts, ends, scope paths) of the XLA ops in the
    # window, clipped to it; None for an op without a ``tf_op`` stat
    ops: list

    @property
    def scope_s(self) -> dict:
        """Scope path (``combine/while/body``) -> device seconds, ops
        summed, averaged over the devices; ops without a ``tf_op`` stat
        are left out."""
        out = collections.Counter()
        for starts, ends, paths in self.ops:
            for a, b, p in zip(starts, ends, paths):
                if p is not None:
                    out[p] += (b - a) * 1e-9
        return {k: v / self.devices for k, v in out.items()}

    def scope_busy_s(self, scope: str) -> float:
        """Device seconds under ``scope`` (any level of the path): the
        union of its ops' intervals per device, averaged."""
        total = 0.0
        for starts, ends, paths in self.ops:
            pick = np.asarray([p is not None and scope in p.split("/")
                               for p in paths], bool)
            if pick.any():
                us, ue = tr._union(np.asarray(starts)[pick],
                                   np.asarray(ends)[pick])
                total += float((ue - us).sum())
        return total / self.devices * 1e-9


def _cuts(spans, w0, w1) -> list:
    return sorted({w0, w1, *[t for _, a, b in spans for t in (a, b)
                             if w0 < t < w1]})


def _sweep(spans, cuts, default) -> list:
    """The label of each piece between ``cuts``: the name of the
    innermost of ``spans`` (``(name, start, end)``) open at its midpoint,
    the latest to start and the later in ``spans`` on a tie; ``default``
    where none is open."""
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    heap, j, labels = [], 0, []
    for a, b in zip(cuts[:-1], cuts[1:]):
        mid = (a + b) / 2
        while j < len(order) and spans[order[j]][1] <= mid:
            heapq.heappush(heap, (-spans[order[j]][1], -order[j]))
            j += 1
        while heap and spans[-heap[0][1]][2] <= mid:
            heapq.heappop(heap)
        labels.append(spans[-heap[0][1]][0] if heap else default)
    return labels


def _host_kinds(plane) -> dict:
    """Event metadata id -> ``(kind, label)`` of the host events this
    module reads: ``bench``, ``dfo`` or ``jax`` (compile and lowering)."""
    kinds = {}
    for e in plane.event_metadata:
        name = e.value.name
        if name.startswith(tr.SPAN_PREFIX):
            kinds[e.key] = ("bench", name[len(tr.SPAN_PREFIX):])
        elif name.startswith(PREFIX):
            kinds[e.key] = ("dfo", name[len(PREFIX):])
        else:
            for prefix, label in JAX_EVENTS:
                if name.startswith(prefix):
                    kinds[e.key] = ("jax", label)
    return kinds


def _op_scopes(plane) -> dict:
    """Event metadata id -> scope path of the op's ``tf_op`` stat."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    op_stat = [k for k, v in stat_names.items() if v == OP_NAME_STAT]
    scopes = {}
    for e in plane.event_metadata:
        for st in e.value.stats:
            if op_stat and st.metadata_id == op_stat[0]:
                scopes[e.key] = scope_path(
                    st.str_value or stat_names.get(st.ref_value, ""))
    return scopes


def _span(line, ev) -> tuple:
    """An event's ``(start, end)`` in ns, as ``ProfileData`` gives them:
    whole nanoseconds, as floats."""
    a = float(line.timestamp_ns + ev.offset_ps // 1000)
    return a, a + float(ev.duration_ps // 1000)


def _thread_tables(spans, w0, w1, label_at):
    """Seconds, seconds by bench label and self seconds by bench label of
    one thread's ``dfo.*`` spans ``(name, start, end)``, clipped to the
    window.  A span inside one of its own name adds no seconds (its outer
    span holds them)."""
    span_s, by_label, self_by = (collections.Counter() for _ in range(3))
    clipped = sorted(((n, max(a, w0), min(b, w1))
                      for n, a, b in spans if b > w0 and a < w1),
                     key=lambda s: (s[1], -s[2]))
    keys = [f"{label_at(a)}/{n}" for n, a, _ in clipped]
    stack = []
    for i, (name, a, b) in enumerate(clipped):
        while stack and clipped[stack[-1]][2] <= a:
            stack.pop()
        if stack:                       # the parent loses it from self
            self_by[keys[stack[-1]]] -= (b - a) * 1e-9
        repeat = any(clipped[j][0] == name for j in stack)
        stack.append(i)
        self_by[keys[i]] += (b - a) * 1e-9
        if not repeat:
            span_s[name] += (b - a) * 1e-9
            by_label[keys[i]] += (b - a) * 1e-9
    return span_s, by_label, self_by


def reduce_xspace(xspace: bytes) -> ProgramTrace:
    """Reduce a serialized XSpace."""
    space = _xspace_class()()
    space.ParseFromString(xspace)
    bench, threads, device_ops, main = [], [], [], None
    for plane in space.planes:
        if tr.DEVICE_PLANE.match(plane.name):
            scopes = _op_scopes(plane)
            ops = [(*_span(line, ev), scopes.get(ev.metadata_id))
                   for line in plane.lines if line.name == tr.OPS_LINE
                   for ev in line.events]
            device_ops.append(ops)
            continue
        if not plane.name.startswith("/host:"):
            continue
        kinds = _host_kinds(plane)
        for line in plane.lines:
            own, jax_ev = [], []
            for ev in line.events:
                kind = kinds.get(ev.metadata_id)
                if kind is None:
                    continue
                span = (kind[1], *_span(line, ev))
                if kind[0] == "bench":
                    bench.append(span)
                    if kind[1] == tr.WINDOW:
                        main = len(threads)
                else:
                    (own if kind[0] == "dfo" else jax_ev).append(span)
            threads.append((own, jax_ev))
    windows = [(a, b) for n, a, b in bench if n == tr.WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {tr.SPAN_PREFIX}{tr.WINDOW} span")
    if not device_ops:
        raise ValueError("the trace has no device plane")
    w0, w1 = windows[0]
    inner = [s for s in bench if s[0] != tr.WINDOW and s[1] < w1 and s[2] > w0]
    bench_a, _, bench_label = tr._label_segments(inner, w0, w1)
    bench_a = bench_a.tolist()

    def label_at(t):
        return bench_label[max(bisect.bisect_right(bench_a, t) - 1, 0)]

    own, jax_ev = threads[main]
    # outer before inner where two start together
    program = sorted((s for s in own + jax_ev if s[1] < w1 and s[2] > w0),
                     key=lambda s: (s[1], -s[2]))
    cuts = _cuts(inner + program, w0, w1)
    seg_a, seg_b = np.asarray(cuts[:-1]), np.asarray(cuts[1:])
    keys = []
    for a, b, p in zip(cuts[:-1], cuts[1:], _sweep(program, cuts, None)):
        label = label_at((a + b) / 2)
        keys.append(f"{label}/{p}" if p else label)

    busy_by, idle_by = collections.Counter(), collections.Counter()
    clipped = []
    for ops in device_ops:
        inside = [(max(a, w0), min(b, w1), p) for a, b, p in ops
                  if b > w0 and a < w1]
        starts, ends, paths = (list(c) for c in zip(*inside)) if inside \
            else ([], [], [])
        clipped.append((starts, ends, paths))
        us, ue = tr._union(np.asarray(starts, float), np.asarray(ends, float))
        seg_busy = (tr._covered(us, ue, seg_b) - tr._covered(us, ue, seg_a)
                    if us.size else np.zeros(seg_a.size))
        for key, a, b, busy in zip(keys, seg_a, seg_b, seg_busy):
            busy_by[key] += busy
            idle_by[key] += (b - a) - busy
    n = len(device_ops)

    span_s, by_label, self_by = (collections.Counter() for _ in range(3))
    for own, _ in threads:
        if own:
            for total, part in zip((span_s, by_label, self_by),
                                   _thread_tables(own, w0, w1, label_at)):
                total.update(part)
    return ProgramTrace(
        window=(w0, w1), devices=n,
        idle_by_span={k: v / n * 1e-9 for k, v in idle_by.items()},
        busy_by_span={k: v / n * 1e-9 for k, v in busy_by.items()},
        span_s=dict(span_s), span_s_by_label=dict(by_label),
        self_s_by_label=dict(self_by), ops=clipped)


def reduce_trace(path: str) -> ProgramTrace:
    """Reduce the ``.xplane.pb`` at ``path`` (or the newest under it)."""
    if os.path.isdir(path):
        path = tr.find_xspace(path)
    with open(path, "rb") as f:
        return reduce_xspace(f.read())


def for_window(window):
    """The program trace of a traced run's window, reduced once and kept
    on the window; None for an untraced window, or where no trace under
    the run's temporary directory has this window's length."""
    if window.trace is None:
        return None
    if not hasattr(window, "program_trace"):
        window.program_trace = _find(window.trace.window_s)
    return window.program_trace


def _find(window_s: float):
    """The newest profile under ``<tmp>/bench-*/trace`` (where the harness
    writes a traced run's profile; it hands the readers the reduced
    ``TraceSummary`` and not the file) whose window lasts ``window_s``:
    the window is ``trace_reduce``'s to the bit, so another run's profile,
    still on disk, is told apart."""
    found = glob.glob(os.path.join(tempfile.gettempdir(), "bench-*",
                                   "trace", "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        pt = reduce_trace(path)
        w0, w1 = pt.window
        if (w1 - w0) * 1e-9 == window_s:
            return pt
    return None


def per_pe_ms(window, *names, self_time=False):
    """Milliseconds of the ``dfo.*`` spans ``names`` that start inside
    the window's ProcessEdges spans, per ProcessEdges call; with
    ``self_time``, less their direct ``dfo.*`` children.  None where the
    trace has none of them (a program without these spans)."""
    pt = for_window(window)
    s = window.spans
    if pt is None or s is None or not s.count("process_edges"):
        return None
    table = pt.self_s_by_label if self_time else pt.span_s_by_label
    found = [table[k] for k in (f"process_edges/{n}" for n in names)
             if k in table]
    if not found:
        return None
    return 1e3 * sum(found) / s.count("process_edges")


# ---------------------------------------------------------------------------
# Name stacks, and the XSpace protobuf
# ---------------------------------------------------------------------------

def scope_path(op_name: str) -> str:
    """The name-stack scopes of an op's ``tf_op`` stat, outermost first:
    the program (``jit(step)``) and the primitive are dropped, and a
    transform's wrapper is peeled (``vmap(combine)`` gives ``combine``,
    ``vmap()`` nothing).  ``jit(step)/vmap(dispatch)/while/body/add:``
    gives ``dispatch/while/body``."""
    head = op_name.rsplit(":", 1)[0] if op_name.endswith(":") else op_name
    parts = _split(head)[1:-1]
    out = []
    for part in parts:
        out.extend(_peel(part))
    return "/".join(out)


def _split(path: str) -> list:
    """Split at the slashes outside parentheses."""
    parts, depth, cur = [], 0, []
    for ch in path:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    return parts


def _peel(part: str) -> list:
    for t in _TRANSFORMS:
        if part.startswith(t + "(") and part.endswith(")"):
            inner = part[len(t) + 1:-1]
            return [p for q in _split(inner) for p in _peel(q)] if inner \
                else []
    return [part] if part else []


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The XSpace message (tsl/profiler/protobuf/xplane.proto), declared
    with the fields this module reads; its maps as repeated entries."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    pkg = ".bench_xplane."

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, num, ftype, rep, tname in fields:
            f = m.field.add(name=fname, number=num, type=ftype,
                            label=F.LABEL_REPEATED if rep
                            else F.LABEL_OPTIONAL)
            if tname:
                f.type_name = pkg + tname

    i64, u64, s, msg = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING, \
        F.TYPE_MESSAGE
    message("XSpace", ("planes", 1, msg, True, "XPlane"))
    message("XPlane", ("name", 2, s, False, None),
            ("lines", 3, msg, True, "XLine"),
            ("event_metadata", 4, msg, True, "EventMetadataEntry"),
            ("stat_metadata", 5, msg, True, "StatMetadataEntry"))
    message("EventMetadataEntry", ("key", 1, i64, False, None),
            ("value", 2, msg, False, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, i64, False, None),
            ("value", 2, msg, False, "XStatMetadata"))
    message("XLine", ("name", 2, s, False, None),
            ("timestamp_ns", 3, i64, False, None),
            ("events", 4, msg, True, "XEvent"))
    message("XEvent", ("metadata_id", 1, i64, False, None),
            ("offset_ps", 2, i64, False, None),
            ("duration_ps", 3, i64, False, None))
    message("XEventMetadata", ("id", 1, i64, False, None),
            ("name", 2, s, False, None), ("stats", 5, msg, True, "XStat"))
    message("XStat", ("metadata_id", 1, i64, False, None),
            ("str_value", 5, s, False, None),
            ("ref_value", 7, u64, False, None))
    message("XStatMetadata", ("id", 1, i64, False, None),
            ("name", 2, s, False, None))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))
