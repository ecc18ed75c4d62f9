"""Kernels layer (LOCAL's jitted ProcessEdges step, XLA ops): device busy
time inside the ProcessEdges spans, per call, from the trace."""


def read(window):
    t = window.trace
    s = window.spans
    if t is None or s is None or not s.count("process_edges"):
        return None
    busy = t.busy_by_label.get("process_edges", 0.0)
    if not busy:
        return None
    return 1e3 * busy / s.count("process_edges")
