"""Kernels layer (LOCAL's jitted ProcessEdges step): device time of the ops
that carry the step's ``combine`` scope in their name stack, per
ProcessEdges call, from the trace; a part of ``step_device_ms``.

On a v5e these are the per-edge gathers of the messages (``jit(_take)``)
and a few small element-wise ops.  The segment reductions of the combine
are not in it: XLA's TPU scatter rewrite leaves their fusions with no
name stack, so the trace cannot tell them from the step's other unnamed
ops."""
from bench import program_trace


def read(window):
    pt = program_trace.for_window(window)
    s = window.spans
    if pt is None or s is None or not s.count("process_edges"):
        return None
    busy = pt.scope_busy_s("combine")
    if not busy:
        return None
    return 1e3 * busy / s.count("process_edges")
