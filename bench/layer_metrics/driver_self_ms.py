"""Driver layer (``core/algorithms.py``): the job spans' time outside the
engine's ProcessEdges and ProcessVertices spans (host syncs, counter
accumulation, state set-up, the final gather), per ProcessEdges call."""


def read(window):
    s = window.spans
    if s is None or not s.count("process_edges"):
        return None
    self_s = (s.total("job") - s.total("process_edges")
              - s.total("process_vertices"))
    return 1e3 * self_s / s.count("process_edges")
