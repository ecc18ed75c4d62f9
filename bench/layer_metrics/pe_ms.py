"""Engine layer (``core/engine.py``, ``core/executor.py``): mean span of
one ``Engine.process_edges`` call, waiting for its outputs."""


def read(window):
    s = window.spans
    if s is None or not s.count("process_edges"):
        return None
    return 1e3 * s.total("process_edges") / s.count("process_edges")
