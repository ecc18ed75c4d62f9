"""Storage layer (``core/chunkstore.py``): bytes the store served, per
ProcessEdges call, from the audited counters (edge reads plus vertex-spill
reads and writes of ProcessEdges and ProcessVertices)."""

KEYS = ("measured_edge_read_bytes", "measured_vertex_read_bytes",
        "measured_vertex_write_bytes")


def read(window):
    vals = [window.counter(k) for k in KEYS]
    if any(v is None for v in vals) or not window.pe_calls:
        return None
    return sum(vals) / window.pe_calls
