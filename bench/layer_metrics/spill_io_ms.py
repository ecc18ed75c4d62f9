"""Storage layer (``core/chunkstore.py``): main-thread time in the vertex
spill's reads and writes inside ProcessEdges (``dfo.spill.read`` and
``dfo.spill.write``: active bitmap, generate-time and apply-time vertex
batches), per ProcessEdges call, from the trace."""
from bench import program_trace


def read(window):
    return program_trace.per_pe_ms(window, "spill.read", "spill.write")
