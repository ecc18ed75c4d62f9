"""Storage layer (``core/chunkstore.py``): prefetch-thread time in the
chunk store's reads (``dfo.chunk.read``: the ``pread`` of the chosen
sections and their checksums), per ProcessEdges call, from the trace."""
from bench import program_trace


def read(window):
    return program_trace.per_pe_ms(window, "chunk.read")
