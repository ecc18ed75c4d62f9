"""Storage layer (``core/chunkstore.py``): time the out-of-core executor's
main thread waits on the chunk prefetch queue (``dfo.ooc.stream_wait``),
per ProcessEdges call, from the trace.  High when chunk reads and decodes
set the pace of the stream; near zero when the combine does."""
from bench import program_trace


def read(window):
    return program_trace.per_pe_ms(window, "ooc.stream_wait")
