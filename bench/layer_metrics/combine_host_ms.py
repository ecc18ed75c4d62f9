"""Engine layer (``core/executor.py``): main-thread time in the out-of-core
combine of the streamed batches (``dfo.ooc.combine``: the numpy segment
scatter, or the Pallas block-CSR call), per ProcessEdges call, from the
trace."""
from bench import program_trace


def read(window):
    return program_trace.per_pe_ms(window, "ooc.combine")
