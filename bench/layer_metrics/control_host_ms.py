"""Engine layer (``core/executor.py``): main-thread time of the out-of-core
ProcessEdges phases other than the stream (``dfo.ooc.generate``,
``dfo.ooc.filter``, ``dfo.ooc.dispatch``, ``dfo.ooc.apply``: signal,
filter and network model, dispatch and format choice, apply), less the
vertex-spill I/O inside them, per ProcessEdges call, from the trace."""
from bench import program_trace

PHASES = ("ooc.generate", "ooc.filter", "ooc.dispatch", "ooc.apply")


def read(window):
    return program_trace.per_pe_ms(window, *PHASES, self_time=True)
