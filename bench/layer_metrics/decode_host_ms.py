"""Kernels layer (``kernels/varint.py``): prefetch-thread wall time of the
chunk decode (``dfo.chunk.decode``: the device decode's dispatches and
syncs, or the host codec, and the batch's assembly), per ProcessEdges
call, from the trace; ``decode_kernel_ms`` is the device's share of it."""
from bench import program_trace


def read(window):
    return program_trace.per_pe_ms(window, "chunk.decode")
