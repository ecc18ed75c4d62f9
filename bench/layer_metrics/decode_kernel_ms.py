"""Kernels layer (``kernels/varint.py``): device time of the chunk-decode
programs (the jitted varint decode, delta restores and index expansions),
per ProcessEdges call, from the trace."""

MODULES = ("jit_varint_decode", "jit_pair_delta_restore",
           "jit_expand_dcsr_index", "jit_expand_csr_index",
           "jit_dst_delta_restore", "jit_blocked_scan")


def read(window):
    t = window.trace
    if t is None or not window.pe_calls:
        return None
    secs = sum(v for k, v in t.module_s.items() if k in MODULES)
    if not secs:
        return None
    return 1e3 * secs / t.devices / window.pe_calls
