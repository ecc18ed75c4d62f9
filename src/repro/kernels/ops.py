"""Jitted public wrappers for the Pallas kernels.

``interpret`` defaults to the backend: kernels execute via the Pallas
interpreter off-TPU and lower through Mosaic on a TPU, so benchmarks
measure the compiled kernel where it exists.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.csr_spmv import (  # noqa: F401
    block_csr_combine, block_csr_spmv, build_block_csr, build_tile_struct,
    default_interpret,
)
from repro.kernels.flash_attention import flash_attention  # noqa: F401
from repro.kernels.gla_chunk import gla_chunked  # noqa: F401


def spmv(graph_blocks: dict, x: jnp.ndarray, *, tile: int,
         interpret: bool | None = None) -> jnp.ndarray:
    """Block-CSR SpMV over a prebuilt ``build_block_csr`` structure."""
    return block_csr_spmv(
        jnp.asarray(graph_blocks["tiles"]),
        jnp.asarray(graph_blocks["tile_col"]),
        jnp.asarray(graph_blocks["row_ptr"]),
        jnp.asarray(x, jnp.float32),
        tile=tile,
        max_tiles_per_row=graph_blocks["max_tiles_per_row"],
        interpret=interpret)


def attention(q, k, v, *, causal=True, window=0, softcap=0.0,
              interpret: bool | None = None):
    return flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        interpret=interpret)


def gla(q, k, v, w, u=None, *, chunk=64, include_current=True,
        interpret: bool | None = None):
    return gla_chunked(
        q, k, v, w, u, chunk=chunk, include_current=include_current,
        interpret=interpret)
