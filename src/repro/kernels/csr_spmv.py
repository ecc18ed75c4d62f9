"""Block-sparse SpMV / combine Pallas kernels — the FOOC processing hot loop.

Paper §4.1's CSR/DCSR edge chunks are a disk format; the TPU-native compute
format is **block-CSR**: the (dst batch x src partition) adjacency is tiled
into dense T x T blocks, only nonempty tiles are stored, and each tile is an
MXU matmul (ADD monoid) or a VPU masked extremum (MIN/MAX monoid).  This is
the hardware adaptation of "narrow the span of random access": the
destination accumulator block lives in VMEM for the whole row sweep (the
paper's vertex batch), and source-vector blocks stream in HBM -> VMEM
selected by the tile's column index (the paper's message file reads) via
scalar-prefetch-driven BlockSpecs.

Two kernels:

* ``block_csr_spmv`` — the original rectangular-storage matmul SpMV (kept as
  the standalone kernel the microbenchmarks and kernel tests exercise).
* ``block_csr_combine`` — the engine's ProcessEdges phase-4 kernel
  (DESIGN.md §4): generalizes the tile combine to the add/min/max monoids,
  produces the per-vertex has-message counts alongside the aggregate, and is
  **selective**: the caller passes runtime-compacted ``tile_idx``/``tile_col``
  arrays plus per-row live counts (``row_cnt``) so tiles whose (src
  partition, dst batch) chunk received no messages are zero-skipped — the
  grid row pointer sweeps live tiles only, matching the paper's "only active
  chunks are read" I/O claim on the compute side.

TPU layout: Mosaic tiles the last two dims of every block by (8, 128)
unless a block dim spans the whole array dim, so vectors travel as
``[blocks, 1, T]`` arrays with ``(1, 1, T)`` blocks (the wrappers reshape
the flat ``[n * T]`` vectors callers pass), and each tile matvec runs at
``HIGHEST`` precision so the MXU keeps full f32.

``interpret`` defaults to the backend: the Pallas interpreter off-TPU,
Mosaic lowering on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def default_interpret() -> bool:
    """Interpret off-TPU, compile on TPU: the backend alone decides."""
    return jax.default_backend() != "tpu"


def _dot(tile, x):
    """[T, T] tile times a [T] vector, full f32 on the MXU."""
    return jnp.dot(tile, x, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# Standalone rectangular block-CSR SpMV (microbenchmark / reference kernel)
# ---------------------------------------------------------------------------

def _kernel(row_ptr_ref, col_ref, tiles_ref, x_ref, out_ref):
    """One (row block r, tile slot j) grid step.

    tiles_ref block: [T, T]    — tile j of row r (zero tile if padding)
    x_ref block:     [1, 1, T] — source block selected by col[row_ptr[r]+j]
    out_ref block:   [1, 1, T] — dst accumulator (revisited across j)
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0, 0] += _dot(tiles_ref[...], x_ref[0, 0])


@functools.partial(jax.jit,
                   static_argnames=("tile", "max_tiles_per_row",
                                    "interpret"))
def block_csr_spmv(tiles: jnp.ndarray, tile_col: jnp.ndarray,
                   row_ptr: jnp.ndarray, x: jnp.ndarray, *,
                   tile: int, max_tiles_per_row: int,
                   interpret: bool | None = None) -> jnp.ndarray:
    """tiles: [n_tiles, T, T] f32 (padded so every row has exactly
    ``max_tiles_per_row`` tiles); tile_col: [n_tiles] i32 source block ids;
    row_ptr: [n_rows + 1] i32; x: [n_src_blocks * T] f32.
    Returns out: [n_rows * T] f32."""
    if interpret is None:
        interpret = default_interpret()
    n_rows = row_ptr.shape[0] - 1
    t = tile

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # row_ptr, tile_col
        grid=(n_rows, max_tiles_per_row),
        in_specs=[
            pl.BlockSpec((1, t, t),
                         lambda r, j, row_ptr, col: (row_ptr[r] + j, 0, 0)),
            pl.BlockSpec((1, 1, t),
                         lambda r, j, row_ptr, col:
                         (col[row_ptr[r] + j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, t),
                               lambda r, j, row_ptr, col: (r, 0, 0)),
    )

    def kernel(row_ptr_ref, col_ref, tiles_ref, x_ref, out_ref):
        _kernel(row_ptr_ref, col_ref, tiles_ref[0], x_ref, out_ref)

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, 1, t), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(row_ptr, tile_col, tiles, x.reshape(-1, 1, t))
    return out.reshape(-1)


# ---------------------------------------------------------------------------
# Monoid-generalized selective combine kernel (the engine's phase 4)
# ---------------------------------------------------------------------------

def _make_combine_kernel(mode: str, identity: float):
    """Kernel body for one (row block r, live tile slot j) grid step.

    Scalar-prefetch refs: row_ptr [R+1] (static slot layout), tile_idx [S]
    (runtime-compacted storage index per slot — live tiles first within each
    row), tile_col [S] (source block per compacted slot), row_cnt [R] (live
    tiles this row; slots j >= row_cnt[r] are skipped).

    Tensor refs depend on mode:
      add:    tiles_v, tiles_cnt, xv, xc          -> val += V@xv ; hc += C@xc
      add_b:  tiles_v, tiles_b, tiles_cnt, xv, xc -> val += V@xv + B@xc
      min/max: tiles_b, tiles_cnt, xv, xc
              -> val = comb(val, row-comb(B + xv)) ; hc += C@xc
    where xv is the (slot-transformed, presence-masked) message vector and
    xc the float presence mask; absent entries of xv carry the monoid
    identity (extremum modes) or 0 (add modes).
    """
    comb = {"min": jnp.minimum, "max": jnp.maximum}.get(mode)

    def init(val_ref, hc_ref):
        val_ref[...] = jnp.full_like(val_ref, identity)
        hc_ref[...] = jnp.zeros_like(hc_ref)

    if mode == "add":
        def kernel(rp_ref, idx_ref, col_ref, cnt_ref,
                   tv_ref, tc_ref, xv_ref, xc_ref, val_ref, hc_ref):
            r, j = pl.program_id(0), pl.program_id(1)

            @pl.when(j == 0)
            def _():
                init(val_ref, hc_ref)

            @pl.when(j < cnt_ref[r])
            def _():
                val_ref[0, 0] += _dot(tv_ref[0], xv_ref[0, 0])
                hc_ref[0, 0] += _dot(tc_ref[0], xc_ref[0, 0])
        return kernel

    if mode == "add_b":
        def kernel(rp_ref, idx_ref, col_ref, cnt_ref,
                   tv_ref, tb_ref, tc_ref, xv_ref, xc_ref, val_ref, hc_ref):
            r, j = pl.program_id(0), pl.program_id(1)

            @pl.when(j == 0)
            def _():
                init(val_ref, hc_ref)

            @pl.when(j < cnt_ref[r])
            def _():
                val_ref[0, 0] += (_dot(tv_ref[0], xv_ref[0, 0])
                                  + _dot(tb_ref[0], xc_ref[0, 0]))
                hc_ref[0, 0] += _dot(tc_ref[0], xc_ref[0, 0])
        return kernel

    reduce = jnp.min if mode == "min" else jnp.max

    def kernel(rp_ref, idx_ref, col_ref, cnt_ref,
               tb_ref, tc_ref, xv_ref, xc_ref, val_ref, hc_ref):
        r, j = pl.program_id(0), pl.program_id(1)

        @pl.when(j == 0)
        def _():
            init(val_ref, hc_ref)

        @pl.when(j < cnt_ref[r])
        def _():
            contrib = tb_ref[0] + xv_ref[0]                  # [T, T]
            val_ref[0, 0] = comb(val_ref[0, 0], reduce(contrib, axis=1))
            hc_ref[0, 0] += _dot(tc_ref[0], xc_ref[0, 0])
    return kernel


@functools.partial(jax.jit,
                   static_argnames=("mode", "tile", "max_tiles_per_row",
                                    "identity", "interpret"))
def block_csr_combine(row_ptr, tile_idx, tile_col, row_cnt,
                      tiles_v, tiles_b, tiles_cnt, xv, xc, *,
                      mode: str, tile: int, max_tiles_per_row: int,
                      identity: float = 0.0,
                      interpret: bool | None = None):
    """Selective monoid combine over runtime-compacted block-CSR tiles.

    row_ptr [R+1] i32: static slot offsets per destination row block.
    tile_idx [S] i32: storage tile per compacted slot (live-first per row).
    tile_col [S] i32: source block id per compacted slot.
    row_cnt [R] i32: live tiles per row; the j grid dim skips the rest.
    tiles_v / tiles_b [S, T, T] f32 or None depending on ``mode``
      (add: tiles_v; add_b: tiles_v + tiles_b; min/max: tiles_b).
    tiles_cnt [S, T, T] f32: per-cell valid-edge multiplicities.
    xv [C * T] f32: slot-transformed masked messages (identity where absent).
    xc [C * T] f32: 0/1 message-presence mask.
    (Vectors are re-laid as [C, 1, T] for the kernel; see the module
    docstring.)

    Returns (val [R*T] f32 — monoid aggregate, identity where nothing
    arrived; hascnt [R*T] f32 — number of live edges that delivered)."""
    if interpret is None:
        interpret = default_interpret()
    t = tile
    n_rows = row_ptr.shape[0] - 1
    n_slots = tile_idx.shape[0]

    def slot(r, j, rp, idx, col, cnt):
        return jnp.minimum(rp[r] + j, n_slots - 1)

    tile_spec = pl.BlockSpec(
        (1, t, t), lambda r, j, rp, idx, col, cnt:
        (idx[slot(r, j, rp, idx, col, cnt)], 0, 0))
    vec_spec = pl.BlockSpec(
        (1, 1, t), lambda r, j, rp, idx, col, cnt:
        (col[slot(r, j, rp, idx, col, cnt)], 0, 0))
    out_spec = pl.BlockSpec((1, 1, t),
                            lambda r, j, rp, idx, col, cnt: (r, 0, 0))
    xv, xc = xv.reshape(-1, 1, t), xc.reshape(-1, 1, t)

    if mode == "add":
        tensors = (tiles_v, tiles_cnt, xv, xc)
        in_specs = [tile_spec, tile_spec, vec_spec, vec_spec]
    elif mode == "add_b":
        tensors = (tiles_v, tiles_b, tiles_cnt, xv, xc)
        in_specs = [tile_spec, tile_spec, tile_spec, vec_spec, vec_spec]
    elif mode in ("min", "max"):
        tensors = (tiles_b, tiles_cnt, xv, xc)
        in_specs = [tile_spec, tile_spec, vec_spec, vec_spec]
    else:
        raise ValueError(mode)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # row_ptr, tile_idx, tile_col, row_cnt
        grid=(n_rows, max_tiles_per_row),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
    )

    val, hc = pl.pallas_call(
        _make_combine_kernel(mode, identity),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_rows, 1, t), jnp.float32),
                   jax.ShapeDtypeStruct((n_rows, 1, t), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(row_ptr, tile_idx, tile_col, row_cnt, *tensors)
    return val.reshape(-1), hc.reshape(-1)


# ---------------------------------------------------------------------------
# Multi-query value panels: [tile] -> [tile, Q] (DESIGN.md §11)
# ---------------------------------------------------------------------------

def _make_combine_kernel_mq(mode: str, identity: float, num_queries: int):
    """Panel variant of :func:`_make_combine_kernel`: the tile refs are the
    shared decoded chunk structure, the vector refs carry one column per
    query ([T, Q] blocks), and the per-query column ops are unrolled so each
    column runs exactly the single-vector kernel's op sequence (per-column
    gemv / masked extremum) — bit-identical to Q separate kernel calls over
    the same tiles, which is what makes "one decode feeds Q combines" safe
    to assert in the parity suite."""
    comb = {"min": jnp.minimum, "max": jnp.maximum}.get(mode)
    nq = num_queries

    def init(val_ref, hc_ref):
        val_ref[...] = jnp.full_like(val_ref, identity)
        hc_ref[...] = jnp.zeros_like(hc_ref)

    if mode == "add":
        def kernel(rp_ref, idx_ref, col_ref, cnt_ref,
                   tv_ref, tc_ref, xv_ref, xc_ref, val_ref, hc_ref):
            r, j = pl.program_id(0), pl.program_id(1)

            @pl.when(j == 0)
            def _():
                init(val_ref, hc_ref)

            @pl.when(j < cnt_ref[r])
            def _():
                for c in range(nq):
                    val_ref[:, c] += _dot(tv_ref[0], xv_ref[:, c])
                    hc_ref[:, c] += _dot(tc_ref[0], xc_ref[:, c])
        return kernel

    if mode == "add_b":
        def kernel(rp_ref, idx_ref, col_ref, cnt_ref,
                   tv_ref, tb_ref, tc_ref, xv_ref, xc_ref, val_ref, hc_ref):
            r, j = pl.program_id(0), pl.program_id(1)

            @pl.when(j == 0)
            def _():
                init(val_ref, hc_ref)

            @pl.when(j < cnt_ref[r])
            def _():
                for c in range(nq):
                    val_ref[:, c] += (_dot(tv_ref[0], xv_ref[:, c])
                                      + _dot(tb_ref[0], xc_ref[:, c]))
                    hc_ref[:, c] += _dot(tc_ref[0], xc_ref[:, c])
        return kernel

    reduce = jnp.min if mode == "min" else jnp.max

    def kernel(rp_ref, idx_ref, col_ref, cnt_ref,
               tb_ref, tc_ref, xv_ref, xc_ref, val_ref, hc_ref):
        r, j = pl.program_id(0), pl.program_id(1)

        @pl.when(j == 0)
        def _():
            init(val_ref, hc_ref)

        @pl.when(j < cnt_ref[r])
        def _():
            for c in range(nq):
                contrib = tb_ref[0] + xv_ref[:, c][None, :]      # [T, T]
                val_ref[:, c] = comb(val_ref[:, c],
                                     reduce(contrib, axis=1))
                hc_ref[:, c] += _dot(tc_ref[0], xc_ref[:, c])
    return kernel


@functools.partial(jax.jit,
                   static_argnames=("mode", "tile", "max_tiles_per_row",
                                    "num_queries", "identity", "interpret"))
def block_csr_combine_mq(row_ptr, tile_idx, tile_col, row_cnt,
                         tiles_v, tiles_b, tiles_cnt, xv, xc, *,
                         mode: str, tile: int, max_tiles_per_row: int,
                         num_queries: int, identity: float = 0.0,
                         interpret: bool | None = None):
    """:func:`block_csr_combine` over Q-column value panels.

    Same tile structure arguments; ``xv`` / ``xc`` are [C * T, Q] panels
    (one slot-transformed message column + presence column per query) and
    the outputs are [R * T, Q] panels.  The decoded tiles are read once per
    grid step and combined against all Q columns, which is the multi-query
    amortization at the kernel level; each column's result is bit-identical
    to a single-query :func:`block_csr_combine` call with that column."""
    if interpret is None:
        interpret = default_interpret()
    t = tile
    nq = num_queries
    n_rows = row_ptr.shape[0] - 1
    n_slots = tile_idx.shape[0]

    def slot(r, j, rp, idx, col, cnt):
        return jnp.minimum(rp[r] + j, n_slots - 1)

    tile_spec = pl.BlockSpec(
        (1, t, t), lambda r, j, rp, idx, col, cnt:
        (idx[slot(r, j, rp, idx, col, cnt)], 0, 0))
    vec_spec = pl.BlockSpec(
        (t, nq), lambda r, j, rp, idx, col, cnt:
        (col[slot(r, j, rp, idx, col, cnt)], 0))
    out_spec = pl.BlockSpec((t, nq),
                            lambda r, j, rp, idx, col, cnt: (r, 0))

    if mode == "add":
        tensors = (tiles_v, tiles_cnt, xv, xc)
        in_specs = [tile_spec, tile_spec, vec_spec, vec_spec]
    elif mode == "add_b":
        tensors = (tiles_v, tiles_b, tiles_cnt, xv, xc)
        in_specs = [tile_spec, tile_spec, tile_spec, vec_spec, vec_spec]
    elif mode in ("min", "max"):
        tensors = (tiles_b, tiles_cnt, xv, xc)
        in_specs = [tile_spec, tile_spec, vec_spec, vec_spec]
    else:
        raise ValueError(mode)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # row_ptr, tile_idx, tile_col, row_cnt
        grid=(n_rows, max_tiles_per_row),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
    )

    return pl.pallas_call(
        _make_combine_kernel_mq(mode, identity, nq),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_rows * t, nq), jnp.float32),
                   jax.ShapeDtypeStruct((n_rows * t, nq), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(row_ptr, tile_idx, tile_col, row_cnt, *tensors)


# ---------------------------------------------------------------------------
# Host-side structure builders
# ---------------------------------------------------------------------------

def build_tile_struct(row_blk: np.ndarray, col_blk: np.ndarray,
                      n_row_blocks: int, n_col_blocks: int):
    """Edge block coordinates -> ragged tile structure sorted by (row, col).

    Returns (slot_row [S] i32, slot_col [S] i32, row_ptr [R+1] i32,
    edge_slot [E] i32 — which slot each edge's cell belongs to)."""
    key = row_blk.astype(np.int64) * n_col_blocks + col_blk.astype(np.int64)
    uniq, inv = np.unique(key, return_inverse=True)
    slot_row = (uniq // n_col_blocks).astype(np.int32)
    slot_col = (uniq % n_col_blocks).astype(np.int32)
    counts = np.bincount(slot_row, minlength=n_row_blocks)
    row_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return slot_row, slot_col, row_ptr, inv.astype(np.int32)


def compact_live_tiles(slot_row: np.ndarray, slot_col: np.ndarray,
                       row_ptr: np.ndarray, live: np.ndarray,
                       n_rows: int):
    """Host-side mirror of the engine's runtime live-tile compaction.

    Packs live slots to the front of their row's slot range (the layout
    ``block_csr_combine`` expects): returns (tile_idx [S], tile_col [S],
    row_cnt [R]) with dead positions zeroed."""
    s = slot_row.shape[0]
    row_cnt = np.bincount(slot_row[live], minlength=n_rows).astype(np.int32)
    cnt_cum = np.concatenate([[0], np.cumsum(row_cnt)]).astype(np.int64)
    rank = np.cumsum(live) - live            # exclusive rank among live
    dest = np.where(live, row_ptr[slot_row] + (rank - cnt_cum[slot_row]), s)
    tile_idx = np.zeros((s,), np.int32)
    tile_col = np.zeros((s,), np.int32)
    keep = dest < s
    tile_idx[dest[keep]] = np.arange(s, dtype=np.int32)[keep]
    tile_col[dest[keep]] = slot_col[keep]
    return tile_idx, tile_col, row_cnt


def build_block_csr(src, dst, data, num_vertices: int, tile: int):
    """Host-side: edge list -> padded block-CSR (numpy).

    Returns dict(tiles [n, T, T] f32, tile_col [n] i32,
    row_ptr [n_rows+1] i32, n_rows, n_cols, max_tiles_per_row)."""
    t = tile
    n_blocks = -(-num_vertices // t)
    slot_row, slot_col, rp, edge_slot = build_tile_struct(
        np.asarray(dst) // t, np.asarray(src) // t, n_blocks, n_blocks)
    max_tiles = max(1, int((rp[1:] - rp[:-1]).max()) if n_blocks else 1)

    tiles = np.zeros((n_blocks * max_tiles, t, t), np.float32)
    tile_col = np.zeros((n_blocks * max_tiles,), np.int32)
    row_ptr = np.arange(0, n_blocks * max_tiles + 1, max_tiles,
                        dtype=np.int32)
    # rectangular re-layout: slot i of row r -> padded slot r*max_tiles + i
    padded_slot = (slot_row.astype(np.int64) * max_tiles
                   + (np.arange(slot_row.shape[0]) - rp[slot_row]))
    tile_col[padded_slot] = slot_col
    np.add.at(tiles,
              (padded_slot[edge_slot],
               np.asarray(dst) % t, np.asarray(src) % t),
              np.asarray(data, np.float32))
    return dict(tiles=tiles, tile_col=tile_col, row_ptr=row_ptr,
                n_rows=n_blocks, n_cols=n_blocks,
                max_tiles_per_row=max_tiles)
