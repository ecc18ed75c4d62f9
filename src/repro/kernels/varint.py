"""Pallas varint/delta decode kernels — the device half of the compression
tier (DESIGN.md §9, §10).

The numpy codec in :mod:`repro.core.codec` decodes a compressed chunk with
three host-CPU bursts: LEB128 varint expansion, interleaved pair-delta
cumsums, and the per-run dst-residue restore.  These kernels move that
byte-level work onto the accelerator so the prefetched chunks of one dst
batch go bytes -> device buffers -> decode -> host in one chain of jit
dispatches and one sync — and without the compute token: the decode is
a few dispatches instead of a GIL-holding numpy burst (DESIGN.md §8).

Scope: the **int32 value domain** (values < 2**31, <= 5 varint groups) —
the same domain :func:`repro.core.codec.varint_sizes`'s jnp path prices,
and enough for every pair delta, dst residue, and wire gap the engine
encodes (jax runs with x64 disabled, so there is no uint64 on device).
The full-uint64 codec stays numpy-only; round-trip parity against it is
bit-exact on this domain (tests/test_varint_kernels.py).

Two Pallas kernels carry the per-byte work:

* a 5-tap **stencil decode kernel** (:func:`_decode_kernel`): per byte,
  find the distance to its varint's first byte — a static 5-way select
  over the terminator mask of the previous four bytes, haloed across
  block boundaries — and assemble the value from shifted 7-bit group
  reads.  No scan, no gather, no scatter inside the kernel.
* an op-parameterized **blocked scan kernel** (:func:`_make_scan_kernel`,
  add / running-max): sequential grid with an SMEM carry — the Pallas
  form of :func:`repro.core.sparse_collectives.blocked_cumsum`'s
  two-level idiom.  Reused for value placement (cumsum of the terminator
  mask), the pair-delta cumsums, and the run-structure restores, where a
  scatter + running-max forward fill replaces numpy's ``repeat``.

Byte streams travel as ``[nb, 1, BLK]`` arrays with ``(1, 1, BLK)``
blocks: Mosaic tiles a block's last two dims by (8, 128) unless they span
the whole array dim, which a ``(1, BLK)`` block of an ``[nb, BLK]`` array
does not.

Everything composes under jit; ``interpret`` auto-selects exactly like
:mod:`repro.kernels.csr_spmv` (interpret off-TPU, compile on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.csr_spmv import default_interpret
from repro.utils import ceil_div

_SCAN_BLK = 512         # lanes-multiple scan block
_DEC_BLK = 512          # lanes-multiple stencil block
_HALO = 4               # an int32-domain varint spans <= 5 bytes


# ---------------------------------------------------------------------------
# Blocked scan kernel (add / running-max), SMEM carry
# ---------------------------------------------------------------------------

def _make_scan_kernel(mode: str):
    """One grid step scans one [1, 1, BLK] block and threads the carry through
    an SMEM scalar; within the block a log-step shift-combine (the register
    form of blocked_cumsum's "cumsum within blocks") avoids a serial loop.
    Identity/carry seed is 0 for both modes — ``max`` therefore assumes
    nonnegative inputs, which every engine stream satisfies."""
    comb = jnp.add if mode == "add" else jnp.maximum

    def kernel(x_ref, out_ref, carry_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            carry_ref[0, 0] = 0

        x = x_ref[0]                                 # [1, BLK] int32
        blk = x.shape[1]
        ii = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        s = 1
        while s < blk:
            x = comb(x, jnp.where(ii >= s, jnp.roll(x, s, axis=1), 0))
            s *= 2
        out = comb(x, carry_ref[0, 0])
        out_ref[0] = out
        carry_ref[0, 0] = out[0, blk - 1]

    return kernel


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def blocked_scan(x: jnp.ndarray, *, mode: str = "add",
                 interpret: bool | None = None) -> jnp.ndarray:
    """Inclusive scan of an int32 vector on device.

    mode "add": cumulative sum; mode "max": running maximum (inputs must
    be nonnegative — the carry and shift identity are 0).  Tail padding to
    the block size is zeros, sliced off before returning."""
    if mode not in ("add", "max"):
        raise ValueError(mode)
    if interpret is None:
        interpret = default_interpret()
    n = x.shape[0]
    blk = _SCAN_BLK
    nb = max(1, ceil_div(n, blk))
    x3 = jnp.pad(x.astype(jnp.int32), (0, nb * blk - n)).reshape(nb, 1, blk)
    spec = pl.BlockSpec((1, 1, blk), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        _make_scan_kernel(mode),
        grid=(nb,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((nb, 1, blk), jnp.int32),
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(x3)
    return out.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# Varint stencil decode kernel
# ---------------------------------------------------------------------------

def _decode_kernel(cur_ref, prev_ref, term_ref, val_ref):
    """Per byte j: terminator flag + the value of the varint ending at j.

    The same byte array is passed twice — block i and block i-1 (clamped)
    — so the four-byte halo needed by the stencil is read without dynamic
    slicing.  Positions with negative global index (only reachable while
    i == 0, where "block i-1" aliases block 0) are forced to terminators:
    that clamps ``gpos`` at the stream start, and since group reads only
    go back ``gpos`` bytes, the aliased bytes are never selected."""
    i = pl.program_id(0)
    cur = cur_ref[0]                                 # [1, BLK] bytes as i32
    prev = prev_ref[0]
    blk = cur.shape[1]
    ext = jnp.concatenate([prev[:, blk - _HALO:], cur], axis=1)
    gext = (i * blk - _HALO
            + jax.lax.broadcasted_iota(jnp.int32, ext.shape, 1))
    term_ext = ((ext & 0x80) == 0) | (gext < 0)
    grp_ext = ext & 0x7F
    # distance from byte j to its varint's first byte: first d in 0..4
    # with byte j-1-d a terminator (5-way select over the halo)
    t = [term_ext[:, _HALO - 1 - d: 2 * _HALO - 1 - d + blk - _HALO]
         for d in range(_HALO)]
    gpos = jnp.where(t[0], 0,
                     jnp.where(t[1], 1,
                               jnp.where(t[2], 2,
                                         jnp.where(t[3], 3, 4))))
    gpos = gpos.astype(jnp.int32)
    # little-endian 7-bit groups: byte j-d holds group gpos-d of the value
    # ending at j; assemble in uint32 so a 5-group read cannot overflow
    val = jnp.zeros(cur.shape, jnp.uint32)
    for d in range(_HALO + 1):
        g = grp_ext[:, _HALO - d: _HALO - d + blk].astype(jnp.uint32)
        sh = (7 * jnp.maximum(gpos - d, 0)).astype(jnp.uint32)
        val = val + jnp.where(d <= gpos, jax.lax.shift_left(g, sh),
                              jnp.uint32(0))
    term_ref[0] = term_ext[:, _HALO:].astype(jnp.int32)
    val_ref[0] = val.astype(jnp.int32)


def _byte_stencil(b: jnp.ndarray, *, interpret: bool):
    """b: int32 [nb * _DEC_BLK] byte stream -> (term [N], val [N]) int32."""
    nb = b.shape[0] // _DEC_BLK
    b3 = b.reshape(nb, 1, _DEC_BLK)
    spec = pl.BlockSpec((1, 1, _DEC_BLK), lambda i: (i, 0, 0))
    term, val = pl.pallas_call(
        _decode_kernel,
        grid=(nb,),
        in_specs=[
            spec,
            pl.BlockSpec((1, 1, _DEC_BLK),
                         lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
        ],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((nb, 1, _DEC_BLK), jnp.int32),
                   jax.ShapeDtypeStruct((nb, 1, _DEC_BLK), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(b3, b3)
    return term.reshape(-1), val.reshape(-1)


@functools.partial(jax.jit, static_argnames=("count", "interpret"))
def varint_decode(buf: jnp.ndarray, nbytes, *, count: int,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Decode LEB128 varints (int32 domain) from a zero-right-padded buffer.

    buf: uint8/int32 [N] — the live stream occupies [0, nbytes); anything
    after is ignored.  ``count`` is static (callers pad to a per-store
    maximum); when the stream holds fewer than ``count`` varints the tail
    of the result stays 0.  Bit-identical to codec.varint_decode on values
    < 2**31.  Unlike the numpy codec this path does NOT validate the
    stream — corruption checks stay on the host read path, which is also
    where the byte counts are measured."""
    if interpret is None:
        interpret = default_interpret()
    b = jnp.asarray(buf).astype(jnp.int32)
    n = b.shape[0]
    npad = max(_DEC_BLK, ceil_div(n, _DEC_BLK) * _DEC_BLK)
    b = jnp.pad(b, (0, npad - n))
    term, val = _byte_stencil(b, interpret=interpret)
    live = (term > 0) & (jnp.arange(npad, dtype=jnp.int32) < nbytes)
    li = live.astype(jnp.int32)
    vidx = blocked_scan(li, mode="add", interpret=interpret) - li
    tgt = jnp.where(live & (vidx < count), vidx, count)
    return jnp.zeros((count,), jnp.int32).at[tgt].set(val, mode="drop")


# ---------------------------------------------------------------------------
# Delta restores (device twins of the codec's cumsum/repeat restores)
# ---------------------------------------------------------------------------
#
# The restores decode every chunk of one dst batch in one chain of
# dispatches (DeviceChunkDecoder.decode_batch): the chunks' streams are
# concatenated, each chunk's cumsums restart at its first pair, and every
# run head carries ``slot * vpad + src`` (slot = the chunk's place in the
# batch, src < vpad), so head values increase across chunk boundaries and
# one running-max forward fill serves the whole batch.  One chunk is a
# batch of one.  vpad is a power of two, so a mask strips the slot: TPUs
# have no integer divide, and XLA's emulation of ``%`` by a runtime value
# compiles for tens of seconds at 2**20 lanes.

@functools.partial(jax.jit, static_argnames=("interpret",))
def pair_delta_restore(deltas: jnp.ndarray, seg_runs, seg_src, seg_pos, *,
                       interpret: bool | None = None):
    """Interleaved [ds0, di0, ds1, di1, ...] int32 deltas -> (src, idx)
    int32 cumulative arrays — the device twin of
    codec.pair_delta_restore, for several chunks' pair streams back to
    back.  Chunk c's pairs start at run ``seg_runs[c]`` (sorted; pad with
    a value past the last run); its cumsums restart there, and
    ``seg_src[c]`` / ``seg_pos[c]`` are added to its src / idx.  Both
    running sums may wrap int32 across chunks; a chunk's difference of two
    wrapped sums is still exact.  Zero-padded tails stay at the final
    value, which downstream consumers mask by the live run count."""
    if interpret is None:
        interpret = default_interpret()
    v = deltas.reshape(-1, 2)
    src = blocked_scan(v[:, 0], mode="add", interpret=interpret)
    idx = blocked_scan(v[:, 1], mode="add", interpret=interpret)
    r = jnp.arange(src.shape[0], dtype=jnp.int32)
    c = jnp.maximum(
        jnp.searchsorted(seg_runs, r, side="right").astype(jnp.int32) - 1, 0)
    first = jnp.minimum(seg_runs[c], src.shape[0] - 1)   # pads: no chunk
    src = src - (src - v[:, 0])[first] + seg_src[c]
    idx = idx - (idx - v[:, 1])[first] + seg_pos[c]
    return src, idx


@functools.partial(jax.jit, static_argnames=("out_len", "interpret"))
def expand_dcsr_index(srcs: tuple, starts: tuple, nnz: tuple, n_e, vpad, *,
                      out_len: int, interpret: bool | None = None):
    """DCSR (src, start) runs -> per-edge (src [out_len], run-start mask
    [out_len]) via scatter + running-max forward fill.

    ``srcs`` / ``starts`` / ``nnz`` are tuples, groups of heads of one
    width W (the first ``nnz[g]`` of group g live, at most W in all), each
    head holding ``slot * vpad + src`` at its edge position in the batch
    (``vpad`` a power of two above every src).  Head values increase over
    the live heads taken group after group, and the first sits at
    position 0, so a max-scan of the scattered heads reconstructs numpy's
    ``repeat(srcs, runs)`` exactly; the returned src is the fill mod
    ``vpad``.  The groups' live heads are first shifted into one list, so
    one scatter serves them all."""
    if interpret is None:
        interpret = default_interpret()
    width = srcs[0].shape[0]
    lane = jnp.arange(width, dtype=jnp.int32)
    pad = jnp.zeros((width,), jnp.int32)

    def after(x, at):                # x moved to start at lane ``at``
        return jax.lax.dynamic_slice(jnp.concatenate([pad, x]),
                                     (width - at,), (width,))
    heads, pos, live = pad, pad, 0
    for s, st, n in zip(srcs, starts, nnz):
        tail = lane >= live
        heads = jnp.where(tail, after(s, live), heads)
        pos = jnp.where(tail, after(st, live), pos)
        live = live + n
    # one scatter: heads land as src + 1, so a nonzero lane is a run start
    ok = lane < live
    tgt = jnp.where(ok, pos, out_len)
    src0 = jnp.zeros((out_len,), jnp.int32).at[tgt].max(
        jnp.where(ok, heads + 1, 0), mode="drop")
    src = (blocked_scan(src0, mode="max", interpret=interpret) - 1) \
        & (vpad - 1)
    keep = jnp.arange(out_len, dtype=jnp.int32) < n_e
    return jnp.where(keep, src, 0), (keep & (src0 > 0)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dst_delta_restore(res: jnp.ndarray, start_mask: jnp.ndarray, base,
                      n_e, src, *, interpret: bool | None = None):
    """Residue stream + run-start mask -> ``[src, dst]`` int32 [2, N] —
    the device twin of codec.dst_delta_restore, returning the per-edge
    ``src`` beside dst so the batch comes back to the host in one
    transfer.

    csum[j] - csum[start_of_run(j) - 1] telescopes the in-run deltas.  The
    run head of every edge is found by forward-filling head *positions*
    with a max-scan, and the "residues before" value is gathered from
    there.  The running sum of a large batch passes 2**31 (every run
    restarts at its batch offset), so csum may wrap; positions never do,
    and the in-run difference of two wrapped int32 sums is still exact.
    A batch of chunks needs nothing more: every chunk starts a run, and
    they share the base.  Entries beyond ``n_e`` are zeroed."""
    if interpret is None:
        interpret = default_interpret()
    pos = jnp.arange(res.shape[0], dtype=jnp.int32)
    csum = blocked_scan(res, mode="add", interpret=interpret)
    head = blocked_scan(jnp.where(start_mask > 0, pos, 0), mode="max",
                        interpret=interpret)
    before = (csum - res)[head]
    return jnp.stack([src, jnp.where(pos < n_e, base + csum - before, 0)])
