"""Inter-worker message exchange for distributed fully-out-of-core execution.

This layer realizes the paper's need-list-filtered push (§4.3) *on a wire*:
phase 2's filter emits, per (source partition p, destination partition q),
a send list — the active vertices of p that q needs — and this module turns
each list into a **message batch** whose byte representation is chosen
adaptively (the §4.1 CSR/DCSR idea applied to the network):

* ``pairs`` — compacted ``(src_local int32, value float32)`` entries, one
  per message: ``count * (4 + msg_bytes)`` bytes.  The DCSR-analogue — only
  live entries move (grown out of
  :func:`repro.core.sparse_collectives.compacted_all_to_all`).
* ``vpairs`` — the compression tier (DESIGN.md §9): the same compacted
  entries, but the int32 index column is replaced by a delta-varint gap
  stream (the indices are sorted, so most gaps fit one byte):
  ``gap_bytes(mask) + count * msg_bytes``.  Chosen only when
  ``EngineConfig.compression`` is on.
* ``slab``  — a dense batch slab over the source partition's vertex span:
  a row-packed presence bitmap plus ``v_max`` dense values:
  ``ceil(v_max / 8) + v_max * msg_bytes`` bytes.  The CSR-analogue —
  position-indexed, wins when most vertices send (grown out of
  :func:`repro.core.sparse_collectives.filtered_all_to_all`).
* ``uval``  — the wire twin of the chunk store's values-elided layout
  (DESIGN.md §10): when every message value in the batch is identical
  (BFS frontiers, unweighted label propagation), the value column
  collapses to ONE f32 — ``gap_bytes(mask) + msg_bytes`` bytes.
  Chosen only when ``EngineConfig.compression`` is on; uniformity is
  decided by the same masked min==max reduction the analytic model uses
  (:func:`repro.core.phases.batch_value_uniform`), so the priced and the
  serialized bytes agree per batch.

The decision rule (cheapest of the enabled encodings, ties preferring the
cheaper decode: pairs, then vpairs, then slab) and the priced bytes come
from ONE function (:func:`batch_wire_bytes`, with
:func:`repro.core.codec.mask_gap_bytes` supplying the data-dependent
vpairs index size to the analytic counters), used both by the executors'
``net_bytes`` counters and by :meth:`Exchange.post` to pick the physical
encoding — so ``measured_net_bytes == modeled_net_bytes`` by construction,
the same audit discipline the chunk store established for disk (DESIGN.md
§6/§7).

Framing metadata — (p, q, format tag, count) per batch — travels
out-of-band as Python scalars and is *not* priced: like the dispatching
graph and the need-bitmaps, it is O(P^2) control state, not bulk data
(the paper keeps the analogous metadata memory-resident).

:class:`DecodeAhead` is the receive-side twin of
:class:`~repro.core.chunkstore.ChunkPrefetcher`: a worker thread assembles
destination partition q+1's ``(recv_mask, recv_msg)`` view while the
consumer streams and combines q's chunks — incoming exchange decode
overlaps disk reads and compute.
"""
from __future__ import annotations

import queue
import threading
from typing import Iterator, Sequence

import numpy as np

from repro.core import codec
from repro.utils import ceil_div, token_ctx

WIRE_MSG_BYTES = 4          # float32 payload values on the wire
_IDX_BYTES = 4              # int32 source-local index per compacted pair

FMT_PAIRS = 0
FMT_SLAB = 1
FMT_VPAIRS = 2              # delta-varint index stream + dense value column
FMT_UVAL = 3                # delta-varint index stream + ONE uniform value
FMT_MQPANEL = 4             # multi-query panel: ONE union gap stream +
                            # per-query presence bitmap + value column
                            # (DESIGN.md §11)


# ---------------------------------------------------------------------------
# The byte model (shared by analytic counters and the physical encoder)
# ---------------------------------------------------------------------------

def pair_batch_bytes(count, msg_bytes: int):
    """Compacted (index, value) encoding: ``count`` live messages."""
    return count * float(_IDX_BYTES + msg_bytes)


def slab_batch_bytes(v_max: int, msg_bytes: int) -> float:
    """Dense batch slab: presence bitmap + one value per source vertex."""
    return float(ceil_div(v_max, 8) + v_max * msg_bytes)


def vpair_batch_bytes(count, gap_bytes, msg_bytes: int):
    """Delta-varint pairs: the gap stream plus one value per message.
    ``gap_bytes`` comes from :func:`repro.core.codec.mask_gap_bytes` on the
    same send mask the encoder serializes."""
    return gap_bytes + count * float(msg_bytes)


def uval_batch_bytes(gap_bytes, msg_bytes: int):
    """Uniform-value batch: the gap stream plus ONE value for the whole
    batch (the wire twin of the chunk store's values-elided layout).
    Valid only for batches whose masked values are all identical."""
    return gap_bytes + float(msg_bytes)


def batch_wire_bytes(count, v_max: int, msg_bytes: int, gap_bytes=None,
                     uniform=None, xp=np):
    """Priced wire bytes of one (p -> q) message batch.

    ``count`` may be a scalar or an array (numpy or jnp via ``xp``); empty
    batches are never sent and cost 0.  With ``gap_bytes`` (the delta-
    varint index stream size of the same mask) the price is the
    compressed-tier minimum including the ``vpairs`` encoding — and,
    where ``uniform`` (same shape as ``count``: every masked value of the
    batch identical, from :func:`repro.core.phases.batch_value_uniform`)
    is True, the single-value ``uval`` encoding.  Without ``gap_bytes``,
    the legacy two-way pairs/slab choice (``EngineConfig.compression``
    off; ``uniform`` is then ignored).  This is the single source of
    truth for the network model: every executor's ``net_bytes`` counter
    and the encoder's format choice derive from it.  The host (numpy)
    path prices in float64 so the model stays exact against the integer
    byte sum the wire measures (float32 would round past the verify_io
    tolerance once a call moves ≳16 MB); the jit path keeps float32,
    matching the analytic counters' dtype."""
    acc = xp.float64 if xp is np else xp.float32
    pairs = pair_batch_bytes(xp.asarray(count, acc), msg_bytes)
    slab = slab_batch_bytes(v_max, msg_bytes)
    best = xp.minimum(pairs, slab)
    if gap_bytes is not None:
        gb = xp.asarray(gap_bytes, acc)
        best = xp.minimum(best, vpair_batch_bytes(
            xp.asarray(count, acc), gb, msg_bytes))
        if uniform is not None:
            best = xp.where(
                xp.asarray(uniform),
                xp.minimum(best, uval_batch_bytes(gb, msg_bytes)), best)
    return xp.where(xp.asarray(count) > 0, best, 0.0)


def choose_wire_format(count: int, v_max: int, msg_bytes: int,
                       gap_bytes=None, uniform: bool = False) -> int:
    """The encoder's scalar realization of :func:`batch_wire_bytes`: the
    cheapest enabled encoding, ties preferring the cheaper decode
    (pairs, then vpairs, then uval, then slab).  Any tie-break yields the
    same byte count as the model's minimum — which is the invariant that
    matters."""
    best, cost = FMT_PAIRS, pair_batch_bytes(count, msg_bytes)
    if gap_bytes is not None:
        vb = vpair_batch_bytes(count, float(gap_bytes), msg_bytes)
        if vb < cost:
            best, cost = FMT_VPAIRS, vb
        if uniform:
            ub = uval_batch_bytes(float(gap_bytes), msg_bytes)
            if ub < cost:
                best, cost = FMT_UVAL, ub
    if slab_batch_bytes(v_max, msg_bytes) < cost:
        best = FMT_SLAB
    return best


def choose_physical_exchange(capacity: int, v_max: int, msg_bytes: int,
                             nq: int = 1) -> bool:
    """Price the SHARD_MAP physical wire (DESIGN.md §12): True where a
    compacted collective of ``capacity`` per peer costs less than the
    dense slab.  The executor's wire ladder (``executor.wire_ladder``)
    tops out at the largest power of two where this holds.

    This is the SAME cost comparison :func:`choose_wire_format` runs for
    the serialized wire, applied to the collective's per-peer volume: a
    compacted exchange is a pairs batch of ``capacity`` entries, the
    dense exchange is a slab, so the solo decision is literally
    ``choose_wire_format(capacity, ...) == FMT_PAIRS`` (the compressed
    encodings don't apply — the collective ships raw arrays, not byte
    streams).  The multi-query panel applies the identical primitives per
    value column: the shared index stream is paid once
    (:func:`pair_batch_bytes` minus its value bytes) and each of the Q
    columns adds ``capacity`` values + presence flags against its own
    dense slab."""
    if nq <= 1:
        return choose_wire_format(capacity, v_max, msg_bytes) == FMT_PAIRS
    comp = (capacity * float(_IDX_BYTES)
            + nq * capacity * float(msg_bytes + 1))
    return comp < nq * slab_batch_bytes(v_max, msg_bytes)


# ---------------------------------------------------------------------------
# Physical encode / decode
# ---------------------------------------------------------------------------

def encode_batch(mask: np.ndarray, values: np.ndarray,
                 count: int | None = None, *,
                 compression: bool = False) -> tuple[int, bytes]:
    """Serialize one message batch; returns (format tag, payload bytes).

    mask [v_max] bool, values [v_max] float32 (entries where ``mask`` is
    False are never read — unread spill batches may hold garbage).
    ``count`` is the mask's popcount if the caller already has it.
    ``compression`` enables the delta-varint ``vpairs`` / single-value
    ``uval`` encodings in the choice.  The payload length equals
    :func:`batch_wire_bytes` (with ``gap_bytes`` + ``uniform`` iff
    ``compression``) exactly."""
    v_max = mask.shape[0]
    if count is None:
        count = int(mask.sum())

    def slab_payload():
        bits = np.packbits(np.asarray(mask, bool))
        dense = np.where(mask, values, 0.0).astype("<f4")
        return FMT_SLAB, bits.tobytes() + dense.tobytes()

    # Batch uniformity: the identical masked min == max reduction the
    # analytic model runs (phases.batch_value_uniform), so the encoder
    # and the net_bytes counters always agree on whether uval applies.
    uni = False
    if compression and count:
        vm = np.asarray(values, np.float32)
        hi = np.max(np.where(mask, vm, -np.inf))
        uni = bool(hi == np.min(np.where(mask, vm, np.inf)))
    # Dense fast path: when the slab beats the pairs AND the vpairs floor
    # (every gap varint is >= 1 byte, so vpairs >= count * (msg + 1)), the
    # slab is certainly the minimum — skip building the index column
    # entirely (dense PageRank supersteps post slabs per (p, q) batch; the
    # old two-way encoder had the same O(1) slab path).  A uniform batch
    # never takes it: uval's floor (count + msg) undercuts the slab for
    # any realistic v_max.
    slab = slab_batch_bytes(v_max, WIRE_MSG_BYTES)
    if not uni and slab < pair_batch_bytes(count, WIRE_MSG_BYTES) and (
            not compression
            or slab < vpair_batch_bytes(count, float(count),
                                        WIRE_MSG_BYTES)):
        return slab_payload()
    idx = np.flatnonzero(mask)
    gaps = gb = None
    if compression:
        gaps = np.diff(idx, prepend=-1).astype(np.uint64)
        gb = int(codec.varint_sizes(gaps).sum())
    fmt = choose_wire_format(count, v_max, WIRE_MSG_BYTES, gb, uniform=uni)
    if fmt == FMT_SLAB:
        return slab_payload()
    if fmt == FMT_UVAL:
        return FMT_UVAL, (codec.varint_encode(gaps).tobytes()
                          + np.asarray(hi, "<f4").tobytes())
    vals = np.asarray(values, "<f4")[idx]
    if fmt == FMT_VPAIRS:
        return FMT_VPAIRS, (codec.varint_encode(gaps).tobytes()
                            + vals.tobytes())
    return FMT_PAIRS, idx.astype("<i4").tobytes() + vals.tobytes()


def mq_encode_panel(masks: np.ndarray, values: np.ndarray,
                    union_mask: np.ndarray, counts: Sequence[int]
                    ) -> tuple[list, bytes]:
    """Serialize one multi-query (p -> q) batch as a **panel**: one
    delta-varint gap stream over the union positions, then — for each query
    with a nonempty column — a presence bitmap over those union positions
    plus its value column (ONE value when the masked values are all
    identical, the uval idea per column; else ``count_j`` values).

    masks [Q, v_max] bool, values [Q, v_max] f32.  Returns
    ``(cols, payload)`` where ``cols`` is the framing metadata the decoder
    needs: ``[(j, count_j, uniform_j), ...]`` plus the gap-stream length is
    recoverable as ``len(payload) - sum(column bytes)``.  The payload
    length equals the panel arm of
    :func:`repro.core.phases.mq_wire_bytes` exactly."""
    idx_u = np.flatnonzero(union_mask)
    gaps = np.diff(idx_u, prepend=-1).astype(np.uint64)
    parts = [codec.varint_encode(gaps).tobytes()]
    cols = []
    for j, c in enumerate(counts):
        if not c:
            continue
        mj = np.asarray(masks[j], bool)
        vm = np.asarray(values[j], np.float32)
        hi = np.max(np.where(mj, vm, -np.inf))
        uni = bool(hi == np.min(np.where(mj, vm, np.inf)))
        parts.append(np.packbits(mj[idx_u]).tobytes())
        if uni:
            parts.append(np.asarray(hi, "<f4").tobytes())
        else:
            parts.append(vm[mj].astype("<f4").tobytes())
        cols.append((j, int(c), uni))
    return cols, b"".join(parts)


def mq_decode_panel(cols: list, payload: bytes, union_count: int,
                    v_max: int, num_queries: int, device: bool = False
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Inverse of :func:`mq_encode_panel` ->
    (masks [Q, v_max] bool, values [Q, v_max] f32)."""
    masks = np.zeros((num_queries, v_max), bool)
    values = np.zeros((num_queries, v_max), np.float32)
    pres_nb = ceil_div(union_count, 8)
    cols_nb = sum(pres_nb + (WIRE_MSG_BYTES if uni
                             else c * WIRE_MSG_BYTES)
                  for _, c, uni in cols)
    idx_u = _gap_decode(payload[:len(payload) - cols_nb], union_count,
                        device)
    off = len(payload) - cols_nb
    for j, c, uni in cols:
        bits = np.frombuffer(payload[off:off + pres_nb], np.uint8)
        off += pres_nb
        pres = np.unpackbits(bits)[:union_count].astype(bool)
        pos = idx_u[pres]
        if uni:
            vals = np.full(c, np.frombuffer(
                payload[off:off + WIRE_MSG_BYTES], "<f4")[0], np.float32)
            off += WIRE_MSG_BYTES
        else:
            vals = np.frombuffer(payload[off:off + c * WIRE_MSG_BYTES],
                                 "<f4")
            off += c * WIRE_MSG_BYTES
        masks[j, pos] = True
        values[j, pos] = vals
    return masks, values


def _gap_decode(stream: bytes, count: int, device: bool) -> np.ndarray:
    """Decode a batch's delta-varint gap stream to sorted indices.

    ``device=True`` runs the byte-level varint unpacking through the
    Pallas kernel (``kernels/varint.py``; gaps are < 2**31, its int32
    domain) — bit-identical to the host codec, but a GIL-releasing jit
    dispatch instead of a host numpy burst (DESIGN.md §10).  Buffer and
    count are padded to power-of-two buckets so compiled mode sees O(log²)
    distinct shapes, not one per batch."""
    if device and count:
        from repro.kernels import varint as vk
        nb = len(stream)
        buf = np.zeros(1 << max(4, (nb - 1).bit_length()), np.uint8)
        buf[:nb] = np.frombuffer(stream, np.uint8)
        cap = 1 << max(4, (count - 1).bit_length())
        gaps = np.asarray(vk.varint_decode(buf, nb, count=cap))[:count]
    else:
        gaps = codec.varint_decode(stream, count)
    return (np.cumsum(gaps.astype(np.int64)) - 1).astype(np.int64)


def decode_batch(fmt: int, payload: bytes, count: int, v_max: int,
                 device: bool = False) -> tuple[np.ndarray, np.ndarray]:
    """Inverse of :func:`encode_batch` -> (mask [v_max], values [v_max]).
    ``device`` routes the varint gap streams (vpairs / uval) through the
    Pallas decode kernel; results are bit-identical either way."""
    if fmt == FMT_SLAB:
        nbits = ceil_div(v_max, 8)
        bits = np.frombuffer(payload[:nbits], np.uint8)
        mask = np.unpackbits(bits)[:v_max].astype(bool)
        values = np.frombuffer(payload[nbits:], "<f4").copy()
        return mask, values
    if fmt == FMT_VPAIRS:
        vals_nb = count * WIRE_MSG_BYTES
        idx = _gap_decode(payload[:len(payload) - vals_nb], count, device)
        vals = np.frombuffer(payload[len(payload) - vals_nb:], "<f4")
    elif fmt == FMT_UVAL:
        idx = _gap_decode(payload[:len(payload) - WIRE_MSG_BYTES], count,
                          device)
        vals = np.full(count, np.frombuffer(
            payload[len(payload) - WIRE_MSG_BYTES:], "<f4")[0], np.float32)
    elif fmt == FMT_PAIRS:
        idx = np.frombuffer(payload[:count * _IDX_BYTES], "<i4")
        vals = np.frombuffer(payload[count * _IDX_BYTES:], "<f4")
    else:
        raise ValueError(f"unknown wire format tag {fmt!r}")
    mask = np.zeros(v_max, bool)
    values = np.zeros(v_max, np.float32)
    mask[idx] = True
    values[idx] = vals
    return mask, values


# ---------------------------------------------------------------------------
# Exchange: per-worker mailboxes with measured wire traffic
# ---------------------------------------------------------------------------

class Exchange:
    """Message routing between workers of one dist_ooc ProcessEdges call.

    Senders :meth:`post` one batch per nonempty (p, q) send list; batches
    whose destination worker differs from the source worker are physically
    serialized (measured — ``bytes_sent`` is what crossed the wire), while
    worker-local batches hand the arrays over by reference (nothing crosses
    a wire, exactly as LOCAL's model counts no self-partition traffic).
    Receivers drain their inbox per destination partition via
    :meth:`take_dest`, decoding wire batches back to (mask, values).

    Thread safety: posts and inbox pops are serialized by a lock so the
    parallel dist_ooc executor can run its W send loops concurrently
    (DESIGN.md §8).  Senders racing into the same (worker, q) box only
    permute the order of entries with *distinct* source partitions p, and
    :meth:`take_dest` assigns each p its own rows — so the assembled
    receive view, the integer batch tallies, and ``bytes_sent`` (a float64
    sum of integer byte counts, exact under reordering) are all independent
    of thread completion order."""

    def __init__(self, num_workers: int, v_max: int,
                 compression: bool = True):
        self.num_workers = num_workers
        self.v_max = v_max
        # ``compression`` enables the delta-varint vpairs wire encoding in
        # every posted batch's three-way choice (mirrors
        # EngineConfig.compression — the engine passes its flag through).
        self.compression = compression
        # inbox[w][q] -> list of (p, entry); entry is ("local", mask, values)
        # or ("wire", fmt, count, payload)
        self._inbox: list[dict[int, list]] = [
            {} for _ in range(num_workers)]
        self._lock = threading.Lock()
        self.bytes_sent = 0.0
        self.pair_batches = 0
        self.slab_batches = 0
        self.vpair_batches = 0
        self.uval_batches = 0
        self.mq_batches = 0
        self.bytes_by_sender = np.zeros(num_workers, np.float64)
        # Per-(src worker, dst worker) posted-batch tallies.  The diagonal
        # counts by-reference local hand-offs; every off-diagonal entry is
        # a physically serialized wire batch (one frame on a process
        # transport) — which is what lets the fault-injection tests assert
        # exactly how many frames each (src, dst) pair posted, dropped and
        # redelivered.  A legacy multi-query post (one inbox entry carrying
        # Q solo batches) counts once.
        self.posted = np.zeros((num_workers, num_workers), np.int64)

    def _put_entry(self, src_worker: int, dst_worker: int, q: int, p: int,
                   entry: tuple) -> None:
        """Delivery hook: route one posted entry into (dst_worker, q)'s
        inbox.  The process transport (:mod:`repro.core.transport`)
        overrides this to frame cross-worker entries onto a socket; local
        (same-worker) entries always land by reference."""
        with self._lock:
            self._inbox[dst_worker].setdefault(q, []).append((p, entry))

    def post(self, src_worker: int, dst_worker: int, p: int, q: int,
             mask: np.ndarray, values: np.ndarray,
             count: int | None = None) -> None:
        """``count`` is the mask's popcount when the sender already has it
        (the routing counts) — avoids re-reducing the mask per batch."""
        if src_worker == dst_worker:
            with self._lock:
                self.posted[src_worker, dst_worker] += 1
            self._put_entry(src_worker, dst_worker, q, p,
                            ("local", mask, values))
            return
        if count is None:
            count = int(mask.sum())
        fmt, payload = encode_batch(mask, values, count,
                                    compression=self.compression)
        with self._lock:
            self.bytes_sent += len(payload)
            self.bytes_by_sender[src_worker] += len(payload)
            if fmt == FMT_SLAB:
                self.slab_batches += 1
            elif fmt == FMT_VPAIRS:
                self.vpair_batches += 1
            elif fmt == FMT_UVAL:
                self.uval_batches += 1
            else:
                self.pair_batches += 1
            self.posted[src_worker, dst_worker] += 1
        self._put_entry(src_worker, dst_worker, q, p,
                        ("wire", fmt, count, payload))

    def post_mq(self, src_worker: int, dst_worker: int, p: int, q: int,
                masks: np.ndarray, values: np.ndarray,
                counts: Sequence[int]) -> None:
        """Post one multi-query (p, q) batch: ``masks``/``values`` are
        [Q, v_max] per-query send masks and message values, ``counts``
        their popcounts (>= 1 must be nonempty).  Cross-worker batches
        serialize as the cheaper of the two arms the analytic model prices
        (:func:`repro.core.phases.mq_wire_bytes`): Q independent solo-format
        batches, or one shared-index panel (compression on) — so
        ``bytes_sent`` equals the model by construction."""
        if src_worker == dst_worker:
            with self._lock:
                self.posted[src_worker, dst_worker] += 1
            self._put_entry(src_worker, dst_worker, q, p,
                            ("local_mq", masks, values))
            return
        items = []
        legacy_sum = 0
        for j, c in enumerate(counts):
            if not c:
                continue
            fmt, payload = encode_batch(masks[j], values[j], int(c),
                                        compression=self.compression)
            legacy_sum += len(payload)
            items.append((j, fmt, int(c), payload))
        panel = None
        if self.compression:
            u = int(np.asarray(masks, bool).any(axis=0).sum())
            cols, payload = mq_encode_panel(
                masks, values, np.asarray(masks, bool).any(axis=0), counts)
            if len(payload) < legacy_sum:
                panel = (cols, u, payload)
        if panel is not None:
            cols, u, payload = panel
            with self._lock:
                self.bytes_sent += len(payload)
                self.bytes_by_sender[src_worker] += len(payload)
                self.mq_batches += 1
                self.posted[src_worker, dst_worker] += 1
            self._put_entry(src_worker, dst_worker, q, p,
                            ("wire_mq_panel", cols, u, payload))
            return
        with self._lock:
            self.bytes_sent += legacy_sum
            self.bytes_by_sender[src_worker] += legacy_sum
            for _, fmt, _, _ in items:
                if fmt == FMT_SLAB:
                    self.slab_batches += 1
                elif fmt == FMT_VPAIRS:
                    self.vpair_batches += 1
                elif fmt == FMT_UVAL:
                    self.uval_batches += 1
                else:
                    self.pair_batches += 1
            self.posted[src_worker, dst_worker] += 1
        self._put_entry(src_worker, dst_worker, q, p,
                        ("wire_mq_legacy", items))

    def take_dest_mq(self, dst_worker: int, q: int, p_cnt: int,
                     num_queries: int, device_decode: bool = False
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Assemble destination partition q's multi-query receive view:
        (recv_mask [Q, P, v_max], recv_msg [Q, P, v_max])."""
        nq = num_queries
        recv_mask = np.zeros((nq, p_cnt, self.v_max), bool)
        recv_msg = np.zeros((nq, p_cnt, self.v_max), np.float32)
        with self._lock:
            entries = self._inbox[dst_worker].pop(q, ())
        for p, entry in entries:
            if entry[0] == "local_mq":
                _, masks, values = entry
                m = np.asarray(masks, bool)
                recv_mask[:, p] = m
                recv_msg[:, p] = np.where(m, values, 0.0)
            elif entry[0] == "wire_mq_panel":
                _, cols, u, payload = entry
                masks, values = mq_decode_panel(
                    cols, payload, u, self.v_max, nq,
                    device=device_decode)
                recv_mask[:, p] = masks
                recv_msg[:, p] = values
            else:
                _, items = entry
                for j, fmt, count, payload in items:
                    recv_mask[j, p], recv_msg[j, p] = decode_batch(
                        fmt, payload, count, self.v_max,
                        device=device_decode)
        return recv_mask, recv_msg

    def take_dest(self, dst_worker: int, q: int, p_cnt: int,
                  device_decode: bool = False
                  ) -> tuple[np.ndarray, np.ndarray]:
        """Assemble destination partition q's receive-major view:
        (recv_mask [P, v_max], recv_msg [P, v_max]).  ``device_decode``
        routes varint gap streams through the Pallas kernels."""
        recv_mask = np.zeros((p_cnt, self.v_max), bool)
        recv_msg = np.zeros((p_cnt, self.v_max), np.float32)
        with self._lock:
            entries = self._inbox[dst_worker].pop(q, ())
        for p, entry in entries:
            if entry[0] == "local":
                _, mask, values = entry
                m = np.asarray(mask, bool)
                recv_mask[p] = m
                recv_msg[p] = np.where(m, values, 0.0)
            else:
                _, fmt, count, payload = entry
                recv_mask[p], recv_msg[p] = decode_batch(
                    fmt, payload, count, self.v_max, device=device_decode)
        return recv_mask, recv_msg

    def counter_snapshot(self) -> dict:
        """All measured-wire counters as plain values, for cross-rank
        reduction: the process-mode executor allgathers each rank's
        snapshot and sums them in rank order, reproducing the single
        shared-Exchange totals of thread mode exactly (integer tallies,
        and float64 sums of integer byte counts, are order-exact)."""
        with self._lock:
            return {
                "bytes_sent": self.bytes_sent,
                "pair_batches": self.pair_batches,
                "slab_batches": self.slab_batches,
                "vpair_batches": self.vpair_batches,
                "uval_batches": self.uval_batches,
                "mq_batches": self.mq_batches,
                "bytes_by_sender": self.bytes_by_sender.copy(),
                "posted": self.posted.copy(),
            }


class DecodeAhead:
    """Thread-based decode-ahead over a worker's destination partitions.

    Iterates ``(q, recv_mask [P, v_max], recv_msg [P, v_max])`` for each
    owned destination partition, assembling/decoding partition *q+1*'s view
    on a worker thread while the consumer combines *q*'s chunks (the
    receive-side analogue of the chunk store's prefetch pipeline).
    Worker exceptions re-raise in the consumer.

    In the dist_ooc executor the "consumer" is itself a pipeline stage: the
    worker's lazy schedule generator iterates DecodeAhead *on the chunk
    prefetch thread*, computing partition q's dispatch as its view is
    delivered and handing the resulting chunk requests straight to the
    long-lived :class:`~repro.core.chunkstore.ChunkPrefetcher` — so decode,
    dispatch, disk reads, and combine all overlap with no per-partition
    teardown (DESIGN.md §8)."""

    _DONE = object()

    def __init__(self, exchange: Exchange, worker: int,
                 dests: Sequence[int], p_cnt: int, depth: int = 1,
                 compute_lock=None, runner=None,
                 device_decode: bool = False, num_queries: int = 1):
        self._exchange = exchange
        self._worker = worker
        self._dests = list(dests)
        self._p_cnt = p_cnt
        self._device_decode = bool(device_decode)
        # num_queries > 1 assembles [Q, P, v_max] panel views via
        # take_dest_mq (DESIGN.md §11); 1 keeps the solo [P, v_max] view.
        self._num_queries = int(num_queries)
        self._lock_ctx = token_ctx(compute_lock)
        self._queue: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        if runner is None:
            thread = threading.Thread(target=self._run, daemon=True)
            thread.start()
            self._join = thread.join
        else:
            future = runner.submit(self._run)
            self._join = lambda: future.exception()

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _run(self):
        try:
            for q in self._dests:
                with self._lock_ctx:       # compute token: decode burst
                    if self._num_queries > 1:
                        mask, msg = self._exchange.take_dest_mq(
                            self._worker, q, self._p_cnt,
                            self._num_queries,
                            device_decode=self._device_decode)
                    else:
                        mask, msg = self._exchange.take_dest(
                            self._worker, q, self._p_cnt,
                            device_decode=self._device_decode)
                if not self._put((q, mask, msg)):
                    return
            self._put(self._DONE)
        except BaseException as exc:       # propagate to the consumer
            self._put(exc)

    def close(self) -> None:
        self._stop.set()
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        self._join()

    def __iter__(self) -> Iterator[tuple]:
        try:
            while True:
                item = self._queue.get()
                if item is self._DONE:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            self.close()
