"""DFO collectives: the paper's filtered push generalized for LM layers.

DFOGraph's phases 2-3 (filter -> inter-node pass -> intra-node dispatch)
abstract to: *move only needed payloads between shards, bounded by a
precomputed need-list capacity*.  Consumers:

* MoE dispatch (tokens = messages, experts = vertex partitions, router =
  ``signal``, expert FFN = ``slot``, router weights = edge data).  Two paths
  mirror the paper's CSR/DCSR adaptivity:
    - ``dense_dispatch``/``dense_combine`` — one-hot capacity dispatch
      (CSR-analogue: position-indexed, O(1) "seek", best when most tokens
      route); works under plain pjit, XLA inserts the all-to-alls.
    - ``sorted_dispatch`` under shard_map — sort/compact by destination
      (DCSR-analogue: only live entries move), best when routing is sparse
      relative to capacity.
* Vocab-sharded embedding/logits: token ids pushed to the shard owning their
  row range; the need list is the static range mask.
* ``filtered_all_to_all`` — shard_map primitive used by the graph engine and
  by the sparse gradient exchange.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Routing (the "signal" phase)
# ---------------------------------------------------------------------------

def blocked_cumsum(x: jnp.ndarray, block: int) -> jnp.ndarray:
    """Two-level cumulative sum along axis 0 (paper §2.2 applied to the
    routing scan): cumsum within blocks + exclusive cumsum of block totals.
    An XLA reduce-window over millions of rows is catastrophically expensive;
    blocking confines the window span the way intra-node batching confines
    the paper's random-access span."""
    n = x.shape[0]
    if n <= block:
        return jnp.cumsum(x, axis=0)
    pad = (-n) % block
    xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    nb = xp.shape[0] // block
    xb = xp.reshape(nb, block, *x.shape[1:])
    within = jnp.cumsum(xb, axis=1)
    totals = within[:, -1]
    offsets = jnp.cumsum(totals, axis=0) - totals            # exclusive
    out = (within + offsets[:, None]).reshape(nb * block, *x.shape[1:])
    return out[:n]


def topk_routing(router_logits: jnp.ndarray, k: int, capacity: int,
                 *, renormalize: bool = True, block: int | None = None,
                 groups: int | None = None):
    """Top-k token->expert routing with per-expert capacity (need-list bound).

    router_logits: [T, E].  Returns:
      dispatch: bool [T, k] valid slot flag (token kept by its c-th choice)
      expert_idx: int32 [T, k]
      position:   int32 [T, k] slot within the expert's capacity buffer
      weights:    float [T, k] combine weights (softmax over chosen logits)
      group_id:   int32 [T, k] or None — token's capacity group
    Tokens beyond capacity are dropped (standard capacity-factor semantics —
    the static-shape analogue of the paper's bounded message buffers).

    block:  two-level position scan (perf; exact same positions).
    groups: per-group capacity — tokens are split into ``groups`` contiguous
      ranges (= data shards) and each (group, expert) pair gets
      capacity/groups slots.  This is the paper's per-pair |L_ij| bound: the
      position scan becomes shard-local (no cross-device sequential
      dependency) and the dispatch buffer shards cleanly over the data axis.
      Capacity semantics change from global-order to per-source-group.
    """
    t, e = router_logits.shape
    weights_full = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top_w, top_i = jax.lax.top_k(weights_full, k)            # [T, k]
    if renormalize:
        top_w = top_w / jnp.clip(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    flat_e = top_i.reshape(-1)                               # [T*k]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)      # [T*k, E]
    rows = t * k
    if groups:
        assert rows % groups == 0, (rows, groups)
        per = rows // groups
        oh = onehot.reshape(groups, per, e)
        if block and block < per:
            pos_g = jax.vmap(lambda o: blocked_cumsum(o, block))(oh) - 1
        else:
            pos_g = jnp.cumsum(oh, axis=1) - 1
        pos_in_expert = pos_g.reshape(rows, e)
        cap_g = -(-capacity // groups)
        position = jnp.take_along_axis(pos_in_expert, flat_e[:, None],
                                       axis=1).reshape(t, k)
        dispatch = position < cap_g
        group_id = jnp.repeat(jnp.arange(groups, dtype=jnp.int32), per) \
            .reshape(t, k)
        return dispatch, top_i, position.astype(jnp.int32), top_w, group_id
    if block:
        pos_in_expert = blocked_cumsum(onehot, block) - 1
    else:
        pos_in_expert = jnp.cumsum(onehot, axis=0) - 1       # occurrences before
    position = jnp.take_along_axis(pos_in_expert, flat_e[:, None],
                                   axis=1).reshape(t, k)
    dispatch = position < capacity
    return dispatch, top_i, position.astype(jnp.int32), top_w, None


def dense_dispatch(x: jnp.ndarray, dispatch, expert_idx, position,
                   num_experts: int, capacity: int,
                   group_id=None, groups: int = 1) -> jnp.ndarray:
    """Push tokens into per-expert capacity buffers (the CSR-analogue:
    position-addressed scatter).

    Without groups: x [T, D] -> [E, C, D].
    With groups (per-source-group capacity): -> [E, G, C/G, D]; group g's
    tokens land only in the g-slice, so a buffer sharded over G on the data
    axis receives a shard-local scatter."""
    t, d = x.shape
    k = expert_idx.shape[1]
    flat_ok = dispatch.reshape(-1)
    src = jnp.repeat(x, k, axis=0)                                # [T*k, D]
    if group_id is None:
        slots = num_experts * capacity
        flat_idx = (expert_idx * capacity + position).reshape(-1)
        flat_idx = jnp.where(flat_ok, flat_idx, slots)            # drop
        buf = jnp.zeros((slots, d), x.dtype)
        buf = buf.at[flat_idx].add(jnp.where(flat_ok[:, None], src, 0),
                                   mode="drop")
        return buf.reshape(num_experts, capacity, d)
    cap_g = -(-capacity // groups)
    slots = num_experts * groups * cap_g
    flat_idx = ((expert_idx * groups + group_id) * cap_g
                + position).reshape(-1)
    flat_idx = jnp.where(flat_ok, flat_idx, slots)
    buf = jnp.zeros((slots, d), x.dtype)
    buf = buf.at[flat_idx].add(jnp.where(flat_ok[:, None], src, 0),
                               mode="drop")
    return buf.reshape(num_experts, groups, cap_g, d)


def dense_combine(expert_out: jnp.ndarray, dispatch, expert_idx, position,
                  weights, seq_len: int, group_id=None) -> jnp.ndarray:
    """Pull expert outputs back to token order with combine weights.
    expert_out: [E, C, D] or [E, G, Cg, D] -> [T, D]."""
    if group_id is None:
        e, c, d = expert_out.shape
        flat = expert_out.reshape(e * c, d)
        flat_idx = (expert_idx * c + position)                   # [T, k]
    else:
        e, g, cg, d = expert_out.shape
        flat = expert_out.reshape(e * g * cg, d)
        flat_idx = (expert_idx * g + group_id) * cg + position
    n = flat.shape[0]
    gathered = flat[jnp.clip(flat_idx, 0, n - 1)]                # [T, k, D]
    w = jnp.where(dispatch, weights, 0.0).astype(flat.dtype)
    return jnp.einsum("tkd,tk->td", gathered, w)


# ---------------------------------------------------------------------------
# shard_map-level filtered all-to-all (graph engine / gradient exchange)
# ---------------------------------------------------------------------------

def filtered_all_to_all(payload: jnp.ndarray, send_mask: jnp.ndarray,
                        axis: str):
    """Per-destination masked exchange (paper phase 2).

    payload: [V, ...] local values; send_mask: [P, V] bool — which local
    entries each destination shard needs (the need-list ∧ active filter).
    Returns (recv_payload [P, V, ...], recv_mask [P, V]): entry [p, v] is
    source shard p's value v, present iff p sent it.
    Must be called inside shard_map over ``axis``.
    """
    p = send_mask.shape[0]
    send = jnp.where(
        send_mask.reshape(send_mask.shape + (1,) * (payload.ndim - 1)),
        payload[None], 0)
    recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=True)
    rmask = jax.lax.all_to_all(send_mask.astype(jnp.int8), axis, 0, 0,
                               tiled=True) > 0
    return recv, rmask


def compacted_all_to_all(payload: jnp.ndarray, dest: jnp.ndarray,
                         capacity: int, axis: str):
    """DCSR-analogue exchange: compact live entries per destination before
    sending, bounded by ``capacity`` per peer (the |L_ij| bound).

    payload: [V, D]; dest: [V] int32 destination shard (or -1 = inactive).
    Returns (recv [P, capacity, D], recv_src_index [P, capacity] int32,
    overflow bool scalar).  Wire bytes drop from P*V*D to P*capacity*D —
    this is what makes filtering show up in the collective roofline term
    rather than only in counters.

    Padding contract: slots a peer did not fill carry ``recv_src_index ==
    -1`` and **zero** payload rows; consumers must treat ``recv_src_index
    >= 0`` as the only validity signal (never read payload rows at
    padding slots as data — a live entry may legitimately carry value 0).
    ``overflow`` is the ``pmax``'d live-count check: True (identically on
    every shard) iff ANY (source, destination) pair had more than
    ``capacity`` live entries, in which case entries past ``capacity``
    were dropped and the caller must fall back to a dense exchange
    (:func:`filtered_all_to_all`) rather than use the truncated result.
    """
    p = jax.lax.axis_size(axis)
    v, d = payload.shape
    dest0 = jnp.maximum(dest, 0)
    # stable position of each entry within its destination's send buffer
    onehot = jax.nn.one_hot(dest, p, dtype=jnp.int32)            # [V, P]
    pos = jnp.cumsum(onehot, axis=0) - 1                         # [V, P]
    pos = jnp.take_along_axis(pos, dest0[:, None], 1)[:, 0]
    ok = (dest >= 0) & (pos < capacity)
    slot = jnp.where(ok, dest0 * capacity + pos, p * capacity)
    buf = jnp.zeros((p * capacity, d), payload.dtype)
    buf = buf.at[slot].add(jnp.where(ok[:, None], payload, 0), mode="drop")
    idx = jnp.full((p * capacity,), -1, jnp.int32)
    idx = idx.at[slot].max(jnp.where(ok, jnp.arange(v, dtype=jnp.int32), -1),
                           mode="drop")
    buf = buf.reshape(p, capacity, d)
    idx = idx.reshape(p, capacity)
    counts = jnp.sum(onehot, axis=0)                             # [P]
    overflow = jax.lax.pmax(jnp.max(counts), axis) > capacity
    recv = jax.lax.all_to_all(buf, axis, 0, 0, tiled=False)
    recv_idx = jax.lax.all_to_all(idx, axis, 0, 0, tiled=False)
    return recv, recv_idx, overflow


def masked_compacted_all_to_all(payload: jnp.ndarray,
                                send_mask: jnp.ndarray,
                                capacity: int, axis: str):
    """Mask-form compacted exchange: the graph engine's phase-2 wire.

    Unlike :func:`compacted_all_to_all`'s single destination per entry,
    a DFO message travels to EVERY destination whose need-list contains
    it, so the send decision is a [P, V] mask (the
    :func:`repro.core.phases.filter_sendmask` output).  Each destination
    row is compacted independently: row p ships its ≤ ``capacity`` live
    entries as (value, source-local index) pairs.

    payload: [V] local message values; send_mask: [P, V] bool.
    Returns (recv [P, capacity], recv_src_index [P, capacity] int32,
    overflow bool scalar) with the same padding contract and ``pmax``'d
    overflow semantics as :func:`compacted_all_to_all`: padding slots are
    ``recv_src_index == -1`` with zero payload, and a True ``overflow``
    means the result is truncated and the caller must fall back to
    :func:`filtered_all_to_all`.
    """
    p, v = send_mask.shape
    sm = send_mask.astype(jnp.int32)
    pos = jnp.cumsum(sm, axis=1) - 1                             # [P, V]
    ok = send_mask & (pos < capacity)
    rows = jnp.arange(p, dtype=jnp.int32)[:, None]
    slot = jnp.where(ok, rows * capacity + pos, p * capacity)
    buf = jnp.zeros((p * capacity,), payload.dtype)
    buf = buf.at[slot.ravel()].add(
        jnp.where(ok, payload[None, :], 0).ravel(), mode="drop")
    src_idx = jnp.broadcast_to(jnp.arange(v, dtype=jnp.int32)[None, :],
                               (p, v))
    idx = jnp.full((p * capacity,), -1, jnp.int32)
    idx = idx.at[slot.ravel()].max(
        jnp.where(ok, src_idx, -1).ravel(), mode="drop")
    overflow = jax.lax.pmax(jnp.max(jnp.sum(sm, axis=1)), axis) > capacity
    recv = jax.lax.all_to_all(buf.reshape(p, capacity), axis, 0, 0,
                              tiled=False)
    recv_idx = jax.lax.all_to_all(idx.reshape(p, capacity), axis, 0, 0,
                                  tiled=False)
    return recv, recv_idx, overflow


def masked_compacted_all_to_all_mq(values: jnp.ndarray,
                                   send_maskp: jnp.ndarray,
                                   capacity: int, axis: str):
    """Tiled multi-query panel variant of
    :func:`masked_compacted_all_to_all` (DESIGN.md §11 wire, §12 physical).

    values: [V, Q] per-query message values; send_maskp: [P, V, Q] bool
    per-(destination, vertex, query) send decisions.  Entries are
    compacted by the UNION (any-query) mask — the panel ships ONE shared
    source-index stream per peer plus Q value columns and Q presence
    flags, the physical twin of the ``FMT_MQPANEL`` shared-index pricing.
    Returns (recv_vals [P, capacity, Q], recv_maskp [P, capacity, Q] bool,
    recv_src_index [P, capacity] int32, overflow bool scalar); the
    padding/overflow contract matches :func:`masked_compacted_all_to_all`
    (capacity bounds the per-peer UNION count).
    """
    p, v, q = send_maskp.shape
    union = jnp.any(send_maskp, axis=-1)                         # [P, V]
    pos = jnp.cumsum(union.astype(jnp.int32), axis=1) - 1
    ok = union & (pos < capacity)
    rows = jnp.arange(p, dtype=jnp.int32)[:, None]
    slot = jnp.where(ok, rows * capacity + pos, p * capacity)
    vals_src = jnp.where(send_maskp, values[None, :, :], 0)      # [P, V, Q]
    bufv = jnp.zeros((p * capacity, q), values.dtype)
    bufv = bufv.at[slot.ravel()].add(
        jnp.where(ok[:, :, None], vals_src, 0).reshape(p * v, q),
        mode="drop")
    bufm = jnp.zeros((p * capacity, q), jnp.int8)
    bufm = bufm.at[slot.ravel()].max(
        jnp.where(ok[:, :, None], send_maskp, False)
        .astype(jnp.int8).reshape(p * v, q), mode="drop")
    src_idx = jnp.broadcast_to(jnp.arange(v, dtype=jnp.int32)[None, :],
                               (p, v))
    idx = jnp.full((p * capacity,), -1, jnp.int32)
    idx = idx.at[slot.ravel()].max(
        jnp.where(ok, src_idx, -1).ravel(), mode="drop")
    ucounts = jnp.sum(union.astype(jnp.int32), axis=1)
    overflow = jax.lax.pmax(jnp.max(ucounts), axis) > capacity
    recv_vals = jax.lax.all_to_all(bufv.reshape(p, capacity, q), axis,
                                   0, 0, tiled=False)
    recv_mask = jax.lax.all_to_all(bufm.reshape(p, capacity, q), axis,
                                   0, 0, tiled=False) > 0
    recv_idx = jax.lax.all_to_all(idx.reshape(p, capacity), axis, 0, 0,
                                  tiled=False)
    return recv_vals, recv_mask, recv_idx, overflow


def compacted_scatter_back(recv: jnp.ndarray, recv_idx: jnp.ndarray,
                           v_max: int):
    """Re-densify a compacted receive into the [P, v_max] slab layout.

    Inverse of the send-side compaction: each live (value, source index)
    pair lands at its source-local position; padding slots
    (``recv_src_index == -1``) contribute nothing.  Safe as a pure
    scatter because source indices within one peer row are unique — each
    target cell receives at most one add, so values are copied (not
    summed) and the result is bit-identical to the dense
    :func:`filtered_all_to_all` slab.  The downstream monoid combine is
    order-independent (DESIGN.md §3), so feeding it this reconstruction
    changes nothing."""
    p, _cap = recv_idx.shape
    valid = recv_idx >= 0
    tgt = jnp.where(valid, recv_idx, v_max)                      # drop row
    rows = jnp.arange(p, dtype=jnp.int32)[:, None]
    msg = jnp.zeros((p, v_max + 1), recv.dtype)
    msg = msg.at[rows, tgt].add(jnp.where(valid, recv, 0), mode="drop")
    mask = jnp.zeros((p, v_max + 1), jnp.int32)
    mask = mask.at[rows, tgt].max(valid.astype(jnp.int32), mode="drop")
    return msg[:, :v_max], mask[:, :v_max] > 0


def compacted_scatter_back_mq(recv_vals: jnp.ndarray,
                              recv_maskp: jnp.ndarray,
                              recv_idx: jnp.ndarray, v_max: int):
    """Panel twin of :func:`compacted_scatter_back`: re-densify a
    [P, capacity, Q] compacted panel into the [P, v_max, Q] slab the
    multi-query combine consumes, bit-identical to the dense panel
    exchange."""
    p, _cap, q = recv_vals.shape
    valid = recv_idx >= 0
    tgt = jnp.where(valid, recv_idx, v_max)
    rows = jnp.arange(p, dtype=jnp.int32)[:, None]
    vals = jnp.zeros((p, v_max + 1, q), recv_vals.dtype)
    vals = vals.at[rows, tgt].add(
        jnp.where(valid[:, :, None], recv_vals, 0), mode="drop")
    maskp = jnp.zeros((p, v_max + 1, q), jnp.int32)
    maskp = maskp.at[rows, tgt].max(
        jnp.where(valid[:, :, None], recv_maskp, False).astype(jnp.int32),
        mode="drop")
    return vals[:, :v_max], maskp[:, :v_max] > 0


# ---------------------------------------------------------------------------
# Vocab-sharded embedding push (huge-vocab archs)
# ---------------------------------------------------------------------------

def vocab_sharded_embed(tokens: jnp.ndarray, embedding: jnp.ndarray,
                        vocab_size: int) -> jnp.ndarray:
    """Embedding lookup written so that, with ``embedding`` sharded over the
    vocab axis, XLA lowers it to a masked partial-lookup + all-reduce — the
    pjit form of the DFO push: each shard contributes only rows it owns.

    tokens: int32 [...]; embedding: [vocab, D] (shard spec: ('model', None)).
    """
    onehot = jax.nn.one_hot(tokens, vocab_size, dtype=embedding.dtype)
    return onehot @ embedding


def take_embed(tokens: jnp.ndarray, embedding: jnp.ndarray) -> jnp.ndarray:
    """Gather-form lookup (better when the table is replicated or
    row-sharded with small vocab)."""
    return jnp.take(embedding, tokens, axis=0)
