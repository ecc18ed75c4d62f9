"""Chunk-scheduled ProcessEdges executors (DESIGN.md §1, §6, §7).

One shared phase pipeline (:mod:`repro.core.phases`) drives four executors;
storage is reached through the ChunkSource contract of
:mod:`repro.core.chunkstore`:

* ``make_local_pe``  — one device; the partition axis is a leading array
  axis.  The inter-partition exchange is a vmap re-axis (``out_axes=1``
  builds the receive-major [Q, P, V] view directly — no dense [P, P, V]
  broadcast of the active mask and no send-major transpose), and
  "network" traffic is accounted analytically by counters.
* ``make_sharded_pe`` — the partition axis is a mesh axis; the exchange is
  a real ``lax.all_to_all`` on the interconnect and counters are reduced
  with ``lax.psum``.
* ``make_ooc_pe``    — fully-out-of-core: edge chunks and vertex arrays are
  disk-resident (:class:`~repro.core.chunkstore.ChunkStore` /
  :class:`~repro.core.chunkstore.VertexSpill`); the executor walks
  dst-batches streaming only the chunks the selective schedule marks
  active, overlapping reads with compute via a double-buffered prefetch
  thread, and reports **measured** I/O counters next to the analytic ones.
* ``make_dist_ooc_pe`` — distributed fully-out-of-core: W workers, each
  owning a contiguous block of destination partitions backed by its own
  chunk-store shard and vertex spill; the inter-node pass goes through
  :mod:`repro.core.exchange` — need-list-filtered message batches with an
  adaptively chosen pair/slab wire encoding whose **measured** bytes equal
  the analytic network model by construction.

All four executors price the network with the same routing-derived model
(``phases.routing_counts`` -> ``phases.net_bytes_model``): each nonempty
cross-node (p, q) message batch costs its cheaper wire encoding.

Phase 4 runs on one of two compute backends (``EngineConfig.compute_backend``):

* ``"segment"``   — flat per-edge gather + ``segment_{sum,min,max}``; the
  reference implementation.
* ``"block_csr"`` — the Pallas block-CSR combine kernel over per-(source
  partition, destination batch) tiles, zero-skipping tiles whose chunk
  received no messages (the paper's selective computation realized on the
  compute path, not just in the I/O counters).

The block backend requires the slot function to be *affine in the message*
per edge — ``slot(m, d) = a(d) * m + b(d)`` — which every monoid-compatible
slot in the paper's four algorithms satisfies (DESIGN.md §2).  The slot is
probed numerically; non-affine slots fall back to the segment backend with
a warning.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait as futures_wait

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import codec
from repro.core import exchange as exchange_mod
from repro.core import phases
from repro.core import sparse_collectives
from repro.core.chunkstore import (
    REP_CSR, REP_DCSR, REP_DCSR_DELTA, ChunkPrefetcher, HBMChunkSource,
    ScheduleMark,
)
from repro.core.formats import BlockTilesHost
from repro.core.partition import row_block_batch_map
from repro.core.tracing import span
from repro.kernels.csr_spmv import (
    block_csr_combine, build_tile_struct, default_interpret,
)
from repro.utils import ceil_div, token_ctx


# ---------------------------------------------------------------------------
# Slot lowering for the block-CSR backend (DESIGN.md §2)
# ---------------------------------------------------------------------------

def fn_code_key(fn):
    """Hashable behavioral identity for a user callback, or None.

    Algorithm loops create fresh lambdas every iteration; the code object
    (plus consts, defaults, and closure values) identifies the behavior
    across iterations so probes and jitted executors are cached per
    algorithm, not re-built per call."""
    try:
        code = fn.__code__
        key = (code.co_code, code.co_consts, fn.__defaults__,
               tuple(c.cell_contents for c in (fn.__closure__ or ())))
        hash(key)
        return key
    except Exception:
        return None


def slot_probe_key(slot_fn, monoid):
    """Cache key for the affine-slot probe (see :func:`fn_code_key`)."""
    key = fn_code_key(slot_fn)
    return None if key is None else (monoid.name,) + key


def probe_slot_affine(slot_fn, monoid, edge_data, edge_valid):
    """Numerically probe ``slot(m, d) = a(d) * m + b(d)``.

    edge_data/edge_valid: host [P, E] arrays (padding masked by edge_valid).
    Returns (cache_key, mode, a_const, a [P, E], b [P, E]) or None when the
    slot is not affine in the message (or, for extremum monoids, when the
    slope varies across edges so per-cell minima cannot be precombined)."""
    d = jnp.asarray(edge_data)
    b = np.asarray(slot_fn(jnp.zeros_like(d), d), np.float32)
    a = np.asarray(slot_fn(jnp.ones_like(d), d), np.float32) - b
    m = np.asarray(edge_valid)
    # Check the fitted line at non-integer points too: slots built from
    # round/floor/mod are linear at integer probes but not in between.
    for t in (2.0, 0.37282, 2.414214):
        ft = np.asarray(slot_fn(jnp.full_like(d, t), d), np.float32)
        if not np.allclose(ft[m], (t * a + b)[m], rtol=1e-4, atol=1e-5):
            return None
    a_const = 1.0
    if monoid.name in ("min", "max"):
        av = a[m]
        if av.size:
            a_const = float(av.flat[0])
            if not np.allclose(av, a_const, rtol=1e-5, atol=1e-7):
                return None
        mode = monoid.name
    elif monoid.name == "add":
        mode = "add_b" if np.any(np.abs(b[m]) > 0) else "add"
    else:
        return None
    key = hashlib.sha1(
        monoid.name.encode() + a.tobytes() + b.tobytes()).hexdigest()
    return key, mode, a_const, a, b


def build_value_tiles(host: BlockTilesHost, monoid, mode: str,
                      a: np.ndarray, b: np.ndarray) -> dict:
    """Scatter the probed per-edge (a, b) into value tiles (numpy).

    add / add_b : tiles_v[cell] = sum a_e (+ tiles_b[cell] = sum b_e) —
                  parallel edges accumulate, so the tile matmul reproduces
                  the per-edge segment sum exactly.
    min / max   : tiles_b[cell] = extremum of b_e over the cell's edges
                  (valid because the slope is constant), identity elsewhere.
    """
    p_cnt, _ = host.edge_slot.shape
    s_max, t = host.s_max, host.tile
    m = host.edge_valid
    qi = np.broadcast_to(np.arange(p_cnt)[:, None], host.edge_slot.shape)[m]
    cell = (qi, host.edge_slot[m], host.edge_roff[m], host.edge_coff[m])
    out = {}
    if mode in ("add", "add_b"):
        tv = np.zeros((p_cnt, s_max, t, t), np.float32)
        np.add.at(tv, cell, a[m])
        out["tiles_v"] = tv
        if mode == "add_b":
            tb = np.zeros((p_cnt, s_max, t, t), np.float32)
            np.add.at(tb, cell, b[m])
            out["tiles_b"] = tb
    else:
        tb = np.full((p_cnt, s_max, t, t), monoid.identity, np.float32)
        scatter = np.minimum if mode == "min" else np.maximum
        scatter.at(tb, cell, b[m])
        out["tiles_b"] = tb
    return out


# ---------------------------------------------------------------------------
# Shared destination-side pipeline (phases 3 + 4 on one partition's view)
# ---------------------------------------------------------------------------

def _dest_phases(d, recv_msg, recv_mask, *, slot_fn, monoid, spec, cfg,
                 backend, part_sizes, gamma, mode_meta, rb_map, bt_static,
                 interpret):
    """Dispatch + process for one destination partition.

    d: dict of this destination's arrays (DCSR dispatch/format slices, plus
    per-edge arrays for the segment backend or tile arrays for block_csr).
    Returns (agg [V], has [V], counter contributions dict)."""
    v_max, b_cnt = spec.v_max, spec.num_batches
    with jax.named_scope("dispatch"):
        chunk_active, dispatched = phases.dispatch_one_dest(
            d["dcsr_src"], d["dcsr_part"], d["dcsr_batch"], d["dcsr_valid"],
            recv_mask, v_max, b_cnt)
        c = {"msgs_dispatched": dispatched,
             "chunks_read": jnp.sum(chunk_active, dtype=jnp.float32)}
        if cfg.enable_adaptive_formats:
            msgs_from = jnp.sum(recv_mask, axis=1).astype(jnp.int32)
            c.update(phases.format_choice_one_dest(
                d["dcsr_ptr"], d["has_csr"], d["csr_bytes"],
                d["dcsr_bytes"], d["dcsr_delta_bytes"], d["csr_raw_bytes"],
                d["dcsr_raw_bytes"], part_sizes, gamma, msgs_from,
                cfg.compression, chunk_active))
        else:
            # Non-adaptive baseline: CSR for every chunk (the behavior the
            # paper improves on; model-only — ooc executors reject this
            # config).  The CSR family still follows cfg.compression so
            # the disk and wire counters of one run price one layout; the
            # raw twin keeps the fully-legacy number either way.
            base = d["csr_bytes"] if cfg.compression else d["csr_raw_bytes"]
            c["seek_cost"] = jnp.zeros((), jnp.float32)
            c["edge_read_bytes"] = jnp.sum(
                jnp.where(chunk_active, base, 0.0), dtype=jnp.float32)
            c["edge_read_bytes_raw"] = jnp.sum(
                jnp.where(chunk_active, d["csr_raw_bytes"], 0.0),
                dtype=jnp.float32)
            c["chunks_read_csr"] = c["chunks_read"]
            c["chunks_read_dcsr"] = jnp.zeros((), jnp.float32)
            c["chunks_read_dcsr_delta"] = jnp.zeros((), jnp.float32)

    with jax.named_scope("combine"):
        if backend == "segment":
            agg, has, touched = phases.process_segment_one_dest(
                d["edge_src_part"], d["edge_src_local"], d["edge_dst_local"],
                d["edge_data"], d["edge_valid"], recv_msg, recv_mask,
                slot_fn, monoid, v_max)
        else:
            bt = {k: d[k] for k in ("slot_row", "slot_col", "slot_part",
                                    "slot_valid", "row_ptr", "tiles_cnt")}
            vals = {"mode": mode_meta[0], "a": mode_meta[1],
                    "tiles_v": d.get("tiles_v"), "tiles_b": d.get("tiles_b")}
            agg, has, touched = phases.process_block_one_dest(
                bt, vals, recv_msg, recv_mask, chunk_active, monoid, rb_map,
                tile=bt_static.tile, v_pad=bt_static.v_pad,
                n_rows=bt_static.n_rows,
                max_tiles_per_row=bt_static.max_tiles_per_row,
                interpret=interpret)
    c["edges_touched"] = touched
    return agg, has, c


def _apply_and_account(state, agg, has, global_id, vertex_valid, apply_fn,
                       cfg, batch_size, amask):
    """Shared apply: masked state update + vertex-batch I/O accounting.

    The vertex I/O model (paper §4.4, mirrored byte-for-byte by the OOC
    executor's spill requests): the generating phase reads the active
    bitmap plus the vertex arrays of batches containing active vertices;
    apply reads and writes the arrays of updated batches and writes the
    new-active bitmap."""
    updates, new_active, ret = apply_fn(state, agg, has, global_id)
    new_state = dict(state)
    upd_mask = has & vertex_valid
    for k, v in updates.items():
        new_state[k] = jnp.where(upd_mask, v, state[k])
    new_active = new_active & vertex_valid
    total = jnp.sum(jnp.where(upd_mask, ret, 0).astype(jnp.float32))
    io = {}
    if cfg.account_io:
        arrays_bytes = sum(np.dtype(v.dtype).itemsize
                           for v in state.values())
        bitmap = phases.bitmap_model_bytes(amask)
        touched_v = phases.batch_touched(upd_mask, batch_size)
        gen_v = phases.batch_touched(amask, batch_size)
        io["vertex_read_bytes"] = ((gen_v + touched_v) * arrays_bytes
                                   + bitmap)
        io["vertex_write_bytes"] = touched_v * arrays_bytes + bitmap
    return new_state, new_active, total, io


def _zero_counters(keys):
    return {k: jnp.zeros((), jnp.float32) for k in keys}


# ---------------------------------------------------------------------------
# LOCAL executor (single device, stacked partition axis)
# ---------------------------------------------------------------------------

def make_local_pe(engine, signal_fn, slot_fn, monoid, apply_fn, backend,
                  mode_meta):
    cfg = engine.config
    spec = engine.graph.spec
    p_cnt = spec.num_partitions
    gamma = engine.fmts.gamma
    part_sizes = jnp.asarray(spec.partition_sizes(), jnp.float32)
    bt_static = engine._block if backend == "block_csr" else None
    rb_map = (jnp.asarray(row_block_batch_map(spec, bt_static.tile))
              if backend == "block_csr" else None)
    interpret = default_interpret()
    counter_keys = engine.counter_keys
    dp = functools.partial(
        _dest_phases, slot_fn=slot_fn, monoid=monoid, spec=spec, cfg=cfg,
        backend=backend, part_sizes=part_sizes, gamma=gamma,
        mode_meta=mode_meta, rb_map=rb_map, bt_static=bt_static,
        interpret=interpret)

    @jax.jit
    def step(state, active, g, fmts, global_id, bt, vals):
        counters = _zero_counters(counter_keys)
        amask = g.vertex_valid if active is None else (active & g.vertex_valid)
        # Phase 1: generate
        with jax.named_scope("generate"):
            msg = signal_fn(state, global_id)                    # [P, V]
            m_p = jnp.sum(amask, axis=1, dtype=jnp.float32)      # [P]
            counters["msgs_generated"] = jnp.sum(m_p)
            counters["msg_disk_bytes"] = jnp.sum(m_p) * (cfg.msg_bytes + 4)

        # Phase 2: filter + pass, built receive-major per destination —
        # no dense [P, P, V] broadcast of amask, no send-major transpose.
        with jax.named_scope("filter"):
            recv_mask = jax.vmap(
                lambda a_, n_, nc_, mm: phases.filter_sendmask(
                    a_, n_, nc_, mm, cfg),
                in_axes=(0, 0, 0, 0), out_axes=1)(
                amask, g.need, g.need_counts, m_p)               # [Q, P, V]
            recv_msg = jnp.where(recv_mask, msg[None, :, :], 0)
            total_sent = jnp.sum(recv_mask, dtype=jnp.float32)
            n_active = jnp.sum(amask, dtype=jnp.float32)
            counters["msgs_sent"] = total_sent
            counters["msgs_sent_nofilter"] = p_cnt * n_active
            # Network model from the routing structure: each nonempty
            # off-node (p, q) message batch is priced at its adaptive wire
            # encoding (three-way — incl. the delta-varint vpairs, whose
            # data-dependent index size comes from the same masks — when
            # compression is on).
            counts = phases.routing_counts(recv_mask)            # [Q, P]
            gapb = unib = None
            if cfg.compression:
                gapb = codec.mask_gap_bytes(recv_mask, xp=jnp)
                unib = phases.batch_value_uniform(recv_mask,
                                                  msg[None, :, :])
            cross = jnp.arange(p_cnt)[:, None] != jnp.arange(p_cnt)[None, :]
            counters["net_bytes"], counters["net_bytes_raw"] = (
                phases.net_bytes_model(counts, cross, spec.v_max,
                                       cfg.msg_bytes, gap_bytes=gapb,
                                       uniform=unib))
            counters["net_bytes_nofilter"] = ((p_cnt - 1) * n_active
                                              * (cfg.msg_bytes + 4))

        # Phases 3 + 4 per destination partition (in-HBM ChunkSource)
        d = HBMChunkSource.dest_arrays(fmts)
        if backend == "segment":
            d.update(HBMChunkSource.edge_arrays(g))
            agg, has, cd = jax.vmap(dp)(d, recv_msg, recv_mask)
            cd = {k: jnp.sum(v) for k, v in cd.items()}
        else:
            d.update(slot_row=bt.slot_row, slot_col=bt.slot_col,
                     slot_part=bt.slot_part, slot_valid=bt.slot_valid,
                     row_ptr=bt.row_ptr, tiles_cnt=bt.tiles_cnt, **vals)
            # the Pallas grid is per destination; unroll the (small) Q loop
            outs = [dp(jax.tree_util.tree_map(lambda x: x[q], d),
                       recv_msg[q], recv_mask[q]) for q in range(p_cnt)]
            agg = jnp.stack([o[0] for o in outs])
            has = jnp.stack([o[1] for o in outs])
            cd = {k: sum(o[2][k] for o in outs) for k in outs[0][2]}
        counters.update(cd)

        with jax.named_scope("apply"):
            new_state, new_active, total, io = _apply_and_account(
                state, agg, has, global_id, g.vertex_valid, apply_fn, cfg,
                spec.batch_size, amask)
        counters.update(io)
        return new_state, new_active, total, counters

    return step


# ---------------------------------------------------------------------------
# SHARD_MAP executor (partition axis = mesh axis, all_to_all exchange)
# ---------------------------------------------------------------------------

def _dense_exchange(msg_row, sendmask, axis):
    """The legacy physical wire: one dense [P, V] slab per peer (values +
    int8 presence).  Returns (recv_msg [P, V], recv_mask [P, V],
    measured payload elements this shard shipped to its P-1 peers)."""
    p_cnt, v = sendmask.shape
    send_msg = jnp.where(sendmask, msg_row[None, :], 0)          # [P, V]
    recv_msg = jax.lax.all_to_all(send_msg, axis, 0, 0, tiled=True)
    recv_mask = jax.lax.all_to_all(
        sendmask.astype(jnp.int8), axis, 0, 0, tiled=True) > 0
    measured = jnp.float32((p_cnt - 1) * (send_msg[0].size
                                          + sendmask[0].size))
    return recv_msg, recv_mask, measured


def _compacted_exchange(msg_row, sendmask, capacity, axis):
    """The compacted physical wire (DESIGN.md §12): ≤ ``capacity``
    (value, source-index) pairs per peer, re-densified on the receive
    side so phases 3-4 see the exact dense-slab layout."""
    p_cnt, v = sendmask.shape
    recv, recv_idx, _ = sparse_collectives.masked_compacted_all_to_all(
        msg_row, sendmask, capacity, axis)
    recv_msg, recv_mask = sparse_collectives.compacted_scatter_back(
        recv, recv_idx, v)
    measured = jnp.float32((p_cnt - 1) * (recv[0].size
                                          + recv_idx[0].size))
    return recv_msg, recv_mask, measured


# The SHARD_MAP wire ladder (DESIGN.md §12): adjacent rungs differ by
# this factor, and no rung is below the floor.  Every rung is one more
# branch compiled into the step.
WIRE_LADDER_STEP = 8
WIRE_LADDER_FLOOR = 8


def wire_ladder(v_max: int, msg_bytes: int, nq: int = 1) -> tuple:
    """The compacted wire's capacities for one SHARD_MAP executor,
    ascending: the largest power of two at which
    ``exchange.choose_physical_exchange`` still ships the compacted
    collective, then down by ``WIRE_LADDER_STEP`` while at least
    ``WIRE_LADDER_FLOOR``.  Empty where even the floor pays the dense
    slab's price."""
    def pays(cap):
        return exchange_mod.choose_physical_exchange(cap, v_max, msg_bytes,
                                                     nq)
    if not pays(WIRE_LADDER_FLOOR):
        return ()
    cap = WIRE_LADDER_FLOOR
    while pays(2 * cap):
        cap *= 2
    rungs = []
    while cap >= WIRE_LADDER_FLOOR:
        rungs.append(cap)
        cap //= WIRE_LADDER_STEP
    return tuple(reversed(rungs))


def sharded_wire(counts, ladder, dense, compacted, elems_model, axis,
                 counters):
    """The physical wire of one SHARD_MAP exchange (DESIGN.md §12), for
    the solo and the Q-panel step alike: the first ``ladder`` rung that
    holds this iteration's ``pmax``'d max per-peer live count (``counts``
    per destination), else the dense slab.  The rung index is the same on
    every shard, so every shard takes the same ``lax.switch`` branch and
    the collectives stay in lockstep; every branch hands phases 3-4 the
    dense receive layout, so results are bit-identical whichever ships.

    ``dense()`` and ``compacted(capacity)`` return (received values,
    received mask, measured payload elements); ``elems_model(capacity)``
    prices a rung, ``None`` the dense slab.  Writes the wire counters and
    returns (received values, received mask)."""
    live = jax.lax.pmax(jnp.max(counts), axis)
    rung = jnp.sum(live > jnp.asarray(ladder, live.dtype))
    recv_vals, recv_mask, measured = jax.lax.switch(
        rung, [functools.partial(compacted, c) for c in ladder] + [dense])
    elems = jnp.asarray([elems_model(c) for c in ladder + (None,)],
                        jnp.float32)
    dense_f = (rung == len(ladder)).astype(jnp.float32)
    is0 = (jax.lax.axis_index(axis) == 0).astype(jnp.float32)
    counters["net_payload_elems_dense"] = elems[-1]
    counters["net_payload_elems"] = elems[rung]
    counters["measured_net_payload_elems"] = measured
    counters["exchange_compacted_iters"] = (1.0 - dense_f) * is0
    counters["exchange_dense_iters"] = dense_f * is0
    return recv_vals, recv_mask


def make_sharded_pe(engine, signal_fn, slot_fn, monoid, apply_fn, backend,
                    mode_meta):
    cfg = engine.config
    spec = engine.graph.spec
    p_cnt = spec.num_partitions
    mesh, axis = engine.mesh, engine.axis
    gamma = engine.fmts.gamma
    part_sizes = jnp.asarray(spec.partition_sizes(), jnp.float32)
    bt_static = engine._block if backend == "block_csr" else None
    rb_map = (jnp.asarray(row_block_batch_map(spec, bt_static.tile))
              if backend == "block_csr" else None)
    interpret = default_interpret()
    counter_keys = engine.counter_keys
    ladder = (wire_ladder(spec.v_max, cfg.msg_bytes)
              if engine.physical_sparse_exchange else ())
    dp = functools.partial(
        _dest_phases, slot_fn=slot_fn, monoid=monoid, spec=spec, cfg=cfg,
        backend=backend, part_sizes=part_sizes, gamma=gamma,
        mode_meta=mode_meta, rb_map=rb_map, bt_static=bt_static,
        interpret=interpret)

    def step(state, active, garrs, bt, vals):
        counters = _zero_counters(counter_keys)
        vertex_valid = garrs["vertex_valid"]               # [1, V]
        amask = vertex_valid if active is None else (active & vertex_valid)
        # Phase 1: generate
        with jax.named_scope("generate"):
            msg = signal_fn(state, garrs["global_id"])     # [1, V]
            m_p = jnp.sum(amask, dtype=jnp.float32)
            counters["msgs_generated"] = m_p
            counters["msg_disk_bytes"] = m_p * (cfg.msg_bytes + 4)

        # Phase 2: filter + real interconnect exchange
        with jax.named_scope("filter"):
            my = jax.lax.axis_index(axis)
            sendmask = phases.filter_sendmask(
                amask[0], garrs["need"][0], garrs["need_counts"][0], m_p, cfg)
            counters["msgs_sent"] = jnp.sum(sendmask, dtype=jnp.float32)
            counters["msgs_sent_nofilter"] = p_cnt * m_p
            # Same routing-derived network model as LOCAL (psum across shards
            # recovers the full [Q, P] sum): per-destination batch counts,
            # priced at the adaptive wire encoding, self-shard excluded.
            counts = phases.routing_counts(sendmask)             # [Q]
            gapb = unib = None
            if cfg.compression:
                gapb = codec.mask_gap_bytes(sendmask, xp=jnp)
                unib = phases.batch_value_uniform(sendmask, msg[0][None, :])
            counters["net_bytes"], counters["net_bytes_raw"] = (
                phases.net_bytes_model(counts, jnp.arange(p_cnt) != my,
                                       spec.v_max, cfg.msg_bytes,
                                       gap_bytes=gapb, uniform=unib))
            counters["net_bytes_nofilter"] = ((p_cnt - 1) * m_p
                                              * (cfg.msg_bytes + 4))
            # Physical wire (DESIGN.md §12)
            recv_msg, recv_mask = sharded_wire(
                counts, ladder,
                lambda: _dense_exchange(msg[0], sendmask, axis),
                lambda c: _compacted_exchange(msg[0], sendmask, c, axis),
                functools.partial(phases.net_payload_elems_model, p_cnt,
                                  spec.v_max),
                axis, counters)

        # Phases 3 + 4 on this shard's destination view (in-HBM ChunkSource)
        d = {k: v[0] for k, v in HBMChunkSource.dest_arrays(garrs).items()}
        if backend == "segment":
            d.update({k: v[0]
                      for k, v in HBMChunkSource.edge_arrays(garrs).items()})
        else:
            d.update(jax.tree_util.tree_map(
                lambda x: x[0],
                dict(slot_row=bt.slot_row, slot_col=bt.slot_col,
                     slot_part=bt.slot_part, slot_valid=bt.slot_valid,
                     row_ptr=bt.row_ptr, tiles_cnt=bt.tiles_cnt, **vals)))
        agg, has, cd = dp(d, recv_msg, recv_mask)
        counters.update(cd)
        agg, has = agg[None, :], has[None, :]

        with jax.named_scope("apply"):
            new_state, new_active, total, io = _apply_and_account(
                state, agg, has, garrs["global_id"], vertex_valid, apply_fn,
                cfg, spec.batch_size, amask)
        counters.update(io)
        total = jax.lax.psum(total, axis)
        counters = {k: jax.lax.psum(v, axis) for k, v in counters.items()}
        return new_state, new_active, total, counters

    # check_vma=False: the Pallas combine kernel's out_shape carries no
    # varying-mesh-axis annotation
    return jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=P(axis),
        out_specs=(P(axis), P(axis), P(), P()), check_vma=False))


# ---------------------------------------------------------------------------
# OOC executor (disk-resident chunks + vertex spill, streamed dst-batches)
# ---------------------------------------------------------------------------

def _batch_any(mask, batch_size, num_batches):
    """[P, V] bool -> [P, B]: which intra-node batches contain a set bit."""
    p_cnt = mask.shape[0]
    pad = num_batches * batch_size - mask.shape[1]
    m = np.pad(np.asarray(mask, bool), ((0, 0), (0, pad)))
    return m.reshape(p_cnt, num_batches, batch_size).any(axis=2)


def _max_tiles_per_batch_row(g, tile, pb):
    """Static bound: max distinct (column-block) tiles in any (destination,
    dst batch, batch-local row block) — sizes the OOC per-batch Pallas
    grids so every batch compiles to the same shape."""
    spec = g.spec
    bs = spec.batch_size
    p_cnt = spec.num_partitions
    ncb = p_cnt * pb
    n_rows_b = ceil_div(bs, tile)
    esl = np.asarray(g.edge_src_local)
    esp = np.asarray(g.edge_src_part)
    edl = np.asarray(g.edge_dst_local)
    ev = np.asarray(g.edge_valid)
    best = 1
    for q in range(p_cnt):
        m = ev[q]
        if not m.any():
            continue
        dst = edl[q][m]
        k = dst // bs
        row = (dst % bs) // tile
        col = esp[q][m].astype(np.int64) * pb + esl[q][m] // tile
        key = (k.astype(np.int64) * n_rows_b + row) * ncb + col
        uniq = np.unique(key)
        cnt = np.bincount(uniq // ncb)
        if cnt.size:
            best = max(best, int(cnt.max()))
    return best


def _stream_tile_layout(work, *, tile, pb, n_rows_b, max_tpr, n_col_blocks,
                        bs):
    """Fixed-shape rectangular block-CSR layout for one streamed dst-batch.

    The streamed chunk edges are laid out into n_rows_b * max_tpr slots so
    every batch reuses one compiled kernel.  Returns (row_ptr, tile_idx,
    tile_col, row_cnt, cells, n_slots) where ``cells`` is the
    (slot, row-offset, col-offset) scatter target of each edge — the
    query-independent half of the per-batch kernel inputs, built once and
    shared by every query of a multi-query combine (DESIGN.md §11)."""
    t = tile
    dst_b = work.dst - work.k * bs
    slot_row, slot_col, rp, eslot = build_tile_struct(
        dst_b // t, work.part.astype(np.int64) * pb + work.src // t,
        n_rows_b, n_col_blocks)
    s_cnt = slot_row.shape[0]
    n_slots = n_rows_b * max_tpr
    padded_slot = (slot_row.astype(np.int64) * max_tpr
                   + (np.arange(s_cnt) - rp[slot_row]))
    tile_col = np.zeros((n_slots,), np.int32)
    tile_col[padded_slot] = slot_col
    row_cnt = (rp[1:] - rp[:-1]).astype(np.int32)
    row_ptr = np.arange(0, n_slots + 1, max_tpr, dtype=np.int32)
    tile_idx = np.arange(n_slots, dtype=np.int32)
    cells = (padded_slot[eslot], dst_b % t, work.src % t)
    return row_ptr, tile_idx, tile_col, row_cnt, cells, n_slots


def _stream_value_tiles(work, cells, n_slots, slot_fn, monoid, mode, tile):
    """Scatter the per-edge affine coefficients of one streamed dst-batch
    into value tiles: (tiles_cnt, tiles_v, tiles_b).  The coefficients are
    probed on the streamed edge data (affinity was certified by the
    engine's slot probe); like the layout, they are query-independent.
    The slot runs on host numpy, as in the segment combine: every batch
    has its own edge count, and a device call would compile per count."""
    t = tile
    identity = float(monoid.identity)
    d = np.asarray(work.data, np.float32)
    b_e = np.asarray(slot_fn(np.zeros_like(d), d), np.float32)
    a_e = np.asarray(slot_fn(np.ones_like(d), d), np.float32) - b_e
    tiles_cnt = np.zeros((n_slots, t, t), np.float32)
    np.add.at(tiles_cnt, cells, 1.0)
    tiles_v = tiles_b = None
    if mode in ("add", "add_b"):
        tiles_v = np.zeros((n_slots, t, t), np.float32)
        np.add.at(tiles_v, cells, a_e)
        if mode == "add_b":
            tiles_b = np.zeros((n_slots, t, t), np.float32)
            np.add.at(tiles_b, cells, b_e)
    else:
        tiles_b = np.full((n_slots, t, t), identity, np.float32)
        scatter = np.minimum if mode == "min" else np.maximum
        scatter.at(tiles_b, cells, b_e)
    return tiles_cnt, tiles_v, tiles_b


def _ooc_combine_batch(work, xv_q, xc_q, slot_fn, monoid, mode,
                       *, tile, pb, n_rows_b, max_tpr, bs, interpret):
    """Phase 4 for one streamed dst-batch through the Pallas combine
    kernel: fixed-shape layout + value tiles (helpers above), one kernel
    call."""
    t = tile
    identity = float(monoid.identity)
    row_ptr, tile_idx, tile_col, row_cnt, cells, n_slots = (
        _stream_tile_layout(work, tile=t, pb=pb, n_rows_b=n_rows_b,
                            max_tpr=max_tpr,
                            n_col_blocks=xc_q.shape[0] // t, bs=bs))
    tiles_cnt, tiles_v, tiles_b = _stream_value_tiles(
        work, cells, n_slots, slot_fn, monoid, mode, t)

    to_j = lambda x: None if x is None else jnp.asarray(x)
    val, hc = block_csr_combine(
        jnp.asarray(row_ptr), jnp.asarray(tile_idx), jnp.asarray(tile_col),
        jnp.asarray(row_cnt), to_j(tiles_v), to_j(tiles_b),
        jnp.asarray(tiles_cnt), jnp.asarray(xv_q), jnp.asarray(xc_q),
        mode=mode, tile=t, max_tiles_per_row=max_tpr, identity=identity,
        interpret=interpret)
    return np.asarray(val), np.asarray(hc)


def _dispatch_schedule_one_dest(source, q, recv_mask_q, part_sizes, gamma,
                                compression):
    """Host-side phases 3 + 3.5 for one destination partition, shared by
    the OOC and dist_ooc executors: dispatch presence over the
    memory-resident DCSR graph, the runtime three-way format choice
    (CSR-pruned / DCSR-raw / DCSR-delta when ``compression``, the legacy
    two-way otherwise), and the streamed-chunk schedule.  The exact
    decision both prices the model and drives the physical reads below it,
    so measured bytes match modeled bytes by design.

    Returns (counter contributions dict, chunk_active [P, B],
    schedule items [(q, k, [(p, rep), ...]), ...])."""
    p_cnt, b_cnt = source.has_csr.shape[1], source.has_csr.shape[2]
    present = (recv_mask_q[source.dcsr_part[q], source.dcsr_src[q]]
               & source.dcsr_valid[q])
    chunk_active = np.zeros((p_cnt, b_cnt), bool)
    chunk_active[source.dcsr_part[q][present],
                 source.dcsr_batch[q][present]] = True
    msgs_from = recv_mask_q.sum(axis=1)
    # Host (numpy) evaluation of the shared pricing function: this runs on
    # every worker's prefetch thread, and jax's eager dispatch serializes
    # badly across threads — numpy keeps parallel workers contention-free
    # while the float32 pinning keeps the decision bit-identical to the
    # jitted model.
    uc, ud, seek, per_chunk, per_raw = phases.format_choice_matrix(
        source.dcsr_ptr[q], source.has_csr[q],
        source.csr_bytes[q].astype(np.float32),
        source.dcsr_bytes[q].astype(np.float32),
        source.dcsr_delta_bytes[q].astype(np.float32),
        source.csr_raw_bytes[q].astype(np.float32),
        source.dcsr_raw_bytes[q].astype(np.float32),
        part_sizes, gamma, msgs_from, compression, xp=np)
    rep = np.where(uc, REP_CSR, np.where(ud, REP_DCSR_DELTA, REP_DCSR))
    # float64 sums: float32 stops being exact past 2**24 bytes, and the
    # measured side counts every byte
    red = lambda x: float(x[chunk_active].sum(dtype=np.float64))  # noqa: E731
    cd = {
        "msgs_dispatched": float(present.sum()),
        "chunks_read": float(chunk_active.sum()),
        "seek_cost": red(seek),
        "edge_read_bytes": red(per_chunk),
        "edge_read_bytes_raw": red(per_raw),
        "chunks_read_csr": float((chunk_active & uc).sum()),
        "chunks_read_dcsr_delta": float((chunk_active & ud).sum()),
        "chunks_read_dcsr": float((chunk_active & ~uc & ~ud).sum()),
    }
    schedule = []
    for k in range(b_cnt):
        ps = np.nonzero(chunk_active[:, k])[0]
        if ps.size:
            schedule.append((q, k, [(int(p), int(rep[p, k])) for p in ps]))
    return cd, chunk_active, schedule


def _block_dest_vectors(recv_mask_q, msg_q, mode, a_const, identity,
                        v_pad_t):
    """Flattened source vectors (xv, xc) for one destination's per-batch
    block_csr combine, shared by the OOC and dist_ooc executors: pad the
    [P, V] receive view to tile-aligned per-partition spans, carry message
    presence in xc, and pre-apply the affine slope for extremum modes."""
    p_cnt, v_max = recv_mask_q.shape
    mask_p = np.zeros((p_cnt, v_pad_t), bool)
    mask_p[:, :v_max] = recv_mask_q
    msg_p = np.zeros((p_cnt, v_pad_t), np.float32)
    msg_p[:, :v_max] = np.where(recv_mask_q, msg_q, 0.0)
    xc = mask_p.astype(np.float32).reshape(-1)
    if mode in ("add", "add_b"):
        xv = msg_p.reshape(-1)
    else:
        xv = np.where(mask_p, a_const * msg_p, identity).reshape(-1)
    return xv, xc


def _combine_stream_batch(wk, recv_mask_q, msg_q, slot_fn, monoid, agg, has,
                          *, backend, mode, blk, xv, xc, v_max):
    """Phase 4 for one prefetched dst-batch work item, shared by the OOC
    and dist_ooc executors: combine into ``agg[wk.q]`` / ``has[wk.q]`` with
    the numpy monoid scatter (segment) or the fixed-shape Pallas combine
    (block_csr); returns edges touched.

    recv_mask_q / msg_q: destination ``wk.q``'s [P, V] receive view
    (message values may be garbage where the mask is False — never read).
    blk: static block_csr parameters (tile, pb, n_rows_b, max_tpr, bs,
    interpret); xv / xc: the destination's flattened source vectors."""
    pm = recv_mask_q[wk.part, wk.src]
    if backend == "segment":
        mv = msg_q[wk.part, wk.src]
        # Evaluate the slot on host numpy arrays: arithmetic slot functions
        # (all four paper algorithms) stay entirely in numpy, which runs
        # GIL-free from every parallel worker — routing each per-batch call
        # through jax's eager dispatch would serialize the worker pool.
        # Message values are garbage off-mask; contrib is masked below.
        with np.errstate(all="ignore"):
            contrib = np.asarray(slot_fn(mv, wk.data), np.float32)
        dsts = wk.dst[pm]
        if dsts.size:
            scatter = {"add": np.add, "min": np.minimum,
                       "max": np.maximum}[monoid.name]
            scatter.at(agg[wk.q], dsts, contrib[pm])
            has[wk.q][dsts] = True
        return float(pm.sum())
    tile, pb, n_rows_b, max_tpr, bs, interpret = blk
    val, hc = _ooc_combine_batch(
        wk, xv, xc, slot_fn, monoid, mode, tile=tile, pb=pb,
        n_rows_b=n_rows_b, max_tpr=max_tpr, bs=bs, interpret=interpret)
    lo = wk.k * bs
    hi = min(lo + bs, v_max)
    agg[wk.q, lo:hi] = val[:hi - lo]
    has[wk.q, lo:hi] = hc[:hi - lo] > 0.5
    return float(hc.sum())


def make_ooc_pe(engine, signal_fn, slot_fn, monoid, apply_fn, backend,
                mode_meta):
    """Fully-out-of-core ProcessEdges (DESIGN.md §6).

    Phases 1–3 run host-side on the in-memory control state (active masks,
    need-bitmaps, the DCSR dispatching graph — the paper's memory-resident
    metadata); bulk data moves through measured requests only: vertex
    arrays batch-by-batch via the spill, edge chunks via the store with a
    double-buffered prefetch thread feeding phase 4.  Analytic counters are
    computed with the same formulas as the in-HBM executors; ``measured_*``
    counters report the bytes the storage tier actually served."""
    cfg = engine.config
    g = engine.graph
    spec = g.spec
    source = engine.ooc_source
    spill = engine.spill
    p_cnt, v_max = spec.num_partitions, spec.v_max
    b_cnt, bs = spec.num_batches, spec.batch_size
    need = np.asarray(g.need)
    need_counts = np.asarray(g.need_counts).astype(np.float64)
    vertex_valid = np.asarray(g.vertex_valid)
    global_id = engine.global_id
    part_sizes = np.asarray(spec.partition_sizes(), np.float32)
    gamma = engine.fmts.gamma
    identity = float(monoid.identity)
    mb = cfg.msg_bytes + 4
    interpret = default_interpret()
    tile = cfg.block_tile
    mode = blk = None
    if backend == "block_csr":
        v_pad_t = ceil_div(v_max, tile) * tile
        pb = v_pad_t // tile
        n_rows_b = ceil_div(bs, tile)
        max_tpr = _max_tiles_per_batch_row(g, tile, pb)
        mode, a_const = mode_meta
        blk = (tile, pb, n_rows_b, max_tpr, bs, interpret)

    def step(active):
        counters = {k: 0.0 for k in engine.counter_keys}
        sr0, sw0 = spill.bytes_read, spill.bytes_written
        amask = (vertex_valid if active is None
                 else np.asarray(active, bool) & vertex_valid)
        arrays_bytes = spill.arrays_bytes()
        bitmap = float(spill.bitmap_nbytes())

        # Host spans (repro.core.tracing) cover the call phase by phase:
        # generate, filter, dispatch, then per streamed batch the wait on
        # the prefetch queue and the combine, then apply.
        # Phase 1: generate — read the active bitmap + active batches
        with span("ooc.generate") as sp:
            spill.read_bitmap()                                 # measured
            gen_batches = _batch_any(amask, bs, b_cnt)
            sp.set_metadata(batches=int(gen_batches.sum()))
            gstate = {k: v[:, :v_max]
                      for k, v in spill.read(gen_batches).items()}  # measured
            # unread (inactive) batches hold zeros; their message values
            # are garbage by contract (recv_mask never selects them) —
            # silence the 0/0-style warnings that garbage can trigger in
            # numpy signal fns
            with np.errstate(all="ignore"):
                msg = np.asarray(signal_fn(gstate, global_id), np.float32)
            m_p = amask.sum(axis=1).astype(np.float64)
            counters["msgs_generated"] = float(m_p.sum())
            counters["msg_disk_bytes"] = float(m_p.sum()) * mb

        # Phase 2: filter (receive-major [Q, P, V]; traffic is analytic —
        # single host, nothing crosses a wire)
        with span("ooc.filter") as sp:
            recv_mask = np.empty((p_cnt, p_cnt, v_max), bool)
            for p in range(p_cnt):
                recv_mask[:, p] = phases.filter_sendmask(
                    amask[p], need[p], need_counts[p], m_p[p], cfg, xp=np)
            total_sent = float(recv_mask.sum())
            sp.set_metadata(sent=int(total_sent))
            n_active = float(amask.sum())
            counters["msgs_sent"] = total_sent
            counters["msgs_sent_nofilter"] = p_cnt * n_active
            counts = phases.routing_counts(recv_mask, xp=np)     # [Q, P]
            gapb = unib = None
            if cfg.compression:
                gapb = codec.mask_gap_bytes(recv_mask, xp=np)
                unib = phases.batch_value_uniform(
                    recv_mask, msg[None, :, :], xp=np)
            cross = np.arange(p_cnt)[:, None] != np.arange(p_cnt)[None, :]
            net, net_raw = phases.net_bytes_model(
                counts, cross, v_max, cfg.msg_bytes, gap_bytes=gapb,
                uniform=unib, xp=np)
            counters["net_bytes"] = float(net)
            counters["net_bytes_raw"] = float(net_raw)
            counters["net_bytes_nofilter"] = (p_cnt - 1) * n_active * mb

        # Phases 3 + 3.5 + schedule per destination (shared helper: the
        # runtime format decision prices the model AND drives the disk
        # reads below, so measured bytes match the model by design).
        with span("ooc.dispatch") as sp:
            schedule = []
            for q in range(p_cnt):
                cd, _, sched_q = _dispatch_schedule_one_dest(
                    source, q, recv_mask[q], part_sizes, gamma,
                    cfg.compression)
                for ck, cv in cd.items():
                    counters[ck] += cv
                schedule.extend(sched_q)
            sp.set_metadata(chunks=int(counters["chunks_read"]))

            # Phase 4: stream active chunks dst-batch by dst-batch, double-
            # buffered; combine with the monoid (numpy segment scatter) or
            # the Pallas block-CSR kernel.
            agg = np.full((p_cnt, v_max), identity, np.float32)
            has = np.zeros((p_cnt, v_max), bool)
            edges_touched = 0.0
            if backend == "block_csr":
                vec_cache = {}

                def vectors(q):
                    if q not in vec_cache:
                        vec_cache[q] = _block_dest_vectors(
                            recv_mask[q], msg, mode, a_const, identity,
                            v_pad_t)
                    return vec_cache[q]

        for w in ChunkPrefetcher(source, schedule,
                                 depth=cfg.ooc_prefetch_depth,
                                 device_decode=engine.device_decode):
            xv_q, xc_q = (vectors(w.q) if backend == "block_csr"
                          else (None, None))
            with span("ooc.combine", q=w.q, k=w.k, edges=int(w.src.size)):
                edges_touched += _combine_stream_batch(
                    w, recv_mask[w.q], msg, slot_fn, monoid, agg, has,
                    backend=backend, mode=mode, blk=blk, xv=xv_q, xc=xc_q,
                    v_max=v_max)
            counters["measured_chunks_read"] += w.n_chunks
            counters["measured_edge_read_bytes"] += w.nbytes
            counters["measured_chunks_device_decoded"] += w.n_device_chunks
            counters["measured_device_decode_calls"] += w.n_device_calls
        counters["edges_touched"] = edges_touched

        # Apply: read updated batches, masked update, write back + bitmap
        with span("ooc.apply") as sp:
            upd_mask = has & vertex_valid
            upd_batches = _batch_any(upd_mask, bs, b_cnt)
            sp.set_metadata(batches=int(upd_batches.sum()))
            astate_pad = spill.read(upd_batches)                # measured
            astate = {k: v[:, :v_max] for k, v in astate_pad.items()}
            state_j = {k: jnp.asarray(v) for k, v in astate.items()}
            updates, new_active, ret = apply_fn(
                state_j, jnp.asarray(agg), jnp.asarray(has), global_id)
            spill.merge_write(astate_pad, updates, upd_mask,
                              upd_batches)                      # measured
            new_active = np.asarray(new_active, bool) & vertex_valid
            spill.write_bitmap(new_active)                      # measured
            total = float(np.where(upd_mask,
                                   np.asarray(ret, np.float32), 0.0).sum())

        # Modeled vertex I/O (same formulas as _apply_and_account) next to
        # the measured bytes the spill actually served.
        gen_v = float(gen_batches.sum()) * bs
        upd_v = float(upd_batches.sum()) * bs
        counters["vertex_read_bytes"] = ((gen_v + upd_v) * arrays_bytes
                                         + bitmap)
        counters["vertex_write_bytes"] = upd_v * arrays_bytes + bitmap
        counters["measured_vertex_read_bytes"] = spill.bytes_read - sr0
        counters["measured_vertex_write_bytes"] = spill.bytes_written - sw0

        new_state = spill.state_views()
        return new_state, new_active, total, counters

    return step


# ---------------------------------------------------------------------------
# DIST_OOC executor (per-worker chunk shards + filtered sparse exchange)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DestHeader(ScheduleMark):
    """Per-destination-partition header of the lazy dist_ooc schedule.

    Produced on the prefetch thread (as :class:`DecodeAhead` delivers
    partition q's receive view and phase 3's dispatch runs over it) and
    forwarded through the chunk prefetch FIFO ahead of q's
    :class:`~repro.core.chunkstore.BatchWork` items, so the consumer learns
    each partition's receive view and dispatch counters in stream order —
    no per-partition pipeline teardown (DESIGN.md §8)."""
    q: int
    recv_mask: np.ndarray      # [P, v_max] message presence per source part
    recv_msg: np.ndarray       # [P, v_max] message values (garbage off-mask)
    counter_delta: dict        # phase-3 contributions (dispatch, seek, the
    #                            compressed/raw read-byte twins, per-format
    #                            chunk counts) of _dispatch_schedule_one_dest


def run_worker_pool(thunks, parallel: bool, pool=None):
    """Run one phase's per-worker thunks; results in worker index order.

    ``parallel=False`` runs them inline — the sequential reference order.
    ``parallel=True`` runs one thread per worker and joins them all before
    returning, which is the phase barrier the dist_ooc executor relies on
    (all sends posted before any receive drains the exchange).  ``pool``
    reuses a long-lived executor (the engine keeps one per dist_ooc
    engine) instead of spawning threads per phase.  Results (and any
    exception, re-raised from the lowest-indexed failing worker, after
    every worker has finished) are identical either way; only wall clock
    differs."""
    if not parallel or len(thunks) <= 1:
        return [t() for t in thunks]
    # Caller-runs-first: worker 0 executes on the calling thread while
    # workers 1..W-1 run on the pool — one fewer wakeup + context-switch
    # round trip per phase barrier, which matters for the small send /
    # ProcessVertices phases whose per-worker work is only a few ms.
    if pool is None:
        with ThreadPoolExecutor(max_workers=len(thunks) - 1,
                                thread_name_prefix="dist-worker") as tmp:
            futures = [tmp.submit(t) for t in thunks[1:]]
            first = thunks[0]()
            return [first] + [f.result() for f in futures]
    futures = [pool.submit(t) for t in thunks[1:]]
    try:
        first = thunks[0]()
    except BaseException:
        futures_wait(futures)      # full phase barrier even when worker 0
        raise                      # fails on the calling thread
    futures_wait(futures)
    return [first] + [f.result() for f in futures]


def make_dist_ooc_pe(engine, signal_fn, slot_fn, monoid, apply_fn, backend,
                     mode_meta):
    """Distributed fully-out-of-core ProcessEdges (DESIGN.md §7, §8).

    W workers each own a contiguous block of destination partitions backed
    by their **own** chunk-store shard and vertex spill.  Send side: each
    worker reads only its active vertex batches, generates messages, and
    posts one need-list-filtered message batch per nonempty (p, q) send
    list through the :class:`~repro.core.exchange.Exchange` — cross-worker
    batches are physically serialized with the adaptively chosen pair/slab
    wire format (measured network bytes), worker-local batches hand arrays
    over by reference.  Receive side: each worker runs one long-lived
    pipeline over all its destination partitions — a lazy schedule advanced
    on the prefetch thread iterates :class:`~repro.core.exchange.DecodeAhead`
    (partition q+1's incoming batches decode while q is in flight), computes
    q's dispatch as its view lands, and feeds both the per-partition
    :class:`DestHeader` and the selective-schedule-active chunk reads to a
    single :class:`~repro.core.chunkstore.ChunkPrefetcher` — so the last
    batch of partition q overlaps partition q+1's first disk read, and the
    consumer only ever combines and applies into the worker's spill.

    With ``EngineConfig.parallel_workers`` the W send loops and the W
    receive pipelines each run on a per-phase thread pool (workers overlap
    each other's disk, decode, and compute); every float a worker produces
    accumulates in worker-private state and is reduced in worker index
    order after the phase joins (``phases.reduce_worker_counters``), so
    parallel runs are bit-identical to sequential ones — values, counters,
    and the ``measured_* == model`` audit alike."""
    cfg = engine.config
    g = engine.graph
    spec = g.spec
    p_cnt, v_max = spec.num_partitions, spec.v_max
    b_cnt, bs = spec.num_batches, spec.batch_size
    n_workers = cfg.num_workers
    worker_parts = engine.worker_parts
    worker_of = engine.worker_of
    spills = engine.spills
    sources = engine.dist_sources
    need = np.asarray(g.need)
    need_counts = np.asarray(g.need_counts).astype(np.float64)
    vertex_valid = np.asarray(g.vertex_valid)
    global_id = engine.global_id
    part_sizes = np.asarray(spec.partition_sizes(), np.float32)
    gamma = engine.fmts.gamma
    identity = float(monoid.identity)
    mb = cfg.msg_bytes + 4
    interpret = default_interpret()
    tile = cfg.block_tile
    mode = blk = None
    if backend == "block_csr":
        v_pad_t = ceil_div(v_max, tile) * tile
        pb = v_pad_t // tile
        mode, a_const = mode_meta
        blk = (tile, pb, ceil_div(bs, tile),
               _max_tiles_per_batch_row(g, tile, pb), bs, interpret)

    parallel = cfg.parallel_workers
    # Process-mode transport (DESIGN.md §13): when the engine carries a
    # ProcContext, cross-rank message batches travel over sockets through a
    # ProcExchange and the phase barriers become allgathers keyed by
    # logical worker — reduced in the same worker/rank order every run, so
    # process mode is bit-identical to thread mode.
    ctx = getattr(engine, "proc_ctx", None)
    if ctx is not None:
        from repro.core import transport as transport_mod
        merge_op = {"min": np.minimum, "max": np.maximum,
                    "add": np.add}[monoid.name]

    def _gather_by_worker(payload_mine, extra):
        """Allgather ``({worker: value}, extra)`` and return
        (worker-ordered [W] values, rank-ordered extras)."""
        gathered = ctx.allgather((payload_mine, extra))
        by_w, extras = {}, []
        for got in gathered:
            if got is None:
                continue
            mine_r, extra_r = got
            for w, o in mine_r.items():
                if w in by_w:
                    raise transport_mod.TransportError(
                        f"logical worker {w} reported by two ranks")
                by_w[w] = o
            extras.append(extra_r)
        missing = [w for w in range(n_workers) if w not in by_w]
        if missing:
            # an owner that died before this collective started never
            # raises inside allgather (its slot is already None) — the
            # missing worker IS the death signal, so trigger recovery
            with ctx.mesh.cv:
                dead = ({ctx.assign[w] for w in missing}
                        & set(ctx.mesh.dead))
            if dead:
                raise transport_mod.WorkerDied(dead)
            raise transport_mod.TransportError(
                f"no live rank reported workers {missing}")
        return [by_w[w] for w in range(n_workers)], extras

    def step(active):
        counters = {k: 0.0 for k in engine.counter_keys}
        inj = ctx.injector if ctx is not None else None
        if inj is not None:
            inj.maybe_kill(ctx, "start")
        local_workers = (list(ctx.my_workers()) if ctx is not None
                         else list(range(n_workers)))
        amask = (vertex_valid if active is None
                 else np.asarray(active, bool) & vertex_valid)
        arrays_bytes = spills[local_workers[0]].arrays_bytes()
        spill_io0 = [(sp.bytes_read, sp.bytes_written) for sp in spills]
        store_io0 = [(src.store.chunks_read, src.store.bytes_read)
                     for src in sources]
        ex = (transport_mod.ProcExchange(
                  n_workers, v_max, cfg.compression, ctx, merge_op)
              if ctx is not None else
              exchange_mod.Exchange(n_workers, v_max,
                                    compression=cfg.compression))
        # Shared compute token for the parallel pools (utils.token_ctx):
        # CPU bursts across the W worker pipelines take turns holding it,
        # avoiding the GIL convoy of interleaved small numpy calls; queue
        # handoffs and blocking waits always happen outside the token.
        token = threading.Lock() if parallel else None
        tok = token_ctx(token)

        # Phase 1 + 2 per worker: generate from the worker's spill, filter,
        # and post message batches (serialized when crossing workers).  The
        # W send loops run on the phase pool; each returns its own routing
        # columns so the [q, p] counts assemble deterministically after the
        # join, whatever order the workers finished in.
        def send_task(w):
            t0 = time.perf_counter()
            parts = worker_parts[w]
            lo, hi = parts[0], parts[-1] + 1
            spill = spills[w]
            with tok:                       # compute token: generate burst
                spill.read_bitmap()                         # measured
                am_w = amask[lo:hi]
                gen_b = _batch_any(am_w, bs, b_cnt)
                gstate = {k: v[:, :v_max]
                          for k, v in spill.read(gen_b).items()}  # measured
            with tok, np.errstate(all="ignore"):
                msg_w = np.asarray(signal_fn(
                    {k: jnp.asarray(v) for k, v in gstate.items()},
                    global_id[lo:hi]), np.float32)
            counts_w = np.zeros((p_cnt, len(parts)), np.float64)
            gapb_w = np.zeros((p_cnt, len(parts)), np.float64)
            unib_w = np.zeros((p_cnt, len(parts)), bool)
            for i, p in enumerate(parts):
                with tok:                   # compute token: filter + encode
                    m_p = float(am_w[i].sum())
                    sendmask = phases.filter_sendmask(
                        am_w[i], need[p], need_counts[p], m_p, cfg, xp=np)
                    counts_w[:, i] = phases.routing_counts(sendmask, xp=np)
                    if cfg.compression:
                        # vpairs index-stream sizes and value-uniformity
                        # of the very masks the wire serializes — the
                        # model's data-dependent terms.
                        gapb_w[:, i] = codec.mask_gap_bytes(sendmask, xp=np)
                        unib_w[:, i] = phases.batch_value_uniform(
                            sendmask, msg_w[i][None, :], xp=np)
                    for q in range(p_cnt):
                        c = int(counts_w[q, i])
                        if c:
                            ex.post(w, int(worker_of[q]), p, q, sendmask[q],
                                    msg_w[i], count=c)
            return counts_w, gapb_w, unib_w, float(gen_b.sum()), \
                time.perf_counter() - t0

        send_out = run_worker_pool(
            [functools.partial(send_task, w) for w in local_workers],
            parallel, pool=engine.worker_pool)
        if ctx is not None:
            # Send barrier: every rank contributes its workers' routing
            # columns and its exchange counter snapshot.  TCP FIFO per
            # link means a sender's data frames precede its allgather
            # contribution — once the gather completes, every expected
            # frame has arrived, been dropped (ledger resend below), or
            # is held (deferred past the straggler deadline).
            send_rows, ex_snaps = _gather_by_worker(
                dict(zip(local_workers, send_out)), ex.counter_snapshot())
            send_items = list(enumerate(send_rows))
        else:
            send_items = list(zip(local_workers, send_out))
        counts = np.zeros((p_cnt, p_cnt), np.float64)       # [q, p] routing
        gapb = np.zeros((p_cnt, p_cnt), np.float64)
        unib = np.zeros((p_cnt, p_cnt), bool)
        gen_batches_total = 0.0
        for w, (counts_w, gapb_w, unib_w, gen_b_sum, dt) in send_items:
            lo, hi = worker_parts[w][0], worker_parts[w][-1] + 1
            counts[:, lo:hi] = counts_w
            gapb[:, lo:hi] = gapb_w
            unib[:, lo:hi] = unib_w
            gen_batches_total += gen_b_sum
            engine.worker_times[w]["send_s"] += dt

        n_active = float(amask.sum())
        counters["msgs_generated"] = n_active
        counters["msg_disk_bytes"] = n_active * mb
        counters["msgs_sent"] = float(counts.sum())
        counters["msgs_sent_nofilter"] = p_cnt * n_active
        counters["net_bytes_nofilter"] = (p_cnt - 1) * n_active * mb
        # Modeled network traffic from the same routing counts the wire
        # used; cross iff source and destination workers differ.
        cross = (worker_of[np.newaxis, :] != worker_of[:, np.newaxis])
        net, net_raw = phases.net_bytes_model(
            counts, cross, v_max, cfg.msg_bytes,
            gap_bytes=gapb if cfg.compression else None,
            uniform=unib if cfg.compression else None, xp=np)
        counters["net_bytes"] = float(net)
        counters["net_bytes_raw"] = float(net_raw)
        if ctx is not None:
            # Wire counters are global: sum the per-rank snapshots in rank
            # order (integer byte/batch tallies — the sums are exact, so
            # process mode reproduces thread mode's single-process
            # accumulation bit for bit).
            for ck, nk in (("bytes_sent", "measured_net_bytes"),
                           ("pair_batches", "net_pair_batches"),
                           ("slab_batches", "net_slab_batches"),
                           ("vpair_batches", "net_vpair_batches"),
                           ("uval_batches", "net_uval_batches")):
                counters[nk] = float(sum(s[ck] for s in ex_snaps))
            posted_total = np.zeros((n_workers, n_workers), np.int64)
            for s in ex_snaps:
                posted_total += np.asarray(s["posted"], np.int64)
            # Receive barrier: block until every cross-rank frame destined
            # to this rank's workers arrived, was redelivered from the
            # sender's ledger (injected drops), or was acknowledged as
            # held (injected delays, merged next op).
            if inj is not None:
                inj.maybe_kill(ctx, "recv")
            ctx.resolve_arrivals(posted_total)
        else:
            counters["measured_net_bytes"] = ex.bytes_sent
            counters["net_pair_batches"] = float(ex.pair_batches)
            counters["net_slab_batches"] = float(ex.slab_batches)
            counters["net_vpair_batches"] = float(ex.vpair_batches)
            counters["net_uval_batches"] = float(ex.uval_batches)

        # Phases 3 + 4 + apply per worker, against its own shard.  The
        # send pool has fully joined, so every message batch is posted
        # before any receive pipeline drains the exchange (phase barrier).
        # agg / has / new_active rows are partitioned by ownership, so the
        # concurrent writes below never alias.
        agg = np.full((p_cnt, v_max), identity, np.float32)
        has = np.zeros((p_cnt, v_max), bool)
        new_active = np.zeros((p_cnt, v_max), bool)

        def recv_task(w):
            t0 = time.perf_counter()
            parts = worker_parts[w]
            lo, hi = parts[0], parts[-1] + 1
            spill = spills[w]
            source = sources[w]
            cw = {}                       # worker-private counter deltas

            def lazy_schedule():
                # Runs on the prefetch thread: as DecodeAhead delivers
                # partition q's receive view, phase 3's dispatch + the
                # runtime format choice price q's reads and emit them
                # right behind q's header — partition q+1's decode, q's
                # dispatch, and q-1's tail disk reads all overlap.
                for q, recv_mask_q, recv_msg_q in exchange_mod.DecodeAhead(
                        ex, w, parts, p_cnt, compute_lock=token,
                        runner=engine.pipeline_pool,
                        device_decode=engine.device_decode):
                    with tok:               # compute token: dispatch burst
                        cd, _, sched_q = _dispatch_schedule_one_dest(
                            source, q, recv_mask_q, part_sizes, gamma,
                            cfg.compression)
                        header = DestHeader(
                            q=q, recv_mask=recv_mask_q, recv_msg=recv_msg_q,
                            counter_delta=cd)
                    yield header
                    yield from sched_q

            w_edges = 0.0
            w_dev_chunks = 0.0
            w_dev_calls = 0.0
            cur = None
            xv_q = xc_q = None
            for item in ChunkPrefetcher(source, lazy_schedule(),
                                        depth=cfg.ooc_prefetch_depth,
                                        compute_lock=token,
                                        runner=engine.pipeline_pool,
                                        device_decode=engine.device_decode):
                if isinstance(item, DestHeader):
                    cur = item
                    xv_q = xc_q = None
                    for ck, cv in item.counter_delta.items():
                        cw[ck] = cw.get(ck, 0.0) + cv
                    continue
                w_dev_chunks += item.n_device_chunks
                w_dev_calls += item.n_device_calls
                with tok:                   # compute token: combine burst
                    if backend == "block_csr" and xv_q is None:
                        xv_q, xc_q = _block_dest_vectors(
                            cur.recv_mask, cur.recv_msg, mode, a_const,
                            identity, v_pad_t)
                    w_edges += _combine_stream_batch(
                        item, cur.recv_mask, cur.recv_msg, slot_fn, monoid,
                        agg, has, backend=backend, mode=mode, blk=blk,
                        xv=xv_q, xc=xc_q, v_max=v_max)

            # Apply into this worker's spill (measured vertex I/O).
            with tok:                       # compute token: apply burst
                upd_w = has[lo:hi] & vertex_valid[lo:hi]
                upd_b = _batch_any(upd_w, bs, b_cnt)
                astate_pad = spill.read(upd_b)              # measured
                astate = {k: v[:, :v_max] for k, v in astate_pad.items()}
            with tok:
                updates, na_w, ret = apply_fn(
                    {k: jnp.asarray(v) for k, v in astate.items()},
                    jnp.asarray(agg[lo:hi]), jnp.asarray(has[lo:hi]),
                    global_id[lo:hi])
            with tok:
                spill.merge_write(astate_pad, updates, upd_w,
                                  upd_b)                    # measured
                na_w = np.asarray(na_w, bool) & vertex_valid[lo:hi]
                spill.write_bitmap(na_w)                    # measured
                new_active[lo:hi] = na_w
                total_w = float(np.where(
                    upd_w, np.asarray(ret, np.float32), 0.0).sum())

            # Per-worker measured traffic (table 7's max-per-worker rows).
            cr0, br0 = store_io0[w]
            sr0, sw0 = spill_io0[w]
            edge_b = source.store.bytes_read - br0
            vert_b = ((spill.bytes_read - sr0)
                      + (spill.bytes_written - sw0))
            cw["measured_chunks_read"] = source.store.chunks_read - cr0
            cw["measured_edge_read_bytes"] = edge_b
            cw["measured_chunks_device_decoded"] = w_dev_chunks
            cw["measured_device_decode_calls"] = w_dev_calls
            cw["measured_vertex_read_bytes"] = spill.bytes_read - sr0
            cw["measured_vertex_write_bytes"] = spill.bytes_written - sw0
            cw["edges_touched"] = w_edges
            wt = engine.worker_totals[w]
            wt["disk_bytes"] += edge_b + vert_b
            wt["net_bytes"] += float(ex.bytes_by_sender[w])
            wt["edges_touched"] += w_edges
            return cw, total_w, float(upd_b.sum()), time.perf_counter() - t0

        recv_out = run_worker_pool(
            [functools.partial(recv_task, w) for w in local_workers],
            parallel, pool=engine.worker_pool)
        if ctx is not None:
            if inj is not None:
                inj.maybe_kill(ctx, "apply")
            # Final collective: per-worker results (counters, totals, the
            # new-active rows, and the authoritative worker_totals
            # snapshots) gathered by logical worker; per-rank deferred
            # counts ride along so a round with held (delayed) frames
            # cannot read as converged.
            mine = {w: (cw, total_w, upd_b_sum, dt,
                        new_active[worker_parts[w][0]:
                                   worker_parts[w][-1] + 1].copy(),
                        dict(engine.worker_totals[w]))
                    for w, (cw, total_w, upd_b_sum, dt)
                    in zip(local_workers, recv_out)}
            recv_rows, deferred = _gather_by_worker(
                mine, ctx.pending_deferred())
            recv_items = []
            for w, (cw, total_w, upd_b_sum, dt, na_w, wt) in \
                    enumerate(recv_rows):
                lo, hi = worker_parts[w][0], worker_parts[w][-1] + 1
                new_active[lo:hi] = np.asarray(na_w, bool)
                engine.worker_totals[w] = dict(wt)
                recv_items.append((w, (cw, total_w, upd_b_sum, dt)))
            pending = int(sum(int(d) for d in deferred))
        else:
            recv_items = list(zip(local_workers, recv_out))
            pending = 0
        # Deterministic reduction: every float above accumulated in
        # worker-private state; summing in worker index order after the
        # join makes parallel runs bit-identical to sequential ones.
        phases.reduce_worker_counters(
            counters, [cw for _, (cw, _, _, _) in recv_items])
        total = 0.0
        upd_batches_total = 0.0
        for w, (_, total_w, upd_b_sum, dt) in recv_items:
            total += total_w
            upd_batches_total += upd_b_sum
            engine.worker_times[w]["recv_s"] += dt
        # Held (delayed) frames apply next op through the slot monoid; the
        # promise keeps fixpoint drivers (they stop on total == 0) alive
        # until the deferred contributions actually land.
        total += float(pending)

        # Modeled vertex I/O: identical formulas to the other executors
        # (per-worker bitmaps sum to the full [P, V] bitmap bytes).
        bitmap = float(sum(sp.bitmap_nbytes() for sp in spills))
        gen_v = gen_batches_total * bs
        upd_v = upd_batches_total * bs
        counters["vertex_read_bytes"] = ((gen_v + upd_v) * arrays_bytes
                                         + bitmap)
        counters["vertex_write_bytes"] = upd_v * arrays_bytes + bitmap

        new_state = engine._dist_state_views()
        return new_state, new_active, total, counters

    return step
