"""Multi-query (Q-panel) ProcessEdges executors (DESIGN.md §11).

Concurrent query serving amortizes ONE selective chunk stream across Q
simultaneous queries: vertex state grows a trailing query axis
([P, v_max, Q] panels), the scheduled active set is the bitwise OR of the
per-query frontiers, and per-query masks keep every monoid combine
independent — each query's column is bit-identical to the solo run that
query would have made, while the chunk decode, the disk seeks, and the
shared-index wire panels are paid once for the whole batch.

Counter semantics (the per-query byte attribution the serving benchmark
prices):

* **logical counters** — ``msgs_generated`` / ``msgs_sent`` /
  ``edges_touched`` / the vertex byte terms — are the SUM over queries of
  the solo formulas; vertex spill traffic is physically per-query (each
  query owns ``{key}@q{j}`` columns and an ``active_q{j}`` bitmap), so
  measured == Σ solo exactly.
* **shared-stream counters** — ``msgs_dispatched`` / ``chunks_read`` /
  ``seek_cost`` / ``edge_read_bytes`` / ``net_bytes`` — are priced ONCE
  over the union frontier.  The union format choice is pure min-bytes
  (:func:`repro.core.phases.mq_format_choice_matrix`) and the wire price
  is ``min(panel, Σ legacy)`` per batch
  (:func:`repro.core.phases.mq_wire_bytes`), so the batched pass never
  costs more than the Q solo passes it replaces — that inequality is what
  the serving curve (bytes-per-query ~ 1/Q) and the parity suite assert.

A query whose frontier has died is *physically* skipped: the OOC / dist
executors read none of its spill batches, none of its bitmaps, and post
none of its wire columns (zero cost); the jitted LOCAL / SHARD_MAP
executors gate the only shape-static model term (the bitmap bytes) on an
aliveness flag so the analytic counters agree.
"""
from __future__ import annotations

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import codec
from repro.core import exchange as exchange_mod
from repro.core import phases
from repro.core import sparse_collectives
from repro.core.chunkstore import REP_CSR, REP_DCSR, REP_DCSR_DELTA, \
    ChunkPrefetcher, HBMChunkSource
from repro.core.executor import (
    DestHeader, _apply_and_account, _batch_any, _block_dest_vectors,
    _combine_stream_batch, _max_tiles_per_batch_row, _stream_tile_layout,
    _stream_value_tiles, _zero_counters, run_worker_pool, sharded_wire,
    wire_ladder,
)
from repro.kernels.csr_spmv import block_csr_combine_mq, default_interpret
from repro.utils import ceil_div, token_ctx


def mq_base_names(spill) -> list[str]:
    """Base state-array names of a multi-query spill (the ``{key}@q{j}``
    flattening inverted), in the insertion order of the loaded state."""
    suffix = "@q0"
    return [n[: -len(suffix)] for n in spill.names() if n.endswith(suffix)]


def mq_query_keys(base: list[str], j: int) -> list[str]:
    return [f"{k}@q{j}" for k in base]


# ---------------------------------------------------------------------------
# Shared host-side pieces (OOC + dist_ooc)
# ---------------------------------------------------------------------------

def _dispatch_schedule_one_dest_mq(source, q, union_mask_q, part_sizes,
                                   gamma, compression):
    """Multi-query twin of ``executor._dispatch_schedule_one_dest``:
    dispatch presence over the UNION receive mask and the pure min-bytes
    format choice (:func:`repro.core.phases.mq_format_choice_matrix`) —
    the one decision that both prices the model and drives the physical
    chunk reads, so measured union bytes equal the modeled ones and never
    exceed what any solo frontier would have paid per chunk."""
    p_cnt, b_cnt = source.has_csr.shape[1], source.has_csr.shape[2]
    present = (union_mask_q[source.dcsr_part[q], source.dcsr_src[q]]
               & source.dcsr_valid[q])
    chunk_active = np.zeros((p_cnt, b_cnt), bool)
    chunk_active[source.dcsr_part[q][present],
                 source.dcsr_batch[q][present]] = True
    msgs_from = union_mask_q.sum(axis=1)
    uc, ud, seek, per_chunk, per_raw = phases.mq_format_choice_matrix(
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


def _mq_panel_vectors(recv_mask, recv_msg, mode, a_const, identity,
                      v_pad_t, nq):
    """Stack per-query ``_block_dest_vectors`` outputs into the [C*T, Q]
    value panels one panel-kernel call consumes (dead queries contribute
    identity / zero columns)."""
    xvs, xcs = [], []
    for j in range(nq):
        xv_j, xc_j = _block_dest_vectors(recv_mask[j], recv_msg[j], mode,
                                         a_const, identity, v_pad_t)
        xvs.append(xv_j)
        xcs.append(xc_j)
    return np.stack(xvs, axis=1), np.stack(xcs, axis=1)


def _ooc_combine_batch_mq(work, xv_panel, xc_panel, slot_fn, monoid, mode,
                          *, tile, pb, n_rows_b, max_tpr, bs, num_queries,
                          interpret):
    """Phase 4 for one streamed dst-batch through the multi-query Pallas
    combine: the tile layout and value tiles are built ONCE from the
    decoded chunk edges (they are query-independent) and one kernel call
    combines them against all Q message columns — the "one decode feeds Q
    combines" amortization at the kernel level."""
    t = tile
    identity = float(monoid.identity)
    row_ptr, tile_idx, tile_col, row_cnt, cells, n_slots = (
        _stream_tile_layout(work, tile=t, pb=pb, n_rows_b=n_rows_b,
                            max_tpr=max_tpr,
                            n_col_blocks=xc_panel.shape[0] // t, bs=bs))
    tiles_cnt, tiles_v, tiles_b = _stream_value_tiles(
        work, cells, n_slots, slot_fn, monoid, mode, t)
    to_j = lambda x: None if x is None else jnp.asarray(x)
    val, hc = block_csr_combine_mq(
        jnp.asarray(row_ptr), jnp.asarray(tile_idx), jnp.asarray(tile_col),
        jnp.asarray(row_cnt), to_j(tiles_v), to_j(tiles_b),
        jnp.asarray(tiles_cnt), jnp.asarray(xv_panel),
        jnp.asarray(xc_panel), mode=mode, tile=t,
        max_tiles_per_row=max_tpr, num_queries=num_queries,
        identity=identity, interpret=interpret)
    return np.asarray(val), np.asarray(hc)


# ---------------------------------------------------------------------------
# LOCAL executor (single device, trailing query axis)
# ---------------------------------------------------------------------------

def make_local_pe_mq(engine, signal_fn, slot_fn, monoid, apply_fn, nq):
    """Multi-query LOCAL ProcessEdges (segment backend).

    Per-query phases 1/2/4/apply are the exact solo traced ops (unrolled
    over the small Q axis — bit-identical columns); the chunk model and
    the network price run once over the union frontier."""
    cfg = engine.config
    spec = engine.graph.spec
    p_cnt, v_max, b_cnt = (spec.num_partitions, spec.v_max,
                           spec.num_batches)
    gamma = engine.fmts.gamma
    part_sizes = jnp.asarray(spec.partition_sizes(), jnp.float32)
    counter_keys = engine.counter_keys
    mb = cfg.msg_bytes + 4

    def dest_sched(d_, um_q):
        chunk_active, dispatched = phases.dispatch_one_dest(
            d_["dcsr_src"], d_["dcsr_part"], d_["dcsr_batch"],
            d_["dcsr_valid"], um_q, v_max, b_cnt)
        c = {"msgs_dispatched": dispatched,
             "chunks_read": jnp.sum(chunk_active, dtype=jnp.float32)}
        msgs_from = jnp.sum(um_q, axis=1).astype(jnp.int32)
        c.update(phases.mq_format_choice_one_dest(
            d_["dcsr_ptr"], d_["has_csr"], d_["csr_bytes"],
            d_["dcsr_bytes"], d_["dcsr_delta_bytes"], d_["csr_raw_bytes"],
            d_["dcsr_raw_bytes"], part_sizes, gamma, msgs_from,
            cfg.compression, chunk_active))
        return c

    def seg_one(e_, rmsg, rmask):
        return phases.process_segment_one_dest(
            e_["edge_src_part"], e_["edge_src_local"], e_["edge_dst_local"],
            e_["edge_data"], e_["edge_valid"], rmsg, rmask, slot_fn,
            monoid, v_max)

    @jax.jit
    def step(state, active, g, fmts, global_id):
        counters = _zero_counters(counter_keys)
        # Phases 1 + 2 per query: solo ops on the query's state column.
        amasks, msgs, recv_masks = [], [], []
        for j in range(nq):
            state_j = {k: v[..., j] for k, v in state.items()}
            amask_j = (g.vertex_valid if active is None
                       else (active[..., j] & g.vertex_valid))
            msg_j = signal_fn(state_j, global_id)                # [P, V]
            m_p = jnp.sum(amask_j, axis=1, dtype=jnp.float32)    # [P]
            n_active = jnp.sum(m_p)
            counters["msgs_generated"] += n_active
            counters["msg_disk_bytes"] += n_active * mb
            recv_mask_j = jax.vmap(
                lambda a_, n_, nc_, mm: phases.filter_sendmask(
                    a_, n_, nc_, mm, cfg),
                in_axes=(0, 0, 0, 0), out_axes=1)(
                amask_j, g.need, g.need_counts, m_p)             # [Q, P, V]
            counters["msgs_sent"] += jnp.sum(recv_mask_j,
                                             dtype=jnp.float32)
            counters["msgs_sent_nofilter"] += p_cnt * n_active
            counters["net_bytes_nofilter"] += ((p_cnt - 1) * n_active * mb)
            amasks.append(amask_j)
            msgs.append(msg_j)
            recv_masks.append(recv_mask_j)

        # Union frontier: one scheduled active set for the whole batch.
        union_mask = recv_masks[0]
        for j in range(1, nq):
            union_mask = union_mask | recv_masks[j]              # [Q, P, V]

        # Network model: per-batch min(panel, Σ legacy) over the union.
        counts = jnp.stack([phases.routing_counts(rm)
                            for rm in recv_masks])               # [nq, Q, P]
        ucounts = phases.routing_counts(union_mask)              # [Q, P]
        gapb = unib = ugap = None
        if cfg.compression:
            gapb = jnp.stack([codec.mask_gap_bytes(rm, xp=jnp)
                              for rm in recv_masks])
            unib = jnp.stack([phases.batch_value_uniform(
                rm, m[None, :, :]) for rm, m in zip(recv_masks, msgs)])
            ugap = codec.mask_gap_bytes(union_mask, xp=jnp)
        cross = jnp.arange(p_cnt)[:, None] != jnp.arange(p_cnt)[None, :]
        counters["net_bytes"], counters["net_bytes_raw"] = (
            phases.mq_net_bytes_model(counts, ucounts, cross, v_max,
                                      cfg.msg_bytes, gap_bytes=gapb,
                                      union_gap=ugap, uniform=unib))

        # Phase 3 + the chunk model once, over the union frontier.
        d = HBMChunkSource.dest_arrays(fmts)
        cd = jax.vmap(dest_sched)(d, union_mask)
        for k, v in cd.items():
            counters[k] += jnp.sum(v)

        # Phase 4 + apply per query (solo ops; the union adds nothing to a
        # query's column — presence masks exclude foreign edges).
        e = HBMChunkSource.edge_arrays(g)
        new_cols, new_act, totals = {k: [] for k in state}, [], []
        for j in range(nq):
            recv_msg_j = jnp.where(recv_masks[j], msgs[j][None, :, :], 0)
            agg, has, touched = jax.vmap(seg_one)(e, recv_msg_j,
                                                  recv_masks[j])
            counters["edges_touched"] += jnp.sum(touched)
            state_j = {k: v[..., j] for k, v in state.items()}
            ns_j, na_j, total_j, io = _apply_and_account(
                state_j, agg, has, global_id, g.vertex_valid, apply_fn,
                cfg, spec.batch_size, amasks[j])
            # The bitmap term of the vertex model is shape-static; gate it
            # (and the rest of the per-query I/O) on the query being alive
            # so a converged query prices zero, like the physical skip.
            alive_f = jnp.any(amasks[j]).astype(jnp.float32)
            for k, v in io.items():
                counters[k] += alive_f * v
            for k in state:
                new_cols[k].append(ns_j[k])
            new_act.append(na_j)
            totals.append(total_j)

        new_state = {k: jnp.stack(cols, axis=-1)
                     for k, cols in new_cols.items()}
        new_active = jnp.stack(new_act, axis=-1)
        return new_state, new_active, jnp.stack(totals), counters

    return step


# ---------------------------------------------------------------------------
# SHARD_MAP executor (mesh axis, one panel all_to_all)
# ---------------------------------------------------------------------------

def make_sharded_pe_mq(engine, signal_fn, slot_fn, monoid, apply_fn, nq):
    """Multi-query SHARD_MAP ProcessEdges (segment backend).

    The exchange ships ONE [P, V, Q] panel ``all_to_all`` (a pure per-column
    permutation — each column equals the solo exchange bit-for-bit); the
    network model prices each crossing batch at the multi-query minimum."""
    cfg = engine.config
    spec = engine.graph.spec
    p_cnt, v_max, b_cnt = (spec.num_partitions, spec.v_max,
                           spec.num_batches)
    mesh, axis = engine.mesh, engine.axis
    gamma = engine.fmts.gamma
    part_sizes = jnp.asarray(spec.partition_sizes(), jnp.float32)
    counter_keys = engine.counter_keys
    ladder = (wire_ladder(v_max, cfg.msg_bytes, nq)
              if engine.physical_sparse_exchange else ())
    mb = cfg.msg_bytes + 4

    def step(state, active, garrs):
        counters = _zero_counters(counter_keys)
        vertex_valid = garrs["vertex_valid"]                 # [1, V]
        my = jax.lax.axis_index(axis)

        amasks, msgs, sendmasks = [], [], []
        for j in range(nq):
            state_j = {k: v[..., j] for k, v in state.items()}
            amask_j = (vertex_valid if active is None
                       else (active[..., j] & vertex_valid))
            msg_j = signal_fn(state_j, garrs["global_id"])    # [1, V]
            m_p = jnp.sum(amask_j, dtype=jnp.float32)
            counters["msgs_generated"] += m_p
            counters["msg_disk_bytes"] += m_p * mb
            sendmask_j = phases.filter_sendmask(
                amask_j[0], garrs["need"][0], garrs["need_counts"][0],
                m_p, cfg)                                     # [P, V]
            counters["msgs_sent"] += jnp.sum(sendmask_j,
                                             dtype=jnp.float32)
            counters["msgs_sent_nofilter"] += p_cnt * m_p
            counters["net_bytes_nofilter"] += (p_cnt - 1) * m_p * mb
            amasks.append(amask_j)
            msgs.append(msg_j)
            sendmasks.append(sendmask_j)

        union_sm = sendmasks[0]
        for j in range(1, nq):
            union_sm = union_sm | sendmasks[j]                # [P, V]

        counts = jnp.stack([phases.routing_counts(sm)
                            for sm in sendmasks])             # [nq, P]
        ucounts = phases.routing_counts(union_sm)             # [P]
        gapb = unib = ugap = None
        if cfg.compression:
            gapb = jnp.stack([codec.mask_gap_bytes(sm, xp=jnp)
                              for sm in sendmasks])
            unib = jnp.stack([phases.batch_value_uniform(
                sm, m[0][None, :]) for sm, m in zip(sendmasks, msgs)])
            ugap = codec.mask_gap_bytes(union_sm, xp=jnp)
        counters["net_bytes"], counters["net_bytes_raw"] = (
            phases.mq_net_bytes_model(counts, ucounts,
                                      jnp.arange(p_cnt) != my, v_max,
                                      cfg.msg_bytes, gap_bytes=gapb,
                                      union_gap=ugap, uniform=unib))

        # ONE panel exchange: all_to_all permutes rows per column, so each
        # query's received view is bit-identical to its solo exchange.
        # Physically (DESIGN.md §12) the panel ships either the dense
        # [P, V, nq] slab or the union-compacted panel — ONE shared
        # source-index stream per peer plus nq value columns and nq
        # presence flags, the collective twin of the FMT_MQPANEL wire
        # pricing — chosen as for the solo exchange.
        send_valsp = jnp.stack([m[0] for m in msgs], axis=-1)  # [V, nq]
        send_maskp = jnp.stack(sendmasks, axis=-1)            # [P, V, nq]

        def dense_panel():
            sv = jnp.where(send_maskp, send_valsp[None], 0)   # [P, V, nq]
            rv = jax.lax.all_to_all(sv, axis, 0, 0, tiled=True)
            rm = jax.lax.all_to_all(send_maskp.astype(jnp.int8), axis,
                                    0, 0, tiled=True) > 0     # [P, V, nq]
            return rv, rm, jnp.float32((p_cnt - 1) * 2 * sv[0].size)

        def compacted_panel(capacity):
            rv, rm, ridx, _ = \
                sparse_collectives.masked_compacted_all_to_all_mq(
                    send_valsp, send_maskp, capacity, axis)
            rvf, rmf = sparse_collectives.compacted_scatter_back_mq(
                rv, rm, ridx, v_max)
            measured = jnp.float32(
                (p_cnt - 1) * (rv[0].size + rm[0].size + ridx[0].size))
            return rvf, rmf, measured

        recv_vals, recv_maskp = sharded_wire(
            ucounts, ladder, dense_panel, compacted_panel,
            functools.partial(phases.net_payload_elems_model, p_cnt, v_max,
                              nq=nq),
            axis, counters)

        # Phase 3 + chunk model over the union of the received columns.
        d = {k: v[0] for k, v in HBMChunkSource.dest_arrays(garrs).items()}
        union_recv = jnp.any(recv_maskp, axis=-1)             # [P, V]
        chunk_active, dispatched = phases.dispatch_one_dest(
            d["dcsr_src"], d["dcsr_part"], d["dcsr_batch"],
            d["dcsr_valid"], union_recv, v_max, b_cnt)
        counters["msgs_dispatched"] += dispatched
        counters["chunks_read"] += jnp.sum(chunk_active,
                                           dtype=jnp.float32)
        cd = phases.mq_format_choice_one_dest(
            d["dcsr_ptr"], d["has_csr"], d["csr_bytes"], d["dcsr_bytes"],
            d["dcsr_delta_bytes"], d["csr_raw_bytes"], d["dcsr_raw_bytes"],
            part_sizes, gamma,
            jnp.sum(union_recv, axis=1).astype(jnp.int32),
            cfg.compression, chunk_active)
        for k, v in cd.items():
            counters[k] += v

        # Phase 4 + apply per query on this shard's destination view.
        e = {k: v[0] for k, v in HBMChunkSource.edge_arrays(garrs).items()}
        new_cols, new_act, totals = {k: [] for k in state}, [], []
        for j in range(nq):
            rmask_j = recv_maskp[..., j]
            rmsg_j = jnp.where(rmask_j, recv_vals[..., j], 0)
            agg, has, touched = phases.process_segment_one_dest(
                e["edge_src_part"], e["edge_src_local"],
                e["edge_dst_local"], e["edge_data"], e["edge_valid"],
                rmsg_j, rmask_j, slot_fn, monoid, v_max)
            counters["edges_touched"] += touched
            state_j = {k: v[..., j] for k, v in state.items()}
            ns_j, na_j, total_j, io = _apply_and_account(
                state_j, agg[None, :], has[None, :], garrs["global_id"],
                vertex_valid, apply_fn, cfg, spec.batch_size, amasks[j])
            # Global aliveness (a frontier alive on ANY shard keeps the
            # whole query's bitmap I/O priced, as a solo run would).
            alive_f = (jax.lax.psum(
                jnp.sum(amasks[j], dtype=jnp.float32), axis) > 0
            ).astype(jnp.float32)
            for k, v in io.items():
                counters[k] += alive_f * v
            for k in state:
                new_cols[k].append(ns_j[k])
            new_act.append(na_j)
            totals.append(total_j)

        new_state = {k: jnp.stack(cols, axis=-1)
                     for k, cols in new_cols.items()}
        new_active = jnp.stack(new_act, axis=-1)
        totals = jax.lax.psum(jnp.stack(totals), axis)
        counters = {k: jax.lax.psum(v, axis) for k, v in counters.items()}
        return new_state, new_active, totals, counters

    return jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=P(axis),
        out_specs=(P(axis), P(axis), P(), P()), check_vma=False))


# ---------------------------------------------------------------------------
# OOC executor (one spill with per-query columns, one union chunk stream)
# ---------------------------------------------------------------------------

def make_ooc_pe_mq(engine, signal_fn, slot_fn, monoid, apply_fn, backend,
                   mode_meta, nq):
    """Multi-query fully-out-of-core ProcessEdges.

    Vertex traffic is physically per-query (``{key}@q{j}`` columns,
    ``active_q{j}`` bitmaps — a dead query costs zero bytes); the edge
    stream runs ONCE over the union schedule and each prefetched batch
    feeds every alive query's combine (one decode, Q combines)."""
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
    mode = a_const = v_pad_t = pb = n_rows_b = max_tpr = None
    if backend == "block_csr":
        v_pad_t = ceil_div(v_max, tile) * tile
        pb = v_pad_t // tile
        n_rows_b = ceil_div(bs, tile)
        max_tpr = _max_tiles_per_batch_row(g, tile, pb)
        mode, a_const = mode_meta

    def step(active):
        counters = {k: 0.0 for k in engine.counter_keys}
        sr0, sw0 = spill.bytes_read, spill.bytes_written
        base = mq_base_names(spill)
        bitmap = float(spill.bitmap_nbytes())
        amask = [(vertex_valid if active is None
                  else np.asarray(active[..., j], bool) & vertex_valid)
                 for j in range(nq)]
        alive = [j for j in range(nq) if amask[j].any()]

        # Phase 1 per alive query: its bitmap + its active batches only.
        msgs = np.zeros((nq, p_cnt, v_max), np.float32)
        gen_v = {}
        for j in alive:
            keys_j = mq_query_keys(base, j)
            spill.read_bitmap(name=f"active_q{j}")              # measured
            gen_b = _batch_any(amask[j], bs, b_cnt)
            gread = spill.read(gen_b, keys=keys_j)              # measured
            gstate = {bk: gread[f"{bk}@q{j}"][:, :v_max] for bk in base}
            with np.errstate(all="ignore"):
                msgs[j] = np.asarray(signal_fn(gstate, global_id),
                                     np.float32)
            gen_v[j] = float(gen_b.sum()) * bs
            n_active = float(amask[j].sum())
            counters["msgs_generated"] += n_active
            counters["msg_disk_bytes"] += n_active * mb
            counters["msgs_sent_nofilter"] += p_cnt * n_active
            counters["net_bytes_nofilter"] += (p_cnt - 1) * n_active * mb

        # Phase 2 per alive query, then the union frontier.
        recv = np.zeros((nq, p_cnt, p_cnt, v_max), bool)
        for j in alive:
            m_p = amask[j].sum(axis=1).astype(np.float64)
            for p in range(p_cnt):
                recv[j][:, p] = phases.filter_sendmask(
                    amask[j][p], need[p], need_counts[p], m_p[p], cfg,
                    xp=np)
            counters["msgs_sent"] += float(recv[j].sum())
        union = recv.any(axis=0)                         # [Q, P, v_max]

        counts = np.stack([phases.routing_counts(recv[j], xp=np)
                           for j in range(nq)])          # [nq, Q, P]
        gapb = unib = ugap = None
        if cfg.compression:
            gapb = np.zeros((nq, p_cnt, p_cnt), np.float64)
            unib = np.zeros((nq, p_cnt, p_cnt), bool)
            for j in alive:
                gapb[j] = codec.mask_gap_bytes(recv[j], xp=np)
                unib[j] = phases.batch_value_uniform(
                    recv[j], msgs[j][None, :, :], xp=np)
            ugap = codec.mask_gap_bytes(union, xp=np)
        ucounts = phases.routing_counts(union, xp=np)
        cross = np.arange(p_cnt)[:, None] != np.arange(p_cnt)[None, :]
        net, net_raw = phases.mq_net_bytes_model(
            counts, ucounts, cross, v_max, cfg.msg_bytes, gap_bytes=gapb,
            union_gap=ugap, uniform=unib, xp=np)
        counters["net_bytes"] = float(net)
        counters["net_bytes_raw"] = float(net_raw)

        # Phases 3 + 3.5 once, over the union frontier.
        schedule = []
        for q in range(p_cnt):
            cd, _, sched_q = _dispatch_schedule_one_dest_mq(
                source, q, union[q], part_sizes, gamma, cfg.compression)
            for ck, cv in cd.items():
                counters[ck] += cv
            schedule.extend(sched_q)

        # Phase 4: ONE chunk stream; each batch combines into every alive
        # query's column.
        agg = np.full((nq, p_cnt, v_max), identity, np.float32)
        has = np.zeros((nq, p_cnt, v_max), bool)
        edges_touched = 0.0
        vec_cache = {}
        for w in ChunkPrefetcher(source, schedule,
                                 depth=cfg.ooc_prefetch_depth,
                                 device_decode=engine.device_decode):
            if backend == "segment":
                for j in alive:
                    edges_touched += _combine_stream_batch(
                        w, recv[j][w.q], msgs[j], slot_fn, monoid, agg[j],
                        has[j], backend="segment", mode=None, blk=None,
                        xv=None, xc=None, v_max=v_max)
            else:
                if w.q not in vec_cache:
                    vec_cache[w.q] = _mq_panel_vectors(
                        recv[:, w.q], msgs, mode, a_const, identity,
                        v_pad_t, nq)
                xv_p, xc_p = vec_cache[w.q]
                val, hc = _ooc_combine_batch_mq(
                    w, xv_p, xc_p, slot_fn, monoid, mode, tile=tile,
                    pb=pb, n_rows_b=n_rows_b, max_tpr=max_tpr, bs=bs,
                    num_queries=nq, interpret=interpret)
                lo = w.k * bs
                hi = min(lo + bs, v_max)
                for j in alive:
                    agg[j][w.q, lo:hi] = val[:hi - lo, j]
                    has[j][w.q, lo:hi] = hc[:hi - lo, j] > 0.5
                    edges_touched += float(hc[:, j].sum())
            counters["measured_chunks_read"] += w.n_chunks
            counters["measured_edge_read_bytes"] += w.nbytes
            counters["measured_chunks_device_decoded"] += w.n_device_chunks
            counters["measured_device_decode_calls"] += w.n_device_calls
        counters["edges_touched"] = edges_touched

        # Apply per alive query into its own columns + bitmap.
        new_active = np.zeros((p_cnt, v_max, nq), bool)
        totals = np.zeros(nq, np.float64)
        for j in alive:
            keys_j = mq_query_keys(base, j)
            ab_j = spill.arrays_bytes(keys_j)
            upd = has[j] & vertex_valid
            upd_b = _batch_any(upd, bs, b_cnt)
            astate_pad = spill.read(upd_b, keys=keys_j)         # measured
            state_j = {bk: jnp.asarray(astate_pad[f"{bk}@q{j}"][:, :v_max])
                       for bk in base}
            updates, na, ret = apply_fn(
                state_j, jnp.asarray(agg[j]), jnp.asarray(has[j]),
                global_id)
            upd_renamed = {f"{bk}@q{j}": v for bk, v in updates.items()}
            spill.merge_write(astate_pad, upd_renamed, upd,
                              upd_b)                            # measured
            na = np.asarray(na, bool) & vertex_valid
            spill.write_bitmap(na, name=f"active_q{j}")         # measured
            new_active[:, :, j] = na
            totals[j] = float(np.where(
                upd, np.asarray(ret, np.float32), 0.0).sum())
            upd_v = float(upd_b.sum()) * bs
            counters["vertex_read_bytes"] += ((gen_v[j] + upd_v) * ab_j
                                              + bitmap)
            counters["vertex_write_bytes"] += upd_v * ab_j + bitmap
        counters["measured_vertex_read_bytes"] = spill.bytes_read - sr0
        counters["measured_vertex_write_bytes"] = (spill.bytes_written
                                                   - sw0)

        views = spill.state_views()
        new_state = {bk: np.stack([views[f"{bk}@q{j}"]
                                   for j in range(nq)], axis=-1)
                     for bk in base}
        return new_state, new_active, totals, counters

    return step


# ---------------------------------------------------------------------------
# DIST_OOC executor (per-worker shards, shared-index wire panels)
# ---------------------------------------------------------------------------

def make_dist_ooc_pe_mq(engine, signal_fn, slot_fn, monoid, apply_fn,
                        backend, mode_meta, nq):
    """Multi-query distributed fully-out-of-core ProcessEdges.

    Same worker pipeline as the solo executor (send pool -> phase barrier
    -> receive pipelines with DecodeAhead + one ChunkPrefetcher per
    worker), but each (p, q) send is one multi-query batch
    (:meth:`repro.core.exchange.Exchange.post_mq`: shared-index panel or Q
    legacy batches, whichever the model prices cheaper) and each decoded
    chunk batch combines into every alive query's column.  All counters
    accumulate worker-private and reduce in index order, so parallel
    workers stay bit-identical to sequential ones."""
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
    mode = a_const = v_pad_t = pb = n_rows_b = max_tpr = None
    if backend == "block_csr":
        v_pad_t = ceil_div(v_max, tile) * tile
        pb = v_pad_t // tile
        n_rows_b = ceil_div(bs, tile)
        max_tpr = _max_tiles_per_batch_row(g, tile, pb)
        mode, a_const = mode_meta

    parallel = cfg.parallel_workers

    def step(active):
        base = mq_base_names(spills[0])
        counters = {k: 0.0 for k in engine.counter_keys}
        amask = [(vertex_valid if active is None
                  else np.asarray(active[..., j], bool) & vertex_valid)
                 for j in range(nq)]
        alive = [j for j in range(nq) if amask[j].any()]
        spill_io0 = [(sp.bytes_read, sp.bytes_written) for sp in spills]
        store_io0 = [(src.store.chunks_read, src.store.bytes_read)
                     for src in sources]
        ex = exchange_mod.Exchange(n_workers, v_max,
                                   compression=cfg.compression)
        token = threading.Lock() if parallel else None
        tok = token_ctx(token)

        # Phase 1 + 2 per worker: per-query generate (per-query spill
        # columns + bitmaps — dead queries cost zero), union the send
        # masks per (p, q), and post ONE multi-query batch each.
        def send_task(w):
            t0 = time.perf_counter()
            parts = worker_parts[w]
            lo, hi = parts[0], parts[-1] + 1
            spill = spills[w]
            bitmap_w = float(spill.bitmap_nbytes())
            msg_w = np.zeros((nq, len(parts), v_max), np.float32)
            vr_model_w = 0.0
            for j in alive:
                keys_j = mq_query_keys(base, j)
                ab_j = spill.arrays_bytes(keys_j)
                with tok:                   # compute token: generate burst
                    spill.read_bitmap(name=f"active_q{j}")      # measured
                    gen_b = _batch_any(amask[j][lo:hi], bs, b_cnt)
                    gread = spill.read(gen_b, keys=keys_j)      # measured
                    gstate = {bk: gread[f"{bk}@q{j}"][:, :v_max]
                              for bk in base}
                with tok, np.errstate(all="ignore"):
                    msg_w[j] = np.asarray(signal_fn(
                        {bk: jnp.asarray(v) for bk, v in gstate.items()},
                        global_id[lo:hi]), np.float32)
                vr_model_w += (float(gen_b.sum()) * bs * ab_j + bitmap_w)
            counts_w = np.zeros((nq, p_cnt, len(parts)), np.float64)
            gapb_w = np.zeros((nq, p_cnt, len(parts)), np.float64)
            unib_w = np.zeros((nq, p_cnt, len(parts)), bool)
            ugap_w = np.zeros((p_cnt, len(parts)), np.float64)
            ucounts_w = np.zeros((p_cnt, len(parts)), np.float64)
            for i, p in enumerate(parts):
                with tok:                   # compute token: filter + encode
                    sm = np.zeros((nq, p_cnt, v_max), bool)
                    for j in alive:
                        m_p = float(amask[j][p].sum())
                        sm[j] = phases.filter_sendmask(
                            amask[j][p], need[p], need_counts[p], m_p,
                            cfg, xp=np)
                        counts_w[j][:, i] = phases.routing_counts(sm[j],
                                                                  xp=np)
                        if cfg.compression:
                            gapb_w[j][:, i] = codec.mask_gap_bytes(sm[j],
                                                                   xp=np)
                            unib_w[j][:, i] = phases.batch_value_uniform(
                                sm[j], msg_w[j][i][None, :], xp=np)
                    union_sm = sm.any(axis=0)
                    ucounts_w[:, i] = union_sm.sum(axis=1)
                    if cfg.compression:
                        ugap_w[:, i] = codec.mask_gap_bytes(union_sm,
                                                            xp=np)
                    for q in range(p_cnt):
                        cj = [int(counts_w[j][q, i]) for j in range(nq)]
                        if any(cj):
                            ex.post_mq(w, int(worker_of[q]), p, q,
                                       sm[:, q], msg_w[:, i], cj)
            return (counts_w, gapb_w, unib_w, ugap_w, ucounts_w,
                    vr_model_w, time.perf_counter() - t0)

        send_out = run_worker_pool(
            [functools.partial(send_task, w) for w in range(n_workers)],
            parallel, pool=engine.worker_pool)
        counts = np.zeros((nq, p_cnt, p_cnt), np.float64)
        gapb = np.zeros((nq, p_cnt, p_cnt), np.float64)
        unib = np.zeros((nq, p_cnt, p_cnt), bool)
        ugap = np.zeros((p_cnt, p_cnt), np.float64)
        ucounts = np.zeros((p_cnt, p_cnt), np.float64)
        for w, (counts_w, gapb_w, unib_w, ugap_w, ucounts_w, vr_model_w,
                dt) in enumerate(send_out):
            lo, hi = worker_parts[w][0], worker_parts[w][-1] + 1
            counts[:, :, lo:hi] = counts_w
            gapb[:, :, lo:hi] = gapb_w
            unib[:, :, lo:hi] = unib_w
            ugap[:, lo:hi] = ugap_w
            ucounts[:, lo:hi] = ucounts_w
            counters["vertex_read_bytes"] += vr_model_w
            engine.worker_times[w]["send_s"] += dt

        for j in alive:
            n_active = float(amask[j].sum())
            counters["msgs_generated"] += n_active
            counters["msg_disk_bytes"] += n_active * mb
            counters["msgs_sent_nofilter"] += p_cnt * n_active
            counters["net_bytes_nofilter"] += (p_cnt - 1) * n_active * mb
        counters["msgs_sent"] = float(counts.sum())

        cross = (worker_of[np.newaxis, :] != worker_of[:, np.newaxis])
        net, net_raw = phases.mq_net_bytes_model(
            counts, ucounts, cross, v_max, cfg.msg_bytes,
            gap_bytes=gapb if cfg.compression else None,
            union_gap=ugap if cfg.compression else None,
            uniform=unib if cfg.compression else None, xp=np)
        counters["net_bytes"] = float(net)
        counters["net_bytes_raw"] = float(net_raw)
        counters["measured_net_bytes"] = ex.bytes_sent
        counters["net_pair_batches"] = float(ex.pair_batches)
        counters["net_slab_batches"] = float(ex.slab_batches)
        counters["net_vpair_batches"] = float(ex.vpair_batches)
        counters["net_uval_batches"] = float(ex.uval_batches)

        # Phases 3 + 4 + apply per worker over its own shard; the chunk
        # stream runs once per worker over the union schedule.
        agg = np.full((nq, p_cnt, v_max), identity, np.float32)
        has = np.zeros((nq, p_cnt, v_max), bool)
        new_active = np.zeros((p_cnt, v_max, nq), bool)

        def recv_task(w):
            t0 = time.perf_counter()
            parts = worker_parts[w]
            lo, hi = parts[0], parts[-1] + 1
            spill = spills[w]
            source = sources[w]
            bitmap_w = float(spill.bitmap_nbytes())
            cw = {}

            def lazy_schedule():
                for q, pmask, pmsg in exchange_mod.DecodeAhead(
                        ex, w, parts, p_cnt, compute_lock=token,
                        runner=engine.pipeline_pool,
                        device_decode=engine.device_decode,
                        num_queries=nq):
                    with tok:               # compute token: dispatch burst
                        cd, _, sched_q = _dispatch_schedule_one_dest_mq(
                            source, q, pmask.any(axis=0), part_sizes,
                            gamma, cfg.compression)
                        header = DestHeader(
                            q=q, recv_mask=pmask, recv_msg=pmsg,
                            counter_delta=cd)
                    yield header
                    yield from sched_q

            w_edges = 0.0
            w_dev_chunks = 0.0
            w_dev_calls = 0.0
            cur = None
            xv_p = xc_p = None
            for item in ChunkPrefetcher(source, lazy_schedule(),
                                        depth=cfg.ooc_prefetch_depth,
                                        compute_lock=token,
                                        runner=engine.pipeline_pool,
                                        device_decode=engine.device_decode):
                if isinstance(item, DestHeader):
                    cur = item
                    xv_p = xc_p = None
                    for ck, cv in item.counter_delta.items():
                        cw[ck] = cw.get(ck, 0.0) + cv
                    continue
                w_dev_chunks += item.n_device_chunks
                w_dev_calls += item.n_device_calls
                with tok:                   # compute token: combine burst
                    if backend == "segment":
                        for j in alive:
                            w_edges += _combine_stream_batch(
                                item, cur.recv_mask[j], cur.recv_msg[j],
                                slot_fn, monoid, agg[j], has[j],
                                backend="segment", mode=None, blk=None,
                                xv=None, xc=None, v_max=v_max)
                    else:
                        if xv_p is None:
                            xv_p, xc_p = _mq_panel_vectors(
                                cur.recv_mask, cur.recv_msg, mode,
                                a_const, identity, v_pad_t, nq)
                        val, hc = _ooc_combine_batch_mq(
                            item, xv_p, xc_p, slot_fn, monoid, mode,
                            tile=tile, pb=pb, n_rows_b=n_rows_b,
                            max_tpr=max_tpr, bs=bs, num_queries=nq,
                            interpret=interpret)
                        klo = item.k * bs
                        khi = min(klo + bs, v_max)
                        for j in alive:
                            agg[j][item.q, klo:khi] = val[:khi - klo, j]
                            has[j][item.q, klo:khi] = (hc[:khi - klo, j]
                                                       > 0.5)
                            w_edges += float(hc[:, j].sum())

            # Apply per alive query into this worker's spill columns.
            totals_w = np.zeros(nq, np.float64)
            upd_model_r = 0.0
            upd_model_w = 0.0
            for j in alive:
                keys_j = mq_query_keys(base, j)
                ab_j = spill.arrays_bytes(keys_j)
                with tok:                   # compute token: apply burst
                    upd_wj = has[j][lo:hi] & vertex_valid[lo:hi]
                    upd_b = _batch_any(upd_wj, bs, b_cnt)
                    astate_pad = spill.read(upd_b, keys=keys_j)  # measured
                    state_j = {
                        bk: jnp.asarray(astate_pad[f"{bk}@q{j}"][:, :v_max])
                        for bk in base}
                with tok:
                    updates, na_wj, ret = apply_fn(
                        state_j, jnp.asarray(agg[j][lo:hi]),
                        jnp.asarray(has[j][lo:hi]), global_id[lo:hi])
                with tok:
                    upd_renamed = {f"{bk}@q{j}": v
                                   for bk, v in updates.items()}
                    spill.merge_write(astate_pad, upd_renamed, upd_wj,
                                      upd_b)                    # measured
                    na_wj = np.asarray(na_wj, bool) & vertex_valid[lo:hi]
                    spill.write_bitmap(na_wj,
                                       name=f"active_q{j}")     # measured
                    new_active[lo:hi, :, j] = na_wj
                    totals_w[j] = float(np.where(
                        upd_wj, np.asarray(ret, np.float32), 0.0).sum())
                upd_v = float(upd_b.sum()) * bs
                upd_model_r += upd_v * ab_j
                upd_model_w += upd_v * ab_j + bitmap_w
            cw["vertex_read_bytes"] = upd_model_r
            cw["vertex_write_bytes"] = upd_model_w

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
            return cw, totals_w, time.perf_counter() - t0

        recv_out = run_worker_pool(
            [functools.partial(recv_task, w) for w in range(n_workers)],
            parallel, pool=engine.worker_pool)
        phases.reduce_worker_counters(
            counters, [cw for cw, _, _ in recv_out])
        totals = np.zeros(nq, np.float64)
        for w, (_, totals_w, dt) in enumerate(recv_out):
            totals += totals_w
            engine.worker_times[w]["recv_s"] += dt

        new_state = _dist_mq_state_views(spills, worker_parts, base, nq)
        return new_state, new_active, totals, counters

    return step


def _dist_mq_state_views(spills, worker_parts, base, nq):
    """Assemble the [P, v_max, Q] state panel from the per-worker spills'
    per-query column views (copies — the spills stay authoritative)."""
    out = {}
    for bk in base:
        rows = np.concatenate(
            [np.stack([spills[w].state_views()[f"{bk}@q{j}"]
                       for j in range(nq)], axis=-1)
             for w in range(len(worker_parts))], axis=0)
        out[bk] = rows
    return out
